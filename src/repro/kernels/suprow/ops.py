"""jit'd wrapper for the fused sup-row Pallas kernel."""
import jax
import jax.numpy as jnp

from .kernel import suprow_update_p
from .ref import suprow_update_ref

__all__ = ["suprow_update", "suprow_update_ref"]


def suprow_update(x: jax.Array, src: jax.Array, k: int):
    """x: (k+m,) target row slice; src: (k, k+m). Returns (y, xr)."""
    m = x.shape[0] - k

    def rnd(v, mult=8):
        return max(mult, -(-v // mult) * mult)

    kp, mp = rnd(k), rnd(max(m, 1), 128 if m >= 128 else 8)
    u = jnp.eye(kp, dtype=x.dtype).at[:k, :k].set(src[:, :k])
    b = jnp.zeros((kp, mp), x.dtype).at[:k, :m].set(src[:, k:])
    xk = jnp.zeros((1, kp), x.dtype).at[0, :k].set(x[:k])
    xm = jnp.zeros((1, mp), x.dtype).at[0, :m].set(x[k:])
    y, xr = suprow_update_p(xk, xm, u, b)
    return y[0, :k], xr[0, :m]
