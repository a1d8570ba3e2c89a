"""jit'd wrapper for the sup-sup update (TRSM + GEMM Pallas kernels)."""
import jax
import jax.numpy as jnp

from repro.kernels.trisolve import ops as trisolve_ops
from .kernel import bmm, gemm_update
from .ref import supsup_update_ref, gemm_update_ref

__all__ = ["supsup_update", "gemm", "gemm_batched", "supsup_update_ref",
           "gemm_update_ref"]


def _rnd(v, mult=8):
    return max(mult, -(-v // mult) * mult)


def _rnd_lane(v):
    """A lane/contraction dim padded to whole 128-tiles once it spans more
    than one (small ones to a sublane multiple, bounding padding waste)."""
    return _rnd(v, 128 if v >= 128 else 8)


def supsup_update(x: jax.Array, src: jax.Array, k: int):
    """The full sup-sup numeric update on a gathered panel slice.

    x:   (nr, k+m) target panel slice (gathered through col_map)
    src: (k, k+m)  source supernode rows (diag block + U panel)
    Returns (lts, xr): the solved multipliers and the updated trailing part.
    """
    lts = trisolve_ops.trsm(src[:, :k], x[:, :k])
    xr = gemm(x[:, k:], lts, src[:, k:])
    return lts, xr


def gemm_batched(a: jax.Array, b: jax.Array) -> jax.Array:
    """Batched A @ B (the trailing-update GEMM of one bucketed sup-sup
    edge application): a (E, nr, k), b (E, k, m) → (E, nr, m), padding
    nr/k/m to sublane/lane multiples.  Zero-padding is exact: padded rows
    and columns of the product land in scatter positions the engine
    directs at its scratch slot."""
    e, nr, k = a.shape
    m = b.shape[2]
    if m == 0 or k == 0:
        return jnp.zeros((e, nr, m), a.dtype)

    nrp, mp, kp = _rnd(nr), _rnd_lane(m), _rnd_lane(k)
    if (nrp, mp, kp) != (nr, m, k):
        ap = jnp.zeros((e, nrp, kp), a.dtype).at[:, :nr, :k].set(a)
        bp = jnp.zeros((e, kp, mp), b.dtype).at[:, :k, :m].set(b)
        return bmm(ap, bp)[:, :nr, :m]
    return bmm(a, b)


def gemm(c: jax.Array, a: jax.Array, b: jax.Array) -> jax.Array:
    """C - A @ B, padding every dim to sublane/lane multiples (8 / 128-ish;
    small solver panels use 8-multiples to bound padding waste)."""
    nr, m = c.shape
    k = a.shape[1]
    if m == 0 or k == 0:
        return c

    nrp, mp, kp = _rnd(nr), _rnd_lane(m), _rnd_lane(k)
    if (nrp, mp, kp) != (nr, m, k):
        cp = jnp.zeros((nrp, mp), c.dtype).at[:nr, :m].set(c)
        ap = jnp.zeros((nrp, kp), a.dtype).at[:nr, :k].set(a)
        bp = jnp.zeros((kp, mp), b.dtype).at[:k, :m].set(b)
        return gemm_update(cp, ap, bp)[:nr, :m]
    return gemm_update(c, a, b)
