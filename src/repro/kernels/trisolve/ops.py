"""jit'd wrappers for the TRSM Pallas kernel (padding to TPU-friendly tiles).

The engine's bucketed factorization routes each sup-sup edge bucket's
multiplier solve (Y @ U = X against the source supernode's diagonal block)
through :func:`trsm_batched` when ``use_pallas=True``.  Its block
substitution needs the two *left*-solve shapes — U·w = b and (unit)
L·w = b, batched — expressed on the same kernel through the
transpose/flip identities

    L w = b           ⇔  wᵀ = bᵀ · (Lᵀ)⁻¹          (Lᵀ upper, unit diag)
    U w = b           ⇔  (Jw)ᵀ = (Jb)ᵀ · ((JUJ)ᵀ)⁻¹  (J U ᵀ J upper)

where J is the row-flip.  The kernel runs compiled on a TPU and
interpreted elsewhere (:mod:`repro.kernels`).

Dtype contract: every op runs in its operands' dtype (float64 /
float32 / bfloat16) — tile padding builds identity diagonals in ``u.dtype``
and the solves never upcast, so the mixed-precision engine's
reduced-precision path flows through unchanged.  Mosaic has no float64.
"""
import jax
import jax.numpy as jnp

from .kernel import trsm_upper
from .ref import (trsm_upper_ref, trsm_upper_ref_batched,
                  trsm_left_upper_ref_batched,
                  trsm_left_unit_lower_ref_batched)

__all__ = ["trsm", "trsm_batched", "trsm_left_upper_batched",
           "trsm_left_unit_lower_batched", "trsm_upper_ref",
           "trsm_upper_ref_batched", "trsm_left_upper_ref_batched",
           "trsm_left_unit_lower_ref_batched"]


def trsm(u: jax.Array, x: jax.Array) -> jax.Array:
    """Solve Y @ U = X with the Pallas kernel. Pads k to a multiple of 8
    (sublane) — padded diagonal is identity so the solve is unaffected."""
    nr, k = x.shape
    kp = max(8, -(-k // 8) * 8)
    if kp != k:
        u_p = jnp.eye(kp, dtype=u.dtype).at[:k, :k].set(u)
        x_p = jnp.zeros((nr, kp), x.dtype).at[:, :k].set(x)
        return trsm_upper(u_p, x_p)[:, :k]
    return trsm_upper(u, x)


def trsm_batched(u: jax.Array, x: jax.Array,
                 unit_diag: bool = False) -> jax.Array:
    """Batched TRSM: u (K, k, k), x (K, nr, k) — K independent panel solves
    through one vmapped pallas_call (the engine's sup-sup edge buckets, and
    its block substitution through the left-solve wrappers below)."""
    nr, k = x.shape[-2:]
    kp = max(8, -(-k // 8) * 8)

    def solve(uu, xx):
        return trsm_upper(uu, xx, unit_diag=unit_diag)

    if kp != k:
        kb = x.shape[0]
        u_p = (jnp.zeros((kb, kp, kp), u.dtype)
               .at[:, jnp.arange(kp), jnp.arange(kp)].set(1.0)
               .at[:, :k, :k].set(u))
        x_p = jnp.zeros((kb, nr, kp), x.dtype).at[:, :, :k].set(x)
        return jax.vmap(solve)(u_p, x_p)[:, :, :k]
    return jax.vmap(solve)(u, x)


def trsm_left_unit_lower_batched(blk: jax.Array, b: jax.Array) -> jax.Array:
    """Solve L[i] @ w[i] = b[i] with L = tril(blk[i], -1) + I.

    blk (K, k, k) dense diagonal blocks straight from the panel buffer
    (upper part, which holds U values, is ignored); b (K, k, m)."""
    lt = jnp.triu(jnp.swapaxes(blk, 1, 2), 1)          # Lᵀ, strict upper
    y = trsm_batched(lt, jnp.swapaxes(b, 1, 2),
                     unit_diag=True)                    # (K, m, k) = wᵀ
    return jnp.swapaxes(y, 1, 2)


def trsm_left_upper_batched(blk: jax.Array, b: jax.Array) -> jax.Array:
    """Solve U[i] @ w[i] = b[i] with U = triu(blk[i]).

    blk (K, k, k) dense diagonal blocks straight from the panel buffer
    (strict lower part, which holds L values, is ignored); b (K, k, m)."""
    u_flip = jnp.flip(jnp.swapaxes(jnp.triu(blk), 1, 2), axis=(1, 2))
    y = trsm_batched(u_flip, jnp.swapaxes(jnp.flip(b, axis=1), 1, 2))
    return jnp.flip(jnp.swapaxes(y, 1, 2), axis=1)     # (K, m, k) = (Jw)ᵀ
