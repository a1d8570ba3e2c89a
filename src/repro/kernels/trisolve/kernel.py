"""Pallas TPU kernel: dense row-panel triangular solve  Y @ U = X.

This is the in-VMEM TRSM used by the sup-row / sup-sup numeric kernels
(the "solve against the source supernode's diagonal block" step).  The
whole problem fits one VMEM block by construction: supernode widths are
capped at analysis time (max_super ≤ 128, MXU-aligned), and panel heights
are tiled by the caller.

Tiling: grid over row tiles of X (TILE_NR rows each); U (k×k, k ≤ 128)
is resident in VMEM for every tile, passed transposed so that column j of
U is row j of the block.  Inside the kernel the solve runs as k sequential
column updates on the VPU (the recurrence is inherently sequential in k,
parallel over rows — exactly the paper's sup-row shape).  Column j is
read and written through lane masks and reductions, never a dynamic lane
index, so Mosaic compiles it.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import I0, interpret_mode


def _trsm_kernel(ut_ref, x_ref, y_ref, *, unit_diag: bool):
    ut = ut_ref[...]                                  # (k, k) = Uᵀ
    x = x_ref[...]                                    # (tile, k)
    k = x.shape[1]
    zero = jnp.zeros((), x.dtype)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    subl = jax.lax.broadcasted_iota(jnp.int32, (k, 1), 0)

    def body(j, y):
        ucol = jnp.sum(jnp.where(subl == j, ut, zero), axis=0,
                       keepdims=True)                 # (1, k) = U[:, j]ᵀ
        xj = jnp.sum(jnp.where(lanes == j, x, zero), axis=1, keepdims=True)
        # columns >= j of y are still zero, so only U[:j, j] contributes
        acc = xj - jnp.sum(y * ucol, axis=1, keepdims=True)
        if not unit_diag:
            acc = acc / jnp.sum(jnp.where(lanes == j, ucol, zero), axis=1,
                                keepdims=True)
        return jnp.where(lanes == j, acc, y)

    # int32 bounds (see repro.kernels)
    y_ref[...] = jax.lax.fori_loop(jnp.int32(0), jnp.int32(k), body,
                                   jnp.zeros_like(x))


@functools.partial(jax.jit, static_argnames=("tile_nr", "interpret",
                                             "unit_diag"))
def trsm_upper(u: jax.Array, x: jax.Array, tile_nr: int = 256,
               interpret: bool | None = None,
               unit_diag: bool = False) -> jax.Array:
    """Solve Y @ U = X. u: (k, k) upper-tri; x: (nr, k).

    ``unit_diag=True`` treats U's diagonal as implicit ones (skips the
    per-column divide) — the shape of the unit-lower left-solve that the
    engine's block substitution routes through this kernel transposed.
    ``interpret=None`` follows the platform
    (:func:`repro.kernels.interpret_mode`)."""
    nr, k = x.shape
    tile = min(tile_nr, max(nr, 1))
    grid = (pl.cdiv(nr, tile),)
    return pl.pallas_call(
        functools.partial(_trsm_kernel, unit_diag=unit_diag),
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, k), lambda i: (I0, I0)),        # Uᵀ resident
            pl.BlockSpec((tile, k), lambda i: (i, I0)),     # row tile of X
        ],
        out_specs=pl.BlockSpec((tile, k), lambda i: (i, I0)),
        out_shape=jax.ShapeDtypeStruct((nr, k), x.dtype),
        interpret=interpret_mode(interpret),
    )(u.T, x)
