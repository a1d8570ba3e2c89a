"""Pallas TPU kernel: fused sup-row update (TRSV + GEMV in one VMEM pass).

HYLU's level-2 kernel: "the sup-row kernel still updates a row at a time,
but uses supernodes as source data ... level-2 BLAS can be called".  On TPU
a standalone row is a (1, w) panel; fusing the triangular solve and the
panel GEMV in one kernel keeps the row slice and the source panel resident
in VMEM for the whole update (one HBM round-trip instead of two).

The source panel is tiled over its width m (lane dim); the k×k diag block
and the row are resident.  Grid: (m/TN,) with the TRSV done on the first
grid step into a VMEM scratch shared by later steps.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _suprow_kernel(xk_ref, xm_ref, u_ref, b_ref, y_ref, xr_ref, y_scr, *,
                   k: int):
    @pl.when(pl.program_id(0) == 0)
    def _trsv():
        u = u_ref[...]
        x = xk_ref[...]                       # (1, k)

        def body(j, y):
            acc = x[0, j] - y[0] @ u[:, j]
            return y.at[0, j].set(acc / u[j, j])

        y = jax.lax.fori_loop(0, k, body, jnp.zeros_like(x))
        y_scr[...] = y
        y_ref[...] = y

    y = y_scr[...]
    xr_ref[...] = xm_ref[...] - y @ b_ref[...]     # GEMV tile


@functools.partial(jax.jit, static_argnames=("tn", "interpret"))
def suprow_update_p(xk: jax.Array, xm: jax.Array, u: jax.Array, b: jax.Array,
                    tn: int = 512, interpret: bool | None = None):
    """xk: (1,k) row head; xm: (1,m) row tail; u: (k,k); b: (k,m)."""
    k = u.shape[0]
    m = xm.shape[1]
    tn = min(tn, m)
    grid = (pl.cdiv(m, tn),)
    return pl.pallas_call(
        functools.partial(_suprow_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, k), lambda j: (0, 0)),
            pl.BlockSpec((1, tn), lambda j: (0, j)),
            pl.BlockSpec((k, k), lambda j: (0, 0)),
            pl.BlockSpec((k, tn), lambda j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, k), lambda j: (0, 0)),
            pl.BlockSpec((1, tn), lambda j: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, k), xk.dtype),
            jax.ShapeDtypeStruct((1, m), xm.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1, k), xk.dtype)],
        interpret=interpret_mode(interpret),
    )(xk, xm, u, b)
