"""Pallas TPU kernel: in-supernode dense LU (right-looking, in-VMEM).

The internal factorization of a supernode: partial pivoting restricted to
the diagonal block (HYLU's supernode diagonal pivoting — legal because the
rows of a supernode share their U structure) + pivot perturbation for
small/zero pivots (SuperLU_DIST-style, ref [13] of the paper).

One bucket member per grid step: the whole panel (nr ≤ 128 rows × wt
cols) is one VMEM block, which the supernode width cap chosen at analysis
time guarantees fits.  The perturbation threshold eps_p is a runtime
scalar ((1,1) VMEM input) because it depends on max|B| of the current
values (refactorization changes it without recompiling).

Every step is written with iota masks, lane/sublane reductions and
selects — no gathers, no dynamic lane indexing — so Mosaic compiles it:
the pivot row is found as the first row holding the column maximum, and
rows j and p are exchanged by two masked row reductions and a select.

Outputs: factored panels, local pivot permutations, #perturbed pivots.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import I0, interpret_mode


def _panel_lu_bucketed_kernel(panel_ref, eps_ref, out_ref, perm_ref,
                              nper_ref, *, wu: int):
    """Dense LU of one column-reordered panel [diag block | U suffix |
    L prefix].  Elimination is masked to the static window [0, wu);
    trailing (prefix) columns only row-swap.  Padded block diagonals are
    identity (set up by the gather map), so padded pivot steps are exact
    no-ops and never count as perturbations."""
    panel = panel_ref[0]                                  # (nr, wt)
    nr, wt = panel.shape
    eps_p = eps_ref[...]                                  # (1, 1)
    zero = jnp.zeros((), panel.dtype)
    rows = jax.lax.broadcasted_iota(jnp.int32, (nr, 1), 0)
    rows_f = rows.astype(jnp.float32)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, wt), 1)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, nr), 1)
    lanes_f = lanes.astype(jnp.float32)

    def column(panel, j):                                 # (nr, 1)
        return jnp.sum(jnp.where(cols == j, panel, zero), axis=1,
                       keepdims=True)

    def body(j, carry):
        panel, perm, nper = carry                         # perm (1, nr) f32
        cand = jnp.where(rows >= j, jnp.abs(column(panel, j)), -1.0)
        best = jnp.max(cand, axis=0, keepdims=True)
        p = jnp.min(jnp.where(cand == best, rows_f, float(nr)), axis=0,
                    keepdims=True)                        # first argmax
        row_j = jnp.sum(jnp.where(rows == j, panel, zero), axis=0,
                        keepdims=True)
        row_p = jnp.sum(jnp.where(rows_f == p, panel, zero), axis=0,
                        keepdims=True)
        panel = jnp.where(rows == j, row_p,
                          jnp.where(rows_f == p, row_j, panel))
        perm_j = jnp.sum(jnp.where(lanes == j, perm, 0.0), axis=1,
                         keepdims=True)
        perm_p = jnp.sum(jnp.where(lanes_f == p, perm, 0.0), axis=1,
                         keepdims=True)
        perm = jnp.where(lanes == j, perm_p,
                         jnp.where(lanes_f == p, perm_j, perm))
        piv = jnp.sum(jnp.where(cols == j, row_p, zero), axis=1,
                      keepdims=True)                      # (1, 1)
        small = jnp.abs(piv) < eps_p
        piv = jnp.where(small, jnp.where(piv >= 0, eps_p, -eps_p), piv)
        nper = nper + small.astype(jnp.int32)
        l = jnp.where(rows > j, column(panel, j) / piv, zero)
        urow = jnp.where((cols > j) & (cols < wu), row_p, zero)
        panel = panel - l * urow                          # VPU rank-1
        panel = jnp.where(cols == j,
                          jnp.where(rows > j, l,
                                    jnp.where(rows == j, piv, panel)),
                          panel)
        return panel, perm, nper

    # int32 bounds (see repro.kernels)
    panel, perm, nper = jax.lax.fori_loop(
        jnp.int32(0), jnp.int32(nr), body,
        (panel, lanes_f, jnp.zeros((1, 1), jnp.int32)))
    out_ref[0] = panel
    perm_ref[0] = perm.astype(jnp.int32)
    nper_ref[0] = nper


@functools.partial(jax.jit, static_argnames=("wu", "interpret"))
def panel_lu_bucketed_p(panels: jax.Array, eps_p: jax.Array, wu: int,
                        interpret: bool | None = None):
    """Bucketed panel LU: panels (B, nr, wt), one grid step per bucket
    member (the leading bucket dim is the Pallas grid).  Returns the
    factored panels, per-panel local pivot permutations (B, nr) and
    per-panel perturbation counts (B,).  ``interpret=None`` follows the
    platform (:func:`repro.kernels.interpret_mode`)."""
    B, nr, wt = panels.shape
    eps2d = jnp.reshape(eps_p.astype(panels.dtype), (1, 1))
    out, perm, nper = pl.pallas_call(
        functools.partial(_panel_lu_bucketed_kernel, wu=wu),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, nr, wt), lambda b: (b, I0, I0)),
            pl.BlockSpec((1, 1), lambda b: (I0, I0)),
        ],
        out_specs=[
            pl.BlockSpec((1, nr, wt), lambda b: (b, I0, I0)),
            pl.BlockSpec((1, 1, nr), lambda b: (b, I0, I0)),
            pl.BlockSpec((1, 1, 1), lambda b: (b, I0, I0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nr, wt), panels.dtype),
            jax.ShapeDtypeStruct((B, 1, nr), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
        ],
        interpret=interpret_mode(interpret),
    )(panels, eps2d)
    return out, perm[:, 0], nper[:, 0, 0]
