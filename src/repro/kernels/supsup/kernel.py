"""Pallas TPU kernel: sup-sup trailing update  C -= A @ B  (MXU GEMM).

This is HYLU's level-3-BLAS kernel mapped to the MXU: a supernode's dense
U-panel B (k × m) updates a target panel slice C (nr × m) through the just
solved multipliers A (nr × k).  The gather/scatter through ``col_map``
happens outside (XLA gather fuses with the kernel's HBM reads on TPU); the
kernel is the flop-dominant GEMM with explicit VMEM tiling:

  grid (i, j, l) over (nr/TM, m/TN, k/TK); C tile accumulated in a VMEM
  scratch accumulator across the contraction dimension l (arbitrary-order
  innermost axis), written back on the last l step.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import I0, interpret_mode

# f32 operands multiply at full f32 precision on the MXU (the default
# would round them to bf16 first); f64 exists only in CPU interpretation
_PRECISION = jax.lax.Precision.HIGHEST


def _acc_dtype(dtype):
    """Accumulator dtype: float32 for float32/bfloat16 operands, float64
    only for the interpreter's float64 operands (Mosaic has no float64)."""
    return jnp.promote_types(dtype, jnp.float32)


def _gemm_update_kernel(c_ref, a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = c_ref[...].astype(acc_ref.dtype)

    acc_ref[...] -= jnp.dot(a_ref[...], b_ref[...], precision=_PRECISION,
                            preferred_element_type=acc_ref.dtype)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _bmm_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[0], b_ref[0], precision=_PRECISION,
                            preferred_element_type=acc_ref.dtype)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tn", "tk", "interpret"))
def bmm(a: jax.Array, b: jax.Array, tn: int = 128, tk: int = 128,
        interpret: bool | None = None) -> jax.Array:
    """Batched A @ B for the bucketed sup-sup update: a (E, nr, k),
    b (E, k, m) → (E, nr, m).  The leading bucket dim is the outer Pallas
    grid axis; each bucket member's GEMM tiles its m/k dims into VMEM with
    a scratch accumulator over the contraction axis (the nr dim of one
    supernode edge is ≤ 128 and stays whole).  k must be a multiple of
    the contraction tile (or at most one tile): a partial contraction
    block would sum over out-of-bounds garbage."""
    E, nr, k = a.shape
    m = b.shape[2]
    tn, tk = min(tn, m), min(tk, k)
    grid = (E, pl.cdiv(m, tn), pl.cdiv(k, tk))
    return pl.pallas_call(
        functools.partial(_bmm_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, nr, tk), lambda e, j, l: (e, I0, l)),   # A
            pl.BlockSpec((1, tk, tn), lambda e, j, l: (e, l, j)),   # B
        ],
        out_specs=pl.BlockSpec((1, nr, tn), lambda e, j, l: (e, I0, j)),
        out_shape=jax.ShapeDtypeStruct((E, nr, m), a.dtype),
        scratch_shapes=[pltpu.VMEM((nr, tn), _acc_dtype(a.dtype))],
        interpret=interpret_mode(interpret),
    )(a, b)


@functools.partial(jax.jit,
                   static_argnames=("tm", "tn", "tk", "interpret"))
def gemm_update(c: jax.Array, a: jax.Array, b: jax.Array,
                tm: int = 128, tn: int = 128, tk: int = 128,
                interpret: bool | None = None) -> jax.Array:
    """C - A @ B with VMEM tiling. c: (nr, m), a: (nr, k), b: (k, m); k
    a multiple of the contraction tile or at most one tile (see
    :func:`bmm`)."""
    nr, m = c.shape
    k = a.shape[1]
    tm, tn, tk = min(tm, nr), min(tn, m), min(tk, k)
    grid = (pl.cdiv(nr, tm), pl.cdiv(m, tn), pl.cdiv(k, tk))
    return pl.pallas_call(
        functools.partial(_gemm_update_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, tn), lambda i, j, l: (i, j)),   # C
            pl.BlockSpec((tm, tk), lambda i, j, l: (i, l)),   # A
            pl.BlockSpec((tk, tn), lambda i, j, l: (l, j)),   # B
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nr, m), c.dtype),
        scratch_shapes=[pltpu.VMEM((tm, tn), _acc_dtype(c.dtype))],
        interpret=interpret_mode(interpret),
    )(c, a, b)
