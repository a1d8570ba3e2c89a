"""Differentiable sparse solve (beyond-paper feature).

``make_sparse_solve(analysis)`` returns a jittable ``f(a_data, b) -> x``
solving A x = b with HYLU factors, equipped with an implicit-function-theorem
custom VJP:

    b̄        = A⁻ᵀ x̄                     (transpose solve, same LU factors)
    ā_(i,j)  = -(A⁻ᵀ x̄)_i · x_j           (one fused gather per nnz)

The adjoint reuses the forward factorization — the numerical analogue of
HYLU's repeated-solve path — so a training loop that backprops through the
solver pays one factorization and two triangular solves per step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .api import Analysis
from .jax_engine import make_factor_fn, make_lu_solver, make_permuted_apply
from .options import resolve_perturb_eps
from .structure import build_solve_structure


def make_sparse_solve(an: Analysis, dtype=jnp.float64, use_pallas: bool = False):
    """Emit the differentiable solver for a fixed sparsity pattern."""
    plan = an.plan
    ss = build_solve_structure(plan, bulk_min_width=an.opts.bulk_min_width)
    factor_fn = make_factor_fn(plan,
                               perturb_eps=resolve_perturb_eps(an.opts, dtype),
                               dtype=dtype, use_pallas=use_pallas)
    lu_solve, lut_solve = make_lu_solver(ss, dtype=dtype)

    n = an.n
    p_ = jnp.asarray(an.p)
    q_ = jnp.asarray(an.q)
    r_ = jnp.asarray(an.match.row_scale, dtype=dtype)
    s_ = jnp.asarray(an.match.col_scale, dtype=dtype)
    src_map = jnp.asarray(an.src_map)
    scale_map = jnp.asarray(an.scale_map, dtype=dtype)
    # original-pattern (row, col) per nnz for the A-values cotangent
    indptr, indices = an.m_pattern  # M pattern; invert src_map below.

    lu_apply = make_permuted_apply(lu_solve, an.n, an.p, an.q,
                                   an.match.row_scale, an.match.col_scale,
                                   dtype=dtype)

    def _fwd_impl(a_data, b):
        a_data = a_data.astype(dtype)
        m_data = a_data[src_map] * scale_map
        f = factor_fn(m_data)
        return lu_apply(f.vals, f.inode_perm, b), f

    @jax.custom_vjp
    def sparse_solve(a_data, b):
        return _fwd_impl(a_data, b)[0]

    def fwd(a_data, b):
        x, f = _fwd_impl(a_data, b)
        return x, (f.vals, f.inode_perm, x)

    def bwd(res, g):
        vals, inode, x = res
        t = (s_ * g.astype(dtype))[q_][p_]
        t = lut_solve(vals, t)
        t = jnp.zeros(n, dtype).at[inode].set(t)
        lam = r_ * jnp.zeros(n, dtype).at[p_].set(t)
        abar = -(lam[rows_a] * x[cols_a])
        return abar, lam

    sparse_solve.defvjp(fwd, bwd)

    # host: original A pattern (rows/cols per nnz) — recover from analysis:
    # an.m_pattern is M's; the tracked src_map tells which A entry each M
    # entry came from, so invert.
    nnz = len(an.src_map)
    m_rows = np.repeat(np.arange(n), np.diff(indptr))
    m_cols = np.asarray(indices)
    # M[i,j] = scaled A[src]; A entry src sits at original (row,col): we can
    # reconstruct A's (row, col): row = p[m_row] pre-ordering is B2's row;
    # B2 row == A row; B2 col j maps to A col q[j].
    a_rows_np = np.empty(nnz, dtype=np.int64)
    a_cols_np = np.empty(nnz, dtype=np.int64)
    a_rows_np[an.src_map] = an.p[m_rows]
    a_cols_np[an.src_map] = an.q[an.p[m_cols]]
    rows_a = jnp.asarray(a_rows_np)
    cols_a = jnp.asarray(a_cols_np)

    return sparse_solve
