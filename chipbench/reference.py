"""The plain reference: host float64 ``scipy.sparse.linalg.splu``.

It imports nothing of the program and takes nothing the program made: each
system's matrix is rebuilt from the values and pattern the benchmark sent.
``perturbed_values`` and ``Oracle`` are copies of ``chip_smoke.py``'s;
``tests/test_yardstick.py`` checks that they agree with the originals.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
import scipy.sparse.linalg as spla


def perturbed_values(a_sp, k: int, rng):
    """``k`` seeded value sets on ``a_sp``'s pattern that keep its diagonal
    dominance: every off-diagonal entry scaled by a factor in [0.5, 1.5],
    every diagonal entry set to its row's new off-diagonal 1-norm plus the
    original row's margin."""
    a = a_sp.tocsr()
    n = a.shape[0]
    rows = np.repeat(np.arange(n), np.diff(a.indptr))
    diag = rows == a.indices
    if int(diag.sum()) != n:
        raise ValueError("pattern lacks a full diagonal")
    starts = a.indptr[:-1]
    margin = a.data[diag] - np.add.reduceat(
        np.where(diag, 0.0, np.abs(a.data)), starts)
    v = a.data * rng.uniform(0.5, 1.5, (k, a.nnz))
    v[:, diag] = np.add.reduceat(np.where(diag, 0.0, np.abs(v)), starts,
                                 axis=1) + margin
    return v


class Oracle:
    """Host float64 reference for one value set: ``splu`` and its condition
    estimate."""

    def __init__(self, a_sp, values):
        a = sp.csr_matrix((values, a_sp.indices, a_sp.indptr),
                          shape=a_sp.shape).tocsc()
        self.a = a
        self.lu = spla.splu(a)
        inv = spla.LinearOperator(
            a.shape, matvec=self.lu.solve,
            rmatvec=lambda x: self.lu.solve(x, trans="T"), dtype=np.float64)
        # onenormest draws random start vectors from numpy's global state:
        # seed it so that one system always gets the same estimate
        state = np.random.get_state()
        np.random.seed(0)
        try:
            self.cond1 = float(spla.norm(a, 1) * spla.onenormest(inv))
        finally:
            np.random.set_state(state)

    def errors(self, x, b, tol):
        """(relative 1-norm error vs splu, its bound, host residual)."""
        ref = self.lu.solve(b)
        err = float(np.abs(x - ref).sum() / np.abs(ref).sum())
        resid = float(np.abs(b - self.a @ x).sum() / np.abs(b).sum())
        return err, 10.0 * self.cond1 * tol, resid


def compare(a_sp, cases, tol):
    """Worst numbers of ``cases`` — ``(values, b, x)`` triples — against the
    reference: the host float64 residual ‖b − A x‖₁/‖b‖₁ and the forward
    error against ``splu`` in units of κ₁(A)·tol.  A non-finite answer reads
    as infinite on both."""
    worst_resid = worst_ratio = 0.0
    for values, b, x in cases:
        x = np.asarray(x, dtype=np.float64)
        if not np.isfinite(x).all():
            return float("inf"), float("inf")
        orc = Oracle(a_sp, values)
        err, _, resid = orc.errors(x, b, tol)
        worst_resid = max(worst_resid, resid)
        worst_ratio = max(worst_ratio, err / (orc.cond1 * tol))
    return worst_resid, worst_ratio
