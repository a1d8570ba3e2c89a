"""Useful work counts, on patterns whose counts are known by hand."""
import numpy as np
import pytest
import scipy.sparse as sp

from chipbench import work


def _analysis(a_sp):
    from repro.core import CSR, HyluOptions
    from repro.core.analysis import analyze

    return analyze(CSR.from_scipy(a_sp.tocsr()), HyluOptions())


def test_tridiagonal_counts():
    """A tridiagonal n × n matrix factors with no fill: L has n−1
    off-diagonal entries, each an update of one U entry and the pivot
    (2·(1+1) operations), so flops = 4(n−1), nnz(L+U) = 3n−2 = nnz(A)."""
    n = 5
    a = sp.diags([-np.ones(n - 1), 4 * np.ones(n), -np.ones(n - 1)],
                 [-1, 0, 1])
    c = work.Counts.of(_analysis(a))
    assert c == work.Counts(flops=16.0, nnz_lu=13, nnz_a=13)


def test_diagonal_counts():
    c = work.Counts.of(_analysis(sp.diags(np.arange(1.0, 7.0))))
    assert c == work.Counts(flops=0.0, nnz_lu=6, nnz_a=6)


def test_factor_and_solve_work():
    c = work.Counts(flops=16.0, nnz_lu=13, nnz_a=13)
    assert work.factor_work(c, 4, 4, 8) == (64.0, 4 * (13 * 8 + 13 * 4))
    # 3 substitutions: 3 × (2·13 + 2·13) ops, 3 × (13·4 + 13·8) bytes
    assert work.solve_work(c, 3, 4, 8) == (156.0, 3.0 * (52 + 104))


def test_roofline_share_takes_the_larger_bound():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_share(100.0, 5.0, 2.0, peaks) == (50.0, "compute")
    assert work.roofline_share(10.0, 10.0, 4.0, peaks) == (25.0, "memory")
    assert work.roofline_share(1.0, 1.0, 0.0, peaks) is None


@pytest.mark.parametrize("n", [50, 120])
def test_counts_match_the_symbolic_stats(n):
    from chipbench import patterns

    an = _analysis(patterns.build({"generator": "circuit_like",
                                   "args": {"n": n, "seed": 3}}))
    c = work.Counts.of(an)
    st = an.choice.stats
    assert c.flops == st["flops"] and c.nnz_lu == 2 * st["nnz_l"] + n
    assert c.nnz_a == an.src_map.size
