"""Scenario-matrix parity suite for the fused batched solve.

For every scenario × kernel mode × execution path (plain jit vs the
Pallas-TRSM block substitution in interpret mode), the fused on-device
solve_batched — substitution + device CSR residual matvec + the whole
lax.while_loop refinement — must agree with a Python loop of ref-engine
factor+solve to 1e-10, and the two paths' residuals must agree to 1e-10.
"""
import numpy as np
import pytest

from repro.core import CSR, HyluOptions, analyze, factor, solve
from repro.core.api import factor_batched, solve_batched, _solve_batched_hostloop

from tests.helpers import SCENARIOS, scenario_system

MODES = ["rowrow", "hybrid", "supernodal"]
PATHS = ["jit", "pallas-interpret"]
K = 3
N = 30


def _value_sets(Ac, k, seed):
    rng = np.random.default_rng(seed)
    return Ac.data[None, :] * rng.uniform(0.8, 1.2, (k, Ac.nnz))


@pytest.fixture(scope="module")
def fused_case(request):
    """One compiled fused-solve case per (scenario, mode, path) combo."""
    scenario, mode, path = request.param
    Ac, a_sp, b, _ = scenario_system(scenario, n=N, seed=3)
    an = analyze(Ac, HyluOptions(force_mode=mode, engine="jax",
                                 use_pallas=(path == "pallas-interpret")))
    vb = _value_sets(Ac, K, seed=7)
    rng = np.random.default_rng(17)
    bb = rng.normal(size=(K, Ac.n))
    bst = factor_batched(an, Ac, vb)
    x, info = solve_batched(bst, bb)
    return scenario, mode, path, Ac, an, vb, bb, bst, x, info


def _ref_loop(an_mode, Ac, vb, bb):
    """Python loop of ref-engine factor + solve over the K value sets."""
    an = analyze(Ac, HyluOptions(force_mode=an_mode, engine="ref"))
    xs, resids = [], []
    for i in range(vb.shape[0]):
        ai = CSR(Ac.n, Ac.indptr, Ac.indices, vb[i].copy())
        st = factor(an, ai, engine="ref")
        x, info = solve(st, bb[i])
        xs.append(x)
        resids.append(info["residual"])
    return np.stack(xs), np.asarray(resids)


ALL_CASES = [(s, m, p) for s in SCENARIOS for m in MODES for p in PATHS]


@pytest.mark.parametrize(
    "fused_case", ALL_CASES, indirect=True,
    ids=[f"{s}-{m}-{p}" for s, m, p in ALL_CASES])
def test_fused_matches_ref_loop(fused_case):
    scenario, mode, path, Ac, an, vb, bb, bst, x, info = fused_case
    assert info["residual"].shape == (K,)
    assert info["residual"].max() < 1e-10, (scenario, mode, path)

    x_ref, resid_ref = _ref_loop(mode, Ac, vb, bb)
    scale = np.abs(x_ref).max() + 1e-30
    assert np.abs(x - x_ref).max() / scale < 1e-10, (scenario, mode, path)
    assert np.abs(info["residual"] - resid_ref).max() < 1e-10, \
        (scenario, mode, path)

    # and the fused program ≡ the host-loop implementation it replaced
    x_host, info_host = _solve_batched_hostloop(bst, bb)
    assert np.abs(x - x_host).max() / scale < 1e-12
    assert np.abs(info["residual"] - info_host["residual"]).max() < 1e-12


@pytest.mark.parametrize(
    "fused_case", [("banded", "hybrid", "jit")], indirect=True,
    ids=["banded-hybrid-jit"])
def test_fused_multi_rhs(fused_case):
    """Multi-RHS (K, n, m) through the same fused program: each column must
    match the single-RHS solve of that column."""
    scenario, mode, path, Ac, an, vb, bb, bst, x, info = fused_case
    rng = np.random.default_rng(5)
    m = 3
    bm = rng.normal(size=(K, Ac.n, m))
    xm, infom = solve_batched(bst, bm)
    assert xm.shape == (K, Ac.n, m)
    assert infom["residual"].shape == (K, m)
    assert infom["residual"].max() < 1e-10
    for j in range(m):
        xj, infoj = solve_batched(bst, bm[:, :, j])
        assert np.abs(xm[:, :, j] - xj).max() < 1e-12
    # the host-loop oracle handles the same multi-RHS shapes
    xh, infoh = _solve_batched_hostloop(bst, bm)
    assert np.abs(xm - xh).max() < 1e-12
    assert np.abs(infom["residual"] - infoh["residual"]).max() < 1e-12
    # broadcast rhs still works
    xb, infob = solve_batched(bst, bb[0])
    assert xb.shape == (K, Ac.n)
    assert infob["residual"].max() < 1e-10


@pytest.mark.parametrize(
    "fused_case", [("circuit", "rowrow", "jit")], indirect=True,
    ids=["circuit-rowrow-jit"])
def test_refine_false_and_zero_rhs(fused_case):
    scenario, mode, path, Ac, an, vb, bb, bst, x, info = fused_case
    x0, info0 = solve_batched(bst, bb, refine=False)
    assert info0["n_refine"] == 0
    assert np.all(info0["n_refine_per_system"] == 0)
    # all-zero rhs: the zero-bnorm guard must not divide by zero, and the
    # solution of A x = 0 is exactly 0
    xz, infoz = solve_batched(bst, np.zeros((K, Ac.n)))
    assert np.all(np.isfinite(infoz["residual"]))
    assert np.abs(xz).max() == 0.0
    assert infoz["residual"].max() == 0.0


@pytest.mark.parametrize(
    "fused_case", [("circuit", "hybrid", "jit")], indirect=True,
    ids=["circuit-hybrid-jit"])
def test_refinement_engaged_parity(fused_case):
    """tol=0 forces the refinement loop to actually iterate until it
    stalls; the fused while_loop and the host-loop oracle follow the same
    per-system acceptance rule.  Their accept/reject decisions sit at the
    round-off floor (device segment-sum vs numpy reduceat residuals), so
    trajectories may differ in which noise-level step they accept — but
    both must genuinely iterate and land on the same solution to full
    refinement accuracy.  tol is a dynamic arg, so this reuses the
    compiled program."""
    scenario, mode, path, Ac, an, vb, bb, bst, x, info = fused_case
    tol_saved = an.opts.refine_tol
    an.opts.refine_tol = 0.0
    try:
        xf, inff = solve_batched(bst, bb, refine=True)
        xh, infh = _solve_batched_hostloop(bst, bb, refine=True)
    finally:
        an.opts.refine_tol = tol_saved
    assert inff["n_refine"] >= 1              # the fused loop really ran
    assert infh["n_refine"] >= 1
    scale = np.abs(xh).max() + 1e-30
    assert np.abs(xf - xh).max() / scale < 1e-12
    assert inff["residual"].max() < 1e-12
    assert infh["residual"].max() < 1e-12


@pytest.mark.parametrize("scenario", ["banded", "denseish", "circuit"])
@pytest.mark.parametrize("upper", [False, True])
def test_block_solve_schedule_invariants(scenario, upper):
    """The Pallas block substitution's schedule at routing scale: every
    row is solved exactly once (as a width-1 node or inside one diagonal
    block), every off-block dependency reads a row solved at an earlier
    level, padding points only at the sentinels, and the chunk count —
    the traced program's size — stays bounded."""
    from repro.core.structure import block_solve_schedule
    from tests.helpers import routing_system

    Ac, _, _ = routing_system(scenario)
    plan = analyze(Ac, HyluOptions(engine="jax", use_pallas=True)).plan
    n, zero, one = plan.n, plan.total_slots, plan.total_slots + 1
    chunks = block_solve_schedule(plan, upper=upper)
    assert 0 < len(chunks) <= 24
    solved_at = np.full(n + 1, -1)
    level = 0
    for ch in chunks:
        for li in range(ch.dep_rows.shape[0]):
            dr, dc, ds = ch.dep_rows[li], ch.dep_cols[li], ch.dep_slots[li]
            real = dr < n
            assert np.all(ds[~real] == zero) and np.all(dc[~real] == n)
            assert np.all(solved_at[dc[real]] >= 0)
            assert np.all(solved_at[dc[real]] < level)
            rows = np.concatenate([ch.w1_rows[li], ch.blk_rows[li].ravel()])
            rows = rows[rows < n]
            assert np.all(solved_at[rows] == -1)
            assert len(np.unique(rows)) == len(rows)
            assert set(dr[real]) <= set(rows)
            solved_at[rows] = level
            assert np.all(ch.w1_slots[li][ch.w1_rows[li] == n] == one)
            for br, bs in zip(ch.blk_rows[li], ch.blk_slots[li]):
                pad = br == n
                assert np.all(np.diag(bs)[pad] == one)
                assert np.all(bs[pad][:, ~pad] == zero)
                assert np.all(bs[~pad][:, pad] == zero)
            level += 1
    assert np.all(solved_at[:n] >= 0)
