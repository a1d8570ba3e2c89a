"""Parity suite for the level-bucketed factorization trace.

The bucketed schedule (O(levels × shape-buckets) trace) must produce the
same factors as the historical per-node/per-edge unrolled trace AND the
numpy reference engine — same panel values to 1e-10, same in-node pivot
choices (``inode_perm`` equality) and the same pivot-perturbation counts —
across the scenario matrix × kernel modes × execution paths (plain jit vs
Pallas interpret).  The two jax schedules differ only in floating-point
summation order of trailing updates, so agreement is at round-off.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from repro.core import CSR, HyluOptions, analyze
from repro.core.api import _m_values, factor, solve
from repro.core.jax_engine import make_factor_fn
from repro.core import ref_engine

from tests.helpers import SCENARIOS, scenario_system

MODES = ["rowrow", "hybrid", "supernodal"]
PATHS = ["jit", "pallas-interpret"]
N = 30
ALL_CASES = [(s, m, p) for s in SCENARIOS for m in MODES for p in PATHS]


@pytest.fixture(scope="module")
def bucket_case(request):
    """One compiled (scenario, mode, path) combo: ref factors + bucketed
    and unrolled jax factors of the same preprocessed values."""
    scenario, mode, path = request.param
    Ac, a_sp, b, _ = scenario_system(scenario, n=N, seed=5)
    # bulk_min_width=2 so the bucketed path actually engages its bulk mode
    # (panel/edge buckets) at this test scale, not just the scan tail
    an = analyze(Ac, HyluOptions(force_mode=mode, bulk_min_width=2))
    m = _m_values(an, Ac)
    pallas = path == "pallas-interpret"
    f_ref = ref_engine.factor(an.plan, m, perturb_eps=an.opts.perturb_eps)
    fb = jax.jit(make_factor_fn(an.plan, use_pallas=pallas,
                                bulk_min_width=2))(jnp.asarray(m.data))
    fu = jax.jit(make_factor_fn(an.plan, use_pallas=pallas,
                                schedule="unrolled"))(jnp.asarray(m.data))
    return scenario, mode, path, an, f_ref, fb, fu


@pytest.mark.parametrize("bucket_case", ALL_CASES, indirect=True,
                         ids=[f"{s}-{m}-{p}" for s, m, p in ALL_CASES])
def test_bucketed_vs_unrolled_vs_ref(bucket_case):
    scenario, mode, path, an, f_ref, fb, fu = bucket_case
    for name, f in (("bucketed", fb), ("unrolled", fu)):
        tag = (scenario, mode, path, name)
        assert np.abs(np.asarray(f.vals) - f_ref.vals).max() < 1e-10, tag
        assert np.array_equal(np.asarray(f.inode_perm), f_ref.inode_perm), tag
        assert int(f.n_perturb) == f_ref.n_perturb, tag
    assert np.abs(np.asarray(fb.vals) - np.asarray(fu.vals)).max() < 1e-10


@pytest.mark.parametrize("mode", MODES)
def test_perturbation_count_parity(mode):
    """A numerically singular system (duplicate row) must trigger the same
    pivot perturbations — count and positions — in all three engines."""
    rng = np.random.default_rng(7)
    a = sp.random(26, 26, density=0.18,
                  random_state=np.random.RandomState(3), format="lil")
    a = a + sp.diags(rng.uniform(1, 2, 26))
    a[9, :] = a[4, :]                      # exactly dependent rows
    Ac = CSR.from_scipy(a.tocsr())
    an = analyze(Ac, HyluOptions(force_mode=mode, bulk_min_width=2))
    m = _m_values(an, Ac)
    f_ref = ref_engine.factor(an.plan, m, perturb_eps=an.opts.perturb_eps)
    assert f_ref.n_perturb >= 1
    fb = jax.jit(make_factor_fn(an.plan, bulk_min_width=2))(
        jnp.asarray(m.data))
    assert int(fb.n_perturb) == f_ref.n_perturb
    assert np.array_equal(np.asarray(fb.inode_perm), f_ref.inode_perm)
    assert np.abs(np.asarray(fb.vals) - f_ref.vals).max() < 1e-8


@pytest.mark.parametrize("mode", MODES)
def test_default_bulk_width_end_to_end(mode):
    """With the production bulk_min_width the engine must still solve to
    refinement accuracy (the schedule then mixes unrolled bulk levels,
    per-node sequential nodes and scanned width-1 tails)."""
    Ac, a_sp, b, _ = scenario_system("circuit", n=40, seed=11)
    an = analyze(Ac, HyluOptions(force_mode=mode, engine="jax"))
    st = factor(an, Ac)
    x, info = solve(st, b)
    assert info["residual"] < 1e-10, mode


def _dependent_row_system(n=26):
    """The perturbation test's system: two identical rows, so one pivot
    collapses to round-off and must be perturbed."""
    rng = np.random.default_rng(7)
    a = sp.random(n, n, density=0.18,
                  random_state=np.random.RandomState(3), format="lil")
    a = a + sp.diags(rng.uniform(1, 2, n))
    a[9, :] = a[4, :]
    return CSR.from_scipy(a.tocsr())


# (circuit n and seed, or None for the dependent-row system; tail window
# cap; bulk_min_width; batch K).  A bulk_min_width above n scans every
# level, so the tail does all of the factorization.
WINDOW_CASES = {
    "many-windows": ((60, 2), 48, 8, 1),
    "one-window": ((60, 2), None, 8, 1),
    "dup-writes": ((80, 0), 400, 8, 1),
    "tiny-pivot": (None, 24, 64, 1),
    "vmap-k3": ((60, 2), 48, 8, 3),
}


@pytest.mark.parametrize("case", list(WINDOW_CASES))
def test_windowed_tail_parity(case, monkeypatch):
    """The scanned width-1 tail runs window by window over small slot
    buffers (``ScanChunk``); whatever the windows, it must give the
    unrolled oracle's and ``ref_engine``'s values, pivots and
    perturbation counts, also vmapped over K value sets."""
    from repro.core import structure

    system, cap, bmw, k = WINDOW_CASES[case]
    if system is None:
        Ac = _dependent_row_system()
    else:
        Ac = scenario_system("circuit", n=system[0], seed=system[1])[0]
    if cap is not None:
        monkeypatch.setattr(structure, "TAIL_WINDOW_SLOTS", cap)
    an = analyze(Ac, HyluOptions(force_mode="rowrow", bulk_min_width=bmw))
    plan = an.plan
    sched = structure.get_bucket_schedule(plan, bulk_min_width=bmw)
    chunks = sched.scan_chunks
    n_win = [len(c.windows) for c in chunks]
    lens = [int(c.lv1 - c.lv0) for c in chunks]

    # the case exercises what it names
    assert chunks
    if case in ("many-windows", "vmap-k3"):
        assert max(n_win) >= 3
        assert structure.bucket_stats(
            plan, bulk_min_width=bmw)["tail_windowed_level_share"] > 0
    if case == "one-window":
        assert all(w == 1 for w in n_win) and max(lens) > 1
    if case == "dup-writes":
        assert max(n_win) >= 3
        dup = False
        for c in chunks:
            for (l0, l1) in c.windows:
                if l1 - l0 > 1:
                    for w in c.write_idx[l0:l1]:
                        real = w[w < c.n_slots]
                        dup |= len(np.unique(real)) < len(real)
        assert dup
    if case == "tiny-pivot":
        assert not sched.steps and max(n_win) >= 2

    m = _m_values(an, Ac)
    eps = an.opts.perturb_eps
    datas = [m.data * (1.0 + 0.25 * i) for i in range(k)]
    refs = [ref_engine.factor(plan, CSR(m.n, m.indptr, m.indices, d),
                              perturb_eps=eps) for d in datas]
    if case == "tiny-pivot":
        assert refs[0].n_perturb >= 1
    tol = 1e-8 if case == "tiny-pivot" else 1e-10
    fb_fn = make_factor_fn(plan, bulk_min_width=bmw)
    fu_fn = jax.jit(make_factor_fn(plan, schedule="unrolled"))
    if k > 1:
        fbs = jax.jit(jax.vmap(fb_fn))(jnp.asarray(np.stack(datas)))
        fbs = [jax.tree.map(lambda x, i=i: x[i], fbs) for i in range(k)]
    else:
        fbs = [jax.jit(fb_fn)(jnp.asarray(datas[0]))]
    for i, (fb, fr) in enumerate(zip(fbs, refs)):
        fu = fu_fn(jnp.asarray(datas[i]))
        for name, f in (("windowed", fb), ("unrolled", fu)):
            tag = (case, i, name)
            assert np.abs(np.asarray(f.vals) - fr.vals).max() < tol, tag
            assert np.array_equal(np.asarray(f.inode_perm),
                                  fr.inode_perm), tag
            assert int(f.n_perturb) == fr.n_perturb, tag
        assert np.abs(np.asarray(fb.vals) - np.asarray(fu.vals)).max() < tol
