"""Property tests on the FactorPlan — the paper's core data structure.

Invariants (hypothesis over random sparse systems):
  - panel slots partition the storage exactly (no overlap, no gaps);
  - every edge's col_map hits real pattern positions of the target;
  - edges reference only earlier nodes (DAG), sources ascend;
  - levelization is a topological schedule (dual-mode split consistent);
  - A-scatter positions are unique and in-range;
  - plan flops accounting: useful ≤ padded.
"""
import numpy as np
import scipy.sparse as sp
from tests._hyp import given, settings, st

from repro.core.api import HyluOptions, analyze
from repro.core.matrix import CSR


def _analysis(seed, n, density, mode, amalg_fill_tol=0.0):
    rng = np.random.default_rng(seed)
    a = sp.random(n, n, density=density,
                  random_state=np.random.RandomState(seed), format="csr")
    a = a + sp.diags(rng.uniform(1, 2, n) * rng.choice([-1, 1], n))
    return analyze(CSR.from_scipy(a.tocsr()),
                   HyluOptions(force_mode=mode,
                               amalg_fill_tol=amalg_fill_tol))


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 10_000), st.integers(10, 80), st.floats(0.03, 0.2),
       st.sampled_from(["rowrow", "hybrid", "supernodal"]),
       st.sampled_from([0.0, 0.5, 2.0]))
def test_plan_invariants(seed, n, density, mode, amalg_fill_tol):
    an = _analysis(seed, n, density, mode, amalg_fill_tol)
    plan = an.plan

    # --- panel layout partitions storage ---------------------------------
    total = 0
    for nd in plan.nodes:
        off = plan.panel_offset[nd.nid]
        assert off == total
        total += nd.nr * nd.width
    assert total == plan.total_slots

    # --- rows partition [0, n) ------------------------------------------
    covered = np.concatenate([np.arange(nd.r0, nd.r1) for nd in plan.nodes])
    assert np.array_equal(np.sort(covered), np.arange(plan.n))

    # --- patterns sorted; block present; edges consistent -----------------
    level = np.zeros(plan.n_nodes, dtype=int)
    for nd in plan.nodes:
        pat = nd.pattern
        assert np.all(np.diff(pat) > 0)
        assert np.array_equal(pat[nd.lsize:nd.lsize + nd.nr],
                              np.arange(nd.r0, nd.r1))
        prev_src = -1
        for e in nd.edges:
            assert prev_src < e.src < nd.nid        # DAG + ascending
            prev_src = e.src
            snd = plan.nodes[e.src]
            src_cols = snd.pattern[np.searchsorted(snd.pattern, snd.r0):]
            # col_map maps exactly the source block+U cols into the target
            assert len(e.col_map) == len(src_cols)
            assert np.array_equal(pat[e.col_map], src_cols)
            level[nd.nid] = max(level[nd.nid], level[e.src] + 1)
        assert level[nd.nid] == nd.level            # topological levels

    # --- dual-mode schedule covers all nodes once -------------------------
    sched = np.concatenate(plan.levels) if plan.levels else np.empty(0, int)
    assert np.array_equal(np.sort(sched), np.arange(plan.n_nodes))
    assert 0 <= plan.n_bulk_levels <= len(plan.levels)

    # --- A-scatter unique + in-range --------------------------------------
    assert len(np.unique(plan.a_scatter)) == len(plan.a_scatter)
    assert plan.a_scatter.min() >= 0
    assert plan.a_scatter.max() < plan.total_slots

    # --- flops accounting --------------------------------------------------
    assert plan.useful_flops <= plan.padded_flops + 1e-6
    if mode == "rowrow" and amalg_fill_tol == 0.0:
        # width-1 nodes: no padding waste by construction (amalgamation
        # re-fattens panels, so the equality only holds with it off)
        assert abs(plan.useful_flops - plan.padded_flops) < 1e-6


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.integers(12, 70), st.floats(0.04, 0.22),
       st.sampled_from(["rowrow", "hybrid", "supernodal"]),
       st.sampled_from([2, 8]),
       st.sampled_from([0.0, 1.0]),
       st.sampled_from([8, 64, None]))
def test_bucket_schedule_invariants(seed, n, density, mode, bmw,
                                    amalg_fill_tol, window_cap):
    """The level-bucketed factor schedule must be a complete, non-
    overlapping re-grouping of the plan: every node's internal LU appears
    exactly once (diag bucket, panel bucket, sequential list, or scanned
    level), every edge exactly once (unrolled edge bucket or scan chunk),
    all padded indices point at the sentinel slots, and all multiplier
    scatter positions within a level are disjoint.  The scanned tail's
    windows partition each chunk's levels, and each window's buffer holds
    exactly the slots its levels touch (``window_cap`` None keeps the
    module's tail window cap)."""
    from unittest import mock

    from repro.core import structure

    an = _analysis(seed, n, density, mode, amalg_fill_tol)
    plan = an.plan
    cap = window_cap or structure.TAIL_WINDOW_SLOTS
    with mock.patch.object(structure, "TAIL_WINDOW_SLOTS", cap):
        sched = structure.build_bucket_schedule(plan, bulk_min_width=bmw)
        stats = structure.bucket_stats(plan, bulk_min_width=bmw)
    total = sched.total_slots
    sentinels = {sched.zero_slot, sched.one_slot, sched.scratch_slot}

    # --- nodes covered exactly once ---------------------------------------
    seen = []
    for s in sched.steps:
        if s.diag is not None:
            seen.extend(s.diag.nids.tolist())
            assert all(plan.nodes[t].nr == 1 for t in s.diag.nids)
        for pb in s.panels:
            seen.extend(pb.nids.tolist())
            assert all(plan.nodes[t].nr > 1 for t in pb.nids)
        seen.extend(s.seq.tolist())
    for c in sched.scan_chunks:
        for lv in range(c.lv0, c.lv1):
            nids = plan.levels[lv]
            assert all(plan.nodes[int(t)].nr == 1 for t in nids)
            seen.extend(int(t) for t in nids)
    assert np.array_equal(np.sort(np.asarray(seen)),
                          np.arange(plan.n_nodes))

    # --- edges covered exactly once ---------------------------------------
    n_edges_plan = sum(len(nd.edges) for nd in plan.nodes)
    n_edges_steps = sum(len(eb.srcs) for s in sched.steps for eb in s.edges)
    n_edges_scan = sum(int((c.x_idx < c.n_slots).sum())
                       for c in sched.scan_chunks)
    assert n_edges_steps + n_edges_scan == n_edges_plan

    # --- padding discipline ------------------------------------------------
    for s in sched.steps:
        mult_slots = []
        for eb in s.edges:
            for arr, allowed in ((eb.src_idx, {sched.zero_slot,
                                               sched.one_slot}),
                                 (eb.x_idx, {sched.zero_slot}),
                                 (eb.write_idx, {sched.scratch_slot})):
                assert arr.min() >= 0 and arr.max() < sched.n_ext
                pads = arr[arr >= total]
                assert set(np.unique(pads)) <= allowed
            # source levels all equal the step's level
            assert all(plan.nodes[int(t)].level == s.level for t in eb.srcs)
            mult = eb.write_idx[:, :eb.nr * eb.k].ravel()
            mult_slots.append(mult[mult < total])
        if mult_slots:
            mult_all = np.concatenate(mult_slots)
            # multiplier write-back positions are disjoint within a level
            # (same-level sources own disjoint block columns) — the single
            # combined scatter-.add relies on this
            assert len(np.unique(mult_all)) == len(mult_all)
        for pb in s.panels:
            real = pb.scatter[pb.scatter < total]
            assert len(np.unique(real)) == len(real)
            # real slot count == the gathered panels' true storage
            expect = sum(plan.nodes[t].nr * plan.nodes[t].width
                         for t in pb.nids)
            assert len(real) == expect

    # --- scanned tail windows ----------------------------------------------
    win_slots, windowed = [], 0
    for c in sched.scan_chunks:
        g = c.n_slots
        zero, one, scr = g, g + 1, g + 2
        w = c.windows
        # every scanned level lies in exactly one window
        assert w[0, 0] == 0 and w[-1, 1] == c.lv1 - c.lv0
        assert np.all(w[:, 1] > w[:, 0]) and np.all(w[1:, 0] == w[:-1, 1])
        assert c.gidx.shape[0] == len(w)
        for (l0, l1), gidx in zip(w, c.gidx):
            # the buffer's own zero / one / scratch entries gather the
            # global sentinels
            assert list(gidx[g:]) == [sched.zero_slot, sched.one_slot,
                                      sched.scratch_slot]
            gidx = gidx[:g]
            real_g = gidx[gidx < total]
            # real slots first, sorted and unique; pads → global scratch
            assert np.all(gidx[len(real_g):] == sched.scratch_slot)
            assert np.all(np.diff(real_g) > 0)
            touched = []
            for arr, allowed in ((c.dsl, {one}), (c.x_idx, {zero}),
                                 (c.src_idx[:, :, :1], {one}),
                                 (c.src_idx[:, :, 1:], {zero}),
                                 (c.write_idx, {scr})):
                blk = arr[l0:l1]
                assert blk.min() >= 0 and blk.max() < g + 3
                # pads point at the window's own zero / one / scratch
                assert set(np.unique(blk[blk >= g])) <= allowed
                # real positions never land on a gidx pad
                assert np.all(blk[blk < g] < len(real_g))
                touched.append(gidx[blk[blk < g]])
            # the buffer covers exactly the real slots its levels touch
            assert np.array_equal(np.unique(np.concatenate(touched)),
                                  real_g)
            # the diagonals are the levels' own pivot slots
            for lv in range(c.lv0 + l0, c.lv0 + l1):
                d = c.dsl[lv - c.lv0]
                expect = plan.row_perm_slots[
                    [plan.nodes[int(t)].r0 for t in plan.levels[lv]]]
                assert np.array_equal(np.sort(gidx[d[d < g]]),
                                      np.sort(expect))
            if l1 - l0 > 1:
                assert len(real_g) <= cap
                windowed += int(l1 - l0)
            win_slots.append(len(real_g))
        # a window stops only where its next level would pass the cap
        for (_, l1), gidx in zip(w[:-1], c.gidx[:-1, :g]):
            nxt = [arr[l1][arr[l1] < g] for arr in (c.dsl, c.x_idx,
                                                    c.src_idx, c.write_idx)]
            nxt = c.gidx[np.searchsorted(w[:, 0], l1)][
                np.concatenate([a.ravel() for a in nxt])]
            assert len(np.union1d(gidx[gidx < total], nxt)) > cap
    # the schedule's counters agree with the windows
    n_scanned = sum(c.lv1 - c.lv0 for c in sched.scan_chunks)
    assert stats["n_tail_windows"] == len(win_slots)
    assert stats["tail_window_slots_max"] == max(win_slots, default=0)
    assert np.isclose(stats["tail_window_slots_mean"],
                      np.mean(win_slots) if win_slots else 0.0)
    assert np.isclose(stats["tail_windowed_level_share"],
                      windowed / max(n_scanned, 1))


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000), st.integers(12, 60), st.floats(0.05, 0.25))
def test_solve_structure_invariants(seed, n, density):
    from repro.core.structure import build_solve_structure
    an = _analysis(seed, n, density, "hybrid")
    ss = build_solve_structure(an.plan)
    # L forward schedule: every row finalized exactly once, deps point to
    # already-finalized rows
    seen = np.zeros(n, dtype=bool)
    for rows, cols, slot, seg in zip(ss.l_fwd.rows, ss.l_fwd.cols,
                                     ss.l_fwd.slot, ss.l_fwd.seg):
        if len(cols):
            assert seen[cols].all()
        assert not seen[rows].any()
        seen[rows] = True
        assert (slot < an.plan.total_slots).all()
    assert seen.all()
    # U backward: reverse dependency direction
    seen = np.zeros(n, dtype=bool)
    for rows, cols, slot, seg in zip(ss.u_bwd.rows, ss.u_bwd.cols,
                                     ss.u_bwd.slot, ss.u_bwd.seg):
        if len(cols):
            assert seen[cols].all()
        seen[rows] = True
    assert seen.all()
