"""Per-phase device time and host-path idle from the solver's ``hylu.``
spans and scopes (``chipbench/scopes.py``)."""
import glob
import os

import pytest

from chipbench import scopes, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
DEV = "/device:TPU:0"
F, S = "jit__refactor", "jit_solve_refined"


def _ev(kind, name, start_us, end_us, track=DEV):
    return (kind, name, int(start_us * 1000), int(end_us * 1000), track)


def _op(module, op, scope, start_us, end_us, dev=DEV):
    return (module, op, scope, int(start_us * 1000), int(end_us * 1000), dev)


def _span(name, start_us, end_us):
    return (name, int(start_us * 1000), int(end_us * 1000))


def hand_trace():
    """A 200 µs window holding one step: a refactor run (10–50 µs) whose
    scanned tail is a ``while`` with its body ops inside it, and an XLA
    copy with no ``op_name``; a solve run (70–170 µs) whose unscoped
    refinement ``while`` holds the substitution and residual ops and an
    unscoped loop test."""
    events = [
        _ev("span", "cb:window", 0, 200, "python3"),
        _ev("span", "cb:factor_batched", 0, 60, "python3"),
        _ev("span", "cb:solve_batched", 60, 180, "python3"),
        _ev("module", F, 10, 50),
        _ev("module", S, 70, 170),
    ]
    ops = [
        _op(F, "%fusion.1", "hylu.factor.stage", 10, 14),
        _op(F, "%gather.2", "hylu.factor.panel", 14, 20),
        _op(F, "%scatter.3", "hylu.factor.edge", 20, 26),
        _op(F, "%while.4", "hylu.factor.tail", 26, 46),
        _op(F, "%fusion.5", "hylu.factor.tail", 27, 30),   # in the while
        _op(F, "%fusion.6", "hylu.factor.tail", 31, 45),   # in the while
        _op(F, "%copy.7", None, 46, 48),
        _op(F, "%slice.8", "hylu.factor.stage", 48, 50),
        _op(S, "%while.9", "", 70, 170),
        _op(S, "%fusion.10", "hylu.solve.subst", 72, 120),
        _op(S, "%fusion.11", "hylu.solve.residual", 120, 160),
        _op(S, "%compare.12", "", 160, 165),
    ]
    events += [_ev("op", o[1], o[3] / 1e3, o[4] / 1e3) for o in ops]
    hspans = [
        _span("hylu.factor_batched", 0, 60),
        _span("hylu.stage", 0, 8),
        _span("hylu.solve_batched", 60, 180),
        _span("hylu.stage", 60, 65),
    ]
    return events, hspans, ops


@pytest.mark.parametrize("op_name, scope", [
    ("jit(_refactor)/vmap(hylu.factor.tail)/while/body/add",
     "hylu.factor.tail"),
    ("jit(solve_refined)/while/body/hylu.solve.subst/scan",
     "hylu.solve.subst"),
    ("jit(solve_refined)/while", ""),
    ("", None),
    (None, None),
])
def test_scope_of(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_scope_seconds_count_a_loop_and_its_body_once():
    _, _, ops = hand_trace()
    sec = scopes.scope_seconds(ops, 0, 200_000)
    assert sec["hylu.factor.tail"] == pytest.approx(20e-6)   # not 37 µs
    assert sec["hylu.factor.stage"] == pytest.approx(6e-6)
    assert sec["hylu.solve.subst"] == pytest.approx(48e-6)
    assert sec["hylu.solve.residual"] == pytest.approx(40e-6)
    assert "" not in sec


def test_phases_of_the_hand_trace():
    p = scopes.phases(*hand_trace())
    assert p == {
        "factor_stage_ms.sweep": pytest.approx(0.006),
        "factor_panel_ms.sweep": pytest.approx(0.006),
        "factor_edge_ms.sweep": pytest.approx(0.006),
        "factor_tail_ms.sweep": pytest.approx(0.020),
        "solve_subst_ms.sweep": pytest.approx(0.048),
        "solve_residual_ms.sweep": pytest.approx(0.040),
        "host_stage_ms.sweep": pytest.approx(0.013),
        # idle [0,10] + [50,70] + [170,180] under the open spans, not the
        # [180,200] after them
        "solver_idle_ms.sweep": pytest.approx(0.040),
    }


def test_a_phase_the_plan_lacks_reads_zero():
    events, hspans, ops = hand_trace()
    ops = [o for o in ops if o[2] != "hylu.factor.tail"]
    assert scopes.phases(events, hspans, ops)["factor_tail_ms.sweep"] == 0.0


def test_coverage_and_what_is_left():
    _, _, ops = hand_trace()
    f = scopes.coverage(ops, "_refactor", "hylu.factor.", 0, 200_000)
    assert f["share"] == pytest.approx(1.0)
    assert f["no_op_name"] == [["%copy.7", pytest.approx(2e-6)]]
    assert f["remainder"] == []
    s = scopes.coverage(ops, "solve_refined", "hylu.solve.", 0, 200_000)
    assert s["share"] == pytest.approx(88 / 100)
    # the unscoped while outside its body ops, then the loop test
    assert s["remainder"] == [["%while.9", pytest.approx(12e-6)],
                              ["%compare.12", pytest.approx(5e-6)]]
    assert s["no_op_name_s"] == 0


def test_phases_read_nothing_without_spans_and_scopes():
    """A solver without spans and scopes (the recorded v5e trace) gives
    None for every phase, and the harness's own reduction is untouched."""
    ev = trace.load_events_json(os.path.join(DATA, "v5e_small_events.json"))
    p = scopes.phases(ev, [], [])
    assert set(p) == set(scopes.DEVICE_METRICS) | {"host_stage_ms.sweep",
                                                   "solver_idle_ms.sweep"}
    assert all(v is None for v in p.values())
    s = trace.reduce(ev)
    assert s.program("_refactor")[1] == 2


def test_recorded_chip_trace_with_scopes():
    """A trace recorded on a TPU v5e with ``scopes.py`` (circuit-sweep's
    programs at n=200, K=4, two steps): every phase reads a number, the
    scopes cover at least 95 % of each program's ops that carry an
    op_name, and the harness's reduction of the same events is what its
    own metrics read."""
    events, hspans, ops = scopes.load(
        os.path.join(DATA, "v5e_small_scoped.json"))
    p = scopes.phases(events, hspans, ops)
    assert all(v is not None and v >= 0 for v in p.values())
    assert p["factor_tail_ms.sweep"] > 0 and p["solve_subst_ms.sweep"] > 0
    s = trace.reduce(events)
    f_s, f_runs = s.program("_refactor")
    assert f_runs == 2
    factor_phases = sum(p[m] for m in p if m.startswith("factor_"))
    assert factor_phases <= 1e3 * f_s / f_runs * 1.0001
    lo, hi = scopes.window(events)
    for program, prefix in (("_refactor", "hylu.factor."),
                            ("solve_refined", "hylu.solve.")):
        assert scopes.coverage(ops, program, prefix, lo, hi)["share"] >= 0.95


def test_unscoped_ops_alone_read_nothing():
    events, hspans, ops = hand_trace()
    bare = [o[:2] + ("",) + o[3:] for o in ops]
    p = scopes.phases(events, hspans, bare)
    assert all(p[m] is None for m in scopes.DEVICE_METRICS)
    assert p["host_stage_ms.sweep"] == pytest.approx(0.013)


def test_save_and_load(tmp_path):
    events, hspans, ops = hand_trace()
    path = str(tmp_path / "scoped.json")
    scopes.save(path, events, hspans, ops)
    assert scopes.load(path) == (events, hspans, ops)


def test_collect_reads_host_spans_from_a_trace(tmp_path):
    """On a CPU trace (no device plane) ``collect`` finds the solver's
    host spans and no device operation."""
    import jax

    from repro.core.tracing import span

    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("stage"):
            jax.numpy.ones(3).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    hspans, ops = scopes.collect(path)
    assert [h[0] for h in hspans] == ["hylu.stage"]
    assert hspans[0][1] < hspans[0][2]
    assert ops == []


def test_hlo_op_names_from_the_metadata_plane(tmp_path):
    """The HLO kept in a trace's /host:metadata plane gives each
    instruction's op_name, scopes included (a CPU trace keeps it too)."""
    import jax
    import jax.numpy as jnp

    from repro.core.tracing import scope

    def f(x):
        with scope("factor.stage"):
            y = jnp.sin(x) * 2
        with scope("factor.tail"):
            y, _ = jax.lax.scan(lambda c, a: (c * a + 1, None), y,
                                jnp.arange(3.0))
        return y

    g = jax.jit(f)
    jax.profiler.start_trace(str(tmp_path))
    try:
        g(jnp.ones(4)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = scopes.hlo_op_names(path)
    found = {scopes.scope_of(n) for (m, _), n in names.items()
             if m == "jit_f"}
    assert {"hylu.factor.stage", "hylu.factor.tail"} <= found
    assert all(op.startswith("%") for _, op in names)
