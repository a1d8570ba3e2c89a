"""The factor and solve readers (``factor_ms``, ``factor_roofline``,
``solve_ms``, ``solve_roofline``) read the same work whatever programs run
it: module runs are given to the factor or the solve by the ``hylu.``
scopes of the operations they hold, and counted per traced step."""
import os

import pytest

from chipbench import harness, scopes, trace
from chipbench.work import Counts, factor_work, roofline_share, solve_work

DATA = os.path.join(os.path.dirname(__file__), "data")
DEV = "/device:TPU:0"
READERS = ("factor_ms.sweep", "factor_roofline.sweep", "solve_ms.sweep",
           "solve_roofline.sweep")
PHASES = tuple(scopes.DEVICE_METRICS) + ("host_stage_ms.sweep",
                                         "solver_idle_ms.sweep")
STEP_US = 200
#: one step of the plain solver, in µs from the step's start: the
#: refactor's ops, then the fused solve's (op, scope, start, end)
FACTOR_OPS = [("%fusion.1", "hylu.factor.stage", 10, 14),
              ("%fusion.2", "hylu.factor.panel", 14, 20),
              ("%scatter.3", "hylu.factor.edge", 20, 26),
              ("%while.4", "hylu.factor.tail", 26, 46),
              ("%fusion.5", "hylu.factor.tail", 27, 45),
              ("%copy.6", None, 46, 48),
              ("%slice.7", "hylu.factor.stage", 48, 50)]
SOLVE_OPS = [("%while.8", "", 70, 170),
             ("%fusion.9", "hylu.solve.subst", 72, 120),
             ("%fusion.10", "hylu.solve.residual", 120, 160),
             ("%compare.11", "", 160, 165),
             ("%copy.12", None, 165, 168)]
PLAIN = {"factor": [("jit__refactor", 10, 50)],
         "solve": [("jit_solve_refined", 70, 170)]}


def _ns(us):
    return int(us * 1000)


def hand_trace(modules=PLAIN, steps=2, scoped=True, device_steps=None,
               solve_shift=0, factor_span_end=60):
    """``steps`` steps of one traced window, each a factor span (0–60 µs),
    a solve span (60–180 µs) and the device work of ``modules``: family ->
    [(module, start, end)], each op held by the module run that covers its
    start.  ``device_steps`` < ``steps`` drops the device events of the
    last steps, as a trace that lost them would; ``solve_shift`` moves the
    solve's ops by that many µs, and ``factor_span_end`` ends the factor
    span early, as a factor that returns before its device work would."""
    device_steps = steps if device_steps is None else device_steps
    solve = [(op, scope, s + solve_shift, e + solve_shift)
             for op, scope, s, e in SOLVE_OPS]
    events = [("span", "cb:window", 0, _ns(steps * STEP_US), "python3")]
    hspans, ops = [], []
    for t in range(steps):
        o = t * STEP_US
        events += [("span", "cb:factor_batched", _ns(o),
                    _ns(o + factor_span_end), "h"),
                   ("span", "cb:solve_batched", _ns(o + 60), _ns(o + 180),
                    "h")]
        hspans += [("hylu.factor_batched", _ns(o), _ns(o + 60)),
                   ("hylu.stage", _ns(o), _ns(o + 8)),
                   ("hylu.solve_batched", _ns(o + 60), _ns(o + 180))]
        if t >= device_steps:
            continue
        runs = [(n, s, e) for fam in ("factor", "solve")
                for n, s, e in modules[fam]]
        for n, s, e in runs:
            events.append(("module", n, _ns(o + s), _ns(o + e), DEV))
        for op, scope, s, e in FACTOR_OPS + solve:
            holder = [n for n, ms, me in runs if ms <= s < me]
            module = holder[0] if holder else ""
            events.append(("op", op, _ns(o + s), _ns(o + e), DEV))
            ops.append((module, op, scope if scoped else "", _ns(o + s),
                        _ns(o + e), DEV))
    return events, hspans, ops


COUNTS = Counts(flops=3.0e6, nnz_lu=50_000, nnz_a=20_000)


def context(events, hspans, ops, steps=2, substitutions=(8, 8)):
    return harness.Context(
        summary=trace.reduce(events),
        counters={"steps": steps, "k": 4,
                  "substitutions": list(substitutions)},
        work=COUNTS, peaks=harness.peaks_for("TPU v5 lite"),
        factor_bytes=4, values_bytes=8,
        scoped=scopes.Window(events, hspans, ops, steps))


def read_all(ctx, names=READERS):
    out = {}
    for name in names:
        reader = harness.load_module(harness.BENCH / "metrics" / f"{name}.py")
        out[name] = reader.read(ctx)
    return out


def old_readings(ctx):
    """The four readers as they were: per run of the modules whose name
    holds ``_refactor`` / ``solve_refined``, the solve's share only where
    that program ran once per step."""
    f_s, f_runs = ctx.summary.program("_refactor")
    s_s, s_runs = ctx.summary.program("solve_refined")
    subst = ctx.counters["substitutions"]
    ops, nbytes = factor_work(ctx.work, ctx.counters["k"], ctx.factor_bytes,
                              ctx.values_bytes)
    s_ops, s_bytes = solve_work(ctx.work, sum(subst), ctx.factor_bytes,
                                ctx.values_bytes)
    return {
        "factor_ms.sweep": 1e3 * f_s / f_runs,
        "factor_roofline.sweep": roofline_share(ops, nbytes, f_s / f_runs,
                                                ctx.peaks)[0],
        "solve_ms.sweep": 1e3 * s_s / s_runs,
        "solve_roofline.sweep": (roofline_share(s_ops, s_bytes, s_s,
                                                ctx.peaks)[0]
                                 if len(subst) == s_runs else None),
    }


def test_plain_trace_reads_as_before():
    ctx = context(*hand_trace())
    new = read_all(ctx)
    assert new["factor_ms.sweep"] == pytest.approx(0.040)
    assert new["solve_ms.sweep"] == pytest.approx(0.100)
    assert new == pytest.approx(old_readings(ctx), rel=1e-12)
    att = ctx.scoped.attribution
    assert att.modules["factor"] == {"jit__refactor": [2, pytest.approx(
        80e-6), 14]}
    assert att.other == {}


RESTRUCTURED = {
    "renamed": {"factor": [("jit_numeric_lu", 10, 50)],
                "solve": [("jit_fused_refine", 70, 170)]},
    "refactor in two programs": {
        "factor": [("jit_lu_head", 10, 26), ("jit_lu_tail", 26, 50)],
        "solve": [("jit_solve_refined", 70, 170)]},
    "solve in two programs": {
        "factor": [("jit__refactor", 10, 50)],
        "solve": [("jit_subst", 70, 120), ("jit_refine", 120, 170)]},
}


@pytest.mark.parametrize("how", list(RESTRUCTURED))
def test_restructured_programs_read_the_plain_numbers(how):
    plain = read_all(context(*hand_trace()))
    ctx = context(*hand_trace(RESTRUCTURED[how]))
    assert read_all(ctx) == plain
    for name, value in read_all(ctx, PHASES).items():
        assert value == read_all(context(*hand_trace()), (name,))[name]


def test_split_refactor_lists_both_programs():
    events, hspans, ops = hand_trace(RESTRUCTURED["refactor in two programs"])
    att = scopes.attribute(events, ops)
    assert att.modules["factor"] == {
        "jit_lu_head": [2, pytest.approx(32e-6), 6],
        "jit_lu_tail": [2, pytest.approx(48e-6), 8]}
    lines = scopes.describe(att)
    assert lines[0].startswith("factor: jit_lu_head 2 runs")


def test_one_program_holding_both_families():
    """A fused program that runs the refactor and the solve back to back
    (the solve's ops 20 µs earlier, where the plain solve program starts
    after a gap) reads what the two programs read: the loop that holds
    the solve's ops is the solve's, the copy between two factor ops the
    factor's, and nothing of the run is left over."""
    plain = context(*hand_trace())
    both = {"factor": [("jit_step", 10, 150)], "solve": []}
    ctx = context(*hand_trace(both, solve_shift=-20))
    att = ctx.scoped.attribution
    assert att.modules == {"factor": {"jit_step": [2, pytest.approx(80e-6),
                                                    14]},
                           "solve": {"jit_step": [2, pytest.approx(200e-6),
                                                   10]}}
    assert att.other == {}
    assert read_all(ctx) == read_all(plain)
    assert read_all(ctx, tuple(scopes.DEVICE_METRICS)) == read_all(
        plain, tuple(scopes.DEVICE_METRICS))
    lines = scopes.describe(att)
    assert lines[0].startswith("factor: jit_step 2 runs")
    assert lines[1].startswith("solve: jit_step 2 runs")


@pytest.mark.parametrize("held, want", [
    # a copy before any scoped op goes to the first family, the gap after
    # the factor's last op to the factor, the tail of the run to the solve
    ([(None, 0, 2), ("factor", 2, 5), (None, 5, 7), ("solve", 7, 9),
      (None, 9, 10)], {"factor": [7, 3], "solve": [5, 2]}),
    # an unscoped loop that holds solve ops is the solve's from its start
    ([("factor", 0, 5), (None, 6, 11), ("solve", 7, 9)],
     {"factor": [6, 1], "solve": [6, 2]}),
])
def test_a_run_of_both_families_is_shared_whole(held, want):
    assert scopes._split(0, 12, held) == want


@pytest.mark.parametrize("source", ["hand, plain names", "hand, renamed",
                                    "v5e_small_events.json"])
def test_a_trace_without_scopes_reads_nothing(source, capsys):
    """A trace whose ops carry no ``hylu.`` scope (a solver from before the
    scopes, or one that dropped them) gives the four readers nothing to
    read, whatever its programs are called, and each says so."""
    if source.startswith("hand"):
        modules = PLAIN if source.endswith("plain names") else \
            RESTRUCTURED["renamed"]
        ctx = context(*hand_trace(modules, scoped=False))
    else:
        events = trace.load_events_json(os.path.join(DATA, source))
        ctx = context(events, [], [])
    assert all(v is None for v in read_all(ctx).values())
    err = capsys.readouterr().err.splitlines()
    assert len(err) == len(READERS)
    assert all("reads nothing: no scoped op: none carries a hylu. scope"
               in line for line in err)


def test_a_family_without_scoped_ops_reads_nothing(capsys):
    events, hspans, ops = hand_trace()
    ops = [o[:2] + ("",) + o[3:] if o[2] and o[2].startswith("hylu.solve.")
           else o for o in ops]
    got = read_all(context(events, hspans, ops))
    assert got["factor_ms.sweep"] == pytest.approx(0.040)
    assert got["solve_ms.sweep"] is None
    assert got["solve_roofline.sweep"] is None
    err = capsys.readouterr().err
    assert "solve_ms.sweep reads nothing: no scoped op of the family" in err


def test_a_trace_that_lost_its_last_step_reads_nothing(capsys):
    """Device events that end before the last ``cb:solve_batched`` span
    starts: the reading would be short, so every reader reads nothing and
    says why in one line."""
    events, hspans, ops = hand_trace(steps=3, device_steps=2)
    ctx = context(events, hspans, ops, steps=3, substitutions=(8, 8, 8))
    assert all(v is None for v in read_all(ctx, READERS + PHASES).values())
    err = capsys.readouterr().err.splitlines()
    assert len(err) == len(READERS + PHASES)
    assert all("trace incomplete" in line for line in err)
    assert "before the last cb:solve_batched span starts" in err[0]


def test_a_trace_that_lost_its_first_step_reads_nothing():
    events, hspans, ops = hand_trace(steps=2)
    late = 1000 * STEP_US
    events = [e for e in events if e[0] == "span" or e[2] >= late]
    ops = [o for o in ops if o[3] >= late]
    why = scopes.incomplete(events)
    assert why and "after the first cb:solve_batched span ends" in why


def test_a_factor_that_returns_before_its_device_work_reads_as_before():
    """A factor dispatched without waiting: its span ends before the
    device starts the step's work, and the trace is still whole."""
    events, hspans, ops = hand_trace(factor_span_end=5)
    ctx = context(events, hspans, ops)
    assert ctx.scoped.incomplete is None
    assert read_all(ctx) == read_all(context(*hand_trace()))


def test_no_traced_step_reads_nothing(capsys):
    ctx = context(*hand_trace(), steps=0)
    assert read_all(ctx)["factor_ms.sweep"] is None
    assert "no traced step" in capsys.readouterr().err


def test_recorded_chip_traces_read_as_before():
    """On a trace recorded on a TPU v5e (circuit-sweep's programs at
    n=200, K=4, two steps), the readers by scope equal the per-run ones
    by module name."""
    events, hspans, ops = scopes.load(os.path.join(DATA,
                                                   "v5e_small_scoped.json"))
    ctx = context(events, hspans, ops, steps=2, substitutions=(8, 8))
    assert ctx.scoped.incomplete is None
    assert ctx.scoped.attribution.other == {}
    new, old = read_all(ctx), old_readings(ctx)
    assert all(v is not None for v in new.values())
    for name in READERS:
        assert new[name] == pytest.approx(old[name], rel=1e-9), name


def test_the_harness_hands_the_scoped_window_to_the_readers(monkeypatch,
                                                             capsys):
    """A traced run on the CPU, whose profiler writes no device lines,
    with this file's hand trace standing in for what the profiler and
    ``scopes.collect`` would read: every reader of the cell reads, and the
    run names the modules it gave to each family."""
    events, hspans, ops = hand_trace()
    monkeypatch.setattr(trace, "events_from_xplane", lambda path: events)
    monkeypatch.setattr(scopes, "collect", lambda path: (hspans, ops))
    memo = {}
    r = harness.run_cell(
        "circuit-sweep", 7, 1.0, True, device_check=False,
        compile_cache=False, config_over={"pattern": {"args": {"n": 200}}},
        traffic_over={"systems_per_step": 4, "samples_per_step": 2,
                      "trace_steps": 2}, memo=memo)
    assert r["correct"]
    ctx = memo["last_context"]
    assert ctx.scoped.steps == 2 and ctx.scoped.ops is ops
    cell = harness.load_cell("circuit-sweep")
    # the CPU has no row in peaks.json, so only the two shares are missing
    missing = {m["name"] for m in cell.per_layer} - set(r["metrics"])
    assert missing == {"factor_roofline.sweep", "solve_roofline.sweep"}
    assert r["metrics"]["factor_ms.sweep"]["value"] == pytest.approx(0.040)
    err = capsys.readouterr().err
    assert "factor: jit__refactor 2 runs" in err
    assert "factor_roofline.sweep reads nothing: the device is not in " \
           "peaks.json" in err
