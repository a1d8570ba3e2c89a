"""The solver's spans and scopes (``repro.core.tracing``).

Device scopes must reach the compiled programs' ``op_name`` metadata
exactly for the phases a plan has; host spans must land in a
``jax.profiler`` trace nested as documented, change no result, and keep
filling the documented ``timings`` / ``stats`` keys.
"""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (HyluOptions, PlanCache, analyze, factor,
                        factor_batched, solve, solve_batched, solve_sequence)
from repro.core.analysis import jax_repeated_engine
from repro.core.structure import get_bucket_schedule
from repro.core.tracing import span
from repro.serve.solver_service import SolverService

from tests.helpers import routing_system
from tests.test_mixed_precision import _illconditioned_batch

FACTOR_SCOPES = ("hylu.factor.stage", "hylu.factor.panel",
                 "hylu.factor.edge", "hylu.factor.tail")
SOLVE_SCOPES = ("hylu.solve.subst", "hylu.solve.residual")
K = 2


def _scopes(compiled_text):
    return set(re.findall(r"hylu\.[a-z_]+\.[a-z_]+", compiled_text))


def _expected_factor_scopes(an):
    sched = get_bucket_schedule(an.plan,
                                bulk_min_width=an.opts.bulk_min_width)
    has = {
        "hylu.factor.stage": True,
        "hylu.factor.panel": any(s.diag is not None or s.panels or s.seq
                                 for s in sched.steps),
        "hylu.factor.edge": any(s.edges for s in sched.steps),
        "hylu.factor.tail": bool(sched.scan_chunks),
    }
    return {name for name, present in has.items() if present}


@pytest.fixture(scope="module")
def compiled_scopes():
    """{scenario: (mode, expected factor scopes, factor scopes, solve
    scopes)} from the compiled HLO text of a rowrow and a hybrid system."""
    out = {}
    for name in ("circuit", "banded"):
        a, _, mode = routing_system(name)
        an = analyze(a, HyluOptions(engine="jax"))
        eng = jax_repeated_engine(an)
        v = jax.ShapeDtypeStruct((K, a.nnz), jnp.dtype(eng.values_dtype))
        factor_txt = eng.refactor_batched.lower(v).compile().as_text()
        jf = jax.eval_shape(eng.refactor_batched, v)
        solver = eng.refined_batched_solver(a.indptr, a.indices)
        solve_txt = solver.lower(
            jf.vals, jf.inode_perm, v,
            jax.ShapeDtypeStruct((K, a.n), jnp.dtype(eng.values_dtype)),
            3, 1e-12).compile().as_text()
        out[name] = (mode, an.choice.mode, _expected_factor_scopes(an),
                     _scopes(factor_txt), _scopes(solve_txt))
    return out


@pytest.mark.parametrize("name", ["circuit", "banded"])
def test_factor_scopes_follow_the_plan(compiled_scopes, name):
    expected_mode, mode, expected, factor_s, _ = compiled_scopes[name]
    assert mode == expected_mode
    assert factor_s == expected


@pytest.mark.parametrize("name", ["circuit", "banded"])
def test_solve_scopes_in_the_fused_solve(compiled_scopes, name):
    *_, solve_s = compiled_scopes[name]
    assert solve_s == set(SOLVE_SCOPES)


def test_rowrow_and_hybrid_cover_every_scope(compiled_scopes):
    seen = set()
    for *_, factor_s, solve_s in compiled_scopes.values():
        seen |= factor_s | solve_s
    assert seen == set(FACTOR_SCOPES + SOLVE_SCOPES)
    # the rowrow circuit has the scanned tail, the hybrid band has none
    assert "hylu.factor.tail" in compiled_scopes["circuit"][3]
    assert "hylu.factor.tail" not in compiled_scopes["banded"][3]


# --------------------------------------------------------------------------
# host spans under a profiler trace
# --------------------------------------------------------------------------
def _traced(fn, tmp_path):
    """fn()'s result and the ``hylu.`` host spans (name, start, end) of a
    CPU ``jax.profiler`` trace around it."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.end_ns) for e in line.events
                          if e.name.startswith("hylu.")]
    return out, spans


def _inside(spans, inner, outer):
    """Every ``outer`` span holds at least one ``inner`` span."""
    outs = [s for s in spans if s[0] == outer]
    ins = [s for s in spans if s[0] == inner]
    return bool(outs) and all(
        any(o[1] <= i[1] and i[2] <= o[2] for i in ins) for o in outs)


def _ill_step():
    """One factor_batched + solve_batched on a batch whose ill systems go
    through the fp64 fallback."""
    Ac, vb, bb = _illconditioned_batch()
    an = analyze(Ac, HyluOptions(engine="jax", factor_dtype="float32"))
    bst = factor_batched(an, Ac, vb)
    x, info = solve_batched(bst, bb)
    return an, bst, x, info


def test_batched_spans_nest_under_a_trace(tmp_path):
    (_, _, _, info), spans = _traced(_ill_step, tmp_path)
    assert info["n_fp64_fallback"] == 2
    names = {s[0] for s in spans}
    assert {"hylu.factor_batched", "hylu.solve_batched", "hylu.stage",
            "hylu.fp64_fallback"} <= names
    assert _inside(spans, "hylu.stage", "hylu.factor_batched")
    assert _inside(spans, "hylu.stage", "hylu.solve_batched")
    assert _inside(spans, "hylu.fp64_fallback", "hylu.solve_batched")
    assert _inside(spans, "hylu.stage", "hylu.fp64_fallback")
    # the analysis phases are spans of the same trace
    assert {"hylu.analyze.matching", "hylu.analyze.ordering",
            "hylu.analyze.symbolic", "hylu.analyze.plan"} <= names


def test_results_bit_identical_with_the_profiler_on(tmp_path):
    _, _, x_off, info_off = _ill_step()
    (_, _, x_on, info_on), spans = _traced(_ill_step, tmp_path)
    assert spans
    np.testing.assert_array_equal(x_on, x_off)
    for key in ("residual", "n_refine_per_system", "fallback_mask",
                "refine_failed"):
        np.testing.assert_array_equal(info_on[key], info_off[key])
    assert info_on["n_refine"] == info_off["n_refine"]


# --------------------------------------------------------------------------
# the documented timing keys
# --------------------------------------------------------------------------
def test_batched_timing_keys_are_filled():
    an, bst, _, info = _ill_step()
    assert set(an.timings) >= {"matching", "ordering", "symbolic", "plan",
                               "total"}
    assert an.timings["total"] == pytest.approx(
        sum(v for k, v in an.timings.items() if k != "total"))
    assert bst.timings["factor_batched"] > 0
    assert 0 < info["fallback_time"] <= info["solve_time"]


def test_scalar_and_pipeline_timing_keys_are_filled():
    a, b, _ = routing_system("circuit")
    an = analyze(a, HyluOptions(engine="ref"))
    st = factor(an, a)
    _, info = solve(st, b)
    assert st.timings["factor"] > 0 and st.timings["solve_plan"] > 0
    assert info["solve_time"] > 0
    vb = np.stack([a.data * s for s in (1.0, 1.1)])
    _, info = solve_sequence(a, [vb, vb * 1.05], b,
                             HyluOptions(engine="jax"))
    assert info["solve_time"] == info["timings"]["pipeline"] > 0


def test_plan_cache_and_service_stats_are_filled(tmp_path):
    a, b, _ = routing_system("circuit")
    opts = HyluOptions(engine="jax")
    cache = PlanCache(directory=str(tmp_path))
    cache.get_or_analyze(a, opts)
    assert cache.stats["analyze_s"] > 0 and cache.stats["load_s"] == 0
    fresh = PlanCache(directory=str(tmp_path))
    an = fresh.get_or_analyze(a, opts)
    assert fresh.stats["disk_hits"] == 1 and fresh.stats["load_s"] > 0
    assert an.timings["load"] == an.timings["total"] > 0
    assert an.timings["analyzed_total"] > 0
    svc = SolverService(opts, batch_size=2, cache=fresh)
    res = svc.solve_batch([(a, b), (a, 2 * b)])
    assert all(r.status == "solved" for r in res)
    assert svc.stats["solve_s"] > 0


def test_span_adds_its_seconds_only_when_it_ends_cleanly():
    acc = {"x": 1.0}
    with span("unit", into=acc, key="x"):
        pass
    assert acc["x"] > 1.0
    with span("unit", into=acc):
        pass
    assert acc["unit"] > 0
    before = dict(acc)
    with pytest.raises(RuntimeError):
        with span("unit", into=acc):
            raise RuntimeError("no time recorded")
    assert acc == before


def test_service_span_holds_the_batched_calls(tmp_path):
    """Spans opened by the service land on the same trace as the solver's:
    ``hylu.service.solve_batch`` holds the batched calls it dispatches."""
    a, b, _ = routing_system("circuit")
    svc = SolverService(HyluOptions(engine="jax"), batch_size=2,
                        cache=PlanCache(directory=None))
    _, spans = _traced(lambda: svc.solve_batch([(a, b), (a, 2 * b)]),
                       tmp_path)
    assert _inside(spans, "hylu.factor_batched", "hylu.service.solve_batch")
    assert _inside(spans, "hylu.solve_batched", "hylu.service.solve_batch")
    assert _inside(spans, "hylu.plan_cache.analyze",
                   "hylu.service.solve_batch")
