"""Repeated-solve benchmark (paper Fig 8 scenario + the batched JAX engine).

One analysis, K refactorizations (+ solves) of the same sparsity pattern
with drifting values — the circuit-simulation workload HYLU's headline
2.90× repeated-factorization speedup comes from.  Three engines:

  looped-ref   K × ref_engine.factor in a Python loop (numpy reference)
  jitted-jax   K × pre-compiled XLA refactor calls (engine="jax")
  batched-jax  one vmapped XLA program for all K (factor_batched)

plus a solve-phase section comparing the fused on-device batched solve
(substitution + CSR residual matvec + the whole refinement loop as ONE
XLA program, `solve_batched`) against the pre-fusion host-loop baseline
(`api._solve_batched_hostloop`: one host round-trip per refinement
iteration).

Compile time is reported first-class: compile_scalar_s / compile_batched_s
per matrix plus their geomeans in the summary, and a compile-vs-run table
(also written next to the JSON) — the level-bucketed factor trace lives or
dies by this number.  ``--large`` adds the circuit_2000-scale matrices
that only compile at all with the bucketed trace.  The persistent JAX
compilation cache lives where ``repro.launch.compile_cache`` says
(``$JAX_COMPILATION_CACHE_DIR``, else ``<repo>/.jax_cache``);
``--no-jax-cache`` turns it off — recorded compile numbers are only
*cold* numbers with the cache off or empty.

The ``analyze`` section records the host preprocessing phase per matrix
(matching/ordering/symbolic/plan breakdown) plus plan-cache cold vs warm
timings (in-memory hit and disk-artifact load — what a fresh process pays
instead of re-analyzing), and the ``serving`` section measures an
interleaved circuit/banded/unsym mixed-pattern request stream through
``SolverService`` (cold analyze+compile vs warm cache hits, req/s).

``--devices N`` adds the multi-device sweep: the batched refactor+solve
on a 1-D solver mesh over 1, 2, …, N devices (``HyluOptions(mesh=d)``),
recorded as the ``devices_sweep`` section — batched refactor throughput
(systems/s) vs device count.  On a TPU host these are its chips; on the
CPU platform ``launch.mesh.ensure_devices`` forces N virtual devices
before jax initializes, so ``--devices`` is handled by this process from
the start.

Writes BENCH_repeated.json (per-matrix timings + geomean speedups over
looped-ref) so successive PRs have a perf trajectory.

    PYTHONPATH=src python -m benchmarks.bench_factor_repeated \
        [--k 32] [--quick] [--large] [--no-jax-cache] [--devices N]
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import jax
import numpy as np

jax.config.update("jax_enable_x64", True)

from repro.core import CSR, analyze, factor, refactor, solve
from repro.core.api import (factor_batched, solve_batched,
                            _solve_batched_hostloop, jax_repeated_engine)
from repro.core.ref_engine import factor_value_loop

from . import matrices


def _geomean(xs):
    xs = [x for x in xs if x and np.isfinite(x) and x > 0]
    return float(np.exp(np.mean(np.log(xs)))) if xs else float("nan")


def _value_drift(data, k, rng):
    """K value sets with the mild drift of Newton/transient sequences."""
    return data[None, :] * rng.uniform(0.9, 1.1, (k, len(data)))


def bench_matrix(name, Ac, k):
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    an = analyze(Ac)
    vb = _value_drift(Ac.data, k, rng)
    bb = rng.normal(size=(k, Ac.n))
    mats = [CSR(Ac.n, Ac.indptr, Ac.indices, vb[i]) for i in range(k)]
    rec = dict(n=Ac.n, nnz=Ac.nnz, mode=an.choice.mode, k=k)

    # ---- looped-ref: numeric refactorization only, then end-to-end --------
    mb = vb[:, an.src_map] * an.scale_map
    t0 = time.perf_counter()
    factor_value_loop(an.plan, an.m_pattern, mb,
                      perturb_eps=an.opts.perturb_eps)
    rec["refac_ref_loop_s"] = time.perf_counter() - t0

    st = factor(an, Ac, engine="ref")
    t0 = time.perf_counter()
    for i in range(k):
        st_i = refactor(st, mats[i])
        solve(st_i, bb[i])
    rec["end2end_ref_loop_s"] = time.perf_counter() - t0

    # ---- jitted-jax: compile once, K scalar pre-compiled calls ------------
    eng = jax_repeated_engine(an)
    t0 = time.perf_counter()
    st_j = factor(an, Ac, engine="jax")          # triggers refactor compile
    solve(st_j, bb[0])                           # triggers apply compile
    rec["compile_scalar_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    for i in range(k):
        jf = eng.refactor(jnp.asarray(vb[i]))
    jax.block_until_ready(jf.vals)
    rec["refac_jax_jit_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    for i in range(k):
        st_i = refactor(st_j, mats[i])
        solve(st_i, bb[i])
    rec["end2end_jax_jit_s"] = time.perf_counter() - t0

    # ---- batched-jax: one vmapped XLA program for all K -------------------
    t0 = time.perf_counter()
    bst = factor_batched(an, Ac, vb)             # includes vmap compile
    x, info = solve_batched(bst, bb)
    rec["compile_batched_s"] = time.perf_counter() - t0
    assert float(info["residual"].max()) < 1e-8, (name, info["residual"].max())

    t0 = time.perf_counter()
    bst = factor_batched(an, Ac, vb)
    rec["refac_jax_batched_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    bst = factor_batched(an, Ac, vb)
    x, info = solve_batched(bst, bb)
    rec["end2end_jax_batched_s"] = time.perf_counter() - t0

    # ---- solve phase: fused on-device refinement vs the host-loop baseline
    # (device substitution + numpy residual matvec + Python refine loop).
    # best-of-N timing: these are millisecond-scale calls on a shared
    # machine, where a mean is dominated by scheduler noise ----------------
    reps = 10

    def _best(fn):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return min(ts)

    _solve_batched_hostloop(bst, bb)             # warm the scalar apply path
    rec["solve_hostloop_s"] = _best(lambda: _solve_batched_hostloop(bst, bb))
    x, info = solve_batched(bst, bb)             # fused program is compiled
    rec["solve_fused_s"] = _best(lambda: solve_batched(bst, bb))
    rec["solve_n_refine"] = int(info["n_refine"])
    rec["speedup_solve_fused"] = (rec["solve_hostloop_s"]
                                  / rec["solve_fused_s"])
    # the fused on-device solve must not lose to the host loop even when
    # refinement doesn't iterate (0.9: timing-jitter allowance).  Guarded
    # on the core suite at production batch sizes only: below K≈16 the
    # lax.while_loop's fixed ~0.3 ms overhead dominates sub-ms solves, and
    # the --large matrices' multi-hundred-ms solves swing tens of percent
    # with machine load (informational there).  Recorded per matrix and
    # raised only after the whole suite is written out, so one noisy
    # sample can't discard the run's results.
    rec["solve_fused_ok"] = (k < 16 or Ac.n > 1000
                             or rec["speedup_solve_fused"] >= 0.9)

    # refinement-engaged: tol=0 forces the loop to iterate until it stalls,
    # so the per-iteration host round-trip of the baseline is actually on
    # the clock (tol is a dynamic arg — no recompile)
    tol_saved = an.opts.refine_tol
    an.opts.refine_tol = 0.0
    try:
        _solve_batched_hostloop(bst, bb, refine=True)
        rec["solve_refined_hostloop_s"] = _best(
            lambda: _solve_batched_hostloop(bst, bb, refine=True))
        _, info_f = solve_batched(bst, bb, refine=True)
        rec["solve_refined_fused_s"] = _best(
            lambda: solve_batched(bst, bb, refine=True))
        rec["solve_refined_n_iter"] = int(info_f["n_refine"])
        rec["speedup_solve_refined_fused"] = (
            rec["solve_refined_hostloop_s"] / rec["solve_refined_fused_s"])
    finally:
        an.opts.refine_tol = tol_saved

    for which in ("jax_jit", "jax_batched"):
        rec[f"speedup_refac_{which}"] = (rec["refac_ref_loop_s"]
                                         / rec[f"refac_{which}_s"])
        rec[f"speedup_end2end_{which}"] = (rec["end2end_ref_loop_s"]
                                           / rec[f"end2end_{which}_s"])
    return rec


def bench_analyze_matrix(name, Ac, cache_root=None):
    """Analyze-phase benchmark for one matrix: the host preprocessing
    breakdown (matching / ordering / symbolic / plan) plus plan-cache
    timings — cold (analyze + persist), warm in-memory hit, and warm disk
    hit from a fresh cache over the same ``checkpoints/``-style artifact
    store (a fresh process pays only this load instead of the analyze).

    cache_root: directory for the throwaway artifact store; None creates
    (and removes) a fresh temp dir."""
    import shutil

    from repro.core import HyluOptions
    from repro.core.plan_cache import PlanCache

    own_root = cache_root is None
    if own_root:
        cache_root = tempfile.mkdtemp(prefix="hylu_bench_plan_cache_")
    d = os.path.join(cache_root, name)
    shutil.rmtree(d, ignore_errors=True)
    opts = HyluOptions()
    cache = PlanCache(directory=d)

    t0 = time.perf_counter()
    an = cache.get_or_analyze(Ac, opts)          # cold: analyze + save
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    cache.get_or_analyze(Ac, opts)               # warm: in-memory hit
    warm_mem_s = time.perf_counter() - t0
    fresh = PlanCache(directory=d)
    t0 = time.perf_counter()
    an2 = fresh.get_or_analyze(Ac, opts)         # warm: disk artifact load
    warm_disk_s = time.perf_counter() - t0
    assert fresh.stats["disk_hits"] == 1 and fresh.stats["analyze_calls"] == 0
    rec = dict(
        n=Ac.n, nnz=Ac.nnz, mode=an.choice.mode,
        analyze_s=dict(
            matching=an.timings["matching"], ordering=an.timings["ordering"],
            symbolic=an.timings["symbolic"], plan=an.timings["plan"],
            total=an.timings["total"]),
        plan_cache=dict(cold_s=cold_s, warm_mem_s=warm_mem_s,
                        warm_disk_s=warm_disk_s,
                        artifact_bytes=os.path.getsize(fresh.path_for(
                            an2.fingerprint)),
                        speedup_warm_disk=an.timings["total"] / warm_disk_s),
    )
    print(f"[analyze]  {name:14s} n={Ac.n:5d} "
          f"analyze={an.timings['total']*1e3:7.1f}ms "
          f"(match={an.timings['matching']*1e3:6.1f} "
          f"order={an.timings['ordering']*1e3:6.1f} "
          f"sym={an.timings['symbolic']*1e3:6.1f} "
          f"plan={an.timings['plan']*1e3:6.1f}) "
          f"cache cold={cold_s*1e3:7.1f}ms mem={warm_mem_s*1e6:5.0f}us "
          f"disk={warm_disk_s*1e3:6.1f}ms "
          f"({rec['plan_cache']['speedup_warm_disk']:.1f}x vs analyze)",
          flush=True)
    if own_root:
        shutil.rmtree(cache_root, ignore_errors=True)
    return rec


def bench_serving(k_per_pattern=8, reps=2, batch_size=8, cache_root=None):
    """Mixed-pattern serving throughput: an interleaved stream of circuit /
    banded / unsym requests (distinct sparsity patterns, per-request value
    drift) through ``SolverService`` — cold (analyze + compile on first
    touch of each pattern) vs warm (every plan and engine cached).

    cache_root: directory to put the run's throwaway plan-cache store
    under; None creates (and owns) a fresh temp dir."""
    import shutil

    from repro.serve.solver_service import SolverService, SolveRequest

    own_root = cache_root is None
    if own_root:
        cache_root = tempfile.mkdtemp(prefix="hylu_bench_serving_")
    d = os.path.join(cache_root, "serving")
    shutil.rmtree(d, ignore_errors=True)
    pats = [("circuit", CSR.from_scipy(matrices.circuit_like(200, 1)
                                       .tocsr())),
            ("banded", CSR.from_scipy(matrices.banded(150, 6, 2).tocsr())),
            ("unsym", CSR.from_scipy(matrices.unsym_random(120, 0.02, 8)
                                     .tocsr()))]

    def stream(seed):
        rng = np.random.default_rng(seed)
        reqs = []
        for rep in range(reps):
            for _ in range(k_per_pattern):
                for name, Ac in pats:
                    reqs.append(SolveRequest(
                        a=CSR(Ac.n, Ac.indptr, Ac.indices,
                              Ac.data * rng.uniform(0.9, 1.1, Ac.nnz)),
                        b=rng.normal(size=Ac.n), tag=name))
        rng.shuffle(reqs)                       # genuinely interleaved
        return reqs

    svc = SolverService(cache_dir=d, batch_size=batch_size)
    reqs = stream(1)
    t0 = time.perf_counter()
    res = svc.solve_batch(reqs)
    cold_s = time.perf_counter() - t0
    worst = max(float(np.max(r.residual)) for r in res)
    assert worst < 1e-8, worst
    reqs2 = stream(2)
    t0 = time.perf_counter()
    svc.solve_batch(reqs2)
    warm_s = time.perf_counter() - t0
    rec = dict(
        n_requests=len(reqs), n_patterns=len(pats),
        batch_size=batch_size,
        patterns={name: dict(n=Ac.n, nnz=Ac.nnz) for name, Ac in pats},
        modes=sorted(svc.pattern_modes.values()),
        cold_s=cold_s, warm_s=warm_s,
        cold_req_per_s=len(reqs) / cold_s,
        warm_req_per_s=len(reqs2) / warm_s,
        worst_residual=worst,
        dispatches=svc.stats["dispatches"],
        padded_systems=svc.stats["padded_systems"],
        plan_cache=dict(svc.cache.stats),
    )
    print(f"[serving]  {len(reqs)} mixed requests over {len(pats)} patterns "
          f"(batch={batch_size}): cold={cold_s:5.1f}s "
          f"({rec['cold_req_per_s']:6.1f} req/s) "
          f"warm={warm_s:5.2f}s ({rec['warm_req_per_s']:7.1f} req/s) "
          f"worst_resid={worst:.1e}", flush=True)
    if own_root:
        shutil.rmtree(cache_root, ignore_errors=True)
    return rec


def bench_serving_async(n_requests=520, batch_size=8, fault_rate=0.15,
                        n=32, seed=0, deadline_ms=200.0,
                        max_linger_ms=20.0):
    """The ``serving_async`` section: the continuous-batching async server
    (AsyncSolverServer) under a >=500-request mixed-pattern load-generator
    stream laced with the full fault matrix (``serve.faultinject``).
    Records steady-state throughput (req/s), p50/p99 latency,
    deadline-miss / reject / quarantine rates, and the robustness contract
    (zero lost requests, healthy traffic at fp64-oracle parity) — the
    serving tier's perf trajectory."""
    import asyncio

    from repro.serve.solver_service import SolverService
    from repro.serve.async_server import AsyncSolverServer
    from repro.serve import faultinject

    async def _run():
        service = SolverService(cache_dir=None, batch_size=batch_size)
        server = AsyncSolverServer(
            service,
            max_queue_per_group=n_requests,   # load generator submits the
            max_pending=n_requests + 8,       # whole stream up front —
            #                                   backpressure rejects would
            #                                   pollute the throughput number
            max_linger_ms=max_linger_ms,
            default_deadline_ms=deadline_ms)
        async with server:
            stream = faultinject.make_stream(
                n_requests, fault_rate=fault_rate, seed=seed, n=n)
            # warm analyze + engine compile outside the timed window (one
            # healthy request per (pattern, RHS-shape) group), so req/s and
            # the percentiles are steady-state serving numbers
            seen = set()
            for item in stream:
                if item.kind is not None:
                    continue
                key = (id(item.a.indptr), item.b.shape[1:])
                if key not in seen:
                    seen.add(key)
                    await server.solve(item.a, item.b, tag=("warmup",))
            server._latencies_ms.clear()
            t0 = time.perf_counter()
            report = await faultinject.run_stream(server, stream,
                                                  warmup=False)
            report["wall_s"] = time.perf_counter() - t0
        return report

    report = asyncio.run(_run())
    violations = faultinject.check_report(report)
    s = report["server_stats"]
    rec = dict(
        n_requests=n_requests, batch_size=batch_size, fault_rate=fault_rate,
        deadline_ms=deadline_ms, wall_s=report["wall_s"],
        req_per_s=n_requests / report["wall_s"],
        p50_ms=s["p50_ms"], p99_ms=s["p99_ms"],
        deadline_miss_rate=s["deadline_miss_rate"],
        reject_rate=s["reject_rate"],
        retries=s["retries"], quarantined=s["quarantined"],
        failed=s["failed"], dispatch_batches=s["dispatch_batches"],
        statuses=report["by_status"],
        lost=report["lost"], zero_lost=report["lost"] == 0,
        worst_healthy_err=report["worst_healthy_err"],
        n_healthy_checked=report["n_healthy_checked"],
        n_violations=len(violations),
    )
    print(f"[serving-async] {n_requests} requests "
          f"(fault_rate={fault_rate:.2f}, batch={batch_size}): "
          f"{rec['req_per_s']:7.1f} req/s "
          f"p50={rec['p50_ms']:.1f}ms p99={rec['p99_ms']:.1f}ms "
          f"miss={rec['deadline_miss_rate']:.3f} "
          f"reject={rec['reject_rate']:.3f} "
          f"quarantined={rec['quarantined']} "
          f"healthy_err={rec['worst_healthy_err']:.1e} "
          f"lost={rec['lost']}", flush=True)
    if violations:
        raise AssertionError(
            f"serving-async robustness contract violated "
            f"({len(violations)}): " + "; ".join(violations[:5]))
    return rec


def _peak_rss_mb() -> float:
    """Process high-water resident set in MB (linux ru_maxrss is KB).
    Monotone — per-phase snapshots record the watermark *after* each
    phase, so ``phase_peaks[p]`` is "the largest the process ever got up
    to and including p", and the increments attribute growth to phases."""
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def bench_corpus_entry(entry, k=4, amalg_fill_tol=0.2, cache_root=None):
    """One corpus matrix through the scale lane: analyze (amalgamation on)
    + bucketed-schedule build + batched-engine compile + steady-state
    batched refactor + fused solve, recording runtime, the peak-RSS
    watermark after every phase, the plan's deterministic byte accounting
    (``memory_stats``) and the pad-waste / bulk-coverage numbers that
    drive sub-bucket tuning.  ``analyze_only`` entries stop after the
    schedule build (past the XLA compile budget)."""
    import jax
    import jax.numpy as jnp

    from repro.core import HyluOptions
    from repro.core.plan import plan_stats

    try:
        from . import corpus as corpus_mod
    except ImportError:
        import corpus as corpus_mod

    peaks = {"start": _peak_rss_mb()}
    t0 = time.perf_counter()
    Ac, _, meta = corpus_mod.load_entry(entry, root=cache_root)
    load_s = time.perf_counter() - t0
    peaks["load"] = _peak_rss_mb()

    opts = HyluOptions(amalg_fill_tol=amalg_fill_tol,
                       orderings=("natural", "min_degree"))
    t0 = time.perf_counter()
    an = analyze(Ac, opts)
    analyze_s = time.perf_counter() - t0
    peaks["analyze"] = _peak_rss_mb()

    t0 = time.perf_counter()
    ps = plan_stats(an.plan, bulk_min_width=opts.bulk_min_width)
    schedule_s = time.perf_counter() - t0
    peaks["schedule"] = _peak_rss_mb()

    amalg = an.choice.stats.get("amalg", {})
    rec = dict(
        meta=meta, k=k, mode=an.choice.mode, ordering=an.ordering_name,
        amalg_fill_tol=amalg_fill_tol, amalg=amalg,
        load_s=load_s, analyze_s=analyze_s, schedule_s=schedule_s,
        analyze_timings={name: round(v, 4)
                         for name, v in an.timings.items()},
        plan=dict(n_nodes=ps["n_nodes"], n_levels=ps["n_levels"],
                  n_scanned_levels=ps.get("n_scanned_levels"),
                  total_slots=ps["total_slots"],
                  pad_waste_frac=ps.get("pad_waste_frac"),
                  bulk_node_coverage=ps.get("bulk_node_coverage"),
                  mean_panel_width=ps["mean_panel_width"]),
        memory_bytes={f: ps[f] for f in
                      ("panel_bytes", "workspace_bytes",
                       "schedule_index_bytes", "batched_bytes",
                       "total_bytes") if f in ps},
    )
    if entry.analyze_only:
        rec["peak_rss_mb"] = {p: round(v, 1) for p, v in peaks.items()}
        print(f"[large] {entry.name:14s} n={meta['n']:6d} "
              f"({meta['source']}) analyze={analyze_s:6.1f}s "
              f"schedule={schedule_s:5.1f}s nodes={ps['n_nodes']} "
              f"levels={ps['n_levels']} ANALYZE-ONLY "
              f"peakRSS={peaks['schedule']:.0f}MB", flush=True)
        return rec

    rng = np.random.default_rng(0)
    vb = _value_drift(Ac.data, k, rng)
    bb = rng.normal(size=(k, Ac.n))
    t0 = time.perf_counter()
    bst = factor_batched(an, Ac, vb)              # batched refactor compile
    x, info = solve_batched(bst, bb)              # fused solve compile
    compile_s = time.perf_counter() - t0
    peaks["compile"] = _peak_rss_mb()
    worst = float(np.max(info["residual"]))
    assert worst < 1e-8, (entry.name, worst)

    t0 = time.perf_counter()
    bst = factor_batched(an, Ac, vb)              # steady-state refactor
    refac_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    x, info = solve_batched(bst, bb)
    solve_s = time.perf_counter() - t0
    peaks["run"] = _peak_rss_mb()

    eng = jax_repeated_engine(an)
    rec.update(
        compile_s=compile_s, refac_batched_s=refac_s, solve_fused_s=solve_s,
        refac_systems_per_s=k / refac_s, worst_residual=worst,
        engine_memory_bytes=eng.memory_stats(k=k),
        peak_rss_mb={p: round(v, 1) for p, v in peaks.items()},
    )
    print(f"[large] {entry.name:14s} n={meta['n']:6d} ({meta['source']}) "
          f"analyze={analyze_s:6.1f}s compile={compile_s:6.1f}s "
          f"refac={refac_s:6.2f}s solve={solve_s:5.2f}s "
          f"padwaste={ps.get('pad_waste_frac', 0):.2f} "
          f"amalg {amalg.get('n_nodes_before', ps['n_nodes'])}->"
          f"{amalg.get('n_nodes_after', ps['n_nodes'])} "
          f"peakRSS={peaks['run']:.0f}MB resid={worst:.1e}", flush=True)
    return rec


def bench_corpus(k=4, smoke=False, amalg_fill_tol=0.2, cache_root=None):
    """The ``--large`` scale lane: the SuiteSparse-class corpus
    (real matrices when reachable, statistics-matched synthetic stand-ins
    offline) end-to-end with amalgamation on.  ``smoke`` restricts to the
    CI subset (one circuit-class + one FEM-class matrix at n>=10^4)."""
    try:
        from . import corpus as corpus_mod
    except ImportError:
        import corpus as corpus_mod

    entries = (corpus_mod.smoke_corpus() if smoke else corpus_mod.corpus())
    recs = {}
    for entry in entries:
        recs[entry.name] = bench_corpus_entry(
            entry, k=k, amalg_fill_tol=amalg_fill_tol, cache_root=cache_root)
    full = [r for r in recs.values() if "refac_batched_s" in r]
    return dict(
        smoke=bool(smoke), amalg_fill_tol=amalg_fill_tol,
        matrices=recs,
        geomean=dict(
            analyze_s=_geomean([r["analyze_s"] for r in recs.values()]),
            compile_s=_geomean([r["compile_s"] for r in full]),
            refac_batched_s=_geomean([r["refac_batched_s"] for r in full]),
            pad_waste_frac=_geomean(
                [r["plan"]["pad_waste_frac"] for r in recs.values()
                 if r["plan"].get("pad_waste_frac")]),
        ),
        peak_rss_mb=max((r["peak_rss_mb"].get("run",
                                              r["peak_rss_mb"]["schedule"])
                         for r in recs.values()), default=0.0),
    )


def bench_mixed_precision_matrix(name, Ac, k, reps=10):
    """fp32-factor + fp64-refine vs pure fp64 on one matrix: steady-state
    batched refactor and fused-solve times per dtype, the plan-derived
    factor-panel bytes (the memory the reduced precision halves), how many
    refinement iterations the fp64 recovery costs, the fp64-fallback rate,
    and solution parity against the fp64 path."""
    from repro.core import HyluOptions

    rng = np.random.default_rng(0)
    vb = _value_drift(Ac.data, k, rng)
    bb = rng.normal(size=(k, Ac.n))

    def _best(fn):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return min(ts)

    rec = dict(n=Ac.n, nnz=Ac.nnz, k=k, dtypes={})
    xs = {}
    for dt in ("float64", "float32"):
        an = analyze(Ac, HyluOptions(factor_dtype=dt))
        t0 = time.perf_counter()
        bst = factor_batched(an, Ac, vb)          # batched refactor compile
        x, info = solve_batched(bst, bb)          # fused solve compile
        compile_s = time.perf_counter() - t0
        refac_s = _best(lambda: factor_batched(an, Ac, vb))
        bst = factor_batched(an, Ac, vb)
        solve_s = _best(lambda: solve_batched(bst, bb))
        x, info = solve_batched(bst, bb)
        xs[dt] = x
        eng = jax_repeated_engine(an)
        rec["dtypes"][dt] = dict(
            mode=an.choice.mode, compile_s=compile_s,
            refac_batched_s=refac_s, solve_fused_s=solve_s,
            n_refine=int(info["n_refine"]),
            n_refine_per_system_max=int(
                np.max(info["n_refine_per_system"])),
            worst_residual=float(np.max(info["residual"])),
            n_refine_failed=int(np.sum(info["refine_failed"])),
            n_fp64_fallback=int(info["n_fp64_fallback"]),
            fallback_rate=float(info["n_fp64_fallback"]) / k,
            factor_panel_bytes=eng.memory_stats(k=k)["panel_bytes"],
        )
    r64, r32 = rec["dtypes"]["float64"], rec["dtypes"]["float32"]
    scale = float(np.abs(xs["float64"]).max()) + 1e-30
    rec["x_diff_vs_fp64"] = float(
        np.abs(xs["float32"] - xs["float64"]).max()) / scale
    rec["speedup_refac_fp32"] = (r64["refac_batched_s"]
                                 / r32["refac_batched_s"])
    rec["speedup_solve_fp32"] = r64["solve_fused_s"] / r32["solve_fused_s"]
    rec["panel_bytes_ratio"] = (r32["factor_panel_bytes"]
                                / r64["factor_panel_bytes"])
    print(f"[mixed]    {name:14s} n={rec['n']:5d} "
          f"refac fp64={r64['refac_batched_s']*1e3:7.1f}ms "
          f"fp32={r32['refac_batched_s']*1e3:7.1f}ms "
          f"({rec['speedup_refac_fp32']:.2f}x) "
          f"solve {rec['speedup_solve_fp32']:.2f}x "
          f"bytes={rec['panel_bytes_ratio']:.2f} "
          f"resid={r32['worst_residual']:.1e} "
          f"fallback={r32['fallback_rate']:.2f} "
          f"xdiff={rec['x_diff_vs_fp64']:.1e}", flush=True)
    return rec


def bench_mixed_precision(k=32, quick=False):
    """The ``mixed_precision`` section: fp32-factor + fp64-refine over the
    main suite — refactor/solve speedups over pure fp64, the halved
    factor-panel bytes, fp64-quality residual parity, and the fp64-fallback
    rate (healthy suite matrices should never trip the escape hatch)."""
    recs = {}
    for name, Ac in suite(quick=quick):
        recs[name] = bench_mixed_precision_matrix(name, Ac, k)
    fp32 = [r["dtypes"]["float32"] for r in recs.values()]
    return dict(
        k=k, matrices=recs,
        geomean=dict(
            speedup_refac_fp32=_geomean(
                [r["speedup_refac_fp32"] for r in recs.values()]),
            speedup_solve_fp32=_geomean(
                [r["speedup_solve_fp32"] for r in recs.values()]),
            panel_bytes_ratio=_geomean(
                [r["panel_bytes_ratio"] for r in recs.values()]),
        ),
        worst_residual_fp32=max(r["worst_residual"] for r in fp32),
        worst_x_diff_vs_fp64=max(r["x_diff_vs_fp64"]
                                 for r in recs.values()),
        fallback_rate=float(np.mean([r["fallback_rate"] for r in fp32])),
    )


def suite(quick=False, large=False):
    if quick:
        return [("circuit_150", CSR.from_scipy(matrices.circuit_like(150, 1)
                                               .tocsr()))]
    mats = [
        ("circuit_200", CSR.from_scipy(matrices.circuit_like(200, 1).tocsr())),
        ("fem2d_12", CSR.from_scipy(matrices.fem2d(12, 12, 4).tocsr())),
        ("unsym_150", CSR.from_scipy(matrices.unsym_random(150, 0.02, 8)
                                     .tocsr())),
    ]
    if large:
        mats += [(name, CSR.from_scipy(fn().tocsr()))
                 for name, fn in matrices.large_suite()]
    return mats


def bench_devices_sweep(name, Ac, k, n_devices, reps=5):
    """Batched refactor+solve throughput vs device count: the same matrix,
    K value sets, on a 1-D solver mesh over d = 1, 2, … devices.  Every
    mesh size runs the identical per-system program (parity is tested in
    tests/test_sharding.py); this measures only throughput."""
    import jax.numpy as jnp

    from repro.core import HyluOptions
    from repro.core.api import factor_batched, solve_batched

    rng = np.random.default_rng(0)
    vb = _value_drift(Ac.data, k, rng)
    bb = rng.normal(size=(k, Ac.n))
    counts = sorted({1, n_devices} | {d for d in (2, 4, 8, 16, 32, 64)
                                      if d < n_devices})
    out = dict(matrix=name, n=Ac.n, nnz=Ac.nnz, k=k, counts={})

    def _best(fn):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return min(ts)

    vdev = jnp.asarray(vb)          # committed device buffer: staging cost
    #                                 excluded, like the single-device rows
    for d in counts:
        an = analyze(Ac, HyluOptions(mesh=d))
        t0 = time.perf_counter()
        bst = factor_batched(an, Ac, vdev)
        solve_batched(bst, bb)
        compile_s = time.perf_counter() - t0
        refac_s = _best(lambda: factor_batched(an, Ac, vdev))
        bst = factor_batched(an, Ac, vdev)
        solve_s = _best(lambda: solve_batched(bst, bb))
        rec = dict(devices=d, compile_s=compile_s,
                   refac_batched_s=refac_s, solve_fused_s=solve_s,
                   refac_systems_per_s=k / refac_s,
                   end2end_systems_per_s=k / (refac_s + solve_s))
        out["counts"][str(d)] = rec
        base = out["counts"]["1"]
        rec["speedup_refac_vs_1dev"] = (base["refac_batched_s"]
                                        / rec["refac_batched_s"])
        print(f"[devices] {name:14s} d={d:2d} "
              f"refac={refac_s*1e3:7.1f}ms "
              f"({rec['refac_systems_per_s']:8.0f} sys/s, "
              f"{rec['speedup_refac_vs_1dev']:.2f}x vs 1dev) "
              f"solve={solve_s*1e3:6.1f}ms compile={compile_s:4.1f}s",
              flush=True)
    return out


def compile_table(records) -> str:
    """Compile-vs-run table: the bucketed trace's headline numbers."""
    lines = [f"{'matrix':14s} {'n':>6s} {'compile_scalar':>15s} "
             f"{'compile_batched':>16s} {'refac_batched':>14s} "
             f"{'compile/run':>12s}"]
    for name, r in records.items():
        ratio = r["compile_batched_s"] / max(r["refac_jax_batched_s"], 1e-12)
        lines.append(f"{name:14s} {r['n']:6d} {r['compile_scalar_s']:13.2f}s "
                     f"{r['compile_batched_s']:14.2f}s "
                     f"{r['refac_jax_batched_s']*1e3:12.1f}ms "
                     f"{ratio:11.0f}x")
    return "\n".join(lines)


def bench_repeated(k=32, quick=False, large=False,
                   out_path="BENCH_repeated.json", jax_cache=None,
                   jax_cache_warm=False, devices=None, serving=True,
                   large_smoke=False, large_only=False, large_k=4,
                   amalg_tol=0.2, mixed_only=False,
                   serving_async_only=False):
    if serving_async_only:
        # the CI serving-chaos lane: just the async-server load-generator
        # section.  Merge into an existing results file instead of
        # clobbering the other sections, so the committed trajectory keeps
        # its full shape when only this lane reruns.
        out = {}
        if os.path.exists(out_path):
            try:
                with open(out_path) as f:
                    out = json.load(f)
            except (OSError, ValueError):
                out = {}
        out["serving_async"] = bench_serving_async(
            n_requests=80 if quick else 520,
            fault_rate=0.15)
        with open(out_path, "w") as f:
            json.dump(out, f, indent=1)
        print(f"results → {out_path}")
        return out
    if mixed_only:
        # the CI mixed-precision smoke: just the fp32-vs-fp64 section
        out = dict(k=k, jax_compilation_cache=jax_cache or None,
                   jax_cache_warm=bool(jax_cache_warm),
                   mixed_precision=bench_mixed_precision(k=k, quick=quick))
        with open(out_path, "w") as f:
            json.dump(out, f, indent=1)
        print(f"results → {out_path}")
        return out
    if large_only:
        # the CI scale lane: just the corpus section, skipping the main
        # suite entirely (the scale job budget is the corpus' budget)
        out = dict(k=k, jax_compilation_cache=jax_cache or None,
                   jax_cache_warm=bool(jax_cache_warm),
                   large=bench_corpus(k=large_k, smoke=large_smoke,
                                      amalg_fill_tol=amalg_tol))
        with open(out_path, "w") as f:
            json.dump(out, f, indent=1)
        print(f"results → {out_path}")
        return out
    records = {}
    analyze_records = {}
    mats = suite(quick=quick, large=large)
    for name, Ac in mats:
        analyze_records[name] = bench_analyze_matrix(name, Ac)
        t0 = time.time()
        records[name] = bench_matrix(name, Ac, k)
        r = records[name]
        print(f"[repeated] {name:14s} n={r['n']:5d} mode={r['mode']:8s} "
              f"compile={r['compile_scalar_s']:5.1f}/"
              f"{r['compile_batched_s']:5.1f}s "
              f"refac ref={r['refac_ref_loop_s']*1e3:7.1f}ms "
              f"jit={r['refac_jax_jit_s']*1e3:7.1f}ms "
              f"batched={r['refac_jax_batched_s']*1e3:7.1f}ms "
              f"({r['speedup_refac_jax_batched']:.1f}x) "
              f"solve host={r['solve_hostloop_s']*1e3:6.1f}ms "
              f"fused={r['solve_fused_s']*1e3:6.1f}ms "
              f"({r['speedup_solve_fused']:.1f}x; refined "
              f"{r['speedup_solve_refined_fused']:.1f}x) "
              f"[{time.time()-t0:.0f}s]", flush=True)

    summary = {
        "refactor_jit": _geomean(
            [r["speedup_refac_jax_jit"] for r in records.values()]),
        "refactor_batched": _geomean(
            [r["speedup_refac_jax_batched"] for r in records.values()]),
        "end2end_jit": _geomean(
            [r["speedup_end2end_jax_jit"] for r in records.values()]),
        "end2end_batched": _geomean(
            [r["speedup_end2end_jax_batched"] for r in records.values()]),
        "solve_fused": _geomean(
            [r["speedup_solve_fused"] for r in records.values()]),
        "solve_refined_fused": _geomean(
            [r["speedup_solve_refined_fused"] for r in records.values()]),
        # absolute one-time costs (seconds), tracked so trace-size blowups
        # show up in the perf trajectory as hard numbers
        "compile_scalar_s": _geomean(
            [r["compile_scalar_s"] for r in records.values()]),
        "compile_batched_s": _geomean(
            [r["compile_batched_s"] for r in records.values()]),
    }
    # label whether compile numbers could have hit a warm persistent cache
    # — only cold (jax_cache disabled/fresh) numbers are trajectory-grade
    out = dict(k=k, jax_compilation_cache=jax_cache or None,
               jax_cache_warm=bool(jax_cache_warm),
               matrices=records, geomean_speedup_over_ref_loop=summary,
               analyze=analyze_records)
    # mixed precision: fp32-factor + fp64-refine vs pure fp64 (refactor
    # speedup, halved factor-panel bytes, fallback rate)
    out["mixed_precision"] = bench_mixed_precision(k=k, quick=quick)
    if serving:
        # mixed-pattern serving throughput (smaller request volume on
        # --quick so the CI bench job still records the section)
        out["serving"] = bench_serving(
            k_per_pattern=2 if quick else 8, reps=1 if quick else 2)
        # async continuous-batching server under the fault-injection load
        # generator (>=500-request stream on full runs)
        out["serving_async"] = bench_serving_async(
            n_requests=80 if quick else 520, fault_rate=0.15)
    if devices and devices > 1:
        # multi-device sweep on the first suite matrix (throughput vs
        # device count; bit-exact parity is the test suite's job)
        name0, Ac0 = mats[0]
        out["devices_sweep"] = bench_devices_sweep(name0, Ac0, k, devices)
    if large:
        # the scale trajectory: the SuiteSparse-class corpus at n>=10^4
        # with amalgamation on — runtime + peak-memory + pad-waste per
        # matrix, so scale regressions surface like speed regressions
        out["large"] = bench_corpus(k=large_k, smoke=large_smoke,
                                    amalg_fill_tol=amalg_tol)
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    table = compile_table(records)
    table_path = out_path.rsplit(".", 1)[0] + "_compile_table.txt"
    with open(table_path, "w") as f:
        f.write(table + "\n")
    print("\ncompile-vs-run (one-time cost amortized over the sequence):")
    print(table)
    print(f"\ngeomean speedups over looped-ref (K={k}): "
          + "  ".join(f"{n}={v:.2f}{'' if n.endswith('_s') else 'x'}"
                      for n, v in summary.items()))
    print(f"results → {out_path}  compile table → {table_path}")
    bad = [name for name, r in records.items() if not r["solve_fused_ok"]]
    if bad:
        raise AssertionError(
            "no-refine fused solve slower than host loop on: "
            + ", ".join(bad) + " (results were still written)")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--k", type=int, default=32)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--large", action="store_true",
                    help="add the circuit_2000-scale matrices AND run the "
                         "SuiteSparse-class corpus lane (the `large` "
                         "section: runtime + peak memory + pad waste at "
                         "n>=10^4, amalgamation on)")
    ap.add_argument("--large-smoke", action="store_true",
                    help="restrict the corpus lane to the CI scale-smoke "
                         "subset (one circuit-class + one FEM-class "
                         "matrix at n>=10^4)")
    ap.add_argument("--large-only", action="store_true",
                    help="run ONLY the corpus lane (the CI scale job), "
                         "skipping the main repeated-solve suite")
    ap.add_argument("--large-k", type=int, default=4,
                    help="system-batch size for the corpus lane's batched "
                         "refactor (smaller than --k: n>=10^4 systems)")
    ap.add_argument("--serving-async", action="store_true",
                    help="run ONLY the serving_async section (the CI "
                         "serving-chaos lane): the async continuous-"
                         "batching server under the fault-injection load "
                         "generator — req/s, p50/p99 latency, deadline-"
                         "miss and reject rates, merged into the "
                         "serving_async section of the results JSON")
    ap.add_argument("--mixed-only", action="store_true",
                    help="run ONLY the mixed_precision section (the CI "
                         "mixed-precision smoke): fp32-factor+fp64-refine "
                         "vs fp64 refactor/solve times, factor-panel "
                         "bytes, residual parity and fp64-fallback rate")
    ap.add_argument("--amalg-tol", type=float, default=0.2,
                    help="amalgamation fill tolerance for the corpus lane "
                         "(HyluOptions.amalg_fill_tol)")
    ap.add_argument("--out", default="BENCH_repeated.json")
    ap.add_argument("--no-jax-cache", action="store_true",
                    help="turn the persistent JAX compilation cache off "
                         "(cold compiles; the cache otherwise lives at "
                         "$JAX_COMPILATION_CACHE_DIR or <repo>/.jax_cache)")
    ap.add_argument("--devices", type=int, default=None, metavar="N",
                    help="also sweep the sharded batched path over "
                         "1..N devices (a TPU host's chips, else virtual "
                         "CPU devices) -> devices_sweep section of the "
                         "JSON")
    ap.add_argument("--no-serving", action="store_true",
                    help="skip the mixed-pattern SolverService section")
    args = ap.parse_args(argv)

    if args.devices and args.devices > 1:
        # must happen before anything touches jax devices in this process
        from repro.launch.mesh import ensure_devices
        ensure_devices(args.devices)

    from repro.launch.compile_cache import enable_compilation_cache
    cache = enable_compilation_cache(not args.no_jax_cache)
    # pre-run state: a populated cache dir means the recorded compile
    # numbers may be warm-cache hits, not trajectory-grade cold compiles
    warm = bool(cache) and os.path.isdir(cache) and bool(os.listdir(cache))
    if cache:
        print(f"[jax] persistent compilation cache at {cache} "
              f"({'warm' if warm else 'cold'})")
    bench_repeated(k=args.k, quick=args.quick, large=args.large,
                   out_path=args.out, jax_cache=cache, jax_cache_warm=warm,
                   devices=args.devices, serving=not args.no_serving,
                   large_smoke=args.large_smoke, large_only=args.large_only,
                   large_k=args.large_k, amalg_tol=args.amalg_tol,
                   mixed_only=args.mixed_only,
                   serving_async_only=args.serving_async)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
