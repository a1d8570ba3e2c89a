#!/usr/bin/env python3
"""Smoke run of the solver's main path on a TPU.

    python3 chip_smoke.py              # one chip: device, sweep, serve, pallas
    python3 chip_smoke.py --chips 4    # four chips: the sharded sweep only

The path is the one users call: ``analyze`` → ``PlanCache`` → the batched
engine (batched factor plus fused refined solve) → ``SolverService``, on
the repository's own corpus patterns, generated from seeds:

  device  the first JAX device must be a TPU; there is no CPU fallback.
  sweep   circuit_10k, K=1024 seeded value sets × T=3 steps through
          ``solve_sequence``; every system must meet the refinement
          tolerance, and 4 sampled systems must match a host float64
          ``scipy.sparse.linalg.splu`` solve.
  serve   ``SolverService(cache_dir=None, batch_size=8)`` answers 16
          seeded requests over circuit_10k and fem2d_4900; every request
          must come back ``solved`` and match host ``splu``.
  pallas  fem2d_4900's serve batch factored and solved again with
          ``use_pallas=True``; its factor and fused-solve programs must
          hold compiled kernels (``tpu_custom_call``) and its answers must
          match the serve phase's.
  sharded (``--chips 4`` only) circuit_10k, 4×256 systems × 2 steps,
          through ``solve_sequence`` with ``HyluOptions(mesh=4,
          donate=True)``, and the same batch on one device with
          ``mesh=None``; the results must be equal bit for bit.

fem2d_4900 is fem2d_10k's generator and seed cut to a 70×70 grid, which
still routes ``hybrid`` (panel and edge buckets, the Pallas kernels).  It
is the only FEM grid whose fused solve is known to finish on a TPU v5e:
fem2d_10k's did not, when its head levels were unrolled, and the scanned
solve that replaced it has not run at that size.  By compile times alone
fem2d_10k would fit the script's 1200 s (about 930 s estimated).

Every phase factors in float32 and refines in float64: XLA:TPU compiles
circuit_10k's float64 factor, but not fem2d_10k's hybrid one within this
script's time budget, so the float32/float64 pair is the one that runs
every phase.  The pair's tolerance is ``resolve_refine_tol`` (1e-12).

A solution matches its reference when the relative 1-norm difference is
at most 10·κ₁(A)·tol: the forward-error bound that the residual tolerance
implies, with κ₁ estimated on the host (``onenormest`` over ``splu``).

Lines before the last are smoke figures of this one run, not metrics.
The last line is one JSON object naming the device.  The script exits
nonzero and prints no such line when JAX finds no TPU, when the
repository is not beside this file, or when any phase fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 20261016
K_SWEEP = 1024            # systems per chip in the sweep
T_STEPS = 3
K_SHARD = 256             # systems per chip in the --chips 4 comparison
T_SHARD = 2
N_SAMPLED = 4             # sweep systems checked against host splu
SERVE_REQUESTS = 16
BATCH = 8
FEM_GRID = 70              # fem2d_4900: fem2d_10k's generator on 70×70
FACTOR_DTYPE = "float32"  # with refine_dtype "auto" → float64
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class SmokeFailure(Exception):
    """A phase's result broke its check."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Wall seconds JAX spends tracing, lowering and compiling, from the
    time spans of its own monitoring events (nested traces counted once),
    plus persistent-cache hits."""

    def __init__(self, jax):
        self.spans: list = []
        self.cache_hits = 0
        jax.monitoring.register_event_time_span_listener(self._on_span)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_span(self, event, start_time, end_time, **_):
        if event in COMPILE_EVENTS:
            self.spans.append((start_time, end_time))

    def seconds(self, since: int = 0) -> float:
        """Length of the union of the spans recorded from index ``since``
        (a ``len(clock.spans)`` taken earlier) on."""
        total, reach = 0.0, float("-inf")
        for start, end in sorted(self.spans[since:]):
            if end > reach:
                total += end - max(start, reach)
                reach = end
        return total

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def perturbed_values(a_sp, k: int, rng):
    """``k`` seeded value sets on ``a_sp``'s pattern that keep its
    diagonal dominance: every off-diagonal entry scaled by a factor in
    [0.5, 1.5], every diagonal entry set to its row's new off-diagonal
    1-norm plus the original row's margin."""
    import numpy as np

    a = a_sp.tocsr()
    n = a.shape[0]
    rows = np.repeat(np.arange(n), np.diff(a.indptr))
    diag = rows == a.indices
    check(int(diag.sum()) == n, "pattern lacks a full diagonal")
    starts = a.indptr[:-1]
    margin = a.data[diag] - np.add.reduceat(
        np.where(diag, 0.0, np.abs(a.data)), starts)
    v = a.data * rng.uniform(0.5, 1.5, (k, a.nnz))
    v[:, diag] = np.add.reduceat(np.where(diag, 0.0, np.abs(v)), starts,
                                 axis=1) + margin
    return v


class Oracle:
    """Host float64 reference for one value set: ``splu`` and its
    condition estimate."""

    def __init__(self, a_sp, values):
        import numpy as np
        import scipy.sparse as sp
        import scipy.sparse.linalg as spla

        a = sp.csr_matrix((values, a_sp.indices, a_sp.indptr),
                          shape=a_sp.shape).tocsc()
        self.a = a
        self.lu = spla.splu(a)
        inv = spla.LinearOperator(
            a.shape, matvec=self.lu.solve,
            rmatvec=lambda x: self.lu.solve(x, trans="T"), dtype=np.float64)
        self.cond1 = float(spla.norm(a, 1) * spla.onenormest(inv))

    def errors(self, x, b, tol):
        """(relative 1-norm error vs splu, its bound, host residual)."""
        import numpy as np

        ref = self.lu.solve(b)
        err = float(np.abs(x - ref).sum() / np.abs(ref).sum())
        resid = float(np.abs(b - self.a @ x).sum() / np.abs(b).sum())
        return err, 10.0 * self.cond1 * tol, resid


def load(name: str):
    """(CSR, scipy CSR) of circuit_10k (the corpus entry) or fem2d_4900."""
    from benchmarks import corpus, matrices
    from repro.core import CSR

    if name == "fem2d_4900":
        a_sp = matrices.fem2d(FEM_GRID, FEM_GRID, seed=930).tocsr()
        a_sp.sort_indices()
        return CSR.from_scipy(a_sp), a_sp
    entry = {e.name: e for e in corpus.corpus()}[name]
    a, a_sp, meta = corpus.load_entry(entry, allow_download=False)
    check(meta["source"] == "synthetic", f"{name}: not the seeded stand-in")
    return a, a_sp


def figures(phase: str, **kv) -> None:
    """One line of smoke figures (this run only, not metrics)."""
    body = " ".join(f"{k}={v}" for k, v in kv.items())
    print(f"[smoke figures] {phase}: {body}", flush=True)


def peak_bytes(jax) -> int | str:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def phase_sweep(jax, clock, mesh=None, donate=False, k=K_SWEEP,
                t_steps=T_STEPS):
    """circuit_10k through solve_sequence: t_steps steps of k systems."""
    import numpy as np

    from repro.core import HyluOptions, solve_sequence
    from repro.core.options import resolve_refine_tol

    a, a_sp = load("circuit_10k")
    rng = np.random.default_rng(SEED)
    t0 = time.perf_counter()
    steps = [perturbed_values(a_sp, k, rng) for _ in range(t_steps)]
    b = rng.standard_normal((k, a.n))
    data_s = time.perf_counter() - t0
    opts = HyluOptions(engine="jax", factor_dtype=FACTOR_DTYPE, mesh=mesh,
                       donate=donate)
    c0, t0 = len(clock.spans), time.perf_counter()
    x, info = solve_sequence(a, steps, b, opts)
    wall = time.perf_counter() - t0
    compile_s = clock.seconds(c0)
    analyze_s = info["timings"]["preprocess"]["total"]
    tol = resolve_refine_tol(opts, "float64")
    resid = np.asarray(info["residual"])
    check(x.shape == (t_steps, k, a.n), f"sweep: x has shape {x.shape}")
    check(np.isfinite(x).all(), "sweep: non-finite solution")
    check(not np.asarray(info["refine_failed"]).any(),
          f"sweep: {int(np.asarray(info['refine_failed']).sum())} systems "
          f"exited refinement above tol {tol:g}")
    check(float(resid.max()) <= tol,
          f"sweep: worst residual {resid.max():.3e} > tol {tol:g}")
    worst_err = 0.0
    for t, j in zip(rng.integers(0, t_steps, N_SAMPLED),
                    rng.choice(k, N_SAMPLED, replace=False)):
        err, bound, host_resid = Oracle(a_sp, steps[t][j]).errors(
            x[t, j], b[j], tol)
        check(err <= bound, f"sweep: step {t} system {j} differs from "
              f"splu by {err:.3e} > bound {bound:.3e}")
        check(host_resid <= 10 * tol, f"sweep: step {t} system {j} host "
              f"residual {host_resid:.3e}")
        worst_err = max(worst_err, err)
    figures("sweep" if mesh is None else f"sweep mesh={mesh}",
            matrix="circuit_10k", mode=info["mode"], K=k, T=t_steps,
            pair=f"{FACTOR_DTYPE}/float64", data_s=f"{data_s:.2f}",
            analyze_s=f"{analyze_s:.2f}", compile_s=f"{compile_s:.2f}",
            run_s=f"{wall - analyze_s - compile_s:.2f}",
            peak_device_bytes=peak_bytes(jax),
            worst_residual=f"{resid.max():.3e}",
            worst_splu_err=f"{worst_err:.3e}",
            n_fp64_fallback="0 (the pipeline runs none)",
            compile_cache_hits=clock.cache_hits)
    return x, info


def phase_serve(jax, clock):
    """16 seeded requests over two patterns through SolverService."""
    import numpy as np

    from repro.core import CSR, HyluOptions
    from repro.core.options import resolve_refine_tol
    from repro.serve.solver_service import SolverService, SolveRequest

    rng = np.random.default_rng(SEED + 1)
    patterns = {name: load(name) for name in ("circuit_10k", "fem2d_4900")}
    names = list(patterns)
    requests, oracles = [], []
    for i in range(SERVE_REQUESTS):
        name = names[i % len(names)]
        a, a_sp = patterns[name]
        v = perturbed_values(a_sp, 1, rng)[0]
        requests.append(SolveRequest(a=CSR(a.n, a.indptr, a.indices, v),
                                     b=rng.standard_normal(a.n),
                                     tag=(name, i)))
        oracles.append(Oracle(a_sp, v))
    # Each rung of the escalation ladder (fp64 fallback, perturbed
    # retries) compiles another engine, and a float64 fem2d_10k engine does
    # not compile within the time budget: the smoke holds every healthy
    # request to the first rung, so a refinement failure shows up as a
    # quarantined request instead of a stall.
    opts = HyluOptions(engine="jax", factor_dtype=FACTOR_DTYPE,
                       fp64_fallback=False, retry_max=0)
    tol = resolve_refine_tol(opts, "float64")
    svc = SolverService(opts=opts, cache_dir=None, batch_size=BATCH)
    c0, t0 = len(clock.spans), time.perf_counter()
    results = svc.solve_batch(requests)
    wall = time.perf_counter() - t0
    compile_s = clock.seconds(c0)
    analyze_s = svc.cache.stats["analyze_s"]
    st = svc.stats
    check(st["failed"] == 0 and st["quarantined"] == 0,
          f"serve: {st['failed']} failed, {st['quarantined']} quarantined: "
          + "; ".join(f"{r.tag}: {r.error.message}" for r in results
                      if r.error is not None)[:2000])
    worst_err = worst_resid = 0.0
    for req, res, orc in zip(requests, results, oracles):
        check(res.status == "solved" and not res.refine_failed,
              f"serve: {req.tag} came back {res.status}")
        check(float(res.residual) <= tol,
              f"serve: {req.tag} residual {res.residual:.3e} > {tol:g}")
        err, bound, host_resid = orc.errors(res.x, req.b, tol)
        check(err <= bound, f"serve: {req.tag} differs from splu by "
              f"{err:.3e} > bound {bound:.3e}")
        worst_err = max(worst_err, err)
        worst_resid = max(worst_resid, float(res.residual))
    figures("serve", requests=len(requests), batch_size=BATCH,
            modes=sorted(set(svc.pattern_modes.values())),
            pair=f"{FACTOR_DTYPE}/float64", analyze_s=f"{analyze_s:.2f}",
            compile_s=f"{compile_s:.2f}",
            run_s=f"{wall - analyze_s - compile_s:.2f}",
            peak_device_bytes=peak_bytes(jax),
            worst_residual=f"{worst_resid:.3e}",
            worst_splu_err=f"{worst_err:.3e}",
            n_fp64_fallback=f"{st['fp64_fallbacks']} (off)",
            failed=st["failed"], quarantined=st["quarantined"],
            compile_cache_hits=clock.cache_hits)
    fem = [i for i, r in enumerate(requests) if r.tag[0] == "fem2d_4900"]
    return svc, [requests[i] for i in fem], [results[i] for i in fem], \
        [oracles[i] for i in fem]


def phase_pallas(jax, clock, svc, requests, results, oracles):
    """fem2d_4900's serve batch again, on the compiled Pallas kernels."""
    import numpy as np

    from repro.core import analyze, factor_batched, solve_batched
    from repro.core.analysis import jax_repeated_engine
    from repro.core.options import resolve_refine_tol

    a0 = requests[0].a
    an_serve = svc.cache.get_or_analyze(a0, svc.opts)
    opts = dataclasses.replace(svc.opts, use_pallas=True)
    tol = resolve_refine_tol(opts, "float64")
    c0, t0 = len(clock.spans), time.perf_counter()
    an = analyze(a0, opts, reuse=an_serve)
    analyze_s = an.timings["total"]
    eng = jax_repeated_engine(an)
    values = jax.device_put(np.stack([r.a.data for r in requests]))
    b = jax.device_put(np.stack([r.b for r in requests]))
    n_kernels = eng.refactor_batched.lower(values).as_text().count(
        "tpu_custom_call")
    check(n_kernels > 0, "pallas: the factor program holds no compiled "
          "kernel (tpu_custom_call)")
    bst = factor_batched(an, (a0.indptr, a0.indices), values)
    n_solve_kernels = eng.refined_batched_solver(*bst.a_pattern).lower(
        bst.vals, bst.inode_perm, bst.values_dev, b, opts.refine_max_iter,
        tol).as_text().count("tpu_custom_call")
    check(n_solve_kernels > 0, "pallas: the fused solve program holds no "
          "compiled kernel (tpu_custom_call)")
    x, info = solve_batched(bst, b)
    wall = time.perf_counter() - t0
    compile_s = clock.seconds(c0)
    check(not np.asarray(info["refine_failed"]).any(),
          "pallas: a system exited refinement above tolerance")
    check(float(np.max(info["residual"])) <= tol,
          f"pallas: worst residual {np.max(info['residual']):.3e}")
    worst_err = worst_diff = 0.0
    for j, (req, res, orc) in enumerate(zip(requests, results, oracles)):
        err, bound, _ = orc.errors(x[j], req.b, tol)
        check(err <= bound, f"pallas: {req.tag} differs from splu by "
              f"{err:.3e} > bound {bound:.3e}")
        diff = float(np.abs(x[j] - res.x).sum() / np.abs(res.x).sum())
        check(diff <= bound, f"pallas: {req.tag} differs from the serve "
              f"answer by {diff:.3e} > bound {bound:.3e}")
        worst_err, worst_diff = max(worst_err, err), max(worst_diff, diff)
    figures("pallas", matrix="fem2d_4900", mode=an.choice.mode,
            K=len(requests), pair=f"{FACTOR_DTYPE}/float64",
            analyze_s=f"{analyze_s:.2f}", compile_s=f"{compile_s:.2f}",
            run_s=f"{wall - analyze_s - compile_s:.2f}",
            kernels_in_factor=n_kernels, kernels_in_solve=n_solve_kernels,
            peak_device_bytes=peak_bytes(jax),
            worst_residual=f"{np.max(info['residual']):.3e}",
            worst_splu_err=f"{worst_err:.3e}",
            worst_diff_vs_serve=f"{worst_diff:.3e}",
            n_fp64_fallback=info["n_fp64_fallback"],
            compile_cache_hits=clock.cache_hits)


def phase_sharded(jax, clock):
    """The sharded donating pipeline on four chips against the same batch
    on one device, in this one process.

    The one-device run holds the whole batch on one chip.  The sweep's
    measured peak at K=1024 is 9.1 GB of a v5e's 16 GB, so 1024 systems
    is the largest batch known to fit there and 4×1024 would not: 4×256
    systems over 2 steps exercise the sharding, and donation across a
    step boundary, on the full circuit_10k pattern, and the one-device
    run is the sweep's own K=1024 program."""
    import numpy as np

    k = 4 * K_SHARD
    x4, _ = phase_sweep(jax, clock, mesh=4, donate=True, k=k,
                        t_steps=T_SHARD)
    x1, _ = phase_sweep(jax, clock, mesh=None, donate=False, k=k,
                        t_steps=T_SHARD)
    diff = np.abs(x4 - x1)
    n_diff = int((diff > 0).sum())
    rel = float(diff.max() / np.abs(x1).max())
    figures("sharded vs one device", systems=k, steps=T_SHARD,
            entries_differing=n_diff, max_rel_diff=f"{rel:.3e}")
    check(n_diff == 0, f"sharded: {n_diff} solution entries differ from "
          f"the one-device run (max relative difference {rel:.3e})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: device, sweep, serve, pallas; 4: only the "
                         "sharded sweep and its one-device comparison")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    try:
        import jax
        from repro.launch.compile_cache import enable_compilation_cache
        import benchmarks.corpus  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the repository is not beside this script ({e})",
              file=sys.stderr)
        return 2

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "devices", file=sys.stderr)
        return 1
    jax.config.update("jax_enable_x64", True)
    cache = enable_compilation_cache()
    clock = CompileClock(jax)
    figures("device", platform=dev.platform, kind=repr(dev.device_kind),
            count=len(devices), compile_cache=cache)
    t_all = time.perf_counter()
    phase = "setup"
    try:
        if args.chips == 4:
            phase = "sharded"
            phase_sharded(jax, clock)
        else:
            phase = "sweep"
            phase_sweep(jax, clock)
            phase = "serve"
            svc, reqs, results, oracles = phase_serve(jax, clock)
            phase = "pallas"
            phase_pallas(jax, clock, svc, reqs, results, oracles)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL in {phase}: {e}", file=sys.stderr)
        return 1
    figures("total", wall_s=f"{time.perf_counter() - t_all:.2f}",
            compile_s=f"{clock.seconds():.2f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
