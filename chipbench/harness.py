"""One run of one benchmark cell: set-up, the measured window, the check
against the plain reference, and the result line.

The cell, its configuration, its traffic mix and its metrics are read from
``BENCHMARK.json`` and the files it names; nothing here is specific to one
of them.  A configuration's pattern generator (``generators/<name>.py``)
provides ``generate(**args)``, its matrix.  A traffic module (``traffic/<kind>.py``) provides ``Traffic``::

    Traffic(env, params, seed, seconds, traced)   set-up: inputs, warm-up
    .window(seconds, traced)                      the measured window
    .release()                                    drop device state
    .outcome() -> Outcome                         what the window produced

and each per-layer metric's reader (``metrics/<name>.py``) provides
``read(ctx) -> float | None``, None where it finds nothing to read.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
PLAN_CACHE_DIR = ROOT / "checkpoints" / "plan_cache_bench"
COMPILE_CACHE_DIR = ROOT / ".jax_cache"
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class NoDevice(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class CompileClock:
    """Wall seconds JAX spends tracing, lowering and compiling, from the
    time spans of its own monitoring events (nested spans counted once),
    plus persistent-cache hits.  Copied from ``chip_smoke.py``."""

    def __init__(self, jax):
        self.spans: list = []
        self.cache_hits = 0
        jax.monitoring.register_event_time_span_listener(self._on_span)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_span(self, event, start_time, end_time, **_):
        if event in COMPILE_EVENTS:
            self.spans.append((start_time, end_time))

    def seconds(self, since: int = 0, until: int | None = None) -> float:
        """Length of the union of the spans recorded from index ``since``
        (a ``len(clock.spans)`` taken earlier) up to ``until``."""
        total, reach = 0.0, float("-inf")
        for start, end in sorted(self.spans[since:until]):
            if end > reach:
                total += end - max(start, reach)
                reach = end
        return total

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


_CLOCK = []


def compile_clock(jax) -> CompileClock:
    """The process's one clock: JAX's listeners cannot be removed."""
    if not _CLOCK:
        _CLOCK.append(CompileClock(jax))
    return _CLOCK[0]


def rng(seed: int, stream: int):
    """The generator of one input stream of a run: any whole ``seed``,
    however large or negative, maps to the same inputs every time."""
    import numpy as np

    return np.random.default_rng([seed & (2 ** 64 - 1), stream])


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list      # BENCHMARK.json entries this cell reports
    per_layer: list


def load_cell(name: str, spec: dict | None = None) -> Cell:
    spec = spec or load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name=name, chips=int(w["chips"]),
                config=load_json(ROOT / cfg["file"]),
                traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                end_to_end=e2e, per_layer=per_layer)


def solver_options(config: dict):
    """The configuration's HyluOptions: its precision pair and options."""
    from repro.core import HyluOptions

    return HyluOptions(engine="jax",
                       factor_dtype=config["precision"]["factor_dtype"],
                       refine_dtype=config["precision"]["refine_dtype"],
                       **config.get("options", {}))


def contract_tol(config: dict) -> float:
    """The configuration's residual limit, which must be what the stated
    precision pair resolves to (``resolve_refine_tol``)."""
    from repro.core.options import resolve_refine_tol

    tol = float(config["limits"]["residual"])
    stated = resolve_refine_tol(solver_options(config),
                                config["precision"]["refine_dtype"])
    if tol != stated:
        raise ValueError(f"configuration {config['name']}: residual limit "
                         f"{tol:g} is not the stated pair's {stated:g}")
    return tol


@dataclasses.dataclass
class Env:
    """What a traffic module gets to work with."""
    a_sp: object          # scipy CSR: the pattern with its generator values
    a: object             # the same as the program's CSR
    analysis: object      # from the PlanCache
    cache: object         # the PlanCache
    opts: object          # HyluOptions of the run
    span: object          # span(name): a harness span in the trace


@dataclasses.dataclass
class Outcome:
    """What a window produced.  ``cases`` are ``(values, b, x)`` triples
    for the reference check."""
    end_to_end: dict
    attempted: int
    failed: int
    counters: dict
    cases: list


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader gets."""
    summary: object       # trace.Summary of the traced window (or None)
    counters: dict        # the harness's and the traffic's counts
    work: object          # work.Counts of the configuration's pattern
    peaks: dict           # the device's row of peaks.json
    factor_bytes: int     # item size of the factor dtype
    values_bytes: int     # item size of the staged (refine) dtype
    scoped: object = None  # scopes.Window: the window's events, the
    #                        solver's host spans and scoped ops, its steps


def progress(t_start: float, what: str) -> None:
    """One line of set-up progress on standard error."""
    print(f"chipbench: {time.perf_counter() - t_start:9.2f} s  {what}",
          file=sys.stderr, flush=True)


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation("cb:" + name)


def _device(jax, chips: int, check: bool):
    devices = jax.devices()
    if check:
        if devices[0].platform == "cpu":
            raise NoDevice(f"JAX found no accelerator (platform "
                           f"{devices[0].platform!r})")
        if len(devices) < chips:
            raise NoDevice(f"the cell asks for {chips} chips, JAX found "
                           f"{len(devices)}")
    return devices


def peaks_for(kind: str, check: bool = True) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        if check:
            raise KeyError(f"device kind {kind!r} is not in peaks.json")
        return {}
    return table[kind]


def _merge(base: dict, over: dict | None) -> dict:
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = _merge(out.get(k, {}), v) if isinstance(v, dict) else v
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             device_check: bool = True, compile_cache: bool = True,
             config_over: dict | None = None,
             traffic_over: dict | None = None,
             save_events: str | None = None,
             memo: dict | None = None,
             spec: dict | None = None,
             t_start: float | None = None) -> dict:
    """Run one cell once and return its result line as a dict.

    ``config_over``/``traffic_over`` merge into the cell's files, and
    ``memo`` (a dict the caller keeps) lets several runs in one process
    share the analysis and its compiled programs; ``spec`` stands in for
    ``BENCHMARK.json``.  The sizing and readings scripts and the tests
    use them; the benchmark's own runs never do."""
    t_start = time.perf_counter() if t_start is None else t_start
    import jax

    cell = load_cell(name, spec)
    devices = _device(jax, cell.chips, device_check)
    dev = devices[0]
    peaks = peaks_for(dev.device_kind, check=device_check)
    jax.config.update("jax_enable_x64", True)
    if compile_cache:
        # the cache stays inside the checkout at a fixed path, whatever the
        # host sets: two checkouts measured side by side share nothing,
        # and the program takes the directory through its own variable
        from repro.launch.compile_cache import enable_compilation_cache
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(COMPILE_CACHE_DIR)
        enable_compilation_cache()
    clock = compile_clock(jax)
    c0 = len(clock.spans)

    from repro.core import CSR
    from repro.core.options import np_dtype
    from repro.core.plan_cache import PlanCache
    from . import patterns, reference, work
    from .trace import events_from_xplane, find_xplane, reduce, save_events \
        as dump_events

    config = _merge(cell.config, config_over)
    params = _merge(cell.traffic, traffic_over)
    tol = contract_tol(cell.config)
    opts = solver_options(config)
    a_sp = patterns.build(config["pattern"])
    a = CSR.from_scipy(a_sp)
    memo = {} if memo is None else memo
    cache = memo.setdefault("plan_cache", PlanCache(
        capacity=4, directory=str(PLAN_CACHE_DIR)))
    analysis = cache.get_or_analyze(a, opts)
    progress(t_start, f"analysis of n={a.n}, nnz={a.nnz} "
             f"({analysis.choice.mode}): plan cache {cache.stats}")
    env = Env(a_sp=a_sp, a=a, analysis=analysis, cache=cache,
              opts=opts, span=span)
    traffic_mod = load_module(BENCH / "traffic" / f"{params['kind']}.py")
    traffic = traffic_mod.Traffic(env, params, seed, seconds, trace)
    c_window = len(clock.spans)
    setup_s = time.perf_counter() - t_start
    progress(t_start, f"set-up done: compile {clock.seconds(c0):.2f} s, "
             f"{clock.cache_hits} persistent-cache hits")

    summary = None
    if trace:
        from . import scopes
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        try:
            jax.profiler.start_trace(tdir)
            try:
                with span("window"):
                    traffic.window(seconds, True)
            finally:
                jax.profiler.stop_trace()
            path = find_xplane(tdir)
            events = events_from_xplane(path)
            hspans, ops = scopes.collect(path)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        if save_events:
            dump_events(events, save_events)
        summary = reduce(events)
    else:
        traffic.window(seconds, False)
    window_compiles = len(clock.spans) - c_window
    progress(t_start, f"window done ({window_compiles} compiles in it)")

    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    traffic.release()
    out = traffic.outcome()
    resid, ratio = reference.compare(a_sp, out.cases, tol)
    progress(t_start, f"reference check of {len(out.cases)} answers done")
    limits = cell.config["limits"]
    checks = {
        "residual": {"value": resid, "limit": tol},
        "error_ratio": {"value": ratio, "limit": float(limits["error_ratio"])},
        "checked": {"value": len(out.cases), "limit": 1},
    }
    correct = (len(out.cases) >= 1 and resid <= tol
               and ratio <= float(limits["error_ratio"]))

    counters = dict(out.counters)
    counters["analyze_s"] = cache.stats["analyze_s"] + cache.stats["load_s"]
    counters["compile_s"] = clock.seconds(c0, c_window)
    counters["window_compiles"] = window_compiles
    memo["last_counters"] = counters
    metrics = {}
    if trace:
        ctx = Context(summary=summary, counters=counters,
                      work=work.Counts.of(analysis), peaks=peaks,
                      factor_bytes=np_dtype(opts.factor_dtype).itemsize,
                      values_bytes=np_dtype(
                          config["precision"]["refine_dtype"]).itemsize,
                      scoped=scopes.Window(events, hspans, ops,
                                           counters.get("steps", 0)))
        memo["last_context"] = ctx
        for m in cell.per_layer:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        for line in scopes.describe(ctx.scoped.attribution):
            print(f"chipbench: {line}", file=sys.stderr, flush=True)
    else:
        e2e = dict(out.end_to_end, setup_s=setup_s)
        for m in cell.end_to_end:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                      "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": int(out.attempted),
              "failed": int(out.failed), "metrics": metrics,
              "device": device}
    if trace:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["window_compiles"] = window_compiles
    result["checks"] = checks
    return result


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--save-events", default=None,
                    help="with --trace 1: also write the flattened trace "
                         "events to this JSON file")
    args = ap.parse_args(argv)
    try:
        import repro.core  # noqa: F401
    except ImportError as e:
        print(f"chipbench: the solver is not beside the benchmark ({e})",
              file=sys.stderr)
        return 2
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), save_events=args.save_events,
                          t_start=t_start)
    except NoDevice as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    for key, c in result["checks"].items():
        print(f"check {key}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
