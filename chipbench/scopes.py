#!/usr/bin/env python3
"""The solver's own phases in a ``jax.profiler`` trace: device time per
``hylu.`` scope and the device's idle time while the solver's host path
runs.

The solver names its device phases with ``jax.named_scope("hylu.<phase>")``,
which XLA keeps in each operation's ``op_name`` metadata, and its host path
with ``hylu.<name>`` host spans (``repro.core.tracing``).  :func:`collect`
reads both from an ``.xplane.pb``:

* ``hspans``: ``(name, start_ns, end_ns)`` of every ``hylu.`` host span;
* ``ops``: ``(module, op, scope, start_ns, end_ns, device)`` of every device
  operation, where ``scope`` is the ``hylu.`` scope in its ``op_name``,
  ``""`` when the ``op_name`` holds none, and ``None`` when the operation
  carries no ``op_name`` (copies XLA inserts, for example).

The reductions work on those lists alone.  A scope's device time is the
union of its operations' intervals, so a loop and the body operations it
holds count once.  :func:`phases` gives the eight per-phase numbers of a
traced sweep window; each is None where the trace holds nothing to read
(a solver without spans and scopes).

The benchmark's harness does not collect these events yet, so ``main``
runs traced cells itself and reads them from the same trace file:

    python3 chipbench/scopes.py --workload circuit-sweep --seeds 1,2 \\
        [--untraced] [--seconds s] [--config JSON] [--traffic JSON] \\
        [--save-events FILE]

Each run prints one JSON line: the harness's metrics, the median step
time, and for a traced run the eight phase numbers, each program's scope
coverage with what is left over, and the seconds of every host span.
"""
from __future__ import annotations

import bisect
import json
import os
import re
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from chipbench import trace  # noqa: E402

HOST_PREFIX = "hylu."
SCOPE = re.compile(r"hylu\.[a-z_]+\.[a-z_]+")
FACTOR_PROGRAM = "_refactor"
SOLVE_PROGRAM = "solve_refined"
#: metric name -> (scope, program whose runs it is divided by)
DEVICE_METRICS = {
    "factor_stage_ms.sweep": ("hylu.factor.stage", FACTOR_PROGRAM),
    "factor_panel_ms.sweep": ("hylu.factor.panel", FACTOR_PROGRAM),
    "factor_edge_ms.sweep": ("hylu.factor.edge", FACTOR_PROGRAM),
    "factor_tail_ms.sweep": ("hylu.factor.tail", FACTOR_PROGRAM),
    "solve_subst_ms.sweep": ("hylu.solve.subst", SOLVE_PROGRAM),
    "solve_residual_ms.sweep": ("hylu.solve.residual", SOLVE_PROGRAM),
}
STEP_SPAN = "hylu.factor_batched"
STAGE_SPAN = "hylu.stage"


def scope_of(op_name: str | None) -> str | None:
    """The innermost ``hylu.`` scope named in an ``op_name``; ``""`` when
    it names none, None when there is no ``op_name``."""
    if not op_name:
        return None
    found = SCOPE.findall(op_name)
    return found[-1] if found else ""


# -- the HLO the trace keeps in its /host:metadata plane ---------------------
# A v5e trace's op events carry no op_name (their stats are times only);
# the HLO of every program it ran does.  Field numbers of tsl/profiler/protobuf/xplane.proto and xla/service/
# hlo.proto, read with a minimal protobuf wire-format reader.
_XSPACE_PLANES, _XPLANE_NAME, _XPLANE_EVENT_METADATA = 1, 2, 4
_XPLANE_STAT_METADATA, _MAP_VALUE = 5, 2
_XEVENTMETA_NAME, _XEVENTMETA_STATS = 2, 5
_XSTATMETA_ID, _XSTATMETA_NAME = 1, 2
_XSTAT_METADATA_ID, _XSTAT_BYTES = 1, 6
_HLOPROTO_MODULE, _MODULE_COMPUTATIONS, _COMPUTATION_INSTRUCTIONS = 1, 3, 2
_INSTRUCTION_NAME, _INSTRUCTION_METADATA, _OPMETA_OP_NAME = 1, 7, 2
HLO_PROTO_STAT = "Hlo Proto"


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited field; fixed-width fields are
    skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _first(buf, field):
    return next((v for f, v in _fields(buf) if f == field), None)


def hlo_op_names(path: str) -> dict:
    """{(module, "%" + instruction): op_name} from the HLO protos of the
    trace's ``/host:metadata`` plane ({} when it holds none)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != _XSPACE_PLANES or bytes(
                _first(plane, _XPLANE_NAME) or b"") != b"/host:metadata":
            continue
        stat_names, metas = {}, []
        for f, v in _fields(plane):
            if f == _XPLANE_STAT_METADATA:
                sm = _first(v, _MAP_VALUE)
                stat_names[_first(sm, _XSTATMETA_ID) or 0] = bytes(
                    _first(sm, _XSTATMETA_NAME) or b"").decode()
            elif f == _XPLANE_EVENT_METADATA:
                metas.append(_first(v, _MAP_VALUE))
        for em in metas:
            module = trace._short(bytes(
                _first(em, _XEVENTMETA_NAME) or b"").decode())
            for f, stat in _fields(em):
                if f != _XEVENTMETA_STATS or stat_names.get(
                        _first(stat, _XSTAT_METADATA_ID) or 0) \
                        != HLO_PROTO_STAT:
                    continue
                hlo = _first(_first(stat, _XSTAT_BYTES), _HLOPROTO_MODULE)
                for f2, comp in _fields(hlo):
                    if f2 != _MODULE_COMPUTATIONS:
                        continue
                    for f3, ins in _fields(comp):
                        if f3 != _COMPUTATION_INSTRUCTIONS:
                            continue
                        name = _first(ins, _INSTRUCTION_NAME)
                        meta = _first(ins, _INSTRUCTION_METADATA)
                        op_name = meta is not None and _first(
                            meta, _OPMETA_OP_NAME)
                        if name is not None and op_name:
                            out[(module, "%" + bytes(name).decode())] = \
                                bytes(op_name).decode()
    return out


def collect(path: str) -> tuple[list, list]:
    """``(hspans, ops)`` of the trace at ``path`` (see the module's
    docstring).  An operation's module is the module run that holds its
    start on the same device; its ``op_name`` is the one the HLO in the
    trace's ``/host:metadata`` plane gives (module, operation name)."""
    from jax.profiler import ProfileData

    hspans, ops, hlo, lookup = [], [], None, {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            if trace.OPS_LINE not in lines:
                continue
            mods = sorted(
                (int(e.start_ns), int(e.end_ns), trace._short(e.name))
                for e in (lines[trace.MODULES_LINE].events
                          if trace.MODULES_LINE in lines else []))
            starts = [m[0] for m in mods]
            for e in lines[trace.OPS_LINE].events:
                s, t = int(e.start_ns), int(e.end_ns)
                i = bisect.bisect_right(starts, s) - 1
                module = mods[i][2] if i >= 0 and s < mods[i][1] else ""
                op = trace._short(e.name)
                key = (module, op)
                if key not in lookup:
                    if hlo is None:
                        hlo = hlo_op_names(path)
                    lookup[key] = scope_of(hlo.get(key))
                ops.append((module, op, lookup[key], s, t, plane.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        hspans.append((e.name, int(e.start_ns),
                                       int(e.end_ns)))
    return hspans, ops


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def _intersect(a, b) -> int:
    """Length of the intersection of two merged, sorted interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _overlap(merged, ends, s, e) -> int:
    """Length of [s, e) inside ``merged`` (merged, sorted; ``ends`` its
    interval ends)."""
    total = 0
    for lo, hi in merged[bisect.bisect_right(ends, s):]:
        if lo >= e:
            break
        total += min(hi, e) - max(lo, s)
    return total


def _complement(merged, lo, hi):
    out, edge = [], lo
    for s, e in merged:
        if s > edge:
            out.append((edge, s))
        edge = max(edge, e)
    if hi > edge:
        out.append((edge, hi))
    return out


def _devices(ops) -> list:
    return sorted({o[5] for o in ops})


def scope_seconds(ops, lo: int, hi: int) -> dict:
    """{scope: device seconds in [lo, hi)}: the union of the scope's
    operation intervals on each device, averaged over the devices."""
    by = {}
    for _, _, scope, s, e, dev in ops:
        if scope:
            by.setdefault((scope, dev), []).append((s, e))
    nd = max(len(_devices(ops)), 1)
    out = {}
    for (scope, _), iv in by.items():
        out[scope] = out.get(scope, 0.0) + _length(
            trace._union(trace._clip(iv, lo, hi))) / 1e9 / nd
    return out


def coverage(ops, program: str, prefix: str, lo: int, hi: int,
             top: int = 10) -> dict:
    """How much of ``program``'s device time its ``prefix`` scopes cover,
    counted over the operations that carry an ``op_name``: the covered
    share, the seconds left over (by operation, the part of each
    unscoped operation outside every scope), and the seconds of the
    operations with no ``op_name`` at all."""
    mine = [o for o in ops if program in o[0]]
    shares, rest, bare = [], {}, {}
    for dev in _devices(mine):
        on = [o for o in mine if o[5] == dev]
        meta = trace._union(trace._clip(
            [(o[3], o[4]) for o in on if o[2] is not None], lo, hi))
        scoped = trace._union(trace._clip(
            [(o[3], o[4]) for o in on if o[2] and o[2].startswith(prefix)],
            lo, hi))
        if meta:
            shares.append(_length(scoped) / _length(meta))
        uncovered = _complement(scoped, lo, hi)
        ends = [e for _, e in uncovered]
        for _, op, scope, s, e, _ in on:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            if scope is None:
                bare[op] = bare.get(op, 0.0) + (e - s) / 1e9
            elif not scope.startswith(prefix):
                left = _overlap(uncovered, ends, s, e)
                if left:
                    rest[op] = rest.get(op, 0.0) + left / 1e9

    def ranked(d):
        return [[n, s] for n, s in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]
    return {"share": sum(shares) / len(shares) if shares else None,
            "remainder_s": sum(rest.values()), "remainder": ranked(rest),
            "no_op_name_s": sum(bare.values()), "no_op_name": ranked(bare)}


def span_seconds(hspans, name: str, lo: int, hi: int) -> float:
    """Wall seconds in [lo, hi) inside host spans called ``name``."""
    return _length(trace._union(trace._clip(
        [(s, e) for n, s, e in hspans if n == name], lo, hi))) / 1e9


def solver_idle_seconds(ops, hspans, lo: int, hi: int) -> float | None:
    """Device idle seconds in [lo, hi) during which a ``hylu.`` host span
    was open (the interval intersection), averaged over the devices."""
    if not hspans or not ops:
        return None
    host = trace._union(trace._clip([(s, e) for _, s, e in hspans], lo, hi))
    devs = _devices(ops)
    idle = 0
    for dev in devs:
        busy = trace._union(trace._clip(
            [(o[3], o[4]) for o in ops if o[5] == dev], lo, hi))
        idle += _intersect(_complement(busy, lo, hi), host)
    return idle / 1e9 / len(devs)


def window(events) -> tuple[int, int]:
    """The harness's ``cb:window`` span, or the device's first to last
    event (as :func:`trace.reduce`)."""
    win = [(s, e) for k, n, s, e, _ in events
           if k == "span" and n == trace.WINDOW_SPAN]
    if win:
        return win[0]
    dev = [(s, e) for k, _, s, e, _ in events if k in ("module", "op")]
    return min(s for s, _ in dev), max(e for _, e in dev)


def phases(events, hspans, ops) -> dict:
    """The eight per-phase numbers of a traced sweep window, from the
    harness's flattened ``events`` and this module's ``hspans``/``ops``:
    device ms per program run in each scope, host ms per step in
    ``hylu.stage``, and device idle ms per step under an open ``hylu.``
    span.  A step is a ``hylu.factor_batched`` span that starts in the
    window.  A value is None where the trace has nothing to read: no
    scoped operation, or no step span.  A scope the plan lacks (the
    scanned tail of a plan without one) reads 0."""
    summary = trace.reduce(events)
    lo, hi = window(events)
    scoped = any(o[2] for o in ops)
    sec = scope_seconds(ops, lo, hi)
    out = {}
    for metric, (scope, program) in DEVICE_METRICS.items():
        _, runs = summary.program(program)
        out[metric] = (1e3 * sec.get(scope, 0.0) / runs
                       if runs and scoped else None)
    steps = sum(1 for n, s, _ in hspans if n == STEP_SPAN and lo <= s < hi)
    idle = solver_idle_seconds(ops, hspans, lo, hi)
    out["host_stage_ms.sweep"] = (
        1e3 * span_seconds(hspans, STAGE_SPAN, lo, hi) / steps
        if steps else None)
    out["solver_idle_ms.sweep"] = (1e3 * idle / steps
                                   if steps and idle is not None else None)
    return out


def save(path: str, events, hspans, ops) -> None:
    with open(path, "w") as f:
        json.dump({"events": [list(e) for e in events],
                   "hspans": [list(h) for h in hspans],
                   "ops": [list(o) for o in ops]}, f)


def load(path: str) -> tuple[list, list, list]:
    with open(path) as f:
        d = json.load(f)
    return ([tuple(e) for e in d["events"]], [tuple(h) for h in d["hspans"]],
            [tuple(o) for o in d["ops"]])


def _median_step_s(counters) -> float | None:
    import statistics

    ends = counters.get("step_ends") or []
    took = [b - a for a, b in zip([0.0] + ends[:-1], ends)]
    return statistics.median(took) if took else None


def main(argv=None) -> int:
    import argparse
    import time

    from chipbench import harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--untraced", action="store_true",
                    help="also run each seed untraced, for the tracing cost")
    ap.add_argument("--config")
    ap.add_argument("--traffic")
    ap.add_argument("--save-events",
                    help="write the last traced run's events, host spans "
                         "and scoped operations to this JSON file")
    args = ap.parse_args(argv)

    # the harness reads its events through trace.events_from_xplane; this
    # wrapper reads the scopes from the same trace file before it is removed
    seen = {}
    read = trace.events_from_xplane

    def events_and_scopes(path):
        seen["scoped"] = collect(path)
        seen["events"] = read(path)
        return seen["events"]

    trace.events_from_xplane = events_and_scopes
    memo = {}
    for seed in [int(s) for s in args.seeds.split(",")]:
        for traced in (True, False) if args.untraced else (True,):
            t0 = time.perf_counter()
            r = harness.run_cell(
                args.workload, seed, args.seconds, traced,
                config_over=json.loads(args.config or "{}"),
                traffic_over=json.loads(args.traffic or "{}"),
                memo=memo, t_start=t0)
            line = {"seed": seed, "traced": traced,
                    "correct": r["correct"],
                    "run_s": time.perf_counter() - t0,
                    "median_step_s": _median_step_s(memo["last_counters"]),
                    "metrics": r["metrics"], "device": r["device"]}
            if traced:
                events = seen.pop("events")
                hspans, ops = seen.pop("scoped")
                lo, hi = window(events)
                line["breakdown"] = r["breakdown"]
                line["phases"] = phases(events, hspans, ops)
                line["coverage"] = {
                    "factor": coverage(ops, FACTOR_PROGRAM, "hylu.factor.",
                                       lo, hi),
                    "solve": coverage(ops, SOLVE_PROGRAM, "hylu.solve.",
                                      lo, hi)}
                line["host_spans_s"] = {
                    n: span_seconds(hspans, n, lo, hi)
                    for n in sorted({h[0] for h in hspans})}
                if args.save_events:
                    save(args.save_events, events, hspans, ops)
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
