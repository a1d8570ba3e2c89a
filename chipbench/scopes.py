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

:func:`attribute` gives each module run of the window to the factor or the
solve by the scopes of the operations it holds, whatever the program is
called or however the work is split into programs (a trace without
scopes gives nothing); :func:`family_seconds` and :func:`phase` are what
the per-layer readers call, and give a one-line reason on standard error
where they read nothing.  The harness collects
``(hspans, ops)`` in every traced run and hands them to the readers as a
:class:`Window`.

``main`` runs traced cells in one process and prints what the readers
read from each window, by scope and by module:

    python3 chipbench/scopes.py --workload circuit-sweep --seeds 1,2 \\
        [--untraced] [--seconds s] [--config JSON] [--traffic JSON] \\
        [--save-events FILE]

Each run prints one JSON line: the harness's metrics, the median step
time, and for a traced run the eight phase numbers, the attribution of
module runs to the factor and the solve, each program's scope coverage
with what is left over, and the seconds of every host span.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
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
#: metric name -> the scope whose device time it reads
DEVICE_METRICS = {
    "factor_stage_ms.sweep": "hylu.factor.stage",
    "factor_panel_ms.sweep": "hylu.factor.panel",
    "factor_edge_ms.sweep": "hylu.factor.edge",
    "factor_tail_ms.sweep": "hylu.factor.tail",
    "solve_subst_ms.sweep": "hylu.solve.subst",
    "solve_residual_ms.sweep": "hylu.solve.residual",
}
STEP_SPAN = "hylu.factor_batched"
STAGE_SPAN = "hylu.stage"
#: family -> the scope prefix of its operations
FAMILIES = {"factor": "hylu.factor.", "solve": "hylu.solve."}
#: the harness span of each step's solve, which returns host arrays and so
#: waits for the step's device work, however the factor is dispatched
SOLVE_SPAN = trace.SPAN_PREFIX + "solve_batched"


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


def incomplete(events) -> str | None:
    """Why the trace cannot hold all of the window's device work, or None.

    Each ``cb:solve_batched`` span returns the step's answers to the host,
    so it cannot end before the step's refactor and solve have run on the
    device, and the device cannot run the step's solve before the span
    starts.  So each line of device events ("XLA Modules", "XLA Ops") of
    a complete trace starts before the window's first such span ends and
    ends after its last one starts.  A trace that lost events at either
    end does not; one without those spans cannot be judged and passes."""
    lo, hi = window(events)
    solves = [(s, e) for k, n, s, e, _ in events
              if k == "span" and n == SOLVE_SPAN and lo <= s < hi]
    firsts = [e for _, e in solves]
    lasts = [s for s, _ in solves]
    reach = {}
    for k, _, s, e, dev in events:
        if k in ("module", "op") and e > lo and s < hi:
            r = reach.setdefault((dev, k), [s, e])
            r[0], r[1] = min(r[0], s), max(r[1], e)
    for (dev, k), (first, last) in sorted(reach.items()):
        line = dev + " " + (trace.MODULES_LINE if k == "module"
                            else trace.OPS_LINE)
        if firsts and first >= min(firsts):
            return (f"trace incomplete: the {line} events start "
                    f"{(first - lo) / 1e9:.6f} s into the window, after the "
                    f"first {SOLVE_SPAN} span ends at "
                    f"{(min(firsts) - lo) / 1e9:.6f} s")
        if lasts and last <= max(lasts):
            return (f"trace incomplete: the {line} events end "
                    f"{(last - lo) / 1e9:.6f} s into the window, before the "
                    f"last {SOLVE_SPAN} span starts at "
                    f"{(max(lasts) - lo) / 1e9:.6f} s")
    return None


def family_of(scope: str | None) -> str | None:
    """The family (``factor``/``solve``) whose scopes hold ``scope``."""
    if scope:
        for family, prefix in FAMILIES.items():
            if scope.startswith(prefix):
                return family
    return None


@dataclasses.dataclass
class Attribution:
    """The window's module runs given to the factor and the solve.

    Seconds are device seconds inside the window and runs count the runs
    that start in it, both averaged over the devices, as
    :class:`trace.Summary` does."""
    seconds: dict          # family -> device seconds
    modules: dict          # family -> {module: [runs, seconds, op events]}
    scoped_ops: dict       # family -> operations with one of its scopes
    other: dict            # module -> seconds of runs of no family


def _split(lo: int, hi: int, held) -> dict:
    """{family: [ns, op events]} of one module run [lo, hi) that holds the
    operations of more than one family; ``held`` is its operations as
    ``(family or None, start, end)``.

    Each family holds its scoped operations and the unscoped ones that
    enclose operations of it alone (a loop around its body).  Every other
    instant of the run, a copy with no ``op_name`` or an idle gap, goes to
    the family that holds the one before it (the first family, before any),
    so the families share the whole run between them."""
    starts = {}
    for family, s, _ in held:
        if family:
            starts.setdefault(family, []).append(s)
    for v in starts.values():
        v.sort()
    spans = [(s, e, f) for f, s, e in held if f]
    for f0, s, e in held:
        if f0 is None:
            inside = [f for f, v in starts.items()
                      if bisect.bisect_left(v, s) < bisect.bisect_left(v, e)]
            if len(inside) == 1:
                spans.append((s, e, inside[0]))
    spans.sort()
    out = {f: [0, 0] for f in starts}
    cuts = [max(lo, s) for s, _, _ in spans[1:]] + [hi]
    edge = lo
    for (_, _, f), cut in zip(spans, cuts):
        if cut > edge:
            out[f][0] += cut - edge
            edge = cut
    owner = [f for _, _, f in spans]
    at = [max(lo, s) for s, _, _ in spans]
    for f, s, _ in held:
        out[owner[max(bisect.bisect_right(at, s) - 1, 0)]][1] += 1
    return out


def attribute(events, ops) -> Attribution:
    """Give each module run in the window to a family by the scopes of the
    operations it holds (on the same device, starting inside it), whatever
    the program is called or however the work is split into programs.

    A run that holds one family's scopes counts whole for it, operations
    without an ``op_name`` and idle gaps included.  A run that holds both
    families is shared between them by :func:`_split`, so it counts whole
    too.  A run that holds no ``hylu.`` scope is in ``other``."""
    lo, hi = window(events)
    devices = sorted({t for k, _, _, _, t in events if k in ("module", "op")})
    nd = max(len(devices), 1)
    runs = {dev: [] for dev in devices}
    for k, n, s, e, t in events:
        if k == "module":
            runs[t].append((s, e, n))
    on_dev = {dev: [] for dev in devices}
    for o in ops:
        on_dev.setdefault(o[5], []).append(o)
    # whole nanoseconds and counts summed over the devices, so that work
    # split over more runs or programs sums to the same total exactly
    modules = {f: {} for f in FAMILIES}
    scoped_ops = {f: 0 for f in FAMILIES}
    other = {}

    def add(family, name, started, ns, n_ops):
        m = modules[family].setdefault(name, [0, 0, 0])
        m[0] += started
        m[1] += ns
        m[2] += n_ops

    for dev in devices:
        mods = sorted(runs[dev])
        starts = [m[0] for m in mods]
        held = [[] for _ in mods]      # (family or None, start, end)
        for _, _, scope, s, e, _ in on_dev.get(dev, []):
            family = family_of(scope)
            if family and s < hi and e > lo:
                scoped_ops[family] += 1
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < mods[i][1]:
                held[i].append((family, s, e))
        for i, (s, e, name) in enumerate(mods):
            clip = trace._clip([(s, e)], lo, hi)
            if not clip:
                continue
            run_lo, run_hi = clip[0]
            started = int(lo <= s < hi)
            families = {f for f, _, _ in held[i] if f}
            if not families:
                other[name] = other.get(name, 0) + run_hi - run_lo
            elif len(families) == 1:
                add(families.pop(), name, started, run_hi - run_lo,
                    len(held[i]))
            else:
                for f, (ns, n_ops) in _split(run_lo, run_hi,
                                             held[i]).items():
                    add(f, name, started, ns, n_ops)
    return Attribution(
        seconds={f: sum(m[1] for m in modules[f].values()) / 1e9 / nd
                 for f in FAMILIES},
        modules={f: {n: [r / nd, ns / 1e9 / nd, o / nd]
                     for n, (r, ns, o) in sorted(modules[f].items())}
                 for f in FAMILIES},
        scoped_ops=scoped_ops,
        other={n: ns / 1e9 / nd for n, ns in other.items()})


def describe(att: Attribution, top: int = 5) -> list:
    """Lines that say which module runs each family was given."""
    out = []
    for family in FAMILIES:
        mods = "; ".join(
            f"{n} {r:g} runs {s:.6f} s {o / r if r else o:.0f} op events a run"
            for n, (r, s, o) in sorted(att.modules[family].items()))
        out.append(f"{family}: {mods or 'no module'}")
    if att.other:
        ranked = sorted(att.other.items(), key=lambda kv: -kv[1])[:top]
        out.append("no family: " + "; ".join(f"{n} {s:.6f} s"
                                             for n, s in ranked))
    return out


def phases(events, hspans, ops, steps: int | None = None) -> dict:
    """The eight per-phase numbers of a traced sweep window, from the
    harness's flattened ``events`` and this module's ``hspans``/``ops``:
    device ms per step in each scope, host ms per step in ``hylu.stage``,
    and device idle ms per step under an open ``hylu.`` span.  ``steps``
    is the window's step count; without it, the ``hylu.factor_batched``
    spans that start in the window.  A value is None where the trace has
    nothing to read: no scoped operation, or no step.  A scope the plan
    lacks (the scanned tail of a plan without one) reads 0."""
    lo, hi = window(events)
    if steps is None:
        steps = sum(1 for n, s, _ in hspans
                    if n == STEP_SPAN and lo <= s < hi)
    scoped = any(o[2] for o in ops)
    sec = scope_seconds(ops, lo, hi)
    out = {}
    for metric, scope in DEVICE_METRICS.items():
        out[metric] = (1e3 * sec.get(scope, 0.0) / steps
                       if steps and scoped else None)
    idle = solver_idle_seconds(ops, hspans, lo, hi)
    out["host_stage_ms.sweep"] = (
        1e3 * span_seconds(hspans, STAGE_SPAN, lo, hi) / steps
        if steps else None)
    out["solver_idle_ms.sweep"] = (1e3 * idle / steps
                                   if steps and idle is not None else None)
    return out


class Window:
    """One traced window as the per-layer readers get it: the harness's
    flattened events, the solver's host spans and scoped operations, and
    the steps the window ran; what they reduce to is worked out once."""

    def __init__(self, events, hspans, ops, steps: int):
        self.events, self.hspans, self.ops = events, hspans, ops
        self.steps = steps

    @functools.cached_property
    def incomplete(self) -> str | None:
        return incomplete(self.events)

    @functools.cached_property
    def attribution(self) -> Attribution:
        return attribute(self.events, self.ops)

    @functools.cached_property
    def phases(self) -> dict:
        return phases(self.events, self.hspans, self.ops, self.steps)


def nothing(metric: str, why: str) -> None:
    """Say on standard error why ``metric`` reads nothing; returns None."""
    print(f"chipbench: {metric} reads nothing: {why}", file=sys.stderr,
          flush=True)


def _unreadable(ctx) -> str | None:
    win = ctx.scoped
    if win is None:
        return "no traced window"
    if not win.steps:
        return "no traced step"
    return win.incomplete


def family_seconds(ctx, family: str, metric: str) -> float | None:
    """Device seconds of ``family`` per traced step of the window in
    ``ctx`` (:func:`attribute`); None, with the reason on standard error,
    where the window has nothing to read."""
    why = _unreadable(ctx)
    if why is None:
        att = ctx.scoped.attribution
        prefix = FAMILIES[family]
        if not any(att.scoped_ops.values()):
            why = "no scoped op: none carries a hylu. scope"
        elif not att.scoped_ops[family]:
            why = f"no scoped op of the family: none carries a {prefix} scope"
        elif att.seconds[family] <= 0:
            why = f"no module of the family: no run holds a {prefix} scope"
    if why is not None:
        return nothing(metric, why)
    return ctx.scoped.attribution.seconds[family] / ctx.scoped.steps


def phase(ctx, metric: str) -> float | None:
    """One of :func:`phases`' numbers for the window in ``ctx``; None, with
    the reason on standard error, where the window has nothing to read."""
    why = _unreadable(ctx)
    if why is None:
        value = ctx.scoped.phases[metric]
        if value is not None:
            return value
        why = ("no scoped op: none carries a hylu. scope"
               if metric in DEVICE_METRICS else
               "no hylu. host span or no device op")
    return nothing(metric, why)


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
                win = memo["last_context"].scoped
                events, hspans, ops = win.events, win.hspans, win.ops
                lo, hi = window(events)
                line["breakdown"] = r["breakdown"]
                line["phases"] = win.phases
                line["attribution"] = dataclasses.asdict(win.attribution)
                line["incomplete"] = win.incomplete
                line["events"] = {"ops": len(ops), "hspans": len(hspans),
                                  "modules": sum(e[0] == "module"
                                                 for e in events)}
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
