"""From a ``jax.profiler`` trace to per-program device time, the device's
busy union, idle gaps labelled by the harness span that was open, and the
``breakdown`` of the result line.

A trace is first flattened to events ``(kind, name, start_ns, end_ns,
track)``:

* ``module`` — one run of a compiled program on a device (the device
  plane's "XLA Modules" line); ``track`` is the device plane;
* ``op`` — one device operation ("XLA Ops" line);
* ``span`` — a harness span: a host ``TraceAnnotation`` whose name starts
  with ``SPAN_PREFIX``.

``reduce`` works on that list only, so it can be tested on a small recorded
trace kept as JSON (``save_events`` / ``load_events_json``).
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re

DEVICE_PREFIX = "/device:TPU:"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "cb:"
WINDOW_SPAN = SPAN_PREFIX + "window"
NO_SPAN = "no harness span"
_CALL_SUFFIX = re.compile(r"\(\d+\)$")


def _short(name: str) -> str:
    """A module's name without its call id, an op's HLO instruction name
    without its text: ``jit_f(123)`` → ``jit_f``, ``%fusion.4 = f32[..]
    fusion(..)`` → ``%fusion.4``."""
    return _CALL_SUFFIX.sub("", name.split(" = ", 1)[0])


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def events_from_xplane(path: str) -> list:
    """The trace's module, op and harness-span events."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PREFIX):
            kinds = {MODULES_LINE: "module", OPS_LINE: "op"}
            for line in plane.lines:
                kind = kinds.get(line.name)
                if kind is None:
                    continue
                for e in line.events:
                    out.append((kind, _short(e.name), int(e.start_ns),
                                int(e.end_ns), plane.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        out.append(("span", e.name, int(e.start_ns),
                                    int(e.end_ns), line.name))
    return out


def save_events(events: list, path: str) -> None:
    with open(path, "w") as f:
        json.dump([list(e) for e in events], f)


def load_events_json(path: str) -> list:
    with open(path) as f:
        return [tuple(e) for e in json.load(f)]


def _union(intervals):
    """Sorted, merged copy of ``intervals`` ((start, end) pairs)."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclasses.dataclass
class Summary:
    """What one traced window shows, averaged over the device planes."""
    window_s: float
    busy_s: float
    n_devices: int
    program_s: dict        # program name -> device seconds in the window
    program_runs: dict     # program name -> runs that started in it
    op_s: dict             # device op name -> seconds in the window
    gaps: list             # (label, seconds) of every idle gap, longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program(self, key: str) -> tuple[float, int]:
        """(device seconds, runs) of the programs whose name holds ``key``."""
        names = [n for n in self.program_s if key in n]
        return (sum(self.program_s[n] for n in names),
                sum(self.program_runs[n] for n in names))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.gaps[:top]]}


def _label(spans, t):
    """The innermost harness span open at ``t``: of those that cover it,
    the latest to start, and of those the first to end."""
    best = None
    for s, e, name in spans:
        if s <= t < e and (best is None or (s, -e) > best[0]):
            best = ((s, -e), name)
    return best[1][len(SPAN_PREFIX):] if best else NO_SPAN


def reduce(events: list) -> Summary:
    """Reduce flattened events to a :class:`Summary`.  The window is the
    harness's ``cb:window`` span, or the device's first to last event when
    the trace has none."""
    spans = sorted((s, e, n) for k, n, s, e, _ in events if k == "span")
    devices = sorted({t for k, _, _, _, t in events if k in ("module", "op")})
    if not devices:
        raise ValueError("the trace holds no device event")
    win = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if win:
        lo, hi = win[0]
    else:
        lo = min(s for k, _, s, _, _ in events if k in ("module", "op"))
        hi = max(e for k, _, _, e, _ in events if k in ("module", "op"))
    nd = len(devices)
    busy = 0.0
    program_s, program_runs, op_s = {}, {}, {}
    gaps = []
    for dev in devices:
        ops = [(s, e, n) for k, n, s, e, t in events
               if k == "op" and t == dev]
        mods = [(s, e, n) for k, n, s, e, t in events
                if k == "module" and t == dev]
        busy_iv = _union(_clip([(s, e) for s, e, _ in (ops or mods)],
                               lo, hi))
        busy += sum(e - s for s, e in busy_iv) / 1e9
        for s, e, n in mods:
            c = _clip([(s, e)], lo, hi)
            if c:
                program_s[n] = program_s.get(n, 0.0) + (c[0][1] - c[0][0]) / 1e9
            if lo <= s < hi:
                program_runs[n] = program_runs.get(n, 0) + 1
        for s, e, n in ops:
            c = _clip([(s, e)], lo, hi)
            if c:
                op_s[n] = op_s.get(n, 0.0) + (c[0][1] - c[0][0]) / 1e9
        edge = lo
        for s, e in busy_iv + [(hi, hi)]:
            if s > edge:
                gaps.append((_label(spans, (edge + s) / 2), (s - edge) / 1e9))
            edge = max(edge, e)
    avg = {n: s / nd for n, s in program_s.items()}
    runs = {n: r / nd for n, r in program_runs.items()}
    ops_avg = {n: s / nd for n, s in op_s.items()}
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy / nd, n_devices=nd,
                   program_s=avg, program_runs=runs, op_s=ops_avg, gaps=gaps)

