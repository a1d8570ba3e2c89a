#!/usr/bin/env python3
"""The runs that fixed the benchmark's sizes and limits, kept so that
they can be made again.  One command runs one cell on several seeds in ONE
process (one chip, one compile), printing each run's result line;
``--config`` / ``--traffic`` merge into the cell's files: the sizing runs
(another n or K) and the control (a lower precision) use them.

    python3 chipbench/sweeps.py --workload circuit-sweep --seeds 1,2,3 \
        [--seconds s] [--trace] [--config JSON] [--traffic JSON] [--cpu]
"""
import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from chipbench.harness import run_cell  # noqa: E402


def _runs(args, memo):
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        r = run_cell(args.workload, seed, args.seconds, args.trace,
                     config_over=json.loads(args.config or "{}"),
                     traffic_over=json.loads(args.traffic or "{}"),
                     save_events=args.save_events,
                     memo=memo, t_start=t0, device_check=not args.cpu,
                     compile_cache=not args.cpu)
        r["seed"] = seed
        r["run_s"] = time.perf_counter() - t0
        r["counters"] = {k: v for k, v in memo["last_counters"].items()
                         if not isinstance(v, list) or len(v) <= 64}
        print(json.dumps(r), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--config")
    ap.add_argument("--traffic")
    ap.add_argument("--save-events")
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse on the CPU: no device check, no "
                         "compilation cache; its times are not device times")
    args = ap.parse_args(argv)
    _runs(args, {})
    return 0


if __name__ == "__main__":
    sys.exit(main())
