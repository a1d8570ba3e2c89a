"""Serve: the async solver-server entrypoint with a built-in load generator.

Stands up an :class:`AsyncSolverServer` over a :class:`SolverService` and
drives it with the fault-injection harness's mixed-pattern stream
(``repro.serve.faultinject``) — healthy circuit/banded/denseish systems
interleaved with the full fault matrix at ``--fault-rate``.  Prints a
serving report (throughput, p50/p99 latency, deadline-miss / reject /
quarantine rates, per-status outcome counts) and exits nonzero if the
robustness contract is violated (a lost request, a silently-wrong
solution, or a healthy request off fp64-oracle parity).

    PYTHONPATH=src python -m repro.launch.serve --requests 200 \
        --batch-size 8 --fault-rate 0.2 --deadline-ms 200

This is the runnable face of ROADMAP item 3; the ``--serving-async``
section of ``benchmarks/bench_factor_repeated.py`` records the same
numbers into BENCH_repeated.json for the perf trajectory.
"""
from __future__ import annotations

import argparse
import asyncio
import sys
import time


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--requests", type=int, default=500,
                   help="stream length (default 500)")
    p.add_argument("--n", type=int, default=32,
                   help="system size per request (default 32)")
    p.add_argument("--batch-size", type=int, default=8,
                   help="dispatch batch size (default 8)")
    p.add_argument("--fault-rate", type=float, default=0.2,
                   help="fraction of the stream replaced by injected "
                        "faults (default 0.2; 0 = pure healthy load)")
    p.add_argument("--deadline-ms", type=float, default=None,
                   help="default per-request latency budget (default: "
                        "none)")
    p.add_argument("--max-queue-per-group", type=int, default=64,
                   help="bounded per-pattern queue depth (default 64)")
    p.add_argument("--max-pending", type=int, default=1024,
                   help="global admission bound (default 1024)")
    p.add_argument("--max-linger-ms", type=float, default=50.0,
                   help="flush a non-empty window at most this long after "
                        "its oldest request arrived (default 50)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--devices", type=int, default=None,
                   help="shard dispatches over the first N jax devices")
    return p


async def _serve_and_drive(args) -> dict:
    from repro.core.options import HyluOptions
    from repro.serve.solver_service import SolverService
    from repro.serve.async_server import AsyncSolverServer
    from repro.serve import faultinject

    opts = HyluOptions(deadline_ms=args.deadline_ms,
                       mesh=(args.devices if args.devices
                             and args.devices > 1 else None))
    service = SolverService(opts=opts, cache_dir=None,
                            batch_size=args.batch_size)
    stream = faultinject.make_stream(args.requests,
                                     fault_rate=args.fault_rate,
                                     seed=args.seed, n=args.n)
    async with AsyncSolverServer(
            service,
            max_queue_per_group=args.max_queue_per_group,
            max_pending=args.max_pending,
            max_linger_ms=args.max_linger_ms,
            default_deadline_ms=args.deadline_ms) as server:
        t0 = time.perf_counter()
        report = await faultinject.run_stream(server, stream)
        report["wall_s"] = time.perf_counter() - t0
    return report


def print_report(report: dict, file=sys.stdout) -> None:
    s = report["server_stats"]
    n = report["n_requests"]
    wall = report.get("wall_s") or 1e-9

    def fmt(v, spec=".2f"):
        return "n/a" if v is None else format(v, spec)

    print(f"serve: {n} requests in {wall:.2f}s "
          f"({n / wall:.1f} req/s)", file=file)
    print(f"  outcomes: {report['by_status']}", file=file)
    print(f"  lost: {report['lost']}   "
          f"healthy fp64-oracle worst rel err: "
          f"{report['worst_healthy_err']:.3e} "
          f"({report['n_healthy_checked']} checked)", file=file)
    print(f"  latency: p50 {fmt(s['p50_ms'])} ms, p99 {fmt(s['p99_ms'])} ms"
          f"   deadline-miss rate: {s['deadline_miss_rate']:.3f}",
          file=file)
    print(f"  reject rate: {s['reject_rate']:.3f} "
          f"(queue-full {s['rejected_full']}, "
          f"invalid {s['rejected_invalid']})   "
          f"retries: {s['retries']}   quarantined: {s['quarantined']}",
          file=file)
    print(f"  dispatch batches: {s['dispatch_batches']}   "
          f"queue depth at exit: {s['queue_depth']}", file=file)


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.devices and args.devices > 1:
        from repro.launch.mesh import ensure_devices
        ensure_devices(args.devices)    # before anything touches devices

    import jax
    jax.config.update("jax_enable_x64", True)
    from repro.launch.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    report = asyncio.run(_serve_and_drive(args))
    print_report(report)

    from repro.serve.faultinject import check_report
    violations = check_report(report)
    if violations:
        print(f"\nFAIL: {len(violations)} robustness-contract "
              f"violation(s):", file=sys.stderr)
        for v in violations[:20]:
            print(f"  - {v}", file=sys.stderr)
        return 1
    print("\nOK: every request got exactly one terminal result; healthy "
          "traffic at fp64-oracle parity.")
    return 0


if __name__ == "__main__":
    sys.exit(main())
