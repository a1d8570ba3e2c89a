"""Mixed-pattern serving demo: heterogeneous solve traffic through
``SolverService`` with a persistent plan cache.

Production traffic is not one pre-analyzed pattern: a serving process sees
circuit matrices next to banded PDE operators next to general unsymmetric
systems, interleaved arbitrarily.  This demo builds exactly such a stream
and pushes it through the serving stack three times:

  cold    first touch of every pattern: fingerprint → plan-cache miss →
          host analyze → artifact persisted → XLA compile → solve
  warm    same patterns, new values: every plan + compiled engine is an
          in-memory cache hit — only the solves remain
  fresh   a NEW SolverService over the same cache directory (simulating a
          restarted process): plans load from checkpoints/ (the analyze
          phase is skipped; the counter proves it) and only XLA compile is
          re-paid — which the persistent jax compilation cache absorbs in
          real deployments

    PYTHONPATH=src python examples/mixed_pattern_serving.py \
        [--requests 24] [--batch-size 8] [--devices 2] \
        [--cache-dir checkpoints/plan_cache_demo]
"""
import argparse
import os
import sys
import time

import numpy as np

import jax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmarks"))

from repro.core import CSR, HyluOptions
from repro.launch.compile_cache import enable_compilation_cache
from repro.launch.mesh import ensure_devices
from repro.serve.solver_service import SolverService, SolveRequest


def patterns(scale=1.0):
    """Three structurally distinct workloads (the serving mix)."""
    from matrices import banded, circuit_like, unsym_random
    return [
        ("circuit", CSR.from_scipy(circuit_like(int(200 * scale), 1)
                                   .tocsr())),
        ("banded", CSR.from_scipy(banded(int(150 * scale), 6, 2).tocsr())),
        ("unsym", CSR.from_scipy(unsym_random(int(120 * scale), 0.02, 8)
                                 .tocsr())),
    ]


def make_stream(pats, n_requests, seed):
    """Interleaved, shuffled requests with per-request value drift."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n_requests):
        name, Ac = pats[i % len(pats)]
        reqs.append(SolveRequest(
            a=CSR(Ac.n, Ac.indptr, Ac.indices,
                  Ac.data * rng.uniform(0.9, 1.1, Ac.nnz)),
            b=rng.normal(size=Ac.n), tag=name))
    rng.shuffle(reqs)
    return reqs


def run_window(svc, reqs, label):
    t0 = time.perf_counter()
    res = svc.solve_batch(reqs)
    dt = time.perf_counter() - t0
    worst = max(float(np.max(r.residual)) for r in res)
    cs = svc.cache.stats
    print(f"[{label:5s}] {len(reqs):3d} requests in {dt:7.2f}s "
          f"({len(reqs) / dt:8.1f} req/s)  worst resid {worst:.1e}  "
          f"cache: mem={cs['hits']} disk={cs['disk_hits']} "
          f"analyze={cs['analyze_calls']}")
    assert worst < 1e-8
    return res


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=1,
                    help="shard dispatches over N devices (a TPU host's "
                         "chips, else virtual CPU devices)")
    ap.add_argument("--requests", type=int, default=24,
                    help="requests per serving window")
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--cache-dir", default="checkpoints/plan_cache_demo")
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--no-jax-cache", action="store_true",
                    help="turn the persistent JAX compilation cache off "
                         "(with it, the 'fresh' window pays neither "
                         "analyze nor compile)")
    args = ap.parse_args(argv)
    if args.devices > 1:
        ensure_devices(args.devices)    # before anything touches devices
    jax.config.update("jax_enable_x64", True)
    jc = enable_compilation_cache(not args.no_jax_cache)
    if jc:
        print(f"[jax] persistent compilation cache at {jc}")

    opts = HyluOptions(mesh=args.devices if args.devices > 1 else None)
    pats = patterns(args.scale)
    print(f"serving mix: "
          + ", ".join(f"{n} (n={A.n}, nnz={A.nnz})" for n, A in pats)
          + (f"  [mesh over {args.devices} devices]"
             if args.devices > 1 else ""))

    svc = SolverService(opts=opts, cache_dir=args.cache_dir,
                        batch_size=args.batch_size)
    run_window(svc, make_stream(pats, args.requests, seed=1), "cold")
    run_window(svc, make_stream(pats, args.requests, seed=2), "warm")

    # a restarted process: new service, same artifact store
    svc2 = SolverService(opts=opts, cache_dir=args.cache_dir,
                         batch_size=args.batch_size)
    run_window(svc2, make_stream(pats, args.requests, seed=3), "fresh")
    assert svc2.cache.stats["analyze_calls"] == 0, \
        "fresh process should load every plan from the artifact store"
    assert svc2.cache.stats["disk_hits"] == len(pats)

    modes = {name: svc.pattern_modes[
        svc.cache.fingerprint(Ac, opts)] for name, Ac in pats}
    print(f"kernel routing: {modes}")
    print(f"artifact store: {args.cache_dir} "
          f"({len(os.listdir(args.cache_dir))} plans)")
    print("MIXED_PATTERN_SERVING_OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
