"""CI guard: scalar-refactor compile time on circuit_200 must stay under a
generous ceiling so O(nodes+edges) trace-size blowups can't silently
return (the pre-bucketed engine took 70+ s here; the level-bucketed trace
takes single-digit seconds).

Runs with the persistent compilation cache turned off — the measurement
must be a *cold* compile.

    PYTHONPATH=src python -m benchmarks.compile_budget [--ceiling 120]
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--ceiling", type=float, default=120.0,
                    help="hard compile-time ceiling in seconds")
    args = ap.parse_args(argv)

    import jax
    jax.config.update("jax_enable_x64", True)
    # never reuse a warm cache for the guard
    from repro.launch.compile_cache import enable_compilation_cache
    enable_compilation_cache(enabled=False)

    from repro.core.matrix import CSR
    from repro.core.api import analyze, factor, solve

    from . import matrices

    a = CSR.from_scipy(matrices.circuit_like(200, 1).tocsr())
    an = analyze(a)
    b = np.random.default_rng(0).normal(size=a.n)
    t0 = time.perf_counter()
    st = factor(an, a, engine="jax")
    x, info = solve(st, b)
    elapsed = time.perf_counter() - t0
    ok = elapsed <= args.ceiling
    print(f"[compile-budget] circuit_200 scalar refactor+solve compile: "
          f"{elapsed:.1f}s (ceiling {args.ceiling:.0f}s) "
          f"residual={info['residual']:.1e} → {'OK' if ok else 'FAIL'}")
    if not ok:
        print("trace-size blowup: the factor/solve trace is no longer "
              "O(levels × buckets) — check jax_engine.make_factor_fn and "
              "structure.build_bucket_schedule", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
