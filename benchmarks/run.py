"""Benchmark harness — one experiment per paper table/figure.

  Fig 4   preprocessing time, one-time solve
  Fig 5   numerical factorization, one-time
  Fig 6   forward/backward substitution, one-time
  Fig 7   total one-time solve
  Fig 8   numerical (re)factorization, repeated solve
  Fig 9   substitution, repeated solve
  Fig 10  factorization+substitution total, repeated solve
  Fig 11  residual ‖Ax−b‖₁/‖b‖₁

Solvers:
  hylu          — hybrid kernels + smart selection (the paper)
  klu_like      — row-row only internal baseline (KLU design point)
  pardiso_like  — supernodal-only internal baseline (PARDISO design point)
  superlu       — scipy.sparse.linalg.splu (SuperLU; the paper's ref [2]),
                  external C-compiled reference

The paper's headline claims are geomean speedups of hylu over the
level-3-BLAS supernodal design point (2.36× one-time / 2.90× repeated
factorization) and stability across sparsity classes; we report the same
geomeans over the internal baselines (identical engine, only the kernel
strategy differs — a controlled comparison) plus SuperLU absolute numbers
for external reference.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--figures 5,8,11]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np
import scipy.sparse.linalg as spla

from repro.core.api import analyze, factor, refactor, solve
from repro.core import baselines as B
from repro.core.matrix import CSR

from . import matrices

SOLVERS = ["hylu", "klu_like", "pardiso_like", "superlu"]


def geomean(xs):
    xs = [x for x in xs if x and np.isfinite(x) and x > 0]
    return float(np.exp(np.mean(np.log(xs)))) if xs else float("nan")


def bench_matrix(name, Ac, a_sp):
    """Run every solver on one matrix; return timing/accuracy records."""
    rng = np.random.default_rng(0)
    b = rng.normal(size=Ac.n)
    out = {}
    opts = {"hylu": B.hylu_options(), "klu_like": B.klu_like_options(),
            "pardiso_like": B.pardiso_like_options()}
    an0 = None
    for sname in ("hylu", "klu_like", "pardiso_like"):
        t0 = time.perf_counter()
        # matching+ordering are mode-independent: computed once (hylu run),
        # then reused — their cost is included in every mode's `pre` time
        # via t_shared so per-solver preprocessing stays honest.
        an = analyze(Ac, opts[sname], reuse=an0)
        t_pre = time.perf_counter() - t0
        if an0 is None:
            an0 = an
            t_shared = an.timings["matching"] + an.timings["ordering"]
        else:
            t_pre += t_shared
        # fill-blowup guard: when a forced-supernodal plan predicts >25× the
        # hybrid plan's padded flops (the ASIC/circuit5M phenomenon the
        # paper reports for PARDISO), record the ratio instead of burning
        # hours in the reference engine.
        if (sname == "pardiso_like"
                and an.plan.padded_flops > 25 * max(an0.plan.padded_flops, 1)):
            ratio = an.plan.padded_flops / max(an0.plan.padded_flops, 1)
            out[sname] = dict(pre=t_pre, fac=None, sub=None, refac=None,
                              sub2=None, resid=None,
                              mode=f"fill-blowup({ratio:.0f}x flops)",
                              n_perturb=0, flops_ratio_vs_hylu=ratio)
            continue
        t0 = time.perf_counter()
        st = factor(an, Ac)
        t_fac = time.perf_counter() - t0
        x, info = solve(st, b)
        t_sub = info["solve_time"]
        # repeated solve: new values, same pattern
        a2 = Ac.data * rng.uniform(0.9, 1.1, Ac.nnz)
        A2 = CSR(Ac.n, Ac.indptr, Ac.indices, a2)
        t0 = time.perf_counter()
        st2 = refactor(st, A2)
        t_refac = time.perf_counter() - t0
        x2, info2 = solve(st2, b)
        out[sname] = dict(pre=t_pre, fac=t_fac, sub=t_sub, refac=t_refac,
                          sub2=info2["solve_time"], resid=info["residual"],
                          mode=an.choice.mode, n_perturb=info["n_perturb"])
    # SuperLU external reference
    t0 = time.perf_counter()
    lu = spla.splu(a_sp.tocsc())
    t_fac = time.perf_counter() - t0
    t0 = time.perf_counter()
    x = lu.solve(b)
    t_sub = time.perf_counter() - t0
    resid = float(np.abs(a_sp @ x - b).sum() / np.abs(b).sum())
    t0 = time.perf_counter()
    spla.splu(a_sp.tocsc())              # SuperLU exposes no refactor API
    t_refac = time.perf_counter() - t0
    out["superlu"] = dict(pre=0.0, fac=t_fac, sub=t_sub, refac=t_refac,
                          sub2=t_sub, resid=resid, mode="superlu",
                          n_perturb=0)
    return out


FIGS = {
    4: ("preprocessing (one-time)", lambda r: r["pre"]),
    5: ("numerical factorization (one-time)", lambda r: r["fac"]),
    6: ("substitution (one-time)", lambda r: r["sub"]),
    7: ("total one-time", lambda r: r["pre"] + r["fac"] + r["sub"]),
    8: ("factorization (repeated)", lambda r: r["refac"]),
    9: ("substitution (repeated)", lambda r: r["sub2"]),
    10: ("fac+sub total (repeated)", lambda r: r["refac"] + r["sub2"]),
    11: ("residual |Ax-b|1/|b|1", lambda r: r["resid"]),
}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--figures", default="4,5,6,7,8,9,10,11")
    ap.add_argument("--out", default="artifacts/bench")
    ap.add_argument("--repeated-k", type=int, default=32,
                    help="K value sets for the repeated-solve engine bench")
    ap.add_argument("--no-repeated", action="store_true",
                    help="skip the jax/batched repeated-solve engine bench")
    ap.add_argument("--no-jax-cache", action="store_true",
                    help="turn the persistent JAX compilation cache off "
                         "(it otherwise lives at $JAX_COMPILATION_CACHE_DIR "
                         "or <repo>/.jax_cache)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compilation_cache
    cache = enable_compilation_cache(not args.no_jax_cache)
    if cache:
        print(f"[jax] persistent compilation cache at {cache}")
    figs = [int(f) for f in args.figures.split(",")]
    scale = 0.15 if args.quick else 0.35
    os.makedirs(args.out, exist_ok=True)

    records = {}
    t_all = time.time()
    for name_fn in matrices.suite(scale=scale):
        name, Ac, a_sp = matrices.load(name_fn)
        t0 = time.time()
        records[name] = bench_matrix(name, Ac, a_sp)
        records[name]["_meta"] = dict(n=Ac.n, nnz=Ac.nnz)
        print(f"[bench] {name:20s} n={Ac.n:7d} nnz={Ac.nnz:8d} "
              f"mode={records[name]['hylu']['mode']:10s} "
              f"({time.time()-t0:.1f}s)", flush=True)

    print(f"\nsuite done in {time.time()-t_all:.0f}s — "
          f"{len(records)} matrices\n")

    summary = {}
    for fig in figs:
        title, get = FIGS[fig]
        print(f"=== Fig {fig}: {title} ===")
        print(f"{'matrix':20s} " + " ".join(f"{s:>13s}" for s in SOLVERS))
        speed = {s: [] for s in SOLVERS}

        def safe_get(r):
            try:
                v = get(r)
                return v if v is not None else float("nan")
            except TypeError:
                return float("nan")

        for name, rec in records.items():
            row = [safe_get(rec[s]) for s in SOLVERS]
            print(f"{name:20s} " + " ".join(f"{v:13.4g}" for v in row))
            if fig != 11 and row[0] > 0:
                for s, v in zip(SOLVERS, row):
                    if np.isfinite(v):
                        speed[s].append(v / row[0])
        if fig != 11:
            gm = {s: geomean(speed[s]) for s in SOLVERS if s != "hylu"}
            print(f"{'geomean speedup of hylu':24s} " +
                  "  ".join(f"vs {s}: {v:.2f}x" for s, v in gm.items()))
            summary[f"fig{fig}"] = gm
        else:
            gm = {s: geomean([safe_get(rec[s]) for rec in records.values()])
                  for s in SOLVERS}
            print("geomean residuals:", {k: f"{v:.2e}" for k, v in gm.items()})
            summary["fig11"] = gm
        print()

    with open(os.path.join(args.out, "bench_results.json"), "w") as f:
        json.dump(dict(records=records, summary=summary), f, indent=1,
                  default=str)
    print(f"results → {args.out}/bench_results.json")

    # repeated-solve engine comparison (looped-ref vs jitted/batched jax) —
    # the machine-readable perf trajectory for the repeated-solve path
    if 8 in figs and not args.no_repeated:
        from .bench_factor_repeated import bench_repeated
        bench_repeated(k=args.repeated_k, quick=args.quick,
                       out_path=os.path.join(args.out, "BENCH_repeated.json"))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
