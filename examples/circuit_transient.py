"""Repeated-solve production scenario (paper §3.2): transient circuit
simulation — one analysis, many refactor+solve steps — on all three
repeated-solve engines, plus a batched Monte-Carlo corner sweep.

A linear RC network driven by a time-varying source, backward-Euler
integration:  (G + C/dt) v_t = C/dt v_{t-1} + i(t).
The conductance matrix values change every Newton/time step (here: dt
modulation) while the sparsity pattern is fixed — exactly HYLU's
repeated-solve optimization.  The three paths:

  ref          numpy reference engine (looped refactor + solve)
  jax          pre-compiled XLA refactor/solve per step (engine="jax";
               one compile, then every step is two XLA calls)
  jax-batched  K Monte-Carlo conductance corners factored + solved as ONE
               vmapped XLA program (solve_sequence) — the corner-analysis
               workload circuit simulators batch in production

plus the multi-device finale: a T-step × K-corner sweep through the async
double-buffered ``solve_sequence`` pipeline, sharded over the system-batch
axis when more than one device is available (``--devices N`` takes the
chips of a TPU host, or forces N virtual CPU devices on the CPU platform
before jax initializes, which this script does) and with buffer donation
keeping the refactor stream allocation-flat.

    PYTHONPATH=src python examples/circuit_transient.py \
        [--n 240] [--steps 20] [--corners 32] [--devices 2]
"""
import argparse
import os
import sys
import time

import numpy as np

import jax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmarks"))

from repro.core import (CSR, HyluOptions, analyze, factor, refactor, solve,
                        solve_sequence)
from repro.launch.compile_cache import enable_compilation_cache
from repro.launch.mesh import ensure_devices


def rc_network(n, seed=0):
    from matrices import circuit_like
    g = circuit_like(n, seed).tocsr()
    rng = np.random.default_rng(seed)
    c = rng.uniform(1e-12, 1e-9, n)          # node capacitances
    return g, c


def transient(an, A0, c, n_steps, dt, engine):
    """Backward-Euler time stepping on one engine; returns (v, timings)."""
    n = A0.n
    rng = np.random.default_rng(7)
    diag_idx = np.where(A0.indices == np.repeat(
        np.arange(n), np.diff(A0.indptr)))[0]
    v = np.zeros(n)
    st = None
    t_fac = t_sol = 0.0
    for step in range(n_steps):
        dt_k = dt * (1.0 + 0.5 * np.sin(step / 5.0))     # variable step
        data = A0.data.copy()
        data[diag_idx] += c / dt_k
        Ak = CSR(n, A0.indptr, A0.indices, data)
        t0 = time.perf_counter()
        st = refactor(st, Ak) if st is not None else factor(an, Ak,
                                                            engine=engine)
        t_fac += time.perf_counter() - t0
        i_src = np.zeros(n)
        i_src[rng.integers(0, n, 5)] = rng.normal(size=5)
        rhs = c / dt_k * v + i_src
        t0 = time.perf_counter()
        v, info = solve(st, rhs)
        t_sol += time.perf_counter() - t0
        assert info["residual"] < 1e-8, (engine, step, info)
    return v, t_fac, t_sol


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=240)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--corners", type=int, default=32)
    ap.add_argument("--devices", type=int, default=1,
                    help="shard the batched sweeps over N devices (a TPU "
                         "host's chips, else virtual CPU devices)")
    args = ap.parse_args(argv)
    if args.devices > 1:
        ensure_devices(args.devices)    # before anything touches devices
    jax.config.update("jax_enable_x64", True)
    enable_compilation_cache()
    n, n_steps = args.n, args.steps
    dt = 1e-6

    g, c = rc_network(n)
    A0 = CSR.from_scipy(g)

    t0 = time.perf_counter()
    an = analyze(A0)
    t_analyze = time.perf_counter() - t0
    print(f"analysis: {t_analyze*1e3:.0f} ms "
          f"(n={n}, mode={an.choice.mode}, ordering={an.ordering_name})")

    # ---- sequential transient: ref vs jitted-jax --------------------------
    v_ref, fac_ref, sol_ref = transient(an, A0, c, n_steps, dt, "ref")
    print(f"[ref]  {n_steps} steps: refactor {fac_ref*1e3:7.1f} ms, "
          f"solve {sol_ref*1e3:7.1f} ms")

    t0 = time.perf_counter()
    st_warm = factor(an, A0, engine="jax")    # compile refactor, up front
    solve(st_warm, np.zeros(n))               # compile the solve path too
    t_compile = time.perf_counter() - t0
    v_jax, fac_jax, sol_jax = transient(an, A0, c, n_steps, dt, "jax")
    print(f"[jax]  {n_steps} steps: refactor {fac_jax*1e3:7.1f} ms, "
          f"solve {sol_jax*1e3:7.1f} ms "
          f"(+{t_compile:.1f}s one-time compile) — "
          f"{(fac_ref+sol_ref)/(fac_jax+sol_jax):.1f}x vs ref per step")
    assert np.abs(v_ref - v_jax).max() <= 1e-8 * (1 + np.abs(v_ref).max())

    # ---- batched Monte-Carlo corner sweep: one vmapped XLA program --------
    k = args.corners
    rng = np.random.default_rng(42)
    vb = A0.data[None, :] * rng.uniform(0.8, 1.2, (k, A0.nnz))
    i_dc = np.zeros(n)
    i_dc[rng.integers(0, n, 8)] = rng.normal(size=8)
    t0 = time.perf_counter()
    x, info = solve_sequence(A0, vb, i_dc)
    t_batch = time.perf_counter() - t0
    print(f"[jax-batched] {k} conductance corners, one XLA program: "
          f"{t_batch*1e3:.0f} ms total (incl. compile), "
          f"max residual {float(info['residual'].max()):.2e}")
    assert float(info["residual"].max()) < 1e-8

    # per-corner spread of the DC operating point — the payoff of the sweep
    spread = np.abs(x).max(axis=1)
    print(f"corner spread of |v|max: {spread.min():.3e} … {spread.max():.3e}")

    # ---- multi-RHS: per-corner sensitivity to M independent source sets —
    # b of shape (K, n, M) rides the same fused solve+refinement program ----
    m_src = 4
    bm = np.zeros((k, n, m_src))
    for j in range(m_src):
        bm[:, rng.integers(0, n, 6), j] = rng.normal(size=6)
    t0 = time.perf_counter()
    xs, info_m = solve_sequence(A0, vb, bm)
    t_multi = time.perf_counter() - t0
    print(f"[jax-batched] multi-RHS sensitivity sweep x{m_src}: "
          f"x {xs.shape}, residual (K, M) max "
          f"{float(info_m['residual'].max()):.2e}, {t_multi*1e3:.0f} ms")
    assert xs.shape == (k, n, m_src)
    assert float(info_m["residual"].max()) < 1e-8

    # ---- sharded async pipeline: T transient steps × K corners ------------
    # Each step's K corner matrices are factored+solved as one (sharded)
    # XLA program while the host stages the next step's values; donation
    # keeps the refactor stream allocation-flat.  RHS here are per-step
    # source vectors (independent across steps, so nothing serializes the
    # pipeline — the corner-sweep-over-a-transient workload).
    n_dev = min(args.devices, len(jax.devices()))
    t_seq_steps = min(args.steps, 8)
    diag_idx = np.where(A0.indices == np.repeat(
        np.arange(n), np.diff(A0.indptr)))[0]
    steps_v, steps_b = [], []
    for step in range(t_seq_steps):
        dt_k = dt * (1.0 + 0.5 * np.sin(step / 5.0))
        data = A0.data.copy()
        data[diag_idx] += c / dt_k
        steps_v.append(data[None, :] * rng.uniform(0.8, 1.2, (k, A0.nnz)))
        b_t = np.zeros((k, n))
        b_t[:, rng.integers(0, n, 5)] = rng.normal(size=5)
        steps_b.append(b_t)
    opts_seq = HyluOptions(mesh=(n_dev if n_dev > 1 else None), donate=True)
    t0 = time.perf_counter()
    xt, info_t = solve_sequence(A0, steps_v, steps_b, opts_seq)
    t_seq = time.perf_counter() - t0
    print(f"[jax-sharded] {t_seq_steps} steps x {k} corners on "
          f"{n_dev} device(s), double-buffered+donating pipeline: "
          f"x {xt.shape}, max residual {float(info_t['residual'].max()):.2e}, "
          f"{t_seq*1e3:.0f} ms total (incl. analysis+compile)")
    assert xt.shape == (t_seq_steps, k, n)
    assert float(info_t["residual"].max()) < 1e-8
    print("OK")


if __name__ == "__main__":
    main()
