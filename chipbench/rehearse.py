#!/usr/bin/env python3
"""Compile a cell's refactor and fused-solve programs for a described TPU
v5e on a host without one, and print their compile seconds and
``memory_analysis()`` bytes.  Nothing runs, so this gives no device time.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --workload circuit-sweep

``--config`` merges into the cell's configuration, as in ``sweeps.py``:
a configuration that no cell runs yet is rehearsed through a cell of the
same traffic, for example

    --workload fem2d-sweep --config '{"pattern": {"generator": "fem3d",
        "args": {"nx": 22, "ny": 22, "nz": 22, "seed": 931}}}'
"""
import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes")}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--k", type=int, default=None,
                    help="systems per program (default: the cell's)")
    ap.add_argument("--config", help="JSON merged into the configuration")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import harness, patterns
    from repro.core import CSR
    from repro.core.analysis import jax_repeated_engine
    from repro.core.options import resolve_refine_tol
    from repro.core.plan_cache import PlanCache

    jax.config.update("jax_enable_x64", True)
    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(args.workload)
    config = harness._merge(cell.config, json.loads(args.config or "{}"))
    k = args.k or int(cell.traffic["systems_per_step"])
    opts = harness.solver_options(config)
    a_sp = patterns.build(config["pattern"])
    a = CSR.from_scipy(a_sp)
    an = PlanCache(directory=str(harness.PLAN_CACHE_DIR)).get_or_analyze(
        a, opts)
    eng = jax_repeated_engine(an)
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    vdt = jnp.dtype(eng.values_dtype)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    out = {"workload": args.workload, "pattern": config["pattern"], "k": k,
           "mode": an.choice.mode, "n": a.n, "nnz": a.nnz}
    values = sds((k, a.nnz), vdt)
    t0 = time.perf_counter()
    lowered = eng.refactor_batched.lower(values)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    out["refactor"] = dict(lower_s=t1 - t0,
                           compile_s=time.perf_counter() - t1,
                           module=lowered.as_text().split("\n", 1)[0][:80],
                           **_mem(compiled))
    jf = jax.eval_shape(eng.refactor_batched, values)
    solver = eng.refined_batched_solver(a.indptr, a.indices)
    t0 = time.perf_counter()
    lowered = solver.lower(
        sds(jf.vals.shape, jf.vals.dtype),
        sds(jf.inode_perm.shape, jf.inode_perm.dtype), values,
        sds((k, a.n), vdt), opts.refine_max_iter,
        resolve_refine_tol(opts, eng.refine_dtype))
    t1 = time.perf_counter()
    compiled = lowered.compile()
    out["fused_solve"] = dict(lower_s=t1 - t0,
                              compile_s=time.perf_counter() - t1,
                              module=lowered.as_text().split("\n", 1)[0][:80],
                              **_mem(compiled))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
