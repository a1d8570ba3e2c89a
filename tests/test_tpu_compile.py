"""Compile-only checks against a described TPU v5e: the Pallas kernels of
the factor and solve paths, compiled by Mosaic (``interpret=False``), and
the batched refactor plus fused refined solve compiled by XLA:TPU.

Nothing here runs on a chip: the compiler is told about a ``v5e:2x2``
topology that is not attached and compiles for its first chip, which
raises what the chip's compiler would raise.  The topology is described
inside a module fixture — never while a module is imported — so that only
the worker that runs this file loads the TPU library.  The persistent
compilation cache is off around the compiles: an entry written for a
described chip cannot be read back without one.

Plus two CPU tests of where kernels run: the platform decides it
(:func:`repro.kernels.interpret_mode`), and an engine that would hand
float64 operands to Mosaic is refused when it is built.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import CSR, HyluOptions, analyze
from repro.core.analysis import jax_repeated_engine
from repro.kernels import interpret_mode

from tests.helpers import circuit_system

# fem2d_10k (benchmarks/corpus.py) under HyluOptions() defaults: its largest
# panel bucket gathers (5, 32, 448) panels with elimination window wu=104,
# and its largest sup-sup edge bucket holds E=4 edges of nr=32 target rows
# against a k=64 source block with an m=104 U suffix.
PANEL_BUCKET = (5, 32, 448)
PANEL_WU = 104
EDGE_E, EDGE_NR, EDGE_K, EDGE_M = 4, 32, 64, 104
TRSM_K = 128                     # max_super: the widest diagonal block


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _kernel_text(fn, *specs) -> str:
    compiled = jax.jit(fn).lower(*specs).compile()
    return compiled.as_text()


def test_panel_lu_bucketed_compiles(one_chip):
    from repro.kernels.panel.kernel import panel_lu_bucketed_p

    text = _kernel_text(
        lambda p, e: panel_lu_bucketed_p(p, e, PANEL_WU, interpret=False),
        _spec(one_chip, PANEL_BUCKET), _spec(one_chip, ()))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", ["bmm", "gemm_update"])
def test_supsup_kernels_compile(one_chip, kernel):
    from repro.kernels.supsup.kernel import bmm, gemm_update

    if kernel == "bmm":
        text = _kernel_text(
            lambda a, b: bmm(a, b, interpret=False),
            _spec(one_chip, (EDGE_E, EDGE_NR, EDGE_K)),
            _spec(one_chip, (EDGE_E, EDGE_K, EDGE_M)))
    else:
        text = _kernel_text(
            lambda c, a, b: gemm_update(c, a, b, interpret=False),
            _spec(one_chip, (EDGE_NR, EDGE_M)),
            _spec(one_chip, (EDGE_NR, EDGE_K)),
            _spec(one_chip, (EDGE_K, EDGE_M)))
    assert "tpu_custom_call" in text


def test_trsm_upper_compiles(one_chip):
    from repro.kernels.trisolve.kernel import trsm_upper

    text = _kernel_text(
        lambda u, x: trsm_upper(u, x, interpret=False),
        _spec(one_chip, (TRSM_K, TRSM_K)), _spec(one_chip, (256, TRSM_K)))
    assert "tpu_custom_call" in text


@pytest.mark.skipif(not jax.config.jax_enable_x64,
                    reason="the float64 refinement needs jax x64")
def test_batched_refactor_and_refined_solve_compile(one_chip):
    """circuit_system(n=200), K=8, in the chip smoke's precision pair:
    float32 factor, float64 refinement."""
    a = CSR.from_scipy(circuit_system(n=200, seed=0))
    an = analyze(a, HyluOptions(engine="jax", factor_dtype="float32"))
    eng = jax_repeated_engine(an)
    assert np.dtype(eng.factor_dtype) == np.float32
    assert np.dtype(eng.refine_dtype) == np.float64
    k = 8
    values = _spec(one_chip, (k, a.nnz), jnp.float64)
    refactor = eng.refactor_batched.lower(values).compile()
    assert refactor.memory_analysis() is not None
    solver = eng.refined_batched_solver(a.indptr, a.indices)
    solver.lower(_spec(one_chip, (k, an.plan.total_slots)),
                 _spec(one_chip, (k, a.n), jnp.int32), values,
                 _spec(one_chip, (k, a.n), jnp.float64),
                 _spec(one_chip, (), jnp.int64),
                 _spec(one_chip, (), jnp.float64)).compile()


@pytest.mark.skipif(not jax.config.jax_enable_x64,
                    reason="the float64 refinement needs jax x64")
def test_pallas_fused_solve_compiles(one_chip, monkeypatch):
    """The ``use_pallas`` block substitution inside the fused refined
    solve, on the banded routing system (hybrid: supernode diagonal blocks
    on the TRSM kernel), float32 factor, K=8."""
    import repro.kernels.trisolve.kernel as trisolve_kernel
    from tests.helpers import routing_system

    a, _, _ = routing_system("banded")
    an = analyze(a, HyluOptions(engine="jax", factor_dtype="float32",
                                use_pallas=True))
    eng = jax_repeated_engine(an)
    k = 8
    # kernels traced earlier in this process were traced interpreted
    jax.clear_caches()
    monkeypatch.setattr(trisolve_kernel, "interpret_mode",
                        lambda interpret=None: False)
    try:
        solver = eng.refined_batched_solver(a.indptr, a.indices)
        text = solver.lower(
            _spec(one_chip, (k, an.plan.total_slots)),
            _spec(one_chip, (k, a.n), jnp.int32),
            _spec(one_chip, (k, a.nnz), jnp.float64),
            _spec(one_chip, (k, a.n), jnp.float64),
            _spec(one_chip, (), jnp.int64),
            _spec(one_chip, (), jnp.float64)).compile().as_text()
    finally:
        jax.clear_caches()
    assert "tpu_custom_call" in text


def test_interpret_follows_platform(monkeypatch):
    """Compiled on a TPU, interpreted on any other backend; an explicit
    flag wins."""
    assert jax.default_backend() == "cpu"
    assert interpret_mode() is True
    assert interpret_mode(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode() is False
    assert interpret_mode(True) is True


@pytest.mark.skipif(not jax.config.jax_enable_x64,
                    reason="a float64 engine needs jax x64")
def test_pallas_float64_refused_where_kernels_compile(monkeypatch):
    """Mosaic has no float64: where kernels compile, a float64 Pallas
    engine fails when it is built, not deep inside the kernel compiler."""
    import repro.core.jax_engine as jax_engine

    a = CSR.from_scipy(circuit_system(n=40, seed=1))
    monkeypatch.setattr(jax_engine, "interpret_mode", lambda: False)
    an = analyze(a, HyluOptions(engine="jax", use_pallas=True))
    with pytest.raises(ValueError, match="no float64"):
        jax_repeated_engine(an)
    an32 = analyze(a, HyluOptions(engine="jax", use_pallas=True,
                                  factor_dtype="float32"))
    assert np.dtype(jax_repeated_engine(an32).factor_dtype) == np.float32
