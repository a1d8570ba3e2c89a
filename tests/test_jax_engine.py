"""JAX engine ≡ numpy reference; differentiable solve; Pallas path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.api import HyluOptions, analyze, _m_values
from repro.core.jax_engine import make_factor_fn, make_lu_solver
from repro.core.structure import build_solve_structure
from repro.core.autodiff import make_sparse_solve
from repro.core import ref_engine

from tests.helpers import random_system


@pytest.mark.parametrize("mode", ["rowrow", "hybrid"])
def test_jax_factor_matches_ref(mode):
    Ac, _, _ = random_system(90, 0.06, 21)
    an = analyze(Ac, HyluOptions(force_mode=mode))
    m = _m_values(an, Ac)
    f_ref = ref_engine.factor(an.plan, m)
    f_jax = jax.jit(make_factor_fn(an.plan))(jnp.asarray(m.data))
    assert np.abs(np.asarray(f_jax.vals) - f_ref.vals).max() < 1e-11
    assert np.array_equal(np.asarray(f_jax.inode_perm), f_ref.inode_perm)
    assert int(f_jax.n_perturb) == f_ref.n_perturb


def test_jax_solve_and_transpose_solve():
    Ac, a_sp, b = random_system(70, 0.07, 23)
    an = analyze(Ac)
    m = _m_values(an, Ac)
    f = jax.jit(make_factor_fn(an.plan))(jnp.asarray(m.data))
    ss = build_solve_structure(an.plan)
    lu_solve, lut_solve = make_lu_solver(ss)
    from repro.core.ref_engine import extract_lu, factor as rfactor
    fr = rfactor(an.plan, m)
    l, u = extract_lu(fr)
    ld, ud = l.to_dense(), u.to_dense()
    rng = np.random.default_rng(0)
    c = rng.normal(size=70)
    w = np.asarray(lu_solve(f.vals, jnp.asarray(c)))
    w_ref = np.linalg.solve(ud, np.linalg.solve(ld, c))
    assert np.abs(w - w_ref).max() < 1e-9
    wt = np.asarray(lut_solve(f.vals, jnp.asarray(c)))
    wt_ref = np.linalg.solve(ld.T, np.linalg.solve(ud.T, c))
    assert np.abs(wt - wt_ref).max() < 1e-9


@pytest.mark.parametrize("mode", ["rowrow", "hybrid"])
def test_sparse_solve_grads(mode):
    Ac, a_sp, b = random_system(60, 0.07, 29)
    an = analyze(Ac, HyluOptions(force_mode=mode))
    solve = make_sparse_solve(an)
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(size=60))

    def loss(ad, bb):
        return jnp.sum(w * solve(ad, bb))

    g_a, g_b = jax.grad(loss, argnums=(0, 1))(
        jnp.asarray(Ac.data), jnp.asarray(b))
    eps = 1e-6
    for t in rng.choice(Ac.nnz, 4, replace=False):
        d = Ac.data.copy()
        d[t] += eps
        lp = float(loss(jnp.asarray(d), jnp.asarray(b)))
        d[t] -= 2 * eps
        lm = float(loss(jnp.asarray(d), jnp.asarray(b)))
        fd = (lp - lm) / (2 * eps)
        assert abs(fd - float(g_a[t])) < 1e-4 * (1 + abs(fd))
    for t in rng.choice(60, 3, replace=False):
        bb = b.copy()
        bb[t] += eps
        lp = float(loss(jnp.asarray(Ac.data), jnp.asarray(bb)))
        bb[t] -= 2 * eps
        lm = float(loss(jnp.asarray(Ac.data), jnp.asarray(bb)))
        fd = (lp - lm) / (2 * eps)
        assert abs(fd - float(g_b[t])) < 1e-4 * (1 + abs(fd))


def test_jax_engine_pallas_path():
    Ac, _, _ = random_system(50, 0.1, 31)
    an = analyze(Ac, HyluOptions(force_mode="hybrid"))
    m = _m_values(an, Ac)
    f_ref = ref_engine.factor(an.plan, m)
    f_jax = jax.jit(make_factor_fn(an.plan, use_pallas=True))(
        jnp.asarray(m.data))
    assert np.abs(np.asarray(f_jax.vals) - f_ref.vals).max() < 1e-10
