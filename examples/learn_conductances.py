"""Differentiable sparse solve (beyond-paper): learn circuit conductances
from observed node voltages by gradient descent THROUGH the HYLU solver.

The forward pass solves G(θ) v = i with the JAX engine; the backward pass
reuses the same LU factors for the adjoint solve (custom_vjp) — one
factorization + two triangular solves per training step.

    PYTHONPATH=src python examples/learn_conductances.py
"""
import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

from repro.core import CSR, analyze, make_sparse_solve
from repro.launch.compile_cache import enable_compilation_cache


def main():
    enable_compilation_cache()
    rng = np.random.default_rng(0)
    n = 120
    # random resistor network (Laplacian + ground leaks)
    m = 4 * n
    r = rng.integers(0, n, m)
    c = np.clip(r + rng.integers(1, 6, m), 0, n - 1)
    keep = r != c
    r, c = r[keep], c[keep]
    g_true = rng.uniform(0.5, 2.0, len(r))

    def laplacian_data(g):
        # CSR.from_coo keeps the union pattern regardless of values, so the
        # sparsity pattern is identical for every g (required: one analysis)
        d = np.bincount(r, g, n) + np.bincount(c, g, n) + 0.1
        rows = np.concatenate([r, c, np.arange(n)])
        cols = np.concatenate([c, r, np.arange(n)])
        vals = np.concatenate([-g, -g, d])
        return CSR.from_coo(n, rows, cols, vals)

    A_true = laplacian_data(g_true)
    an = analyze(A_true)                       # pattern fixed → one analysis
    solve = make_sparse_solve(an)

    i_src = rng.normal(size=n)
    v_obs = np.asarray(solve(jnp.asarray(A_true.data), jnp.asarray(i_src)))

    # learn log-conductances
    theta = jnp.zeros(len(r))                  # g = exp(theta), start at 1.0
    pattern_ref = laplacian_data(np.ones(len(r)))

    # differentiable assembly: data = M @ g + d0 (linear in g) — precompute M
    nnz = pattern_ref.nnz
    M = np.zeros((nnz, len(r)))
    base = laplacian_data(np.zeros(len(r))).data
    for k in range(len(r)):
        gk = np.zeros(len(r))
        gk[k] = 1.0
        M[:, k] = laplacian_data(gk).data - base
    M = jnp.asarray(M)
    d0 = jnp.asarray(base)

    @jax.jit
    def loss_fn(theta):
        g = jnp.exp(theta)
        data = M @ g + d0
        v = solve(data, jnp.asarray(i_src))
        return jnp.mean((v - jnp.asarray(v_obs)) ** 2)

    grad_fn = jax.jit(jax.grad(loss_fn))
    # Adam on log-conductances
    m_ = jnp.zeros_like(theta)
    v_ = jnp.zeros_like(theta)
    lr = 0.05
    l0 = float(loss_fn(theta))
    for it in range(150):
        g_ = grad_fn(theta)
        m_ = 0.9 * m_ + 0.1 * g_
        v_ = 0.999 * v_ + 0.001 * g_ * g_
        theta = theta - lr * m_ / (jnp.sqrt(v_ / (1 - 0.999 ** (it + 1)))
                                   + 1e-8) / (1 - 0.9 ** (it + 1)) * \
            (1 - 0.9 ** (it + 1))
        if it % 25 == 0:
            err = float(jnp.abs(jnp.exp(theta) - jnp.asarray(g_true)).mean())
            print(f"iter {it:3d} loss {float(loss_fn(theta)):.3e} "
                  f"mean|g-g*| {err:.3f}")
    final = float(loss_fn(theta))
    print(f"loss: {l0:.3e} → {final:.3e} ({l0/final:.0f}x reduction)")
    assert final < l0 / 50
    print("OK")


if __name__ == "__main__":
    main()
