"""5-point Poisson stencil on an nx × ny grid plus a seeded diagonal shift
(``benchmarks/matrices.py``'s ``fem2d``: the thermal / apache class)."""
import numpy as np
import scipy.sparse as sp


def generate(nx, ny, seed=0):
    rng = np.random.default_rng(seed)
    ex = np.ones(nx)
    ey = np.ones(ny)
    tx = sp.diags([-ex[:-1], 2 * ex, -ex[:-1]], [-1, 0, 1])
    ty = sp.diags([-ey[:-1], 2 * ey, -ey[:-1]], [-1, 0, 1])
    a = sp.kronsum(tx, ty).tocsr()
    a = a + sp.diags(rng.uniform(0.0, 0.1, a.shape[0]))
    return a
