"""7-point Poisson stencil on an nx × ny × nz grid plus a seeded diagonal
shift (``benchmarks/matrices.py``'s ``fem3d``: the G3_circuit /
parabolic_fem class)."""
import numpy as np
import scipy.sparse as sp


def generate(nx, ny, nz, seed=0):
    rng = np.random.default_rng(seed)

    def t(m):
        e = np.ones(m)
        return sp.diags([-e[:-1], 2 * e, -e[:-1]], [-1, 0, 1])

    a = sp.kronsum(sp.kronsum(t(nx), t(ny)), t(nz)).tocsr()
    return a + sp.diags(rng.uniform(0.0, 0.1, a.shape[0]))
