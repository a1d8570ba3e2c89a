"""A power grid's admittance Laplacian (``benchmarks/matrices.py``'s
``powergrid_like``): an nx × ny mesh of lines plus a share of seeded
long-range ties, diagonally dominant."""
import numpy as np

from chipbench.patterns import laplacian_of_edges


def generate(nx, ny, seed, extra_frac=0.05):
    rng = np.random.default_rng(seed)
    n = nx * ny
    rows, cols, vals = [], [], []
    for i in range(nx):
        for j in range(ny):
            for di, dj in ((0, 1), (1, 0)):
                if i + di < nx and j + dj < ny:
                    rows.append(i * ny + j)
                    cols.append((i + di) * ny + j + dj)
                    vals.append(rng.uniform(0.5, 5.0))
    m = int(n * extra_frac)
    rows += list(rng.integers(0, n, m))
    cols += list(rng.integers(0, n, m))
    vals += list(rng.uniform(0.1, 2.0, m))
    rows, cols, vals = np.array(rows), np.array(cols), np.array(vals)
    keep = rows != cols
    return laplacian_of_edges(n, rows[keep], cols[keep], vals[keep], 0.5, rng)
