"""Seeded sparsity patterns of the benchmark's configurations.

Copies of the repository's synthetic SuiteSparse-class stand-ins
(``benchmarks/matrices.py``: ``circuit_like``, ``fem2d``), kept here so that
the yardstick does not move when the program's own benchmark code does.
``tests/test_yardstick.py`` checks them against the originals bit for bit.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def _laplacian_of_edges(n, rows, cols, vals, diag_jitter, rng):
    a = sp.coo_matrix((vals, (rows, cols)), shape=(n, n))
    a = a + a.T
    d = np.abs(a).sum(axis=1).A.ravel() + rng.uniform(0.1, 1.0, n) * diag_jitter
    return (sp.diags(d) - a).tocsr()


def circuit_like(n, seed, avg_deg=3.0, locality=16, long_frac=0.005):
    """A circuit netlist's conductance matrix: a local graph (cells talk to
    neighbours) plus a few long wires, diagonally dominant."""
    rng = np.random.default_rng(seed)
    m = int(n * avg_deg / 2)
    rows = rng.integers(0, n, m)
    delta = rng.geometric(1.0 / locality, m)
    cols = np.clip(rows + rng.choice([-1, 1], m) * delta, 0, n - 1)
    ml = int(m * long_frac)
    rows = np.concatenate([rows, rng.integers(0, n, ml)])
    cols = np.concatenate([cols, rng.integers(0, n, ml)])
    vals = rng.uniform(0.1, 10.0, len(rows))
    keep = rows != cols
    return _laplacian_of_edges(n, rows[keep], cols[keep], vals[keep], 1.0, rng)


def fem2d(nx, ny, seed=0):
    """5-point Poisson stencil on an nx × ny grid plus a seeded diagonal
    shift (the thermal / apache class)."""
    rng = np.random.default_rng(seed)
    ex = np.ones(nx)
    ey = np.ones(ny)
    tx = sp.diags([-ex[:-1], 2 * ex, -ex[:-1]], [-1, 0, 1])
    ty = sp.diags([-ey[:-1], 2 * ey, -ey[:-1]], [-1, 0, 1])
    a = sp.kronsum(tx, ty).tocsr()
    a = a + sp.diags(rng.uniform(0.0, 0.1, a.shape[0]))
    return a


GENERATORS = {"circuit_like": circuit_like, "fem2d": fem2d}


def build(pattern: dict):
    """The configuration's matrix as a sorted scipy CSR: ``pattern`` names a
    generator and its keyword arguments."""
    gen = GENERATORS[pattern["generator"]]
    a = sp.csr_matrix(gen(**pattern["args"]))
    a.sort_indices()
    return a
