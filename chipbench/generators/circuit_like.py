"""A circuit netlist's conductance matrix (``benchmarks/matrices.py``'s
``circuit_like``): a local graph (cells talk to neighbours) plus a few
long wires, diagonally dominant."""
import numpy as np

from chipbench.patterns import laplacian_of_edges


def generate(n, seed, avg_deg=3.0, locality=16, long_frac=0.005):
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
    return laplacian_of_edges(n, rows[keep], cols[keep], vals[keep], 1.0, rng)
