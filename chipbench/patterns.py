"""Seeded sparsity patterns of the benchmark's configurations.

A configuration's ``pattern`` names a generator and its keyword arguments.
Each generator is a file of its own, ``generators/<generator>.py``, whose
``generate(**args)`` returns the matrix: copies of the repository's
synthetic SuiteSparse-class stand-ins (``benchmarks/matrices.py``), kept
here so that the yardstick does not move when the program's own benchmark
code does.  ``tests/test_yardstick.py`` checks them against the originals
bit for bit.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

from chipbench import harness

GENERATORS = harness.BENCH / "generators"


def laplacian_of_edges(n, rows, cols, vals, diag_jitter, rng):
    """The weighted graph Laplacian of the edges, plus a seeded diagonal
    margin of up to ``diag_jitter``."""
    a = sp.coo_matrix((vals, (rows, cols)), shape=(n, n))
    a = a + a.T
    d = np.abs(a).sum(axis=1).A.ravel() + rng.uniform(0.1, 1.0, n) * diag_jitter
    return (sp.diags(d) - a).tocsr()


def generator(name: str):
    """The ``generate`` function of ``generators/<name>.py``."""
    path = GENERATORS / f"{name}.py"
    if not path.is_file():
        have = sorted(p.stem for p in GENERATORS.glob("[!_]*.py"))
        raise KeyError(f"no generator {name!r}: {GENERATORS} holds {have}")
    return harness.load_module(path).generate


def build(pattern: dict):
    """The configuration's matrix as a sorted scipy CSR."""
    a = sp.csr_matrix(generator(pattern["generator"])(**pattern["args"]))
    a.sort_indices()
    return a
