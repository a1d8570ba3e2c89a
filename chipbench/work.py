"""Useful work of the factor and solve programs, from the analysis's
symbolic statistics — never from padded shapes, so that the count stays the
same whatever implements it.

Per system, with the analysis's symbolic counts ``flops`` (the
factorization's multiply-adds, 2 per update), ``nnz_lu`` (nonzeros of
L + U) and ``nnz_a`` (nonzeros of A):

* factor: ``flops`` operations; bytes = A's values read once in the
  staging dtype plus L + U written once in the factor dtype.
* solve: each substitution reads L + U once (2·nnz_lu operations) and each
  residual reads A once (2·nnz_a operations); a system runs
  1 + (its accepted refinement steps) of each.  Bytes count only those
  matrix reads.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Counts:
    """The analysis's symbolic counts for one system."""
    flops: float
    nnz_lu: int
    nnz_a: int

    @classmethod
    def of(cls, analysis) -> "Counts":
        st = analysis.choice.stats
        return cls(flops=float(st["flops"]), nnz_lu=int(st["nnz_lu"]),
                   nnz_a=int(analysis.src_map.size))


def factor_work(c: Counts, k: int, factor_bytes: int,
                values_bytes: int) -> tuple[float, float]:
    """(operations, bytes) of factoring ``k`` systems."""
    return (k * c.flops,
            float(k * (c.nnz_a * values_bytes + c.nnz_lu * factor_bytes)))


def solve_work(c: Counts, substitutions: int, factor_bytes: int,
               values_bytes: int) -> tuple[float, float]:
    """(operations, bytes) of ``substitutions`` substitution + residual
    pairs, summed over the systems of a batch."""
    return (substitutions * 2.0 * (c.nnz_lu + c.nnz_a),
            float(substitutions * (c.nnz_lu * factor_bytes
                                   + c.nnz_a * values_bytes)))


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peaks: dict) -> tuple[float, str] | None:
    """(percent of the roofline, the bound that sets it): the least time
    the chip could take — the larger of ops over peak rate and bytes over
    peak bandwidth — over the measured time.  None without a time."""
    if not seconds or seconds <= 0:
        return None
    t_ops = ops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_ops >= t_bytes else "memory"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
