"""Pallas kernels of the factor and solve paths (panel LU, TRSM, GEMM).

Where a kernel runs is decided here, once, from the platform: compiled by
Mosaic on a TPU, and in the Pallas interpreter anywhere else (the CPU test
suite).  Kernel wrappers take ``interpret=None`` to mean this decision; a
compile-only test passes ``interpret=False`` to lower for a TPU that is
described but not attached.

Kernels run under jax x64 too (the engine refines in float64), where a
Python int literal traces as int64 and Mosaic refuses it: BlockSpec index
maps return :data:`I0` for a fixed block, and in-kernel loops take int32
bounds.
"""
import jax
import numpy as np

#: block index 0 for BlockSpec index maps, int32 whatever jax x64 says
I0 = np.int32(0)


def interpret_mode(interpret: bool | None = None) -> bool:
    """True when Pallas kernels run in the interpreter: an explicit
    ``interpret`` verbatim, else ``jax.default_backend() != "tpu"``."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"
