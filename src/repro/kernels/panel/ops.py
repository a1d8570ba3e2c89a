"""jit'd wrappers for the panel-LU Pallas kernel (scalar + bucketed).

Dtype contract: the kernel runs entirely in the panel dtype (float64 /
float32 / bfloat16) — masks and the perturbation threshold are cast to it,
and the identity-pivot sentinel (1e30) is representable in every supported
dtype, so the same kernel serves the mixed-precision engine unchanged.
Mosaic has no float64, so on a TPU the panel dtype is float32 or narrower.
``eps_p`` should already be scaled to the dtype's machine epsilon
(``repro.core.options.resolve_perturb_eps``).
"""
import jax
import jax.numpy as jnp

from .kernel import panel_lu_bucketed_p
from .ref import panel_lu_bucketed_ref, panel_lu_ref

__all__ = ["panel_lu", "panel_lu_batched", "panel_lu_ref",
           "panel_lu_bucketed_ref"]


def _eps_in(dtype, eps_p):
    """``eps_p`` cast to the panel dtype, guarded against underflow: a
    positive threshold that downcasts to zero (bfloat16 underflows near
    1e-38) would silently disable pivot perturbation and let exact-zero
    pivots produce inf/NaN panels — clamp it to the dtype's smallest
    normal instead.  An exactly-zero eps (perturbation off) stays zero."""
    eps0 = jnp.asarray(eps_p)
    eps = eps0.astype(dtype)
    tiny = jnp.asarray(jnp.finfo(dtype).tiny, dtype)
    return jnp.where((eps0 > 0) & (eps <= 0), tiny, eps)


def panel_lu(panel: jax.Array, nr: int, lsize: int, eps_p):
    """LU of one panel laid out [L prefix (lsize) | diag block | U suffix]:
    the columns are rotated so the diagonal block leads, factored as a
    bucket of one by the bucketed kernel, and rotated back.  Returns
    (panel, local_perm (int32 nr), n_perturb (int32 scalar))."""
    w = panel.shape[1]
    wu = w - lsize
    rolled = jnp.concatenate([panel[:, lsize:], panel[:, :lsize]], axis=1)
    out, perm, nper = panel_lu_batched(rolled[None], wu, eps_p)
    out = jnp.concatenate([out[0, :, wu:], out[0, :, :wu]], axis=1)
    return out, perm[0], nper[0]


def panel_lu_batched(panels: jax.Array, wu: int, eps_p):
    """Bucketed panel LU on column-reordered panels (B, nr, wt): the
    leading bucket dim is the Pallas grid, elimination masked to [0, wu).
    Returns (panels, perms (B, nr) int32, n_perturb (B,) int32)."""
    eps = _eps_in(panels.dtype, eps_p)
    return panel_lu_bucketed_p(panels, eps, wu)
