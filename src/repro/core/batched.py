"""Batched repeated solve: K value sets of one pattern as one XLA program.

Top numeric layer of the core stack (options → analysis → batched → api
facade).  Lifts the numeric phase over K value sets of one sparsity pattern
as single pre-compiled XLA programs, optionally sharded across devices over
the system-batch axis (``HyluOptions.mesh``) with an async double-buffered,
buffer-donating sequence pipeline (``HyluOptions.donate``).  Everything here
consumes an :class:`repro.core.analysis.Analysis` and its cached engines —
the serving layer (:mod:`repro.serve.solver_service`) dispatches
heterogeneous traffic onto these entry points, one group per pattern.
"""
from __future__ import annotations

import dataclasses
import numpy as np

from .matrix import CSR
from .analysis import Analysis, analyze, jax_repeated_engine
from .options import HyluOptions, resolve_refine_tol
from .tracing import span


@dataclasses.dataclass
class BatchedFactorState:
    """K factorizations of one sparsity pattern (K value sets), held as
    stacked device arrays — the state of the batched repeated-solve path.

    Under a mesh (``HyluOptions.mesh``) the device arrays are padded from K
    up to ``k_pad`` (a multiple of the device count) and sharded over the
    mesh's system-batch axis; ``k`` is always the caller's true batch size
    and every result is sliced back to it."""
    analysis: Analysis
    a_pattern: tuple           # (indptr, indices) of the original matrices
    values_dev: object         # jax (K_pad, nnz) A values on device (fused
                               # residuals — staged once, not per solve)
    vals: object               # jax (K_pad, total_slots) factored panels
    inode_perm: object         # jax (K_pad, n) in-node pivot permutations
    n_perturb: np.ndarray      # (K,) perturbation counts
    timings: dict
    k: int                     # true batch size (≤ k_pad)
    consumed: bool = False     # buffers donated away by solve_batched(
                               # donate=True) — the state is spent
    _values_host: np.ndarray | None = dataclasses.field(default=None,
                                                        repr=False)

    @property
    def k_pad(self) -> int:
        return int(self.vals.shape[0])

    @property
    def values_batch(self) -> np.ndarray:
        """(K, nnz) host mirror of the A values — the oracle the host-loop
        baseline and tests diff against.  Materialized lazily: when the
        caller committed device buffers (no host copy ever existed), the
        first access is one device→host transfer."""
        if self._values_host is None:
            self._values_host = np.asarray(self.values_dev)[:self.k]
        return self._values_host


def _pattern_of(a_pattern) -> tuple:
    if isinstance(a_pattern, CSR):
        return (a_pattern.indptr, a_pattern.indices)
    indptr, indices = a_pattern
    return (np.asarray(indptr), np.asarray(indices))


def _batched_matvec(pattern: tuple, values_batch: np.ndarray,
                    x_batch: np.ndarray) -> np.ndarray:
    """(A_k x_k) for K CSR matrices sharing one pattern: one gather +
    row-segment reduction for the whole batch.

    Host-side (numpy) reference: the production jax path computes residuals
    with the device matvec baked into the fused solver
    (``jax_engine.make_csr_matvec_batched``); this stays as the oracle for
    tests and as the host-loop benchmark baseline.  x_batch is (K, n) or
    (K, n, m) multi-RHS."""
    indptr, indices = pattern
    if x_batch.ndim == 3:
        prod = values_batch[:, :, None] * x_batch[:, indices]
    else:
        prod = values_batch * x_batch[:, indices]
    counts = np.diff(indptr)
    if len(counts) == 0:
        return np.zeros_like(x_batch)
    if counts.min() > 0:
        return np.add.reduceat(prod, indptr[:-1], axis=1)
    # reduceat mishandles empty rows; fall back to per-batch scatter-add
    # (preserves the batch dtype, unlike bincount which promotes to float64)
    seg = np.repeat(np.arange(len(counts)), counts)
    out = np.zeros((x_batch.shape[0], len(counts)) + x_batch.shape[2:],
                   dtype=prod.dtype)
    for k in range(out.shape[0]):
        np.add.at(out[k], seg, prod[k])
    return out


def _pad_k(eng, k: int) -> int:
    """K padded up to a multiple of the engine's shard count."""
    return -(-k // eng.n_shards) * eng.n_shards


@span("stage")
def _stage_values(eng, values_batch):
    """Stage a (K, nnz) value set on device for the batched engine.

    Honors committed device buffers: a jax array input is used in place —
    no device→host→device round-trip (the pre-sharding code always pulled
    values through numpy).  K is padded to a multiple of the mesh device
    count by replicating system 0 (well-conditioned; padded systems are
    masked out of every result), and the buffer is placed with the
    engine's batch sharding.  Returns ``(values_dev (K_pad, nnz),
    values_host | None, k)`` — ``values_host`` is the (K, nnz) oracle in
    the engine's ``values_dtype`` when the input came from the host, else
    None (materialized lazily by ``BatchedFactorState.values_batch``).

    Staging honors the engine's ``values_dtype`` — the refine-precision
    dtype the fused residual matvec runs against: float64 for a pure-fp64
    or a mixed reduced-factor engine (the original-precision values are
    what refinement recovers accuracy from), the factor dtype for a pure
    reduced-precision engine (no silent fp64 upcast + double copy)."""
    import jax
    import jax.numpy as jnp

    if isinstance(values_batch, jax.Array):
        v = values_batch if values_batch.ndim > 1 else values_batch[None]
        host = None
        k = int(v.shape[0])
        k_pad = _pad_k(eng, k)
        if k_pad != k:
            v = jnp.concatenate(
                [v, jnp.broadcast_to(v[:1], (k_pad - k, v.shape[1]))])
    else:
        host = np.ascontiguousarray(
            np.atleast_2d(np.asarray(values_batch,
                                     dtype=np.dtype(eng.values_dtype))))
        k = host.shape[0]
        k_pad = _pad_k(eng, k)
        v = host if k_pad == k else np.concatenate(
            [host, np.broadcast_to(host[:1], (k_pad - k, host.shape[1]))])
    if eng.batch_sharding is not None:
        v = jax.device_put(v, eng.batch_sharding)
    elif not isinstance(v, jax.Array):
        v = jnp.asarray(v)
    return v, host, k


@span("stage")
def _stage_rhs(eng, b_batch, k: int, copy: bool = False):
    """Stage right-hand sides (K, n) / (n,) broadcast / (K, n, m) on device:
    same device-buffer honoring, zero-padding of K to the mesh multiple
    (zero RHS ⇒ the padded systems converge on iteration 0), and batch
    sharding placement.  A leading dimension that matches neither K nor 1
    raises (it must not silently zero-pad a mis-sized batch).

    copy=True forces a fresh device buffer even when the input is already
    a correctly-shaped jax array — required when the staged buffer will be
    *donated* but the source must survive (the pipeline re-stages a shared
    RHS every step)."""
    import jax
    import jax.numpy as jnp

    k_pad = _pad_k(eng, k)
    if getattr(b_batch, "ndim", 1) > 1 and b_batch.shape[0] != k:
        raise ValueError(f"b_batch has leading (batch) dimension "
                         f"{b_batch.shape[0]} but the factorization batch "
                         f"size is {k}")
    if isinstance(b_batch, jax.Array):
        b = b_batch
        if b.ndim == 1:
            b = jnp.broadcast_to(b, (k,) + b.shape)
        if k_pad != k:
            b = jnp.concatenate(
                [b, jnp.zeros((k_pad - k,) + b.shape[1:], b.dtype)])
        elif copy and b is b_batch:
            b = jnp.array(b)                     # fresh, donatable buffer
    else:
        b = np.asarray(b_batch, dtype=np.dtype(eng.values_dtype))
        if b.ndim == 1:
            b = np.broadcast_to(b, (k,) + b.shape)
        if k_pad != k:
            b = np.concatenate(
                [b, np.zeros((k_pad - k,) + b.shape[1:], dtype=b.dtype)])
    if eng.batch_sharding is not None:
        return jax.device_put(b, eng.batch_sharding)
    return jnp.asarray(b)


def factor_batched(an: Analysis, a_pattern, values_batch) -> BatchedFactorState:
    """K numeric factorizations (one pattern, K value sets) as a single
    pre-compiled vmapped XLA call — HYLU's repeated-solve optimization
    lifted to a batch.

    ``values_batch`` may be a host (K, nnz) array or a committed jax device
    array (no re-upload).  With ``an.opts.mesh`` set the call is sharded
    over the system-batch axis: K is padded to a multiple of the device
    count and each device factors its shard with the identical per-system
    program (bit-identical to the single-device path)."""
    import jax

    eng = jax_repeated_engine(an)
    t = {}
    with span("factor_batched", into=t):
        values_dev, values_host, k = _stage_values(eng, values_batch)
        jf = eng.refactor_batched(values_dev)
        jax.block_until_ready(jf.vals)
    return BatchedFactorState(
        analysis=an, a_pattern=_pattern_of(a_pattern),
        values_dev=values_dev, vals=jf.vals, inode_perm=jf.inode_perm,
        n_perturb=np.asarray(jf.n_perturb)[:k], timings=t, k=k,
        _values_host=values_host)


def solve_batched(bst: BatchedFactorState, b_batch: np.ndarray,
                  refine: bool | None = None, donate: bool = False) -> tuple:
    """Batched substitution + iterative refinement, fused on device: X[k]
    solves A_k x = b_k against the K stored factorizations as ONE
    pre-compiled XLA program — substitution, the batched CSR residual
    matvec (pattern as compile-time constants) and the whole refinement
    loop (``lax.while_loop`` with per-system improved/converged masking)
    execute without any per-iteration host transfer.  Under a mesh the
    program is shard_mapped over the system batch (padded K; results are
    sliced back and bit-identical to the single-device path).

    b_batch: (K, n), (n,) broadcast across the batch, or (K, n, m)
    multi-RHS (adjoint/sensitivity workloads); host or committed jax
    arrays.  Returns (X, info); info["residual"] is (K,) — or (K, m) for
    multi-RHS — and info["n_refine_per_system"] counts accepted refinement
    steps per system/RHS.  refine=False skips refinement; refine=None/True
    runs it until converged, stalled, or refine_max_iter.
    info["refine_failed"] / info["refine_stalled"] are the per-system
    masks from the fused loop: systems that exited refinement above the
    (dtype-aware) tolerance, and the subset that stopped improving.
    info["escalation"] lists the recovery stages this call ran ("refine",
    then "fp64_fallback" when the escape hatch redid a failed subset) —
    the serving layer's escalation ladder appends its own perturbed-retry
    stages on top of this record.

    On a reduced-precision engine (``factor_dtype != "float64"`` with
    fp64-staged values, i.e. the default mixed path) any refinement-failed
    system is automatically re-factored and re-solved in float64 — batched,
    failed subset only — when ``opts.fp64_fallback`` is set:
    info["fallback_mask"] marks the redone systems, info["n_fp64_fallback"]
    counts them, and the returned x/residual/masks reflect the fp64 redo,
    so callers always get fp64-quality answers or an honest failure mask.

    donate=True donates the A-values and RHS buffers into the call (the
    sequence-pipeline mode): XLA may reuse their memory, and ``bst`` is
    marked consumed — further solves against it raise."""
    an = bst.analysis
    opts = an.opts
    eng = jax_repeated_engine(an)
    if bst.consumed:
        raise RuntimeError(
            "this BatchedFactorState was consumed by a donating solve — "
            "refactor (factor_batched) before solving again")
    t = {}
    with span("solve_batched", into=t):
        max_iter = 0 if refine is False else opts.refine_max_iter
        # the escape hatch needs the original fp64 values, so it only arms
        # on a reduced-factor engine whose staging (= refine) dtype is
        # float64
        fallback_armed = (
            max_iter > 0 and bool(opts.fp64_fallback)
            and np.dtype(eng.factor_dtype) != np.float64
            and np.dtype(eng.values_dtype) == np.float64)
        if donate and bst._values_host is None:
            _ = bst.values_batch    # materialize the host oracle before
            #                         the device buffer is donated away
        b_dev = _stage_rhs(eng, b_batch, bst.k)
        # a donated RHS buffer dies with the call — snapshot it while the
        # fallback might still need to re-solve a failed subset
        b_src = np.asarray(b_dev) if (donate and fallback_armed) else b_dev
        solver = eng.refined_batched_solver(*bst.a_pattern, donate=donate)
        x, resid, n_iter, n_ref_sys, stalled, failed = solver(
            bst.vals, bst.inode_perm, bst.values_dev,
            b_dev, max_iter, resolve_refine_tol(opts, eng.refine_dtype))
        if donate:
            bst.consumed = True
            bst.values_dev = None
        k = bst.k
        x = np.asarray(x)[:k]
        failed_h = np.asarray(failed)[:k]
        info = dict(residual=np.asarray(resid)[:k], n_refine=int(n_iter),
                    n_refine_per_system=np.asarray(n_ref_sys)[:k],
                    n_perturb=bst.n_perturb,
                    refine_stalled=np.asarray(stalled)[:k],
                    refine_failed=failed_h,
                    factor_dtype=np.dtype(eng.factor_dtype).name,
                    fallback_mask=np.zeros(k, bool), n_fp64_fallback=0,
                    escalation=(["refine"] if max_iter > 0 else []))
        if max_iter > 0:
            # a NaN/Inf residual or solution must count as failed: the
            # device mask is `resid > tol`, and NaN compares False —
            # without this a numerically singular system's NaN solution
            # would sail through flagged converged (silent garbage instead
            # of an honest failure)
            failed_h = info["refine_failed"] = _nonfinite_failed(x, info)
        if fallback_armed and failed_h.any():
            x = _fp64_redo(bst, b_src, x, info)
            info["escalation"].append("fp64_fallback")
            # the redo's own masks come from the same `> tol` comparison —
            # guard them too in case the fp64 re-solve is still non-finite
            info["refine_failed"] = _nonfinite_failed(x, info)
    info["solve_time"] = t["solve_batched"]
    return x, info


def _nonfinite_failed(x: np.ndarray, info: dict) -> np.ndarray:
    """``refine_failed`` with non-finite residuals/solutions OR-ed in:
    per-system (or per system/RHS for a (K, m) residual) True wherever
    the reported mask is set, the residual is NaN/Inf, or the solution
    contains a non-finite entry."""
    failed = np.asarray(info["refine_failed"])
    resid = np.asarray(info["residual"])
    bad = ~np.isfinite(resid)
    x_bad = ~np.isfinite(x.reshape(x.shape[0], -1)).all(axis=1)
    return failed | bad | (x_bad if bad.ndim == 1 else x_bad[:, None])


def _fp64_redo(bst: BatchedFactorState, b_src, x: np.ndarray,
               info: dict) -> np.ndarray:
    """The per-system fp64 escape hatch of :func:`solve_batched`: re-factor
    and re-solve the refinement-failed subset in float64 (one batched call
    at the subset size) and splice the recovered solutions, residuals and
    masks back into the mixed-precision results.  Needs the fp64-staged
    values (``bst.values_batch``) — the reduced-precision factors are
    discarded for these systems."""
    an = bst.analysis
    opts = an.opts
    with span("fp64_fallback", into=info, key="fallback_time"):
        failed_h = info["refine_failed"]
        sys_mask = failed_h if failed_h.ndim == 1 else failed_h.any(axis=1)
        idx = np.nonzero(sys_mask)[0]
        eng64 = jax_repeated_engine(an, dtype=np.float64,
                                    refine_dtype=np.float64)
        v_sub = np.ascontiguousarray(
            np.asarray(bst.values_batch, dtype=np.float64)[idx])
        b_sub = np.ascontiguousarray(np.asarray(b_src)[idx])
        v_dev, _, f = _stage_values(eng64, v_sub)
        jf = eng64.refactor_batched(v_dev)
        b_dev = _stage_rhs(eng64, b_sub, f)
        solver = eng64.refined_batched_solver(*bst.a_pattern)
        x64, resid64, _, n_ref64, st64, fl64 = solver(
            jf.vals, jf.inode_perm, v_dev, b_dev, opts.refine_max_iter,
            resolve_refine_tol(opts, "float64"))
        x = np.array(x)                   # jax views are read-only; splice
        x[idx] = np.asarray(x64)[:f].astype(x.dtype)
        for key, new in (("residual", resid64),
                         ("n_refine_per_system", n_ref64),
                         ("refine_stalled", st64), ("refine_failed", fl64)):
            merged = np.array(info[key])
            merged[idx] = np.asarray(new)[:f]
            info[key] = merged
        info["fallback_mask"] = sys_mask
        info["n_fp64_fallback"] = int(len(idx))
    return x


def _solve_batched_hostloop(bst: BatchedFactorState, b_batch: np.ndarray,
                            refine: bool | None = None) -> tuple:
    """Pre-fusion reference implementation of :func:`solve_batched`: device
    substitution but numpy residuals and a Python refinement loop (one
    host round-trip per iteration).  Kept as the benchmark baseline the
    fused path is measured against, and as a parity oracle — same
    per-system improved/converged masking, same multi-RHS shapes."""
    import jax.numpy as jnp

    an = bst.analysis
    opts = an.opts
    eng = jax_repeated_engine(an)
    t = {}
    with span("solve_batched", into=t):
        # stage/accumulate in the engine's refine dtype, like the fused
        # path (the substitution itself runs in the factor dtype inside
        # apply_batched)
        rdt = np.dtype(eng.refine_dtype)
        tol = resolve_refine_tol(opts, eng.refine_dtype)
        b_batch = np.asarray(b_batch, dtype=rdt)
        if b_batch.ndim == 1:
            b_batch = np.broadcast_to(b_batch, (bst.k, b_batch.shape[0]))

        # the oracle path always runs unsharded at the true batch size:
        # slice any mesh padding off the (possibly sharded) device buffers
        vals_k, inode_k = bst.vals[:bst.k], bst.inode_perm[:bst.k]

        def residuals(x):
            r = b_batch - _batched_matvec(bst.a_pattern, bst.values_batch,
                                          x)
            return r, np.abs(r).sum(axis=1) / bnorm

        bnorm = np.abs(b_batch).sum(axis=1)          # (K,) or (K, m)
        bnorm = np.where(bnorm == 0.0, 1.0, bnorm)
        x = np.asarray(eng.apply_batched(vals_k, inode_k,
                                         jnp.asarray(b_batch))).astype(rdt)
        r, resid = residuals(x)
        n_ref = 0
        alive = np.ones(resid.shape, bool)
        max_iter = 0 if refine is False else opts.refine_max_iter
        for _ in range(max_iter):
            need = alive & (resid > tol)
            if not need.any():
                break
            x2 = x + np.asarray(eng.apply_batched(
                vals_k, inode_k, jnp.asarray(r))).astype(rdt)
            r2, resid2 = residuals(x2)
            n_ref += 1
            improved = resid2 < resid
            upd = need & improved                 # mirror the fused masking
            x = np.where(upd[:, None], x2, x)
            r = np.where(upd[:, None], r2, r)
            resid = np.where(upd, resid2, resid)
            alive = alive & (improved | ~need)
    failed = (resid > tol) & (max_iter > 0)
    info = dict(residual=resid, n_refine=n_ref, n_perturb=bst.n_perturb,
                refine_failed=failed, refine_stalled=failed & ~alive,
                solve_time=t["solve_batched"])
    return x, info


def _seed_values(values_batch) -> np.ndarray:
    """The (nnz,) float64 host values that seed the analysis: system 0 of
    the (possibly committed-device) batch.  Deliberately float64 whatever
    the engine dtype — the host analysis (MC64 matching/scaling, ordering)
    always runs in full precision; the scale maps are cast down once at
    engine build, not here.  Indexes down to one row *before* the host
    transfer, so a committed (K, nnz) buffer costs one row D2H, not K;
    accepts a list/tuple of value sets, a (K, nnz) batch, or a single
    (nnz,) vector."""
    v0 = values_batch
    while isinstance(v0, (list, tuple)) or getattr(v0, "ndim", 1) > 1:
        v0 = v0[0]
    return np.asarray(v0, dtype=np.float64).copy()


def _is_step_sequence(values_batch) -> bool:
    """True when values_batch is a T-step sequence — a list/tuple of 2-D
    (K, nnz) value sets or a stacked (T, K, nnz) array — rather than one
    batched step.  A list of 1-D (nnz,) value sets keeps its historical
    meaning: ONE batched step of K systems (np.atleast_2d semantics)."""
    if isinstance(values_batch, (list, tuple)):
        if not values_batch:
            return False
        first = values_batch[0]
        ndim = getattr(first, "ndim", None)
        return (np.asarray(first).ndim if ndim is None else ndim) >= 2
    ndim = getattr(values_batch, "ndim", None)
    return ndim == 3


def solve_sequence(a_pattern, values_batch, b_batch,
                   opts: HyluOptions | None = None) -> tuple:
    """Repeated-solve convenience (the paper's §3.2 scenario, batched):
    one analysis, then batched factorizations + solves as pre-compiled
    XLA programs (sharded over the mesh when ``opts.mesh`` is set).

    a_pattern     CSR (or (indptr, indices)) — the shared sparsity pattern
    values_batch  (K, nnz) value sets — ONE batched step — or a T-step
                  sequence ((T, K, nnz) array, or a list of per-step 2-D
                  (K, nnz) arrays, host or committed jax device buffers).
                  A list of 1-D (nnz,) vectors keeps its historical
                  meaning: one batched step of K systems.  The first
                  value set seeds the analysis (matching/ordering are
                  value-dependent but stable across the mild value drift
                  of Newton/transient sequences)
    b_batch       (K, n) right-hand sides, (n,) broadcast, or (K, n, m)
                  multi-RHS (adjoint/sensitivity sweeps); for a step
                  sequence, either one such RHS reused every step or a
                  list/tuple with one entry per step

    For a single step: returns (x (K, n[, m]), info) as before.

    For a T-step sequence the calls run as an **async double-buffered
    pipeline**: while the device factors + solves step t, the host stages
    step t+1's values (``jax.device_put`` overlaps the copy with compute),
    and nothing blocks until the final gather — so H2D staging hides
    behind solves.  With ``opts.donate`` each step additionally recycles
    the previous step's factor buffers (``refactor_batched_reuse``) and
    donates the consumed value/RHS buffers, so a long refactor stream
    runs allocation-flat.  Returns (x (T, K, n[, m]), info) with
    info["residual"] (T, K[, m]) and per-step refinement counts."""
    if _is_step_sequence(values_batch):
        return _solve_sequence_pipelined(a_pattern, values_batch, b_batch,
                                         opts)
    pattern = _pattern_of(a_pattern)
    n = len(pattern[0]) - 1
    a0 = CSR(n, pattern[0], pattern[1], _seed_values(values_batch))
    an = analyze(a0, opts)
    bst = factor_batched(an, pattern, values_batch)
    x, info = solve_batched(bst, b_batch)
    info["timings"] = {"preprocess": an.timings, "factor": bst.timings}
    info["mode"] = an.choice.mode
    info["ordering"] = an.ordering_name
    info["engine"] = "jax-batched"
    info["k"] = bst.k
    return x, info


def _solve_sequence_pipelined(a_pattern, values_steps, b_steps,
                              opts: HyluOptions | None = None) -> tuple:
    """The T-step async pipeline behind :func:`solve_sequence`.

    Per step: refactor (optionally donating the previous step's factor
    buffers into the allocation) + the fused refined solve (optionally
    donating the step's A-values/RHS buffers), dispatched asynchronously;
    step t+1's values are staged to device immediately after dispatch so
    the H2D copy overlaps the device's work on step t.  Host↔device
    synchronization happens once, at the end."""
    import jax

    steps_v = (list(values_steps) if isinstance(values_steps, (list, tuple))
               else [values_steps[t] for t in range(values_steps.shape[0])])
    n_steps = len(steps_v)
    pattern = _pattern_of(a_pattern)
    n = len(pattern[0]) - 1

    # per-step RHS must come as a list/tuple (one entry per step, each any
    # single-step shape); a bare array is a single-step RHS reused every
    # step — keeps (K, n, m) multi-RHS unambiguous
    per_step_b = isinstance(b_steps, (list, tuple))
    if per_step_b and len(b_steps) != n_steps:
        raise ValueError(f"got {len(b_steps)} per-step right-hand sides "
                         f"for {n_steps} steps")

    def b_of(t):
        return b_steps[t] if per_step_b else b_steps

    a0 = CSR(n, pattern[0], pattern[1], _seed_values(steps_v[0]))
    an = analyze(a0, opts)
    opts = an.opts
    eng = jax_repeated_engine(an)
    donate = bool(opts.donate)
    solver = eng.refined_batched_solver(*pattern, donate=donate)
    max_iter = opts.refine_max_iter
    tol = resolve_refine_tol(opts, eng.refine_dtype)

    tm = {}
    with span("pipeline", into=tm):
        # stage step 0 (the analysis already synced the host, so this is
        # cheap); copy=donate: a donated staging buffer must never BE the
        # caller's (or a shared across-steps) committed array — step t+1
        # restages it
        v_dev, _, k = _stage_values(eng, steps_v[0])
        b_dev = _stage_rhs(eng, b_of(0), k, copy=donate)
        outs, n_pert = [], []
        prev = None
        for t in range(n_steps):
            if donate and prev is not None:
                jf = eng.refactor_batched_reuse(prev.vals, prev.inode_perm,
                                                v_dev)
            else:
                jf = eng.refactor_batched(v_dev)
            x, resid, n_iter, n_ref, stalled, failed = solver(
                jf.vals, jf.inode_perm, v_dev, b_dev, max_iter, tol)
            # stage step t+1 while the device chews on step t — this H2D
            # copy is the one the double-buffering hides
            if t + 1 < n_steps:
                v_dev, _, k2 = _stage_values(eng, steps_v[t + 1])
                if k2 != k:
                    raise ValueError(f"step {t + 1} has batch size {k2}, "
                                     f"step 0 had {k}")
                b_dev = _stage_rhs(eng, b_of(t + 1), k, copy=donate)
            outs.append((x, resid, n_iter, n_ref, stalled, failed))
            n_pert.append(jf.n_perturb)
            # hold the factors only to donate them: otherwise step t's
            # buffers would stay live through step t+1's refactor
            prev = jf if donate else None
            del jf
        jax.block_until_ready(outs[-1][0])           # the single sync point

    x = np.stack([np.asarray(o[0])[:k] for o in outs])
    resid = np.stack([np.asarray(o[1])[:k] for o in outs])
    # the async pipeline reports the failure masks but does not run the
    # fp64 escape hatch (a mid-stream redo would stall the double
    # buffering); single-step solve_batched is the fallback-capable path
    info = dict(residual=resid,
                n_refine=[int(o[2]) for o in outs],
                n_refine_per_system=np.stack(
                    [np.asarray(o[3])[:k] for o in outs]),
                n_perturb=np.stack([np.asarray(p)[:k] for p in n_pert]),
                refine_stalled=np.stack(
                    [np.asarray(o[4])[:k] for o in outs]),
                refine_failed=np.stack(
                    [np.asarray(o[5])[:k] for o in outs]),
                solve_time=tm["pipeline"],
                timings={"preprocess": an.timings,
                         "pipeline": tm["pipeline"]},
                mode=an.choice.mode, ordering=an.ordering_name,
                engine="jax-batched", k=k, steps=n_steps,
                donate=donate)
    return x, info
