"""JAX numeric engine: the TPU execution of a FactorPlan.

The plan is static host data; this module emits a jittable function
``b_data -> (vals, inode_perm, n_perturb)`` that executes the hybrid-kernel
schedule.  Nodes/edges are unrolled at trace time with static index maps —
every gather/scatter index is a compile-time constant, so XLA sees pure
dense ops (the TPU-native expression of the static symbolic structure).

Kernel mapping (HYLU §2.2 → TPU):
  row-row  : k==1, nr==1  — scalar divide + vector axpy (VPU)
  sup-row  : k>1,  nr==1  — TRSV + GEMV against the source panel (VPU/MXU)
  sup-sup  : k>1,  nr>1   — TRSM + GEMM on dense panels (MXU; optionally the
                            Pallas gather-GEMM-scatter kernel)
Internal supernode factorization = dense partially-pivoted LU on the
diagonal block (supernode diagonal pivoting + pivot perturbation).

``use_pallas=True`` routes panel updates through the Pallas kernels in
``repro.kernels``, which run compiled on a TPU and interpreted elsewhere
(:func:`repro.kernels.interpret_mode`).  Mosaic has no float64, so a
Pallas engine on a TPU factors in float32 or narrower; asking for float64
there fails at engine construction.

Every matrix product asks for ``Precision.HIGHEST``: on a TPU the default
would round float32 operands to bfloat16 before the MXU multiplies them.
"""
from __future__ import annotations

import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import interpret_mode

from .plan import FactorPlan

_HIGHEST = jax.lax.Precision.HIGHEST


def _jit_donating(fn, donate_argnums):
    """jax.jit with donate_argnums, silencing the 'donated buffers were not
    usable' warning: the A-values buffer intentionally has no same-shaped
    output to alias — its donation is an early-free hint, not a bug."""
    jitted = jax.jit(fn, donate_argnums=donate_argnums)

    def call(*args):
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return jitted(*args)

    return call


class JaxFactors(NamedTuple):
    vals: jax.Array          # flat panel values (float64 or float32)
    inode_perm: jax.Array    # (n,) int32
    n_perturb: jax.Array     # () int32


def _trsm_upper_jax(u, x):
    """Solve Y @ U = X (U upper-triangular, non-unit). Unrolled over k
    (supernode widths are small and static)."""
    k = u.shape[0]
    cols = []
    for j in range(k):
        acc = x[:, j]
        if j:
            yj = jnp.stack(cols, axis=1)            # (nr, j)
            acc = acc - jnp.matmul(yj, u[:j, j], precision=_HIGHEST)
        cols.append(acc / u[j, j])
    return jnp.stack(cols, axis=1)


def _panel_lu(panel, nr, lsize, eps_p, use_pallas=False):
    """Dense LU of the diagonal block with partial pivoting within the
    supernode (supernode diagonal pivoting) + pivot perturbation.
    Returns (panel, local_perm, n_perturb)."""
    if use_pallas and nr > 1:
        from repro.kernels.panel import ops as panel_ops
        return panel_ops.panel_lu(panel, nr, lsize, eps_p)
    w = panel.shape[1]
    perm = jnp.arange(nr, dtype=jnp.int32)
    nper = jnp.int32(0)

    def body(j, carry):
        panel, perm, nper = carry
        col = jax.lax.dynamic_slice_in_dim(panel, lsize + j, 1, axis=1)[:, 0]
        rows = jnp.arange(nr)
        cand = jnp.where(rows >= j, jnp.abs(col), -1.0)
        p = jnp.argmax(cand)
        # swap rows j <-> p of the whole panel (and perm)
        swap = jnp.arange(nr).at[j].set(p).at[p].set(j)
        panel = panel[swap, :]
        perm = perm[swap]
        piv = panel[j, lsize + j]
        small = jnp.abs(piv) < eps_p
        piv = jnp.where(small, jnp.where(piv >= 0, eps_p, -eps_p), piv)
        panel = panel.at[j, lsize + j].set(piv)
        nper = nper + small.astype(jnp.int32)
        # eliminate below the pivot: cols >= lsize+j (mask), rows > j
        l = panel[:, lsize + j] / piv
        rmask = (rows > j).astype(panel.dtype)
        l = l * rmask
        urow = panel[j, :]
        cmask = (jnp.arange(w) > lsize + j).astype(panel.dtype)
        panel = panel - jnp.outer(l, urow * cmask)
        panel = panel.at[:, lsize + j].set(jnp.where(rows > j, l, panel[:, lsize + j]))
        return panel, perm, nper

    if nr == 1:
        piv = panel[0, lsize]
        small = jnp.abs(piv) < eps_p
        piv = jnp.where(small, jnp.where(piv >= 0, eps_p, -eps_p), piv)
        panel = panel.at[0, lsize].set(piv)
        return panel, perm, small.astype(jnp.int32)
    panel, perm, nper = jax.lax.fori_loop(0, nr, body, (panel, perm, nper))
    return panel, perm, nper


def _node_lu_writeback(vals, inode, nper, nd, panel, off, eps_p,
                       use_pallas):
    """Internal LU of one node's (already edge-updated) panel + pivot
    bookkeeping + write-back.  Shared by the fully unrolled trace and the
    bucketed trace's narrow-level sequential nodes (whose edges were
    applied eagerly, so they need exactly this edge-free remainder);
    ``vals``/``inode`` may carry extra sentinel slots past the plan's
    sizes — all offsets touched here are real."""
    nr = nd.nr
    panel, lperm, np_ = _panel_lu(panel, nr, nd.lsize, eps_p,
                                  use_pallas=use_pallas)
    nper = nper + np_
    if nr > 1:
        seg = jax.lax.dynamic_slice(inode, (nd.r0,), (nr,))
        inode = jax.lax.dynamic_update_slice(inode, seg[lperm], (nd.r0,))
    vals = jax.lax.dynamic_update_slice(vals, panel.reshape(-1), (off,))
    return vals, inode, nper


def _node_step_unrolled(vals, inode, nper, nd, nodes, offs, eps_p,
                        use_pallas):
    """One node's left-looking edge loop + internal LU (the per-node
    sequential kernel of the unrolled trace)."""
    off = int(offs[nd.nid])
    nr, w = nd.nr, nd.width
    panel = jax.lax.dynamic_slice(vals, (off,), (nr * w,)).reshape(nr, w)
    for e in nd.edges:
        snd = nodes[e.src]
        soff = int(offs[snd.nid])
        sp = jax.lax.dynamic_slice(
            vals, (soff,), (snd.nr * snd.width,)).reshape(snd.nr, snd.width)
        src = sp[:, snd.lsize:]
        k = snd.nr
        cm = e.col_map
        x = panel[:, cm]
        if k == 1:
            lts = x[:, :1] / src[0, 0]          # row-row / sup-row
            xr = x[:, 1:] - lts * src[:, 1:]
        else:
            if use_pallas and nr > 1:
                from repro.kernels.supsup import ops as supsup_ops
                lts, xr = supsup_ops.supsup_update(x, src, k)
            else:
                lts = _trsm_upper_jax(src[:, :k], x[:, :k])
                xr = x[:, k:] - jnp.matmul(lts, src[:, k:],
                                           precision=_HIGHEST)
        panel = panel.at[:, cm].set(jnp.concatenate([lts, xr], axis=1))
    return _node_lu_writeback(vals, inode, nper, nd, panel, off, eps_p,
                              use_pallas)


def _panel_lu_bucketed(panels, wu, eps_p, use_pallas=False):
    """Dense LU with in-block partial pivoting on a (B, nr, wt) bucket of
    column-reordered panels: elimination runs over the static window
    [0, wu) (block + U suffix); trailing columns (the L prefix) only get
    row-permuted.  Padded block diagonals are identity so padded pivot
    steps are exact no-ops.  Returns (panels, perm (B, nr), nper (B,))."""
    if use_pallas:
        from repro.kernels.panel import ops as panel_ops
        return panel_ops.panel_lu_batched(panels, wu, eps_p)
    from repro.kernels.panel.ref import panel_lu_bucketed_ref
    return panel_lu_bucketed_ref(panels, wu, eps_p)


def _make_factor_fn_bucketed(plan: FactorPlan, perturb_eps, dtype,
                             use_pallas, bulk_min_width=8):
    """Level-bucketed trace: O(levels × shape-buckets) XLA ops instead of
    O(nodes + edges).  Every level's edge applications run as batched
    per-bucket gathers + TRSM / GEMM + scatters; internal LUs are bucketed
    on wide levels (the paper's bulk mode, on the factor path) and
    per-node on narrow levels (sequential mode)."""
    from .structure import get_bucket_schedule

    sched = get_bucket_schedule(plan, bulk_min_width=bulk_min_width)
    nodes = plan.nodes
    offs = plan.panel_offset

    def factor_fn(b_data: jax.Array) -> JaxFactors:
        b_data = b_data.astype(dtype)
        amax = jnp.max(jnp.abs(b_data))
        eps_p = perturb_eps * amax
        vals = jnp.zeros((sched.n_ext,), dtype=dtype)
        vals = vals.at[plan.a_scatter].set(b_data)
        # identity-pivot sentinel: a huge value rather than 1.0, so padded
        # diagonals can never test as "small" even under absurd
        # perturb_eps settings (|1e30| < eps_p is false for any sane eps;
        # padded TRSM/divide still yields exact zeros: 0 / 1e30 == 0)
        vals = vals.at[sched.one_slot].set(jnp.asarray(1e30, dtype))
        inode = jnp.arange(plan.n + 1, dtype=jnp.int32)
        nper = jnp.int32(0)

        for step in sched.steps:
            # ---- internal factorization of this level's nodes ------------
            if step.diag is not None:           # width-1: perturb diagonals
                dsl = jnp.asarray(step.diag.slots)
                d = vals[dsl]
                small = jnp.abs(d) < eps_p
                d = jnp.where(small, jnp.where(d >= 0, eps_p, -eps_p), d)
                vals = vals.at[dsl].set(d)
                nper = nper + jnp.sum(small).astype(jnp.int32)
            for pb in step.panels:              # wider: bucketed dense LU
                P = vals[jnp.asarray(pb.gather)]
                P, perm, npb = _panel_lu_bucketed(
                    P, pb.wu, eps_p, use_pallas=use_pallas)
                vals = vals.at[jnp.asarray(pb.scatter)].set(P)
                nper = nper + jnp.sum(npb).astype(jnp.int32)
                rows = jnp.asarray(pb.rows)
                seg = inode[rows]
                inode = inode.at[rows].set(
                    jnp.take_along_axis(seg, perm, axis=1))
            for t in step.seq:                  # narrow level: per-node LU
                nd = nodes[int(t)]
                off = int(offs[nd.nid])
                panel = jax.lax.dynamic_slice(
                    vals, (off,), (nd.nr * nd.width,)).reshape(nd.nr,
                                                               nd.width)
                vals, inode, nper = _node_lu_writeback(
                    vals, inode, nper, nd, panel, off, eps_p, use_pallas)
            # ---- eager application of this level's outgoing edges --------
            for eb in step.edges:
                S = vals[jnp.asarray(eb.src_idx)]     # (E, k, k+m)
                U, Us = S[:, :, :eb.k], S[:, :, eb.k:]
                X = vals[jnp.asarray(eb.x_idx)]       # (E, nr, k)
                if eb.k == 1:                         # row-row / sup-row
                    lts = X / U[:, 0, 0][:, None, None]
                    delta = lts * Us                  # (E, nr, 1)·(E, 1, m)
                elif use_pallas:                      # sup-sup on Pallas
                    from repro.kernels.supsup import ops as supsup_ops
                    from repro.kernels.trisolve import ops as trisolve_ops
                    lts = trisolve_ops.trsm_batched(U, X)
                    delta = supsup_ops.gemm_batched(lts, Us)
                else:                                 # sup-sup via XLA
                    lts = jax.lax.linalg.triangular_solve(
                        U, X, left_side=False, lower=False)
                    delta = jnp.matmul(lts, Us, precision=_HIGHEST)
                # one combined scatter: multiplier write-back expressed as
                # an add of (lts - X), trailing update as -delta
                ne = lts.shape[0]
                w_vals = jnp.concatenate([(lts - X).reshape(ne, -1),
                                          (-delta).reshape(ne, -1)], axis=1)
                vals = vals.at[jnp.asarray(eb.write_idx)].add(w_vals)

        # ---- scanned width-1 suffix: one traced body per chunk -----------
        def scan_body(carry, xs):
            vals, nper = carry
            dsl, x_i, s_i, w_i = xs
            d = vals[dsl]
            small = jnp.abs(d) < eps_p          # pads read the huge sentinel
            d = jnp.where(small, jnp.where(d >= 0, eps_p, -eps_p), d)
            vals = vals.at[dsl].set(d)
            nper = nper + jnp.sum(small).astype(jnp.int32)
            S = vals[s_i]                       # (E, 1+M)
            X = vals[x_i]                       # (E,)
            lts = X / S[:, 0]
            upd = jnp.concatenate([(lts - X)[:, None],
                                   -lts[:, None] * S[:, 1:]], axis=1)
            vals = vals.at[w_i].add(upd)
            return (vals, nper), None

        for ch in sched.scan_chunks:
            (vals, nper), _ = jax.lax.scan(
                scan_body, (vals, nper),
                (jnp.asarray(ch.dsl), jnp.asarray(ch.x_idx),
                 jnp.asarray(ch.src_idx), jnp.asarray(ch.write_idx)))

        return JaxFactors(vals=vals[:plan.total_slots],
                          inode_perm=inode[:plan.n], n_perturb=nper)

    return factor_fn


def make_factor_fn(plan: FactorPlan, perturb_eps: float = 1e-8,
                   dtype=jnp.float64, use_pallas: bool = False,
                   schedule: str = "bucketed", bulk_min_width: int = 8):
    """Emit the jittable numeric factorization for this plan.

    schedule="bucketed" (default) traces the level-bucketed program —
    O(levels × shape-buckets) ops, the only way compile time stays sane
    past toy sizes; "unrolled" keeps the historical per-node/per-edge
    trace (parity oracle for the bucketed path, and micro-best for very
    small plans)."""
    if schedule == "bucketed":
        return _make_factor_fn_bucketed(plan, perturb_eps, dtype,
                                        use_pallas,
                                        bulk_min_width=bulk_min_width)
    if schedule != "unrolled":
        raise ValueError(f"unknown factor schedule {schedule!r}: "
                         "expected 'bucketed' or 'unrolled'")
    offs = plan.panel_offset
    nodes = plan.nodes

    def factor_fn(b_data: jax.Array) -> JaxFactors:
        b_data = b_data.astype(dtype)
        amax = jnp.max(jnp.abs(b_data))
        eps_p = perturb_eps * amax
        vals = jnp.zeros((plan.total_slots,), dtype=dtype)
        vals = vals.at[plan.a_scatter].set(b_data)
        inode = jnp.arange(plan.n, dtype=jnp.int32)
        nper = jnp.int32(0)
        for nd in nodes:
            vals, inode, nper = _node_step_unrolled(
                vals, inode, nper, nd, nodes, offs, eps_p, use_pallas)
        return JaxFactors(vals=vals, inode_perm=inode, n_perturb=nper)

    return factor_fn


# --------------------------------------------------------------------------
# level-scheduled triangular solves in JAX (static SolveStructure schedules)
# --------------------------------------------------------------------------
def _tri_scan_chunks(sched, n, bulk_min_width: int = 8):
    """Chunked scan schedule for a TriSched's narrow tail levels.

    The trace of a level-unrolled substitution is O(levels); the long
    narrow tail of a sparse triangular schedule makes that expensive to
    compile for zero runtime benefit.  This packs maximal runs of
    consecutive narrow levels — padded to shared (rows, deps) shapes with
    at most 4x waste per dim — into per-chunk index arrays a single
    ``lax.scan`` body consumes.  Padding is maskless: padded rows/cols
    point at the extra row n of the padded unknown vector (which provably
    stays 0), padded slots at slot 0 (multiplied by that 0).

    Returns (n_head_levels, [(rows, rowmap, cols, slot), ...]); cached on
    the TriSched keyed by ``bulk_min_width``."""
    cache = getattr(sched, "_scan_chunks", None)
    if cache is None:
        cache = {}
        sched._scan_chunks = cache
    cached = cache.get(bulk_min_width)
    if cached is not None:
        return cached
    from .structure import segment_levels

    levels = list(zip(sched.rows, sched.cols, sched.slot, sched.seg))
    s = len(levels)
    while s > 0 and len(levels[s - 1][0]) < bulk_min_width:
        s -= 1

    groups = [levels[s + i:s + j]
              for i, j in segment_levels(
                  [(len(l[0]), len(l[1])) for l in levels[s:]])]

    chunks = []
    for group in groups:
        rmax = max(max(len(g[0]) for g in group), 1)
        dmax = max(max(len(g[1]) for g in group), 1)
        nl = len(group)
        rows_a = np.full((nl, rmax), n, np.int64)
        rowmap_a = np.full((nl, dmax), n, np.int64)
        cols_a = np.full((nl, dmax), n, np.int64)
        slot_a = np.zeros((nl, dmax), np.int64)
        for l, (r, c, sl, sg) in enumerate(group):
            rows_a[l, :len(r)] = r
            if len(sg):
                rowmap_a[l, :len(sg)] = r[sg]
            cols_a[l, :len(c)] = c
            slot_a[l, :len(sl)] = sl
        chunks.append((rows_a, rowmap_a, cols_a, slot_a))
    cached = (s, chunks)
    cache[bulk_min_width] = cached
    return cached


def _tri_solve(sched, vals, rhs, diag_slots=None, transpose_diag=False):
    """One triangular substitution following a TriSched.  Each bulk level
    is one vectorized gather + scatter-add; the narrow tail levels run as
    chunked ``lax.scan``s (see ``_tri_scan_chunks``) — the paper's
    bulk-sequential dual mode with an O(bulk levels + chunks) trace.  The
    per-row reduction and the row update fold into a single
    duplicate-accumulating scatter (rows[seg] maps every dependency
    straight to its target row) — scatter op count is what XLA compile
    time scales with."""
    n = rhs.shape[0]
    n_head, chunks = _tri_scan_chunks(sched, n)
    w = rhs
    for rows, cols, slot, seg in zip(sched.rows[:n_head],
                                     sched.cols[:n_head],
                                     sched.slot[:n_head],
                                     sched.seg[:n_head]):
        if diag_slots is None:          # unit-diagonal (L or Lᵀ)
            if len(cols):
                w = w.at[rows[seg]].add(-(vals[slot] * w[cols]))
        else:                           # non-unit diagonal U
            if len(cols):
                w = w.at[rows[seg]].add(-(vals[slot] * w[cols]))
            w = w.at[rows].divide(vals[diag_slots[rows]])
    if chunks:
        if diag_slots is not None:
            dpad = jnp.asarray(np.concatenate([diag_slots, diag_slots[:1]]))
        w = jnp.concatenate([w, jnp.zeros((1,), w.dtype)])

        def body(w, xs):
            rows_l, rowmap_l, cols_l, slot_l = xs
            w = w.at[rowmap_l].add(-(vals[slot_l] * w[cols_l]))
            if diag_slots is not None:
                w = w.at[rows_l].divide(vals[dpad[rows_l]])
            return w, None

        for ch in chunks:
            w, _ = jax.lax.scan(body, w, tuple(jnp.asarray(a) for a in ch))
        w = w[:n]
    return w


def make_lu_solver(ss, dtype=jnp.float64):
    """Emit jittable solves on the flat panel buffer:

        lu_solve(vals, c)   = U⁻¹ L⁻¹ c
        lut_solve(vals, c)  = L⁻ᵀ U⁻ᵀ c      (adjoint path)
    """
    def lu_solve(vals, c):
        y = _tri_solve(ss.l_fwd, vals, c.astype(vals.dtype))
        return _tri_solve(ss.u_bwd, vals, y, diag_slots=ss.lu.u_diag_slots)

    def lut_solve(vals, c):
        y = _tri_solve(ss.ut_fwd, vals, c.astype(vals.dtype),
                       diag_slots=ss.lu.u_diag_slots)
        return _tri_solve(ss.lt_bwd, vals, y)

    return lu_solve, lut_solve


# --------------------------------------------------------------------------
# batched repeated-solve path: K factorizations + K solves, one XLA program
# --------------------------------------------------------------------------
def _tri_solve_batched(sched, vals, rhs, diag_slots=None):
    """Batched level-scheduled substitution: vals (K, slots), rhs (K, n) or
    (K, n, m) for multi-RHS.

    Same schedule as ``_tri_solve``, but every level runs inside the
    chunked ``lax.scan``s of ``_tri_scan_chunks`` — none is unrolled.  This
    substitution runs inside the fused refinement ``while_loop``, where
    each unrolled level adds its own constant index tables to the loop
    body; on a TPU v5e, fem2d_10k's body with 424 unrolled levels did not
    finish in 280 s, while circuit_10k's scanned one ran.  The scalar
    ``_tri_solve`` keeps its unrolled bulk head: it runs outside any loop,
    once per call.  Every op is vectorized over the batch (and any
    trailing RHS dim) and stays batch-first: the reduction is a
    scatter-add on axis 1, so no per-level ``moveaxis`` round-trips
    materialize (K, nnz) transposes."""
    n = rhs.shape[1]
    _, chunks = _tri_scan_chunks(sched, n, bulk_min_width=n + 1)
    w = rhs
    multi = w.ndim == 3
    if diag_slots is not None:
        dpad = jnp.asarray(np.concatenate([diag_slots, diag_slots[:1]]))
    w = jnp.concatenate(
        [w, jnp.zeros(w.shape[:1] + (1,) + w.shape[2:], w.dtype)], axis=1)

    def body(w, xs):
        rows_l, rowmap_l, cols_l, slot_l = xs
        v = vals[:, slot_l]
        prod = v[:, :, None] * w[:, cols_l] if multi else v * w[:, cols_l]
        w = w.at[:, rowmap_l].add(-prod)
        if diag_slots is not None:
            d = vals[:, dpad[rows_l]]
            if multi:
                d = d[:, :, None]
            w = w.at[:, rows_l].divide(d)
        return w, None

    for ch in chunks:
        w, _ = jax.lax.scan(body, w, tuple(jnp.asarray(a) for a in ch))
    return w[:, :n]


def _block_solve_batched(chunks, vals, rhs, upper: bool):
    """Batched block substitution following ``block_solve_schedule``
    chunks: per node level, one scatter-add of every off-block dependency,
    then the width-1 diagonal divides (U only) and one Pallas TRSM over
    all of the level's dense diagonal blocks (``kernels/trisolve``).
    vals (K, slots); rhs (K, n) or (K, n, m)."""
    from repro.kernels.trisolve import ops as trisolve_ops

    solve = (trisolve_ops.trsm_left_upper_batched if upper
             else trisolve_ops.trsm_left_unit_lower_batched)
    multi = rhs.ndim == 3
    w = rhs if multi else rhs[..., None]
    kb, n, m = w.shape
    w = jnp.concatenate([w, jnp.zeros((kb, 1, m), w.dtype)], axis=1)
    v = jnp.concatenate([vals, jnp.zeros((kb, 1), vals.dtype),
                         jnp.ones((kb, 1), vals.dtype)], axis=1)

    def body(w, xs):
        dep_rows, dep_cols, dep_slots, w1_rows, w1_slots, blk_rows, \
            blk_slots = xs
        w = w.at[:, dep_rows].add(-(v[:, dep_slots][..., None]
                                    * w[:, dep_cols]))
        if upper:
            w = w.at[:, w1_rows].divide(v[:, w1_slots][..., None])
        bs, r = blk_rows.shape
        if bs:
            y = solve(v[:, blk_slots].reshape(kb * bs, r, r),
                      w[:, blk_rows].reshape(kb * bs, r, m))
            w = w.at[:, blk_rows].set(y.reshape(kb, bs, r, m))
        return w, None

    for ch in chunks:
        w, _ = jax.lax.scan(body, w,
                            tuple(jnp.asarray(a) for a in ch.arrays()))
    w = w[:, :n]
    return w if multi else w[..., 0]


def make_batched_lu_solver(ss, dtype=jnp.float64, blocks=None):
    """Batched variant of :func:`make_lu_solver` over (K, slots)/(K, n)
    (or (K, n, m) multi-RHS).  ``blocks`` — the forward and backward
    ``block_solve_schedule`` of a ``use_pallas`` engine — swaps the
    level-scheduled scatter-add substitution for the node-block one whose
    supernode diagonal blocks run on the Pallas TRSM kernel."""
    if blocks is not None:
        fwd, bwd = blocks

        def block_lu_solve_batched(vals, c):
            y = _block_solve_batched(fwd, vals, c.astype(vals.dtype),
                                     upper=False)
            return _block_solve_batched(bwd, vals, y, upper=True)
        return block_lu_solve_batched

    def lu_solve_batched(vals, c):
        y = _tri_solve_batched(ss.l_fwd, vals, c.astype(vals.dtype))
        return _tri_solve_batched(ss.u_bwd, vals, y,
                                  diag_slots=ss.lu.u_diag_slots)
    return lu_solve_batched


def make_csr_matvec_batched(indptr, indices):
    """Device-side batched CSR matvec with the pattern baked in as
    compile-time constants: ``(A_k x_k)`` for K matrices sharing one
    sparsity pattern, x (K, n) or (K, n, m).

    One gather + one batch-first scatter-add for the whole batch (no
    per-call transposes of the (K, nnz) product); empty rows stay exact
    zeros (no host fallback), and the batch dtype is preserved.  This is
    the residual matvec of the fused refinement loop — it keeps
    r = b - A x on device."""
    indptr = np.asarray(indptr)
    indices = np.asarray(indices)
    n = len(indptr) - 1
    seg = jnp.asarray(np.repeat(np.arange(n), np.diff(indptr)))
    idx = jnp.asarray(indices)

    def matvec(a_vals, x):
        prod = (a_vals[:, :, None] * x[:, idx] if x.ndim == 3
                else a_vals * x[:, idx])
        return jnp.zeros((x.shape[0], n) + x.shape[2:],
                         prod.dtype).at[:, seg].add(prod)

    return matvec


def _output_perm(p, q):
    """The solve's two output scatters z[p]=w, y[q]=z composed into one
    static gather index:  z[p]=w ⇒ z=w[p⁻¹];  y[q]=z ⇒ y=z[q⁻¹];  hence
    y = w[p⁻¹[q⁻¹]].  Shared by the scalar and batched apply paths so the
    permutation semantics cannot diverge."""
    return jnp.asarray(np.argsort(p)[np.argsort(q)])


def make_permuted_apply(lu_solve, n, p, q, row_scale, col_scale,
                        dtype=jnp.float64):
    """Compose the full solve A⁻¹ b from LU substitution and the analysis
    transformations (see api.py header):

        apply(vals, inode_perm, b) = s · scatter_q(scatter_p(
                                       U⁻¹ L⁻¹ ((r·b)[p][inode_perm]) ))

    Single definition shared by the repeated-solve engine and the
    differentiable solver (autodiff) so the permutation/scaling semantics
    cannot diverge.  The two output scatters z[p]=w, y[q]=z compose into
    one static gather (y = w[p⁻¹∘q⁻¹] — permutation inverses are known at
    analysis time), which is both faster and far cheaper to compile."""
    p_ = jnp.asarray(p)
    out_perm = _output_perm(p, q)
    r_ = jnp.asarray(row_scale, dtype=dtype)
    s_ = jnp.asarray(col_scale, dtype=dtype)

    def apply(vals, inode_perm, b):
        c = (r_ * b.astype(dtype))[p_][inode_perm]
        w = lu_solve(vals, c)
        return s_ * w[out_perm]

    return apply


class RepeatedSolveEngine:
    """Pre-compiled repeated-solve engine for one analysis pattern.

    Holds the jitted callables HYLU's repeated-solve scenario needs — the
    analysis is done once on the host, then every (re)factorization and
    substitution is a single pre-compiled XLA call:

      refactor(a_data)                 -> JaxFactors        (one value set)
      refactor_batched(a_batch)        -> JaxFactors, vmapped over K sets
                                              (shard_mapped over the mesh's
                                              system-batch axis when the
                                              engine was built with one)
      refactor_batched_reuse(prev, a)  -> same, donating the previous step's
                                              JaxFactors buffers so a
                                              refactor *stream* reuses its
                                              allocations instead of growing
      apply(vals, inode_perm, b)       -> x   solving A x = b with the stored
                                              factors (scales + permutations
                                              + LU substitution fused)
      apply_batched(vals, inode, B)    -> X   (K, n) — or (K, n, m) for
                                              multi-RHS — via the natively
                                              batched tri-solve (scanned
                                              scatter-add levels, or the
                                              Pallas-TRSM node-block path
                                              when ``use_pallas=True``);
                                              always single-device (it is the
                                              host-loop oracle path)
      refined_batched_solver(ip, ix)   -> the *fused* batched solve:
                                              substitution + device CSR
                                              residual matvec + the whole
                                              iterative-refinement loop as
                                              ONE jitted XLA program
                                              (lax.while_loop; zero host
                                              transfers per iteration)

    All index maps (scatter/gather, permutations, level schedules) are
    compile-time constants; only values flow through the program, so one
    compilation serves thousands of Newton/time/Monte-Carlo steps.

    Sharding (``mesh`` not None): the batched programs are wrapped in
    ``shard_map`` over the mesh's single axis — each device runs the
    *identical* per-system program on its K/D shard of the batch, and no
    collective touches the numerics (only the refinement iteration count is
    ``pmax``-reduced for reporting), so sharded results are bit-identical
    to the single-device path.  Callers pad K to a multiple of the device
    count (api.factor_batched does this; padded systems ride the same
    per-system ``alive`` masking the refinement loop already carries).
    """

    def __init__(self, plan: FactorPlan, ss, *, src_map, scale_map, p, q,
                 row_scale, col_scale, perturb_eps: float = 1e-8,
                 dtype=jnp.float64, refine_dtype=None,
                 use_pallas: bool = False, schedule: str = "bucketed",
                 bulk_min_width: int = 8, mesh=None):
        if refine_dtype is None:
            # mirror options.resolve_dtype_names: residual/solution
            # accumulation (and A-value/RHS staging) happen in fp64 whenever
            # x64 is available — a reduced factor dtype then still recovers
            # fp64-accurate solutions through refinement
            refine_dtype = (jnp.float64 if jax.config.jax_enable_x64
                            else dtype)
        for role, dt in (("factor", dtype), ("refine", refine_dtype)):
            if np.dtype(dt) == np.float64 and not jax.config.jax_enable_x64:
                # without this, float64 silently degrades to float32 and
                # every solve limps through refinement at ~1e-6 residuals
                raise RuntimeError(
                    f"engine {role} dtype is float64 but jax x64 is "
                    "disabled — run jax.config.update('jax_enable_x64', "
                    "True) before building the engine, or request "
                    "dtype=jnp.float32 explicitly")
        if (use_pallas and np.dtype(dtype) == np.float64
                and not interpret_mode()):
            raise ValueError(
                "use_pallas=True compiles the Pallas kernels with Mosaic on "
                f"this {jax.default_backend()} backend, and Mosaic has no "
                "float64 — use factor_dtype='float32' (refinement still "
                "accumulates in float64) or use_pallas=False")
        self.n = plan.n
        self.dtype = dtype             # factor-panel/substitution dtype
        self.factor_dtype = dtype
        self.refine_dtype = refine_dtype
        #: dtype batched A-values/RHS must be staged in (the residual matvec
        #: runs against these, so they carry the refine precision)
        self.values_dtype = refine_dtype
        self.plan = plan
        self.bulk_min_width = bulk_min_width
        factor_fn = make_factor_fn(plan, perturb_eps=perturb_eps, dtype=dtype,
                                   use_pallas=use_pallas, schedule=schedule,
                                   bulk_min_width=bulk_min_width)
        lu_solve, lut_solve = make_lu_solver(ss, dtype=dtype)
        blocks = None
        if use_pallas:
            from .structure import block_solve_schedule
            blocks = (block_solve_schedule(plan, upper=False),
                      block_solve_schedule(plan, upper=True))
        lu_solve_b = make_batched_lu_solver(ss, dtype=dtype, blocks=blocks)
        src = jnp.asarray(src_map)
        scl = jnp.asarray(scale_map, dtype=dtype)
        p_ = jnp.asarray(p)
        out_perm = _output_perm(p, q)
        r_ = jnp.asarray(row_scale, dtype=dtype)
        s_ = jnp.asarray(col_scale, dtype=dtype)
        n = self.n

        def _refactor(a_data):
            # A.data -> M.data is a pure gather+scale (see api.analyze)
            return factor_fn(a_data.astype(dtype)[src] * scl)

        _apply = make_permuted_apply(lu_solve, n, p, q, row_scale, col_scale,
                                     dtype=dtype)

        def _apply_batched(vals, inode_perm, b):
            multi = b.ndim == 3                    # (K, n, m) multi-RHS
            c = (b.astype(dtype) * (r_[:, None] if multi else r_))[:, p_]
            idx = inode_perm[:, :, None] if multi else inode_perm
            c = jnp.take_along_axis(c, idx, axis=1)
            w = lu_solve_b(vals, c)
            # z[p]=w; y[q]=z composed into one static gather (see
            # make_permuted_apply)
            y = w[:, out_perm]
            return y * (s_[:, None] if multi else s_)

        self._apply_batched_impl = _apply_batched
        self.mesh = mesh
        self.batch_axis = mesh.axis_names[0] if mesh is not None else None
        self.n_shards = int(mesh.size) if mesh is not None else 1
        refactor_b = jax.vmap(_refactor)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            spec = PartitionSpec(self.batch_axis)
            #: the sharding batched inputs should be staged with (device_put
            #: here = no resharding inside the jitted calls)
            self.batch_sharding = NamedSharding(mesh, spec)
            # check_vma=False: the per-system programs never mix shards,
            # and the loop carries built from constants would otherwise
            # each need a pcast to "varying"
            refactor_b = jax.shard_map(
                refactor_b, mesh=mesh, in_specs=(spec,),
                out_specs=JaxFactors(vals=spec, inode_perm=spec,
                                     n_perturb=spec),
                check_vma=False)
        else:
            self.batch_sharding = None
        self._refactor_batched_impl = refactor_b

        def _refactor_reuse(prev_vals, prev_inode, a_batch):
            # numerically identical to refactor_batched; the prev buffers
            # exist only to be donated, so the output JaxFactors alias them
            # (n_perturb is tiny and stays live for reporting — not donated)
            del prev_vals, prev_inode
            return refactor_b(a_batch)

        self.refactor = jax.jit(_refactor)
        self.refactor_batched = jax.jit(refactor_b)
        self.refactor_batched_reuse = _jit_donating(_refactor_reuse,
                                                    donate_argnums=(0, 1))
        self.apply = jax.jit(_apply)
        self.apply_batched = jax.jit(_apply_batched)
        self.lut_solve = jax.jit(lut_solve)
        self._refined_cache: dict = {}

    def memory_stats(self, k: int = 1) -> dict:
        """Plan-derived byte accounting of this engine at system-batch
        size ``k``, with the engine's actual dtype width (see
        :func:`repro.core.plan.memory_stats`)."""
        from .plan import memory_stats
        return memory_stats(self.plan, bulk_min_width=self.bulk_min_width,
                            k=k, dtype_bytes=np.dtype(self.dtype).itemsize)

    def refined_batched_solver(self, indptr, indices, donate: bool = False):
        """The fused batched solve for K systems sharing the given original-A
        pattern (compile-time constants).  Returns a jitted

            solver(vals, inode_perm, a_vals, b, max_iter, tol)
                -> (x, resid, n_iter, n_ref_sys, stalled, failed)

        that runs substitution, the batched CSR residual matvec and the full
        iterative-refinement loop as ONE XLA program: a ``lax.while_loop``
        carries ``(x, r, resid, alive, ...)`` with per-system improved /
        converged masking, so no per-iteration host transfer happens.
        Substitution runs in the engine's factor dtype; b/a_vals/x/residual
        are carried in ``refine_dtype`` (stage them in ``values_dtype``).

        b is (K, n) or (K, n, m) multi-RHS; resid / n_ref_sys / stalled /
        failed are (K,) or (K, m) accordingly (1-norm residuals relative to
        each RHS column).  A system (or RHS column) stops refining once its
        residual is at or below ``tol`` or an iteration fails to improve it
        — the same acceptance rule as the scalar host path.  ``failed``
        marks systems that exited above ``tol`` (the fp64-fallback trigger);
        ``stalled`` marks the subset that stopped improving rather than
        running out of iterations.  ``max_iter=0`` disables refinement
        (refine=False; both masks are all-False then).

        With an engine mesh, the program is shard_mapped over the batch
        axis: each device runs its own refinement loop on its shard (the
        per-system masking makes per-shard loop lengths invisible in x),
        and ``n_iter`` is the pmax across shards.  ``donate=True`` builds a
        variant that donates the A-values and RHS buffers — the
        sequence-pipeline mode where each step's inputs die with the step
        (factor buffers are recycled separately via
        ``refactor_batched_reuse``); the state passed in is consumed."""
        indptr = np.asarray(indptr)
        indices = np.asarray(indices)
        key = (indptr.tobytes(), indices.tobytes(), bool(donate))
        solver = self._refined_cache.get(key)
        if solver is not None:
            return solver

        matvec = make_csr_matvec_batched(indptr, indices)
        apply_b = self._apply_batched_impl
        rdtype = self.refine_dtype
        batch_axis = self.batch_axis

        def solve_refined(vals, inode_perm, a_vals, b, max_iter, tol):
            multi = b.ndim == 3
            # mixed precision: substitution runs in the factor dtype
            # (apply_b casts its RHS down internally), while b, the
            # A-values, the solution and the residual are carried in the
            # refine dtype — the residual must be computed against the
            # original-precision A or the recoverable accuracy is capped
            # at eps(factor_dtype)
            b = b.astype(rdtype)
            a_vals = a_vals.astype(rdtype)
            bnorm = jnp.sum(jnp.abs(b), axis=1)              # (K,) | (K, m)
            bnorm = jnp.where(bnorm == 0.0, 1.0, bnorm)

            def expand(m):                 # mask (K,)|(K,m) -> broadcast to b
                return m[:, None, :] if multi else m[:, None]

            # the base solve is iteration 0 of the loop (x=0, r=b,
            # resid=inf), so the substitution pipeline is traced — and
            # compiled — exactly once instead of once outside and once in
            # the loop body; the iterate sequence is unchanged
            # (0 + A⁻¹b ≡ the old explicit base solve).
            x = jnp.zeros_like(b)
            r = b
            resid = jnp.full(bnorm.shape, jnp.inf, rdtype)
            alive = jnp.ones(resid.shape, bool)
            n_ref = jnp.zeros(resid.shape, jnp.int32)

            def cond(carry):
                _, _, resid, alive, _, it = carry
                return (it < max_iter + 1) & jnp.any(alive & (resid > tol))

            def body(carry):
                x, r, resid, alive, n_ref, it = carry
                need = alive & (resid > tol)
                x2 = x + apply_b(vals, inode_perm, r).astype(rdtype)
                r2 = b - matvec(a_vals, x2)
                resid2 = jnp.sum(jnp.abs(r2), axis=1) / bnorm
                # iteration 0 IS the base solve: accepted unconditionally
                # (like the old explicit pre-loop solve), so a NaN/inf base
                # residual surfaces in x instead of masking back to 0
                improved = (resid2 < resid) | (it == 0)
                upd = need & improved
                x = jnp.where(expand(upd), x2, x)
                r = jnp.where(expand(upd), r2, r)
                resid = jnp.where(upd, resid2, resid)
                alive = alive & (improved | ~need)
                n_ref = n_ref + (upd & (it > 0))     # iteration 0 ≡ solve
                return x, r, resid, alive, n_ref, it + 1

            x, r, resid, alive, n_ref, it = jax.lax.while_loop(
                cond, body, (x, r, resid, alive, n_ref, jnp.int32(0)))
            n_iter = jnp.maximum(it - 1, 0)
            if batch_axis is not None:
                # per-shard loops stop independently; report the global
                # iteration count (the only cross-device op in the engine,
                # and it never feeds back into x)
                n_iter = jax.lax.pmax(n_iter, batch_axis)
            # per-system verdicts (meaningful only when refinement ran):
            # failed = exited above tol; stalled = failed because an
            # iteration stopped improving (vs. ran out of iterations) —
            # the escape-hatch signal for the fp64 fallback path
            ran = jnp.int32(max_iter) > 0
            failed = (resid > tol) & ran
            stalled = failed & ~alive
            return x, resid, n_iter, n_ref, stalled, failed

        fn = solve_refined
        if self.mesh is not None:
            from jax.sharding import PartitionSpec

            spec = PartitionSpec(batch_axis)
            rep = PartitionSpec()
            # check_vma=False as for refactor_batched; n_iter is the one
            # P() output, and the pmax above makes it genuinely replicated
            fn = jax.shard_map(fn, mesh=self.mesh,
                               in_specs=(spec, spec, spec, spec, rep, rep),
                               out_specs=(spec, spec, rep, spec, spec, spec),
                               check_vma=False)
        solver = (_jit_donating(fn, donate_argnums=(2, 3)) if donate
                  else jax.jit(fn))
        self._refined_cache[key] = solver
        return solver
