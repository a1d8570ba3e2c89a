"""Analyze / factor / refactor / solve — the scalar HYLU lifecycle.

Middle layer of the core stack (options → analysis → batched → api facade):
owns the ``Analysis`` artifact (the reusable, content-addressed product of
the preprocessing phase), the per-analysis compiled-engine cache, and the
scalar numeric lifecycle.  The batched/sharded paths live one layer up in
:mod:`repro.core.batched`; callers import everything through the
:mod:`repro.core.api` facade.

Transformations bookkeeping:  with Dr=diag(r), Ds=diag(s) from matching,
column permutation q (matched entry → diagonal), symmetric ordering p and
the numeric in-node pivot permutation g↦inode_perm[g]:

    M = (P_p (Dr A Ds) Q_q P_pᵀ),     L U = M[inode_perm, :]

    A x = b   ⇒   w = U⁻¹ L⁻¹ ((r·b)[p][inode_perm]) ;  z[p]=w ; y[q]=z ; x = s·y
"""
from __future__ import annotations

import dataclasses
import numpy as np

from .matrix import CSR
from .matching import max_weight_matching, MatchResult
from .ordering import select_ordering
from .kernel_select import select_kernel, KernelChoice
from .plan import build_plan, FactorPlan
from .symbolic import Symbolic
from . import ref_engine
from .tracing import span
from .ref_engine import Factors, SolvePlan
from .options import (HyluOptions, pattern_key, plan_fingerprint,
                      _resolve_mesh, _mesh_cache_key, np_dtype,
                      resolve_perturb_eps, resolve_refine_tol)


@dataclasses.dataclass
class Analysis:
    """The reusable product of :func:`analyze` (HYLU §2.1): matching,
    ordering, symbolic structure, the static FactorPlan, and the refactor
    gather maps — everything value-independent about one sparsity pattern.
    Also carries the per-pattern cache of compiled jax engines, so keep it
    alive across refactor/solve streams (the plan cache does exactly that).

    ``pattern_key``/``fingerprint`` are the content address: the pattern
    hash alone, and pattern + plan-affecting options (see
    :mod:`repro.core.options`).  They gate ``analyze(reuse=...)`` and key
    the plan cache."""
    n: int
    opts: HyluOptions
    match: MatchResult
    q: np.ndarray              # column permutation from matching
    p: np.ndarray              # fill-reducing ordering
    ordering_name: str
    choice: KernelChoice
    sym: Symbolic
    plan: FactorPlan
    # refactor fast path: M.data = A.data[src_map] * scale_map
    src_map: np.ndarray
    scale_map: np.ndarray
    m_pattern: tuple           # (indptr, indices) of M
    timings: dict
    pattern_key: str = ""      # sha256 of (n, indptr, indices) alone
    fingerprint: str = ""      # pattern_key + plan-affecting options
    # jit cache keyed on this analysis' plan: (factor dtype, refine dtype,
    # use_pallas, schedule, mesh) → jax_engine.RepeatedSolveEngine (built
    # lazily on first jax-engine use)
    jit_cache: dict = dataclasses.field(default_factory=dict, repr=False)


@dataclasses.dataclass
class FactorState:
    """One numeric factorization of one value set — what :func:`solve`
    consumes and :func:`refactor` refreshes (ref engine: numpy factors +
    solve plan; jax engine: device JaxFactors)."""
    analysis: Analysis
    factors: Factors | None
    solve_plan: SolvePlan | None
    a: CSR                     # the matrix these factors correspond to
    timings: dict
    engine: str = "ref"
    jax_factors: object = None  # jax_engine.JaxFactors when engine == "jax"


def analyze(a: CSR, opts: HyluOptions | None = None, reuse=None) -> Analysis:
    """Preprocessing phase (HYLU §2.1).

    reuse: a prior Analysis of the *same sparsity pattern* — matching and
    ordering are mode-independent and are reused (benchmarking different
    kernel modes re-runs only symbolic + plan).  The reused analysis is
    validated against the new matrix's pattern fingerprint; a mismatch
    raises ``ValueError`` instead of producing silently wrong factors."""
    opts = opts or HyluOptions()
    pkey = pattern_key(a)
    if reuse is not None:
        reuse_key = getattr(reuse, "pattern_key", "")
        if reuse_key != pkey:
            raise ValueError(
                "analyze(reuse=...): the reused analysis was built for a "
                "different sparsity pattern "
                f"(pattern_key {reuse_key[:12] or '<unset>'}… vs "
                f"{pkey[:12]}… for this matrix, n={reuse.n} vs {a.n}); "
                "reusing it would produce silently wrong factors — "
                "run a fresh analyze() for this pattern")
    t: dict[str, float] = {}

    def phase(name):            # host span hylu.analyze.<name> -> t[name]
        return span("analyze." + name, into=t, key=name)

    with phase("matching"):
        match = reuse.match if reuse is not None else max_weight_matching(a)

    # permute/scale with index-tracking data so refactor is a pure gather
    with phase("ordering"):
        seg = np.repeat(np.arange(a.n), np.diff(a.indptr))
        scale_entry = match.row_scale[seg] * match.col_scale[a.indices]
        tracker = CSR(a.n, a.indptr.copy(), a.indices.copy(),
                      np.arange(a.nnz, dtype=np.float64))
        q = match.col_of_row.copy()
        b2_track = tracker.permute(np.arange(a.n), q)

        pat2 = CSR(a.n, b2_track.indptr, b2_track.indices,
                   np.ones(a.nnz)).sym_pattern()
        if reuse is not None:
            p, ord_name = reuse.p, reuse.ordering_name
        else:
            p, ord_name = select_ordering(pat2, candidates=opts.orderings)

    with phase("symbolic"):
        m_track = b2_track.permute(p, p)
        src_map = m_track.data.astype(np.int64)
        scale_map = scale_entry[src_map]
        pat_m = pat2.permute(p, p)
        choice, sym = select_kernel(pat_m, force_mode=opts.force_mode,
                                    relax=opts.relax,
                                    max_super=opts.max_super)

    if opts.amalg_fill_tol > 0:
        from .structure import amalgamate_supernodes
        with phase("amalgamate"):
            sym, amalg_stats = amalgamate_supernodes(
                sym, fill_tol=opts.amalg_fill_tol, max_super=opts.max_super)
            choice.stats["amalg"] = amalg_stats

    with phase("plan"):
        m = CSR(a.n, m_track.indptr, m_track.indices, np.ones(a.nnz))
        plan = build_plan(pat_m, m, sym, mode=choice.mode,
                          bulk_min_width=opts.bulk_min_width)
    t["total"] = sum(t.values())

    return Analysis(n=a.n, opts=opts, match=match, q=q, p=p,
                    ordering_name=ord_name, choice=choice, sym=sym, plan=plan,
                    src_map=src_map, scale_map=scale_map,
                    m_pattern=(m_track.indptr, m_track.indices), timings=t,
                    pattern_key=pkey,
                    fingerprint=plan_fingerprint(a, opts, pkey=pkey))


def _m_values(an: Analysis, a: CSR) -> CSR:
    data = a.data[an.src_map] * an.scale_map
    return CSR(a.n, an.m_pattern[0], an.m_pattern[1], data)


def jax_repeated_engine(an: Analysis, dtype=None, use_pallas: bool | None = None,
                        schedule: str | None = None, mesh=None,
                        refine_dtype=None):
    """The pre-compiled repeated-solve engine for this analysis.

    Built lazily and cached on the analysis (keyed by factor/refine dtype,
    pallas, factor schedule and mesh devices), so every subsequent
    factor/refactor/solve through ``engine="jax"`` — and every batched call
    — is one already-compiled XLA program.  ``dtype`` (default
    ``an.opts.factor_dtype``) is the factor-panel/substitution precision;
    ``refine_dtype`` (default ``an.opts.refine_dtype``, ``"auto"`` → fp64
    whenever x64 is on) is the residual/accumulation precision.  ``mesh``
    (default ``an.opts.mesh``) shards the *batched* programs over the
    system-batch axis; the scalar refactor/apply programs are always
    single-device."""
    import jax

    from .jax_engine import RepeatedSolveEngine
    from .structure import build_solve_structure

    dtype = np_dtype(an.opts.factor_dtype) if dtype is None else dtype
    if refine_dtype is None and an.opts.refine_dtype not in (None, "auto"):
        refine_dtype = np_dtype(an.opts.refine_dtype)
    # the engine applies the same "auto" rule when refine_dtype is None;
    # resolve here too so the cache key names the engine actually built
    rname = (np.dtype(refine_dtype).name if refine_dtype is not None
             else ("float64" if jax.config.jax_enable_x64
                   else np.dtype(dtype).name))
    use_pallas = an.opts.use_pallas if use_pallas is None else use_pallas
    schedule = an.opts.factor_schedule if schedule is None else schedule
    mesh = _resolve_mesh(an.opts.mesh if mesh is None else mesh)
    key = (np.dtype(dtype).name, rname, bool(use_pallas), schedule,
           _mesh_cache_key(mesh))
    eng = an.jit_cache.get(key)
    if eng is None:
        ss = build_solve_structure(an.plan,
                                   bulk_min_width=an.opts.bulk_min_width)
        eng = RepeatedSolveEngine(
            an.plan, ss, src_map=an.src_map, scale_map=an.scale_map,
            p=an.p, q=an.q, row_scale=an.match.row_scale,
            col_scale=an.match.col_scale,
            perturb_eps=resolve_perturb_eps(an.opts, dtype),
            dtype=dtype, refine_dtype=refine_dtype, use_pallas=use_pallas,
            schedule=schedule, bulk_min_width=an.opts.bulk_min_width,
            mesh=mesh)
        an.jit_cache[key] = eng
    return eng


def _factor_jax(an: Analysis, a: CSR) -> FactorState:
    import jax
    import jax.numpy as jnp

    eng = jax_repeated_engine(an)
    t = {}
    with span("factor", into=t):
        jf = eng.refactor(jnp.asarray(a.data))
        jax.block_until_ready(jf.vals)
    return FactorState(analysis=an, factors=None, solve_plan=None, a=a,
                       timings=t, engine="jax", jax_factors=jf)


def factor(an: Analysis, a: CSR, engine=None) -> FactorState:
    """Numeric factorization + solve-plan build.

    engine: "ref" (numpy), "jax" (pre-compiled XLA; solve structure is
    static so no per-factor solve-plan rebuild), a ref-compatible engine
    module, or None → an.opts.engine."""
    engine = an.opts.engine if engine is None else engine
    if engine == "jax":
        return _factor_jax(an, a)
    if engine == "ref":
        mod = ref_engine
    elif hasattr(engine, "factor"):
        mod = engine
    else:
        raise ValueError(f"unknown engine {engine!r}: expected 'ref', 'jax', "
                         "or an engine module with a factor() function")
    t = {}
    with span("factor", into=t):
        f = mod.factor(an.plan, _m_values(an, a),
                       perturb_eps=an.opts.perturb_eps)
    with span("solve_plan", into=t):
        sp = ref_engine.build_solve_plan(
            f, bulk_min_width=an.opts.bulk_min_width)
    return FactorState(analysis=an, factors=f, solve_plan=sp, a=a, timings=t)


def refactor(st: FactorState, a_new: CSR) -> FactorState:
    """Repeated-solve path: same pattern, new values; reuses the analysis
    AND the solve plan's structure (values refresh only).  On the jax
    engine this is a single pre-compiled ``a_data -> factors`` call."""
    an = st.analysis
    if st.engine == "jax":
        return _factor_jax(an, a_new)
    t = {}
    with span("factor", into=t):
        f = ref_engine.factor(an.plan, _m_values(an, a_new),
                              perturb_eps=an.opts.perturb_eps)
    with span("solve_plan", into=t):
        sp = ref_engine.build_solve_plan(
            f, bulk_min_width=an.opts.bulk_min_width)
    return FactorState(analysis=an, factors=f, solve_plan=sp, a=a_new, timings=t)


def solve(st: FactorState, b: np.ndarray, refine: bool | None = None) -> tuple:
    """Forward/backward substitution + iterative refinement (auto when pivot
    perturbation occurred, per paper §2.3). Returns (x, info)."""
    an = st.analysis
    opts = an.opts
    t = {}
    with span("solve", into=t):
        if st.engine == "jax":
            import jax.numpy as jnp

            eng = jax_repeated_engine(an)
            jf = st.jax_factors
            n_perturb = int(jf.n_perturb)
            rtol = resolve_refine_tol(opts, eng.refine_dtype)

            def lu_apply(rhs: np.ndarray) -> np.ndarray:
                return np.asarray(eng.apply(jf.vals, jf.inode_perm,
                                            jnp.asarray(rhs)))
        else:
            f = st.factors
            n_perturb = f.n_perturb
            rtol = resolve_refine_tol(opts, "float64")

            def lu_apply(rhs: np.ndarray) -> np.ndarray:
                c = (an.match.row_scale * rhs)[an.p][f.inode_perm]
                w = ref_engine.solve_lu(st.solve_plan, c)
                z = np.empty_like(w); z[an.p] = w
                y = np.empty_like(z); y[an.q] = z
                return an.match.col_scale * y

        # accumulate x and the residual in float64 on the host regardless
        # of the engine's factor dtype (the batched path does the same in
        # refine_dtype)
        x = np.asarray(lu_apply(b), dtype=np.float64)
        n_ref = 0
        bnorm = float(np.abs(b).sum()) or 1.0
        resid = float(np.abs(b - st.a.matvec(x)).sum()) / bnorm
        # auto-refine when pivot perturbation occurred (paper §2.3) or the
        # residual is above the target
        do_refine = refine if refine is not None else (
            n_perturb > 0 or resid > rtol)
        if do_refine:
            for _ in range(opts.refine_max_iter):
                if resid <= rtol:
                    break
                r = b - st.a.matvec(x)
                x2 = x + lu_apply(r)
                resid2 = float(np.abs(b - st.a.matvec(x2)).sum()) / bnorm
                n_ref += 1
                if resid2 >= resid:
                    break
                x, resid = x2, resid2
    info = dict(residual=resid, n_refine=n_ref, n_perturb=n_perturb,
                refine_failed=bool(do_refine and resid > rtol),
                solve_time=t["solve"])
    return x, info


def solve_system(a: CSR, b: np.ndarray, opts: HyluOptions | None = None):
    """One-call convenience: analyze + factor + solve."""
    an = analyze(a, opts)
    st = factor(an, a)
    x, info = solve(st, b)
    info["timings"] = {"preprocess": an.timings, "factor": st.timings}
    info["mode"] = an.choice.mode
    info["ordering"] = an.ordering_name
    info["engine"] = st.engine
    return x, info
