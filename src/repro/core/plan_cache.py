"""Content-addressed plan cache: Analysis artifacts by pattern fingerprint.

HYLU's analyze phase (matching + ordering + symbolic + plan build) is pure
host work and, for the serving regime, a per-pattern tax that should be
paid **once per pattern, ever** — not once per process.  This module makes
the analysis a cached, persisted, shared artifact:

* ``PlanCache`` — an LRU map ``plan_fingerprint → Analysis`` (the
  fingerprint hashes n, indptr/indices and every plan/engine-affecting
  option; see :mod:`repro.core.options`).  A cached ``Analysis`` carries
  its per-pattern compiled-engine cache (``jit_cache``), so a warm hit
  also reuses every already-compiled XLA program.
* disk persistence — ``save_analysis`` / ``load_analysis`` serialize the
  full analysis artifact (matching, ordering, symbolic structure, the
  static FactorPlan with its node/edge maps) to a single versioned ``.npz``
  under ``<cache root>/plan_cache/<fingerprint>.npz``, where the cache
  root is ``HyluOptions.cache_root`` / ``$HYLU_CACHE_ROOT`` / the repo's
  ``checkpoints`` dir (see :func:`default_cache_root` — never the CWD,
  so bench and CI runs don't scatter cache dirs).  A fresh process
  loads the artifact and skips the host analyze phase entirely; only the
  XLA compile remains, which the persistent jax compilation cache absorbs.
  The level-bucketed factor schedule and solve structure are *derived*
  deterministically from the persisted plan at first engine build, so a
  reloaded analysis produces bit-identical factors and solves.

Persistence format (``FORMAT_VERSION``): one ``.npz`` holding a JSON
``meta`` record (version, fingerprint, options key, scalar fields) plus
flat numpy arrays — ragged plan structures (per-node patterns, per-node
edge lists, per-edge col_maps) are stored as concatenated arrays with
``*_ptr`` offset vectors, CSR-style.  Unknown versions and fingerprint
mismatches raise ``PlanCacheFormatError`` (a ``ValueError``); the cache
treats such files as misses and re-analyzes rather than guessing.

Cache-semantics note: the fingerprint is content-addressed on the
*pattern*, not the values.  A warm hit reuses matching/scaling computed
from the values that first populated the entry — exactly the repeated-
solve discipline of ``solve_sequence`` (static pivoting + perturbation +
refinement absorb mild value drift).  Callers whose values drift far
enough to need fresh pivoting should ``invalidate()`` the pattern.
"""
from __future__ import annotations

import dataclasses
import json
import os
import zipfile
from collections import OrderedDict

import numpy as np

from .matrix import CSR
from .matching import MatchResult
from .kernel_select import KernelChoice
from .symbolic import Symbolic
from .plan import FactorPlan, NodePlan, Edge
from .options import HyluOptions, plan_options_key, plan_fingerprint
from .analysis import Analysis, analyze
from .tracing import span

FORMAT_VERSION = 1
# Sentinel: resolved to <cache root>/plan_cache at PlanCache construction
# (NOT at import), so $HYLU_CACHE_ROOT set after import still wins.
DEFAULT_CACHE_DIR = "auto"


def default_cache_root() -> str:
    """The artifact-store root every component that persists state shares
    (plan cache, corpus downloads): ``$HYLU_CACHE_ROOT`` when set, else
    ``<repo>/checkpoints`` when this package runs from a source checkout
    (the historical location — next to the repo, NOT the CWD), else
    ``~/.cache/hylu`` for installed packages."""
    env = os.environ.get("HYLU_CACHE_ROOT")
    if env:
        return env
    # src/repro/core/plan_cache.py -> repo root is 4 levels up
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    if os.path.exists(os.path.join(repo, "pyproject.toml")):
        return os.path.join(repo, "checkpoints")
    return os.path.join(os.path.expanduser("~"), ".cache", "hylu")


def resolve_cache_dir(directory: str | None,
                      cache_root: str | None = None) -> str | None:
    """Map a PlanCache ``directory`` setting to a concrete path: the
    ``DEFAULT_CACHE_DIR`` sentinel becomes ``<root>/plan_cache`` where
    ``root`` is ``cache_root`` (``HyluOptions.cache_root``) or
    :func:`default_cache_root`; explicit paths and None pass through."""
    if directory != DEFAULT_CACHE_DIR:
        return directory
    return os.path.join(cache_root or default_cache_root(), "plan_cache")


class PlanCacheFormatError(ValueError):
    """Raised when a persisted plan artifact cannot be trusted: unknown
    format version, fingerprint mismatch, or a malformed file."""


def _jsonable(v):
    if isinstance(v, dict):
        return {k: _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    if isinstance(v, np.bool_):
        return bool(v)
    return v


def _cat(arrs, dtype=np.int64):
    """Concatenate possibly-empty ragged pieces with a stable dtype."""
    arrs = [np.asarray(a, dtype=dtype) for a in arrs]
    return (np.concatenate(arrs) if arrs
            else np.empty(0, dtype=dtype))


def _ptr(lengths):
    out = np.zeros(len(lengths) + 1, dtype=np.int64)
    np.cumsum(lengths, out=out[1:])
    return out


def save_analysis(an: Analysis, path: str) -> str:
    """Serialize one Analysis to a versioned ``.npz`` artifact (atomic
    write).  Everything value-independent about the pattern is captured;
    the compiled-engine cache is not (XLA programs persist via the jax
    compilation cache instead)."""
    plan, sym, match = an.plan, an.sym, an.match
    nodes = plan.nodes
    meta = {
        "format_version": FORMAT_VERSION,
        "fingerprint": an.fingerprint,
        "pattern_key": an.pattern_key,
        "options_key": repr(plan_options_key(an.opts)),
        "n": int(an.n),
        "ordering_name": an.ordering_name,
        "match_structurally_singular": bool(match.structurally_singular),
        "choice": {"mode": an.choice.mode, "relax": int(an.choice.relax),
                   "max_super": int(an.choice.max_super),
                   "reason": an.choice.reason,
                   "stats": _jsonable(an.choice.stats)},
        "sym": {"flops": float(sym.flops), "nnz_l": int(sym.nnz_l)},
        "plan": {"total_slots": int(plan.total_slots), "mode": plan.mode,
                 "useful_flops": float(plan.useful_flops),
                 "padded_flops": float(plan.padded_flops),
                 "n_bulk_levels": int(plan.n_bulk_levels)},
        "timings": _jsonable(an.timings),
    }
    edge_lists = [nd.edges for nd in nodes]
    all_edges = [e for edges in edge_lists for e in edges]
    arrays = dict(
        match_col_of_row=match.col_of_row,
        match_row_scale=match.row_scale,
        match_col_scale=match.col_scale,
        q=an.q, p=an.p,
        src_map=an.src_map, scale_map=an.scale_map,
        m_indptr=an.m_pattern[0], m_indices=an.m_pattern[1],
        sym_parent=sym.parent,
        sym_lrow_ptr=sym.lrow_ptr, sym_lrow_idx=sym.lrow_idx,
        sym_lcol_ptr=sym.lcol_ptr, sym_lcol_idx=sym.lcol_idx,
        sym_cc=sym.cc, sym_row_flops=sym.row_flops,
        sym_snode_of=sym.snode_of,
        sym_snode_start=sym.snode_start, sym_snode_end=sym.snode_end,
        plan_panel_offset=plan.panel_offset,
        plan_a_scatter=plan.a_scatter,
        plan_row_perm_slots=plan.row_perm_slots,
        node_r0=np.array([nd.r0 for nd in nodes], dtype=np.int64),
        node_r1=np.array([nd.r1 for nd in nodes], dtype=np.int64),
        node_level=np.array([nd.level for nd in nodes], dtype=np.int64),
        node_lsize=np.array([nd.lsize for nd in nodes], dtype=np.int64),
        node_usize=np.array([nd.usize for nd in nodes], dtype=np.int64),
        node_pat_ptr=_ptr([len(nd.pattern) for nd in nodes]),
        node_pat=_cat([nd.pattern for nd in nodes]),
        edge_ptr=_ptr([len(edges) for edges in edge_lists]),
        edge_src=np.array([e.src for e in all_edges], dtype=np.int64),
        edge_cm_ptr=_ptr([len(e.col_map) for e in all_edges]),
        edge_cm=_cat([e.col_map for e in all_edges]),
    )
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp-{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            np.savez_compressed(f, meta=json.dumps(meta), **arrays)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return path


def load_analysis(path: str, opts: HyluOptions | None = None,
                  expected_fingerprint: str | None = None) -> Analysis:
    """Reconstruct an Analysis from a persisted artifact.

    ``opts`` becomes the loaded analysis' options and must agree with the
    artifact on every plan-affecting field (validated via the persisted
    options key).  ``expected_fingerprint`` additionally pins the artifact
    to a specific content address.  Raises ``PlanCacheFormatError`` when
    the artifact cannot be trusted.  Its ``timings`` hold the seconds of
    this load (``load``, host span ``hylu.analyze.load``) and of the
    original analysis (``analyzed_total``)."""
    t = {}
    with span("analyze.load", into=t, key="load"):
        an = _read_analysis(path, opts or HyluOptions(), expected_fingerprint)
    an.timings.update(load=t["load"], total=t["load"])
    return an


def _read_analysis(path: str, opts: HyluOptions,
                   expected_fingerprint: str | None) -> Analysis:
    try:
        z = np.load(path, allow_pickle=False)
        meta = json.loads(str(z["meta"][()]))
    except (OSError, KeyError, ValueError, EOFError,
            zipfile.BadZipFile) as e:
        raise PlanCacheFormatError(f"unreadable plan artifact {path}: {e}")
    if meta.get("format_version") != FORMAT_VERSION:
        raise PlanCacheFormatError(
            f"{path}: format version {meta.get('format_version')!r} != "
            f"supported {FORMAT_VERSION}")
    if (expected_fingerprint is not None
            and meta.get("fingerprint") != expected_fingerprint):
        raise PlanCacheFormatError(
            f"{path}: stored fingerprint {meta.get('fingerprint')!r} does "
            f"not match expected {expected_fingerprint!r}")
    if meta.get("options_key") != repr(plan_options_key(opts)):
        raise PlanCacheFormatError(
            f"{path}: artifact was analyzed under plan options "
            f"{meta.get('options_key')} but is being loaded with "
            f"{plan_options_key(opts)!r}")
    required = {
        "match_col_of_row", "match_row_scale", "match_col_scale", "q", "p",
        "src_map", "scale_map", "m_indptr", "m_indices", "sym_parent",
        "sym_lrow_ptr", "sym_lrow_idx", "sym_lcol_ptr", "sym_lcol_idx",
        "sym_cc", "sym_row_flops", "sym_snode_of", "sym_snode_start",
        "sym_snode_end", "plan_panel_offset", "plan_a_scatter",
        "plan_row_perm_slots", "node_r0", "node_r1", "node_level",
        "node_lsize", "node_usize", "node_pat_ptr", "node_pat",
        "edge_ptr", "edge_src", "edge_cm_ptr", "edge_cm"}
    missing = required.difference(z.files)
    if missing:
        raise PlanCacheFormatError(
            f"{path}: artifact is missing arrays {sorted(missing)}")

    n = int(meta["n"])
    match = MatchResult(
        col_of_row=z["match_col_of_row"], row_scale=z["match_row_scale"],
        col_scale=z["match_col_scale"],
        structurally_singular=bool(meta["match_structurally_singular"]))
    cm = meta["choice"]
    choice = KernelChoice(mode=cm["mode"], relax=cm["relax"],
                          max_super=cm["max_super"], stats=cm["stats"],
                          reason=cm["reason"])
    sym = Symbolic(
        n=n, parent=z["sym_parent"],
        lrow_ptr=z["sym_lrow_ptr"], lrow_idx=z["sym_lrow_idx"],
        lcol_ptr=z["sym_lcol_ptr"], lcol_idx=z["sym_lcol_idx"],
        cc=z["sym_cc"], flops=float(meta["sym"]["flops"]),
        row_flops=z["sym_row_flops"], snode_of=z["sym_snode_of"],
        snode_start=z["sym_snode_start"], snode_end=z["sym_snode_end"],
        nnz_l=int(meta["sym"]["nnz_l"]))

    node_r0, node_r1 = z["node_r0"], z["node_r1"]
    node_level = z["node_level"]
    node_lsize, node_usize = z["node_lsize"], z["node_usize"]
    pat_ptr, pat = z["node_pat_ptr"], z["node_pat"]
    edge_ptr, edge_src = z["edge_ptr"], z["edge_src"]
    cm_ptr, cm_cat = z["edge_cm_ptr"], z["edge_cm"]
    nodes = []
    for t in range(len(node_r0)):
        edges = []
        for j in range(int(edge_ptr[t]), int(edge_ptr[t + 1])):
            edges.append(Edge(
                src=int(edge_src[j]),
                col_map=cm_cat[int(cm_ptr[j]):int(cm_ptr[j + 1])]))
        nodes.append(NodePlan(
            nid=t, r0=int(node_r0[t]), r1=int(node_r1[t]),
            pattern=pat[int(pat_ptr[t]):int(pat_ptr[t + 1])],
            lsize=int(node_lsize[t]), usize=int(node_usize[t]),
            edges=edges, level=int(node_level[t])))
    n_levels = int(node_level.max()) + 1 if len(node_level) else 0
    levels = [np.where(node_level == lv)[0] for lv in range(n_levels)]
    pm = meta["plan"]
    plan = FactorPlan(
        n=n, nodes=nodes, panel_offset=z["plan_panel_offset"],
        total_slots=int(pm["total_slots"]), a_scatter=z["plan_a_scatter"],
        levels=levels, n_bulk_levels=int(pm["n_bulk_levels"]),
        mode=pm["mode"], useful_flops=float(pm["useful_flops"]),
        padded_flops=float(pm["padded_flops"]),
        row_perm_slots=z["plan_row_perm_slots"])

    timings = {"analyzed_total": float(meta["timings"].get("total", 0.0))}
    return Analysis(
        n=n, opts=opts, match=match, q=z["q"], p=z["p"],
        ordering_name=meta["ordering_name"], choice=choice, sym=sym,
        plan=plan, src_map=z["src_map"], scale_map=z["scale_map"],
        m_pattern=(z["m_indptr"], z["m_indices"]), timings=timings,
        pattern_key=meta["pattern_key"], fingerprint=meta["fingerprint"])


@dataclasses.dataclass
class PlanCache:
    """LRU plan cache with optional disk persistence.

    capacity   — max in-memory entries; least-recently-used analyses (and
                 their compiled engines) are evicted beyond it
    directory  — persistence root (``<directory>/<fingerprint>.npz``);
                 None disables disk entirely; the default ``"auto"``
                 sentinel resolves to ``<cache root>/plan_cache`` at
                 construction via :func:`resolve_cache_dir` — i.e.
                 ``$HYLU_CACHE_ROOT`` or next to the repo, never the CWD
    cache_root — overrides the auto-resolved root (``HyluOptions.
                 cache_root``); ignored when ``directory`` is explicit

    ``stats`` counters: ``hits`` (in-memory), ``disk_hits`` (loaded from
    the artifact store — the analyze phase was skipped), ``misses`` (full
    host analyze ran; equals ``analyze_calls``), ``saves``, ``evictions``,
    plus accumulated ``analyze_s`` / ``load_s`` wall times."""
    capacity: int = 32
    directory: str | None = DEFAULT_CACHE_DIR
    cache_root: str | None = None

    def __post_init__(self):
        self.directory = resolve_cache_dir(self.directory, self.cache_root)
        self._entries: OrderedDict[str, Analysis] = OrderedDict()
        self.stats = dict(hits=0, misses=0, disk_hits=0, saves=0,
                          evictions=0, analyze_calls=0,
                          analyze_s=0.0, load_s=0.0)

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, fingerprint: str) -> bool:
        return fingerprint in self._entries

    def fingerprints(self):
        return list(self._entries)

    def path_for(self, fingerprint: str) -> str | None:
        if self.directory is None:
            return None
        return os.path.join(self.directory, f"{fingerprint}.npz")

    def fingerprint(self, a_or_pattern, opts: HyluOptions | None = None) -> str:
        return plan_fingerprint(a_or_pattern, opts)

    def get_or_analyze(self, a: CSR, opts: HyluOptions | None = None,
                       fingerprint: str | None = None) -> Analysis:
        """The cache's main entry: the Analysis for ``a``'s pattern under
        ``opts``, from memory, from the artifact store, or by running
        ``analyze`` (cold; the result is persisted when a directory is
        configured).  Warm hits ignore ``a``'s values (see the module
        docstring's cache-semantics note).  ``fingerprint`` passes an
        already-computed ``plan_fingerprint(a, opts)`` so hot callers (the
        serving dispatcher groups by it anyway) skip re-hashing the
        O(nnz) pattern."""
        opts = opts or HyluOptions()
        fp = fingerprint or plan_fingerprint(a, opts)
        an = self._entries.get(fp)
        if an is not None:
            self._entries.move_to_end(fp)
            self.stats["hits"] += 1
            return self._with_opts(an, opts)
        path = self.path_for(fp)
        if path is not None and os.path.exists(path):
            try:
                with span("plan_cache.load", into=self.stats, key="load_s"):
                    an = load_analysis(path, opts=opts,
                                       expected_fingerprint=fp)
                self.stats["disk_hits"] += 1
            except PlanCacheFormatError:
                an = None                     # untrusted artifact: re-analyze
        if an is None:
            with span("plan_cache.analyze", into=self.stats,
                      key="analyze_s"):
                an = analyze(a, opts)
            self.stats["misses"] += 1
            self.stats["analyze_calls"] += 1
            if path is not None:
                save_analysis(an, path)
                self.stats["saves"] += 1
        self._insert(fp, an)
        return an

    def put(self, an: Analysis) -> str:
        """Insert an externally-built Analysis (persisting it when a
        directory is configured) and return its fingerprint."""
        if not an.fingerprint:
            raise ValueError("analysis has no fingerprint (built by an old "
                             "analyze()?) — cannot content-address it")
        path = self.path_for(an.fingerprint)
        if path is not None and not os.path.exists(path):
            save_analysis(an, path)
            self.stats["saves"] += 1
        self._insert(an.fingerprint, an)
        return an.fingerprint

    def invalidate(self, fingerprint: str, disk: bool = False) -> None:
        """Drop one entry (e.g. after heavy value drift made the cached
        matching/scaling stale); ``disk=True`` also removes the artifact."""
        self._entries.pop(fingerprint, None)
        path = self.path_for(fingerprint)
        if disk and path is not None and os.path.exists(path):
            os.remove(path)

    def clear(self) -> None:
        self._entries.clear()

    @staticmethod
    def _with_opts(an: Analysis, opts: HyluOptions) -> Analysis:
        """A hit must honor the *caller's* runtime-only options (engine /
        mesh / donate / refinement caps — the fields the fingerprint
        deliberately excludes), not whichever opts first populated the
        entry.  When they differ, return a shallow per-caller view: same
        plan/symbolic/matching arrays AND the same ``jit_cache`` dict
        (compiled engines stay shared — its keys already encode
        dtype/pallas/schedule/mesh), only ``opts`` rebound.  This keeps
        memory hits consistent with the disk-hit path, which loads the
        artifact under the caller's opts."""
        if an.opts == opts:
            return an
        return dataclasses.replace(an, opts=opts)

    def _insert(self, fp: str, an: Analysis) -> None:
        self._entries[fp] = an
        self._entries.move_to_end(fp)
        while len(self._entries) > max(int(self.capacity), 1):
            self._entries.popitem(last=False)
            self.stats["evictions"] += 1
