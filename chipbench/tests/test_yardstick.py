"""The copied yardstick agrees with the repository's originals: the
pattern generators bit for bit, the reference's numbers to rounding."""
import importlib.util
import inspect
import json
import os

import numpy as np
import pytest

from chipbench import harness, patterns, reference

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPEC = harness.load_json(harness.ROOT / "BENCHMARK.json")


def _smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _same(a, b):
    a, b = a.tocsr(), b.tocsr()
    a.sort_indices()
    b.sort_indices()
    return (a.shape == b.shape and np.array_equal(a.indptr, b.indptr)
            and np.array_equal(a.indices, b.indices)
            and np.array_equal(a.data, b.data))


def _corpus_calls():
    """``(generator, args)`` of each corpus entry, read by calling the
    entries' `gen` with the generators replaced by a recorder."""
    from benchmarks import corpus, matrices

    calls = []
    saved = {name: fn for name, fn in vars(corpus).items()
             if inspect.isfunction(fn) and fn.__module__ == matrices.__name__}
    try:
        for name, fn in saved.items():
            sig = inspect.signature(fn)

            def record(*a, _name=name, _sig=sig, **kw):
                calls.append((_name, dict(_sig.bind(*a, **kw).arguments)))
            setattr(corpus, name, record)
        for e in corpus.corpus():
            e.gen()
    finally:
        for name, fn in saved.items():
            setattr(corpus, name, fn)
    return calls


def _generator_cases():
    """Every generator file with a namesake in ``benchmarks/matrices.py``,
    at the args of each corpus entry that uses it, and at a second size of
    each of the benchmark's own two."""
    from benchmarks import matrices

    extra = [("circuit_like", dict(n=2000, seed=912)),
             ("fem2d", dict(nx=70, ny=70, seed=930))]
    return [(name, args) for name, args in _corpus_calls() + extra
            if (patterns.GENERATORS / f"{name}.py").is_file()
            and hasattr(matrices, name)]


@pytest.mark.parametrize("name,args", _generator_cases(),
                         ids=lambda v: v if isinstance(v, str) else
                         "-".join(map(str, v.values())))
def test_generators_reproduce_the_originals(name, args):
    from benchmarks import matrices

    assert _same(patterns.build({"generator": name, "args": args}),
                 getattr(matrices, name)(**args))


@pytest.mark.parametrize("file", [c["file"] for c in SPEC["configs"]])
def test_configs_reproduce_the_corpus(file):
    """Each configuration's stand-in is its corpus entry's matrix, and its
    pattern differs from the stand-in only in the keys it lists as
    reduced."""
    from benchmarks import corpus

    cfg = json.load(open(os.path.join(_ROOT, file)))
    stand_in = cfg["published"]["stand_in"]
    e = {c.name: c for c in corpus.corpus()}[stand_in["corpus_entry"]]
    a, _, meta = corpus.load_entry(e, allow_download=False)
    assert meta["source"] == "synthetic"
    built = patterns.build(dict(cfg["pattern"], args=stand_in["args"]))
    assert _same(built, e.gen()) and built.nnz == stand_in["nnz"]
    args = cfg["pattern"]["args"]
    assert {k for k in args if args[k] != stand_in["args"][k]} \
        <= set(cfg["reduced"])


def test_perturbed_values_and_oracle_agree_with_chip_smoke():
    smoke = _smoke()
    a = patterns.build({"generator": "circuit_like",
                       "args": {"n": 300, "seed": 5}})
    v1 = reference.perturbed_values(a, 3, np.random.default_rng(9))
    v2 = smoke.perturbed_values(a, 3, np.random.default_rng(9))
    assert np.array_equal(v1, v2)
    b = np.random.default_rng(1).standard_normal(300)
    for v in v1:
        mine, theirs = reference.Oracle(a, v), smoke.Oracle(a, v)
        x = theirs.lu.solve(b) * (1 + 1e-9)
        e1, _, r1 = mine.errors(x, b, 1e-12)
        e2, _, r2 = theirs.errors(x, b, 1e-12)
        assert e1 == e2 and r1 == r2
        assert mine.cond1 == pytest.approx(theirs.cond1, rel=1e-6)
