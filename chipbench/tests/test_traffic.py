"""The sweep offers every seed the same work on fresh inputs."""
import numpy as np

from chipbench import harness, patterns

sweep = harness.load_module(harness.BENCH / "traffic" / "sweep.py")


class _Env:
    a_sp = patterns.build({"generator": "circuit_like",
                           "args": {"n": 60, "seed": 3}})

    class a:
        n = 60

    @staticmethod
    def span(name):
        import contextlib
        return contextlib.nullcontext()


def _traffic(seed):
    t = sweep.Traffic.__new__(sweep.Traffic)
    t.env, t.k, t.seed = _Env, 3, seed
    return t


def test_every_step_draws_fresh_inputs():
    t = _traffic(2 ** 31 + 11)
    v0, b0 = t._inputs(2)
    v1, b1 = t._inputs(3)
    assert v0.shape == (3, _Env.a_sp.nnz) and b0.shape == (3, 60)
    assert not np.array_equal(v0, v1) and not np.array_equal(b0, b1)
    v2, b2 = _traffic(2 ** 31 + 11)._inputs(2)
    assert np.array_equal(v0, v2) and np.array_equal(b0, b2)


def test_seeds_give_the_same_inputs_and_differ_from_each_other():
    a = harness.rng(2 ** 31 + 11, 0).standard_normal(4)
    b = harness.rng(2 ** 31 + 11, 0).standard_normal(4)
    c = harness.rng(2 ** 31 + 12, 0).standard_normal(4)
    d = harness.rng(-5, 0).standard_normal(4)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.isfinite(d).all()
