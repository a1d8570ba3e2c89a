"""``correct`` holds on sound runs and comes out false for the control and
for each fault the cells can have, on small sizes on the CPU.

Each run drives the whole harness (set-up, window, reference check) with
the device check off; the faults are planted underneath, in the program's
own entry points, where the answers are produced."""
import json

import numpy as np
import pytest

from chipbench import harness

SMALL = {
    "circuit-sweep": ({"pattern": {"args": {"n": 200}}},
                      {"systems_per_step": 4, "samples_per_step": 4}),
    "fem2d-sweep": ({"pattern": {"args": {"nx": 10, "ny": 10}}},
                    {"systems_per_step": 4, "samples_per_step": 4}),
}
SPEC = harness.load_json(harness.ROOT / "BENCHMARK.json")
CONTROL = {"precision": {"refine_dtype": "float32"}}


@pytest.fixture(scope="module")
def memo():
    return {}


def run(cell, memo, config=None, seed=11, seconds=1.5):
    cfg, trf = SMALL[cell]
    return harness.run_cell(
        cell, seed, seconds, False, device_check=False, compile_cache=False,
        config_over=harness._merge(cfg, config), traffic_over=trf, memo=memo,
        spec=SPEC)


@pytest.mark.parametrize("cell", list(SMALL))
def test_sound_run_is_correct(cell, memo):
    r = run(cell, memo)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 4
    assert list(r)[-1] == "checks"
    assert {"setup_s"} < set(r["metrics"])
    json.dumps(r)


@pytest.mark.parametrize("cell", list(SMALL))
def test_control_is_not_correct(cell, memo):
    """The control: the configuration's float64 refinement lowered to
    float32, the step a later change would be tempted by."""
    r = run(cell, memo, config=CONTROL)
    assert not r["correct"]
    c = r["checks"]
    assert c["residual"]["value"] > 3 * c["residual"]["limit"]


def _alter_one(x):
    x = np.array(x)
    x[0] = x[0] * (1 + 1e-6)
    return x


@pytest.mark.parametrize("cell", list(SMALL))
def test_sweep_answer_altered(cell, memo, monkeypatch):
    from repro.core import batched

    real = batched.solve_batched

    def altered(bst, b, **kw):
        x, info = real(bst, b, **kw)
        return _alter_one(x), info

    monkeypatch.setattr(batched, "solve_batched", altered)
    assert not run(cell, memo)["correct"]


@pytest.mark.parametrize("cell", list(SMALL))
def test_sweep_state_left_unchanged(cell, memo, monkeypatch):
    """The refactor returns its first state again: every later step solves
    the warm-up's matrices, not its own fresh ones."""
    from repro.core import batched

    real = batched.factor_batched
    first = []

    def stale(an, pattern, values):
        if not first:
            first.append(real(an, pattern, values))
        return first[0]

    monkeypatch.setattr(batched, "factor_batched", stale)
    assert not run(cell, memo)["correct"]


@pytest.mark.parametrize("cell", list(SMALL))
def test_sweep_half_the_batch_left_out(cell, memo, monkeypatch):
    """Only the first half of each batch is solved; the rest come back
    as the answers of the first half."""
    from repro.core import batched

    real = batched.solve_batched

    def half(bst, b, **kw):
        x, info = real(bst, b, **kw)
        x = np.array(x)
        h = len(x) // 2
        x[h:] = x[:len(x) - h]
        return x, info

    monkeypatch.setattr(batched, "solve_batched", half)
    assert not run(cell, memo)["correct"]
