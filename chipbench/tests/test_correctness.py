"""``correct`` holds on sound runs and comes out false for the control and
for each fault the cells can have, on small sizes on the CPU.

Each run drives the whole harness (set-up, window, reference check) with
the device check off; the faults are planted underneath, in the program's
own entry points, where the answers are produced."""
import copy
import json

import numpy as np
import pytest

from chipbench import harness

SPEC = harness.load_json(harness.ROOT / "BENCHMARK.json")
CONTROL = {"precision": {"refine_dtype": "float32"}}
# A configuration that no cell of BENCHMARK.json names, brought in as a
# configuration file of its own (its generator has a file of its own too):
# the harness takes it with no edit to an existing file.
ADDED_CONFIG = {
    "name": "fem3d",
    "pattern": {"generator": "fem3d",
                "args": {"nx": 22, "ny": 22, "nz": 22, "seed": 931}},
    # 8 x 8 x 8 routes hybrid, as 22 x 22 x 22 does (6 x 6 x 6: rowrow)
    "rehearsal": {"config": {"pattern": {"args": {"nx": 8, "ny": 8,
                                                  "nz": 8}}},
                  "traffic": {"systems_per_step": 4, "samples_per_step": 4}},
    "precision": {"factor_dtype": "float32", "refine_dtype": "float64"},
    "options": {"fp64_fallback": False, "retry_max": 0},
    "limits": {"residual": 1e-12, "error_ratio": 1.0},
}
ADDED_CELL = {"name": "fem3d-sweep", "config": "fem3d",
              "traffic": "sweep_k32", "chips": 1,
              "why": "a configuration added from new files alone"}
CELLS = [w["name"] for w in SPEC["workloads"]] + [ADDED_CELL["name"]]


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    """BENCHMARK.json with the added configuration and its cell, which
    reports every sweep metric, in memory; the configuration's file is
    written outside the checkout."""
    path = tmp_path_factory.mktemp("configs") / "fem3d.json"
    path.write_text(json.dumps(ADDED_CONFIG))
    out = copy.deepcopy(SPEC)
    out["configs"].append({"name": "fem3d", "file": str(path)})
    out["workloads"].append(ADDED_CELL)
    for m in out["end_to_end"] + out["per_layer"]:
        m.get("workloads", []).append(ADDED_CELL["name"])
    return out


@pytest.fixture(scope="module")
def memo():
    return {}


def run(cell, spec, memo, config=None, seed=11, seconds=1.5):
    """One run of the cell at its configuration's CPU rehearsal size."""
    small = harness.load_cell(cell, spec).config["rehearsal"]
    return harness.run_cell(
        cell, seed, seconds, False, device_check=False, compile_cache=False,
        config_over=harness._merge(small["config"], config),
        traffic_over=small["traffic"], memo=memo, spec=spec)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, spec, memo):
    r = run(cell, spec, memo)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 4
    assert list(r)[-1] == "checks"
    assert {"setup_s"} < set(r["metrics"])
    json.dumps(r)


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell, spec, memo):
    """The control: the configuration's float64 refinement lowered to
    float32, the step a later change would be tempted by."""
    r = run(cell, spec, memo, config=CONTROL)
    assert not r["correct"]
    c = r["checks"]
    assert c["residual"]["value"] > 3 * c["residual"]["limit"]


def _alter_one(x):
    x = np.array(x)
    x[0] = x[0] * (1 + 1e-6)
    return x


@pytest.mark.parametrize("cell", CELLS)
def test_sweep_answer_altered(cell, spec, memo, monkeypatch):
    from repro.core import batched

    real = batched.solve_batched

    def altered(bst, b, **kw):
        x, info = real(bst, b, **kw)
        return _alter_one(x), info

    monkeypatch.setattr(batched, "solve_batched", altered)
    assert not run(cell, spec, memo)["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_sweep_state_left_unchanged(cell, spec, memo, monkeypatch):
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
    assert not run(cell, spec, memo)["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_sweep_half_the_batch_left_out(cell, spec, memo, monkeypatch):
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
    assert not run(cell, spec, memo)["correct"]
