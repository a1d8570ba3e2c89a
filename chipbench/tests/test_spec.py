"""``BENCHMARK.json`` keeps to the benchmark's contract, every name it
holds is found as a file, and a run refuses what it must refuse."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from chipbench import harness

ROOT = harness.ROOT
SPEC = json.load(open(ROOT / "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRIC_KEYS = {"name", "unit", "better", "source", "workloads"}


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "chipbench/run.py"]
    assert SPEC["paths"] == ["chipbench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    n_cells = 24                    # the most cells later PRs may reach
    budget = ((2 + 14 * n_cells) * (SPEC["run_seconds"] + 60)
              + n_cells * 2 * 90 + 1200)
    assert budget <= 43200


def test_names_units_and_text():
    names = ([c["name"] for c in SPEC["configs"]]
             + [w["name"] for w in SPEC["workloads"]]
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert all(NAME.match(n) for n in names)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        ns = [x["name"] for x in SPEC[group]]
        assert len(ns) == len(set(ns))
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for x in SPEC["configs"] + SPEC["workloads"]:
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"]


def test_configs_and_cells():
    cfgs = {c["name"]: c for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200
        f = json.load(open(ROOT / c["file"]))
        assert f["name"] == c["name"] and f["source"] == c["source"]
        assert sorted(f["reduced"]) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert f["published"]["args"][key] != f["pattern"]["args"][key]
        assert (ROOT / "chipbench" / "generators"
                / f"{f['pattern']['generator']}.py").is_file()
        assert set(f["rehearsal"]) == {"config", "traffic"}
    pairs = set()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        t = json.load(open(ROOT / "chipbench" / "traffic"
                           / f"{w['traffic']}.json"))
        assert (ROOT / "chipbench" / "traffic" / f"{t['kind']}.py").exists()
    assert {w["config"] for w in SPEC["workloads"]} == set(cfgs)


def test_metrics():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in SPEC["end_to_end"]:
        assert set(m) <= METRIC_KEYS | {"bound"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) <= METRIC_KEYS | {"layer", "moves"}
        assert m["moves"] in e2e
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").exists()
        for w in m["workloads"]:
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", [w])
    for w in cells:
        cell = harness.load_cell(w, SPEC)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer


def test_an_unknown_generator_fails_before_analysis():
    with pytest.raises(KeyError) as e:
        harness.run_cell("circuit-sweep", 1, 1.0, False, device_check=False,
                         compile_cache=False,
                         config_over={"pattern": {"generator": "no_such"}})
    assert "no_such" in str(e.value)
    assert all(g in str(e.value) for g in ("circuit_like", "fem2d", "fem3d",
                                           "powergrid_like"))


def test_refuses_to_run_without_an_accelerator(capsys):
    rc = harness.main(["--workload", "circuit-sweep", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "accelerator" in out.err


def test_fails_in_a_directory_of_the_benchmark_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "circuit-sweep",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""
