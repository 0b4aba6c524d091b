"""BENCHMARK.json against the files the harness finds by name, and the
harness's refusal to run without a chip."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
M = harness.load_manifest(ROOT)
WORKLOADS = [w["name"] for w in M["workloads"]]
E2E = {m["name"]: m for m in M["end_to_end"]}


def test_top_level_keys_and_command():
    assert set(M) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert M["paths"] == ["benchmarks/chip"]
    script = M["command"][-1]
    assert script.startswith(M["paths"][0] + "/") and os.path.isfile(os.path.join(ROOT, script))
    assert 1 <= M["run_seconds"] <= 51


@pytest.mark.parametrize("name", WORKLOADS)
def test_cell_resolves_by_name(name):
    spec = harness.resolve(name, ROOT)
    w, cfg, traffic = spec["workload"], spec["config"], spec["traffic"]
    assert cfg["name"] == w["config"] and cfg["chips"] == w["chips"] in (1, 4)
    assert traffic["loop"] in ("closed", "open") and traffic["engine"]["max_batch"] >= 1
    assert {"off_share_worst", "max_abs_diff"} <= set(cfg["limits"])
    assert traffic["loop"] == "closed" or len(traffic["phases"]) == traffic["streams"]
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2 and spec["per_layer"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.reader(m["name"]))


def test_names_files_and_uniqueness():
    entries = M["configs"] + M["workloads"] + M["end_to_end"] + M["per_layer"]
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    assert len({e["name"] for e in M["configs"]}) == len(M["configs"])
    assert len(set(WORKLOADS)) == len(WORKLOADS)
    metrics = [m["name"] for m in M["end_to_end"] + M["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for c in M["configs"]:
        assert c["file"].startswith(M["paths"][0] + "/")
        assert {w["config"] for w in M["workloads"]} >= {c["name"]}
    assert len({c["file"] for c in M["configs"]}) == len(M["configs"])


def test_each_layer_metric_moves_what_its_cells_report():
    for m in M["per_layer"]:
        target = E2E[m["moves"]]
        for w in m.get("workloads", WORKLOADS):
            assert w in WORKLOADS
            assert w in target.get("workloads", WORKLOADS), (m["name"], w)
    layers = {}
    for m in M["per_layer"]:
        layers.setdefault(m["layer"], set()).add(m["name"].split(".")[0])
    assert set(layers) <= {"client", "engine", "plan", "device", "kernel"}


def test_roofline_names_follow_the_kernel():
    for m in M["per_layer"]:
        if "roofline" in m["name"]:
            assert m["name"].split(".")[0].endswith("_roofline") and m["unit"] == "%"


def _run_cell(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run_cell.py", "--workload", WORKLOADS[0],
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_no_chip_no_result():
    out = _run_cell(ROOT)
    assert out.returncode != 0
    assert "nothing was run" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_no_result_from_the_benchmark_files_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_cell(tmp_path)
    assert out.returncode != 0
    assert not any(line.startswith("{") for line in out.stdout.splitlines())
