"""Every file the benchmark names loads and is wired by name, and a new
configuration, traffic mix, cell and per-layer metric are picked up from new
files alone."""

import json
import re
import shutil
from pathlib import Path

import pytest

from bench import correct, harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_benchmark_json_keeps_the_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_loads_with_its_files(cell):
    c = harness.load_cell(cell)
    assert c.config["name"] == next(w["config"] for w in BENCH["workloads"]
                                    if w["name"] == cell)
    assert set(c.limits) <= set(correct.NUMBERS)
    compared = [k for k, v in c.limits.items() if "limit" in v]
    assert compared
    for k in compared:
        v = c.limits[k]
        assert v["lower"] < v["limit"] < v["upper"], k
    assert c.traffic["schedule"] in ("fedpart", "fnu")
    assert hasattr(c.reference, "Federation") and hasattr(c.data, "make")
    for m in c.per_layer:
        assert callable(c.metrics[m["name"]].read)


def test_every_metric_file_is_named_in_the_benchmark():
    named = {m["name"] for m in BENCH["per_layer"]}
    files = {p.stem for p in (ROOT / "bench" / "metrics").glob("*.py")}
    assert files == named


def test_new_files_alone_add_a_config_a_cell_and_a_metric(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", ".trace", "__pycache__"))
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "resnet18-cifar100.json").read_text())
    cfg.update(name="resnet8-cifar10", stages=[1, 1, 2], channels=[16, 32, 64],
               num_classes=10)
    cfg["program"]["args"] = {"depth": "resnet8", "num_classes": 10}
    (b / "configs" / "resnet8-cifar10.json").write_text(json.dumps(cfg))
    (b / "traffic" / "fnu-b25.json").write_text(json.dumps(
        dict(json.loads((b / "traffic" / "fedpart-b50-e1-fused.json").read_text()),
             schedule="fnu", batch=25)))
    (b / "limits" / "new-cell.json").write_text(json.dumps(
        {"loss_gap": {"limit": 0.5}}))
    (b / "metrics" / "rounds_traced.py").write_text(
        "def read(ctx):\n    return float(len(ctx['traced_groups']))\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": cfg["name"], "source": cfg["source"],
                             "file": "bench/configs/resnet8-cifar10.json",
                             "reduced": [], "why": "a copy"})
    bench["workloads"].append({"name": "new-cell", "config": cfg["name"],
                               "traffic": "fnu-b25", "chips": 1, "why": "new"})
    bench["per_layer"].append({"name": "rounds_traced", "unit": "rounds",
                               "better": "higher", "source": "program_counter",
                               "layer": "federated round",
                               "moves": "client_samples_per_s",
                               "workloads": ["new-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell("new-cell", root=tmp_path)
    assert cell.traffic["batch"] == 25 and cell.config["name"] == cfg["name"]
    assert [m["name"] for m in cell.per_layer] == ["rounds_traced"]
    assert harness.read_metrics(cell, {"traced_groups": [-1, -1]}) == {
        "rounds_traced": {"value": 2.0, "unit": "rounds"}}


def test_the_command_refuses_a_machine_without_a_tpu(tmp_path):
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = BENCH["command"] + ["--workload", CELLS[0], "--seed", str(2**31 + 5),
                              "--seconds", "1", "--trace", "0"]
    cmd[0] = sys.executable
    out = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_the_command_fails_without_the_program(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's files
    runs nothing and prints no result."""
    import os
    import subprocess
    import sys

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", ".trace", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    cmd = BENCH["command"] + ["--workload", CELLS[0], "--seed", str(2**31 + 5),
                              "--seconds", "1", "--trace", "0"]
    cmd[0] = sys.executable
    out = subprocess.run(cmd, cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "No module named 'repro'" in out.stderr
