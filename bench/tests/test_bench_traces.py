"""The trace reduction and the per-layer metric readers on small traces
recorded on a TPU v5e.

- ``tiny_tpu.xplane.pb.gz``: two FNU rounds of ResNet-4 (3 clients of 20
  images, batch 10, fused masked Adam) on the vmap engine, under the
  benchmark's own round annotations, of a program without spans or scopes.
- ``<config>.xplane.pb.gz`` with ``<config>.ctx.json``, one pair for every
  configuration of ``BENCHMARK.json``: a test-size run of that
  configuration's program with its spans and scopes, recorded by
  ``bench/tests/record_trace.py``.  Every per-layer metric that lists a cell
  of the configuration reads a value on it.
"""

import gzip
import json
import shutil
from pathlib import Path

import pytest

from bench import harness, scopes, traces

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"
FIXTURE = DATA / "tiny_tpu.xplane.pb.gz"
CONFIGS = [c["name"] for c in json.loads((ROOT / "BENCHMARK.json").read_text())["configs"]]
# The readings of bench/scopes.py, each the per-layer metric of its name.
SCOPE_METRICS = ("host_prep_ms", "idle_host_prep_frac", "grad_ms",
                 "masked_adam_ms", "step_overhead_ms")


def _unzip(gz: Path, tmp: Path) -> Path:
    path = tmp / gz.name.removesuffix(".gz")
    path.write_bytes(gzip.decompress(gz.read_bytes()))
    return path


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    return traces.load(str(_unzip(FIXTURE, tmp_path_factory.mktemp("trace"))))


@pytest.fixture(scope="module")
def r18(tmp_path_factory):
    return config_context(ROOT, "resnet18-cifar100", tmp_path_factory.mktemp("r18"))


def config_context(root: Path, config: str, tmp: Path):
    """The cell and the readers' context of ``config``'s recorded trace,
    found by name under ``<root>/bench/tests/data``; the cell's per-layer
    metrics are those that list a cell of the configuration."""
    data = root / "bench" / "tests" / "data"
    meta = harness.load_json(data / f"{config}.ctx.json")
    path = _unzip(data / f"{config}.xplane.pb.gz", tmp)
    bench = harness.load_json(root / "BENCHMARK.json")
    cells = {w["name"] for w in bench["workloads"] if w["config"] == config}
    per_layer = [m for m in bench["per_layer"] if cells & set(m.get("workloads", cells))]
    cfg = meta["config"]
    cell = harness.Cell(config, meta["chips"], cfg, meta["traffic"], {}, per_layer,
                        harness.load_module(root, "reference", cfg["reference"]), None,
                        {m["name"]: harness.load_module(root, "metrics", m["name"])
                         for m in per_layer})
    peaks = harness.load_json(root / "bench" / "peaks.json")[meta["device_kind"]]
    return cell, harness.trace_context(cell, path, peaks, meta["traced_groups"],
                                       meta["setup_compile_s"])


def check_config_readers(root: Path, config: str, tmp: Path) -> dict:
    """Every per-layer metric of ``config``'s cells reads a value on its
    trace, in its unit, a share within its range."""
    cell, ctx = config_context(root, config, tmp)
    out = harness.read_metrics(cell, ctx)
    assert set(out) == {m["name"] for m in cell.per_layer}, config
    for m in cell.per_layer:
        v = out[m["name"]]
        assert v["unit"] == m["unit"]
        if m["unit"] == "%":
            assert 0 < v["value"] < 100, m["name"]
        elif m["unit"] == "share":
            assert 0 <= v["value"] <= 1, m["name"]
        else:
            assert v["value"] > 0, m["name"]
    return out


def test_window_rounds_and_busy_time(trace):
    assert [r.name for r in trace.rounds] == ["bench_round 3 group -1",
                                              "bench_round 4 group -1"]
    assert list(trace.ops) == ["/device:TPU:0"]
    assert trace.window_s == pytest.approx(0.017836, rel=1e-3)
    assert 0 < trace.busy_s() < trace.window_s
    # The busy time is no more than the programs' time: ops sit inside them.
    programs = sum(trace.module_s(m) for m in ("jit_local_round", "jit_agg",
                                                "jit_evaluate", "jit_convert"))
    assert trace.busy_s() <= programs * 1.001


def test_programs_and_kernel_are_found(trace):
    from bench.metrics import masked_adam_roofline

    local = trace.module_s("jit_local_round")
    assert local > 10 * trace.module_s("jit_agg") > 0
    assert trace.module_s("jit_evaluate") > 0
    kernel = trace.op_s(masked_adam_roofline.KERNEL)
    assert 0 < kernel < local


def test_breakdown_lists(trace):
    ops = trace.top_ops()
    assert 0 < len(ops) <= 10
    assert not any(n.startswith(traces.CONTAINERS) for n, _ in ops)
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    gaps = trace.idle_gaps()
    assert 0 < len(gaps) <= 10 and all(isinstance(n, str) and n for n, _ in gaps)
    assert sum(s for _, s in gaps) <= trace.window_s - trace.busy_s() + 1e-9


@pytest.mark.parametrize("config", CONFIGS)
def test_metric_readers_on_the_trace(config, tmp_path):
    check_config_readers(ROOT, config, tmp_path)


def test_resnet18_readings_on_its_trace(r18):
    cell, ctx = r18
    out = {k: v["value"] for k, v in harness.read_metrics(cell, ctx).items()}
    tr, n = ctx["trace"], len(ctx["traced_groups"])
    # the traced rounds are one whole FedPart cycle of partial rounds
    assert sorted(ctx["traced_groups"]) == list(range(cell.reference.num_groups(cell.config)))
    assert 0.9 < out["device_idle_frac"] < 1.0
    assert out["local_round_ms"] == pytest.approx(1e3 * tr.module_s("jit_local_round") / n)
    split = out["grad_ms"] + out["masked_adam_ms"] + out["step_overhead_ms"]
    assert split == pytest.approx(out["local_round_ms"], rel=0.03)
    assert out["idle_host_prep_frac"] < out["device_idle_frac"]
    assert out["setup_compile_s"] > 0


@pytest.mark.parametrize("name", SCOPE_METRICS)
def test_a_scope_reader_is_its_reading(name, r18):
    _, ctx = r18
    mod = harness.load_module(ROOT, "metrics", name)
    got = mod.read(ctx)
    assert got is not None
    assert got == scopes.readings(ctx["trace"], ctx["ops"])[name]


@pytest.mark.parametrize("name", SCOPE_METRICS)
def test_a_scope_reader_reads_nothing_without_marks_or_op_paths(name, trace, r18,
                                                               tmp_path):
    mod = harness.load_module(ROOT, "metrics", name)
    ops = scopes.load_ops(_unzip(FIXTURE, tmp_path), trace.window)
    assert mod.read(dict(trace=trace, ops=ops)) is None
    assert mod.read(dict(r18[1], ops=None)) is None


def test_without_the_xspace_module_only_the_scope_readers_go_silent(monkeypatch, tmp_path):
    monkeypatch.setattr(scopes.importlib.util, "find_spec", lambda name: None)
    cell, ctx = config_context(ROOT, "resnet18-cifar100", tmp_path)
    assert ctx["ops"] is None
    out = harness.read_metrics(cell, ctx)
    assert set(out) == {m["name"] for m in cell.per_layer} - set(SCOPE_METRICS)


def test_a_config_reads_its_metrics_only_from_a_pair_of_its_own(tmp_path):
    """A configuration whose cells a metric lists fails the check until
    its trace and context are added, under its own name."""
    root = tmp_path / "copy"
    root.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(".trace", "__pycache__"))
    b = root / "bench"
    cfg = json.loads((b / "configs" / "resnet18-cifar100.json").read_text())
    cfg.update(name="resnet8-cifar10", stages=[1, 1, 2], channels=[16, 32, 64],
               num_classes=10)
    (b / "configs" / "resnet8-cifar10.json").write_text(json.dumps(cfg))
    (b / "metrics" / "rounds_traced.py").write_text(
        "def read(ctx):\n    return float(len(ctx['traced_groups']))\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": cfg["name"], "source": cfg["source"],
                             "file": "bench/configs/resnet8-cifar10.json",
                             "reduced": [], "why": "a copy"})
    bench["workloads"].append({"name": "new-cell", "config": cfg["name"],
                               "traffic": "fedpart-b50-e1-fused", "chips": 1,
                               "why": "new"})
    bench["per_layer"].append({"name": "rounds_traced", "unit": "rounds",
                               "better": "higher", "source": "program_counter",
                               "layer": "federated round",
                               "moves": "client_samples_per_s",
                               "workloads": ["new-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    with pytest.raises(FileNotFoundError):
        check_config_readers(root, "resnet8-cifar10", tmp_path)
    for suffix in (".xplane.pb.gz", ".ctx.json"):
        shutil.copy(DATA / f"resnet18-cifar100{suffix}",
                    b / "tests" / "data" / f"resnet8-cifar10{suffix}")
    out = check_config_readers(root, "resnet8-cifar10", tmp_path)
    assert set(out) == {"rounds_traced"}
    # the configuration already in the copy still reads all of its own
    assert "rounds_traced" not in check_config_readers(root, "resnet18-cifar100", tmp_path)


def test_a_reader_with_nothing_to_read_returns_none(trace):
    from bench.metrics import aggregate_ms, masked_adam_roofline

    empty = traces.Trace({}, {}, trace.host, trace.rounds)
    ctx = dict(trace=empty, traced_groups=[-1])
    assert aggregate_ms.read(ctx) is None
    assert masked_adam_roofline.read(ctx) is None
