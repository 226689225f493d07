"""The trace reduction and the per-layer metric readers on a small trace
recorded on a TPU v5e: two FNU rounds of ResNet-4 (3 clients of 20 images,
batch 10, fused masked Adam) on the vmap engine, under the benchmark's own
round annotations."""

import gzip
import json
from pathlib import Path

import pytest

from bench import harness, traces

ROOT = Path(__file__).resolve().parents[2]
FIXTURE = Path(__file__).parent / "data" / "tiny_tpu.xplane.pb.gz"
RESNET4 = {"stages": [1, 1], "channels": [8, 16], "num_classes": 5,
           "image_size": 8, "in_channels": 3}


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    path.write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    return traces.load(str(path))


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


def test_metric_readers_on_the_trace(trace):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    per_layer = [m for m in bench["per_layer"] if m["name"] != "setup_compile_s"]
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())["TPU v5 lite"]
    from bench.reference import resnet

    traffic = {"cohort": 3, "batch": 10, "local_epochs": 1}
    cell = harness.Cell("tiny", 1, RESNET4, traffic, {}, per_layer, resnet, None,
                        {m["name"]: harness.load_module(ROOT, "metrics", m["name"])
                         for m in per_layer})
    ctx = dict(trace=trace, cell=cell, peaks=peaks, traced_groups=[-1, -1],
               samples_per_round=60, client_steps=2,
               group_fwd_flops=resnet.group_forward_flops(RESNET4),
               group_trained_params=resnet.group_trained_params(RESNET4))
    out = harness.read_metrics(cell, ctx)
    assert set(out) == {m["name"] for m in per_layer}
    assert 0.9 < out["device_idle_frac"]["value"] < 1.0
    assert 0 < out["round_mfu"]["value"] < 100
    assert 0 < out["masked_adam_roofline"]["value"] < 100
    assert out["local_round_ms"]["value"] == pytest.approx(
        1e3 * trace.module_s("jit_local_round") / 2)
    assert all(out[k]["unit"] == "ms/round" for k in ("aggregate_ms", "eval_ms"))


def test_a_reader_with_nothing_to_read_returns_none(trace):
    from bench.metrics import aggregate_ms, masked_adam_roofline

    empty = traces.Trace({}, {}, trace.host, trace.rounds)
    ctx = dict(trace=empty, traced_groups=[-1])
    assert aggregate_ms.read(ctx) is None
    assert masked_adam_roofline.read(ctx) is None
