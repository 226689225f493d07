"""A CPU rehearsal of a benchmark run at ResNet-4 size, called in-process:
the schedule and window logic, a whole run past the chip check, the control
and the faults a one-chip training cell can have, each of which must turn
``correct`` false."""

import json
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

from bench import correct, harness
from bench.reference import resnet

ROOT = Path(__file__).resolve().parents[2]
CELL = "resnet18-c100-fedpart"

TINY = {
    "name": "tiny", "reference": "resnet",
    "program": {"adapter": "resnet_task", "args": {"depth": "resnet4", "num_classes": 5}},
    "stages": [1, 1], "channels": [8, 16], "num_classes": 5, "image_size": 8,
    "in_channels": 3,
    "data": {"kind": "vision", "num_clients": 6, "samples_per_client": 20,
             "eval_samples": 256, "noise": 0.35, "proto_seed": 1234},
}


def tiny_cell(schedule="fnu", fused=False):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    traffic = json.loads((ROOT / "bench" / "traffic" / "fedpart-b50-e1-fused.json").read_text())
    traffic.update(schedule=schedule, fused_adam=fused, cohort=3, batch=10,
                   eval_batch=256)
    per_layer = [m for m in bench["per_layer"] if CELL in m["workloads"]]
    return harness.Cell(
        name="tiny", chips=1, config=TINY, traffic=traffic,
        limits=json.loads((ROOT / "bench" / "limits" / f"{CELL}.json").read_text()),
        per_layer=per_layer,
        reference=harness.load_module(ROOT, "reference", "resnet"),
        data=harness.load_module(ROOT, "data", "vision"),
        metrics={m["name"]: harness.load_module(ROOT, "metrics", m["name"])
                 for m in per_layer})


def run(cell, seed=2**31 + 11):
    lines = []
    out = harness.run(cell, seed, 0.5, False, time.perf_counter(),
                      log=lambda *a, **k: lines.append(" ".join(map(str, a))))
    return out, lines


def test_rounds_yield_setup_then_a_timed_window_and_replay():
    rounds = harness.Rounds([0, 1, 2, 3], setup=4, seconds=0.05)
    seen = []
    for spec in rounds:
        # run_federated's test for its last round never holds in the window
        assert spec.index != len(rounds) - 1
        seen.append((spec.index, spec.group))
        time.sleep(0.01)
    assert seen[:4] == [(0, 0), (1, 1), (2, 2), (3, 3)]
    assert [s[0] for s in seen] == list(range(len(seen)))
    n = len(rounds.window_rounds)
    # the window outlasts its seconds and ends on a whole cycle of groups
    assert n >= 4 and n % 4 == 0 and len(rounds) == len(seen)
    assert [s.group for s in rounds.window_rounds] == [0, 1, 2, 3] * (n // 4)
    assert rounds.window_end - rounds.window_start >= 0.05
    assert sum(rounds.window_round_s) == pytest.approx(
        rounds.window_end - rounds.window_start)
    assert [(s.index, s.group) for s in rounds] == seen      # second pass


def test_a_window_without_whole_cycles_ends_after_one_round():
    rounds = harness.Rounds([0, 1, 2, 3], setup=3, seconds=0.0, whole_cycles=False)
    assert [s.group for s in rounds] == [0, 1, 2, 3]


def test_fnu_rounds_are_full_network():
    rounds = harness.Rounds(harness.round_groups("fnu", 6), setup=3, seconds=0.0)
    specs = list(rounds)
    assert len(specs) == 4 and all(s.is_full for s in specs)


def test_a_sound_fedpart_run_is_correct_and_reports_its_metrics():
    cell = tiny_cell("fedpart", fused=True)
    out, lines = run(cell)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"client_samples_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {k for k, v in cell.limits.items() if "limit" in v}
    assert any("compile events in window 0" in ln for ln in lines)
    assert out["attempted"] >= 6 + 1     # six groups of set-up, then the window


@pytest.fixture
def broken(monkeypatch):
    """Plant one fault in the program under a run."""
    from repro.core import aggregation
    from repro.models import resnet as model

    def plant(kind):
        if kind == "state_unchanged":
            monkeypatch.setattr(aggregation, "aggregate_full_stacked",
                                lambda g, stacked, weights=None: g)
        elif kind == "half_batch":
            real = model.cls_loss
            monkeypatch.setattr(model, "cls_loss", lambda logits, labels: real(
                logits[: len(labels) // 2], labels[: len(labels) // 2]))
        elif kind == "altered_answer":
            real_acc = model.accuracy
            monkeypatch.setattr(model, "accuracy",
                                lambda logits, labels: real_acc(logits, labels) + 0.125)

    return plant


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "altered_answer"])
def test_each_fault_turns_correct_false(broken, fault):
    broken(fault)
    out, _ = run(tiny_cell("fnu"))
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_the_bfloat16_control_fails_the_comparison():
    """The reference computed in bfloat16, put in the program's place, is
    not correct by the cell's limits."""
    cell = tiny_cell("fnu")
    run_ = harness.build(cell, 5)
    groups = [resnet.FULL] * harness.COMPARED_ROUNDS

    def first_last(out):
        return dict(out, params=[out["params"][0], out["params"][-1]])

    ref = first_last(harness.reference_run(cell, run_, 5, groups))
    ctl = first_last(harness.reference_run(cell, run_, 5, groups, dtype=jnp.bfloat16))
    start = resnet.flatten(run_["start"])
    numbers = correct.numbers(ctl, ref, start, len(run_["eval_set"][1]))
    ok, _ = correct.verdict(numbers["values"], cell.limits)
    assert not ok


def test_limits_are_derived_between_the_sound_and_the_faulty_readings():
    from bench import calibrate

    def row(kind, **values):
        return {"kind": kind, "values": values}

    rows = [row("program", acc_gap=2.0, update_gap=0.1, loss_gap=1e-4),
            row("program", acc_gap=3.0, update_gap=0.2, loss_gap=2e-4),
            row("control_bf16", acc_gap=0.0, update_gap=5.0, loss_gap=1e-4),
            row("altered_answer", acc_gap=32.0, update_gap=0.2, loss_gap=2e-4),
            row("half_batch", acc_gap=1.0, update_gap=0.05, loss_gap=4e-4)]
    lim = calibrate.derive_limits(rows)
    assert lim["acc_gap"]["upper"] == 32.0 and lim["acc_gap"]["failed_by"] == ["altered_answer"]
    assert lim["update_gap"]["upper"] == 1.0      # a state left unchanged
    assert lim["update_gap"]["failed_by"] == ["control_bf16", "state_unchanged"]
    for v in (lim["acc_gap"], lim["update_gap"]):
        assert v["lower"] < v["limit"] < v["upper"]
        assert v["limit"] / v["lower"] > v["upper"] / v["limit"]
    assert "limit" not in lim["loss_gap"]          # nothing reads 10x the sound
