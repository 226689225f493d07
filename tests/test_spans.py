"""The profiler spans and device scopes of a federated round
(``core.telemetry.SPANS``).

Two tiny rounds of ``run_federated`` (vmap engine, fused masked Adam in
interpret mode) run under ``jax.profiler``: every host span of ``SPANS``
appears on the thread that called ``run_federated``, each inside its own
round's ``fl.round``.  Every span and scope the source opens is named in
``SPANS``.
"""

import re
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.schedule import FedPartSchedule, FNUSchedule
from repro.core.telemetry import SPANS, span
from repro.data import (VisionDatasetSpec, balanced_eval_set, build_clients,
                        make_vision_dataset)
from repro.fl import FLRunConfig, resnet_task, run_federated

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"
HOST_SPANS = {n for n in SPANS if n.startswith("fl.")}


def _traced(out, rounds):
    """Run ``rounds`` of a tiny fused vmap federation under the profiler:
    the result and the host events named in ``SPANS``."""
    spec = VisionDatasetSpec(num_classes=4, image_size=8)
    x, y = make_vision_dataset(spec, 48, seed=0)
    xe, ye = make_vision_dataset(spec, 32, seed=9)
    clients = build_clients(x, y, [np.arange(0, 24), np.arange(24, 48)])
    cfg = FLRunConfig(local_epochs=1, batch_size=12, cohort_size=2,
                      engine="vmap", fused_adam=True)
    with jax.profiler.trace(str(out)):
        result = run_federated(resnet_task("resnet4", num_classes=4), clients,
                               balanced_eval_set(xe, ye, per_class=8),
                               rounds, cfg)
    data = ProfileData.from_file(str(next(out.rglob("*.xplane.pb"))))
    events = [(line.name, e.name, e.start_ns, e.start_ns + e.duration_ns,
               dict(e.stats))
              for plane in data.planes if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name in SPANS]
    return result, events


@pytest.fixture(scope="module")
def traced_rounds(tmp_path_factory):
    return _traced(tmp_path_factory.mktemp("trace"), FNUSchedule(2).rounds())


@pytest.fixture(scope="module")
def traced_partial_round(tmp_path_factory):
    """One FedPart round of group 3 (block 1's first convolution, its
    shortcut convolution and their BNs)."""
    rounds = FedPartSchedule(num_groups=6, warmup_rounds=0,
                             rounds_per_layer=1, cycles=1).rounds()[3:4]
    return _traced(tmp_path_factory.mktemp("trace_partial"), rounds)


def test_every_host_span_nests_in_its_round_on_the_calling_thread(traced_rounds):
    result, events = traced_rounds
    assert len(result.history) == 2
    assert {name for _, name, *_ in events} == HOST_SPANS
    assert len({line for line, *_ in events}) == 1
    rounds = [(s, e, st) for _, name, s, e, st in events if name == "fl.round"]
    assert sorted(st["round"] for *_, st in rounds) == [0, 1]
    assert all(st["group"] == -1 and st["phase"] == "warmup" for *_, st in rounds)
    for _, name, s, e, st in events:
        if name != "fl.round":
            holders = [r for r in rounds if r[0] <= s and e <= r[1]]
            assert len(holders) == 1, (name, st)


def test_span_arguments(traced_rounds):
    _, events = traced_rounds
    args = {}
    for _, name, _, _, st in events:
        args.setdefault(name, []).append(st)
    assert all(st == {"clients": 2, "buckets": 1} for st in args["fl.stack"])
    assert {st["program"] for st in args["fl.dispatch"]} == {"local", "agg"}
    assert sorted(st["what"] for st in args["fl.wait"]) == [
        "eval", "eval", "losses", "losses"]
    # fl.wait for the eval sits inside fl.eval
    evals = [(s, e) for _, name, s, e, _ in events if name == "fl.eval"]
    waits = [(s, e) for _, name, s, e, st in events
             if name == "fl.wait" and st["what"] == "eval"]
    assert all(any(a <= s and e <= b for a, b in evals) for s, e in waits)


def _local_dispatches(events):
    return [st for _, name, _, _, st in events
            if name == "fl.dispatch" and st["program"] == "local"]


def test_fused_local_dispatch_counts_the_rows_the_kernel_streams(
        traced_rounds, traced_partial_round):
    """A fused local round's ``fl.dispatch`` names the packed rows the
    masked-Adam kernel streams per client-step and the whole model's: equal
    on FNU rounds, the trained group's alone on a partial round."""
    full = _local_dispatches(traced_rounds[1])
    assert len(full) == 2
    assert all(st["kernel_rows"] == st["model_rows"] for st in full)
    model_rows = full[0]["model_rows"]
    result, events = traced_partial_round
    assert [h["group"] for h in result.history] == [3]
    [part] = _local_dispatches(events)
    assert part["model_rows"] == model_rows
    # blocks of 8 x 128: two for conv1 (1,152 values), one each for sc_conv
    # and the scale and bias of bn1 and sc_bn
    assert part["kernel_rows"] == 7 * 8 < model_rows


def test_every_span_and_scope_in_the_source_is_named_in_spans():
    opened = set()
    for path in SRC.rglob("*.py"):
        text = path.read_text()
        opened |= set(re.findall(r'\bspan\("([^"]+)"', text))
        opened |= set(re.findall(r'named_scope\("([^"]+)"', text))
    # the kernel's scope comes from its pallas_call's name
    # (tests/test_tpu_compile.py)
    assert opened == set(SPANS) - {"masked_adam"}
    assert all(n.startswith("fl.") or n in ("grad", "masked_adam", "mla", "moe_route",
                                           "moe_experts") for n in SPANS)


def test_a_span_outside_a_profiler_session_records_nothing():
    with span("fl.round", round=0, group=-1, phase="partial") as s:
        assert not s.is_enabled()
