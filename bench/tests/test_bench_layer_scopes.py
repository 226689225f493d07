"""The packed decoder's per-layer readers (``bench.layer_scopes``: the
``mla``, ``moe_route`` and ``moe_experts`` scopes and the
``held_assignments`` round counter) on the trace pair of the Moonlight
configuration, ``bench/tests/data/moonlight-16b-a3b-ep8.xplane.pb.gz`` with
its ``.ctx.json``: one FedPart cycle of the test-size model recorded on a
v5e by ``bench/tests/record_trace.py``."""

import gzip

import pytest
from jax.profiler import ProfileData
from test_bench_traces import ROOT, config_context

from bench import harness, layer_scopes, scopes

CONFIG = "moonlight-16b-a3b-ep8"
SCOPE_METRICS = {"mla_ms": "mla", "moe_route_ms": "moe_route",
                 "moe_experts_ms": "moe_experts"}


@pytest.fixture(scope="module")
def moon(tmp_path_factory):
    return config_context(ROOT, CONFIG, tmp_path_factory.mktemp("moon"))


def test_a_scope_is_a_whole_component_of_the_op_path():
    path = "jit(local_round)/vmap()/while/body/grad/transpose(jvp(mla))/dot_general"
    assert layer_scopes.under(path, "mla")
    assert layer_scopes.under("a/grad/jvp(moe_experts)/jit(gmm)", "moe_experts")
    assert layer_scopes.under("a/grad/checkpoint/moe_route", "moe_route")
    assert not layer_scopes.under("a/grad/mla_packed/b", "mla")
    assert not layer_scopes.under("a/grad/moe_experts_x", "moe_experts")


@pytest.mark.parametrize("name", sorted(SCOPE_METRICS))
def test_a_decoder_scope_reader_is_its_reading(name, moon):
    """Each reader equals the device seconds per round of the local round's
    ops in ``bench.scopes.load_ops`` whose path holds its scope."""
    _, ctx = moon
    scope = SCOPE_METRICS[name]
    got = harness.load_module(ROOT, "metrics", name).read(ctx)
    ops = [op for plane in ctx["ops"].values() for op in plane
           if scopes.LOCAL_ROUND in op.module]
    wrapped = (f"/{scope}/", f"({scope})/", f"({scope}))/")
    want = sum(op.seconds for op in ops if any(w in op.tf_op + "/" for w in wrapped))
    assert got is not None and got > 0
    assert got == pytest.approx(1e3 * want / len(ctx["trace"].rounds), rel=1e-12)


def test_the_decoder_scopes_split_the_local_rounds_gradient(moon):
    cell, ctx = moon
    out = {k: v["value"] for k, v in harness.read_metrics(cell, ctx).items()}
    split = out["grad_ms"] + out["masked_adam_ms"] + out["step_overhead_ms"]
    assert split == pytest.approx(out["local_round_ms"], rel=0.03)
    assert out["mla_ms"] + out["moe_route_ms"] + out["moe_experts_ms"] < out["grad_ms"]


def test_the_roofline_counts_the_rows_the_round_counter_names(moon):
    """``moe_experts_roofline`` from the ``held_assignments`` of each traced
    round's losses ``fl.wait``, read here from the recorded file itself."""
    cell, ctx = moon
    raw = gzip.decompress((ROOT / "bench" / "tests" / "data" /
                           f"{CONFIG}.xplane.pb.gz").read_bytes())
    events = [(e.name, dict(e.stats)) for plane in
              ProfileData.from_serialized_xspace(raw).planes
              if plane.name.startswith("/host:") for line in plane.lines
              for e in line.events if e.name in ("fl.round", "fl.wait")]
    groups = [st["group"] for name, st in events if name == "fl.round"]
    held = [[float(v) for v in str(st["held_assignments"]).split()]
            for name, st in events if name == "fl.wait" and "held_assignments" in st]
    # the set-up rounds, then the traced cycle, one counter per round
    assert len(held) == len(groups) and groups[-7:] == ctx["traced_groups"]
    assert layer_scopes.held_assignments(ctx) == list(zip(groups[-7:], held[-7:]))
    ref, cfg = cell.reference, cell.config
    flops = sum(
        (1 + (lg >= g) + (lg == g)) * n * ref.routed_flops_per_row(cfg)
        for g, counts in zip(groups[-7:], held[-7:])
        for lg, n in zip(ref.moe_layer_groups(cfg), counts))
    seconds = 1e-3 * layer_scopes.scope_ms(ctx, "moe_experts") * len(ctx["trace"].rounds)
    got = harness.load_module(ROOT, "metrics", "moe_experts_roofline").read(ctx)
    assert got == pytest.approx(100 * flops / (seconds * ctx["peaks"]["bf16_flops_per_s"]))
    assert 0 < got < 100
