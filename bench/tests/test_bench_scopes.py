"""The readings of the program's spans and device scopes (``bench/scopes.py``)
on two small traces recorded on a TPU v5e, both two FNU rounds of ResNet-4
(3 clients of 20 images, batch 10, fused masked Adam) on the vmap engine:

- ``tiny_tpu.xplane.pb.gz``, of a program without spans or scopes;
- ``tiny_spans_tpu.xplane.pb.gz``, of the program with them, recorded by
  ``bench/tests/record_trace.py``.
"""

import gzip
from pathlib import Path

import pytest

from bench import scopes, traces
from bench.metrics import masked_adam_roofline

DATA = Path(__file__).parent / "data"


def _load(tmp_path_factory, name):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(gzip.decompress((DATA / name).read_bytes()))
    trace = traces.load(str(path))
    return trace, scopes.load_ops(path, trace.window)


@pytest.fixture(scope="module")
def unmarked(tmp_path_factory):
    return _load(tmp_path_factory, "tiny_tpu.xplane.pb.gz")


@pytest.fixture(scope="module")
def marked(tmp_path_factory):
    return _load(tmp_path_factory, "tiny_spans_tpu.xplane.pb.gz")


def _op_s(trace):
    return sum(e.seconds for evs in trace.ops.values() for e in trace.clipped(evs)
               if not traces.short_name(e.name).startswith(traces.CONTAINERS))


@pytest.mark.parametrize("which", ["unmarked", "marked"])
def test_op_paths_are_read_from_the_trace(which, request):
    trace, ops = request.getfixturevalue(which)
    assert list(ops) == list(trace.ops) == ["/device:TPU:0"]
    got = ops["/device:TPU:0"]
    # the same ops, on the same clock, as the trace reduction's
    assert sum(o.seconds for o in got) == pytest.approx(_op_s(trace), rel=1e-9)
    paths = [o.tf_op for o in got if o.tf_op]
    assert len(paths) > len(got) / 2
    assert any(p.startswith("jit(local_round)/vmap()/while/body/") for p in paths)
    [kernel] = {o.tf_op for o in got if "tpu_custom_call" in o.name
                and all(m in o.name for m in masked_adam_roofline.KERNEL)}
    assert kernel.endswith("pallas_call:") or kernel.endswith("pallas_call")
    # the ops of the local round's module cover its time
    local = sum(o.seconds for o in got if scopes.LOCAL_ROUND in o.module)
    assert local == pytest.approx(trace.module_s(scopes.LOCAL_ROUND), rel=0.03)


def test_a_program_without_spans_or_scopes_reads_nothing(unmarked):
    trace, ops = unmarked
    assert set(scopes.readings(trace, ops).values()) == {None}


def test_the_readings_of_a_marked_program(marked):
    trace, ops = marked
    r = scopes.readings(trace, ops)
    assert all(v is not None for v in r.values()), r
    n = len(trace.rounds)
    local_ms = 1e3 * trace.module_s(scopes.LOCAL_ROUND) / n
    split = r["grad_ms"] + r["masked_adam_ms"] + r["step_overhead_ms"]
    assert split == pytest.approx(local_ms, rel=0.03)
    assert r["grad_ms"] > r["masked_adam_ms"] > 0 and r["step_overhead_ms"] > 0
    # the scope finds the kernel the aliasing match finds, and nothing else
    alias_ms = 1e3 * trace.op_s(masked_adam_roofline.KERNEL) / n
    assert r["masked_adam_ms"] == pytest.approx(alias_ms, rel=0.01)
    idle_frac = 1.0 - trace.busy_s() / trace.window_s
    assert r["host_prep_ms"] > 0
    assert 0 < r["idle_host_prep_frac"] < idle_frac


def test_every_host_span_is_in_the_marked_trace(marked):
    from repro.core.telemetry import SPANS

    trace, _ = marked
    names = {e.name for e in trace.host if e.name.startswith("fl.")}
    assert names == {n for n in SPANS if n.startswith("fl.")}
    by_span = scopes.idle_by_span(trace)
    idle_s = sum(e - s for s, e in scopes.idle(trace)) * 1e-9
    assert sum(by_span.values()) == pytest.approx(idle_s, rel=1e-6)
    assert set(by_span) <= names | {"none"}
    # the host's preparation explains part of the idle time, no more
    prep = sum(by_span.get(n, 0.0) for n in scopes.HOST_PREP)
    assert 0 < prep <= scopes.idle_host_prep_frac(trace) * trace.window_s + 1e-9


def test_the_reader_fails_without_the_protobuf_module(monkeypatch):
    monkeypatch.setattr(scopes.importlib.util, "find_spec", lambda name: None)
    with pytest.raises(ModuleNotFoundError, match="xplane_pb2"):
        scopes.xplane_pb2()
