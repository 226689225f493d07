"""Readings of the program's own spans and device scopes in a profiler trace.

The program marks a federated round in two ways (``repro.core.telemetry``,
whose ``SPANS`` names each mark):

- host spans (``fl.round``, ``fl.sample``, ``fl.client_data``, ``fl.stack``,
  ``fl.dispatch``, ``fl.wait``, ``fl.eval``) on the thread that runs the
  rounds, the thread ``bench.traces`` reads;
- device scopes (``grad`` around every local step's forward and backward,
  ``masked_adam`` on the Pallas kernel, from its ``pallas_call``'s name),
  which land in each XLA op's name-stack path.

``jax.profiler.ProfileData`` gives event stats but not the stats of event
metadata, and an op's path is the ``tf_op`` stat of its metadata.  So the
device ops are read here from the ``.xplane.pb`` itself, with the XSpace
protobuf module that TensorFlow installs, loaded from its file: importing
its package would import TensorFlow.  Without that module the reader fails.

Readings, over the window of ``bench_round`` annotations (``bench.traces``),
per traced round where the unit is ms/round:

- ``host_prep_ms``: the union of the ``fl.sample``, ``fl.client_data`` and
  ``fl.stack`` spans;
- ``idle_host_prep_frac``: the first device's idle seconds under those
  spans, over the window's seconds;
- ``grad_ms``, ``masked_adam_ms``, ``step_overhead_ms``: device time of the
  ops that run inside an XLA module of the local round (``local_round`` in
  its name) and sit under ``grad``, under ``masked_adam``, or under
  neither: packing and unpacking, the scan's step-valid select, pads,
  layout copies.  Control-flow ops, whose events span their bodies' ops,
  are left out, as ``Trace.top_ops`` leaves them out.

A reading whose marks the traced program lacks is ``None``: a program
without spans or scopes has nothing to read.  Each reading is also the
per-layer metric of its name (``bench/metrics/<name>.py``, through ``read``).

    python3 bench/scopes.py [trace.xplane.pb]

prints every reading, the kernel's seconds by ``bench.metrics.
masked_adam_roofline``'s aliasing match, the idle seconds under each
innermost ``fl.*`` span and the named idle gaps as one JSON object; the
trace defaults to the one a ``--trace 1`` run leaves under
``bench/.trace``.
"""

from __future__ import annotations

import bisect
import dataclasses
import importlib.util
import json
import sys
from collections import defaultdict
from pathlib import Path

if __name__ == "__main__":
    sys.path[0] = str(Path(__file__).resolve().parents[1])

from bench import traces  # noqa: E402

TRACE_DIR = Path(__file__).resolve().parent / ".trace"
XPLANE_PB2 = ("tsl", "profiler", "protobuf", "xplane_pb2.py")
ROUND_SPAN = "fl.round"
HOST_PREP = ("fl.sample", "fl.client_data", "fl.stack")
GRAD, MASKED_ADAM = "grad", "masked_adam"
LOCAL_ROUND = "local_round"


@dataclasses.dataclass
class Op:
    start: int      # ns
    end: int        # ns
    name: str       # the HLO instruction
    tf_op: str      # the name-stack path ("" where the op has none)
    module: str     # the XLA module the op ran in ("" where none holds it)

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


def xplane_pb2():
    """The XSpace protobuf module, from TensorFlow's installed file."""
    spec = importlib.util.find_spec("tensorflow")
    roots = spec.submodule_search_locations if spec else None
    for root in roots or ():
        path = Path(root).joinpath(*XPLANE_PB2)
        if path.is_file():
            mod_spec = importlib.util.spec_from_file_location(
                "bench_xplane_pb2", path)
            mod = importlib.util.module_from_spec(mod_spec)
            mod_spec.loader.exec_module(mod)
            return mod
    raise ModuleNotFoundError(
        "no tensorflow/" + "/".join(XPLANE_PB2) + " is installed: the op "
        "paths of a trace cannot be read")


def latest_trace(trace_dir: Path = TRACE_DIR) -> Path:
    found = sorted(trace_dir.rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _stats(stats, names) -> dict:
    out = {}
    for s in stats:
        kind = s.WhichOneof("value")
        value = getattr(s, kind) if kind else None
        if kind == "ref_value":
            value = names[value]
        out[names[s.metadata_id]] = value
    return out


def load_ops(path: str | Path, window: tuple[int, int]) -> dict[str, list[Op]]:
    """Device plane -> its XLA ops, clipped to ``window`` (ns), each with its
    ``tf_op`` path and the module it ran in; control-flow ops left out."""
    space = xplane_pb2().XSpace()
    space.ParseFromString(Path(path).read_bytes())
    a, b = window
    out: dict[str, list[Op]] = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        meta = plane.event_metadata
        lines = {line.name: line for line in plane.lines}
        if traces.OP_LINE not in lines:
            continue

        def events(line):
            for e in line.events:
                start = line.timestamp_ns + e.offset_ps // 1000
                yield e, start, start + e.duration_ps // 1000

        modules = sorted((s, t, meta[e.metadata_id].name) for e, s, t in
                         (events(lines[traces.MODULE_LINE])
                          if traces.MODULE_LINE in lines else ()))
        paths = {k: None if traces.short_name(md.name).startswith(
                     traces.CONTAINERS) else _stats(md.stats, names).get("tf_op") or ""
                 for k, md in meta.items()}
        ops, m = [], 0
        for e, s, t in sorted(events(lines[traces.OP_LINE]), key=lambda x: x[1]):
            tf_op = paths[e.metadata_id]
            if t <= a or s >= b or tf_op is None:
                continue
            while m < len(modules) and modules[m][1] <= s:
                m += 1
            module = modules[m][2] if m < len(modules) and modules[m][0] <= s else ""
            ops.append(Op(max(s, a), min(t, b), meta[e.metadata_id].name, tf_op,
                          module))
        out[plane.name] = ops
    return out


def _union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _overlap_s(xs, ys) -> float:
    """Seconds where two sorted, disjoint interval lists overlap."""
    i = j = total = 0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        total += max(0, hi - lo)
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total * 1e-9


def _seconds(intervals) -> float:
    return sum(e - s for s, e in intervals) * 1e-9


def spans(trace: traces.Trace, names) -> list[tuple[int, int]]:
    """Union of the rounds' thread's spans named in ``names``, clipped to
    the window."""
    return _union((e.start, e.end) for e in trace.clipped(trace.host)
                  if e.name in names)


def idle(trace: traces.Trace) -> list[tuple[int, int]]:
    """The first device's idle stretches of the window."""
    if not trace.ops:
        return []
    a, b = trace.window
    busy = _union((e.start, e.end) for e in
                  trace.clipped(next(iter(trace.ops.values()))))
    out, t = [], a
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < b:
        out.append((t, b))
    return out


def has_spans(trace: traces.Trace) -> bool:
    return any(e.name == ROUND_SPAN for e in trace.host)


def host_prep_ms(trace: traces.Trace) -> float | None:
    if not has_spans(trace):
        return None
    return 1e3 * _seconds(spans(trace, HOST_PREP)) / len(trace.rounds)


def idle_host_prep_frac(trace: traces.Trace) -> float | None:
    if not (has_spans(trace) and trace.ops):
        return None
    return _overlap_s(idle(trace), spans(trace, HOST_PREP)) / trace.window_s


def idle_by_span(trace: traces.Trace) -> dict[str, float]:
    """The first device's idle seconds, each instant credited to the
    innermost ``fl.*`` span that holds it ("none" outside every one)."""
    a, b = trace.window
    host = [e for e in trace.clipped(trace.host) if e.name.startswith("fl.")]
    cuts = sorted({a, b} | {t for e in host for t in (e.start, e.end)})
    gaps = idle(trace)
    starts = [g[0] for g in gaps]
    out: dict[str, float] = defaultdict(float)
    for s, e in zip(cuts, cuts[1:]):
        under = [h for h in host if h.start <= s and e <= h.end]
        name = min(under, key=lambda h: h.end - h.start).name if under else "none"
        near = gaps[max(bisect.bisect_right(starts, s) - 1, 0):
                    bisect.bisect_left(starts, e)]
        out[name] += _overlap_s(near, [(s, e)])
    return dict(out)


def local_round_split(ops: dict[str, list[Op]], rounds: int) -> dict | None:
    """``grad_ms``, ``masked_adam_ms`` and ``step_overhead_ms``: device ms
    per round of the local round's ops under ``grad``, under
    ``masked_adam`` and under neither, summed over devices; ``None`` where
    no op carries either scope."""
    s = {GRAD: 0.0, MASKED_ADAM: 0.0, "": 0.0}
    for plane_ops in ops.values():
        for op in plane_ops:
            if LOCAL_ROUND in op.module:
                scope = (MASKED_ADAM if MASKED_ADAM in op.tf_op else
                         GRAD if f"/{GRAD}/" in op.tf_op else "")
                s[scope] += op.seconds
    if not (s[GRAD] or s[MASKED_ADAM]):
        return None
    return {"grad_ms": 1e3 * s[GRAD] / rounds,
            "masked_adam_ms": 1e3 * s[MASKED_ADAM] / rounds,
            "step_overhead_ms": 1e3 * s[""] / rounds}


def readings(trace: traces.Trace, ops: dict[str, list[Op]]) -> dict:
    """Every reading of the module docstring; ``None`` where the program
    lacks the marks."""
    split = local_round_split(ops, len(trace.rounds)) or dict.fromkeys(
        ("grad_ms", "masked_adam_ms", "step_overhead_ms"))
    return {"host_prep_ms": host_prep_ms(trace),
            "idle_host_prep_frac": idle_host_prep_frac(trace), **split}


def read(ctx: dict, name: str) -> float | None:
    """The reading ``name`` for its per-layer metric reader
    (``bench/metrics/<name>.py``): ``None`` where the harness could not read
    the op paths (``ctx["ops"]`` is ``None``) or the program lacks the
    marks."""
    if ctx.get("ops") is None:
        return None
    return readings(ctx["trace"], ctx["ops"])[name]


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    path = Path(args[0]) if args else latest_trace()
    from bench.metrics import masked_adam_roofline

    trace = traces.load(str(path))
    ops = load_ops(path, trace.window)
    n = len(trace.rounds)
    out = readings(trace, ops)
    out.update(
        rounds=n, window_s=trace.window_s,
        device_idle_frac=1.0 - trace.busy_s() / trace.window_s,
        local_round_ms=1e3 * trace.module_s(LOCAL_ROUND) / n,
        kernel_alias_ms=1e3 * trace.op_s(masked_adam_roofline.KERNEL) / n,
        idle_by_span=idle_by_span(trace),
        idle_gaps=trace.idle_gaps(),
    )
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
