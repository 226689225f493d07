"""Readings of the decoder's device scopes and of its round counter, for the
per-layer metrics of the packed-decoder configurations.

- ``scope_ms(ctx, name)``: device milliseconds per traced round of the ops
  that run in an XLA module of the local round and sit under the device
  scope ``name`` (``mla``, ``moe_route``, ``moe_experts``), from the op
  paths that ``bench.scopes.load_ops`` reads (``ctx["ops"]``).  A scope is
  one component of the path, bare or wrapped by JAX's transformations (the
  backward's ``transpose(jvp(mla))``).  ``None`` without op paths or where
  no op carries the scope.
- ``held_assignments(ctx)``: each traced round's trained group and the rows
  routed to the held experts of each MoE layer, summed over the round's
  clients and steps: the ``held_assignments`` argument of the round's
  ``fl.wait`` (what=losses) span (``repro.core.telemetry.SPANS``).  The
  readers' context holds the reduced trace, which keeps no span arguments,
  and not the trace's path, so the file is found by its rounds: the first
  of the ``--trace 1`` run's trace under ``bench/.trace`` and the recorded
  pairs under ``bench/tests/data`` (the context's configuration's first)
  whose ``bench_round`` window is the context's.  ``None`` where no span
  carries the counter.
"""

from __future__ import annotations

import gzip
import re
from pathlib import Path

from bench import scopes, traces

BENCH = Path(__file__).resolve().parent
TRACE_DIR = BENCH / ".trace"
DATA = BENCH / "tests" / "data"
COUNTER = "held_assignments"
ROUND_SPAN, WAIT_SPAN = "fl.round", "fl.wait"


def under(tf_op: str, name: str) -> bool:
    """Whether the op path ``tf_op`` has the scope ``name`` as a component."""
    pattern = re.compile(r"^(?:[\w.]+\()*" + re.escape(name) + r"\)*$")
    return any(pattern.match(part) for part in tf_op.split("/"))


def scope_ms(ctx: dict, name: str) -> float | None:
    ops = ctx.get("ops")
    if ops is None:
        return None
    s = sum(op.seconds for plane in ops.values() for op in plane
            if scopes.LOCAL_ROUND in op.module and under(op.tf_op, name))
    return 1e3 * s / len(ctx["trace"].rounds) if s > 0 else None


def _candidates(ctx: dict):
    yield from sorted(TRACE_DIR.rglob("*.xplane.pb"))
    cell = ctx.get("cell")
    first = DATA / f"{cell.name}.xplane.pb.gz" if cell is not None else None
    if first is not None and first.is_file():
        yield first
    yield from (p for p in sorted(DATA.glob("*.xplane.pb.gz")) if p != first)


def _host_events(path: Path):
    """(name, start ns, end ns, args) of the host events of ``path``."""
    from jax.profiler import ProfileData

    raw = path.read_bytes()
    if path.suffix == ".gz":
        raw = gzip.decompress(raw)
    data = ProfileData.from_serialized_xspace(raw)
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    yield (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
                           dict(e.stats))


def held_assignments(ctx: dict) -> list[tuple[int, list[float]]] | None:
    a, b = ctx["trace"].window
    for path in _candidates(ctx):
        events = list(_host_events(path))
        marks = [(s, e) for name, s, e, _ in events
                 if name.startswith(traces.ROUND_PREFIX)]
        if not marks or abs(min(marks)[0] - a) > 1000 or abs(max(e for _, e in marks) - b) > 1000:
            continue
        rounds = sorted((s, e, st) for name, s, e, st in events
                        if name == ROUND_SPAN and a <= s and e <= b)
        waits = [(s, st[COUNTER]) for name, s, e, st in events
                 if name == WAIT_SPAN and COUNTER in st and a <= s and e <= b]
        out = []
        for s0, e0, st in rounds:
            held = [v for s, v in waits if s0 <= s <= e0]
            if len(held) != 1:
                return None
            out.append((int(st["group"]), [float(x) for x in str(held[0]).split()]))
        return out or None
    return None
