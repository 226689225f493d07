"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

The device planes (``/device:TPU:n``) carry one line of XLA modules (one
event per compiled program run, named ``jit_<function>(<fingerprint>)``) and
one of XLA ops (one event per op, named by its whole HLO instruction; a
``while`` op's event spans the events of its body).  The host plane carries
one line per thread; the thread that runs the rounds carries the
benchmark's own annotation of each round (``bench_round ...``) and the
runtime's host events under it.  Device and host events share one clock.

- The traced window runs from the start of the first round annotation to the
  end of the last.
- A device is busy while any op of its op line runs; busy seconds are the
  union of those intervals inside the window, averaged over the devices.
- An idle gap is a stretch of the window in which no op runs on a device; it
  is named by the innermost event of the rounds' thread under its midpoint.
"""

from __future__ import annotations

import dataclasses
from collections import defaultdict

ROUND_PREFIX = "bench_round"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Event:
    name: str
    start: int      # ns
    end: int        # ns

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


@dataclasses.dataclass
class Trace:
    modules: dict[str, list[Event]]     # device plane -> module events
    ops: dict[str, list[Event]]         # device plane -> op events
    host: list[Event]                   # host events of the rounds' thread
    rounds: list[Event]                 # the benchmark's round annotations

    @property
    def window(self) -> tuple[int, int]:
        return self.rounds[0].start, self.rounds[-1].end

    @property
    def window_s(self) -> float:
        a, b = self.window
        return (b - a) * 1e-9

    def clipped(self, events: list[Event]) -> list[Event]:
        a, b = self.window
        return [Event(e.name, max(e.start, a), min(e.end, b))
                for e in events if e.end > a and e.start < b]

    def busy_s(self) -> float:
        """Union of op intervals in the window, averaged over devices."""
        per = [sum(e.seconds for e in _union(self.clipped(ops)))
               for ops in self.ops.values()]
        return sum(per) / len(per) if per else 0.0

    def module_s(self, match: str) -> float:
        """Device seconds of modules whose name holds ``match``, summed over
        devices."""
        return sum(e.seconds for evs in self.modules.values()
                   for e in self.clipped(evs) if match in e.name)

    def op_s(self, match: tuple[str, ...]) -> float:
        """Device seconds of ops whose name holds every string of ``match``,
        summed over devices."""
        return sum(e.seconds for evs in self.ops.values()
                   for e in self.clipped(evs) if all(m in e.name for m in match))

    def top_ops(self, k: int = 10) -> list[list]:
        """The ``k`` ops with the most device seconds in the window, summed
        by name over rounds and devices.  Control-flow ops, whose events
        span the ops of their bodies, are left out."""
        tot: dict[str, float] = defaultdict(float)
        for evs in self.ops.values():
            for e in self.clipped(evs):
                name = short_name(e.name)
                if not name.startswith(CONTAINERS):
                    tot[name] += e.seconds
        return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])[:k]]

    def idle_gaps(self, k: int = 10) -> list[list]:
        """The ``k`` longest idle stretches of the first device, each named by
        the innermost host event under its midpoint."""
        if not self.ops:
            return []
        a, b = self.window
        busy = _union(self.clipped(next(iter(self.ops.values()))))
        gaps, t = [], a
        for e in busy:
            if e.start > t:
                gaps.append((t, e.start))
            t = max(t, e.end)
        if t < b:
            gaps.append((t, b))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:k]:
            mid = (s + e) // 2
            under = [h for h in self.host if h.start <= mid < h.end]
            name = min(under, key=lambda h: h.end - h.start).name if under else "none"
            out.append([name, (e - s) * 1e-9])
        return out


def short_name(hlo: str, width: int = 120) -> str:
    """An op event's name is its whole HLO instruction; keep its head."""
    return hlo.lstrip("%")[:width]


def _union(events: list[Event]) -> list[Event]:
    out: list[Event] = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1].end:
            out[-1].end = max(out[-1].end, e.end)
        else:
            out.append(Event("busy", e.start, e.end))
    return out


def _events(line) -> list[Event]:
    return [Event(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    modules, ops, host, rounds = {}, {}, [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    modules[plane.name] = _events(line)
                elif line.name == OP_LINE:
                    ops[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = _events(line)
                marks = [e for e in events if e.name.startswith(ROUND_PREFIX)]
                if marks:
                    host, rounds = events, sorted(marks, key=lambda e: e.start)
    if not rounds:
        raise ValueError(f"no {ROUND_PREFIX} annotation in {path}")
    return Trace(modules, ops, host, rounds)
