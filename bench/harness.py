"""One run of one cell: build it from the seed, drive the program's own
federated loop through set-up and a measured window, check the first rounds
against the plain reference, and, with tracing, reduce the profiler trace to
the cell's per-layer metrics.

Everything that belongs to one cell is data, found by name:
``BENCHMARK.json`` names the cell's configuration and traffic;
``bench/configs/<config>.json`` holds the model and data sizes and names its
plain reference (``bench/reference/<name>.py``) and data generator
(``bench/data/<kind>.py``); ``bench/traffic/<traffic>.json`` holds the engine,
cohort, batch, epochs and schedule; ``bench/limits/<cell>.json`` holds the
limits of the comparison; ``bench/metrics/<metric>.py`` reads one per-layer
metric.

The window drives ``repro.fl.server.run_federated`` (sync runtime), one call
per run.  Its schedule is a ``Rounds`` object: it first yields one round of
every distinct program the window uses (and at least the three rounds the
reference follows), then window rounds until the window's time is up and the
schedule's cycle is whole, so that every group's round counts alike.
``run_federated`` reads each round's losses and eval back to the host before
it asks for the next round, so the moment it asks is the moment the previous
round has finished on the device.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import shutil
import statistics
import sys
import time
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_DIR = BENCH / ".trace"
COMPARED_ROUNDS = 3
FULL = -1

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
CACHE_MISS = "/jax/compilation_cache/cache_misses"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(root: Path, kind: str, name: str):
    """``<root>/bench/<kind>/<name>.py``, imported from its file."""
    path = root / "bench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    per_layer: list[dict]       # BENCHMARK.json entries that read this cell
    reference: object           # bench/reference/<name>.py
    data: object                # bench/data/<kind>.py
    metrics: dict               # per-layer metric name -> bench/metrics/<name>.py


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with every file it
    names."""
    bench = load_json(root / "BENCHMARK.json")
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = found[0]
    [c] = [c for c in bench["configs"] if c["name"] == w["config"]]
    config = load_json(root / c["file"])
    per_layer = [m for m in bench["per_layer"] if name in m.get("workloads", [name])]
    return Cell(
        name=name, chips=w["chips"], config=config,
        traffic=load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(root / "bench" / "limits" / f"{name}.json"),
        per_layer=per_layer,
        reference=load_module(root, "reference", config["reference"]),
        data=load_module(root, "data", config["data"]["kind"]),
        metrics={m["name"]: load_module(root, "metrics", m["name"]) for m in per_layer},
    )


def seed_key(seed: int) -> jax.Array:
    """A JAX key from a seed of any size (two 32-bit words of its hash)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jax.numpy.asarray(words))


class CompileClock:
    """Host-clock stamps of JAX's trace, lowering and compile events, and the
    persistent-cache misses."""

    def __init__(self):
        self.events: list[tuple[float, float]] = []
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_):
        if event in COMPILE_EVENTS:
            self.events.append((time.perf_counter(), duration))

    def _on_event(self, event: str, **_):
        if event == CACHE_MISS:
            self.misses += 1

    def count(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t, _ in self.events)

    def seconds(self, t0: float, t1: float) -> float:
        return sum(d for t, d in self.events if t0 <= t <= t1)


def round_groups(schedule: str, num_groups: int) -> list[int]:
    """One cycle of the traffic's schedule."""
    if schedule == "fedpart":
        return list(range(num_groups))
    if schedule == "fnu":
        return [FULL]
    raise ValueError(f"unknown schedule {schedule!r}")


class Rounds:
    """The schedule handed to ``run_federated``: ``setup`` rounds, then
    window rounds until ``seconds`` have passed (or, when tracing, until
    ``trace_rounds`` rounds have run under the profiler) and the window holds
    whole cycles of the schedule (unless ``whole_cycles`` is off, as for the
    comparison's own readings, which need one round past the compared ones).

    ``run_federated`` iterates it once for the rounds and again, after the
    last, for its cost books; the second pass replays the rounds issued."""

    def __init__(self, cycle: list[int], setup: int, seconds: float, *,
                 trace_rounds: int = 0, trace_dir: Path | None = None,
                 whole_cycles: bool = True):
        self.cycle, self.setup, self.seconds = cycle, setup, seconds
        self.period = len(cycle) if whole_cycles else 1
        self.trace_rounds, self.trace_dir = trace_rounds, trace_dir
        self.issued: list = []
        self.ends: list[float] = []      # host time at which each round was done
        self.window_start = self.window_end = None
        self._annotation = None

    def spec(self, i: int):
        from repro.core.schedule import RoundSpec
        g = self.cycle[i % len(self.cycle)]
        if g == FULL:
            return RoundSpec(i, "warmup", -1, FULL)
        return RoundSpec(i, "partial", i // len(self.cycle), g)

    def __len__(self) -> int:
        # While the rounds run, one more than issued: the loop asks for the
        # length only to find its last round, which the window never knows
        # in advance.
        return len(self.issued) + (self.window_end is None)

    def __iter__(self):
        if self.window_end is not None:
            return iter(list(self.issued))
        return self._run()

    def _open(self, spec):
        if self.trace_dir is not None:
            self._annotation = jax.profiler.TraceAnnotation(
                f"bench_round {spec.index} group {spec.group}")
            self._annotation.__enter__()

    def _close(self):
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None

    def _run(self):
        i = 0
        while True:
            now = time.perf_counter()
            if i:
                self._close()
                self.ends.append(now)
            if i == self.setup:
                if self.trace_dir is not None:
                    # Host events of the runtime (level 2) but no Python
                    # function tracing, whose cost would swell the host's
                    # share of the window.
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    opts.host_tracer_level = 2
                    jax.profiler.start_trace(str(self.trace_dir),
                                             profiler_options=opts)
                    now = time.perf_counter()
                self.window_start = now
            elif i > self.setup:
                done = i - self.setup
                enough = (done >= self.trace_rounds if self.trace_dir is not None
                          else now - self.window_start >= self.seconds)
                if enough and done % self.period == 0:
                    if self.trace_dir is not None:
                        jax.profiler.stop_trace()
                    self.window_end = now
                    return
            spec = self.spec(i)
            self.issued.append(spec)
            self._open(spec)
            yield spec
            i += 1

    @property
    def window_rounds(self) -> list:
        return self.issued[self.setup:]

    @property
    def window_round_s(self) -> list[float]:
        t = [self.window_start] + self.ends[self.setup:]
        return [b - a for a, b in zip(t, t[1:])]


class EngineTap:
    """The engine ``run_federated`` builds, with a look at the global weights
    that enter the rounds in ``capture`` (copied to the host before the round
    donates them)."""

    def __init__(self, capture: set[int]):
        self.capture = capture
        self.seen: dict[int, dict] = {}
        self.client_loss: list[list[float]] = []
        self.engine = None

    def __getattr__(self, name):
        return getattr(self.engine, name)

    def run_round(self, params, spec, *args, **kw):
        if spec.index in self.capture:
            self.seen[spec.index] = jax.device_get(params)
        out = self.engine.run_round(params, spec, *args, **kw)
        if spec.index < max(self.capture):
            self.client_loss.append(list(out[1]))
        return out


@contextlib.contextmanager
def tapped_engine(capture: set[int]):
    from repro.fl import server

    tap = EngineTap(capture)
    real = server.make_engine

    def make_engine(*args, **kw):
        tap.engine = real(*args, **kw)
        return tap

    server.make_engine = make_engine
    try:
        yield tap
    finally:
        server.make_engine = real


def build(cell: Cell, seed: int):
    """Data, weights, adapter and run config of one run, from the seed."""
    from repro.data.pipeline import ClientDataset
    from repro.fl import tasks
    from repro.fl.server import FLRunConfig

    cfg, tr = cell.config, cell.traffic
    k_data, k_weights = jax.random.split(seed_key(seed))
    cx, cy, ex, ey = cell.data.make(k_data, cfg)
    params = cell.reference.make_params(k_weights, cfg)
    start = jax.device_get(params)
    prog = cfg["program"]
    adapter = dataclasses.replace(
        getattr(tasks, prog["adapter"])(**prog["args"]),
        init=lambda key: params)
    clients = [ClientDataset(cx[i], cy[i]) for i in range(len(cy))]
    run_cfg = FLRunConfig(
        local_epochs=tr["local_epochs"], batch_size=tr["batch"], lr=tr["lr"],
        adam_eps=tr["adam_eps"], cohort_size=tr["cohort"], seed=int(seed),
        eval_every=tr["eval_every"], eval_batch=tr["eval_batch"],
        engine=tr["engine"], sim_devices=tr["sim_devices"],
        fused_adam=tr["fused_adam"])
    return dict(adapter=adapter, clients=clients, eval_set=(ex, ey),
                run_cfg=run_cfg, start=start, client_x=cx, client_y=cy)


def recipe(cell: Cell, seed: int) -> dict:
    tr = cell.traffic
    return dict(cohort=tr["cohort"], batch=tr["batch"], epochs=tr["local_epochs"],
                lr=tr["lr"], eps=tr["adam_eps"], seed=int(seed))


def samples_per_round(cell: Cell) -> int:
    """Client training samples of one round: full batches of every epoch of
    every cohort client (all clients hold the same number of samples)."""
    tr, n = cell.traffic, cell.config["data"]["samples_per_client"]
    bs = min(tr["batch"], n)
    return tr["cohort"] * tr["local_epochs"] * (n // bs) * bs


def peak_bytes(chips: int) -> int:
    stats = [d.memory_stats() or {} for d in jax.local_devices()[:chips]]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def drive(cell: Cell, seed: int, seconds: float, trace: bool,
          setup: int | None = None, whole_cycles: bool = True) -> dict:
    """Set-up and window through ``run_federated``; returns the record of
    the run (the program's first rounds included).  ``setup`` rounds default
    to one of every distinct program and at least the compared rounds."""
    from repro.fl.server import run_federated

    run = build(cell, seed)
    ng = cell.reference.num_groups(cell.config)
    cycle = round_groups(cell.traffic["schedule"], ng)
    if setup is None:
        setup = max(COMPARED_ROUNDS, len(cycle))
    trace_dir = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        trace_dir = TRACE_DIR
    rounds = Rounds(cycle, setup, seconds,
                    trace_rounds=max(cell.traffic["trace_rounds"], len(cycle)),
                    trace_dir=trace_dir, whole_cycles=whole_cycles)
    with tapped_engine({1, COMPARED_ROUNDS}) as tap:
        result = run_federated(run["adapter"], run["clients"], run["eval_set"],
                               rounds, run["run_cfg"],
                               init_key=jax.random.key(0))
    hist = result.history
    ref = cell.reference
    prog = {"loss": [h["loss"] for h in hist[:COMPARED_ROUNDS]],
            "acc": [h["acc"] for h in hist[:COMPARED_ROUNDS]],
            "client_loss": tap.client_loss,
            "params": [ref.flatten(tap.seen[1]), ref.flatten(tap.seen[COMPARED_ROUNDS])]}
    record = dict(
        run=run, rounds=rounds, prog=prog,
        memory_peak_bytes=peak_bytes(cell.chips),
        trace_counts=(tap.engine.trace_count, tap.engine.trainer.trace_count),
        failed=sum(not (np.isfinite(h["loss"]) and np.isfinite(h.get("acc", 0.0)))
                   for h in hist),
        attempted=len(hist),
    )
    del result, tap
    return record


def reference_run(cell: Cell, run: dict, seed: int, groups: list[int], **kw) -> dict:
    """The plain reference over ``groups`` from the run's start weights."""
    ref = cell.reference
    fed = ref.Federation(cell.config, recipe(cell, seed), run["client_x"],
                         run["client_y"], *run["eval_set"], **kw)
    return fed.run(run["start"], groups)


def compare(cell: Cell, record: dict, seed: int) -> dict:
    """The compared numbers of a run, with the reference run after the
    window.  ``params`` of both are after round 1 and after round 3."""
    from bench import correct

    groups = [s.group for s in record["rounds"].issued[:COMPARED_ROUNDS]]
    ref_out = reference_run(cell, record["run"], seed, groups)
    ref_out["params"] = [ref_out["params"][0], ref_out["params"][-1]]
    start = cell.reference.flatten(record["run"]["start"])
    eval_n = len(record["run"]["eval_set"][1])
    return correct.numbers(record["prog"], ref_out, start, eval_n)


def layer_context(cell: Cell, record: dict, clock: CompileClock, peaks: dict) -> dict:
    """The readers' context of this run's trace (``trace_context``)."""
    rounds = record["rounds"]
    return trace_context(cell, next(TRACE_DIR.rglob("*.xplane.pb")), peaks,
                         traced_groups=[s.group for s in rounds.window_rounds],
                         setup_compile_s=clock.seconds(0.0, rounds.window_start))


def trace_context(cell: Cell, path: Path, peaks: dict, traced_groups: list[int],
                  setup_compile_s: float) -> dict:
    """What the per-layer metric readers read: the reduced trace
    (``trace``), the device ops with their scope paths (``ops``, from the
    same file; ``None`` where the XSpace module cannot be loaded, and then
    only the readers of ``bench.scopes`` read nothing), the cell, the chip's
    peaks and the counts of the traced rounds.

    Every configuration's readers are checked on a trace of its own
    program: ``bench/tests/data/<config>.xplane.pb.gz``, recorded on the
    chip by ``bench/tests/record_trace.py`` from ``<config>.ctx.json``,
    which holds the test-size model and traffic the recording ran and the
    traced groups and set-up compile seconds it read (the arguments of this
    function).  A configuration without that pair fails
    ``test_metric_readers_on_the_trace``."""
    from bench import scopes, traces

    tr = traces.load(str(path))
    try:
        ops = scopes.load_ops(path, tr.window)
    except ModuleNotFoundError:
        ops = None
    ref, cfg = cell.reference, cell.config
    return dict(
        trace=tr, ops=ops, cell=cell, peaks=peaks,
        traced_groups=traced_groups,
        samples_per_round=samples_per_round(cell),
        client_steps=samples_per_round(cell) // (cell.traffic["cohort"] * cell.traffic["batch"]),
        group_fwd_flops=ref.group_forward_flops(cfg),
        group_trained_params=ref.group_trained_params(cfg),
        setup_compile_s=setup_compile_s,
    )


def read_metrics(cell: Cell, ctx: dict) -> dict:
    out = {}
    for m in cell.per_layer:
        value = cell.metrics[m["name"]].read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
        peaks: dict | None = None, log=print) -> dict:
    """One run; returns the result object (the last line's JSON).  ``peaks``
    is the chip's row of ``bench/peaks.json`` (the per-layer metrics need
    it)."""
    from bench import correct

    clock = CompileClock()
    record = drive(cell, seed, seconds, trace)
    rounds = record["rounds"]
    ws, we = rounds.window_start, rounds.window_end
    setup_s = ws - t0
    window_s = we - ws
    n_rounds = len(rounds.window_rounds)
    in_window = clock.count(ws, we)
    per_round = rounds.window_round_s
    device = jax.local_devices()[0]
    log(f"device {device.platform} {device.device_kind} x{cell.chips}")
    log(f"rounds in window {n_rounds} in {window_s:.3f} s; per round p50 "
        f"{statistics.median(per_round):.4f} s max {max(per_round):.4f} s")
    log(f"compile events in window {in_window}; engine traces "
        f"{record['trace_counts'][0]} trainer traces {record['trace_counts'][1]}")
    log(f"peak_bytes_in_use {record['memory_peak_bytes']}")
    log(f"setup {setup_s:.3f} s ({'cold' if clock.misses else 'warm'}: "
        f"{clock.misses} compile-cache misses, compile events "
        f"{clock.seconds(0.0, ws):.3f} s)")
    if in_window:
        raise RuntimeError(f"{in_window} compile events inside the window")

    out = {"correct": False, "attempted": record["attempted"],
           "failed": record["failed"], "metrics": {},
           "device": {"platform": device.platform, "kind": device.device_kind,
                      "count": cell.chips,
                      "memory_peak_bytes": record["memory_peak_bytes"]}}
    if trace:
        ctx = layer_context(cell, record, clock, peaks)
        tr = ctx["trace"]
        out["metrics"] = read_metrics(cell, ctx)
        out["device"].update(busy_s=tr.busy_s(), window_s=tr.window_s)
        out["breakdown"] = {"device_ops": tr.top_ops(), "idle_gaps": tr.idle_gaps()}
        del ctx, tr
    else:
        out["metrics"] = {
            "client_samples_per_s": {
                "value": n_rounds * samples_per_round(cell) / window_s,
                "unit": "samples/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    gc.collect()
    numbers = compare(cell, record, seed)
    ok, checks = correct.verdict(numbers["values"], cell.limits)
    out["correct"] = ok and record["failed"] == 0
    for name, c in checks.items():
        log(f"check {name} {c['value']:.6g} limit {c['limit']:.6g}", file=sys.stderr)
    out["numbers"] = {"values": numbers["values"], "worst_leaf": numbers["leaves"],
                      "counted_leaves": numbers["counted"]}
    out["checks"] = checks
    return out
