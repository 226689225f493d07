"""Server orchestration: the FedPart / FNU round loop (paper §3).

Per round: select trainable group from the schedule, broadcast, clients train
locally, server averages exactly the transmitted parameters (full network on
FNU rounds, the trainable group's subtree on partial rounds; BN running
statistics never travel), evaluates the global model on the balanced set,
and books communication/compute costs.

Client execution is delegated to a pluggable engine (``repro.fl.batched``):

* ``engine="sequential"`` — the reference oracle: a Python loop over the
  selected clients, one jitted dispatch per (client, step);
* ``engine="vmap"``       — the batched engine: clients stacked along a
  leading axis, the whole local round one vmapped compiled program and the
  aggregation one on-device reduction;
* ``engine="shard_map"``  — the multi-device engine: the stacked client axis
  sharded over a 1-D "clients" mesh (``sim_devices`` of them; 0 = all), local
  rounds vmapped per device and aggregation an on-mesh psum of the
  transmitted subtree only.

All three are equivalent to <=1e-5 (``tests/test_engine_equivalence.py``);
docs/ENGINES.md is the quick reference for picking one.

Orthogonally to the engine, ``FLRunConfig(runtime=...)`` picks the *runtime*
— how rounds relate to time:

* ``runtime="sync"``  — this module's loop: one barrier per schedule entry;
* ``runtime="async"`` — the event-driven simulator (``repro.fl.runtime``):
  client availability/latency/dropout on a virtual clock, buffered
  staleness-weighted aggregation (FedBuff), partial participation, and
  time-to-accuracy as first-class output.  In the degenerate config (perfect
  fleet, full buffer, exponent 0) it reproduces this loop to <=1e-5
  (docs/ASYNC.md).

Orthogonally to both, ``FLRunConfig(plan=..., capacity_tiers=...)`` picks the
*per-client layer plan* (``core.schedule.PlanAssigner``): with
``plan="homogeneous"`` (default) every client trains the round's scheduled
group exactly as before; ``"nested"`` / ``"random"`` give capacity-tiered
clients different group subsets in the same round, and aggregation averages
each group over only the clients that trained it (docs/HETEROGENEITY.md).

``FLRunConfig(compression=...)`` additionally compresses the transmitted
subtree at the client→server boundary (int8 / 1-bit / top-k with per-client
error feedback, ``core.compress``); ``"none"`` (default) is structurally
absent — today's paths bit-for-bit (docs/COMPRESSION.md).

``clients_data`` may also be a ``fl.population.ClientPopulation`` — a
*streaming* client store that produces shards on demand from
(seed, client_id), so cohorts can be sampled from populations of millions of
virtual clients with host cost O(cohort): selection is Floyd's O(cohort)
algorithm, per-(round, client) seeds are collision-free ``SeedSequence``
hashes, and cross-round per-client state (MOON prev-models, EF residuals)
lives in a bounded LRU ``ClientStateStore`` with optional disk spill
(``state_store_entries`` / ``state_store_spill``, docs/POPULATION.md).  A
legacy materialised ``Sequence`` is wrapped transparently and behaves
exactly as before.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Sequence

import jax
import numpy as np

from repro.core import compress
from repro.core.costs import VirtualTimeModel, comm_cost, comp_cost
from repro.core.partition import Partition, group_param_counts
from repro.core.schedule import PlanAssigner, RoundSpec
from repro.core.telemetry import StepSizeTracker, Timeline, span
from repro.fl.algorithms import AlgoConfig
from repro.fl.batched import make_engine
from repro.fl.client import LocalTrainer
from repro.fl.population import (ClientPopulation, ClientStateStore,
                                 as_population, client_round_seed,
                                 resolve_cohort_size,
                                 sample_without_replacement)
from repro.fl.runtime.clients import AvailabilityConfig
from repro.fl.tasks import TaskAdapter
from repro.optim.adam import AdamConfig

PyTree = Any

RUNTIMES = ("sync", "async")


@dataclasses.dataclass(frozen=True)
class FLRunConfig:
    local_epochs: int = 8
    batch_size: int = 32
    lr: float = 1e-3
    adam_eps: float = 1e-8
    algo: AlgoConfig = AlgoConfig()
    sample_fraction: float = 1.0    # participation fraction per dispatch/round
    cohort_size: int = 0            # explicit clients per dispatch (0 = use fraction)
    # Async cohort selection (docs/ASYNC.md): "blind" rejection-samples each
    # candidate through its own arrival draw (the legacy path, bit-exact);
    # "biased" weights candidates by current availability and records each
    # pick's inclusion probability for inverse-probability debiased merges.
    participation_sampling: str = "blind"   # "blind" | "biased" (async only)
    seed: int = 0
    eval_every: int = 1
    eval_batch: int = 256
    track_stepsizes: bool = False
    engine: str = "sequential"      # "sequential" | "vmap" | "shard_map"
    sim_devices: int = 0            # shard_map mesh size (0 = all devices)
    donate_buffers: bool = True     # donate params into the agg jit + MOON prev stack (batched engines)
    fused_adam: bool = False        # Pallas masked-Adam local steps (docs/KERNELS.md)
    # -- transmitted-subtree compression (core.compress, docs/COMPRESSION.md)
    compression: str = "none"       # "none" | "int8" | "onebit" | "topk"
    topk_fraction: float = 0.01     # retained fraction per leaf (topk only)
    error_feedback: bool = True     # per-client EF residuals (compressed kinds)
    compression_block_rows: int = 0  # scale granularity: 0 = per leaf, B = B*128-elem blocks
    # -- per-client layer plans (heterogeneous fleets, docs/HETEROGENEITY.md)
    plan: str = "homogeneous"       # "homogeneous" | "nested" | "random"
    capacity_tiers: tuple[float, ...] = ()  # tier capacities in (0,1]; () = one full-capacity tier
    # -- bounded per-client state (population scale, docs/POPULATION.md) ----
    state_store_entries: int = 0    # LRU cap on MOON prevs + EF residuals (0 = unbounded)
    state_store_spill: str = ""     # spill dir for evicted entries ("" = drop on evict)
    # -- runtime (sync barrier loop vs event-driven async simulator) --------
    runtime: str = "sync"           # "sync" | "async" (repro.fl.runtime)
    async_policy: str = "fedbuff"   # "fedbuff" | "sync" (barrier oracle)
    buffer_k: int = 0               # FedBuff goal K (0 = cohort size)
    staleness_exponent: float = 0.0  # poly staleness discount (1+s)^-a
    availability: AvailabilityConfig = AvailabilityConfig()
    vtime: VirtualTimeModel = VirtualTimeModel()
    # Host-parallel dispatch: cohorts concurrently in flight.  1 = the
    # merge-driven dispatch of the original async runtime (dispatch only at
    # merges/stalls); >1 keeps that many cohorts training at once, each on
    # its own disjoint device submesh when the engine has one to give
    # (docs/ASYNC.md "Host-parallel dispatch").
    max_inflight_cohorts: int = 1
    # -- adaptive server control loop (fl/runtime/control.py, docs/CONTROL.md)
    controller: str = "static"      # "static" (no controller object) | "adaptive"
    controller_window: int = 4      # merges per observation window
    controller_inflight_bounds: tuple[int, int] = (1, 4)  # adaptive inflight lo/hi
    controller_buffer_bounds: tuple[int, int] = (1, 8)    # adaptive buffer_k lo/hi
    controller_mix_floor: float = 0.5  # min windowed discounted mixing coeff
    controller_max_repeats: int = 2    # consecutive layer-group repeats cap
    # The two participation knobs (docs/CONTROL.md): a windowed
    # effective-participation target the ParticipationController holds by
    # moving the cohort size inside controller_cohort_bounds (0.0 = off),
    # and the PlanAssignmentController's cap on extra layer groups added to
    # every capacity tier's plan prefix (0 = off).
    controller_participation_target: float = 0.0
    controller_cohort_bounds: tuple[int, int] = (1, 64)
    controller_plan_boost_max: int = 0

    def __post_init__(self):
        """Loud validation of the participation axis — a fraction of 0 used
        to silently train 1 client per round via ``resolve_cohort_size``'s
        ``max(1, ...)`` clamp."""
        if not 0.0 < self.sample_fraction <= 1.0:
            raise ValueError(
                f"sample_fraction must be in (0, 1], got {self.sample_fraction}")
        if self.cohort_size < 0:
            raise ValueError(
                f"cohort_size must be >= 0, got {self.cohort_size}")
        if self.participation_sampling not in ("blind", "biased"):
            raise ValueError(
                f"unknown participation_sampling "
                f"{self.participation_sampling!r}; expected 'blind' or "
                f"'biased'")
        if not 0.0 <= self.controller_participation_target <= 1.0:
            raise ValueError(
                f"controller_participation_target must be in [0, 1], got "
                f"{self.controller_participation_target}")
        lo, hi = self.controller_cohort_bounds
        if not 1 <= lo <= hi:
            raise ValueError(
                f"controller_cohort_bounds must satisfy 1 <= lo <= hi, got "
                f"{self.controller_cohort_bounds}")
        if self.controller_plan_boost_max < 0:
            raise ValueError(
                f"controller_plan_boost_max must be >= 0, got "
                f"{self.controller_plan_boost_max}")

    def make_state_store(self) -> ClientStateStore:
        """The per-run store for cross-round per-client state (MOON
        prev-models, EF residuals).  The defaults mean unbounded — the
        legacy dict semantics, bit-for-bit."""
        return ClientStateStore(max_entries=self.state_store_entries,
                                spill_dir=self.state_store_spill or None)


@dataclasses.dataclass
class FLResult:
    history: list[dict]
    params: PyTree
    partition: Partition
    tracker: StepSizeTracker | None
    comm_total_bytes: int
    comp_total_flops: float
    comm_fnu_bytes: int
    comp_fnu_flops: float
    timeline: Timeline | None = None   # async runtime: virtual-clock event log

    @property
    def best_acc(self) -> float:
        accs = [h["acc"] for h in self.history if "acc" in h]
        return max(accs) if accs else float("nan")

    @property
    def final_acc(self) -> float:
        accs = [h["acc"] for h in self.history if "acc" in h]
        return accs[-1] if accs else float("nan")


def run_federated(
    adapter: TaskAdapter,
    clients_data: Sequence | ClientPopulation,
    eval_set: tuple[np.ndarray, np.ndarray],
    rounds: Sequence[RoundSpec],
    run_cfg: FLRunConfig,
    *,
    init_key=None,
    verbose: bool = False,
) -> FLResult:
    if run_cfg.runtime == "async":
        from repro.fl.runtime.engine import run_federated_async
        return run_federated_async(adapter, clients_data, eval_set, rounds,
                                   run_cfg, init_key=init_key, verbose=verbose)
    if run_cfg.runtime != "sync":
        raise ValueError(
            f"unknown runtime {run_cfg.runtime!r}; expected one of {RUNTIMES}")
    if run_cfg.participation_sampling != "blind":
        raise ValueError(
            "participation_sampling='biased' needs the arrival process — "
            "use runtime='async'")
    if run_cfg.track_stepsizes and run_cfg.engine != "sequential":
        raise ValueError("track_stepsizes requires engine='sequential'")
    key = init_key if init_key is not None else jax.random.key(run_cfg.seed)
    params = adapter.init(key)
    partition = adapter.partition(params)
    trainer = LocalTrainer(
        adapter=adapter,
        partition=partition,
        algo=run_cfg.algo,
        adam=AdamConfig(lr=run_cfg.lr, eps=run_cfg.adam_eps),
    )
    ccfg = compress.make_config(
        run_cfg.compression, topk_fraction=run_cfg.topk_fraction,
        error_feedback=run_cfg.error_feedback,
        block_rows=run_cfg.compression_block_rows)
    state_store = run_cfg.make_state_store()
    engine = make_engine(
        run_cfg.engine, trainer=trainer, partition=partition,
        algo=run_cfg.algo, sim_devices=run_cfg.sim_devices,
        donate=run_cfg.donate_buffers, fused_adam=run_cfg.fused_adam,
        compression=ccfg, state_store=state_store,
    )
    assigner = PlanAssigner(
        num_groups=partition.num_groups, kind=run_cfg.plan,
        capacity_tiers=tuple(run_cfg.capacity_tiers), seed=run_cfg.seed)
    rng = np.random.default_rng(run_cfg.seed)
    eval_x, eval_y = eval_set
    eval_fn = jax.jit(adapter.evaluate)

    tracker = StepSizeTracker() if run_cfg.track_stepsizes else None
    history: list[dict] = []
    is_moon = run_cfg.algo.name == "moon"

    # The population seam: a legacy Sequence becomes a (materialised)
    # population; everything below touches only the sampled cohort, so a
    # streaming population of millions costs O(cohort) per round.
    population = as_population(clients_data)
    n_clients = population.num_clients
    for spec in rounds:
        with span("fl.round", round=spec.index, group=spec.group,
                  phase=spec.phase):
            t_round = time.perf_counter()
            with span("fl.sample"):
                n_pick = resolve_cohort_size(n_clients, run_cfg.sample_fraction,
                                             run_cfg.cohort_size)
                picked = sample_without_replacement(rng, n_clients, n_pick)
                seeds = [client_round_seed(run_cfg.seed, spec.index, ci)
                         for ci in picked]
            if tracker is not None:
                tracker.mark_round_boundary()

            with span("fl.client_data"):
                datasets = [population.dataset(ci) for ci in picked]
                weights = [len(d) for d in datasets]
                prevs = ([state_store.get("moon", int(ci)) for ci in picked]
                         if is_moon else None)

            params, losses, new_locals = engine.run_round(
                params,
                spec,
                datasets,
                seeds=seeds,
                weights=weights,
                epochs=run_cfg.local_epochs,
                batch_size=run_cfg.batch_size,
                prev_params=prevs,
                tracker=tracker,
                plan=assigner.assign(spec, [int(ci) for ci in picked]),
                client_ids=[int(ci) for ci in picked],
            )
            if new_locals is not None:
                for ci, local in zip(picked, new_locals):
                    state_store.put("moon", int(ci), local)

            entry = {"round": spec.index, "phase": spec.phase, "group": spec.group,
                     "loss": float(np.mean(losses))}
            if spec.index % run_cfg.eval_every == 0 or spec.index == len(rounds) - 1:
                with span("fl.eval"):
                    acc = eval_fn(params, eval_x[: run_cfg.eval_batch],
                                  eval_y[: run_cfg.eval_batch])
                    with span("fl.wait", what="eval"):
                        entry["acc"] = float(acc)
            # Host wall clock, compiles included.  The losses (and the eval,
            # when the round has one) are read back to the host, so the
            # round's device work has finished by now; a round without an
            # eval may leave its aggregation to be waited on by the next
            # round.
            entry["seconds"] = time.perf_counter() - t_round
        history.append(entry)
        if verbose:
            print(f"round {spec.index:3d} [{spec.phase}:{spec.group:3d}] "
                  f"loss={entry['loss']:.4f} acc={entry.get('acc', float('nan')):.4f}")

    # Cost bookkeeping (per client, per the paper's Comm./Comp. metrics).
    group_weights = group_param_counts(params, partition).astype(np.float64)
    comm = comm_cost(params, partition, rounds, compression=ccfg)
    comp = comp_cost(partition, rounds, group_fwd_flops=group_weights)
    return FLResult(
        history=history,
        params=params,
        partition=partition,
        tracker=tracker,
        comm_total_bytes=comm.total_bytes,
        comp_total_flops=float(comp.total_flops),
        comm_fnu_bytes=comm.fnu_total_bytes,
        comp_fnu_flops=float(comp.fnu_total_flops),
    )
