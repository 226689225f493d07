"""Production FedPart trainer: the paper's round schedule driving the
*mesh-parallel* step functions (steps.py) on any architecture config.

This is the bridge between the two halves of the repo: `fl/` simulates many
clients on CPU for the paper-faithful experiments; THIS driver runs FedPart
as a datacenter training feature — each round jit-executes either the FNU
step or the partial step for the scheduled layer group, with the gradient
collectives and optimizer state scoped to that group (DESIGN.md §3).
Round boundaries ARE the communication rounds: under data parallelism the
per-step gradient all-reduce plays the role of server aggregation (the
clients-as-data-shards mapping).

CPU-runnable at smoke scale:

    python -m repro.launch.fedtrain --arch tinyllama-1.1b --rounds 8 \
        --steps-per-round 4 --rl 1

It also fronts the many-client *simulation* half (fl/) so the engine choice is
a launch-surface flag: ``--sim-clients N`` runs the paper-faithful federation
on a synthetic vision task with ``--engine sequential`` (per-client oracle
loop, the default — the conv model hits vmap's grouped-conv slow path on
XLA:CPU), ``--engine vmap`` (batched vmap-over-clients), or
``--engine shard_map`` (clients sharded over ``--sim-devices`` mesh devices;
on CPU the flag also forces that many simulated host devices — see
docs/ENGINES.md):

    python -m repro.launch.fedtrain --sim-clients 8 --rounds 12 --engine vmap
    python -m repro.launch.fedtrain --sim-clients 8 --rounds 12 \
        --engine shard_map --sim-devices 4

``--runtime async`` swaps the barrier-per-round loop for the event-driven
simulator (``repro.fl.runtime``, docs/ASYNC.md): partial participation
(``--participation``), buffered staleness-weighted aggregation
(``--buffer-k``, ``--staleness-exp``) and a seeded client
availability/latency model (``--speed-spread``, ``--latency-jitter``,
``--dropout``), with time-to-accuracy booked on a virtual clock.
``--max-inflight N`` keeps N cohorts training concurrently, each on its own
disjoint device submesh (host-parallel dispatch, docs/ASYNC.md):

    python -m repro.launch.fedtrain --sim-clients 8 --rounds 12 \
        --engine vmap --runtime async --participation 0.5 --buffer-k 2 \
        --staleness-exp 0.5 --speed-spread 3.0 --max-inflight 2

``--controller adaptive`` closes the server control loop (docs/CONTROL.md):
between merges the server observes a window of the virtual timeline and
re-targets the in-flight cohort count, the FedBuff goal K, and the next
layer group, within ``--controller-inflight-bounds`` /
``--controller-buffer-bounds`` / ``--controller-max-repeats``:

    python -m repro.launch.fedtrain --sim-clients 8 --rounds 12 \
        --engine vmap --runtime async --participation 0.25 \
        --staleness-exp 0.5 --speed-spread 3.0 --controller adaptive

``--trace diurnal --duty-cycle 0.25 0.9`` drives participation from
deterministic per-client on/off windows instead of the i.i.d.
``--unavailable`` coin; ``--participation-sampling biased`` then weights
cohort selection by current availability and inverse-probability debiases
the merge, and ``--controller-participation-target`` /
``--controller-plan-boost-max`` close the loop on cohort size and
capacity-tier plan depth (docs/ASYNC.md, docs/CONTROL.md):

    python -m repro.launch.fedtrain --sim-clients 8 --rounds 12 \
        --engine vmap --runtime async --participation 0.5 \
        --trace diurnal --duty-cycle 0.25 0.9 --trace-period 2.0 \
        --participation-sampling biased --controller adaptive \
        --controller-participation-target 0.5

``--plan nested --capacity-tiers 0.3 0.6 1.0`` gives capacity-tiered clients
*different layer subsets in the same round* (per-client layer plans,
docs/HETEROGENEITY.md); each group is aggregated over only the clients that
trained it:

    python -m repro.launch.fedtrain --sim-clients 8 --rounds 12 \
        --engine vmap --plan nested --capacity-tiers 0.3 0.6 1.0

``--compression int8|onebit|topk`` quantises/sparsifies the transmitted
subtree at the client→server boundary with per-client error feedback
(docs/COMPRESSION.md); the comm ledger then prices the encoded wire format:

    python -m repro.launch.fedtrain --sim-clients 8 --rounds 12 \
        --engine vmap --compression int8

``--population N`` swaps the materialised client list for a *streaming*
``fl.population.SyntheticPopulation`` of N virtual clients whose shards are
derived on demand from (seed, client_id) — host cost per round is O(cohort),
so N can be millions (docs/POPULATION.md).  ``--cohort-size K`` pins the
dispatch size directly (the natural knob at population scale);
``--state-store-entries`` / ``--state-store-spill`` bound the per-client
MOON/EF state:

    python -m repro.launch.fedtrain --population 1000000 --cohort-size 8 \
        --rounds 12 --runtime async --participation 0.5
"""

from __future__ import annotations

import argparse
import time
from typing import Any

if __name__ == "__main__":
    # --sim-devices N on CPU simulates an N-device host; XLA reads the flag
    # at first-import time, so it must be set before jax loads below.
    from repro.launch._simdev import force_sim_devices
    force_sim_devices()

import jax
import numpy as np

from repro.configs import get_config
from repro.core.compile_cache import enable_compile_cache
from repro.core.schedule import FULL_NETWORK, FedPartSchedule, RoundSpec
from repro.launch import steps
from repro.models import api
from repro.models.api import InputShape
from repro.optim.adam import AdamConfig

PyTree = Any


class FedPartMeshTrainer:
    """Round loop cycling layer groups over jitted partial steps.

    One jitted step per distinct group is cached; optimizer state is
    re-initialised per round over the group's subtree (paper semantics:
    clients start each round fresh from the broadcast model)."""

    def __init__(self, cfg, adam: AdamConfig = AdamConfig(), *,
                 remat: bool = False, donate: bool = True):
        self.cfg = cfg
        self.adam = adam
        self.remat = remat
        self._full = jax.jit(steps.make_train_step(cfg, adam, remat=remat))
        self._partial: dict[int, Any] = {}
        self._groups: list[steps.StackedGroup] | None = None

    def groups(self, params) -> list[steps.StackedGroup]:
        if self._groups is None:
            self._groups = steps.list_groups(params)
        return self._groups

    def _partial_step(self, params, gidx: int):
        if gidx not in self._partial:
            group = self.groups(params)[gidx]
            self._partial[gidx] = jax.jit(
                steps.make_fedpart_train_step(self.cfg, group, self.adam,
                                              remat=self.remat)
            )
        return self._partial[gidx]

    def run_round(self, params, spec: RoundSpec, batches) -> tuple[PyTree, float]:
        """One communication round: several local steps of the scheduled
        group (or the full network), fresh optimizer state."""
        if spec.is_full:
            opt = steps.init_opt_state(params)
            step = self._full
        else:
            gidx = spec.group % len(self.groups(params))
            opt = steps.init_partial_opt_state(params, self.groups(params)[gidx])
            step = self._partial_step(params, gidx)
        losses = []
        for batch in batches:
            params, opt, loss = step(params, opt, batch)
            losses.append(float(loss))
        return params, float(np.mean(losses))

    def transmitted_params(self, params, spec: RoundSpec) -> int:
        """Parameter count this round's aggregation moves (ledger)."""
        if spec.is_full:
            return int(sum(x.size for x in jax.tree.leaves(params)))
        group = self.groups(params)[spec.group % len(self.groups(params))]
        sub = steps._select_group(params, group)
        return int(sum(x.size for x in jax.tree.leaves(sub)))


def run_simulation(args) -> int:
    """Many-client FL simulation (fl/ stack) behind the launch surface."""
    from repro.core.schedule import FedPartSchedule
    from repro.data import (VisionDatasetSpec, balanced_eval_set, build_clients,
                            iid_partition, make_vision_dataset)
    from repro.fl import (AvailabilityConfig, FLRunConfig, resnet_task,
                          run_federated)
    from repro.fl.population import SyntheticPopulation

    spec = VisionDatasetSpec(num_classes=8, image_size=16)
    Xe, ye = make_vision_dataset(spec, 400, seed=99)
    eval_set = balanced_eval_set(Xe, ye, per_class=24)
    if args.population > 0:
        # Streaming population: shards derive lazily from (seed, client_id);
        # nothing O(population) is ever built (docs/POPULATION.md).
        clients = SyntheticPopulation(spec=spec, population=args.population,
                                      samples_per_client=160, seed=0)
        n_clients = args.population
    else:
        X, y = make_vision_dataset(spec, 160 * args.sim_clients, seed=0)
        clients = build_clients(
            X, y, iid_partition(len(y), args.sim_clients, seed=0))
        n_clients = args.sim_clients
    adapter = resnet_task("resnet8", num_classes=8)
    cycles = max(1, -(-args.rounds // (10 * args.rl)))   # just enough rounds
    sched = FedPartSchedule(num_groups=10, warmup_rounds=args.warmup,
                            rounds_per_layer=args.rl, cycles=cycles)
    cfg = FLRunConfig(local_epochs=1, batch_size=args.batch, lr=args.lr,
                      engine=args.engine, sim_devices=args.sim_devices,
                      fused_adam=args.fused_adam,
                      runtime=args.runtime, async_policy=args.async_policy,
                      buffer_k=args.buffer_k,
                      staleness_exponent=args.staleness_exp,
                      sample_fraction=args.participation,
                      cohort_size=args.cohort_size,
                      participation_sampling=args.participation_sampling,
                      state_store_entries=args.state_store_entries,
                      state_store_spill=args.state_store_spill,
                      max_inflight_cohorts=args.max_inflight,
                      controller=args.controller,
                      controller_window=args.controller_window,
                      controller_inflight_bounds=tuple(
                          args.controller_inflight_bounds),
                      controller_buffer_bounds=tuple(
                          args.controller_buffer_bounds),
                      controller_mix_floor=args.controller_mix_floor,
                      controller_max_repeats=args.controller_max_repeats,
                      controller_participation_target=(
                          args.controller_participation_target),
                      controller_cohort_bounds=tuple(
                          args.controller_cohort_bounds),
                      controller_plan_boost_max=args.controller_plan_boost_max,
                      plan=args.plan,
                      capacity_tiers=tuple(args.capacity_tiers),
                      compression=args.compression,
                      topk_fraction=args.topk_fraction,
                      error_feedback=not args.no_error_feedback,
                      compression_block_rows=args.compression_block_rows,
                      availability=AvailabilityConfig(
                          speed_spread=args.speed_spread,
                          latency_jitter=args.latency_jitter,
                          dropout_prob=args.dropout,
                          unavailable_prob=args.unavailable,
                          trace=args.trace,
                          trace_period=args.trace_period,
                          duty_cycle=tuple(args.duty_cycle),
                          trace_path=args.trace_path))
    t0 = time.time()
    res = run_federated(adapter, clients, eval_set,
                        sched.rounds()[: args.rounds], cfg, verbose=True)
    extra = ""
    if res.timeline is not None:
        stale = [h["staleness_max"] for h in res.history]
        extra = (f" vtime={res.timeline.total_seconds:.3f}s "
                 f"max_staleness={max(stale) if stale else 0}")
    print(f"[fedtrain.sim] engine={args.engine} runtime={args.runtime} "
          f"clients={n_clients} rounds={args.rounds} "
          f"in {time.time()-t0:.1f}s | best_acc={res.best_acc:.4f} "
          f"comm={res.comm_total_bytes/max(res.comm_fnu_bytes,1):.2%} of FNU"
          f"{extra}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--full-size", action="store_true",
                    help="full config (mesh scale); default smoke")
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--steps-per-round", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--rl", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--sim-clients", type=int, default=0,
                    help="simulate N federated clients (fl/ stack) instead of "
                         "the mesh trainer")
    ap.add_argument("--population", type=int, default=0,
                    help="stream N virtual clients from a seeded "
                         "SyntheticPopulation instead of materialising "
                         "--sim-clients shards up front; per-round host cost "
                         "is O(cohort), so N can be millions "
                         "(docs/POPULATION.md)")
    ap.add_argument("--cohort-size", type=int, default=0,
                    help="explicit clients per dispatch/round (0 = "
                         "--participation fraction of the fleet); the natural "
                         "knob under --population")
    ap.add_argument("--state-store-entries", type=int, default=0,
                    help="LRU cap on per-client MOON/EF state entries "
                         "(0 = unbounded, the legacy behavior)")
    ap.add_argument("--state-store-spill", default="",
                    help="directory to spill evicted per-client state to "
                         "(empty = evicted entries are dropped)")
    ap.add_argument("--engine", choices=["sequential", "vmap", "shard_map"],
                    default="sequential",
                    help="client engine for --sim-clients: per-client oracle "
                         "loop (default), batched vmap-over-clients, or "
                         "mesh-sharded shard_map (see --sim-devices)")
    ap.add_argument("--fused-adam", action="store_true",
                    help="run local steps through the Pallas masked-Adam "
                         "kernel (packed optimizer state; interpret mode "
                         "off-TPU — docs/KERNELS.md)")
    ap.add_argument("--sim-devices", type=int, default=0,
                    help="shard_map mesh size over the 'clients' axis "
                         "(0 = all visible devices; on CPU, N>1 also forces "
                         "N simulated host devices)")
    ap.add_argument("--runtime", choices=["sync", "async"], default="sync",
                    help="round execution model for --sim-clients: barrier "
                         "per round, or the event-driven async simulator "
                         "(docs/ASYNC.md)")
    ap.add_argument("--async-policy", choices=["fedbuff", "sync"],
                    default="fedbuff",
                    help="async aggregation policy: FedBuff goal-K buffer or "
                         "the per-cohort barrier oracle")
    ap.add_argument("--participation", type=float, default=1.0,
                    help="fraction of clients sampled per dispatch/round")
    ap.add_argument("--participation-sampling", choices=["blind", "biased"],
                    default="blind",
                    help="async cohort selection: rejection-sample the "
                         "arrival process blind (default), or weight "
                         "candidates by current availability and debias the "
                         "merge by inverse inclusion probability "
                         "(docs/ASYNC.md)")
    ap.add_argument("--buffer-k", type=int, default=0,
                    help="FedBuff merge goal K (0 = cohort size)")
    ap.add_argument("--staleness-exp", type=float, default=0.0,
                    help="polynomial staleness discount exponent a in "
                         "(1+staleness)^-a")
    ap.add_argument("--max-inflight", type=int, default=1,
                    help="cohorts concurrently in flight under --runtime "
                         "async: 1 = merge-driven dispatch, >1 trains that "
                         "many cohorts at once on disjoint device submeshes "
                         "(docs/ASYNC.md)")
    ap.add_argument("--controller", choices=["static", "adaptive"],
                    default="static",
                    help="server control loop under --runtime async "
                         "(docs/CONTROL.md): static config (default, no "
                         "controller object) or the adaptive bundle that "
                         "re-targets --max-inflight, --buffer-k, and the "
                         "layer-group schedule between merges")
    ap.add_argument("--controller-window", type=int, default=4,
                    help="merges per controller observation window")
    ap.add_argument("--controller-inflight-bounds", type=int, nargs=2,
                    default=[1, 4], metavar=("LO", "HI"),
                    help="adaptive in-flight cohort target bounds")
    ap.add_argument("--controller-buffer-bounds", type=int, nargs=2,
                    default=[1, 8], metavar=("LO", "HI"),
                    help="adaptive FedBuff goal-K bounds")
    ap.add_argument("--controller-mix-floor", type=float, default=0.5,
                    help="windowed discounted-mixing-coefficient floor the "
                         "staleness controller defends")
    ap.add_argument("--controller-max-repeats", type=int, default=2,
                    help="max consecutive layer-group repeats the progress "
                         "controller may schedule")
    ap.add_argument("--controller-participation-target", type=float,
                    default=0.0,
                    help="windowed effective-participation target the "
                         "participation controller holds by re-sizing the "
                         "cohort (0 = controller off; docs/CONTROL.md)")
    ap.add_argument("--controller-cohort-bounds", type=int, nargs=2,
                    default=[1, 64], metavar=("LO", "HI"),
                    help="adaptive cohort-size bounds for the participation "
                         "controller")
    ap.add_argument("--controller-plan-boost-max", type=int, default=0,
                    help="max extra layer groups the plan-assignment "
                         "controller may grant stalled-tier clients "
                         "(0 = controller off; needs --plan nested|random)")
    ap.add_argument("--plan", choices=["homogeneous", "nested", "random"],
                    default="homogeneous",
                    help="per-client layer plan for --sim-clients "
                         "(docs/HETEROGENEITY.md): every client trains the "
                         "scheduled group (default), FedPLT-style capacity "
                         "prefixes, or seeded random per-client group subsets")
    ap.add_argument("--capacity-tiers", type=float, nargs="*", default=[],
                    help="capacity fractions in (0, 1], one per tier, clients "
                         "assigned round-robin (e.g. 0.3 0.6 1.0); empty = "
                         "one full-capacity tier")
    ap.add_argument("--compression",
                    choices=["none", "int8", "onebit", "topk"],
                    default="none",
                    help="transmitted-subtree compression for --sim-clients "
                         "(docs/COMPRESSION.md): symmetric int8, 1-bit "
                         "sign+scale, or top-k sparsification, each with "
                         "per-client error feedback")
    ap.add_argument("--topk-fraction", type=float, default=0.01,
                    help="retained fraction per leaf under --compression topk")
    ap.add_argument("--no-error-feedback", action="store_true",
                    help="disable the per-client error-feedback residual "
                         "(compressed kinds only)")
    ap.add_argument("--compression-block-rows", type=int, default=0,
                    help="quantisation scale granularity: 0 = one scale per "
                         "leaf, B = one per B*128-element block (the masked-"
                         "Adam packed-row layout, docs/KERNELS.md)")
    ap.add_argument("--speed-spread", type=float, default=0.0,
                    help="per-client compute-speed heterogeneity (log-uniform "
                         "spread; 0 = homogeneous fleet)")
    ap.add_argument("--latency-jitter", type=float, default=0.0,
                    help="per-dispatch multiplicative latency noise")
    ap.add_argument("--dropout", type=float, default=0.0,
                    help="per-dispatch probability a client update is lost")
    ap.add_argument("--unavailable", type=float, default=0.0,
                    help="per-dispatch probability a sampled client is "
                         "offline (the i.i.d. arrival knob)")
    ap.add_argument("--trace", choices=["", "diurnal", "file"], default="",
                    help="trace-driven availability: deterministic per-client "
                         "periodic on/off windows (diurnal) or an on-disk "
                         "trace (file; see --trace-path)")
    ap.add_argument("--trace-period", type=float, default=16.0,
                    help="virtual seconds per on/off trace cycle")
    ap.add_argument("--duty-cycle", type=float, nargs=2, default=[1.0, 1.0],
                    metavar=("LO", "HI"),
                    help="per-client on-fraction range for --trace diurnal")
    ap.add_argument("--trace-path", default="",
                    help="availability trace file (.npz or JSON with "
                         "duty/phase arrays) for --trace file")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.sim_clients > 0 or args.population > 0:
        return run_simulation(args)

    cfg = get_config(args.arch, smoke=not args.full_size)
    key = jax.random.key(0)
    params = api.init(key, cfg)
    trainer = FedPartMeshTrainer(cfg, AdamConfig(lr=args.lr))
    n_groups = len(trainer.groups(params))
    sched = FedPartSchedule(num_groups=n_groups, warmup_rounds=args.warmup,
                            rounds_per_layer=args.rl, cycles=10_000)
    shape = InputShape("t", args.seq, args.batch, "train")

    total_tx, full_tx = 0, 0
    t0 = time.time()
    for spec in sched.rounds()[: args.rounds]:
        batches = [
            api.synth_batch(jax.random.fold_in(key, spec.index * 100 + i), cfg, shape)
            for i in range(args.steps_per_round)
        ]
        params, loss = trainer.run_round(params, spec, batches)
        tx = trainer.transmitted_params(params, spec)
        total_tx += tx
        full_tx += trainer.transmitted_params(params, RoundSpec(0, "warmup", -1, FULL_NETWORK))
        tag = "FNU " if spec.is_full else f"g={spec.group:3d}"
        print(f"[fedtrain] round {spec.index:3d} [{tag}] loss={loss:.4f} "
              f"tx={tx/1e6:.2f}M params")
    print(f"[fedtrain] {args.rounds} rounds in {time.time()-t0:.0f}s | "
          f"comm={total_tx/max(full_tx,1):.2%} of FNU")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
