"""Event-driven asynchronous federated runtime (virtual clock, host-parallel
dispatch).

``run_federated_async`` replaces the synchronous "everyone trains, then we
average" barrier with an explicit discrete-event simulation:

1. **Dispatch.**  While the server sits at version ``v``, it samples a cohort
   of idle, available clients (``sample_fraction`` of the fleet) and trains
   them *as one stacked batch* through the session's client engine
   (``fl.batched.make_engine`` — the vmap / shard_map engines are the
   execution backend, not a parallel implementation).  Every client in the
   cohort trains the layer group scheduled for version ``v``
   (``core.schedule.ScheduleIndex``) against the version-``v`` model.  Up to
   Under a per-client layer plan (``FLRunConfig.plan``, ``PlanAssigner``)
   each cohort member instead trains its *own* group subset for version
   ``v``, and its virtual duration/books use exactly its trained groups'
   bytes and FLOPs (docs/HETEROGENEITY.md).  Up to
   ``FLRunConfig.max_inflight_cohorts`` cohorts may be in flight at once:
   with the default ``1`` dispatch is merge-driven (the original async
   runtime); with more, freed capacity is topped up immediately, so several
   cohorts train concurrently — in virtual time *and* on the host, each on
   its own disjoint device submesh (``launch.mesh.SubmeshPool``,
   ``engine.cohort_pool``).  jax's async dispatch makes the launch
   non-blocking; results are only materialised when a cohort's first
   completion event pops.  When no submesh is free the launch queues, and
   the dispatch is still booked at its virtual time.
2. **Flight.**  Each client's completion is booked on a virtual timeline:
   local compute scaled by its persistent speed multiplier, up/down transfer
   of the transmitted subtree, latency jitter, dropout — all from the seeded
   availability model (``runtime.clients``) and the virtual-time cost model
   (``core.costs.VirtualTimeModel``).  Cohort spans are booked per submesh
   in a ``core.costs.SubmeshOccupancy`` ledger, so the timeline shows how
   much of the run genuinely overlapped.
3. **Merge.**  Delivered updates accumulate in the server buffer; the
   aggregation policy (``runtime.policy``) decides when to merge (barrier,
   or FedBuff's goal-K) and discounts stale updates polynomially.  A merge
   bumps the server version — which advances the FedPart schedule — and
   tops the in-flight cohorts back up, so slow clients from old versions
   keep training while the server moves on: that overlap is the async win.

Time-to-accuracy comes out as first-class output: every dispatch, delivery,
drop, merge, and eval is logged against the virtual clock in a
``core.telemetry.Timeline`` attached to the returned ``FLResult``, with
dispatch events carrying their submesh binding and span.

**Degenerate-config equivalence** (pinned in tests/test_async_runtime.py):
with full participation, a perfect fleet (default ``AvailabilityConfig``),
``buffer_k = 0`` (goal = cohort size), ``staleness_exponent = 0`` and
``max_inflight_cohorts = 1``, every cohort is a barrier round — the
client-selection RNG stream, per-client seeds, local training programs, and
aggregation arithmetic all coincide with the synchronous path, so params /
losses / cost books match ``run_federated`` to <=1e-5 under every engine.
The dispatch decisions depend only on virtual events, never on host speed or
device count, so a given config is reproducible on any machine; submeshes
only decide *where* a cohort's compiled program runs.

**Adaptive server control** (``FLRunConfig.controller``, ``runtime.control``,
docs/CONTROL.md): with ``controller="adaptive"`` a ``ServerController``
observes a merge-aligned ``Timeline.window`` between merges and may adjust
the in-flight cohort target, the FedBuff goal K, or pin the next version's
layer group (``ScheduleIndex.override_group``); every decision is recorded
as a ``"control"`` timeline event.  The default ``"static"`` builds no
controller at all — the hot path has no observation hook and reproduces the
pre-controller runtime bit-for-bit.

**Transmission compression** (``FLRunConfig.compression``, ``core.compress``,
docs/COMPRESSION.md): the local training programs are untouched
(``run_local_async`` always returns exact locals); quantisation happens
host-side at update *resolution*, against the dispatch-version model, with a
runtime-owned per-client error-feedback residual.  Buffered ``ClientUpdate``
subtrees therefore hold the *decompressed* server view — staleness
discounting and the policy merge operate on values — while each update's
``comm_bytes`` (and hence ``VirtualTimeModel.comm_seconds``) books the
*encoded* wire size from the ``core.compress`` byte ledger.

**Population scale** (``fl.population``, docs/POPULATION.md): ``clients_data``
may be a ``ClientPopulation`` instead of a materialised sequence, and every
per-dispatch cost here is O(cohort), never O(population): cohorts are drawn
by Floyd's algorithm over ``range(n) - busy`` (``IncrementalSampler``),
availability filtering runs over *sampled candidates only*
(``ClientAvailability.arrival_ok``), speed multipliers hash lazily from
``(seed, client_id)``, datasets materialise only for picked members, and the
MOON prev-models / EF residuals live in a bounded ``ClientStateStore``
(``FLRunConfig.state_store_entries`` / ``state_store_spill``).  With an empty
busy set the incremental sampler consumes the exact
``sample_without_replacement`` stream of the synchronous server, so the
degenerate-config equivalence holds unchanged.
"""

from __future__ import annotations

import heapq
import itertools
from collections import deque
from typing import TYPE_CHECKING, Any, Sequence

import jax
import numpy as np

from repro.core import aggregation, compress, masking
from repro.core.costs import comm_cost, comp_cost, plan_step_flops
from repro.core.partition import (group_param_bytes, group_param_counts,
                                  total_param_bytes)
from repro.core.schedule import PlanAssigner, RoundSpec, ScheduleIndex
from repro.core.telemetry import Timeline
from repro.fl.batched import make_engine, resolve_plan
from repro.fl.client import LocalTrainer
from repro.fl.population import (ClientPopulation, IncrementalSampler,
                                 as_population, client_round_seed,
                                 resolve_cohort_size,
                                 weighted_sample_without_replacement)
from repro.fl.runtime.clients import ClientAvailability
from repro.fl.runtime.control import make_controller
from repro.fl.runtime.policy import ClientUpdate, make_policy
from repro.fl.tasks import TaskAdapter
from repro.optim.adam import AdamConfig

if TYPE_CHECKING:  # imported lazily at runtime to avoid the fl.server cycle
    from repro.fl.server import FLResult, FLRunConfig

PyTree = Any


def _steps_per_round(n: int, batch_size: int, epochs: int) -> int:
    """Local step count of ``data.pipeline.batch_plan`` without building it."""
    bs = min(batch_size, n)
    per_epoch = (n - bs) // bs + 1 if n >= bs else 1
    return epochs * per_epoch


class _Cohort:
    """One dispatched cohort: virtual bookkeeping happens at dispatch; the
    host launch may be deferred (submesh exhaustion) and the results are
    materialised lazily, at the cohort's first popped member event."""

    __slots__ = ("picked", "datasets", "seeds", "prevs", "spec", "plan",
                 "params", "dispatched_t", "end_t", "updates", "submesh",
                 "stacked", "losses_dev", "launched", "resolved", "tl_event")

    def __init__(self, *, picked, datasets, seeds, prevs, spec, plan, params,
                 dispatched_t, end_t, updates, tl_event):
        self.picked = picked
        self.datasets = datasets
        self.seeds = seeds
        self.prevs = prevs
        self.spec = spec
        self.plan = plan              # per-client group bitmask (None = homogeneous)
        self.params = params          # version-``v`` tree captured at dispatch
        self.dispatched_t = dispatched_t
        self.end_t = end_t            # last member completion (virtual)
        self.updates = updates
        self.tl_event = tl_event
        self.submesh = None
        self.stacked = None
        self.losses_dev = None
        self.launched = False
        self.resolved = False


def run_federated_async(
    adapter: TaskAdapter,
    clients_data: Sequence | ClientPopulation,
    eval_set: tuple[np.ndarray, np.ndarray],
    rounds: Sequence[RoundSpec],
    run_cfg: "FLRunConfig",
    *,
    init_key=None,
    verbose: bool = False,
) -> "FLResult":
    from repro.fl.server import FLResult  # deferred: fl.server imports us

    if run_cfg.track_stepsizes:
        raise ValueError("track_stepsizes requires runtime='sync' with "
                         "engine='sequential'")
    if run_cfg.max_inflight_cohorts < 1:
        raise ValueError("max_inflight_cohorts must be >= 1, got "
                         f"{run_cfg.max_inflight_cohorts}")
    if not rounds:  # mirror the sync loop's graceful no-op
        key = init_key if init_key is not None else jax.random.key(run_cfg.seed)
        params = adapter.init(key)
        partition = adapter.partition(params)
        return FLResult(history=[], params=params, partition=partition,
                        tracker=None, comm_total_bytes=0, comp_total_flops=0.0,
                        comm_fnu_bytes=0, comp_fnu_flops=0.0,
                        timeline=Timeline())
    key = init_key if init_key is not None else jax.random.key(run_cfg.seed)
    params = adapter.init(key)
    partition = adapter.partition(params)
    trainer = LocalTrainer(
        adapter=adapter,
        partition=partition,
        algo=run_cfg.algo,
        adam=AdamConfig(lr=run_cfg.lr, eps=run_cfg.adam_eps),
        fused=run_cfg.fused_adam,
    )
    engine = make_engine(
        run_cfg.engine, trainer=trainer, partition=partition,
        algo=run_cfg.algo, sim_devices=run_cfg.sim_devices,
        donate=run_cfg.donate_buffers,
    )
    policy = make_policy(
        run_cfg.async_policy, partition,
        staleness_exponent=run_cfg.staleness_exponent,
        buffer_goal=run_cfg.buffer_k,
    )
    sched = ScheduleIndex.from_rounds(rounds)
    assigner = PlanAssigner(
        num_groups=partition.num_groups, kind=run_cfg.plan,
        capacity_tiers=tuple(run_cfg.capacity_tiers), seed=run_cfg.seed)
    population = as_population(clients_data)
    n_clients = population.num_clients
    avail = ClientAvailability(run_cfg.availability, n_clients)
    vtm = run_cfg.vtime
    timeline = Timeline()
    # Same selection stream as the synchronous server: one Floyd k-subset
    # sample per dispatch whenever the whole fleet is idle (busy empty).
    rng = np.random.default_rng(run_cfg.seed)
    eval_x, eval_y = eval_set
    eval_fn = jax.jit(adapter.evaluate)
    is_moon = run_cfg.algo.name == "moon"
    ccfg = compress.make_config(
        run_cfg.compression, topk_fraction=run_cfg.topk_fraction,
        error_feedback=run_cfg.error_feedback,
        block_rows=run_cfg.compression_block_rows)
    # Per-client cross-dispatch state — MOON prev-models ("moon") and EF
    # residuals ("ef") — lives in one bounded LRU store so host memory
    # tracks the active cohorts, not the population.
    state_store = run_cfg.make_state_store()

    # Cost tables: upstream bytes + per-step flops per scheduled group.  With
    # compression on, the upstream table prices the *encoded* wire format
    # (payload + scales + indices; BN stats stay dense-f32).
    if ccfg is None:
        group_bytes = group_param_bytes(params, partition)
        full_bytes = int(total_param_bytes(params))
    else:
        group_bytes = compress.group_encoded_bytes(params, partition, ccfg)
        full_bytes = int(group_bytes.sum())
    group_counts = group_param_counts(params, partition).astype(np.float64)
    _flops_cache: dict[int, float] = {}

    def _step_flops(spec: RoundSpec) -> float:
        if spec.group not in _flops_cache:
            _flops_cache[spec.group] = float(
                comp_cost(partition, [spec], group_fwd_flops=group_counts)
                .per_round_flops[0]
            )
        return _flops_cache[spec.group]

    _plan_flops_cache: dict[tuple[int, ...], float] = {}

    def _plan_flops(groups: tuple[int, ...]) -> float:
        if groups not in _plan_flops_cache:
            _plan_flops_cache[groups] = plan_step_flops(
                partition, groups, group_fwd_flops=group_counts)
        return _plan_flops_cache[groups]

    # -- host-parallel dispatch state ---------------------------------------
    max_inflight = run_cfg.max_inflight_cohorts
    # Controller-adjustable dispatch knobs (docs/CONTROL.md): the cohort
    # target the participation controller moves within
    # ``controller_cohort_bounds``, and the plan-prefix boost the plan
    # controller hands to ``PlanAssigner.assign``.  Static runs never touch
    # either, so the resolved values are the legacy constants bit-for-bit.
    cohort_target = resolve_cohort_size(n_clients, run_cfg.sample_fraction,
                                        run_cfg.cohort_size)
    plan_boost = 0
    num_tiers = len(assigner.capacity_tiers)
    # Server control loop (docs/CONTROL.md): None under the default
    # controller="static" — structurally absent, so the static hot path has
    # no observation hook at all.  Adaptive runs may grow the in-flight
    # target later, so the submesh pool is carved for the controller's upper
    # bound up front (dispatches beyond the current target never happen; the
    # pool only bounds where launched cohorts can land).
    controller = make_controller(run_cfg, num_clients=n_clients,
                                 num_groups=partition.num_groups,
                                 cohort_size=cohort_target)
    pool_cap = (max(max_inflight, run_cfg.controller_inflight_bounds[1])
                if controller is not None else max_inflight)
    pool = engine.cohort_pool(pool_cap)
    occupancy = vtm.occupancy()
    launch_queue: deque[_Cohort] = deque()
    # Results land on per-submesh devices; pull them back to the default
    # device at resolve whenever cohorts can live on >1 submesh, so the
    # policy's merge never mixes committed devices.
    xfer_back = pool is not None and pool.num_submeshes > 1
    home = jax.devices()[0] if xfer_back else None

    # -- event-loop state ---------------------------------------------------
    events: list[tuple] = []         # min-heap of (t, seq, kind, upd, cohort)
    seq = itertools.count()          # FIFO tiebreak for simultaneous events
    busy: set[int] = set()
    retry_pending = False            # a "retry" wait event is already booked
    retry_streak = 0                 # consecutive dispatches with no arrival
    buffer: list[ClientUpdate] = []
    history: list[dict] = []
    version = 0                      # server aggregations committed so far
    vclock = 0.0
    pending = 0                      # in-flight updates that WILL deliver
    inflight = 0                     # dispatched-but-unresolved cohorts
    last_cohort = 0
    total = len(rounds)

    def launch(cohort: _Cohort, submesh) -> None:
        """Hand the cohort's stacked local-training program to jax (async
        dispatch: returns before the results exist) and book its occupancy."""
        cohort.submesh = submesh
        cohort.stacked, cohort.losses_dev = engine.run_local_async(
            cohort.params, cohort.spec, cohort.datasets, seeds=cohort.seeds,
            epochs=run_cfg.local_epochs, batch_size=run_cfg.batch_size,
            prev_params=cohort.prevs, submesh=submesh, plan=cohort.plan,
        )
        cohort.launched = True
        idx = submesh.index if submesh is not None else -1
        cohort.tl_event["submesh"] = idx
        occupancy.book(idx, cohort.dispatched_t, cohort.end_t)

    def resolve(cohort: _Cohort) -> None:
        """Materialise the cohort's results into its member updates (blocks
        on the in-flight arrays), free its submesh, and start the next
        queued launch."""
        nonlocal inflight
        if cohort.resolved:
            return
        if not cohort.launched:  # queued past exhaustion: run unbound now
            launch(cohort, None)
        cohort.resolved = True
        inflight -= 1
        stacked = cohort.stacked
        losses = [float(x) for x in np.asarray(cohort.losses_dev)]
        if is_moon:
            moon_stacked = (jax.device_put(stacked, home) if xfer_back
                            else stacked)
            for i, ci in enumerate(cohort.picked):
                state_store.put("moon", int(ci),
                                jax.tree.map(lambda x: x[i], moon_stacked))
        spec = cohort.spec
        if cohort.plan is None:
            sub = stacked if spec.is_full else masking.select(
                stacked, partition, spec.group)
        else:
            # Heterogeneous cohort: pull the cohort's *union* of trained
            # groups off the mesh, then slice each member down to exactly
            # the groups its plan row trained.
            union = tuple(int(g)
                          for g in np.flatnonzero(cohort.plan.any(axis=0)))
            sub = (stacked if len(union) == partition.num_groups
                   else masking.select(stacked, partition, union))
        sub = aggregation.drop_local_stats(sub)
        if xfer_back:
            # Pull only the *transmitted* subtree back to the home device
            # (the paper's Eq. 5 saving applied to the simulator's own
            # traffic) so the merge never mixes committed devices.
            sub = jax.device_put(sub, home)
        subs = masking.unstack_tree(sub, len(cohort.picked))
        # Host-side transmission compression: quantise each member's subtree
        # against the dispatch-version model (stats already dropped), carrying
        # the per-client EF residual across dispatches.  The buffered subtree
        # is the *decompressed* server view; ``comm_bytes`` already booked the
        # encoded size at dispatch.
        g_views: dict = {}

        def _g_view(sel):
            if sel not in g_views:
                t = (cohort.params if sel is None
                     else masking.select(cohort.params, partition, sel))
                g_views[sel] = aggregation.drop_local_stats(t)
            return g_views[sel]

        for i, upd in enumerate(cohort.updates):
            upd_sub = (subs[i] if upd.groups is None else
                       masking.select(subs[i], partition, upd.groups))
            if ccfg is not None:
                sel = (upd.groups if upd.groups is not None
                       else (None if spec.is_full else spec.group))
                res_full = state_store.get("ef", upd.client_id)
                if res_full is None:
                    res_full = compress.init_residual(cohort.params)
                res_sub = aggregation.drop_local_stats(
                    res_full if sel is None
                    else masking.select(res_full, partition, sel))
                upd_sub, new_res = compress.transmit_tree(
                    _g_view(sel), upd_sub, res_sub, ccfg, partition=partition)
                state_store.put("ef", upd.client_id,
                                masking.tree_update(res_full, new_res))
            upd.subtree = upd_sub
            upd.loss = losses[i]
        # Drop the big references now, not at last-straggler pop: the params
        # snapshot, the in-flight outputs, and (MOON) the superseded
        # prev-model trees whose store slots were just overwritten.
        cohort.stacked = cohort.losses_dev = cohort.params = None
        cohort.prevs = None
        if cohort.submesh is not None:
            pool.release(cohort.submesh)
            while launch_queue and pool.free_count > 0:
                nxt = launch_queue.popleft()
                if not nxt.launched:
                    launch(nxt, pool.acquire())

    def book_retry(t: float, rejected: list[int]) -> None:
        """Every sampled candidate is unavailable at ``t``: book one
        deterministic virtual-clock wait/retry event instead of training
        anyone.  The wait is the earliest trace on-window among the rejected
        candidates when the trace rejected them, else the configured
        ``retry_wait`` backoff (an on-window candidate that merely failed
        its i.i.d. coin can pass on the very next attempt).  At most one
        retry event is in flight at a time."""
        nonlocal retry_pending, retry_streak
        if retry_pending:
            return
        retry_streak += 1
        if retry_streak > 1000:
            raise RuntimeError(
                "async runtime: 1000 consecutive dispatch attempts found no "
                "available client — the availability trace/knobs leave the "
                "fleet effectively unreachable")
        waits: list[float] = []
        if avail.cfg.trace:
            coin_failed = False
            for ci in rejected:
                w = avail.next_on_time(ci, t) - t
                if w > 0.0:
                    waits.append(w)
                else:
                    coin_failed = True
            if coin_failed or not waits:
                waits.append(avail.cfg.retry_wait)
        else:
            waits.append(avail.cfg.retry_wait)
        wait = min(waits)
        retry_pending = True
        timeline.record(t, "wait", until=t + wait, rejected=len(rejected))
        heapq.heappush(events, (t + wait, next(seq), "retry", None, None))

    def dispatch(t: float, fragment_ok: bool) -> int:
        """Sample a cohort at the current version, book each member's
        completion on the virtual timeline, and launch its stacked training
        program on a free submesh (queueing the launch when none is).

        ``fragment_ok`` mirrors the merge-driven regime's behaviour: the
        dispatch a merge (or stall) triggers takes whatever idle clients
        exist, while capacity top-ups demand a full cohort's worth — filling
        spare capacity with fragment cohorts would inflate total client work
        (and retrace per cohort width) instead of overlapping it."""
        nonlocal pending, last_cohort, inflight, retry_streak
        spec = sched.for_version(version)
        pool_size = n_clients - len(busy)
        if pool_size <= 0:
            return 0
        n_pick = cohort_target
        if pool_size < n_pick and not fragment_ok:
            return 0
        # O(cohort) selection at population scale: Floyd-sample candidates
        # from range(n) minus the busy set — the fleet is never enumerated.
        # Blind mode filters each candidate through its *own* arrival draw
        # and tops up until the cohort fills or the idle pool runs dry;
        # biased mode weights candidates by their *current* availability and
        # draws the cohort in one weighted pass (docs/ASYNC.md).
        k_target = min(n_pick, pool_size)
        sampler = IncrementalSampler(rng, n_clients, busy)
        picked: list[int] = []
        rejected: list[int] = []
        if run_cfg.participation_sampling == "biased":
            # Availability-biased selection: oversample a candidate pool,
            # weight by current availability (trace window x stationary
            # arrival rate), and take an Efraimidis–Spirakis weighted
            # k-subset — off-window candidates are never picked, and each
            # pick records its inclusion probability so the merge can
            # inverse-probability debias.
            pool_ids: list[int] = []
            pool_w: list[float] = []
            navail = 0
            while navail < k_target and sampler.remaining > 0:
                need = k_target - navail
                ask = (need if not avail.cfg.trace else
                       max(need, min(4 * k_target, sampler.remaining)))
                for ci in sampler.draw(ask):
                    w = avail.availability_weight(ci, t)
                    pool_ids.append(ci)
                    pool_w.append(w)
                    if w > 0.0:
                        navail += 1
            if navail == 0:
                book_retry(t, pool_ids)
                return 0
            picked = weighted_sample_without_replacement(
                rng, pool_ids, pool_w, min(k_target, navail))
        else:
            while len(picked) < k_target and sampler.remaining > 0:
                for ci in sampler.draw(k_target - len(picked)):
                    (picked if avail.arrival_ok(ci, t) else rejected).append(ci)
            if not picked:
                # Every candidate failed its arrival draw: wait, never train
                # provably-unavailable clients.
                book_retry(t, rejected)
                return 0
        retry_streak = 0
        k = len(picked)

        datasets = [population.dataset(ci) for ci in picked]
        seeds = [client_round_seed(run_cfg.seed, spec.index, int(ci))
                 for ci in picked]
        prevs = ([state_store.get("moon", int(ci)) for ci in picked]
                 if is_moon else None)
        # Per-client layer plan for this dispatch.  The raw plan (None only
        # under the homogeneous *kind*) decides the updates' trained group
        # sets, so the policy merge unbundles per (client, group) for every
        # plan-kind dispatch — even a cohort whose rows happen to equal the
        # round mask, which `resolve_plan` collapses to the legacy compiled
        # programs for *execution* only.  Otherwise a collapsed cohort's
        # whole-tree update sharing a buffer with plan updates would dodge
        # the per-group denominators (docs/HETEROGENEITY.md).
        plan_raw = assigner.assign(spec, picked, boost=plan_boost)
        plan = resolve_plan(plan_raw, spec, partition.num_groups)
        up_bytes = full_bytes if spec.is_full else int(group_bytes[spec.group])
        step_flops = _step_flops(spec)

        # Per-member draw order (jitter, then drop) matches the pre-host-
        # parallel runtime exactly, so seeded availability streams replay.
        biased = run_cfg.participation_sampling == "biased"
        members, end_t = [], t
        for i, ci in enumerate(picked):
            if plan_raw is None:
                groups_i, ub, sf = None, up_bytes, step_flops
            else:
                # Capacity-aware books: a client moves and computes exactly
                # its own trained groups' bytes/FLOPs.  (For a collapsed
                # cohort these equal the legacy per-round numbers exactly.)
                groups_i = tuple(int(g) for g in np.flatnonzero(plan_raw[i]))
                ub = (full_bytes if len(groups_i) == partition.num_groups
                      else int(group_bytes[list(groups_i)].sum()))
                sf = _plan_flops(groups_i)
            flops = sf * _steps_per_round(
                len(datasets[i]), run_cfg.batch_size, run_cfg.local_epochs)
            dur = vtm.round_seconds(
                flops, ub, speed=avail.speed(ci), jitter=avail.jitter())
            upd = ClientUpdate(
                client_id=int(ci), version=version, group=spec.group,
                subtree=None, weight=float(len(datasets[i])),
                loss=float("nan"), dispatched_t=t, completed_t=t + dur,
                comp_flops=flops, comm_bytes=ub, groups=groups_i,
                encoding=None if ccfg is None else ccfg.kind,
                inclusion_prob=avail.inclusion_prob(ci) if biased else 1.0,
            )
            members.append((upd, "drop" if avail.drops() else "complete"))
            end_t = max(end_t, t + dur)
        timeline.record(t, "dispatch", version=version, group=spec.group,
                        clients=[int(c) for c in picked], t_end=end_t)
        cohort = _Cohort(picked=picked, datasets=datasets, seeds=seeds,
                         prevs=prevs, spec=spec, plan=plan, params=params,
                         dispatched_t=t, end_t=end_t,
                         updates=[u for u, _ in members],
                         tl_event=timeline.events[-1])
        inflight += 1
        for upd, kind in members:
            if kind == "complete":
                pending += 1
            heapq.heappush(events,
                           (upd.completed_t, next(seq), kind, upd, cohort))
            busy.add(upd.client_id)
        submesh = pool.acquire() if pool is not None else None
        if pool is None or submesh is not None:
            launch(cohort, submesh)
        else:
            launch_queue.append(cohort)
        last_cohort = k
        return k

    def top_up(t: float, fragment_ok: bool = False) -> None:
        """Dispatch until the in-flight target is met (or nothing is
        dispatchable).  With ``max_inflight == 1`` this is exactly one
        attempt — the merge-driven dispatch of the original async runtime.
        Only the first attempt may take a fragment cohort (``fragment_ok``:
        merge- and stall-triggered dispatches), so spare capacity is filled
        with full cohorts or not at all."""
        first = fragment_ok
        while inflight < max_inflight:
            if dispatch(t, first) == 0:
                break
            first = False

    def flush() -> None:
        """Commit one server aggregation: merge the buffer, eval on the sync
        cadence, advance the schedule, let the controller adjust its knobs,
        top the in-flight cohorts back up."""
        nonlocal params, version, max_inflight, cohort_target, plan_boost
        spec = sched.for_version(version)
        params, info = policy.merge(params, buffer, version)
        buffer.clear()
        entry = {"round": spec.index, "phase": spec.phase, "group": spec.group,
                 "loss": info["loss"], "t": vclock, "merged": info["merged"],
                 "staleness_mean": info["staleness_mean"],
                 "staleness_max": info["staleness_max"]}
        timeline.record(vclock, "merge", version=version, group=spec.group, **{
            k: info[k] for k in
            ("loss", "merged", "staleness_mean", "staleness_max")})
        if spec.index % run_cfg.eval_every == 0 or spec.index == total - 1:
            acc = float(eval_fn(params, eval_x[: run_cfg.eval_batch],
                                eval_y[: run_cfg.eval_batch]))
            entry["acc"] = acc
            timeline.record(vclock, "eval", version=version, acc=acc)
        history.append(entry)
        if verbose:
            print(f"merge v{version:3d} [{spec.phase}:{spec.group:3d}] "
                  f"t={vclock:8.2f}s loss={entry['loss']:.4f} "
                  f"acc={entry.get('acc', float('nan')):.4f} "
                  f"stale(mean={entry['staleness_mean']:.2f},"
                  f"max={entry['staleness_max']})")
        version += 1
        if controller is not None and version < total:
            # Observe between merges, apply before the post-merge dispatch so
            # the new targets govern it.  Everything the controller saw is
            # virtual-event-only, so adaptive runs replay on any host.
            adj = controller.observe(timeline.window(run_cfg.controller_window))
            if adj:
                if adj.max_inflight is not None:
                    max_inflight = min(max(adj.max_inflight, 1), pool_cap)
                if adj.buffer_k is not None:
                    policy.buffer_goal = max(adj.buffer_k, 1)
                if (adj.group_override is not None
                        and 0 <= adj.group_override < partition.num_groups):
                    sched.override_group(version, adj.group_override)
                if adj.cohort_size is not None:
                    c_lo, c_hi = run_cfg.controller_cohort_bounds
                    cohort_target = min(max(int(adj.cohort_size), c_lo),
                                        c_hi, n_clients)
                if adj.plan_boost is not None:
                    plan_boost = min(max(int(adj.plan_boost), 0),
                                     run_cfg.controller_plan_boost_max)
                timeline.record(vclock, "control", version=version,
                                max_inflight=max_inflight,
                                buffer_k=policy.buffer_goal,
                                group_override=adj.group_override,
                                cohort_size=cohort_target,
                                plan_boost=plan_boost,
                                note=adj.note)
        if version < total:
            if max_inflight == 1:
                # Merge-driven regime: every merge dispatches, full stop —
                # even when an earlier cohort hasn't delivered its first
                # event yet (a straggler-triggered merge right after another
                # cohort's dispatch).  Gating that on the in-flight count
                # would silently diverge from the original async runtime.
                dispatch(vclock, True)
            else:
                top_up(vclock, fragment_ok=True)

    # -- main loop ----------------------------------------------------------
    top_up(0.0, fragment_ok=True)
    while version < total:
        if not events:
            # No one in flight: either merge the stragglers' leftovers or
            # re-dispatch (e.g. a fully-dropped cohort).
            if buffer and policy.should_merge(len(buffer), 0, last_cohort):
                flush()
                continue
            if dispatch(vclock, True) == 0 and not events:
                # (a failed dispatch may have booked a "retry" wait event —
                # that IS progress: the virtual clock advances to the next
                # arrival window instead of training unavailable clients)
                raise RuntimeError(
                    "async runtime stalled: no events in flight, nothing "
                    "dispatchable, and the buffer cannot merge")
            continue
        t, _, kind, upd, cohort = heapq.heappop(events)
        vclock = t
        if kind == "retry":
            # The booked wait elapsed: the server tries to fill its
            # capacity again, now that an arrival window may have opened.
            retry_pending = False
            if version < total:
                top_up(vclock, fragment_ok=True)
            continue
        busy.discard(upd.client_id)
        resolve(cohort)
        if kind == "complete":
            pending -= 1
            buffer.append(upd)
            timeline.record(t, "complete", client=upd.client_id,
                            staleness=upd.staleness(version),
                            comm_bytes=upd.comm_bytes,
                            comp_flops=upd.comp_flops,
                            inclusion_prob=upd.inclusion_prob,
                            tier=upd.client_id % num_tiers)
        else:
            timeline.record(t, "drop", client=upd.client_id,
                            comp_flops=upd.comp_flops)
        if buffer and policy.should_merge(len(buffer), pending, last_cohort):
            flush()
        elif max_inflight > 1 and version < total:
            top_up(vclock)

    if occupancy.spans:
        timeline.record(vclock, "occupancy", **occupancy.summary())

    # Cost books over the committed server rounds, as actually trained: with
    # no controller the effective specs ARE `rounds` (identical to the sync
    # ledger by construction); group overrides swap in the groups the
    # controller pinned.  The timeline holds the per-update async accounting
    # on top.
    effective = [sched.for_version(v) for v in range(total)]
    comm = comm_cost(params, partition, effective, compression=ccfg)
    comp = comp_cost(partition, effective, group_fwd_flops=group_counts)
    return FLResult(
        history=history,
        params=params,
        partition=partition,
        tracker=None,
        comm_total_bytes=comm.total_bytes,
        comp_total_flops=float(comp.total_flops),
        comm_fnu_bytes=comm.fnu_total_bytes,
        comp_fnu_flops=float(comp.fnu_total_flops),
        timeline=timeline,
    )
