"""Batched client-simulation engines: vmap- and shard_map-over-clients.

The sequential oracle (``SequentialEngine``, the original ``run_federated``
inner loop) dispatches O(clients x steps) jitted calls per round and syncs the
host on every step's loss.  The two batched engines replace that with a
handful of compiled dispatches per (phase, group), sharing one *pad-and-mask
local-round core* (``_BatchedEngineBase``):

* ``VmapEngine`` — the selected clients' batches are stacked along a leading
  client axis (``data.pipeline.stack_client_batches``, or gathered by index
  from a population's ``DeviceMirror`` already on the device) and the whole
  local round runs as one ``jax.vmap``-over-clients program with a
  ``lax.scan`` over steps inside, followed by one on-device stacked
  aggregation (``core.aggregation.*_stacked``).  Single device.
* ``ShardMapEngine`` — the stacked client axis is distributed over a 1-D
  ``jax.sharding.Mesh`` ("clients" axis, ``launch.mesh.make_client_mesh``)
  via ``shard_map``: each device vmaps the local round over its shard of
  clients, and aggregation is an on-mesh ``psum`` of weight-scaled updates —
  only the round's *transmitted* subtree (the trainable group on partial
  rounds, BN running moments always excluded) ever crosses devices, mirroring
  the paper's communication claim.  Clients are padded up to a multiple of
  the mesh size (zero-weight padding clients; see ``stack_client_batches``).

Ragged client datasets follow the pad-and-mask contract: clients are bucketed
by effective batch width ``min(batch_size, n)`` (one compiled program per
width) and padded step-wise inside a bucket; padded steps compute but their
parameter/optimizer updates and losses are discarded via ``step_valid``, so
the engines match the sequential oracle leaf-for-leaf (see
``tests/test_engine_equivalence.py`` and docs/ENGINES.md).

All engines expose ``trace_count`` (XLA traces built so far) — the quantity
``benchmarks/engine_bench.py`` reports next to wall-clock.

Heterogeneous cohorts (per-client layer plans, ``core.schedule.PlanAssigner``,
docs/HETEROGENEITY.md): every entry point takes ``plan=`` — a ``(clients, M)``
group bitmask.  ``resolve_plan`` collapses homogeneous plans to ``None`` so
the legacy single-group programs (and their numerics) are kept structurally;
a genuinely mixed cohort runs the *masked plan program* instead: the bitmask
becomes a stacked per-client batch input to one compiled FNU-shaped step
(Eq. 1's literal masked form — see ``_one_client_plan_fn``), the sequential
oracle trains each client's exact pruned group set, and aggregation averages
each layer group over only the clients that trained it
(``core.aggregation.aggregate_plan*``; the shard_map engine psums per-group
participant-weighted sums on-mesh).

Beyond ``run_round`` (train + aggregate, the synchronous contract), every
engine also exposes ``run_local_async`` — cohort training *without*
aggregation, returning the still-in-flight stacked locally-trained params
(``run_local`` is its blocking wrapper).  That is the async runtime's
execution backend (``repro.fl.runtime``): a dispatched cohort is one stacked
batch through the same compiled local-round core, and aggregation happens
later in the server policy, possibly against a newer global model.  For
host-parallel dispatch (``FLRunConfig.max_inflight_cohorts`` > 1),
``cohort_pool`` carves the engine's devices into disjoint submeshes
(``launch.mesh.SubmeshPool``) and ``run_local_async(submesh=...)`` binds a
cohort's program to one — width-1 device-following jit for the vmap engine,
an AbstractMesh-traced shard_map for the sharded engine — so equal-width
submeshes share a single trace and concurrent cohorts never contend for a
device (docs/ENGINES.md, docs/ASYNC.md).

Transmission compression (``core.compress``, docs/COMPRESSION.md): engines
built with ``compression=`` (a ``CompressionConfig``; ``None`` = off, the
byte-identical legacy paths) apply the quantize→dequantize transmission step
to every client's update at the transmission boundary — the sequential oracle
and the vmap engine right before aggregation, the shard_map engine *inside*
the device program before the weight-scale psum (so only compressed-value
subtrees ever cross the mesh).  Error-feedback residuals are per real client:
``run_round`` then requires ``client_ids=`` so residuals persist across
rounds regardless of cohort composition.  The async runtime compresses
host-side at update resolution instead (``repro.fl.runtime.engine``), so
``run_local_async`` always returns *uncompressed* locals.

With ``donate=True`` (default) the batched engines donate the global params
into the aggregation jit (in-place splice — ``run_round`` then *consumes* its
params argument; thread the returned tree) and the stacked MOON prev-model
tree into the local-round jit.  ``benchmarks/engine_bench.py`` times every
batched engine both ways and reports the delta.

Example (any engine is a drop-in swap at the config level)::

    from repro.fl import FLRunConfig, run_federated
    cfg = FLRunConfig(engine="vmap")                      # single device
    cfg = FLRunConfig(engine="shard_map", sim_devices=0)  # all devices
    run_federated(adapter, clients, eval_set, rounds, cfg)

or directly, one round at a time::

    engine = make_engine("shard_map", trainer=trainer,
                         partition=partition, algo=algo, sim_devices=2)
    new_params, losses, _ = engine.run_round(
        params, spec, datasets, seeds=seeds, weights=weights,
        epochs=1, batch_size=32)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import aggregation, compress, masking
from repro.core.compat import SHARD_MAP_NO_CHECK_KW as _SHARD_MAP_KW
from repro.core.compat import abstract_client_mesh
from repro.core.compat import shard_map as _shard_map
from repro.core.partition import Partition
from repro.core.schedule import FULL_NETWORK, RoundSpec, round_base_mask
from repro.core.telemetry import span
from repro.data.pipeline import ClientDataset, stack_client_batches
from repro.fl.algorithms import AlgoConfig
from repro.fl.client import LocalTrainer

PyTree = Any

ENGINES = ("sequential", "vmap", "shard_map")

CLIENT_AXIS = "clients"  # mesh axis name the shard_map engine reduces over

def resolve_plan(plan, spec: RoundSpec, num_groups: int):
    """Normalise a per-client layer plan (``core.schedule.PlanAssigner``).

    Returns ``None`` — keep the legacy single-group programs — when no plan
    was given *or* every row equals the round's homogeneous mask (all groups
    on FNU rounds, one-hot ``spec.group`` otherwise); the
    ``plan="homogeneous"`` == pre-plan behaviour guarantee is structural
    (same compiled programs, same arithmetic), not a numeric coincidence.
    Otherwise returns the validated ``(clients, M)`` bool array for the
    engines' plan paths (docs/HETEROGENEITY.md)."""
    if plan is None:
        return None
    p = np.asarray(plan, dtype=bool)
    if p.ndim != 2 or p.shape[1] != num_groups:
        raise ValueError(
            f"plan shape {p.shape} does not match {num_groups} layer groups")
    if not p.any(axis=1).all():
        raise ValueError("every client's plan must train at least one group")
    if (p == round_base_mask(spec, num_groups)[None, :]).all():
        return None
    return p


class _CompressionState:
    """Per-client error-feedback residual store shared by the engines.

    Residuals are keyed by the *real* client id (not the cohort position), so
    error feedback telescopes correctly across rounds with partial
    participation.  Entirely inert when ``self.compression is None`` — no
    state is allocated and no compression branch is ever taken.

    With a ``state_store`` (``fl.population.ClientStateStore``) attached the
    residuals live there instead of an unbounded dict — bounded LRU memory
    with optional disk spill, the population-scale contract
    (docs/POPULATION.md).  An evicted-and-spilled residual reloads
    value-exact; an evicted-and-dropped one restarts from zero (the caller
    opted into that by bounding the store without a spill dir)."""

    def _init_compression_state(self) -> None:
        self._residuals: dict[int, PyTree] = {}

    def _require_client_ids(self, client_ids, num: int) -> list[int] | None:
        if self.compression is None:
            return None
        if client_ids is None:
            raise ValueError(
                "compression needs client_ids= on run_round: error-feedback "
                "residuals persist per real client across rounds")
        ids = [int(c) for c in client_ids]
        if len(ids) != num:
            raise ValueError(f"{len(ids)} client_ids for {num} client datasets")
        return ids

    def _residual_for(self, cid: int, params: PyTree) -> PyTree:
        store = getattr(self, "state_store", None)
        res = (store.get("ef", cid) if store is not None
               else self._residuals.get(cid))
        return res if res is not None else compress.init_residual(params)

    def _set_residual(self, cid: int, tree: PyTree) -> None:
        store = getattr(self, "state_store", None)
        if store is not None:
            store.put("ef", cid, tree)
        else:
            self._residuals[cid] = tree


@dataclasses.dataclass
class SequentialEngine(_CompressionState):
    """Reference oracle: one client at a time, aggregation on host."""

    trainer: LocalTrainer
    partition: Partition
    algo: AlgoConfig
    compression: compress.CompressionConfig | None = None
    state_store: Any = None     # fl.population.ClientStateStore (EF residuals)
    name: str = "sequential"

    mirror_device = None        # batches come from the datasets, per client

    def __post_init__(self):
        self._init_compression_state()

    @property
    def trace_count(self) -> int:
        return self.trainer.trace_count

    def run_round(
        self,
        params: PyTree,
        spec: RoundSpec,
        datasets: Sequence[ClientDataset],
        *,
        seeds: Sequence[int],
        weights: Sequence[float],
        epochs: int,
        batch_size: int,
        prev_params: Sequence[PyTree | None] | None = None,
        tracker=None,
        plan=None,
        client_ids: Sequence[int] | None = None,
    ) -> tuple[PyTree, list[float], list[PyTree] | None]:
        plan = resolve_plan(plan, spec, self.partition.num_groups)
        ids = self._require_client_ids(client_ids, len(datasets))
        keep_locals = self.algo.name == "moon"
        uploads, losses, new_locals = [], [], ([] if keep_locals else None)
        for i, (ds, seed) in enumerate(zip(datasets, seeds)):
            groups_i = (tuple(int(g) for g in np.flatnonzero(plan[i]))
                        if plan is not None else None)
            local, loss = self.trainer.run_local_round(
                params,
                spec.group,
                ds,
                epochs=epochs,
                batch_size=batch_size,
                seed=seed,
                prev_params=prev_params[i] if prev_params is not None else None,
                step_tracker=tracker if i == 0 else None,
                groups=groups_i,
            )
            losses.append(loss)
            if keep_locals:
                new_locals.append(local)     # MOON keeps the TRUE local model
            send = local
            if self.compression is not None:
                # Transmission boundary: what travels (and is aggregated) is
                # the compressed view global + Q(update + residual).
                tx_groups = (groups_i if plan is not None
                             else None if spec.is_full else (spec.group,))
                res = self._residual_for(ids[i], params)
                send, new_res = compress.transmit_tree(
                    params, local, res, self.compression,
                    partition=self.partition, groups=tx_groups)
                self._set_residual(ids[i], new_res)
            if plan is not None:
                uploads.append(masking.select(send, self.partition, groups_i))
            elif spec.is_full:
                uploads.append(send)
            else:
                uploads.append(masking.select(send, self.partition, spec.group))
        if plan is not None:
            new_params = aggregation.aggregate_plan(
                params, uploads, self.partition, plan, weights)
        elif spec.is_full:
            new_params = aggregation.aggregate_full(params, uploads, weights)
        else:
            new_params = aggregation.aggregate_partial(params, uploads, weights)
        return new_params, losses, new_locals

    def run_local(
        self,
        params: PyTree,
        spec: RoundSpec,
        datasets: Sequence[ClientDataset],
        *,
        seeds: Sequence[int],
        epochs: int,
        batch_size: int,
        prev_params: Sequence[PyTree | None] | None = None,
        plan=None,
    ) -> tuple[PyTree, list[float]]:
        """Cohort training without aggregation (async runtime backend): the
        per-client oracle loop, locals stacked into the common client-axis
        layout the policies consume."""
        plan = resolve_plan(plan, spec, self.partition.num_groups)
        locals_, losses = [], []
        for i, (ds, seed) in enumerate(zip(datasets, seeds)):
            local, loss = self.trainer.run_local_round(
                params, spec.group, ds,
                epochs=epochs, batch_size=batch_size, seed=seed,
                prev_params=prev_params[i] if prev_params is not None else None,
                groups=(tuple(int(g) for g in np.flatnonzero(plan[i]))
                        if plan is not None else None),
            )
            locals_.append(local)
            losses.append(loss)
        return masking.stack_trees(locals_), losses

    def cohort_pool(self, max_inflight: int):
        """No device binding: the oracle trains eagerly on the default
        device (host-parallel dispatch still applies in *virtual* time)."""
        return None

    def run_local_async(
        self,
        params: PyTree,
        spec: RoundSpec,
        datasets: Sequence[ClientDataset],
        *,
        seeds: Sequence[int],
        epochs: int,
        batch_size: int,
        prev_params: Sequence[PyTree | None] | None = None,
        submesh=None,
        plan=None,
    ) -> tuple[PyTree, np.ndarray]:
        """Common cohort contract for the async runtime; the oracle has no
        deferred execution, so this is ``run_local`` with array losses."""
        if submesh is not None:
            raise ValueError("the sequential engine has no submesh binding")
        stacked, losses = self.run_local(
            params, spec, datasets, seeds=seeds, epochs=epochs,
            batch_size=batch_size, prev_params=prev_params, plan=plan)
        return stacked, np.asarray(losses, dtype=np.float32)


@dataclasses.dataclass
class _BatchedEngineBase(_CompressionState):
    """Shared pad-and-mask local-round core for the stacked engines.

    Owns the pieces both batched engines agree on:

    * ``_one_client_fn(group)`` — the scan-over-steps local round for a single
      client (padded steps masked via ``step_valid``), ready to be ``vmap``-ed
      over a client axis;
    * the bucketed batch plan (``_buckets``): one
      ``data.pipeline.stack_client_batches`` bucket per effective batch
      width (or its gather from a ``DeviceMirror``), with the MOON
      prev-model stacking and padding-client handling;
    * ``_gather_order`` — concatenating per-bucket per-client outputs back
      into the round's picked-client order.

    Subclasses implement ``_local_fn`` (how a bucket's stacked clients are
    executed: plain ``vmap`` vs ``shard_map``-over-mesh) and ``run_round``
    (how the buckets' results are aggregated).
    """

    trainer: LocalTrainer
    partition: Partition
    algo: AlgoConfig
    donate: bool = True
    compression: compress.CompressionConfig | None = None
    state_store: Any = None     # fl.population.ClientStateStore (EF residuals)

    # The device a population's ``DeviceMirror`` should live on for this
    # engine's ``run_round``; ``None`` keeps the host path.
    mirror_device = None

    def __post_init__(self):
        self.trace_count = 0
        self.stack_source: str | None = None   # "device" or "host", last round
        self._local_fns: dict[tuple[int, bool], Callable] = {}
        self._agg_fns: dict[Any, Callable] = {}
        self._cohort_fns: dict[tuple[int, bool], Callable] = {}
        self._kernel_rows: dict[int | None, dict[str, int]] = {}
        self._init_compression_state()

    # Donation sets (active when ``donate``).  Only buffers whose shapes can
    # actually alias an output are donated — donating the stacked
    # inputs/labels would just trigger XLA's "not usable" warning:
    #
    # * the *global params* into the aggregation/splice jit (arg 0): output
    #   tree is leaf-for-leaf shape-identical, so the splice updates in
    #   place instead of holding two full models live.  This makes
    #   ``run_round`` consume its params argument — callers thread the
    #   returned tree (``run_federated`` always did).
    # * the *stacked MOON prev-model* tree into the local-round jit (arg 4):
    #   it is rebuilt host-side every round and matches the stacked-locals
    #   output exactly, saving one whole per-client model copy per bucket.

    def _donate_prev(self, stacked_prev: bool) -> tuple[int, ...]:
        return (4,) if (self.donate and stacked_prev) else ()

    def _donate_params(self) -> tuple[int, ...]:
        return (0,) if self.donate else ()

    def _local_span_args(self, params: PyTree, group: int, plan) -> dict:
        """The local ``fl.dispatch`` span's args on the fused path: the
        packed rows the kernel streams per client-step (``kernel_rows``) and
        the whole model's (``model_rows``), from shapes alone, once per
        program.  A partial round streams its group's trained rows; FNU and
        plan rounds stream the whole model."""
        if not self.trainer.fused:
            return {}
        sel = None if plan is not None or group < 0 else group
        if sel not in self._kernel_rows:
            self._kernel_rows[sel] = {
                "kernel_rows": self.trainer.kernel_rows(params, sel),
                "model_rows": self.trainer.kernel_rows(params)}
        return self._kernel_rows[sel]

    # -- shared local-round core -------------------------------------------

    @staticmethod
    def _scan_local_steps(step_fn, carry0, opt0, global_params, inputs,
                          labels, step_valid, prev):
        """The shared pad-and-mask scan over (possibly padded) steps from
        the carry ``carry0`` (``LocalTrainer.init``): invalid steps compute
        but their carry/optimizer updates and losses are discarded.  Each
        step returns ``(loss, counters)``; the round returns ``(carry, (mean
        loss, counters summed over the valid steps))``."""

        def body(carry, xs):
            params, opt = carry
            x, y, valid = xs
            new_p, new_o, out = step_fn(params, opt, x, y, global_params, prev)
            keep = valid > 0
            params = jax.tree.map(
                lambda a, b: jnp.where(keep, a, b), new_p, params)
            opt = jax.tree.map(lambda a, b: jnp.where(keep, a, b), new_o, opt)
            return (params, opt), jax.tree.map(
                lambda v: jnp.where(keep, v.astype(jnp.float32), 0.0), out)

        (params, _), (step_losses, counts) = jax.lax.scan(
            body, (carry0, opt0), (inputs, labels, step_valid)
        )
        mean_loss = jnp.sum(step_losses) / jnp.maximum(jnp.sum(step_valid), 1.0)
        return params, (mean_loss, jnp.sum(counts, axis=0))

    def _one_client_fn(self, group: int) -> Callable:
        """Single-client local round: ``_scan_local_steps`` over the
        trainer's step for ``group`` (FULL_NETWORK for FNU).  On a partial
        round the scan carries only the group's trained leaves, every
        layer's BN running moments and Adam state for the trained leaves
        (``LocalTrainer.init``); the frozen leaves stay the unbatched global
        ones and are merged back once, after the scan.  The loss comes with
        the steps' counters, ``(loss, counters)``."""
        trainer = self.trainer
        sel = None if group < 0 else group
        step_fn = trainer.make_step(sel)

        def one_client(global_params, inputs, labels, step_valid, prev):
            carry, opt0 = trainer.init(global_params, sel)
            carry, out = self._scan_local_steps(
                step_fn, carry, opt0, global_params, inputs, labels,
                step_valid, prev)
            return masking.tree_update(global_params, carry), out

        return one_client

    def _one_client_plan_fn(self) -> Callable:
        """Single-client local round under a per-client layer plan.

        The FNU step runs every group's arithmetic and the client's ``(M,)``
        group bitmask masks the update inside the step, per leaf (tree Adam)
        or per packed block (the masked-Adam kernel) — the paper's Eq. 1
        literal masked form.  That is what lets ONE compiled program serve
        every plan row in a stacked cohort: the pruned-subtree form the
        homogeneous paths run would need one trace per distinct group set,
        defeating vmap/shard_map.  Frozen leaves keep the broadcast global
        values every step, so trainable leaves see exactly the frozen
        context the pruned form sees (equivalence to the sequential oracle
        pinned in tests/test_engine_equivalence.py).  Client-local
        statistics (BN running moments) always update, mirroring the pruned
        path's stats splice."""
        trainer = self.trainer
        plan_step = trainer.make_step(None)

        def one_client(global_params, inputs, labels, step_valid, prev, gmask):
            carry, opt0 = trainer.init(global_params)

            def step_fn(p, o, x, y, gp, pv):
                return plan_step(p, o, x, y, gp, pv, gmask)

            return self._scan_local_steps(
                step_fn, carry, opt0, global_params, inputs, labels,
                step_valid, prev)

        return one_client

    def _local_fn(self, group: int, stacked_prev: bool) -> Callable:
        raise NotImplementedError

    # -- shared host-side plumbing -----------------------------------------

    @staticmethod
    def _bucket_gmask(plan: np.ndarray, bucket) -> np.ndarray:
        """This bucket's rows of the cohort plan, as the stacked ``(clients,
        M)`` float32 bitmask batch input (padding clients all-zero: they
        train nothing and carry no aggregation weight)."""
        g = np.zeros((bucket.num_clients, plan.shape[1]), dtype=np.float32)
        g[: bucket.num_real] = plan[list(bucket.members)]
        return g

    def _stacked_residuals(self, ids: Sequence[int], members: Sequence[int],
                           num_clients: int, params: PyTree) -> PyTree:
        """Stack the given cohort members' error-feedback residuals along the
        client axis (all-zero residuals for padding clients)."""
        rs = [self._residual_for(ids[m], params) for m in members]
        rs += [compress.init_residual(params)] * (num_clients - len(rs))
        return masking.stack_trees(rs)

    def _store_residuals(self, ids: Sequence[int], members: Sequence[int],
                         new_res_stacked: PyTree) -> None:
        """Write back per-client residual slices (padding rows dropped)."""
        for i, m in enumerate(members):
            self._set_residual(ids[m], jax.tree.map(
                lambda x, i=i: x[i], new_res_stacked))

    def _guard_round(self, weights: Sequence[float], tracker) -> None:
        if tracker is not None:
            raise ValueError(
                "per-step step-size tracking needs engine='sequential' "
                f"(the {self.name} engine never materialises per-step params)"
            )
        # The aggregation normalisation runs inside jit where weights are
        # traced — guard the degenerate case here, mirroring tree_mean's
        # host-side check in the sequential engine.
        if float(sum(weights)) <= 0.0:
            raise ValueError(
                f"client weights must sum to a positive value, got {sum(weights)}"
            )

    def _buckets(
        self,
        params: PyTree,
        datasets: Sequence[ClientDataset],
        *,
        batch_size: int,
        epochs: int,
        seeds: Sequence[int],
        prev_params: Sequence[PyTree | None] | None,
        use_prev: bool,
        pad_clients_to: int = 1,
        mirror=None,
        client_ids: Sequence[int] | None = None,
    ):
        """Yield ``(bucket, prev_arg)`` per batch-width bucket.  ``prev_arg``
        is the MOON previous-local-model argument: stacked per client (padding
        clients fall back to the global model) when ``use_prev``, else the
        global params broadcast unbatched.

        With a ``mirror`` (``fl.population.DeviceMirror``) the buckets are
        gathered on its device from the cohort's ``client_ids``; otherwise
        they are stacked on the host.  ``stack_source`` records which."""
        with span("fl.stack", clients=len(datasets)) as stack:
            if mirror is None:
                buckets = stack_client_batches(
                    datasets, batch_size, epochs, seeds,
                    pad_clients_to=pad_clients_to)
                h2d = sum(b.inputs.nbytes + b.labels.nbytes for b in buckets)
                self.stack_source = "host"
            else:
                if client_ids is None or len(client_ids) != len(datasets):
                    raise ValueError("a device mirror needs one client id "
                                     "per client dataset")
                buckets, h2d = mirror.stack_client_batches(
                    client_ids, batch_size, epochs, seeds,
                    pad_clients_to=pad_clients_to)
                self.stack_source = "device"
            stack.set_metadata(buckets=len(buckets), source=self.stack_source,
                               h2d_bytes=h2d)
        for bucket in buckets:
            if use_prev:
                prevs = [
                    prev_params[p] if prev_params is not None and prev_params[p] is not None else params
                    for p in bucket.members
                ]
                prevs += [params] * (bucket.num_clients - bucket.num_real)
                prev_arg = masking.stack_trees(prevs)
            else:
                prev_arg = params
            yield bucket, prev_arg

    @staticmethod
    def _gather_order(parts: list[tuple[tuple[int, ...], PyTree]], num: int) -> PyTree:
        """Concatenate per-bucket per-client outputs (leading client axis,
        already sliced to real members) back into picked-client order."""
        if len(parts) == 1 and parts[0][0] == tuple(range(num)):
            return parts[0][1]
        order = np.concatenate([np.asarray(m) for m, _ in parts])
        inv = jnp.asarray(np.argsort(order))
        return jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0)[inv], *[t for _, t in parts]
        )

    # -- cohort execution (async runtime backend) ---------------------------

    def _cohort_pad_for(self, submesh) -> int:
        """Client-axis padding multiple for cohort dispatches (the bound
        submesh's width for the shard_map engine, 1 otherwise)."""
        return 1

    def _cohort_fn(self, group: int, stacked_prev: bool, submesh=None) -> Callable:
        """Local-round program *without* aggregation: returns the stacked
        locally-trained params + per-client losses.  The async runtime's
        policies aggregate later, possibly against a newer global model.
        ``submesh`` binds the program to an explicit device set (host-parallel
        dispatch); ``None`` keeps the engine's default placement."""
        raise NotImplementedError

    def _plan_cohort_fn(self, stacked_prev: bool, submesh=None) -> Callable:
        """``_cohort_fn`` for heterogeneous cohorts: same contract with the
        stacked per-client group bitmask as a sixth batch input."""
        raise NotImplementedError

    def _place_cohort_args(self, args: tuple, submesh, *,
                           stacked_prev: bool) -> tuple:
        """Commit one bucket's ``(params, inputs, labels, step_valid, prev)``
        onto ``submesh``'s devices (no-op without a submesh)."""
        return args

    def cohort_pool(self, max_inflight: int):
        """A ``launch.mesh.SubmeshPool`` carving this engine's devices into
        up to ``max_inflight`` disjoint submeshes, or ``None`` when cohorts
        should keep the engine's default placement (``max_inflight == 1`` —
        the PR 3 regime — or an engine with no device binding)."""
        return None

    def run_local_async(
        self,
        params: PyTree,
        spec: RoundSpec,
        datasets: Sequence[ClientDataset],
        *,
        seeds: Sequence[int],
        epochs: int,
        batch_size: int,
        prev_params: Sequence[PyTree | None] | None = None,
        submesh=None,
        plan=None,
    ) -> tuple[PyTree, jax.Array]:
        """Train one *cohort* (clients dispatched together against the same
        global model) without syncing the host: returns
        ``(stacked_locals, losses_dev)`` where both are still-in-flight jax
        arrays — jax's async dispatch returns immediately, so the caller can
        launch further cohorts on other submeshes before materialising any
        results.  ``submesh`` (from ``cohort_pool``) commits the cohort's
        inputs to a disjoint device set; equal-width submeshes share one
        trace (the vmap engine's programs are device-agnostic, the shard_map
        engine traces over an AbstractMesh when this jax supports it).
        ``plan`` (a per-client group bitmask) swaps the single-group program
        for the masked plan program; a homogeneous plan collapses to the
        legacy path (``resolve_plan``)."""
        group = FULL_NETWORK if spec.is_full else spec.group
        plan = resolve_plan(plan, spec, self.partition.num_groups)
        use_prev = self.algo.name == "moon"
        num = len(datasets)

        parts: list[tuple[tuple[int, ...], tuple[PyTree, jax.Array]]] = []
        for bucket, prev_arg in self._buckets(
            params, datasets, batch_size=batch_size, epochs=epochs, seeds=seeds,
            prev_params=prev_params, use_prev=use_prev,
            pad_clients_to=self._cohort_pad_for(submesh),
        ):
            if plan is None:
                fn = self._cohort_fn(group, stacked_prev=use_prev,
                                     submesh=submesh)
                args = (params, bucket.inputs, bucket.labels,
                        bucket.step_valid, prev_arg)
            else:
                fn = self._plan_cohort_fn(stacked_prev=use_prev,
                                          submesh=submesh)
                args = (params, bucket.inputs, bucket.labels,
                        bucket.step_valid, prev_arg,
                        self._bucket_gmask(plan, bucket))
            with span("fl.dispatch", program="local",
                      **self._local_span_args(params, group, plan)):
                args = self._place_cohort_args(args, submesh,
                                               stacked_prev=use_prev)
                locals_stacked, (bucket_losses, _) = fn(*args)
            n = bucket.num_real
            parts.append((bucket.members, (
                jax.tree.map(lambda x: x[:n], locals_stacked), bucket_losses[:n],
            )))

        return self._gather_order(parts, num)

    def run_local(
        self,
        params: PyTree,
        spec: RoundSpec,
        datasets: Sequence[ClientDataset],
        *,
        seeds: Sequence[int],
        epochs: int,
        batch_size: int,
        prev_params: Sequence[PyTree | None] | None = None,
        plan=None,
    ) -> tuple[PyTree, list[float]]:
        """Blocking ``run_local_async``: same cohort contract —
        ``stacked_locals`` carries a leading client axis in ``datasets``
        order (padding clients sliced off) — with the losses materialised as
        floats."""
        stacked, losses_dev = self.run_local_async(
            params, spec, datasets, seeds=seeds, epochs=epochs,
            batch_size=batch_size, prev_params=prev_params, plan=plan)
        with span("fl.wait", what="losses"):
            return stacked, [float(x) for x in np.asarray(losses_dev)]


@dataclasses.dataclass
class VmapEngine(_BatchedEngineBase):
    """Batched engine: whole round = vmapped local training + on-device agg."""

    name: str = "vmap"

    @property
    def mirror_device(self):
        """``run_round`` gathers from a mirror on JAX's default device, where
        its programs run."""
        return jax.devices()[0]

    # -- compiled-program builders ----------------------------------------

    def _local_fn(self, group: int, stacked_prev: bool) -> Callable:
        """Jitted vmap-over-clients local round for ``group`` (FULL_NETWORK
        for FNU).  Cached per (group, prev-layout); batch/step widths retrace
        via jit's shape cache.  Returns the stacked locals and ``(losses,
        counters)`` (``_one_client_fn``)."""
        key = (group, stacked_prev)
        if key in self._local_fns:
            return self._local_fns[key]

        one_client = self._one_client_fn(group)
        prev_axis = 0 if stacked_prev else None

        def local_round(global_params, inputs, labels, step_valid, prev):
            self.trace_count += 1  # trace-time side effect: compiled replays skip it
            return jax.vmap(one_client, in_axes=(None, 0, 0, 0, prev_axis))(
                global_params, inputs, labels, step_valid, prev
            )

        self._local_fns[key] = jax.jit(
            local_round, donate_argnums=self._donate_prev(stacked_prev))
        return self._local_fns[key]

    def _plan_local_fn(self, stacked_prev: bool) -> Callable:
        """Jitted vmap-over-clients *plan* round: one program serves every
        per-client group bitmask — the mask is a stacked batch input, not a
        static constant, so heterogeneous cohorts never retrace."""
        key = ("plan", stacked_prev)
        if key in self._local_fns:
            return self._local_fns[key]

        one_client = self._one_client_plan_fn()
        prev_axis = 0 if stacked_prev else None

        def local_round(global_params, inputs, labels, step_valid, prev, gmask):
            self.trace_count += 1
            return jax.vmap(one_client, in_axes=(None, 0, 0, 0, prev_axis, 0))(
                global_params, inputs, labels, step_valid, prev, gmask
            )

        self._local_fns[key] = jax.jit(
            local_round, donate_argnums=self._donate_prev(stacked_prev))
        return self._local_fns[key]

    def _cohort_fn(self, group: int, stacked_prev: bool, submesh=None) -> Callable:
        # The vmap local round already returns (stacked locals, losses) —
        # sync and async dispatches share one compiled program per group, and
        # because jit follows its committed inputs, every width-1 submesh
        # shares this single trace too (one executable per device, one trace).
        return self._local_fn(group, stacked_prev)

    def _plan_cohort_fn(self, stacked_prev: bool, submesh=None) -> Callable:
        # Same device-following story as _cohort_fn, one program for every
        # plan row and every width-1 submesh.
        return self._plan_local_fn(stacked_prev)

    def _place_cohort_args(self, args: tuple, submesh, *,
                           stacked_prev: bool) -> tuple:
        if submesh is None:
            return args
        dev = submesh.devices[0]
        return tuple(jax.device_put(a, dev) for a in args)

    def cohort_pool(self, max_inflight: int):
        """Width-1 submeshes (this engine's programs are single-device):
        cohort ``i`` runs whole on visible device ``i``."""
        if max_inflight <= 1:
            return None
        from repro.launch.mesh import SubmeshPool

        num = min(max_inflight, len(jax.devices()))
        return SubmeshPool(num, devices=num, width=1)

    def _agg_fn(self, group: int) -> Callable:
        if group in self._agg_fns:
            return self._agg_fns[group]
        partition = self.partition

        def agg(global_params, stacked, weights):
            self.trace_count += 1
            if group < 0:
                return aggregation.aggregate_full_stacked(global_params, stacked, weights)
            return aggregation.aggregate_partial_stacked(
                global_params, stacked, partition, group, weights
            )

        # Donating the global params makes the splice an in-place update —
        # callers must treat run_round as consuming its params argument.
        self._agg_fns[group] = jax.jit(agg, donate_argnums=self._donate_params())
        return self._agg_fns[group]

    def _plan_agg_fn(self) -> Callable:
        """On-device per-group participant-weighted aggregation: the plan
        bitmask and raw weights are traced inputs, so one program serves
        every heterogeneous cohort of a given size."""
        if "plan" in self._agg_fns:
            return self._agg_fns["plan"]
        partition = self.partition

        def agg(global_params, stacked, plan_f, weights):
            self.trace_count += 1
            return aggregation.aggregate_plan_stacked(
                global_params, stacked, partition, plan_f, weights)

        self._agg_fns["plan"] = jax.jit(agg, donate_argnums=self._donate_params())
        return self._agg_fns["plan"]

    def _tx_fn(self, group: int) -> Callable:
        """Jitted vmapped transmission-compression step: the cohort's stacked
        true locals + per-client residuals -> (compressed server view
        ``global + Q(update + residual)``, new residuals).  Runs between the
        local round and the stacked aggregation — the vmap engine's
        transmission boundary."""
        key = ("tx", group)
        if key in self._agg_fns:
            return self._agg_fns[key]
        partition, cfg = self.partition, self.compression
        sel = None if group < 0 else (group,)

        def tx(global_params, stacked, res):
            self.trace_count += 1
            return jax.vmap(
                lambda l, r: compress.transmit_tree(
                    global_params, l, r, cfg, partition=partition, groups=sel)
            )(stacked, res)

        self._agg_fns[key] = jax.jit(tx)
        return self._agg_fns[key]

    def _plan_tx_fn(self) -> Callable:
        """``_tx_fn`` for heterogeneous cohorts: the per-client group bitmask
        rides the stacked axis, so one program serves every plan."""
        key = ("tx", "plan")
        if key in self._agg_fns:
            return self._agg_fns[key]
        partition, cfg = self.partition, self.compression

        def tx(global_params, stacked, res, plan_f):
            self.trace_count += 1
            return jax.vmap(
                lambda l, r, m: compress.transmit_tree_plan(
                    global_params, l, r, m, cfg, partition=partition)
            )(stacked, res, plan_f)

        self._agg_fns[key] = jax.jit(tx)
        return self._agg_fns[key]

    # -- round execution ---------------------------------------------------

    def run_round(
        self,
        params: PyTree,
        spec: RoundSpec,
        datasets: Sequence[ClientDataset],
        *,
        seeds: Sequence[int],
        weights: Sequence[float],
        epochs: int,
        batch_size: int,
        prev_params: Sequence[PyTree | None] | None = None,
        tracker=None,
        plan=None,
        client_ids: Sequence[int] | None = None,
        mirror=None,
    ) -> tuple[PyTree, list[float], list[PyTree] | None]:
        """One synchronous round.  With a ``mirror`` (the population's
        ``DeviceMirror``) the cohort's batches are gathered on the device by
        ``client_ids`` instead of stacked on the host; the local-round
        program gets the same values, shapes and dtypes either way."""
        self._guard_round(weights, tracker)
        plan = resolve_plan(plan, spec, self.partition.num_groups)
        ids = self._require_client_ids(client_ids, len(datasets))
        group = FULL_NETWORK if spec.is_full else spec.group
        use_prev = self.algo.name == "moon"
        num = len(datasets)

        parts: list[tuple[tuple[int, ...], tuple[PyTree, jax.Array]]] = []
        for bucket, prev_arg in self._buckets(
            params, datasets, batch_size=batch_size, epochs=epochs, seeds=seeds,
            prev_params=prev_params, use_prev=use_prev, mirror=mirror,
            client_ids=client_ids,
        ):
            with span("fl.dispatch", program="local",
                      **self._local_span_args(params, group, plan)):
                if plan is None:
                    fn = self._local_fn(group, stacked_prev=use_prev)
                    locals_stacked, bucket_losses = fn(
                        params, bucket.inputs, bucket.labels,
                        bucket.step_valid, prev_arg)
                else:
                    fn = self._plan_local_fn(stacked_prev=use_prev)
                    locals_stacked, bucket_losses = fn(
                        params, bucket.inputs, bucket.labels,
                        bucket.step_valid, prev_arg,
                        self._bucket_gmask(plan, bucket))
            parts.append((bucket.members, (locals_stacked, bucket_losses)))

        stacked, (losses_dev, counts_dev) = self._gather_order(parts, num)
        agg_in = stacked                 # MOON keeps the TRUE locals below
        if self.compression is not None:
            res = self._stacked_residuals(ids, range(num), num, params)
            with span("fl.dispatch", program="tx"):
                if plan is None:
                    agg_in, new_res = self._tx_fn(group)(params, stacked, res)
                else:
                    agg_in, new_res = self._plan_tx_fn()(
                        params, stacked, res, jnp.asarray(plan, jnp.float32))
            self._store_residuals(ids, range(num), new_res)
        with span("fl.dispatch", program="agg"):
            if plan is None:
                new_params = self._agg_fn(group)(
                    params, agg_in, jnp.asarray(weights, dtype=jnp.float32)
                )
            else:
                new_params = self._plan_agg_fn()(
                    params, agg_in, jnp.asarray(plan, dtype=jnp.float32),
                    jnp.asarray(weights, dtype=jnp.float32)
                )
        counter = self.trainer.adapter.counter
        with span("fl.wait", what="losses") as wait:
            losses, counts = jax.device_get((losses_dev, counts_dev))
            if counter:
                wait.set_metadata(**{counter: " ".join(
                    f"{v:.0f}" for v in np.sum(counts, axis=0))})
            losses = [float(x) for x in losses]
        new_locals = masking.unstack_tree(stacked, num) if use_prev else None
        return new_params, losses, new_locals


@dataclasses.dataclass
class ShardMapEngine(_BatchedEngineBase):
    """Multi-device engine: client axis sharded over a 1-D mesh.

    Each bucket's stacked clients are padded to a multiple of the mesh size
    and distributed over the ``"clients"`` axis; every device runs the shared
    vmapped local-round core for its shard, then the round's transmitted
    subtree — the trainable group's weight-scaled update, BN running moments
    dropped — is ``psum``-reduced across the mesh.  Frozen groups are
    replicated with the broadcast global model and never cross devices, so a
    partial round's inter-device traffic shrinks exactly like the paper's
    client<->server communication (Eq. 5).

    ``devices=0`` meshes every visible device.  MOON is the exception to the
    only-the-update-travels rule: its per-client local models leave the mesh
    sharded, but ``run_round`` then reorders and unstacks them into the
    host-side per-client store ``run_federated`` keeps, which does gather
    them each round (the cost of MOON's contrastive term, not of this
    engine).
    """

    name: str = "shard_map"
    devices: int = 0

    def __post_init__(self):
        super().__post_init__()
        from repro.launch.mesh import make_client_mesh

        self.mesh = make_client_mesh(self.devices)

    @property
    def num_devices(self) -> int:
        return self.mesh.shape[CLIENT_AXIS]

    # -- compiled-program builders ----------------------------------------

    def _local_fn(self, group: int, stacked_prev: bool) -> Callable:
        """Jitted shard_map'd (local round + on-mesh weighted reduction) for
        ``group``.  Each device vmaps its client shard; the weight-scaled
        trainable-subtree sum is psum'd so the result is replicated."""
        key = (group, stacked_prev)
        if key in self._local_fns:
            return self._local_fns[key]

        one_client = self._one_client_fn(group)
        partition = self.partition
        prev_axis = 0 if stacked_prev else None
        cfg = self.compression

        if cfg is not None:
            # Compressed transmission boundary: each device quantizes its
            # clients' updates (with per-client error-feedback residuals
            # riding the client axis) BEFORE the weight-scale psum, so only
            # compressed-value subtrees ever cross the mesh.
            sel = None if group < 0 else (group,)

            def device_round(global_params, inputs, labels, step_valid, prev,
                             w_norm, res):
                self.trace_count += 1
                locals_stacked, losses = jax.vmap(
                    one_client, in_axes=(None, 0, 0, 0, prev_axis)
                )(global_params, inputs, labels, step_valid, prev)
                tx_stacked, new_res = jax.vmap(
                    lambda l, r: compress.transmit_tree(
                        global_params, l, r, cfg, partition=partition,
                        groups=sel)
                )(locals_stacked, res)
                sub = (
                    tx_stacked if group < 0
                    else masking.select(tx_stacked, partition, group)
                )
                sub = aggregation.drop_local_stats(sub)
                update = jax.tree.map(
                    lambda x: jnp.tensordot(w_norm, x.astype(jnp.float32),
                                            axes=1), sub
                )
                update = jax.lax.psum(update, CLIENT_AXIS)
                if stacked_prev:
                    return update, losses, locals_stacked, new_res
                return update, losses, new_res

            c = P(CLIENT_AXIS)
            in_specs = (P(), c, c, c, c if stacked_prev else P(), c, c)
            out_specs = ((P(), c, c, c) if stacked_prev else (P(), c, c))
            self._local_fns[key] = jax.jit(
                _shard_map(
                    device_round, mesh=self.mesh, in_specs=in_specs,
                    out_specs=out_specs, **_SHARD_MAP_KW,
                ),
                donate_argnums=self._donate_prev(stacked_prev),
            )
            return self._local_fns[key]

        def device_round(global_params, inputs, labels, step_valid, prev, w_norm):
            self.trace_count += 1
            locals_stacked, losses = jax.vmap(
                one_client, in_axes=(None, 0, 0, 0, prev_axis)
            )(global_params, inputs, labels, step_valid, prev)
            sub = (
                locals_stacked if group < 0
                else masking.select(locals_stacked, partition, group)
            )
            sub = aggregation.drop_local_stats(sub)
            update = jax.tree.map(
                lambda x: jnp.tensordot(w_norm, x.astype(jnp.float32), axes=1), sub
            )
            update = jax.lax.psum(update, CLIENT_AXIS)
            if stacked_prev:
                return update, losses, locals_stacked
            return update, losses

        c = P(CLIENT_AXIS)
        in_specs = (P(), c, c, c, c if stacked_prev else P(), c)
        out_specs = (P(), c, c) if stacked_prev else (P(), c)
        self._local_fns[key] = jax.jit(
            _shard_map(
                device_round, mesh=self.mesh, in_specs=in_specs,
                out_specs=out_specs, **_SHARD_MAP_KW,
            ),
            donate_argnums=self._donate_prev(stacked_prev),
        )
        return self._local_fns[key]

    def _plan_local_fn(self, stacked_prev: bool) -> Callable:
        """Jitted shard_map'd plan round: each device vmaps the masked plan
        step over its client shard, then per-leaf plan-weighted sums are
        ``psum``-reduced across the mesh.  ``eff_w`` arrives host-normalised
        per group over the *whole cohort* (each group's own participant
        denominator, zero rows for padding clients), so summing the psum'd
        buckets yields each group's participant-weighted average directly —
        per-group weight sums on-mesh, exactly like the homogeneous path's
        single-group reduction."""
        key = ("plan", stacked_prev)
        if key in self._local_fns:
            return self._local_fns[key]

        one_client = self._one_client_plan_fn()
        partition = self.partition
        prev_axis = 0 if stacked_prev else None
        cfg = self.compression

        if cfg is not None:
            # Compressed plan boundary: per-client traced bitmask decides
            # which leaves consume error feedback and travel; the per-leaf
            # plan-weighted psum epilogue follows.
            def device_round(global_params, inputs, labels, step_valid, prev,
                             gmask, eff_w, res):
                self.trace_count += 1
                locals_stacked, losses = jax.vmap(
                    one_client, in_axes=(None, 0, 0, 0, prev_axis, 0)
                )(global_params, inputs, labels, step_valid, prev, gmask)
                tx_stacked, new_res = jax.vmap(
                    lambda l, r, m: compress.transmit_tree_plan(
                        global_params, l, r, m, cfg, partition=partition)
                )(locals_stacked, res, gmask)
                sub = aggregation.drop_local_stats(tx_stacked)

                def _wsum(path, x):
                    g = partition.group_of(
                        "/".join(masking._entry_str(e) for e in path))
                    return jnp.tensordot(eff_w[:, g], x.astype(jnp.float32),
                                         axes=1)

                update = jax.tree_util.tree_map_with_path(_wsum, sub)
                update = jax.lax.psum(update, CLIENT_AXIS)
                if stacked_prev:
                    return update, losses, locals_stacked, new_res
                return update, losses, new_res

            c = P(CLIENT_AXIS)
            in_specs = (P(), c, c, c, c if stacked_prev else P(), c, c, c)
            out_specs = ((P(), c, c, c) if stacked_prev else (P(), c, c))
            self._local_fns[key] = jax.jit(
                _shard_map(
                    device_round, mesh=self.mesh, in_specs=in_specs,
                    out_specs=out_specs, **_SHARD_MAP_KW,
                ),
                donate_argnums=self._donate_prev(stacked_prev),
            )
            return self._local_fns[key]

        def device_round(global_params, inputs, labels, step_valid, prev,
                         gmask, eff_w):
            self.trace_count += 1
            locals_stacked, losses = jax.vmap(
                one_client, in_axes=(None, 0, 0, 0, prev_axis, 0)
            )(global_params, inputs, labels, step_valid, prev, gmask)
            sub = aggregation.drop_local_stats(locals_stacked)

            def _wsum(path, x):
                g = partition.group_of(
                    "/".join(masking._entry_str(e) for e in path))
                return jnp.tensordot(eff_w[:, g], x.astype(jnp.float32), axes=1)

            update = jax.tree_util.tree_map_with_path(_wsum, sub)
            update = jax.lax.psum(update, CLIENT_AXIS)
            if stacked_prev:
                return update, losses, locals_stacked
            return update, losses

        c = P(CLIENT_AXIS)
        in_specs = (P(), c, c, c, c if stacked_prev else P(), c, c)
        out_specs = (P(), c, c) if stacked_prev else (P(), c)
        self._local_fns[key] = jax.jit(
            _shard_map(
                device_round, mesh=self.mesh, in_specs=in_specs,
                out_specs=out_specs, **_SHARD_MAP_KW,
            ),
            donate_argnums=self._donate_prev(stacked_prev),
        )
        return self._local_fns[key]

    def _cohort_pad_for(self, submesh) -> int:
        return submesh.width if submesh is not None else self.num_devices

    def _cohort_fn(self, group: int, stacked_prev: bool, submesh=None) -> Callable:
        """Plain (no-psum) shard_map'd local round for async cohorts: each
        device vmaps its client shard and the stacked locals leave the mesh
        sharded — aggregation happens later, in the server policy, possibly
        against a newer global model, so it cannot be fused on-mesh here.

        Without a submesh the program binds the engine's full client mesh
        (the synchronous / PR 3 placement).  With one, the trace is built
        over an *AbstractMesh* of the submesh's width and cached per width —
        the concrete devices arrive through the inputs' ``NamedSharding``
        (``_place_cohort_args``), so every equal-width submesh replays the
        same trace."""
        if submesh is None:
            key, mesh = (group, stacked_prev), self.mesh
        else:
            key = (group, stacked_prev, submesh.width)
            mesh = abstract_client_mesh(submesh.width, CLIENT_AXIS)
        if key in self._cohort_fns:
            return self._cohort_fns[key]

        one_client = self._one_client_fn(group)
        prev_axis = 0 if stacked_prev else None

        def device_cohort(global_params, inputs, labels, step_valid, prev):
            self.trace_count += 1
            return jax.vmap(one_client, in_axes=(None, 0, 0, 0, prev_axis))(
                global_params, inputs, labels, step_valid, prev
            )

        c = P(CLIENT_AXIS)
        in_specs = (P(), c, c, c, c if stacked_prev else P())
        self._cohort_fns[key] = jax.jit(
            _shard_map(
                device_cohort, mesh=mesh, in_specs=in_specs,
                out_specs=(c, c), **_SHARD_MAP_KW,
            ),
            donate_argnums=self._donate_prev(stacked_prev),
        )
        return self._cohort_fns[key]

    def _plan_cohort_fn(self, stacked_prev: bool, submesh=None) -> Callable:
        """Plan-round cohort program: ``_cohort_fn``'s no-psum contract with
        the per-client group bitmask riding the client axis as a sixth
        sharded input.  Same trace-sharing story: one AbstractMesh per
        width."""
        if submesh is None:
            key, mesh = ("plan", stacked_prev), self.mesh
        else:
            key = ("plan", stacked_prev, submesh.width)
            mesh = abstract_client_mesh(submesh.width, CLIENT_AXIS)
        if key in self._cohort_fns:
            return self._cohort_fns[key]

        one_client = self._one_client_plan_fn()
        prev_axis = 0 if stacked_prev else None

        def device_cohort(global_params, inputs, labels, step_valid, prev,
                          gmask):
            self.trace_count += 1
            return jax.vmap(one_client, in_axes=(None, 0, 0, 0, prev_axis, 0))(
                global_params, inputs, labels, step_valid, prev, gmask
            )

        c = P(CLIENT_AXIS)
        in_specs = (P(), c, c, c, c if stacked_prev else P(), c)
        self._cohort_fns[key] = jax.jit(
            _shard_map(
                device_cohort, mesh=mesh, in_specs=in_specs,
                out_specs=(c, c), **_SHARD_MAP_KW,
            ),
            donate_argnums=self._donate_prev(stacked_prev),
        )
        return self._cohort_fns[key]

    def _place_cohort_args(self, args: tuple, submesh, *,
                           stacked_prev: bool) -> tuple:
        if submesh is None:
            # the engine's concrete-mesh programs shard host arrays themselves
            return args
        from jax.sharding import NamedSharding

        rep = NamedSharding(submesh.mesh, P())
        shd = NamedSharding(submesh.mesh, P(CLIENT_AXIS))
        params, inputs, labels, step_valid, prev = args[:5]
        placed = (jax.device_put(params, rep),
                  jax.device_put(inputs, shd),
                  jax.device_put(labels, shd),
                  jax.device_put(step_valid, shd),
                  jax.device_put(prev, shd if stacked_prev else rep))
        if len(args) == 6:      # plan cohorts: the bitmask rides the client axis
            placed += (jax.device_put(args[5], shd),)
        return placed

    def cohort_pool(self, max_inflight: int):
        """Cut this engine's client mesh into equal-width disjoint submeshes,
        one in-flight cohort per submesh."""
        if max_inflight <= 1:
            return None
        from repro.launch.mesh import SubmeshPool

        num = min(max_inflight, self.num_devices)
        return SubmeshPool(num, devices=self.num_devices)

    def _splice_fn(self, group: int, n_buckets: int) -> Callable:
        """Sum the buckets' psum'd updates and splice into the global model
        (cast back to each leaf's dtype; BN stats already dropped on-mesh)."""
        key = (group, n_buckets)
        if key in self._agg_fns:
            return self._agg_fns[key]
        partition = self.partition

        def splice(global_params, updates):
            self.trace_count += 1
            summed = jax.tree.map(lambda *xs: sum(xs), *updates)
            ref = (
                global_params if group < 0
                else masking.select(global_params, partition, group)
            )
            ref = aggregation.drop_local_stats(ref)
            averaged = jax.tree.map(lambda s, r: s.astype(r.dtype), summed, ref)
            return masking.tree_update(global_params, averaged)

        self._agg_fns[key] = jax.jit(splice, donate_argnums=self._donate_params())
        return self._agg_fns[key]

    def _plan_splice_fn(self, n_buckets: int) -> Callable:
        """Sum the buckets' psum'd plan updates and splice: a leaf whose
        group somebody trained takes the summed participant-weighted average
        (cast back to its dtype); a zero-trainer group's leaves keep the
        frozen global *bit-identical* (``trained`` is the per-group
        had-participants bitmap, computed host-side from the plan)."""
        key = ("plan", n_buckets)
        if key in self._agg_fns:
            return self._agg_fns[key]
        partition = self.partition

        def splice(global_params, updates, trained):
            self.trace_count += 1
            summed = jax.tree.map(lambda *xs: sum(xs), *updates)
            ref = aggregation.drop_local_stats(global_params)

            def _choose(path, s, r):
                g = partition.group_of(
                    "/".join(masking._entry_str(e) for e in path))
                return jnp.where(trained[g], s.astype(r.dtype), r)

            averaged = jax.tree_util.tree_map_with_path(_choose, summed, ref)
            return masking.tree_update(global_params, averaged)

        self._agg_fns[key] = jax.jit(splice, donate_argnums=self._donate_params())
        return self._agg_fns[key]

    # -- round execution ---------------------------------------------------

    def run_round(
        self,
        params: PyTree,
        spec: RoundSpec,
        datasets: Sequence[ClientDataset],
        *,
        seeds: Sequence[int],
        weights: Sequence[float],
        epochs: int,
        batch_size: int,
        prev_params: Sequence[PyTree | None] | None = None,
        tracker=None,
        plan=None,
        client_ids: Sequence[int] | None = None,
    ) -> tuple[PyTree, list[float], list[PyTree] | None]:
        self._guard_round(weights, tracker)
        plan = resolve_plan(plan, spec, self.partition.num_groups)
        ids = self._require_client_ids(client_ids, len(datasets))
        group = FULL_NETWORK if spec.is_full else spec.group
        use_prev = self.algo.name == "moon"
        num = len(datasets)
        w = np.asarray(weights, dtype=np.float32)
        w_norm = w / w.sum()
        if plan is not None:
            # Per-group participant denominators over the whole cohort:
            # zero-trainer groups keep eff_w all-zero and are spliced from
            # the frozen global instead.
            denom = aggregation.plan_group_denominators(plan, w)     # (M,)
            eff = w[:, None] * plan.astype(np.float32)               # (num, M)
            eff_norm = eff / np.where(denom > 0, denom, 1.0)[None, :]
            trained = jnp.asarray(denom > 0)

        updates: list[PyTree] = []
        loss_parts: list[tuple[tuple[int, ...], jax.Array]] = []
        local_parts: list[tuple[tuple[int, ...], PyTree]] = []
        for bucket, prev_arg in self._buckets(
            params, datasets, batch_size=batch_size, epochs=epochs, seeds=seeds,
            prev_params=prev_params, use_prev=use_prev,
            pad_clients_to=self.num_devices,
        ):
            res_args: tuple = ()
            if self.compression is not None:
                res_args = (self._stacked_residuals(
                    ids, bucket.members, bucket.num_clients, params),)
            with span("fl.dispatch", program="local",
                      **self._local_span_args(params, group, plan)):
                if plan is None:
                    wb = np.zeros(bucket.num_clients, dtype=np.float32)
                    wb[: bucket.num_real] = w_norm[list(bucket.members)]
                    fn = self._local_fn(group, stacked_prev=use_prev)
                    out = fn(params, bucket.inputs, bucket.labels,
                             bucket.step_valid, prev_arg, wb, *res_args)
                else:
                    wb = np.zeros((bucket.num_clients, plan.shape[1]),
                                  dtype=np.float32)
                    wb[: bucket.num_real] = eff_norm[list(bucket.members)]
                    fn = self._plan_local_fn(stacked_prev=use_prev)
                    out = fn(params, bucket.inputs, bucket.labels,
                             bucket.step_valid, prev_arg,
                             self._bucket_gmask(plan, bucket), wb, *res_args)
            update, (bucket_losses, _) = out[0], out[1]
            updates.append(update)
            n = bucket.num_real
            loss_parts.append((bucket.members, bucket_losses[:n]))
            if use_prev:
                local_parts.append((
                    bucket.members,
                    jax.tree.map(lambda x: x[:n], out[2]),
                ))
            if self.compression is not None:
                self._store_residuals(ids, bucket.members, out[-1])

        with span("fl.dispatch", program="agg"):
            if plan is None:
                new_params = self._splice_fn(group, len(updates))(
                    params, updates)
            else:
                new_params = self._plan_splice_fn(len(updates))(
                    params, updates, trained)
        losses_dev = self._gather_order(loss_parts, num)
        with span("fl.wait", what="losses"):
            losses = [float(x) for x in np.asarray(losses_dev)]
        if use_prev:
            stacked = self._gather_order(local_parts, num)
            new_locals = masking.unstack_tree(stacked, num)
        else:
            new_locals = None
        return new_params, losses, new_locals


def make_engine(
    name: str,
    *,
    trainer: LocalTrainer,
    partition: Partition,
    algo: AlgoConfig,
    sim_devices: int = 0,
    donate: bool = True,
    compression: compress.CompressionConfig | None = None,
    state_store: Any = None,
):
    """Build a client-simulation engine by name.

    ``sim_devices`` only matters for ``"shard_map"``: the number of devices
    to mesh over the ``"clients"`` axis (0 = all visible devices)::

        engine = make_engine("vmap", trainer=trainer, partition=partition,
                             algo=AlgoConfig())
        engine.run_round(...)   # same contract for every engine

    ``donate`` (batched engines only) donates the global params into the
    aggregation/splice jit (in-place update) and the stacked MOON prev-model
    tree into the local-round jit.  With donation on, ``run_round``
    *consumes* its params argument — callers must thread the returned params
    into the next round (``run_federated`` does; pass ``donate=False`` to
    keep re-feeding the same tree, e.g. for fixed-workload benchmarking).

    The local step's update rule is the trainer's (``LocalTrainer.fused``:
    tree Adam, or the Pallas masked-Adam kernel — docs/KERNELS.md); every
    engine aggregates the same per-leaf tree either way.

    ``compression`` (a ``core.compress.CompressionConfig``, or ``None`` for
    the byte-identical legacy paths) compresses every client's transmitted
    update at the engine's transmission boundary with per-client
    error-feedback residuals; ``run_round`` then requires ``client_ids=``
    (docs/COMPRESSION.md).
    """
    if name == "sequential":
        return SequentialEngine(trainer=trainer, partition=partition, algo=algo,
                                compression=compression,
                                state_store=state_store)
    if name == "vmap":
        return VmapEngine(trainer=trainer, partition=partition, algo=algo,
                          donate=donate, compression=compression,
                          state_store=state_store)
    if name == "shard_map":
        return ShardMapEngine(trainer=trainer, partition=partition, algo=algo,
                              donate=donate, devices=sim_devices,
                              compression=compression, state_store=state_store)
    raise ValueError(f"unknown engine {name!r}; expected one of {ENGINES}")
