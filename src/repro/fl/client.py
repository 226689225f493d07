"""Client-side local training.

``LocalTrainer`` owns the local step: one builder (``make_step``), one
gradient (``_grad``) and one update rule, chosen once by ``fused``: tree
Adam, or the Pallas masked-Adam kernel over the packed (rows, 128) layout
with m/v packed across steps (docs/KERNELS.md).  A partial step
differentiates and carries only its trained group, so XLA prunes the dead
backward graph per group set; an FNU step takes a per-client plan's traced
group bitmask instead (Eq. 1's masked form, one program for every plan
row).  BN statistics ride along as a ``has_aux`` output and are spliced
back without a second forward pass.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from repro.core import aggregation, masking
from repro.core.partition import Partition
from repro.fl.algorithms import AlgoConfig, augment_loss
from repro.fl.tasks import TaskAdapter
from repro.kernels import default_interpret
from repro.kernels.masked_adam import ops as madam_ops
from repro.kernels.masked_adam.kernel import masked_adam_kernel
from repro.optim.adam import AdamConfig, AdamState, adam_init, adam_update
from repro.optim.partial import fused_adam_init, guard_fused_config

PyTree = Any

BLOCK_ROWS = 8   # rows of one block of the kernel's packed layout


@dataclasses.dataclass
class LocalTrainer:
    adapter: TaskAdapter
    partition: Partition
    algo: AlgoConfig
    adam: AdamConfig
    fused: bool = False   # masked-Adam kernel update, packed m/v (docs/KERNELS.md)

    def __post_init__(self):
        if self.fused:
            guard_fused_config(self.adam)
        self.trace_count = 0  # jit (re)traces across all cached step fns
        self._steps: dict[Any, Callable] = {}

    def _counted(self, fn: Callable) -> Callable:
        """Wrap a step fn so each XLA trace bumps ``trace_count`` (the wrapper
        body only runs while tracing; compiled replays skip it)."""

        def traced(*args):
            self.trace_count += 1
            return fn(*args)

        return traced

    # -- loss and gradient ---------------------------------------------------

    def _total_loss(self, params, inputs, labels, global_params, prev_params):
        """``(loss, counters)``: the task loss with the algorithm's terms, and
        the adapter's per-step counters from the same forward
        (``TaskAdapter.counted_loss``; an empty float32 vector for an
        adapter that counts nothing)."""
        if self.adapter.counted_loss is None:
            task = self.adapter.loss(params, inputs, labels)
            counts = jnp.zeros((0,), jnp.float32)
        else:
            task, counts = self.adapter.counted_loss(params, inputs, labels)
        kw: dict = {}
        if self.algo.name == "fedprox":
            kw = {"params": params, "global_params": global_params}
        elif self.algo.name == "moon":
            kw = {
                "z": self.adapter.features(params, inputs),
                "z_glob": jax.lax.stop_gradient(
                    self.adapter.features(global_params, inputs)
                ),
                "z_prev": jax.lax.stop_gradient(
                    self.adapter.features(prev_params, inputs)
                ),
            }
        return augment_loss(self.algo, task, **kw), counts

    def _grad(self, tree, rebuild, inputs, labels, global_params,
              prev_params):
        """``(loss, stats, counters, grads)``: the gradient with respect to
        ``tree``, the full params being ``rebuild(tree)``, and the BN
        statistics (``adapter.stats``) of the same forward."""

        def loss_fn(sub):
            p = rebuild(sub)
            loss, counts = self._total_loss(
                p, inputs, labels, global_params, prev_params)
            return loss, (self.adapter.stats(p, inputs), counts)

        with jax.named_scope("grad"):
            (loss, (stats, counts)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(tree)
        return loss, stats, counts, grads

    # -- update rule -----------------------------------------------------------

    def _fused_update(self, params, grads, opt_state, block_mask):
        """Pack params/grads into the kernel layout, run the fused masked
        Adam (m/v stay packed across steps — ``optim.partial
        .fused_adam_init``), unpack the new params."""
        step_i = opt_state.step + 1
        pp, meta = madam_ops.pack(params, BLOCK_ROWS)
        pg, _ = madam_ops.pack(grads, BLOCK_ROWS)
        scalars = madam_ops.adam_scalars(
            step_i, self.adam.lr, self.adam.b1, self.adam.b2, self.adam.eps)
        np_, nm, nv = masked_adam_kernel(
            pp, pg, opt_state.m, opt_state.v, jnp.asarray(block_mask),
            scalars, b1=self.adam.b1, b2=self.adam.b2, block_rows=BLOCK_ROWS,
            interpret=default_interpret(),
        )
        return madam_ops.unpack(np_, meta), AdamState(step_i, nm, nv)

    def _update(self, tree, grads, opt_state, gmask=None):
        """One optimizer step over ``tree``: every leaf but the BN running
        moments trains (the stats splice overwrites those), or, with a traced
        ``(M,)`` group bitmask ``gmask``, only the leaves of the groups whose
        bit is set."""
        partition, stat = self.partition, aggregation.is_local_stat
        if not self.fused:
            new, state = adam_update(grads, opt_state, tree, self.adam)
            if gmask is not None:
                def keep(path, a, b):
                    p = "/".join(masking._entry_str(e) for e in path)
                    if stat(p):
                        return a
                    return jnp.where(gmask[partition.group_of(p)] > 0, a, b)

                new = jax.tree_util.tree_map_with_path(keep, new, tree)
            return new, state
        if gmask is None:
            bm = madam_ops.block_mask_for_group(
                tree, partition, tuple(range(partition.num_groups)),
                BLOCK_ROWS, exclude=stat)
        else:
            bm = madam_ops.plan_block_mask(madam_ops.block_group_ids(
                tree, partition, BLOCK_ROWS, exclude=stat), gmask)
        return self._fused_update(tree, grads, opt_state, bm)

    # -- local step ------------------------------------------------------------

    def _split(self, params, group):
        """``params`` as three pruned trees for a partial step: the trained
        leaves of ``group`` (an int or a set of group ids), BN running
        moments left out; every layer's running moments, which each step's
        forward refreshes; and the rest, which the round leaves as they
        are."""
        sel = {group} if isinstance(group, int) else {int(g) for g in group}
        stat = aggregation.is_local_stat

        def in_sel(p):
            return self.partition.group_of(p) in sel

        return (
            masking.select_where(params, lambda p: in_sel(p) and not stat(p)),
            masking.select_where(params, stat),
            masking.select_where(params, lambda p: not (in_sel(p) or stat(p))),
        )

    def init(self, params, group=None):
        """Start of a local round: ``(carry, opt_state)``.  For
        ``group=None`` the carry is the whole tree and the Adam state covers
        it; otherwise the carry is the group's trained leaves and every
        layer's BN running moments, and the state covers the trained leaves
        alone.  ``masking.tree_update(global_params, carry)`` gives the full
        tree back."""
        if group is not None:
            trained, stats, _ = self._split(params, group)
            params = masking.merge(trained, stats)
        else:
            trained = params
        opt = (fused_adam_init(trained, BLOCK_ROWS) if self.fused
               else adam_init(trained))
        return params, opt

    def kernel_rows(self, params, group=None) -> int:
        """Packed rows the fused update streams per step of
        ``make_step(group)``."""
        tree = params if group is None else self._split(params, group)[0]
        return madam_ops.packed_rows(tree, BLOCK_ROWS)

    def make_step(self, group=None):
        """Raw (unjitted) local step on the carry of ``init(params, group)``
        — reused by the batched engines.  Returns ``(carry, opt_state,
        (loss, counters))``.

        ``group=None`` (FNU) takes the whole-tree gradient; its optional
        seventh argument, a traced ``(M,)`` group bitmask, keeps each
        group's update only where its bit is set.  An int or a sequence
        trains that group set: the gradient is taken with respect to the
        trained leaves alone, the frozen leaves are closed over from
        ``global_params``, and the update covers the trained leaves alone.
        BN running moments stay out of the update and are spliced fresh from
        the forward pass."""

        def step(carry, opt_state, inputs, labels, global_params, prev_params,
                 gmask=None):
            if group is None:
                tree, stats0, frozen = carry, None, None
            else:
                tree, stats0, _ = self._split(carry, group)
                frozen = self._split(global_params, group)[2]

            def rebuild(sub):
                return sub if frozen is None else masking.merge(
                    sub, stats0, frozen)

            loss, stats, counts, grads = self._grad(
                tree, rebuild, inputs, labels, global_params, prev_params)
            new, new_state = self._update(tree, grads, opt_state, gmask)
            if stats0 is not None:
                new = masking.merge(new, stats0)
            if stats is not None:
                new = masking.tree_update(new, stats)
            return new, new_state, (loss, counts)

        return step

    def jit_step(self, group=None) -> Callable:
        """``make_step(group)`` jitted, one cached trace per group key."""
        if group not in self._steps:
            self._steps[group] = jax.jit(self._counted(self.make_step(group)))
        return self._steps[group]

    # -- local round ---------------------------------------------------------

    def run_local_round(
        self,
        global_params: PyTree,
        group: int,                    # FULL_NETWORK (-1) for FNU rounds
        data,                          # ClientDataset
        *,
        epochs: int,
        batch_size: int,
        seed: int,
        prev_params: PyTree | None = None,
        step_tracker=None,
        groups: Sequence[int] | None = None,
    ) -> tuple[PyTree, float]:
        """Train locally; returns (updated full params, mean loss).

        ``groups`` (per-client layer plans) overrides ``group`` with a *set*
        of trainable layer groups; a set covering every group is the FNU
        step."""
        prev = prev_params if prev_params is not None else global_params
        if groups is not None:
            groups = tuple(sorted(int(g) for g in groups))
            key = None if len(groups) == self.partition.num_groups else groups
        else:
            key = None if group < 0 else group
        carry, opt_state = self.init(global_params, key)
        step = self.jit_step(key)

        def full_tree(carry):
            return masking.tree_update(global_params, carry)

        losses = []
        for inputs, labels in data.batches(batch_size, epochs, seed):
            before = carry
            carry, opt_state, (loss, _) = step(
                carry, opt_state, inputs, labels, global_params, prev
            )
            losses.append(float(loss))
            if step_tracker is not None:
                step_tracker.record(full_tree(before), full_tree(carry))
        return (full_tree(carry),
                float(jnp.mean(jnp.array(losses))) if losses else 0.0)
