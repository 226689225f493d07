"""Client-side local training.

``LocalTrainer`` builds jitted per-batch step functions — one FNU variant and
one per layer group (the group index is static, so XLA prunes the dead
backward graph per group exactly as in the production launcher).  BN
statistics ride along as a ``has_aux`` output and are spliced back without a
second forward pass.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

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


@dataclasses.dataclass
class LocalTrainer:
    adapter: TaskAdapter
    partition: Partition
    algo: AlgoConfig
    adam: AdamConfig

    def __post_init__(self):
        self.trace_count = 0  # jit (re)traces across all cached step fns
        self._full_step = jax.jit(self._counted(self.make_full_step()))
        self._partial_steps: dict[int, Callable] = {}
        self._plan_steps: dict[tuple[int, ...], Callable] = {}
        self._fused_steps: dict[Any, Callable] = {}

    def _counted(self, fn: Callable) -> Callable:
        """Wrap a step fn so each XLA trace bumps ``trace_count`` (the wrapper
        body only runs while tracing; compiled replays skip it)."""

        def traced(*args):
            self.trace_count += 1
            return fn(*args)

        return traced

    # -- loss assembly -----------------------------------------------------

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

    # -- step builders -------------------------------------------------------
    # Every step returns ``(params, opt_state, (loss, counters))``.

    def make_full_step(self):
        """Raw (unjitted) FNU step — reused by the batched vmap engine."""

        def step(params, opt_state, inputs, labels, global_params, prev_params):
            def loss_fn(p):
                loss, counts = self._total_loss(
                    p, inputs, labels, global_params, prev_params)
                stats = self.adapter.stats(p, inputs)
                return loss, (stats, counts)

            with jax.named_scope("grad"):
                (loss, (stats, counts)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
            new_params, new_state = adam_update(grads, opt_state, params, self.adam)
            if stats is not None:
                new_params = masking.tree_update(new_params, stats)
            return new_params, new_state, (loss, counts)

        return step

    def make_partial_step(self, group):
        """Raw (unjitted) partial step for ``group`` — an int, or a sequence
        of group ids for per-client layer plans (docs/HETEROGENEITY.md) —
        reused by the batched vmap engine (the group set is static, so XLA
        prunes the dead backward graph per distinct set in both engines)."""

        def step(params, opt_state, inputs, labels, global_params, prev_params):
            trainable = masking.select(params, self.partition, group)
            frozen = masking.complement(params, self.partition, group)

            def loss_fn(sub):
                p = masking.merge(sub, frozen)
                loss, counts = self._total_loss(
                    p, inputs, labels, global_params, prev_params)
                stats = self.adapter.stats(p, inputs)
                return loss, (stats, counts)

            with jax.named_scope("grad"):
                (loss, (stats, counts)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(trainable)
            new_sub, new_state = adam_update(grads, opt_state, trainable, self.adam)
            new_params = masking.merge(new_sub, frozen)
            if stats is not None:
                new_params = masking.tree_update(new_params, stats)
            return new_params, new_state, (loss, counts)

        return step

    # -- fused (Pallas masked-Adam) step builders ---------------------------

    def _fused_update(self, params, grads, opt_state, block_mask, block_rows):
        """Shared tail of every fused step: pack params/grads into the kernel
        layout, run the fused masked Adam (m/v stay packed across steps —
        ``optim.partial.fused_adam_init``), unpack the new params."""
        step_i = opt_state.step + 1
        pp, meta = madam_ops.pack(params, block_rows)
        pg, _ = madam_ops.pack(grads, block_rows)
        scalars = madam_ops.adam_scalars(
            step_i, self.adam.lr, self.adam.b1, self.adam.b2, self.adam.eps)
        np_, nm, nv = masked_adam_kernel(
            pp, pg, opt_state.m, opt_state.v, jnp.asarray(block_mask),
            scalars, b1=self.adam.b1, b2=self.adam.b2, block_rows=block_rows,
            interpret=default_interpret(),
        )
        return madam_ops.unpack(np_, meta), AdamState(step_i, nm, nv)

    def _fused_split(self, params, group):
        """``params`` as three pruned trees for the subtree fused step: the
        trained leaves of ``group`` (an int or a set of group ids), BN
        running moments left out; every layer's running moments, which each
        step's forward refreshes; and the rest, which the round leaves as
        they are."""
        sel = {group} if isinstance(group, int) else {int(g) for g in group}
        stat = aggregation.is_local_stat

        def in_sel(p):
            return self.partition.group_of(p) in sel

        return (
            masking.select_where(params, lambda p: in_sel(p) and not stat(p)),
            masking.select_where(params, stat),
            masking.select_where(params, lambda p: not (in_sel(p) or stat(p))),
        )

    def fused_init(self, params, group=None, block_rows: int = 8):
        """Start of a fused local round: ``(carry, opt_state)``.  For
        ``group=None`` the carry is the whole tree and the packed Adam state
        covers it; otherwise the carry is the group's trained leaves and
        every layer's BN running moments, and the state covers the trained
        leaves alone.  ``masking.tree_update(global_params, carry)`` gives the
        full tree back."""
        if group is None:
            return params, fused_adam_init(params, block_rows)
        trained, stats, _ = self._fused_split(params, group)
        return (masking.merge(trained, stats),
                fused_adam_init(trained, block_rows))

    def fused_kernel_rows(self, params, group=None, block_rows: int = 8) -> int:
        """Packed rows the kernel streams per step of ``make_fused_step``."""
        if group is None:
            return madam_ops.packed_rows(params, block_rows)
        return madam_ops.packed_rows(
            self._fused_split(params, group)[0], block_rows)

    def make_fused_step(self, group=None, block_rows: int = 8):
        """Raw (unjitted) fused step: one fused kernel pass per step over the
        packed (rows, 128) layout, with ``opt_state`` the packed Adam state
        of ``fused_init``.

        ``group=None`` (FNU) takes the whole-tree gradient and updates every
        block but the BN running moments' (Eq. 1's masked form).  An int or a
        sequence trains that homogeneous group set on the carry of
        ``fused_init``: the gradient is taken with respect to the trained
        leaves alone, the frozen leaves are closed over from
        ``global_params``, and the kernel streams the trained leaves' rows
        with every block trained.  XLA then drops the backward below the
        group, as in ``make_partial_step``.  BN running moments stay out of
        the kernel and are spliced fresh from the forward pass, exactly like
        the unfused steps."""
        guard_fused_config(self.adam)
        partition = self.partition

        if group is None:
            def step(params, opt_state, inputs, labels, global_params,
                     prev_params):
                def loss_fn(p):
                    loss, counts = self._total_loss(
                        p, inputs, labels, global_params, prev_params)
                    stats = self.adapter.stats(p, inputs)
                    return loss, (stats, counts)

                with jax.named_scope("grad"):
                    (loss, (stats, counts)), grads = jax.value_and_grad(
                        loss_fn, has_aux=True)(params)
                bm = madam_ops.block_mask_for_group(
                    params, partition, tuple(range(partition.num_groups)),
                    block_rows, exclude=aggregation.is_local_stat)
                new_params, new_state = self._fused_update(
                    params, grads, opt_state, bm, block_rows)
                if stats is not None:
                    new_params = masking.tree_update(new_params, stats)
                return new_params, new_state, (loss, counts)

            return step

        def step(carry, opt_state, inputs, labels, global_params, prev_params):
            trained, stats0, _ = self._fused_split(carry, group)
            frozen = self._fused_split(global_params, group)[2]

            def loss_fn(sub):
                p = masking.merge(sub, stats0, frozen)
                loss, counts = self._total_loss(
                    p, inputs, labels, global_params, prev_params)
                stats = self.adapter.stats(p, inputs)
                return loss, (stats, counts)

            with jax.named_scope("grad"):
                (loss, (stats, counts)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(trained)
            ones = np.ones(madam_ops.packed_rows(trained, block_rows)
                           // block_rows, np.int32)
            new_sub, new_state = self._fused_update(
                trained, grads, opt_state, ones, block_rows)
            new_carry = masking.merge(new_sub, stats0)
            if stats is not None:
                new_carry = masking.tree_update(new_carry, stats)
            return new_carry, new_state, (loss, counts)

        return step

    def make_fused_plan_step(self, block_rows: int = 8):
        """Fused step for per-client layer plans: same kernel pass, but the
        block mask is *traced* from the client's ``(M,)`` group bitmask
        (seventh argument) via static per-block group ids — one compiled
        program serves every plan row, mirroring ``_one_client_plan_fn``'s
        contract without the per-leaf re-pinning (the kernel mask already
        freezes untrained blocks)."""
        guard_fused_config(self.adam)
        partition = self.partition

        def step(params, opt_state, inputs, labels, global_params,
                 prev_params, gmask):
            def loss_fn(p):
                loss, counts = self._total_loss(
                    p, inputs, labels, global_params, prev_params)
                stats = self.adapter.stats(p, inputs)
                return loss, (stats, counts)

            with jax.named_scope("grad"):
                (loss, (stats, counts)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
            gids = madam_ops.block_group_ids(
                params, partition, block_rows,
                exclude=aggregation.is_local_stat)
            bm = madam_ops.plan_block_mask(gids, gmask)
            new_params, new_state = self._fused_update(
                params, grads, opt_state, bm, block_rows)
            if stats is not None:
                new_params = masking.tree_update(new_params, stats)
            return new_params, new_state, (loss, counts)

        return step

    def fused_step(self, group=None) -> Callable:
        """Jitted cache over ``make_fused_step`` keys (None / int / tuple)."""
        key = group if (group is None or isinstance(group, int)) \
            else tuple(sorted(int(g) for g in group))
        if key not in self._fused_steps:
            self._fused_steps[key] = jax.jit(
                self._counted(self.make_fused_step(key)))
        return self._fused_steps[key]

    def partial_step(self, group: int) -> Callable:
        if group not in self._partial_steps:
            self._partial_steps[group] = jax.jit(
                self._counted(self.make_partial_step(group))
            )
        return self._partial_steps[group]

    def plan_step(self, groups: tuple[int, ...]) -> Callable:
        """Jitted partial step for a *set* of layer groups (one cached trace
        per distinct set — capacity tiers, so a handful per run)."""
        key = tuple(sorted(int(g) for g in groups))
        if key not in self._plan_steps:
            self._plan_steps[key] = jax.jit(
                self._counted(self.make_partial_step(key))
            )
        return self._plan_steps[key]

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
        fused: bool = False,
    ) -> tuple[PyTree, float]:
        """Train locally; returns (updated full params, mean loss).

        ``groups`` (per-client layer plans) overrides ``group`` with a *set*
        of trainable layer groups; a set covering every group is the FNU
        step.  ``fused`` routes every step through the Pallas masked-Adam
        kernel (docs/KERNELS.md) with packed optimizer state."""
        params = global_params
        prev = prev_params if prev_params is not None else global_params
        if groups is not None:
            groups = tuple(sorted(int(g) for g in groups))
            full = len(groups) == self.partition.num_groups
        else:
            full = group < 0
        if fused:
            key = None if full else (groups if groups is not None else group)
            step = self.fused_step(key)
            params, opt_state = self.fused_init(params, key)
        elif full:
            opt_state = adam_init(params)
            step = self._full_step
        elif groups is not None:
            opt_state = adam_init(masking.select(params, self.partition, groups))
            step = self.plan_step(groups)
        else:
            opt_state = adam_init(masking.select(params, self.partition, group))
            step = self.partial_step(group)
        # ``params`` is the step's carry: the whole tree, or the fused
        # partial step's trained leaves and BN moments (``fused_init``).
        def full_tree(carry):
            return masking.tree_update(global_params, carry)

        losses = []
        for inputs, labels in data.batches(batch_size, epochs, seed):
            before = params
            params, opt_state, (loss, _) = step(
                params, opt_state, inputs, labels, global_params, prev
            )
            losses.append(float(loss))
            if step_tracker is not None:
                step_tracker.record(full_tree(before), full_tree(params))
        return (full_tree(params),
                float(jnp.mean(jnp.array(losses))) if losses else 0.0)
