"""Partial (FedPart) optimizer: gradients and optimizer state exist only for
the round's trainable group.

The three steps here are independent references: the kernel and
equivalence tests compare the hot path against them.  The hot path itself
is ``fl.client.LocalTrainer`` (one step builder, ``make_step``, whose update
rule is tree Adam or the fused kernel); nothing in the framework calls
these steps.

Two mathematically equivalent realisations (asserted equal in
``tests/test_partial_equivalence.py``):

* ``masked_step``      — paper Eq. 1 literally: full gradient, multiplied by
  the binary mask S.  Reference semantics; wasteful.
* ``partitioned_step`` — gradients w.r.t. the pruned trainable subtree only,
  frozen remainder closed over as constants.  XLA prunes the dead backward
  graph; Adam m/v are allocated for the subtree only.  A homogeneous
  partial round of ``LocalTrainer`` takes this form.

A third realisation, ``fused_masked_step``, is Eq. 1 through the Pallas
masked-Adam kernel (``kernels/masked_adam``): params/grads are packed into
the kernel's (rows, 128) block layout, the whole optimizer update runs as one
fused pass with a per-block mask, and m/v live *packed* across steps
(``fused_adam_init``).  The three-way equivalence is pinned in
``tests/test_kernels_adam.py``.  ``LocalTrainer(fused=True)``
(docs/KERNELS.md) keeps this whole-tree masked form for FNU rounds and
per-client plans; a homogeneous partial round joins the two forms instead:
it packs, differentiates and carries only the trained group, as
``partitioned_step`` does, and runs the kernel over that group's rows with
m/v packed for them alone.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import masking
from repro.core.partition import Partition
from repro.kernels import default_interpret
from repro.kernels.masked_adam import ops as madam_ops
from repro.kernels.masked_adam.kernel import LANES, masked_adam_kernel
from repro.optim.adam import AdamConfig, AdamState, adam_init, adam_update

PyTree = Any


def fused_adam_init(params: PyTree, block_rows: int = 8) -> AdamState:
    """Adam state over the *packed* (rows, 128) layout: m/v are single f32
    buffers aligned with ``ops.pack(params)``, not per-leaf trees.  This is
    what keeps the fused scan pack-free for the optimizer state — only
    params/grads are packed each step.  ``params`` is whatever tree the
    step packs: the whole model, or a partial round's trained subtree."""
    rows = madam_ops.packed_rows(params, block_rows)
    z = jnp.zeros((rows, LANES), jnp.float32)
    return AdamState(step=jnp.zeros((), jnp.int32), m=z, v=jnp.zeros_like(z))


def guard_fused_config(cfg: AdamConfig) -> None:
    """The kernel implements plain Adam — weight decay would silently not be
    applied, so refuse it loudly."""
    if cfg.weight_decay:
        raise ValueError(
            "fused_adam does not support weight_decay "
            f"(got {cfg.weight_decay}); use the unfused engines")


def fused_masked_step(
    loss_fn: Callable[[PyTree], jax.Array],
    params: PyTree,
    opt_state: AdamState,          # packed state from ``fused_adam_init``
    partition: Partition,
    groups,                        # int or set of trainable group ids
    cfg: AdamConfig,
    *,
    block_rows: int = 8,
    interpret: bool | None = None,
) -> tuple[PyTree, AdamState, jax.Array]:
    """Eq. 1 through the fused kernel: full-tree gradient, block-masked
    packed Adam update, frozen blocks copy through bit-exact."""
    guard_fused_config(cfg)
    with jax.named_scope("grad"):
        loss, grads = jax.value_and_grad(loss_fn)(params)
    step = opt_state.step + 1
    bm = madam_ops.block_mask_for_group(params, partition, groups, block_rows)
    pp, meta = madam_ops.pack(params, block_rows)
    pg, _ = madam_ops.pack(grads, block_rows)
    scalars = madam_ops.adam_scalars(step, cfg.lr, cfg.b1, cfg.b2, cfg.eps)
    if interpret is None:
        interpret = default_interpret()
    np_, nm, nv = masked_adam_kernel(
        pp, pg, opt_state.m, opt_state.v, jnp.asarray(bm), scalars,
        b1=cfg.b1, b2=cfg.b2, block_rows=block_rows, interpret=interpret,
    )
    return madam_ops.unpack(np_, meta), AdamState(step, nm, nv), loss


def masked_step(
    loss_fn: Callable[[PyTree], jax.Array],
    params: PyTree,
    opt_state: AdamState,
    mask: PyTree,
    cfg: AdamConfig,
) -> tuple[PyTree, AdamState, jax.Array]:
    """Eq. 1: w ← w − γ·S⊙update(∇L).  Full-tree gradient, masked update."""
    with jax.named_scope("grad"):
        loss, grads = jax.value_and_grad(loss_fn)(params)
    grads = masking.apply_mask(grads, mask)
    new_params, new_state = adam_update(grads, opt_state, params, cfg)
    # Mask the parameter delta too: Adam's bias correction would otherwise
    # move frozen params through stale m/v.
    new_params = jax.tree.map(
        lambda n, o, m: jax.numpy.where(m, n, o), new_params, params, mask
    )
    return new_params, new_state, loss


def partitioned_step(
    loss_fn: Callable[[PyTree], jax.Array],
    params: PyTree,
    partition: Partition,
    group: int,
    opt_state: AdamState | None,
    cfg: AdamConfig,
) -> tuple[PyTree, AdamState, jax.Array]:
    """Gradient w.r.t. the trainable subtree only; merge back after update.

    ``opt_state`` is over the *subtree* (None -> freshly initialised), so m/v
    memory is 1/M of the full model.
    """
    trainable = masking.select(params, partition, group)
    frozen = masking.complement(params, partition, group)

    def sub_loss(sub):
        return loss_fn(masking.merge(sub, frozen))

    with jax.named_scope("grad"):
        loss, grads = jax.value_and_grad(sub_loss)(trainable)
    if opt_state is None:
        opt_state = adam_init(trainable)
    new_sub, new_state = adam_update(grads, opt_state, trainable, cfg)
    return masking.merge(new_sub, frozen), new_state, loss


def full_step(
    loss_fn: Callable[[PyTree], jax.Array],
    params: PyTree,
    opt_state: AdamState,
    cfg: AdamConfig,
) -> tuple[PyTree, AdamState, jax.Array]:
    """FNU step (FedAvg baseline)."""
    with jax.named_scope("grad"):
        loss, grads = jax.value_and_grad(loss_fn)(params)
    new_params, new_state = adam_update(grads, opt_state, params, cfg)
    return new_params, new_state, loss
