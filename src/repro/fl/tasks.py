"""Task adapters: bind a model family (ResNet vision / small-NLP text /
packed-document decoder) to the uniform interface the FL engine consumes.

    adapter.init(key)                       -> params
    adapter.loss(params, inputs, labels)    -> scalar task loss
    adapter.features(params, inputs)        -> (B, d) penultimate features (MOON)
    adapter.evaluate(params, inputs, labels)-> accuracy
    adapter.stats(params, inputs)           -> pruned BN-stat updates (or None)
    adapter.partition(params)               -> core.Partition (Appendix-A groups)
    adapter.counted_loss(params, inputs, labels)
                                            -> (loss, float32 counters) or None
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, get_config
from repro.core.partition import Partition, build_partition
from repro.models import nlp_small, resnet

PyTree = Any


@dataclasses.dataclass(frozen=True)
class TaskAdapter:
    name: str
    init: Callable[[Any], PyTree]
    loss: Callable[[PyTree, jax.Array, jax.Array], jax.Array]
    features: Callable[[PyTree, jax.Array], jax.Array]
    evaluate: Callable[[PyTree, jax.Array, jax.Array], jax.Array]
    stats: Callable[[PyTree, jax.Array], PyTree | None]
    partition: Callable[[PyTree], Partition]
    flops_per_sample: float = 0.0
    # Counters of what the loss's own forward did, per step: ``counted_loss``
    # returns the loss and a float32 vector.  Every local step returns it
    # beside its loss (an empty vector without ``counted_loss``), the local
    # round sums it over steps, and the vmap engine reads it back with the
    # losses and sets its sum over clients on the losses' ``fl.wait`` span
    # as ``counter``.
    counted_loss: Callable[[PyTree, jax.Array, jax.Array],
                           tuple[jax.Array, jax.Array]] | None = None
    counter: str = ""


# ---------------------------------------------------------------------------
# Vision (ResNet)
# ---------------------------------------------------------------------------

def _resnet_features(params, images):
    x = resnet.conv_apply(params["stem"]["conv"], images)
    x, _ = resnet.bn_apply(params["stem"]["bn"], x, train=False)
    x = jax.nn.relu(x)
    for name in sorted(params["blocks"]):
        blk = params["blocks"][name]
        stride = 2 if "sc_conv" in blk else 1
        h = resnet.conv_apply(blk["conv1"], x, stride)
        h, _ = resnet.bn_apply(blk["bn1"], h, train=False)
        h = jax.nn.relu(h)
        h = resnet.conv_apply(blk["conv2"], h)
        h, _ = resnet.bn_apply(blk["bn2"], h, train=False)
        if "sc_conv" in blk:
            sc = resnet.conv_apply(blk["sc_conv"], x, stride)
            sc, _ = resnet.bn_apply(blk["sc_bn"], sc, train=False)
        else:
            sc = x
        x = jax.nn.relu(h + sc)
    return jnp.mean(x, axis=(1, 2))


def _conv_flops(spec, image_size=32) -> float:
    """Rough per-sample forward matmul FLOPs for the cost model."""
    total, hw, cin = 0.0, image_size * image_size, 3
    for stage, (n_blocks, cout) in enumerate(zip(spec["stages"], spec["channels"])):
        for b in range(n_blocks):
            if stage > 0 and b == 0:
                hw /= 4
            total += 2 * 9 * cin * cout * hw + 2 * 9 * cout * cout * hw
            cin = cout
    return total


_RESNET_SPECS = {
    "resnet4": resnet.RESNET4,   # test-scale: fast compile, same BN/shortcut structure
    "resnet8": resnet.RESNET8,
    "resnet18": resnet.RESNET18,
}


def resnet_task(depth: str = "resnet8", num_classes: int = 20) -> TaskAdapter:
    spec = _RESNET_SPECS[depth]

    def init(key):
        return resnet.resnet_init(key, spec, num_classes)

    def loss(params, images, labels):
        logits, _ = resnet.resnet_apply(params, images, train=True)
        return resnet.cls_loss(logits, labels)

    def stats(params, images):
        _, upd = resnet.resnet_apply(params, images, train=True)
        return upd

    def evaluate(params, images, labels):
        # Batch-statistics mode: BN running stats are client-local and never
        # aggregated (paper §4), so the global model is scored with batch
        # stats on the balanced eval set (deterministic given the set).
        logits, _ = resnet.resnet_apply(params, images, train=True)
        return resnet.accuracy(logits, labels)

    def make_partition(params):
        return build_partition(params, resnet.resnet_group_key, resnet.resnet_order_key)

    return TaskAdapter(
        name=depth,
        init=init,
        loss=loss,
        features=_resnet_features,
        evaluate=evaluate,
        stats=stats,
        partition=make_partition,
        flops_per_sample=_conv_flops(spec),
    )


# ---------------------------------------------------------------------------
# Text (small transformer classifier)
# ---------------------------------------------------------------------------

def nlp_task(num_classes: int = 4, cfg: ModelConfig | None = None, smoke: bool = False) -> TaskAdapter:
    cfg = cfg or get_config("nlp-transformer", smoke=smoke)

    def init(key):
        return nlp_small.nlp_init(key, cfg, num_classes)

    def loss(params, tokens, labels):
        logits = nlp_small.nlp_apply(params, cfg, tokens)
        return resnet.cls_loss(logits, labels)

    def features(params, tokens):
        # penultimate = pooled pre-head representation
        import jax.numpy as jnp

        b, s = tokens.shape
        from repro.models.layers import embed, norm_apply

        x = embed(params["embed"], tokens)
        x = x + params["embed"]["pos"][None, :s, :].astype(x.dtype)
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        from repro.models import attention as attn
        from repro.models.layers import mlp_apply

        for i in range(cfg.num_layers):
            p = params["blocks"][str(i)]
            h = norm_apply(cfg.norm_kind, p["attn_norm"], x)
            y, _ = attn.gqa_full(p["attn"], cfg, h, positions, causal=False)
            x = x + y
            h = norm_apply(cfg.norm_kind, p["mlp_norm"], x)
            x = x + mlp_apply(p["mlp"], cfg.mlp_kind, h)
        return jnp.mean(x, axis=1)

    def evaluate(params, tokens, labels):
        logits = nlp_small.nlp_apply(params, cfg, tokens)
        return resnet.accuracy(logits, labels)

    def make_partition(params):
        return build_partition(params, nlp_small.nlp_group_key)

    from repro.models.layers import mlp_flops

    flops = cfg.num_layers * (
        2 * 4 * cfg.d_model * cfg.d_model + mlp_flops(cfg.mlp_kind, cfg.d_model, cfg.d_ff)
    ) * cfg.max_position_embeddings

    return TaskAdapter(
        name="nlp-transformer",
        init=init,
        loss=loss,
        features=features,
        evaluate=evaluate,
        stats=lambda params, tokens: None,
        partition=make_partition,
        flops_per_sample=float(flops),
    )


# ---------------------------------------------------------------------------
# Packed-document decoder (next-token fine-tuning, ROADMAP R1)
# ---------------------------------------------------------------------------

def decoder_task(arch: str = "moonlight-16b-a3b", smoke: bool = False,
                 bos_id: int = 0) -> TaskAdapter:
    """Next-token training on rows of packed documents.

    ``inputs`` are token rows (N, S) int32 over the model's vocabulary (a
    chip's slice of it), each document begun by ``bos_id``; ``labels`` are
    the next-token targets (N, S), ``bos_id`` where the next token starts
    another document or lies past the row, and such targets are skipped.
    ``evaluate`` is the token accuracy over the counted targets.  No batch
    norm: ``stats`` is None.  The partition is FedPart's per-layer one:
    embedding; each block; final norm with the head.  ``counted_loss``
    counts the rows routed to the held experts of each MoE layer
    (``held_assignments``)."""
    from repro.models import moe, transformer

    cfg = get_config(arch, smoke=smoke)
    bias = moe.router_bias(cfg, cfg.num_layers - cfg.first_dense_layers)

    def hidden(params, tokens):
        return transformer.packed_decoder_hidden(params, cfg, tokens, bias, bos_id)

    def counted_loss(params, tokens, labels):
        x, held = hidden(params, tokens)
        logits = x.astype(jnp.float32) @ params["head"]["w"].astype(jnp.float32)
        return transformer.packed_lm_loss(logits, labels, bos_id), held

    def loss(params, tokens, labels):
        return counted_loss(params, tokens, labels)[0]

    def features(params, tokens):
        return jnp.mean(hidden(params, tokens)[0], axis=1)

    def evaluate(params, tokens, labels):
        def one(row):
            t, y = row
            x, _ = hidden(params, t[None])
            logits = x[0].astype(jnp.float32) @ params["head"]["w"].astype(jnp.float32)
            counted = y != bos_id
            return (jnp.sum((jnp.argmax(logits, axis=-1) == y) & counted),
                    jnp.sum(counted))

        hits, n = jax.lax.map(one, (tokens, labels))
        return jnp.sum(hits) / jnp.maximum(jnp.sum(n), 1)

    return TaskAdapter(
        name=cfg.name,
        init=lambda key: transformer.packed_decoder_init(key, cfg),
        loss=loss,
        features=features,
        evaluate=evaluate,
        stats=lambda params, tokens: None,
        partition=build_partition,
        counted_loss=counted_loss,
        counter="held_assignments",
    )
