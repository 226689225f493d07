"""Decoder-only transformer assembly (dense / GQA / MLA / MoE) plus the
xLSTM and Zamba2-hybrid assemblies.

Layers are *stacked*: per-block parameter pytrees carry a leading layer axis
and the forward pass is a ``jax.lax.scan`` over it — keeping the lowered HLO
(and CPU compile time for the 80 dry-run combinations) small.  Heterogeneous
stacks (deepseek's leading dense blocks, zamba2's shared-attention chunks,
xLSTM's mLSTM/sLSTM alternation) are expressed as a few homogeneous stacks.

Set ``scan_layers=False`` in ``init``/``forward`` calls via config name suffix
is NOT supported — the FL-simulation models (paper's ResNet / small NLP
transformer) use the *unstacked* builders in ``repro.models.nlp_small`` and
``repro.models.resnet`` instead, which FedPart partitions per-layer.

The packed-document decoder (``packed_decoder_*``, the FL decoder task of
``fl.tasks.decoder_task``) is the exception: its few layers are held
unstacked, a dict of blocks run in a Python loop, each recomputed in the
backward, so that every FedPart group is whole leaves.  Its MoE layers hold
one chip's share of the experts (``moe.held_moe_apply``).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (
    bf16_grad_barrier,
    embed,
    embedding_init,
    linear,
    linear_init,
    mlp_apply,
    mlp_init,
    norm_apply,
    norm_init,
    rmsnorm,
    rmsnorm_init,
    unembed,
)

PyTree = Any


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def _act_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.activation_dtype)


# ---------------------------------------------------------------------------
# Decoder block
# ---------------------------------------------------------------------------

def block_init(key, cfg: ModelConfig, *, use_moe: bool) -> PyTree:
    dt = _dtype(cfg)
    k1, k2 = jax.random.split(key)
    p: PyTree = {
        "attn_norm": norm_init(cfg.norm_kind, cfg.d_model, dt),
        "mlp_norm": norm_init(cfg.norm_kind, cfg.d_model, dt),
    }
    if cfg.use_mla:
        p["attn"] = attn.mla_init(k1, cfg, dt)
    else:
        p["attn"] = attn.gqa_init(k1, cfg, dt)
    if use_moe:
        p["moe"] = moe_lib.moe_init(k2, cfg, dt)
    else:
        p["mlp"] = mlp_init(k2, cfg.mlp_kind, cfg.d_model, cfg.d_ff, dt)
    return p


def block_forward(
    p: PyTree,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    *,
    use_moe: bool,
    window: int,
    impl: str,
) -> tuple[jax.Array, PyTree, jax.Array]:
    h = norm_apply(cfg.norm_kind, p["attn_norm"], x)
    if cfg.use_mla:
        y, (c0, c1) = attn.mla_full(p["attn"], cfg, h, positions, window=window, impl=impl)
        kv = {"c_kv": c0, "k_rope": c1}
    else:
        y, (ck, cv) = attn.gqa_full(p["attn"], cfg, h, positions, window=window, impl=impl)
        kv = {"k": ck, "v": cv}
    x = x + y
    h = norm_apply(cfg.norm_kind, p["mlp_norm"], x)
    if use_moe:
        y, aux = moe_lib.moe_apply(p["moe"], cfg, h)
    else:
        y, aux = mlp_apply(p["mlp"], cfg.mlp_kind, h), jnp.float32(0.0)
    return bf16_grad_barrier(x + y), kv, aux


def block_decode(
    p: PyTree,
    cfg: ModelConfig,
    x: jax.Array,
    cache: PyTree,
    pos: jax.Array,
    *,
    use_moe: bool,
    window: int,
) -> tuple[jax.Array, PyTree]:
    h = norm_apply(cfg.norm_kind, p["attn_norm"], x)
    if cfg.use_mla:
        y, (c0, c1) = attn.mla_decode(
            p["attn"], cfg, h, cache["c_kv"], cache["k_rope"], pos, window=window
        )
        new_cache = {"c_kv": c0, "k_rope": c1}
    else:
        y, (ck, cv) = attn.gqa_decode(
            p["attn"], cfg, h, cache["k"], cache["v"], pos, window=window
        )
        new_cache = {"k": ck, "v": cv}
    x = x + y
    h = norm_apply(cfg.norm_kind, p["mlp_norm"], x)
    if use_moe:
        y, _ = moe_lib.moe_apply(p["moe"], cfg, h)
    else:
        y = mlp_apply(p["mlp"], cfg.mlp_kind, h)
    return x + y, new_cache


def _scan(body, carry, xs, *, remat: bool = False, unroll: int = 1):
    if remat:
        body = jax.checkpoint(body)
    return jax.lax.scan(body, carry, xs, unroll=max(1, unroll))


def _stack_init(key, n: int, one_init):
    keys = jax.random.split(key, n)
    return jax.vmap(one_init)(keys)


# ---------------------------------------------------------------------------
# Decoder-only model
# ---------------------------------------------------------------------------

def decoder_init(key, cfg: ModelConfig) -> PyTree:
    dt = _dtype(cfg)
    keys = jax.random.split(key, 5)
    n_moe = cfg.num_layers - cfg.first_dense_layers if cfg.is_moe else 0
    n_dense = cfg.num_layers - n_moe
    params: PyTree = {
        "embed": embedding_init(keys[0], cfg.vocab_size, cfg.d_model, dt),
        "final_norm": norm_init(cfg.norm_kind, cfg.d_model, dt),
    }
    if n_dense > 0:
        params["blocks"] = _stack_init(
            keys[1], n_dense, lambda k: block_init(k, cfg, use_moe=False)
        )
    if n_moe > 0:
        params["moe_blocks"] = _stack_init(
            keys[2], n_moe, lambda k: block_init(k, cfg, use_moe=True)
        )
    if not cfg.tie_embeddings:
        params["head"] = linear_init(keys[3], cfg.d_model, cfg.vocab_size, dt)
    if cfg.mtp_depth > 0:  # deepseek-v3 multi-token prediction head
        params["mtp"] = {
            "proj": linear_init(keys[4], 2 * cfg.d_model, cfg.d_model, dt),
            "norm": norm_init(cfg.norm_kind, cfg.d_model, dt),
            "block": block_init(jax.random.fold_in(keys[4], 1), cfg, use_moe=False),
        }
    return params


def _embed_inputs(params, cfg, tokens, media_embeds):
    x = embed(params["embed"], tokens, _act_dtype(cfg))
    if media_embeds is not None:
        x = jnp.concatenate([media_embeds.astype(x.dtype), x], axis=1)
    return x


def _logits(params, cfg, x):
    if cfg.tie_embeddings:
        return unembed(params["embed"], x)
    return linear(params["head"], x.astype(jnp.float32))


MTP_WEIGHT = 0.3   # deepseek-v3 MTP loss weight (lambda in the paper)


def decoder_forward(
    params: PyTree,
    cfg: ModelConfig,
    tokens: jax.Array,
    *,
    labels: jax.Array | None = None,
    media_embeds: jax.Array | None = None,
    window: int = 0,
    impl: str = "xla",
    collect_cache: bool = False,
    remat: bool = False,
    unroll: int = 1,
) -> tuple[jax.Array, PyTree | None, jax.Array]:
    """Full-sequence forward (training / prefill).

    Returns (logits, caches | None, aux_loss).  ``window`` > 0 applies
    sliding-window attention (the long-context variant).
    """
    x = _embed_inputs(params, cfg, tokens, media_embeds)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    aux_total = jnp.float32(0.0)
    caches = {}

    def run_stack(x, aux, stack, use_moe):
        def body(carry, p):
            xc, auxc = carry
            y, kv, aux_l = block_forward(
                p, cfg, xc, positions, use_moe=use_moe, window=window, impl=impl
            )
            return (y, auxc + aux_l), kv if collect_cache else None

        (x, aux), kvs = _scan(body, (x, aux), stack, remat=remat, unroll=unroll)
        return x, aux, kvs

    if "blocks" in params:
        x, aux_total, kvs = run_stack(x, aux_total, params["blocks"], use_moe=False)
        if collect_cache:
            caches["blocks"] = kvs
    if "moe_blocks" in params:
        x, aux_total, kvs = run_stack(x, aux_total, params["moe_blocks"], use_moe=True)
        if collect_cache:
            caches["moe_blocks"] = kvs

    x = norm_apply(cfg.norm_kind, params["final_norm"], x)
    logits = _logits(params, cfg, x)
    if cfg.mtp_depth > 0 and "mtp" in params and labels is not None:
        # deepseek-v3 multi-token prediction (training aux objective):
        # combine position t's hidden state with the embedding of token t+1,
        # run one extra dense block, predict the t+1 position's label.
        st = tokens.shape[1]
        x_tok = x[:, -st:]                       # token positions (skip media)
        mtp = params["mtp"]
        nxt = embed(params["embed"], tokens[:, 1:], x_tok.dtype)
        h = jnp.concatenate([x_tok[:, :-1], nxt], axis=-1)
        h = norm_apply(cfg.norm_kind, mtp["norm"], linear(mtp["proj"], h))
        h, _, _ = block_forward(
            mtp["block"], cfg, h, positions[:, : st - 1],
            use_moe=False, window=window, impl=impl,
        )
        mtp_logits = _logits(params, cfg, h)
        aux_total = aux_total + MTP_WEIGHT * lm_loss(mtp_logits, labels[:, 1:])
    return logits, (caches if collect_cache else None), aux_total


def decoder_decode_step(
    params: PyTree,
    cfg: ModelConfig,
    token: jax.Array,          # (B, 1) int32
    cache: PyTree,
    pos: jax.Array,            # scalar int32
    *,
    window: int = 0,
    unroll: int = 1,
) -> tuple[jax.Array, PyTree]:
    """One-token serve step against the KV cache."""
    x = embed(params["embed"], token, _act_dtype(cfg))
    new_cache: PyTree = {}

    def run_stack(x, stack, stack_cache, use_moe):
        def body(carry, inp):
            p, c = inp
            y, nc = block_decode(p, cfg, carry, c, pos, use_moe=use_moe, window=window)
            return y, nc

        x, ncs = _scan(body, x, (stack, stack_cache), unroll=unroll)
        return x, ncs

    if "blocks" in params:
        x, nc = run_stack(x, params["blocks"], cache["blocks"], use_moe=False)
        new_cache["blocks"] = nc
    if "moe_blocks" in params:
        x, nc = run_stack(x, params["moe_blocks"], cache["moe_blocks"], use_moe=True)
        new_cache["moe_blocks"] = nc

    x = norm_apply(cfg.norm_kind, params["final_norm"], x)
    return _logits(params, cfg, x), new_cache


def decoder_cache_init(cfg: ModelConfig, batch: int, cache_len: int, dtype) -> PyTree:
    n_moe = cfg.num_layers - cfg.first_dense_layers if cfg.is_moe else 0
    n_dense = cfg.num_layers - n_moe
    hd = cfg.resolved_head_dim

    def layer_cache(n_layers):
        if cfg.use_mla:
            return {
                "c_kv": jnp.zeros((n_layers, batch, cache_len, cfg.kv_lora_rank), dtype),
                "k_rope": jnp.zeros(
                    (n_layers, batch, cache_len, cfg.qk_rope_head_dim), dtype
                ),
            }
        return {
            "k": jnp.zeros((n_layers, batch, cache_len, cfg.num_kv_heads, hd), dtype),
            "v": jnp.zeros((n_layers, batch, cache_len, cfg.num_kv_heads, hd), dtype),
        }

    cache: PyTree = {}
    if n_dense > 0:
        cache["blocks"] = layer_cache(n_dense)
    if n_moe > 0:
        cache["moe_blocks"] = layer_cache(n_moe)
    return cache


# ---------------------------------------------------------------------------
# xLSTM model (alternating mLSTM / sLSTM pairs)
# ---------------------------------------------------------------------------

def xlstm_init(key, cfg: ModelConfig) -> PyTree:
    dt = _dtype(cfg)
    keys = jax.random.split(key, 4)
    assert cfg.num_layers % 2 == 0, "xlstm assembly uses mLSTM/sLSTM pairs"
    n_pairs = cfg.num_layers // 2

    def pair_init(k):
        k1, k2, k3, k4 = jax.random.split(k, 4)
        return {
            "m_norm": norm_init(cfg.norm_kind, cfg.d_model, dt),
            "mlstm": ssm_lib.mlstm_init(k1, cfg, dt),
            "s_norm": norm_init(cfg.norm_kind, cfg.d_model, dt),
            "slstm": ssm_lib.slstm_init(k2, cfg, dt),
        }

    return {
        "embed": embedding_init(keys[0], cfg.vocab_size, cfg.d_model, dt),
        "pairs": _stack_init(keys[1], n_pairs, pair_init),
        "final_norm": norm_init(cfg.norm_kind, cfg.d_model, dt),
        "head": linear_init(keys[2], cfg.d_model, cfg.vocab_size, dt),
    }


def _xlstm_pair_forward(p, cfg, x):
    h, m_cache = ssm_lib.mlstm_forward(
        p["mlstm"], cfg, norm_apply(cfg.norm_kind, p["m_norm"], x)
    )
    x = x + h
    h, s_cache = ssm_lib.slstm_forward(
        p["slstm"], cfg, norm_apply(cfg.norm_kind, p["s_norm"], x)
    )
    return x + h, {"mlstm": m_cache, "slstm": s_cache}


def xlstm_forward(
    params: PyTree, cfg: ModelConfig, tokens: jax.Array, *,
    collect_cache: bool = False, remat: bool = False, unroll: int = 1,
) -> tuple[jax.Array, PyTree | None, jax.Array]:
    x = embed(params["embed"], tokens, _act_dtype(cfg))

    def body(carry, p):
        y, cache = _xlstm_pair_forward(p, cfg, carry)
        return y, cache if collect_cache else None

    x, caches = _scan(body, x, params["pairs"], remat=remat, unroll=unroll)
    x = norm_apply(cfg.norm_kind, params["final_norm"], x)
    return _logits(params, cfg, x), caches, jnp.float32(0.0)


def xlstm_decode_step(
    params: PyTree, cfg: ModelConfig, token: jax.Array, cache: PyTree, pos: jax.Array,
    *, unroll: int = 1,
) -> tuple[jax.Array, PyTree]:
    x = embed(params["embed"], token, _act_dtype(cfg))

    def body(carry, inp):
        p, c = inp
        h, mc = ssm_lib.mlstm_decode(
            p["mlstm"], cfg, norm_apply(cfg.norm_kind, p["m_norm"], carry), c["mlstm"]
        )
        x1 = carry + h
        h, sc = ssm_lib.slstm_decode(
            p["slstm"], cfg, norm_apply(cfg.norm_kind, p["s_norm"], x1), c["slstm"]
        )
        return x1 + h, {"mlstm": mc, "slstm": sc}

    x, new_cache = _scan(body, x, (params["pairs"], cache), unroll=unroll)
    x = norm_apply(cfg.norm_kind, params["final_norm"], x)
    return _logits(params, cfg, x), new_cache


def xlstm_cache_init(cfg: ModelConfig, batch: int, dtype) -> PyTree:
    n_pairs = cfg.num_layers // 2

    def one(_):
        return {
            "mlstm": ssm_lib.mlstm_cache_init(cfg, batch, dtype),
            "slstm": ssm_lib.slstm_cache_init(cfg, batch, dtype),
        }

    return jax.vmap(one)(jnp.arange(n_pairs))


# ---------------------------------------------------------------------------
# Zamba2 hybrid (mamba2 chunks + one shared attention block)
# ---------------------------------------------------------------------------

def hybrid_layout(cfg: ModelConfig) -> tuple[int, int]:
    """(num_chunks, tail) — ``num_chunks`` groups of ``attn_every`` mamba
    blocks, each preceded by the shared attention block; ``tail`` leftover
    mamba blocks."""
    per = max(cfg.attn_every, 1)
    return cfg.num_layers // per, cfg.num_layers % per


def hybrid_init(key, cfg: ModelConfig) -> PyTree:
    dt = _dtype(cfg)
    keys = jax.random.split(key, 6)
    n_chunks, tail = hybrid_layout(cfg)
    per = max(cfg.attn_every, 1)

    def chunk_init(k):
        return _stack_init(k, per, lambda kk: {
            "norm": norm_init(cfg.norm_kind, cfg.d_model, dt),
            "mamba": ssm_lib.mamba2_init(kk, cfg, dt),
        })

    params: PyTree = {
        "embed": embedding_init(keys[0], cfg.vocab_size, cfg.d_model, dt),
        "chunks": _stack_init(keys[1], n_chunks, chunk_init),
        "shared_attn": {
            # zamba2: shared block consumes concat(hidden, original embedding)
            "in_proj": linear_init(keys[2], 2 * cfg.d_model, cfg.d_model, dt),
            "block": block_init(keys[3], cfg, use_moe=False),
        },
        "final_norm": norm_init(cfg.norm_kind, cfg.d_model, dt),
        "head": linear_init(keys[4], cfg.d_model, cfg.vocab_size, dt),
    }
    if tail > 0:
        params["tail"] = _stack_init(keys[5], tail, lambda kk: {
            "norm": norm_init(cfg.norm_kind, cfg.d_model, dt),
            "mamba": ssm_lib.mamba2_init(kk, cfg, dt),
        })
    return params


def hybrid_forward(
    params: PyTree,
    cfg: ModelConfig,
    tokens: jax.Array,
    *,
    window: int = 0,
    impl: str = "xla",
    collect_cache: bool = False,
    remat: bool = False,
    unroll: int = 1,
) -> tuple[jax.Array, PyTree | None, jax.Array]:
    x = embed(params["embed"], tokens, _act_dtype(cfg))
    x0 = x
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def mamba_body(carry, p):
        y, c = ssm_lib.mamba2_forward(
            p["mamba"], cfg, norm_apply(cfg.norm_kind, p["norm"], carry)
        )
        return carry + y, c if collect_cache else None

    def chunk_body(carry, chunk_params):
        xc = carry
        h = linear(params["shared_attn"]["in_proj"], jnp.concatenate([xc, x0], axis=-1))
        y, kv, _ = block_forward(
            params["shared_attn"]["block"], cfg, h, positions,
            use_moe=False, window=window, impl=impl,
        )
        xc = xc + y
        xc, mcaches = _scan(mamba_body, xc, chunk_params, unroll=unroll)
        return xc, {"attn_kv": kv, "mamba": mcaches} if collect_cache else None

    x, chunk_caches = _scan(chunk_body, x, params["chunks"], remat=remat, unroll=unroll)
    tail_caches = None
    if "tail" in params:
        x, tail_caches = _scan(mamba_body, x, params["tail"], remat=remat, unroll=unroll)
    x = norm_apply(cfg.norm_kind, params["final_norm"], x)
    logits = _logits(params, cfg, x)
    caches = {"chunks": chunk_caches, "tail": tail_caches} if collect_cache else None
    return logits, caches, jnp.float32(0.0)


def hybrid_decode_step(
    params: PyTree,
    cfg: ModelConfig,
    token: jax.Array,
    cache: PyTree,
    pos: jax.Array,
    *,
    window: int = 0,
    unroll: int = 1,
) -> tuple[jax.Array, PyTree]:
    x = embed(params["embed"], token, _act_dtype(cfg))
    x0 = x

    def mamba_body(carry, inp):
        p, c = inp
        y, nc = ssm_lib.mamba2_decode(
            p["mamba"], cfg, norm_apply(cfg.norm_kind, p["norm"], carry), c
        )
        return carry + y, nc

    def chunk_body(carry, inp):
        xc = carry
        p_chunk, c_chunk = inp
        h = linear(params["shared_attn"]["in_proj"], jnp.concatenate([xc, x0], axis=-1))
        y, attn_nc = block_decode(
            params["shared_attn"]["block"], cfg, h, c_chunk["attn_kv"], pos,
            use_moe=False, window=window,
        )
        xc = xc + y
        xc, m_nc = _scan(mamba_body, xc, (p_chunk, c_chunk["mamba"]), unroll=unroll)
        return xc, {"attn_kv": attn_nc, "mamba": m_nc}

    x, chunk_nc = _scan(chunk_body, x, (params["chunks"], cache["chunks"]), unroll=unroll)
    new_cache: PyTree = {"chunks": chunk_nc}
    if "tail" in params:
        x, tail_nc = _scan(mamba_body, x, (params["tail"], cache["tail"]), unroll=unroll)
        new_cache["tail"] = tail_nc
    else:
        new_cache["tail"] = cache.get("tail")
    x = norm_apply(cfg.norm_kind, params["final_norm"], x)
    return _logits(params, cfg, x), new_cache


def hybrid_cache_init(cfg: ModelConfig, batch: int, cache_len: int, dtype) -> PyTree:
    n_chunks, tail = hybrid_layout(cfg)
    per = max(cfg.attn_every, 1)
    hd = cfg.resolved_head_dim

    def mamba_caches(n):
        return jax.vmap(lambda _: ssm_lib.mamba2_cache_init(cfg, batch, dtype))(
            jnp.arange(n)
        )

    def one_chunk(_):
        return {
            "attn_kv": {
                "k": jnp.zeros((batch, cache_len, cfg.num_kv_heads, hd), dtype),
                "v": jnp.zeros((batch, cache_len, cfg.num_kv_heads, hd), dtype),
            },
            "mamba": mamba_caches(per),
        }

    cache: PyTree = {"chunks": jax.vmap(one_chunk)(jnp.arange(n_chunks))}
    cache["tail"] = mamba_caches(tail) if tail > 0 else None
    return cache


# ---------------------------------------------------------------------------
# Packed-document decoder, layers unstacked (FedPart's decoder task)
# ---------------------------------------------------------------------------

def packed_decoder_init(key, cfg: ModelConfig) -> PyTree:
    """``embed``, ``blocks`` (``"0"`` .. ``str(num_layers - 1)``: MLA, then a
    dense SwiGLU in the first ``first_dense_layers`` and a held-expert MoE
    after them), ``final_norm`` and an untied ``head``."""
    dt, d = _dtype(cfg), cfg.d_model
    keys = jax.random.split(key, cfg.num_layers + 2)
    blocks = {}
    for i in range(cfg.num_layers):
        k1, k2 = jax.random.split(keys[i])
        p: PyTree = {"attn_norm": rmsnorm_init(d, dt),
                     "attn": attn.mla_init(k1, cfg, dt),
                     "mlp_norm": rmsnorm_init(d, dt)}
        if i < cfg.first_dense_layers:
            p["mlp"] = mlp_init(k2, cfg.mlp_kind, d, cfg.d_ff, dt)
        else:
            p["moe"] = moe_lib.held_moe_init(k2, cfg, dt)
        blocks[str(i)] = p
    return {"embed": embedding_init(keys[-2], cfg.vocab_size, d, dt),
            "blocks": blocks,
            "final_norm": rmsnorm_init(d, dt),
            "head": linear_init(keys[-1], d, cfg.vocab_size, dt)}


def _packed_block(p, x, seg, positions, bias, *, cfg):
    eps = cfg.rms_norm_eps
    with jax.named_scope("mla"):
        x = x + attn.mla_packed(p["attn"], cfg, rmsnorm(p["attn_norm"], x, eps),
                                seg, positions)
    h = rmsnorm(p["mlp_norm"], x, eps)
    if "moe" in p:
        y, held = moe_lib.held_moe_apply(p["moe"], cfg, h, bias)
    else:
        y, held = mlp_apply(p["mlp"], cfg.mlp_kind, h), None
    return x + y, held


def packed_decoder_hidden(params: PyTree, cfg: ModelConfig, tokens: jax.Array,
                          bias: jax.Array, bos_id: int) -> tuple[jax.Array, jax.Array]:
    """tokens (B, S) of packed documents, each begun by ``bos_id`` ->
    (final-norm hidden states (B, S, d), rows routed to the held experts per
    MoE layer (float32, (moe layers,))).  ``bias`` is the MoE layers'
    ``moe.router_bias``.  Positions restart at each document."""
    start = tokens == bos_id
    seg = jnp.cumsum(start, axis=-1)
    idx = jnp.arange(tokens.shape[-1])
    positions = idx - jax.lax.cummax(jnp.where(start, idx, 0), axis=tokens.ndim - 1)
    x = embed(params["embed"], tokens, _act_dtype(cfg))
    held = []
    block = jax.checkpoint(functools.partial(_packed_block, cfg=cfg))
    for i in range(cfg.num_layers):
        j = i - cfg.first_dense_layers
        x, n = block(params["blocks"][str(i)], x, seg, positions,
                     bias[j] if j >= 0 else None)
        if n is not None:
            held.append(n)
    x = rmsnorm(params["final_norm"], x, cfg.rms_norm_eps)
    return x, jnp.stack(held)


def packed_lm_loss(logits: jax.Array, labels: jax.Array, bos_id: int) -> jax.Array:
    """Next-token cross entropy over the targets that are not a document's
    first token (``bos_id``, predicted from another document)."""
    return lm_loss(logits, labels, (labels != bos_id).astype(jnp.float32))


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def lm_loss(
    logits: jax.Array, labels: jax.Array, mask: jax.Array | None = None
) -> jax.Array:
    """Next-token cross entropy.  logits: (B,S,V); labels: (B,S)."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
