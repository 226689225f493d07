"""Attention variants: GQA/MQA (full-causal and sliding-window) and
DeepSeek-style MLA (multi-head latent attention) with a compressed KV cache.

Two entry points per variant:

* ``*_full``   — whole-sequence forward (training / prefill).  Returns the
  output and the KV-cache tensors for the sequence (prefill writes them).
* ``*_decode`` — one-token step against an existing cache (serve_step).
  Sliding-window caches are ring buffers: RoPE is applied at *write* time
  with absolute positions so slot order is irrelevant to the attention math.

The default math path is pure jnp (XLA fusions); the Pallas flash-attention
kernel in ``repro.kernels.flash_attention`` is selected via ``impl='pallas'``
where supported (TPU; interpret mode in tests).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import act_sharding
from repro.models.layers import apply_rope, linear, linear_init, rmsnorm, rmsnorm_init, rope_angles

PyTree = Any

NEG_INF = -1e30
# Query block of ``mla_packed``: one block's f32 scores, (B, H, 1024, <=S),
# are live at a time.
PACKED_Q_BLOCK = 1024


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def gqa_init(key, cfg: ModelConfig, dtype) -> PyTree:
    hd = cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": linear_init(k1, cfg.d_model, cfg.num_heads * hd, dtype),
        "wk": linear_init(k2, cfg.d_model, cfg.num_kv_heads * hd, dtype),
        "wv": linear_init(k3, cfg.d_model, cfg.num_kv_heads * hd, dtype),
        "wo": linear_init(k4, cfg.num_heads * hd, cfg.d_model, dtype),
    }


def _split_heads(x: jax.Array, n: int, hd: int) -> jax.Array:
    return x.reshape(*x.shape[:-1], n, hd)


def _sdpa(q, k, v, mask, impl: str = "xla"):
    """q: (B,S,H,D); k/v: (B,T,Hkv,D); mask: (B,S,T) or (S,T) bool."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    if impl == "pallas" and s > 1:
        from repro.kernels.flash_attention import ops as fa_ops

        return fa_ops.flash_attention(q, k, v, mask=mask)
    kr = jnp.repeat(k, rep, axis=2) if rep > 1 else k
    vr = jnp.repeat(v, rep, axis=2) if rep > 1 else v
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    logits = jnp.einsum("bshd,bthd->bhst", q, kr).astype(jnp.float32) * scale
    logits = act_sharding.constrain_scores(logits)
    if mask is not None:
        m = mask if mask.ndim == 3 else mask[None]
        logits = jnp.where(m[:, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    probs = act_sharding.constrain_scores(probs)
    return jnp.einsum("bhst,bthd->bshd", probs, vr)


def causal_mask(s: int, window: int = 0) -> jax.Array:
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    m = j <= i
    if window > 0:
        m = m & (j > i - window)
    return m


def gqa_full(
    params: PyTree,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    *,
    window: int = 0,
    causal: bool = True,
    impl: str = "xla",
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Full-sequence attention.  Returns (y, (k_cache, v_cache))."""
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = _split_heads(linear(params["wq"], x), cfg.num_heads, hd)
    k = _split_heads(linear(params["wk"], x), cfg.num_kv_heads, hd)
    v = _split_heads(linear(params["wv"], x), cfg.num_kv_heads, hd)
    if cfg.use_rope:
        cos, sin = rope_angles(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    mask = causal_mask(s, window) if causal else None
    y = _sdpa(q, k, v, mask, impl=impl)
    y = linear(params["wo"], y.reshape(b, s, cfg.num_heads * hd))
    return y, (k, v)


def gqa_decode(
    params: PyTree,
    cfg: ModelConfig,
    x: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos: jax.Array,
    *,
    window: int = 0,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """One-token decode.  x: (B,1,d); caches: (B,W,Hkv,D); pos: scalar int32.

    ``window == 0`` means the cache length equals the full context and slot
    index == absolute position.  ``window > 0`` means a ring buffer of W slots.
    """
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    cache_len = k_cache.shape[1]
    q = _split_heads(linear(params["wq"], x), cfg.num_heads, hd)
    k = _split_heads(linear(params["wk"], x), cfg.num_kv_heads, hd)
    v = _split_heads(linear(params["wv"], x), cfg.num_kv_heads, hd)
    if cfg.use_rope:
        posv = jnp.full((b, 1), pos, dtype=jnp.int32)
        cos, sin = rope_angles(posv, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    slot = jnp.where(window > 0, pos % cache_len, pos)
    k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype), (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype), (0, slot, 0, 0))
    # Valid slots: all once pos+1 >= cache_len, else indices <= pos.
    idx = jnp.arange(cache_len)
    valid = jnp.where(pos + 1 >= cache_len, jnp.ones((cache_len,), bool), idx <= pos)
    mask = jnp.broadcast_to(valid[None, None, :], (b, 1, cache_len))
    y = _sdpa(q, k_cache.astype(q.dtype), v_cache.astype(q.dtype), mask)
    y = linear(params["wo"], y.reshape(b, 1, cfg.num_heads * hd))
    return y, (k_cache, v_cache)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3)
# ---------------------------------------------------------------------------

def mla_init(key, cfg: ModelConfig, dtype) -> PyTree:
    keys = jax.random.split(key, 6)
    h = cfg.num_heads
    qk_dim = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    p: PyTree = {}
    if cfg.q_lora_rank > 0:
        p["wq_a"] = linear_init(keys[0], cfg.d_model, cfg.q_lora_rank, dtype)
        p["q_norm"] = rmsnorm_init(cfg.q_lora_rank, dtype)
        p["wq_b"] = linear_init(keys[1], cfg.q_lora_rank, h * qk_dim, dtype)
    else:
        p["wq"] = linear_init(keys[0], cfg.d_model, h * qk_dim, dtype)
    p["wkv_a"] = linear_init(keys[2], cfg.d_model, cfg.kv_lora_rank + cfg.qk_rope_head_dim, dtype)
    p["kv_norm"] = rmsnorm_init(cfg.kv_lora_rank, dtype)
    p["wkv_b"] = linear_init(
        keys[3], cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim), dtype
    )
    p["wo"] = linear_init(keys[4], h * cfg.v_head_dim, cfg.d_model, dtype)
    return p


def _mla_q(params: PyTree, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    h = cfg.num_heads
    qk_dim = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    if cfg.q_lora_rank > 0:
        q = linear(params["wq_b"], rmsnorm(params["q_norm"], linear(params["wq_a"], x)))
    else:
        q = linear(params["wq"], x)
    return q.reshape(*x.shape[:-1], h, qk_dim)


def _mla_scores_and_out(params, cfg, q, c_kv, k_rope, mask):
    """q: (B,S,H,qk); c_kv: (B,T,rank); k_rope: (B,T,rope) — shared across heads."""
    h = cfg.num_heads
    nope, rope_d, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    kv = linear(params["wkv_b"], rmsnorm(params["kv_norm"], c_kv))
    kv = kv.reshape(*c_kv.shape[:-1], h, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = 1.0 / jnp.sqrt(jnp.float32(nope + rope_d))
    logits = (
        jnp.einsum("bshd,bthd->bhst", q_nope, k_nope)
        + jnp.einsum("bshd,btd->bhst", q_rope, k_rope)
    ).astype(jnp.float32) * scale
    logits = act_sharding.constrain_scores(logits)
    if mask is not None:
        m = mask if mask.ndim == 3 else mask[None]
        logits = jnp.where(m[:, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    probs = act_sharding.constrain_scores(probs)
    y = jnp.einsum("bhst,bthd->bshd", probs, v)
    return linear(params["wo"], y.reshape(*q.shape[:2], h * vd))


def mla_full(
    params: PyTree,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    *,
    window: int = 0,
    impl: str = "xla",
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Returns (y, (c_kv_cache, k_rope_cache)) — the compressed MLA cache."""
    b, s, _ = x.shape
    q = _mla_q(params, cfg, x)
    ckr = linear(params["wkv_a"], x)
    c_kv, k_rope_raw = ckr[..., : cfg.kv_lora_rank], ckr[..., cfg.kv_lora_rank :]
    cos, sin = rope_angles(positions, cfg.qk_rope_head_dim, cfg.rope_theta)
    nope = cfg.qk_nope_head_dim
    q_rope = apply_rope(q[..., nope:], cos, sin)
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    k_rope = apply_rope(k_rope_raw[..., None, :], cos, sin)[..., 0, :]
    mask = causal_mask(s, window)
    y = _mla_scores_and_out(params, cfg, q, c_kv, k_rope, mask)
    return y, (c_kv, k_rope)


def mla_decode(
    params: PyTree,
    cfg: ModelConfig,
    x: jax.Array,
    c_cache: jax.Array,
    r_cache: jax.Array,
    pos: jax.Array,
    *,
    window: int = 0,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """One-token MLA decode against the latent cache.

    c_cache: (B,W,kv_rank); r_cache: (B,W,rope_dim).  The latent cache costs
    (kv_rank + rope_dim) ≈ 576 bytes·dtype per token per layer — this is what
    makes the 500k-context decode shape feasible for deepseek-v3 (DESIGN §4).
    """
    b = x.shape[0]
    cache_len = c_cache.shape[1]
    q = _mla_q(params, cfg, x)
    ckr = linear(params["wkv_a"], x)
    c_kv, k_rope_raw = ckr[..., : cfg.kv_lora_rank], ckr[..., cfg.kv_lora_rank :]
    posv = jnp.full((b, 1), pos, dtype=jnp.int32)
    cos, sin = rope_angles(posv, cfg.qk_rope_head_dim, cfg.rope_theta)
    nope = cfg.qk_nope_head_dim
    q = jnp.concatenate([q[..., :nope], apply_rope(q[..., nope:], cos, sin)], axis=-1)
    k_rope = apply_rope(k_rope_raw[..., None, :], cos, sin)[..., 0, :]
    slot = jnp.where(window > 0, pos % cache_len, pos)
    c_cache = jax.lax.dynamic_update_slice(c_cache, c_kv.astype(c_cache.dtype), (0, slot, 0))
    r_cache = jax.lax.dynamic_update_slice(r_cache, k_rope.astype(r_cache.dtype), (0, slot, 0))
    idx = jnp.arange(cache_len)
    valid = jnp.where(pos + 1 >= cache_len, jnp.ones((cache_len,), bool), idx <= pos)
    mask = jnp.broadcast_to(valid[None, None, :], (b, 1, cache_len))
    y = _mla_scores_and_out(
        params, cfg, q, c_cache.astype(x.dtype), r_cache.astype(x.dtype), mask
    )
    return y, (c_cache, r_cache)


def _packed_block(q_nope, q_rope, k_nope, k_rope, v, seg_q, seg_k, *, start, scale):
    """One block of queries (from position ``start``) against the keys up to
    its last query: causal and within one document."""
    logits = (
        jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
        + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope)
    ).astype(jnp.float32) * scale
    qi = start + jnp.arange(q_nope.shape[1])[:, None]
    kj = jnp.arange(k_nope.shape[1])[None, :]
    mask = (kj <= qi)[None] & (seg_q[:, :, None] == seg_k[:, None, :])
    logits = jnp.where(mask[:, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def mla_packed(
    params: PyTree,
    cfg: ModelConfig,
    x: jax.Array,
    segment_ids: jax.Array,
    positions: jax.Array,
) -> jax.Array:
    """MLA (no KV cache) over rows of packed documents: a query sees the
    keys at or before it in its own document (``segment_ids``, (B, S)), and
    RoPE takes ``positions`` within the document.  Queries run in blocks of
    ``PACKED_Q_BLOCK``, each against the keys up to its end and each
    recomputed in the backward, so only one block's scores are ever live."""
    b, s, _ = x.shape
    h = cfg.num_heads
    nope, rope_d, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q = _mla_q(params, cfg, x)
    ckr = linear(params["wkv_a"], x)
    c_kv, k_rope = ckr[..., : cfg.kv_lora_rank], ckr[..., cfg.kv_lora_rank :]
    cos, sin = rope_angles(positions, rope_d, cfg.rope_theta)
    q_nope, q_rope = q[..., :nope], apply_rope(q[..., nope:], cos, sin)
    k_rope = apply_rope(k_rope[..., None, :], cos, sin)[..., 0, :]
    kv = linear(params["wkv_b"], rmsnorm(params["kv_norm"], c_kv, cfg.rms_norm_eps))
    kv = kv.reshape(b, s, h, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = 1.0 / jnp.sqrt(jnp.float32(nope + rope_d))
    qb = min(PACKED_Q_BLOCK, s)
    outs = []
    for start in range(0, s, qb):
        end = min(start + qb, s)
        block = jax.checkpoint(functools.partial(_packed_block, start=start, scale=scale))
        outs.append(block(q_nope[:, start:end], q_rope[:, start:end],
                          k_nope[:, :end], k_rope[:, :end], v[:, :end],
                          segment_ids[:, start:end], segment_ids[:, :end]))
    y = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    return linear(params["wo"], y.reshape(b, s, h * vd))
