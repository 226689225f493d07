"""Mixture-of-Experts layer: top-k router + capacity-bounded expert FFNs.

Dispatch is sort-based (MegaBlocks-style) rather than one-hot-einsum
(GShard-style): token->expert assignments are ranked inside each expert via an
argsort + offset subtraction, then scattered into a dense (E, C, d) buffer.
This avoids ever materialising the (T, E, C) dispatch tensor — at
train_4k scale (T=1M tokens, E=256) that tensor would be terabytes — while
remaining fully static-shaped and pjit-shardable: the buffer's E axis is
sharded over the ``model`` mesh axis (expert parallelism), so the scatter
lowers to the MoE all-to-all.

Supports softmax and sigmoid (deepseek-v3) router scores, shared experts,
top-k weight renormalisation, and the standard load-balance auxiliary loss.

``held_moe_apply`` is the other expert layer: one chip's share of an
expert-parallel layer, told which experts it holds.  It routes over all the
router's experts (deepseek-v3 ``noaux_tc``: select by sigmoid score plus a
frozen correction bias, weight by the plain score, normalise over the top-k,
scale), drops no token, and computes its held experts' part with a Pallas
grouped matmul whose work follows the rows routed to them.  Tokens routed to
experts this chip does not hold contribute nothing here.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import linear, linear_init, mlp_apply, mlp_init

PyTree = Any


def moe_init(key, cfg: ModelConfig, dtype) -> PyTree:
    k_r, k_e, k_s = jax.random.split(key, 3)
    e, d, f = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(k_e, 3)
    scale_in = 1.0 / jnp.sqrt(jnp.float32(d))
    scale_out = 1.0 / jnp.sqrt(jnp.float32(f))
    p: PyTree = {
        "router": linear_init(k_r, d, e, dtype),
        "experts": {
            "w_gate": (jax.random.normal(ks[0], (e, d, f)) * scale_in).astype(dtype),
            "w_up": (jax.random.normal(ks[1], (e, d, f)) * scale_in).astype(dtype),
            "w_down": (jax.random.normal(ks[2], (e, f, d)) * scale_out).astype(dtype),
        },
    }
    if cfg.num_shared_experts > 0:
        p["shared"] = mlp_init(
            k_s, cfg.mlp_kind, d, cfg.moe_d_ff * cfg.num_shared_experts, dtype
        )
    return p


def _capacity(num_tokens: int, cfg: ModelConfig) -> int:
    c = int(num_tokens * cfg.num_experts_per_tok * cfg.capacity_factor) // cfg.num_experts
    return max(c, 1)


def moe_apply(params: PyTree, cfg: ModelConfig, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (y, aux_loss).

    Under ``moe_ep.expert_parallel(mesh)`` this dispatches to the explicit
    shard_map expert-parallel path (see moe_ep.py for why)."""
    from repro.models import moe_ep

    if moe_ep.ep_enabled():
        return moe_ep.moe_apply_ep(params, cfg, x)
    b, s, d = x.shape
    t = b * s
    k = cfg.num_experts_per_tok
    e = cfg.num_experts
    cap = _capacity(t, cfg)
    xf = x.reshape(t, d)

    # --- Router -----------------------------------------------------------
    logits = (xf @ params["router"]["w"].astype(xf.dtype)).astype(jnp.float32)
    if cfg.router_score == "sigmoid":            # deepseek-v3
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(scores, k)          # (T, k)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

    # --- Load-balance auxiliary loss (Switch/GShard form) -------------------
    probs_mean = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0)            # (E,)
    counts = jnp.zeros((e,), jnp.float32).at[expert_idx.reshape(-1)].add(1.0)
    frac = counts / (t * k)
    aux_loss = e * jnp.sum(frac * probs_mean) * cfg.aux_loss_weight

    # --- Sort-based dispatch ------------------------------------------------
    flat_expert = expert_idx.reshape(-1)                      # (T*k,)
    order = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[order]
    # rank within expert = index-in-sorted − start offset of that expert
    start = jnp.cumsum(jnp.bincount(flat_expert, length=e)) - jnp.bincount(
        flat_expert, length=e
    )
    rank_sorted = jnp.arange(t * k) - start[sorted_expert]
    slot_sorted = jnp.where(
        rank_sorted < cap, sorted_expert * cap + rank_sorted, e * cap
    )  # overflow tokens -> dropped sentinel slot
    slots = jnp.zeros((t * k,), jnp.int32).at[order].set(slot_sorted.astype(jnp.int32))

    token_idx = jnp.repeat(jnp.arange(t), k)
    buf = jnp.zeros((e * cap + 1, d), dtype=x.dtype)
    buf = buf.at[slots].set(xf[token_idx], mode="drop")
    expert_in = buf[: e * cap].reshape(e, cap, d)

    # --- Expert FFNs (batched over the expert axis; shardable on E) --------
    ew = params["experts"]
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, ew["w_gate"].astype(x.dtype)))
    u = jnp.einsum("ecd,edf->ecf", expert_in, ew["w_up"].astype(x.dtype))
    expert_out = jnp.einsum("ecf,efd->ecd", g * u, ew["w_down"].astype(x.dtype))

    # --- Combine ------------------------------------------------------------
    out_buf = jnp.concatenate(
        [expert_out.reshape(e * cap, d), jnp.zeros((1, d), x.dtype)], axis=0
    )
    gathered = out_buf[slots]                                  # (T*k, d)
    weighted = gathered * gate_vals.reshape(-1)[:, None].astype(x.dtype)
    y = jnp.zeros((t, d), x.dtype).at[token_idx].add(weighted)

    if cfg.num_shared_experts > 0:
        y = y + mlp_apply(params["shared"], cfg.mlp_kind, xf)
    return y.reshape(b, s, d), aux_loss


def moe_flops_per_token(cfg: ModelConfig) -> int:
    """Active matmul FLOPs per token (routed top-k x capacity + shared)."""
    routed = 2 * 3 * cfg.d_model * cfg.moe_d_ff * cfg.num_experts_per_tok
    shared = 2 * 3 * cfg.d_model * cfg.moe_d_ff * cfg.num_shared_experts
    router = 2 * cfg.d_model * cfg.num_experts
    return int(routed * cfg.capacity_factor + shared + router)


# ---------------------------------------------------------------------------
# One chip's share of an expert-parallel layer (dropless, grouped matmul)
# ---------------------------------------------------------------------------

def held_moe_init(key, cfg: ModelConfig, dtype) -> PyTree:
    """The router over all ``num_experts``, the ``held_experts`` this chip
    holds, and the shared experts (one SwiGLU of their summed width)."""
    k_r, k_e, k_s = jax.random.split(key, 3)
    e, d, f = cfg.held_experts, cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(k_e, 3)
    scale_in = 1.0 / jnp.sqrt(jnp.float32(d))
    scale_out = 1.0 / jnp.sqrt(jnp.float32(f))
    return {
        "router": linear_init(k_r, d, cfg.num_experts, dtype),
        "experts": {
            "w_gate": (jax.random.normal(ks[0], (e, d, f)) * scale_in).astype(dtype),
            "w_up": (jax.random.normal(ks[1], (e, d, f)) * scale_in).astype(dtype),
            "w_down": (jax.random.normal(ks[2], (e, f, d)) * scale_out).astype(dtype),
        },
        "shared": mlp_init(k_s, cfg.mlp_kind, d, f * cfg.num_shared_experts, dtype),
    }


def router_bias(cfg: ModelConfig, num_layers: int) -> jax.Array:
    """The ``noaux_tc`` correction bias of ``num_layers`` MoE layers, (layers,
    num_experts) float32: N(0, ``router_bias_std``) from
    ``router_bias_seed``.  Frozen: it is no parameter, in no FedPart group."""
    key = jax.random.key(cfg.router_bias_seed)
    return jax.random.normal(key, (num_layers, cfg.num_experts)) * cfg.router_bias_std


def _gmm_tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """Tiles of one grouped matmul, forward or backward (megablox asks for
    each product's own (m, k, n)): 256 rows, 1024-wide blocks of a dimension
    that splits into them, else the whole dimension (1,408 is 11 x 128)."""
    def tile(size, pref):
        return pref if size % pref == 0 else size

    return tile(m, 256), tile(k, 1024), tile(n, 1024)


def _grouped_swiglu(ew: PyTree, rows: jax.Array, sizes: jax.Array) -> jax.Array:
    """The held experts' SwiGLU over ``rows`` sorted by expert, ``sizes[e]``
    rows each, live rows first.  Each product is a megablox grouped matmul
    whose grid covers only the tiles of live rows; the rows past them are
    never computed and read as zero.  On the TPU its operands are bfloat16
    with float32 accumulation, what the default matmul precision gives the
    XLA matmuls around it; the CPU's interpreter keeps float32."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    from repro.kernels import default_interpret

    m = rows.shape[0]
    interpret = default_interpret()
    dt = jnp.float32 if interpret else jnp.bfloat16
    live = (jnp.arange(m) < jnp.sum(sizes))[:, None]

    def gmm(a, w):
        out = megablox.gmm(a.astype(dt), w.astype(dt), sizes, jnp.float32,
                           _gmm_tiling, None, None, False, interpret)
        # past the live rows the kernel writes nothing: mask before anything
        # reads them, in the forward and in the backward alike
        return jnp.where(live, out, 0.0)

    x = jnp.where(live, rows, 0.0)
    h = jax.nn.silu(gmm(x, ew["w_gate"])) * gmm(x, ew["w_up"])
    return gmm(h, ew["w_down"]).astype(rows.dtype)


def held_moe_apply(params: PyTree, cfg: ModelConfig, x: jax.Array,
                   bias: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (y, held_rows): the held experts' part of the routed
    result plus the shared experts, and the number of (token, expert) rows
    routed to the held experts (float32 scalar).

    The dispatch buffer holds every (token, slot) pair, tokens x top-k rows,
    the held experts' rows first (sorted by expert) and the rest after them:
    the worst case, so no token is ever dropped."""
    b, s, d = x.shape
    t, k, e = b * s, cfg.num_experts_per_tok, cfg.held_experts
    xf = x.reshape(t, d)
    with jax.named_scope("moe_route"):
        scores = jax.nn.sigmoid(linear(params["router"], xf).astype(jnp.float32))
        _, idx = jax.lax.top_k(scores + bias, k)            # select: score + bias
        gate = jnp.take_along_axis(scores, idx, axis=-1)    # weight: plain score
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True) * cfg.routed_scale
        local = idx - cfg.held_expert_start
        held = (local >= 0) & (local < e)
        slot = jnp.where(held, local, e).reshape(-1)        # absent experts last
        order = jnp.argsort(slot, stable=True)
        sizes = jnp.bincount(slot, length=e + 1)[:e].astype(jnp.int32)
        rows = xf[order // k]
    with jax.named_scope("moe_experts"):
        out = _grouped_swiglu(params["experts"], rows, sizes)
    with jax.named_scope("moe_route"):
        back = out[jnp.argsort(order)].reshape(t, k, d)     # (token, slot) order
        w = jnp.where(held, gate, 0.0).astype(x.dtype)
        y = jnp.einsum("tk,tkd->td", w, back)
    y = y + mlp_apply(params["shared"], cfg.mlp_kind, xf)
    return y.reshape(b, s, d), jnp.sum(sizes).astype(jnp.float32)
