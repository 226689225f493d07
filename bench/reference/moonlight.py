"""Plain reference for Moonlight-16B-A3B (a DeepSeek-V3 block) as one chip's
share of an 8-way expert-parallel deployment, trained by FedPart, in
straightforward jax.numpy.  It imports nothing of the program under test
and takes nothing the program made; it shares with the ResNet reference only
the federation's cohort and batch-order conventions.

The model, per the configuration's keys (the published ``config.json``'s
names, cut as its ``reduced`` says):

- a token embedding over the vocabulary slice; ``num_hidden_layers`` blocks,
  the first ``first_k_dense_replace`` with a dense SwiGLU of width
  ``intermediate_size`` and the rest with the MoE; a final RMSNorm and an
  untied head over the slice.  Every norm is RMSNorm with ``rms_norm_eps``.
- MLA without q-LoRA: queries from one projection (nope and rope parts), a
  compressed latent (``kv_lora_rank``, normed) decompressed into per-head
  keys and values, one RoPE key shared by the heads, RoPE (theta
  ``rope_theta``) rotating the halves of the rope part at each token's
  position within its document, softmax scale 1/sqrt(nope + rope).  A query
  sees the keys at or before it in its own document; a document starts at
  each ``bos_id`` token.
- the MoE: sigmoid scores over the router's ``router_experts`` outputs;
  the top ``num_experts_per_tok`` chosen by score plus the correction bias
  (``router_bias``: N(0, std) from its seed, frozen), weighted by the plain
  score, normalised over the chosen and scaled by
  ``routed_scaling_factor``; the ``n_routed_experts`` held experts (from
  ``held_expert_start``) each a SwiGLU of width ``moe_intermediate_size``,
  computed here densely over every token (one contraction over the held
  experts) and masked by its weight; experts
  not held contribute nothing; plus ``n_shared_experts`` shared experts as
  one SwiGLU of their summed width, on every token.
- the loss: next-token cross entropy over the slice, mean over the targets
  that are not ``bos_id`` (a document's first token, predicted from another
  document); the eval: token accuracy over those targets.

The sequence-wise auxiliary loss is left out (its weight is not published),
as in the program.  RoPE here rotates halves where the published code
rotates interleaved pairs: with seeded weights the two differ by a fixed
permutation of the rope columns.

FLOPs (``group_forward_flops``, per sequence): 2 x multiply-adds of every
projection; routed experts at the uniform expectation, top-k x held/router
experts per token; attention causal over the whole sequence (documents
ignored), S^2/2 query-key pairs.

The float32 reference computes its matmuls at "highest" precision, one
sequence at a time, each block recomputed in the backward and queries in
blocks of ``Q_BLOCK``, so that it fits next to the program's buffers; the
query blocks run in a ``lax.map`` and the half-batch fault's length is an
array, so each program compiles once (at "highest" a group's local round
takes over a minute to compile for the TPU).  The
same code in bfloat16 (parameters, activations and optimizer state) is the
control that the comparison has to fail.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.resnet import batch_order, client_seed, floyd_sample

ADAM_B1, ADAM_B2 = 0.9, 0.999
FULL = -1          # the group id of a full-network (FNU) round
Q_BLOCK = 1024
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Architecture
# ---------------------------------------------------------------------------

def is_moe(cfg: dict, layer: int) -> bool:
    return layer >= cfg["first_k_dense_replace"]


def moe_layers(cfg: dict) -> list[int]:
    return [i for i in range(cfg["num_hidden_layers"]) if is_moe(cfg, i)]


def param_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    d, h, v = cfg["hidden_size"], cfg["num_attention_heads"], cfg["vocab_size"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    kv, f, e = cfg["kv_lora_rank"], cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    shapes = {"embed/table": (v, d)}
    for i in range(cfg["num_hidden_layers"]):
        b = f"blocks/{i}"
        shapes.update({
            f"{b}/attn_norm/scale": (d,),
            f"{b}/attn/wq/w": (d, h * (nope + rope)),
            f"{b}/attn/wkv_a/w": (d, kv + rope),
            f"{b}/attn/kv_norm/scale": (kv,),
            f"{b}/attn/wkv_b/w": (kv, h * (nope + vd)),
            f"{b}/attn/wo/w": (h * vd, d),
            f"{b}/mlp_norm/scale": (d,),
        })
        if is_moe(cfg, i):
            fs = f * cfg["n_shared_experts"]
            shapes.update({
                f"{b}/moe/router/w": (d, cfg["router_experts"]),
                f"{b}/moe/experts/w_gate": (e, d, f),
                f"{b}/moe/experts/w_up": (e, d, f),
                f"{b}/moe/experts/w_down": (e, f, d),
                f"{b}/moe/shared/w_gate/w": (d, fs),
                f"{b}/moe/shared/w_up/w": (d, fs),
                f"{b}/moe/shared/w_down/w": (fs, d),
            })
        else:
            ff = cfg["intermediate_size"]
            shapes.update({f"{b}/mlp/w_gate/w": (d, ff), f"{b}/mlp/w_up/w": (d, ff),
                           f"{b}/mlp/w_down/w": (ff, d)})
    shapes["final_norm/scale"] = (d,)
    shapes["head/w"] = (d, v)
    return shapes


def num_groups(cfg: dict) -> int:
    """FedPart groups: the embedding, each block, the final norm with the
    head."""
    return cfg["num_hidden_layers"] + 2


def group_of(path: str, cfg: dict) -> int:
    parts = path.split("/")
    if parts[0] == "embed":
        return 0
    if parts[0] == "blocks":
        return 1 + int(parts[1])
    return num_groups(cfg) - 1


def moe_layer_groups(cfg: dict) -> list[int]:
    """The FedPart group of each MoE layer, in order."""
    return [1 + i for i in moe_layers(cfg)]


def routed_flops_per_row(cfg: dict) -> float:
    """Forward FLOPs of one (token, held expert) row: its SwiGLU's three
    matmuls."""
    return 2.0 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def group_forward_flops(cfg: dict) -> list[float]:
    """Forward FLOPs per sequence of each group (module docstring).  The
    embedding is a lookup; norms, RoPE, softmax and the router's top-k are
    not counted."""
    d, h, s = cfg["hidden_size"], cfg["num_attention_heads"], cfg["data"]["seq_len"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    kv = cfg["kv_lora_rank"]
    attn = 2.0 * (d * h * (nope + rope) + d * (kv + rope) + kv * h * (nope + vd)
                  + h * vd * d) * s
    attn += 2.0 * h * (nope + rope + vd) * s * s / 2
    out = [0.0] * num_groups(cfg)
    for i in range(cfg["num_hidden_layers"]):
        if is_moe(cfg, i):
            f = cfg["moe_intermediate_size"]
            routed = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
                      / cfg["router_experts"]) * routed_flops_per_row(cfg)
            ffn = (2.0 * d * cfg["router_experts"] + routed
                   + 2.0 * 3 * d * f * cfg["n_shared_experts"]) * s
        else:
            ffn = 2.0 * 3 * d * cfg["intermediate_size"] * s
        out[1 + i] = attn + ffn
    out[-1] = 2.0 * d * cfg["vocab_size"] * s
    return out


def group_trained_params(cfg: dict) -> list[int]:
    out = [0] * num_groups(cfg)
    for path, shape in param_shapes(cfg).items():
        out[group_of(path, cfg)] += int(np.prod(shape))
    return out


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *heads, last = path.split("/")
        for hd in heads:
            node = node.setdefault(hd, {})
        node[last] = leaf
    return tree


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else str(k)
        if isinstance(v, dict):
            out.update(flatten(v, path))
        else:
            out[path] = v
    return out


def make_params(key: jax.Array, cfg: dict) -> dict:
    """The initial weights as the program's nested dict, float32, in one
    jitted call: the embedding N(0, 1), every projection and expert matrix
    N(0, 1/fan_in), unit norm scales."""
    shapes = param_shapes(cfg)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(shapes))
        flat = {}
        for k, (path, shape) in zip(keys, shapes.items()):
            if path.endswith("/scale"):
                flat[path] = jnp.ones(shape, jnp.float32)
            elif path == "embed/table":
                flat[path] = jax.random.normal(k, shape)
            else:
                flat[path] = jax.random.normal(k, shape) / np.sqrt(shape[-2])
        return nest(flat)

    return build(key)


def router_bias(cfg: dict) -> jax.Array:
    """(MoE layers, router experts): the frozen correction bias."""
    rb = cfg["router_bias"]
    key = jax.random.key(rb["seed"])
    return jax.random.normal(key, (len(moe_layers(cfg)), cfg["router_experts"])) * rb["std"]


# ---------------------------------------------------------------------------
# Forward pass, one sequence
# ---------------------------------------------------------------------------

def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, pos, theta):
    """Rotate the halves of the last axis; ``x`` (S, ..., D), ``pos`` (S,)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    c, s = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _attention(p, b, x, seg, pos, cfg):
    s = x.shape[0]
    h = cfg["num_attention_heads"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    kv_rank = cfg["kv_lora_rank"]
    q = (x @ p[f"{b}/attn/wq/w"]).reshape(s, h, nope + rope)
    ckr = x @ p[f"{b}/attn/wkv_a/w"]
    c = _rms(ckr[:, :kv_rank], p[f"{b}/attn/kv_norm/scale"], cfg["rms_norm_eps"])
    kv = (c @ p[f"{b}/attn/wkv_b/w"]).reshape(s, h, nope + vd)
    k_rope = _rope(ckr[:, kv_rank:], pos, cfg["rope_theta"])
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope[:, None], (s, h, rope))],
                        axis=-1)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, cfg["rope_theta"])],
                        axis=-1)
    v = kv[..., nope:]
    scale = 1.0 / np.sqrt(nope + rope)

    qlen = min(Q_BLOCK, s)

    def block(args):
        qb, start = args
        logits = jnp.einsum("qhd,khd->hqk", qb, k).astype(jnp.float32) * scale
        qi = start + jnp.arange(qlen)[:, None]
        kj = jnp.arange(s)[None, :]
        qseg = jax.lax.dynamic_slice_in_dim(seg, start, qlen)
        seen = (kj <= qi) & (qseg[:, None] == seg[None, :])
        probs = jax.nn.softmax(jnp.where(seen[None], logits, NEG_INF), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs.astype(v.dtype), v)

    out = jax.lax.map(jax.checkpoint(block),
                      (q.reshape(s // qlen, qlen, h, nope + rope),
                       jnp.arange(0, s, qlen)))
    return out.reshape(s, h * vd) @ p[f"{b}/attn/wo/w"]


def _moe(p, b, x, bias, cfg):
    scores = jax.nn.sigmoid((x @ p[f"{b}/moe/router/w"]).astype(jnp.float32))
    _, idx = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * cfg["routed_scaling_factor"]
    held = cfg["held_expert_start"] + jnp.arange(cfg["n_routed_experts"])
    we = jnp.sum(jnp.where(idx[:, :, None] == held, w[:, :, None], 0.0), axis=1)
    gate = jnp.einsum("td,edf->etf", x, p[f"{b}/moe/experts/w_gate"])
    up = jnp.einsum("td,edf->etf", x, p[f"{b}/moe/experts/w_up"])
    hid = jax.nn.silu(gate) * up * we.T[:, :, None].astype(x.dtype)
    y = jnp.einsum("etf,efd->td", hid, p[f"{b}/moe/experts/w_down"])
    return y + _swiglu(x, p[f"{b}/moe/shared/w_gate/w"], p[f"{b}/moe/shared/w_up/w"],
                       p[f"{b}/moe/shared/w_down/w"])


def _block(p, b, x, seg, pos, bias, layer, cfg):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(p, b, _rms(x, p[f"{b}/attn_norm/scale"], eps), seg, pos, cfg)
    h = _rms(x, p[f"{b}/mlp_norm/scale"], eps)
    if is_moe(cfg, layer):
        return x + _moe(p, b, h, bias, cfg)
    return x + _swiglu(h, p[f"{b}/mlp/w_gate/w"], p[f"{b}/mlp/w_up/w"],
                       p[f"{b}/mlp/w_down/w"])


def forward(p: dict, tokens: jax.Array, bias: jax.Array, cfg: dict) -> jax.Array:
    """Logits (S, vocab) of one row of packed documents, in the dtype of
    ``p``.  The caller sets the matmul precision."""
    bos = cfg["data"]["bos_id"]
    start = tokens == bos
    seg = jnp.cumsum(start)
    idx = jnp.arange(tokens.shape[0])
    pos = idx - jax.lax.cummax(jnp.where(start, idx, 0))
    x = p["embed/table"][tokens]
    layers = moe_layers(cfg)
    for i in range(cfg["num_hidden_layers"]):
        lb = bias[layers.index(i)] if is_moe(cfg, i) else None
        x = jax.checkpoint(functools.partial(_block, b=f"blocks/{i}", layer=i, cfg=cfg))(
            p, x=x, seg=seg, pos=pos, bias=lb)
    x = _rms(x, p["final_norm/scale"], cfg["rms_norm_eps"])
    return x @ p["head/w"]


def _nll_sums(p, tokens, labels, bias, cfg, upto=None):
    """Summed next-token NLL and count of the counted targets of one row;
    ``upto`` counts only the targets before that position (the half-batch
    fault passes half the row, as an array, so no new program compiles)."""
    logits = forward(p, tokens, bias, cfg).astype(jnp.float32)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    nll = jax.scipy.special.logsumexp(logits, axis=-1) - gold
    mask = labels != cfg["data"]["bos_id"]
    if upto is not None:
        mask = mask & (jnp.arange(labels.shape[0]) < upto)
    return jnp.sum(jnp.where(mask, nll, 0.0)), jnp.sum(mask)


def batch_loss(p, tokens, labels, bias, cfg, upto=None):
    """Mean NLL over the counted targets of a batch of rows."""
    sums = [_nll_sums(p, t, y, bias, cfg, upto) for t, y in zip(tokens, labels)]
    return sum(s for s, _ in sums) / jnp.maximum(sum(n for _, n in sums), 1)


# ---------------------------------------------------------------------------
# The federated round
# ---------------------------------------------------------------------------

def _precision(dtype):
    return "highest" if dtype == jnp.float32 else "default"


def _items(cfg: dict) -> tuple:
    """The keys the forward reads, as a hashable static argument."""
    keys = ("hidden_size", "num_attention_heads", "vocab_size", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "rms_norm_eps",
            "rope_theta", "num_hidden_layers", "first_k_dense_replace",
            "num_experts_per_tok", "n_routed_experts", "held_expert_start",
            "routed_scaling_factor")
    return tuple((k, cfg[k]) for k in keys) + (("data", (("bos_id", cfg["data"]["bos_id"]),)),)


def _cfg(items: tuple) -> dict:
    cfg = dict(items)
    cfg["data"] = dict(cfg["data"])
    return cfg


@functools.partial(jax.jit, static_argnames=("cfg_items", "lr", "eps"))
def _local_round(trained, frozen, xs, ys, bias, upto, *, cfg_items, lr, eps):
    """One client's local Adam over the ``trained`` leaves, one step per
    batch of ``xs``; returns them and the mean loss over the steps (each
    taken before its step's update)."""
    cfg = _cfg(cfg_items)
    dtype = next(iter(trained.values())).dtype

    def body(carry, batch):
        q, m, v, t = carry
        x, y = batch
        with jax.default_matmul_precision(_precision(dtype)):
            loss, g = jax.value_and_grad(
                lambda q: batch_loss({**frozen, **q}, x, y, bias, cfg, upto))(q)
        t = t + 1.0
        bc1, bc2 = 1.0 - ADAM_B1 ** t, 1.0 - ADAM_B2 ** t
        m = jax.tree.map(lambda a, b: ADAM_B1 * a + (1 - ADAM_B1) * b, m, g)
        v = jax.tree.map(lambda a, b: ADAM_B2 * a + (1 - ADAM_B2) * b * b, v, g)
        q = jax.tree.map(lambda w, a, b: w - (lr * (a / bc1) / (jnp.sqrt(b / bc2) + eps)
                                              ).astype(dtype), q, m, v)
        return (q, m, v, t), loss.astype(jnp.float32)

    zeros = jax.tree.map(jnp.zeros_like, trained)
    (q, _, _, _), losses = jax.lax.scan(
        body, (trained, zeros, jax.tree.map(jnp.copy, zeros), jnp.float32(0.0)),
        (xs, ys))
    return q, jnp.mean(losses)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _eval(p, x, y, bias, *, cfg_items):
    cfg = _cfg(cfg_items)
    bos = cfg["data"]["bos_id"]

    def one(row):
        t, lab = row
        with jax.default_matmul_precision(_precision(p["head/w"].dtype)):
            pred = jnp.argmax(forward(p, t, bias, cfg), axis=-1)
        counted = lab != bos
        return jnp.sum((pred == lab) & counted), jnp.sum(counted)

    hits, n = jax.lax.map(one, (x, y))
    return jnp.sum(hits) / jnp.maximum(jnp.sum(n), 1)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _grad_norms(p, x, y, bias, *, cfg_items):
    with jax.default_matmul_precision("highest"):
        g = jax.grad(lambda q: batch_loss(q, x, y, bias, _cfg(cfg_items)))(p)
    return jax.tree.map(jnp.linalg.norm, g)


@dataclasses.dataclass
class Federation:
    """The reference federation: clients' token rows on the host, the
    global weights, the cohort stream.  ``dtype`` is float32 for the
    reference and bfloat16 for the control.  Two faults, for the
    comparison's own readings: ``half_batch`` trains on the first half of
    every row's targets, ``half_cohort`` averages half of the cohort's
    clients."""

    cfg: dict
    recipe: dict            # cohort, batch, epochs, lr, eps, seed
    client_x: np.ndarray    # (clients, n, S) int32 tokens
    client_y: np.ndarray    # (clients, n, S) int32 next-token targets
    eval_x: np.ndarray
    eval_y: np.ndarray
    dtype: object = jnp.float32
    half_batch: bool = False
    half_cohort: bool = False

    def run(self, params: dict, groups: list[int]) -> dict:
        """Rounds on ``groups`` from ``params`` (the program's nested dict);
        returns each round's mean client loss and eval accuracy, the weights
        after every round (flat float32 numpy; a leaf a round left alone is
        the previous round's array), and the per-leaf gradient norms at the
        start (first client's first batch)."""
        rc, items = self.recipe, _items(self.cfg)
        p = {k: jnp.asarray(v, self.dtype) for k, v in flatten(params).items()}
        host = {k: np.asarray(v, np.float32) for k, v in flatten(params).items()}
        bias = router_bias(self.cfg).astype(self.dtype)
        rng = np.random.default_rng(rc["seed"])
        n_clients, n, seq = self.client_x.shape
        upto = jnp.int32(seq // 2 if self.half_batch else seq)
        ex, ey = jnp.asarray(self.eval_x), jnp.asarray(self.eval_y)
        out = {"loss": [], "client_loss": [], "acc": [], "params": [],
               "grad_norms": None}
        for r, group in enumerate(groups):
            names = [k for k in p if group == FULL or group_of(k, self.cfg) == group]
            picked = floyd_sample(rng, n_clients, rc["cohort"])
            news, losses = [], []
            for c in picked:
                idx = batch_order(n, rc["batch"], rc["epochs"],
                                  client_seed(rc["seed"], r, c))
                xs = jnp.asarray(self.client_x[c][idx])
                ys = jnp.asarray(self.client_y[c][idx])
                if out["grad_norms"] is None:
                    gn = _grad_norms({k: v.astype(jnp.float32) for k, v in p.items()},
                                     xs[0], ys[0], bias.astype(jnp.float32),
                                     cfg_items=items)
                    out["grad_norms"] = {k: float(v) for k, v in gn.items()}
                trained = {k: p[k] for k in names}
                frozen = {k: v for k, v in p.items() if k not in trained}
                q, loss = _local_round(trained, frozen, xs, ys, bias, upto,
                                       cfg_items=items, lr=rc["lr"], eps=rc["eps"])
                news.append(q)
                losses.append(float(loss))
            if self.half_cohort:
                news, losses = news[: len(news) // 2], losses[: len(losses) // 2]
            for k in names:
                p[k] = jnp.mean(jnp.stack([q[k] for q in news]).astype(jnp.float32),
                                axis=0).astype(self.dtype)
            del news
            host = dict(host, **{k: np.asarray(p[k], np.float32) for k in names})
            out["loss"].append(float(np.mean(losses)))
            out["client_loss"].append(losses)
            out["acc"].append(float(_eval(p, ex, ey, bias, cfg_items=items)))
            out["params"].append(host)
        return out
