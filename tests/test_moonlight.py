"""The packed-document decoder of Moonlight-16B-A3B at its CPU test size
(``configs.moonlight_16b_a3b.smoke``: d 64, 4 heads, kv_lora 16, nope/rope
8/4, 8 experts of which 4 held, top-3, 1 shared, vocabulary 256): the
held-expert MoE layer as one chip's share, its dropless routing, the FedPart
partition of the unstacked layers, and the round counter on the losses'
``fl.wait`` span.  The comparison with the plain reference is in
``bench/tests/test_bench_moonlight.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.core.schedule import RoundSpec
from repro.data.pipeline import ClientDataset
from repro.fl import FLRunConfig, run_federated
from repro.fl.tasks import decoder_task
from repro.models import moe
from repro.models.layers import mlp_apply

CFG = get_config("moonlight-16b-a3b", smoke=True)
TOKENS = 32


def _x(key=1):
    return jax.random.normal(jax.random.key(key), (1, TOKENS, CFG.d_model))


def _dense_routed(p, cfg, x, bias):
    """The held experts' part by a plain loop: each held expert over every
    token, weighted by its routing weight (zero where not chosen)."""
    xf = x.reshape(-1, cfg.d_model)
    scores = jax.nn.sigmoid(xf @ p["router"]["w"])
    _, idx = jax.lax.top_k(scores + bias, cfg.num_experts_per_tok)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * cfg.routed_scale
    y = jnp.zeros_like(xf)
    for e in range(cfg.held_experts):
        we = jnp.sum(jnp.where(idx == cfg.held_expert_start + e, w, 0.0), axis=-1)
        ew = {k: v[e] for k, v in p["experts"].items()}
        h = jax.nn.silu(xf @ ew["w_gate"]) * (xf @ ew["w_up"])
        y = y + we[:, None] * (h @ ew["w_down"])
    return y.reshape(x.shape)


def _share(p, start, n):
    return dict(p, experts={k: v[start: start + n] for k, v in p["experts"].items()})


def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_layer():
    """Two chips of 4 held experts each: their results, with the shared
    expert that both compute counted once, add up to one layer holding all
    8, and their held rows to every (token, slot) pair."""
    uncut = CFG.with_(held_experts=CFG.num_experts)
    p = moe.held_moe_init(jax.random.key(0), uncut, jnp.float32)
    bias = moe.router_bias(CFG, 1)[0]
    x = _x()
    y_all, n_all = moe.held_moe_apply(p, uncut, x, bias)
    shared = mlp_apply(p["shared"], CFG.mlp_kind, x)
    h = CFG.held_experts
    ya, na = moe.held_moe_apply(_share(p, 0, h), CFG, x, bias)
    yb, nb = moe.held_moe_apply(_share(p, h, h), CFG.with_(held_expert_start=h), x, bias)
    np.testing.assert_allclose(ya + yb - shared, y_all, rtol=1e-5, atol=1e-5)
    assert float(na) + float(nb) == float(n_all) == TOKENS * CFG.num_experts_per_tok


@pytest.mark.parametrize("forced", [None, 0, 3])
def test_routing_drops_no_token(forced):
    """The share's routed part equals the plain per-expert loop for every
    token, also when the bias forces every token onto one held expert (a
    capacity-bounded layer would drop most of them)."""
    p = moe.held_moe_init(jax.random.key(2), CFG, jnp.float32)
    bias = moe.router_bias(CFG, 1)[0]
    if forced is not None:
        bias = bias.at[CFG.held_expert_start + forced].add(100.0)
    x = _x(3)
    y, held = moe.held_moe_apply(p, CFG, x, bias)
    routed = y - mlp_apply(p["shared"], CFG.mlp_kind, x)
    np.testing.assert_allclose(routed, _dense_routed(p, CFG, x, bias),
                               rtol=1e-5, atol=1e-5)
    scores = jax.nn.sigmoid(x.reshape(TOKENS, -1) @ p["router"]["w"])
    _, idx = jax.lax.top_k(scores + bias, CFG.num_experts_per_tok)
    local = np.asarray(idx) - CFG.held_expert_start
    assert float(held) == np.sum((local >= 0) & (local < CFG.held_experts))
    if forced is not None:
        assert np.all(np.asarray(idx)[:, 0] == CFG.held_expert_start + forced)
        assert float(held) >= TOKENS


def test_packed_attention_in_query_blocks_is_one_block(monkeypatch):
    """``mla_packed`` over queries in blocks of 8 (each against the keys up
    to its end) gives the loss and gradients of one block over the row."""
    from repro.models import attention

    adapter = decoder_task(smoke=True)
    params = adapter.init(jax.random.key(3))
    t = np.random.default_rng(3).integers(1, CFG.vocab_size, (2, TOKENS)).astype(np.int32)
    t[:, [0, 11, 20]] = 0                                  # three documents a row
    y = np.concatenate([t[:, 1:], np.zeros((2, 1), np.int32)], axis=1)
    whole = jax.value_and_grad(adapter.loss)(params, t, y)
    monkeypatch.setattr(attention, "PACKED_Q_BLOCK", 8)
    blocked = jax.value_and_grad(adapter.loss)(params, t, y)
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(blocked)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5 * float(np.abs(a).max()))


def test_the_partition_is_fedpart_groups_of_whole_leaves_without_the_bias():
    adapter = decoder_task(smoke=True)
    params = jax.eval_shape(adapter.init, jax.random.key(0))
    part = adapter.partition(params)
    assert part.group_keys == (("embed",),) + tuple(
        ("block", "blocks", i) for i in range(CFG.num_layers)) + (("head",),)
    paths = ["/".join(str(k.key) for k in path)
             for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]]
    assert sorted(part.assignment) == sorted(paths)      # each leaf in one group
    assert [part.group_of(p) for p in ("embed/table", "blocks/0/mlp/w_up/w",
                                       "blocks/4/moe/experts/w_down",
                                       "final_norm/scale", "head/w")] == [0, 1, 5, 6, 6]
    # the correction bias is no parameter: it lives in the adapter, frozen
    assert not any("bias" in p for p in paths)


def test_the_round_counter_rides_on_the_losses_wait(tmp_path):
    """A traced fused round sets ``held_assignments`` on its losses'
    ``fl.wait``: one count per MoE layer, summed over clients and steps."""
    adapter = decoder_task(smoke=True)
    rng = np.random.default_rng(0)

    def rows(n):
        t = rng.integers(1, CFG.vocab_size, (n, TOKENS)).astype(np.int32)
        t[:, 0] = 0
        return t, np.concatenate([t[:, 1:], np.zeros((n, 1), np.int32)], axis=1)

    clients = [ClientDataset(*rows(2)) for _ in range(2)]
    cfg = FLRunConfig(local_epochs=1, batch_size=1, cohort_size=2, engine="vmap",
                      fused_adam=True)
    with jax.profiler.trace(str(tmp_path)):
        run_federated(adapter, clients, rows(1), [RoundSpec(0, "partial", 0, 6)], cfg)
    data = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
    waits = [dict(e.stats) for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events if e.name == "fl.wait"]
    [held] = [st["held_assignments"] for st in waits if st["what"] == "losses"]
    counts = [float(v) for v in str(held).split()]
    moe_layers = CFG.num_layers - CFG.first_dense_layers
    # 2 clients x 2 steps x 32 tokens x top-3, half the experts held
    assert len(counts) == moe_layers
    assert all(0 < c <= 2 * 2 * TOKENS * CFG.num_experts_per_tok for c in counts)
