"""Moonlight-16B-A3B's program against its plain reference
(``bench/reference/moonlight.py``) at the test size of its recorded trace
pair (``bench/tests/data/moonlight-16b-a3b-ep8.ctx.json``: d 64, 4 heads,
kv_lora 16, nope/rope 8/4, 8 experts of which 4 held, top-3, 1 shared,
vocabulary 256, packed documents in rows of 32 tokens) on seeded weights,
on the CPU, where both compute in float32 and agree to rounding."""

import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"
CONFIG = "moonlight-16b-a3b-ep8"
SEED = 2**31 + 77


@pytest.fixture(scope="module")
def tiny():
    meta = json.loads((DATA / f"{CONFIG}.ctx.json").read_text())
    cfg = meta["config"]
    return harness.Cell("tiny", 1, cfg, meta["traffic"], {}, [],
                        harness.load_module(ROOT, "reference", cfg["reference"]),
                        harness.load_module(ROOT, "data", cfg["data"]["kind"]), {})


def test_the_configuration_files_state_the_model_the_program_runs(tiny):
    """The benchmark's configuration (published keys, cut as ``reduced``
    says) and the test size describe the program's ``full()`` and
    ``smoke()``."""
    from repro.configs import get_config

    full = json.loads((ROOT / "bench" / "configs" / f"{CONFIG}.json").read_text())
    for cfg, smoke in ((full, False), (tiny.config, True)):
        m = get_config(cfg["program"]["args"]["arch"], smoke=smoke)
        assert cfg["program"]["args"]["smoke"] is smoke
        assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"],
                cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                cfg["intermediate_size"], cfg["moe_intermediate_size"],
                cfg["router_experts"], cfg["n_routed_experts"], cfg["held_expert_start"],
                cfg["num_experts_per_tok"], cfg["n_shared_experts"], cfg["vocab_size"],
                cfg["num_hidden_layers"], cfg["first_k_dense_replace"]) == (
            m.d_model, m.num_heads, m.kv_lora_rank, m.qk_nope_head_dim,
            m.qk_rope_head_dim, m.v_head_dim, m.d_ff, m.moe_d_ff, m.num_experts,
            m.held_experts, m.held_expert_start, m.num_experts_per_tok,
            m.num_shared_experts, m.vocab_size, m.num_layers, m.first_dense_layers)
        assert (cfg["rms_norm_eps"], cfg["rope_theta"], cfg["routed_scaling_factor"],
                cfg["router_bias"]["std"], cfg["router_bias"]["seed"]) == (
            m.rms_norm_eps, m.rope_theta, m.routed_scale, m.router_bias_std,
            m.router_bias_seed)
    ref = tiny.reference
    assert sum(ref.group_trained_params(full)) == 568_484_352
    assert ref.group_trained_params(full)[2] == 100_405_760


def test_the_decoder_loss_and_gradients_equal_the_reference(tiny):
    from repro.fl.tasks import decoder_task

    ref, cfg = tiny.reference, tiny.config
    k_data, k_weights = jax.random.split(harness.seed_key(SEED))
    cx, cy, _, _ = tiny.data.make(k_data, cfg)
    x, y = cx[0, :2], cy[0, :2]
    assert (x == cfg["data"]["bos_id"]).sum() > 2          # several documents a row
    params = ref.make_params(k_weights, cfg)
    adapter = decoder_task(**cfg["program"]["args"])
    loss, grads = jax.value_and_grad(adapter.loss)(params, x, y)
    bias = ref.router_bias(cfg)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = jax.value_and_grad(
            lambda p: ref.batch_loss(p, x, y, bias, cfg))(ref.flatten(params))
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    flat = ref.flatten(grads)
    assert sorted(flat) == sorted(ref_grads)
    for k, g in ref_grads.items():
        np.testing.assert_allclose(flat[k], g, rtol=2e-4,
                                   atol=2e-5 * float(np.abs(g).max()), err_msg=k)


def test_three_fedpart_rounds_equal_the_reference_federation(tiny):
    """Three FedPart rounds (the embedding, the dense layer, the first MoE
    layer) through ``run_federated`` with the vmap engine and fused masked
    Adam, against the reference ``Federation`` on the same cohorts and
    batches: every compared number at rounding level."""
    record = harness.drive(tiny, SEED, 0.0, False, setup=harness.COMPARED_ROUNDS,
                           whole_cycles=False)
    assert [s.group for s in record["rounds"].issued] == [0, 1, 2, 3]
    numbers = harness.compare(tiny, record, SEED)
    values = numbers["values"]
    assert numbers["counted"] == numbers["of"]
    assert values["loss_gap"] < 1e-6 and values["client_loss_gap"] < 1e-6
    assert values["acc_gap"] == 0.0
    for name in ("update_gap", "update_gap_tree", "change_gap", "change_gap_tree",
                 "update_dir_gap", "change_dir_gap"):
        assert values[name] < 1e-3, name
