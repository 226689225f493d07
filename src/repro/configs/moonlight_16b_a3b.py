"""moonlight-16b-a3b [moe] — a DeepSeek-V3 block: MLA without q-LoRA, 64
routed experts top-6 with sigmoid ``noaux_tc`` scoring, 2 shared experts
[https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json].

Published: 27 layers (the first dense, width 11,264), d_model 2,048, 16
heads, kv_lora 512, qk nope/rope 128/64, v 128, rope theta 50,000; 64
routed experts of width 1,408, top-6, 2 shared, routed scale 2.446,
normalised top-k weights, ``n_group``/``topk_group`` 1; vocabulary 163,840,
untied head; RMSNorm eps 1e-5.

``full()`` is one chip's share of a cross-silo FedPart deployment in which
each layer is divided over 8 chips by expert parallelism and the absent
layers lie on further chips as pipeline stages: this chip holds 8 of the 64
experts of each MoE layer (the router keeps its 64 outputs and top-6),
20,480 of the 163,840 vocabulary rows, and the dense layer plus 4 MoE
layers — 568.5 M parameters, every width as published.  Its layers are
held unstacked (``transformer.packed_decoder_*``), so each FedPart group is
whole leaves.  ``smoke()`` is the CPU test size.
"""

from repro.configs.base import ModelConfig, register

SOURCE = "https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json"


def full() -> ModelConfig:
    return ModelConfig(
        name="moonlight-16b-a3b",
        family="moe",
        kind="decoder",
        source=SOURCE,
        num_layers=5,                 # published 27: the dense layer + 4 MoE
        d_model=2048,
        num_heads=16,
        num_kv_heads=16,
        d_ff=11264,                   # the leading dense layer
        vocab_size=20480,             # published 163,840: this chip's slice
        rms_norm_eps=1e-5,
        rope_theta=50_000.0,
        num_experts=64,
        num_experts_per_tok=6,
        num_shared_experts=2,
        moe_d_ff=1408,
        first_dense_layers=1,
        router_score="sigmoid",
        held_experts=8,               # of 64: one chip of 8-way expert parallelism
        held_expert_start=0,
        routed_scale=2.446,
        router_bias_std=0.01,
        router_bias_seed=20250222,
        use_mla=True,
        q_lora_rank=0,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        param_dtype="float32",
        activation_dtype="float32",
    )


def smoke() -> ModelConfig:
    return full().with_(
        d_model=64,
        num_heads=4,
        num_kv_heads=4,
        d_ff=128,
        vocab_size=256,
        num_experts=8,
        num_experts_per_tok=3,
        num_shared_experts=1,
        moe_d_ff=32,
        held_experts=4,
        kv_lora_rank=16,
        qk_nope_head_dim=8,
        qk_rope_head_dim=4,
        v_head_dim=8,
    )


register("moonlight-16b-a3b", full, smoke)
