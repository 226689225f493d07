"""Expert-parallel (shard_map) MoE must match the GSPMD-auto path exactly —
run in a subprocess with 8 forced host devices."""

import json
import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
import numpy as np
from repro.configs import get_config
from repro.models import api, moe_ep
from repro.models.api import InputShape

results = {}
for arch in ("deepseek-v3-671b", "llama4-maverick-400b-a17b"):
    cfg = get_config(arch, smoke=True).with_(num_experts=8)  # 8 experts / 2 model ranks
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    params = api.init(jax.random.key(0), cfg)
    shape = InputShape("t", 16, 4, "train")
    batch = api.synth_batch(jax.random.key(1), cfg, shape)

    with mesh:
        base = jax.jit(lambda p, b: api.loss(p, cfg, b))(params, batch)
        logits_base = jax.jit(lambda p, b: api.forward(p, cfg, b)[0])(params, batch)
    with moe_ep.expert_parallel(mesh):
        ep_fn = jax.jit(lambda p, b: api.loss(p, cfg, b))
        lg_fn = jax.jit(lambda p, b: api.forward(p, cfg, b)[0])
        with mesh:
            ep = ep_fn(params, batch)
            logits_ep = lg_fn(params, batch)
    # gradients too
    with mesh:
        g_base = jax.jit(jax.grad(lambda p: api.loss(p, cfg, batch)))(params)
    with moe_ep.expert_parallel(mesh):
        g_fn = jax.jit(jax.grad(lambda p: api.loss(p, cfg, batch)))
        with mesh:
            g_ep = g_fn(params)
    gdiff = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree.leaves(g_base), jax.tree.leaves(g_ep))
    )
    results[arch] = {
        "loss_base": float(base), "loss_ep": float(ep),
        "loss_diff": abs(float(base) - float(ep)), "grad_maxdiff": gdiff,
        "logits_maxdiff": float(jnp.max(jnp.abs(logits_base - logits_ep))),
    }
print(json.dumps(results))
"""


import pytest


@pytest.mark.slow
def test_ep_matches_auto():
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(__file__)), timeout=480,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    for arch, r in out.items():
        # model math must agree tightly
        assert r["logits_maxdiff"] < 2e-4, (arch, r)
        # the aux load-balance loss is computed per data shard + pmean under
        # EP (standard expert-parallel semantics) vs globally under auto —
        # a small, documented statistical difference.
        assert r["loss_diff"] < 2e-3, (arch, r)
        assert r["grad_maxdiff"] < 1e-2, (arch, r)
