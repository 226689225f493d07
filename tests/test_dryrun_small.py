"""Dry-run machinery on a small host mesh, run in a subprocess so the forced
device count never leaks into other tests."""

import json
import os
import subprocess
import sys


SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, "src")
import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.launch import hlo_analysis, steps
from repro.launch.sharding import input_shardings, params_shardings
from repro.models import api
from repro.models.api import InputShape

cfg = get_config("tinyllama-1.1b", smoke=True)
mesh = jax.make_mesh((4, 2), ("data", "model"))
shape = InputShape("t", 64, 8, "train")
params_shapes = jax.eval_shape(lambda: api.init(jax.random.key(0), cfg))
p_shard = params_shardings(params_shapes, mesh)
specs = api.input_specs(cfg, shape)
b_shard = input_shardings(specs, mesh)
opt_shapes = jax.eval_shape(steps.init_opt_state, params_shapes)
opt_shard = type(opt_shapes)(
    step=NamedSharding(mesh, P()),
    m=params_shardings(opt_shapes.m, mesh),
    v=params_shardings(opt_shapes.v, mesh),
)
step = steps.make_train_step(cfg, remat=True)
with mesh:
    compiled = jax.jit(
        step, in_shardings=(p_shard, opt_shard, b_shard),
        out_shardings=(p_shard, opt_shard, NamedSharding(mesh, P())),
    ).lower(params_shapes, opt_shapes, specs).compile()

mem = hlo_analysis.extract_memory(compiled)
cost = hlo_analysis.extract_cost(compiled)
coll = hlo_analysis.collective_bytes(compiled.as_text())
print(json.dumps({
    "devices": jax.device_count(),
    "temp": mem["temp_size_in_bytes"],
    "flops": cost["flops"],
    "coll_total": coll["total_bytes"],
    "ar_count": coll["per_kind_count"]["all-reduce"],
}))
"""


def test_small_mesh_dryrun_compiles():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
        timeout=300,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["devices"] == 8
    assert out["flops"] > 0
    assert out["coll_total"] > 0      # data-parallel grads must all-reduce
    assert out["ar_count"] > 0


def test_production_mesh_shapes():
    # mesh construction itself (without devices) is covered by the dryrun
    # artifacts; here we only check the axis bookkeeping helpers.
    from repro.launch.mesh import dp_axes

    class FakeMesh:
        axis_names = ("pod", "data", "model")

    assert dp_axes(FakeMesh()) == ("pod", "data")
