"""Compile the repo's Pallas kernels for a described TPU v5e, with no chip.

The TPU compiler is installed with JAX and compiles for a topology that is
described rather than attached, so a layout Mosaic refuses (a block shape off
the (8, 128) tiling, a rank-1 SMEM block) fails here on the CPU instead of on
the chip.  Nothing runs: these tests say nothing about results or speed.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library, and
every test worker imports this file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.fl import resnet_task
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.masked_adam import ops as madam_ops
from repro.kernels.masked_adam.kernel import LANES, masked_adam_kernel
from repro.kernels.ssd_chunk import ops as ssd_ops

BLOCK_ROWS = 8
COHORT = 8
SEQ = 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def resnet18_rows():
    """Packed (rows, 128) size of ResNet-18 at CIFAR-100 width, from shapes."""
    adapter = resnet_task("resnet18", num_classes=100)
    shapes = jax.eval_shape(adapter.init, jax.random.key(0))
    return madam_ops.packed_rows(shapes, BLOCK_ROWS)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _adam(p, g, m, v, mask, sc):
    return masked_adam_kernel(p, g, m, v, mask, sc, block_rows=BLOCK_ROWS,
                              interpret=False)


def _adam_args(one_chip, rows, lead=(), mask_lead=()):
    def s(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    t = s(lead + (rows, LANES))
    return (t, t, t, t, s(mask_lead + (rows // BLOCK_ROWS,), jnp.int32),
            s(lead + (4,)))


def test_masked_adam_compiles_at_resnet18_size(one_chip, resnet18_rows):
    assert resnet18_rows > 80_000
    _compile(_adam, *_adam_args(one_chip, resnet18_rows))


@pytest.mark.parametrize("batched_mask", [False, True],
                         ids=["one_group_mask", "per_client_plan_mask"])
def test_masked_adam_vmapped_over_cohort_compiles(one_chip, resnet18_rows,
                                                  batched_mask):
    """The engines vmap the kernel over the cohort: a shared mask for a
    homogeneous round, one mask per client for a layer-plan round, and Adam
    scalars per client always (each client keeps its own step count)."""
    mask_axis = 0 if batched_mask else None
    fn = jax.vmap(_adam, in_axes=(0, 0, 0, 0, mask_axis, 0))
    args = _adam_args(one_chip, resnet18_rows, lead=(COHORT,),
                      mask_lead=(COHORT,) if batched_mask else ())
    _compile(fn, *args)


def test_masked_adam_op_carries_its_name_and_scope(one_chip):
    """The kernel's op is named after ``masked_adam`` and its name-stack
    path holds the ``masked_adam`` scope (``core.telemetry.SPANS``) through
    the vmap over the cohort, both from the ``pallas_call``'s name, so a
    trace of any program finds it by name."""
    fn = jax.vmap(_adam, in_axes=(0, 0, 0, 0, None, 0))
    text = _compile(fn, *_adam_args(one_chip, 8 * BLOCK_ROWS,
                                    lead=(COHORT,))).as_text()
    [call] = [ln for ln in text.splitlines()
              if 'custom_call_target="tpu_custom_call"' in ln]
    assert re.match(r"\s*(ROOT )?%\S*masked_adam", call)
    assert re.search(r'op_name="[^"]*masked_adam[^"/]*/pallas_call"', call)


@pytest.mark.parametrize("head_dim", [64, 128])
def test_flash_attention_forward_compiles(one_chip, head_dim):
    x = jax.ShapeDtypeStruct((1, SEQ, 4, head_dim), jnp.bfloat16,
                             sharding=one_chip)
    _compile(lambda q, k, v: fa_ops.flash_attention(q, k, v, interpret=False),
             x, x, x)


def test_ssd_chunk_compiles(one_chip):
    x = jax.ShapeDtypeStruct((1, SEQ, 4, 128), jnp.float32, sharding=one_chip)
    log_a = jax.ShapeDtypeStruct((1, SEQ, 4), jnp.float32, sharding=one_chip)
    _compile(lambda q, k, v, a: ssd_ops.ssd_scan(q, k, v, a, chunk=128,
                                                 interpret=False),
             x, x, x, log_a)


def _kernel_p_rows(text):
    """Rows of the masked-Adam kernel's ``p`` operand (operand 2) in a
    compiled program's text, from the custom call's operand layouts."""
    [call] = [ln for ln in text.splitlines()
              if 'custom_call_target="tpu_custom_call"' in ln]
    shapes = call.split("operand_layout_constraints={", 1)[1]
    p = re.findall(r"\w+\[([\d,]*)\]", shapes)[2]
    return int(p.split(",")[-2])


def test_fused_partial_round_streams_only_the_trained_group(one_chip,
                                                            resnet18_rows,
                                                            monkeypatch):
    """The vmap engine's fused local round of ResNet-18 at published widths,
    for its largest group (14: a 3x3x512x512 convolution and its BN's scale
    and bias, 2,360,320 parameters) against FNU's, on a small cohort: the
    kernel's ``p`` operand holds the group's packed trained rows, not the
    whole model's, no buffer is sized to the whole packed model, and the
    program needs less scratch memory than FNU's."""
    import repro.fl.client as client
    from repro.fl import AlgoConfig, LocalTrainer, make_engine
    from repro.optim.adam import AdamConfig

    # compile the kernel, not its interpret-mode emulation
    monkeypatch.setattr(client, "default_interpret", lambda: False)
    adapter = resnet_task("resnet18", num_classes=100)
    shapes = jax.eval_shape(adapter.init, jax.random.key(0))
    part = adapter.partition(shapes)
    trainer = LocalTrainer(adapter=adapter, partition=part, algo=AlgoConfig(),
                           adam=AdamConfig(), fused=True)
    engine = make_engine("vmap", trainer=trainer, partition=part,
                         algo=AlgoConfig())

    def s(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    clients, steps, batch = 2, 2, 8
    params = jax.tree.map(lambda x: s(x.shape, x.dtype), shapes)
    args = (params, s((clients, steps, batch, 32, 32, 3)),
            s((clients, steps, batch), jnp.int32), s((clients, steps)), params)
    partial = _compile(engine._local_fn(14, False), *args)
    full = _compile(engine._local_fn(-1, False), *args)

    trained = trainer.kernel_rows(shapes, 14)
    assert trained == 18_448                 # 18,432 + 8 + 8 rows
    assert _kernel_p_rows(partial.as_text()) == trained
    assert _kernel_p_rows(full.as_text()) == resnet18_rows
    assert f",{resnet18_rows},{LANES}]" not in partial.as_text()
    assert partial.memory_analysis().temp_size_in_bytes \
        < full.memory_analysis().temp_size_in_bytes


def test_held_expert_layer_compiles_at_moonlight_width(one_chip, monkeypatch):
    """Moonlight's held-expert MoE layer at published widths (d 2,048,
    expert width 1,408, 8 of 64 experts held, top-6), forward and backward,
    vmapped over a cohort of 2 with one 4,096-token row each: the grouped
    matmuls compile as Pallas kernels (three forward; per product, the
    input and the weight gradient backward) inside the scope the benchmark
    reads."""
    import repro.kernels as kernels
    from repro.configs import get_config
    from repro.models import moe

    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    cfg = get_config("moonlight-16b-a3b")
    params = jax.eval_shape(lambda k: moe.held_moe_init(k, cfg, jnp.float32),
                            jax.random.key(0))

    def s(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def loss(p, x, bias):
        y, held = moe.held_moe_apply(p, cfg, x, bias)
        return jnp.sum(y * y) + held

    fn = jax.vmap(jax.grad(loss, argnums=(0, 1)), in_axes=(None, 0, None))
    text = _compile(fn, jax.tree.map(lambda a: s(a.shape), params),
                    s((2, 1, 4096, cfg.d_model)), s((cfg.num_experts,))).as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 9
    assert all(re.search(r'op_name="[^"]*moe_experts', ln) for ln in calls)
