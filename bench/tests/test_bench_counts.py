"""The benchmark's work counts against hand counts, and its picture of the
model against the program's parameter tree and layer groups."""

import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import counts
from bench.reference import resnet

ROOT = Path(__file__).resolve().parents[2]


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def test_resnet18_forward_flops_match_the_hand_count():
    f = resnet.group_forward_flops(config("resnet18-cifar100"))
    assert len(f) == 18
    assert f[0] == 2 * 9 * 3 * 64 * 32 * 32                  # stem, 3.5 M
    assert f[1:5] == [2 * 9 * 64 * 64 * 32 * 32] * 4          # 75.5 M each
    stage = 2 * 9 * 64 * 128 * 16 * 16 + 2 * 64 * 128 * 16 * 16 + 3 * 75_497_472
    assert sum(f[5:9]) == stage == 268_435_456                # 37.7 + 4.2 + 3 x 75.5 M
    assert f[-1] == 2 * 512 * 100                             # head, 0.1 M
    assert sum(f) == pytest.approx(1.11e9, rel=1e-3)


def test_step_flops_fnu_and_partial():
    f = resnet.group_forward_flops(config("resnet18-cifar100"))
    assert counts.step_flops_per_sample(f, counts.FULL) == 3 * sum(f)
    assert counts.step_flops_per_sample(f, 17) == sum(f) + 2 * f[17]
    assert counts.step_flops_per_sample(f, 0) == 2 * sum(f) + f[0]
    mean = np.mean([counts.step_flops_per_sample(f, g) for g in range(18)])
    assert mean == pytest.approx(1.75e9, rel=5e-3)


def test_adam_bytes_count_trained_rows_only():
    assert counts.adam_bytes(1) == 7 * 4
    assert counts.adam_bytes(0) == 0
    trained = resnet.group_trained_params(config("resnet18-cifar100"))
    assert trained[0] == 3 * 3 * 3 * 64 + 2 * 64
    assert sum(trained) == 11_220_132


RESNET8 = {"stages": [1, 1, 2], "channels": [16, 32, 64], "num_classes": 10,
           "image_size": 32, "in_channels": 3,
           "program": {"adapter": "resnet_task",
                       "args": {"depth": "resnet8", "num_classes": 10}}}


@pytest.mark.parametrize("name", ["resnet18-cifar100", "resnet8"])
def test_reference_weights_fit_the_program(name):
    """The benchmark's weights have the program's tree, shapes and layer
    groups: the program trains what the reference trains."""
    from repro.fl import tasks

    cfg = RESNET8 if name == "resnet8" else config(name)
    adapter = getattr(tasks, cfg["program"]["adapter"])(**cfg["program"]["args"])
    prog = jax.eval_shape(adapter.init, jax.random.key(0))
    ours = jax.eval_shape(lambda k: resnet.make_params(k, cfg), jax.random.key(0))
    assert jax.tree.structure(prog) == jax.tree.structure(ours)
    assert jax.tree.map(lambda a: a.shape, prog) == jax.tree.map(lambda a: a.shape, ours)
    flat = resnet.flatten(prog)
    assert {k: v.shape for k, v in flat.items()} == resnet.param_shapes(cfg)
    partition = adapter.partition(prog)
    assert partition.num_groups == resnet.num_groups(cfg)
    for path in flat:
        assert partition.group_of(path) == resnet.group_of(path, cfg), path
