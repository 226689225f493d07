"""Each round's batches gathered on the device from a population's mirror.

A materialised population copies its clients to the vmap engine's device
once (``MaterializedPopulation.device_mirror``); every round's buckets are
then gathered there by index (``DeviceMirror.stack_client_batches``).  The
buckets, and so whole runs, must equal the host stacking of
``data.pipeline.stack_client_batches`` bit for bit; streaming populations,
the shard_map engine and the async runtime keep the host path.
"""

import jax
import numpy as np
import pytest

from repro.core.schedule import FedPartSchedule
from repro.data import (ClientDataset, VisionDatasetSpec, balanced_eval_set,
                        build_clients, make_vision_dataset,
                        stack_client_batches)
from repro.fl import FLRunConfig, resnet_task, run_federated, server
from repro.fl.population import MaterializedPopulation, SyntheticPopulation
from repro.fl.runtime import engine as async_runtime


def _clients(sizes, tokens=False, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for n in sizes:
        if tokens:
            x = rng.integers(0, 50, (n, 6)).astype(np.int32)
            y = rng.integers(0, 50, (n, 6)).astype(np.int32)
        else:
            x = rng.normal(size=(n, 4, 4, 3)).astype(np.float32)
            y = rng.integers(0, 10, n).astype(np.int32)
        out.append(ClientDataset(x, y))
    return out


@pytest.mark.parametrize("sizes, epochs, pad_to, tokens", [
    ([16, 16, 16, 16, 16], 1, 1, False),      # equal clients
    ([20, 6, 33, 6, 16], 1, 1, False),        # ragged, two buckets
    ([20, 6, 33, 6, 16], 2, 1, False),        # two epochs
    ([20, 6, 33, 6, 16], 1, 4, False),        # padding clients
    ([9, 12, 12, 3, 12], 1, 2, True),         # token rows
], ids=["equal", "ragged", "epochs2", "pad4", "tokens"])
def test_gathered_buckets_equal_host_stacking(sizes, epochs, pad_to, tokens):
    pop = MaterializedPopulation(_clients(sizes, tokens))
    mirror = pop.device_mirror(jax.devices()[0])
    assert mirror is pop.mirror is not None
    assert pop.device_mirror(jax.devices()[0]) is mirror      # built once
    ids = [3, 0, 4, 2]
    seeds = [11, 12, 13, 14]
    host = stack_client_batches([pop.dataset(i) for i in ids], 8, epochs,
                                seeds, pad_clients_to=pad_to)
    dev, h2d = mirror.stack_client_batches(ids, 8, epochs, seeds,
                                           pad_clients_to=pad_to)
    assert len(dev) == len(host)
    assert h2d == sum(b.step_valid.size * b.batch_width * 4 for b in host)
    for d, h in zip(dev, host):
        assert isinstance(d.inputs, jax.Array)
        for name in ("inputs", "labels", "step_valid"):
            got, want = np.asarray(getattr(d, name)), getattr(h, name)
            assert got.dtype == want.dtype and got.shape == want.shape, name
            assert np.array_equal(got, want), name
        assert d.members == h.members


def _federation():
    spec = VisionDatasetSpec(num_classes=4, image_size=8)
    x, y = make_vision_dataset(spec, 78, seed=0)
    xe, ye = make_vision_dataset(spec, 32, seed=9)
    parts = [np.arange(0, 24), np.arange(24, 48), np.arange(48, 66),
             np.arange(66, 78)]
    return (resnet_task("resnet4", num_classes=4), build_clients(x, y, parts),
            balanced_eval_set(xe, ye, per_class=8))


class _Engines:
    """The engines ``run_federated`` builds, kept to read their state."""

    def __init__(self, monkeypatch):
        self.built = []
        real = server.make_engine

        def make_engine(*args, **kw):
            self.built.append(real(*args, **kw))
            return self.built[-1]

        monkeypatch.setattr(server, "make_engine", make_engine)
        monkeypatch.setattr(async_runtime, "make_engine", make_engine)


def test_run_with_mirror_equals_host_path(monkeypatch):
    adapter, clients, eval_set = _federation()
    rounds = FedPartSchedule(num_groups=6, warmup_rounds=1,
                             rounds_per_layer=1).rounds()[:3]
    cfg = FLRunConfig(local_epochs=1, batch_size=12, cohort_size=3,
                      engine="vmap", fused_adam=True)
    engines = _Engines(monkeypatch)
    pop = MaterializedPopulation(clients)
    dev = run_federated(adapter, pop, eval_set, rounds, cfg)
    assert pop.mirror is not None and engines.built[-1].stack_source == "device"

    from repro.fl.batched import VmapEngine
    monkeypatch.setattr(VmapEngine, "mirror_device", None)
    host_pop = MaterializedPopulation(clients)
    host = run_federated(adapter, host_pop, eval_set, rounds, cfg)
    assert host_pop.mirror is None and engines.built[-1].stack_source == "host"

    assert [h["loss"] for h in dev.history] == [h["loss"] for h in host.history]
    assert [h["acc"] for h in dev.history] == [h["acc"] for h in host.history]
    for a, b in zip(jax.tree.leaves(dev.params), jax.tree.leaves(host.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("engine, streaming, runtime, source", [
    ("vmap", False, "sync", "device"),
    ("vmap", True, "sync", "host"),
    ("shard_map", False, "sync", "host"),
    ("vmap", False, "async", "host"),
])
def test_which_path_a_round_takes(monkeypatch, engine, streaming, runtime,
                                  source):
    adapter, clients, eval_set = _federation()
    if streaming:
        pop = SyntheticPopulation(VisionDatasetSpec(num_classes=4, image_size=8),
                                  population=50, samples_per_client=24)
    else:
        pop = MaterializedPopulation(clients)
    rounds = FedPartSchedule(num_groups=6, warmup_rounds=1,
                             rounds_per_layer=1).rounds()[:1]
    cfg = FLRunConfig(local_epochs=1, batch_size=12, cohort_size=2,
                      engine=engine, runtime=runtime)
    engines = _Engines(monkeypatch)
    run_federated(adapter, pop, eval_set, rounds, cfg)
    assert engines.built[-1].stack_source == source
    assert (pop.mirror is not None) == (source == "device")
