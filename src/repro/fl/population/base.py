"""``ClientPopulation`` — client state produced on demand from (seed, id).

The historical simulator API materialises every client host-side
(``run_federated(clients_data: Sequence, ...)``), which caps the fleet at a
few hundred clients.  Production federations sample cohorts from populations
of 10^6–10^8 mostly-offline devices; only the sampled cohort should ever
cost memory or compute (docs/POPULATION.md).

A ``ClientPopulation`` is the lazy contract behind that:

* ``num_clients``            — the population size N (a number, not a list);
* ``dataset(client_id)``     — that client's shard, built (or fetched from a
  bounded cache) on demand;
* ``num_samples(client_id)`` — the shard size *without* building the arrays
  (aggregation weights and virtual-time cost books need only this);
* ``capacity_tier(client_id)`` — the client's capacity tier for per-client
  layer plans (stable in ``client_id``, never an O(N) table).

``MaterializedPopulation`` wraps today's ``Sequence[ClientDataset]`` so the
legacy call signature keeps working verbatim: ``as_population`` is the single
adapter seam both runtimes go through.

A materialised population can also hold a ``DeviceMirror``: every client's
data copied once to one device, from which an engine gathers each round's
batches by index instead of stacking them on the host and copying them over
(``device_mirror``; streaming populations have none).
"""

from __future__ import annotations

import abc
import dataclasses
import functools
from typing import Sequence

import jax
import numpy as np

from repro.data.pipeline import (ClientDataset, StackedClientBatches,
                                 plan_buckets)


class ClientPopulation(abc.ABC):
    """Lazy client-state factory: everything is a function of (seed, id)."""

    mirror: DeviceMirror | None = None   # what device_mirror built, if any

    @property
    @abc.abstractmethod
    def num_clients(self) -> int:
        """Population size N.  Only ever used as a sampling bound."""

    @abc.abstractmethod
    def dataset(self, client_id: int) -> ClientDataset:
        """The client's dataset shard, produced on demand.  Must be
        deterministic in (population seed, client_id): two calls — or two
        processes — see identical arrays."""

    def num_samples(self, client_id: int) -> int:
        """Shard size without materialising it.  Subclasses with a cheap
        closed form should override; the default builds the shard."""
        return len(self.dataset(client_id))

    def capacity_tier(self, client_id: int, num_tiers: int) -> int:
        """Stable capacity-tier assignment (round-robin by id — matches
        ``core.schedule.PlanAssigner.tier_of``, so plan semantics are
        identical whether the fleet is materialised or streamed)."""
        return int(client_id) % max(1, num_tiers)

    def _check_id(self, client_id: int) -> int:
        cid = int(client_id)
        if not 0 <= cid < self.num_clients:
            raise IndexError(
                f"client_id {cid} out of range for population of "
                f"{self.num_clients}")
        return cid

    def device_mirror(self, device) -> DeviceMirror | None:
        """Every client's data on ``device``, or ``None`` where this
        population keeps its data on the host (always, unless overridden)."""
        return None

    def materialize(self) -> list[ClientDataset]:
        """Eagerly build every shard (tests / tiny populations only)."""
        n = self.num_clients
        if n > 100_000:
            raise ValueError(
                f"refusing to materialize {n} clients host-side; sample a "
                "cohort instead (that is the point of this class)")
        return [self.dataset(i) for i in range(n)]


class MaterializedPopulation(ClientPopulation):
    """The legacy path as a (trivial) population: a host-side ``Sequence`` of
    ``ClientDataset``.  O(1) per lookup, nothing lazy — exists so both
    runtimes speak only ``ClientPopulation``."""

    def __init__(self, clients: Sequence[ClientDataset]):
        self._clients = list(clients)
        if not self._clients:
            raise ValueError("population must contain at least one client")

    @property
    def num_clients(self) -> int:
        return len(self._clients)

    def dataset(self, client_id: int) -> ClientDataset:
        return self._clients[self._check_id(client_id)]

    def num_samples(self, client_id: int) -> int:
        return len(self._clients[self._check_id(client_id)])

    def device_mirror(self, device) -> DeviceMirror | None:
        """Build, once per device, the ``DeviceMirror`` of every client (kept
        as ``mirror``) on ``device``, JAX's default device.  ``None`` when
        ``device`` is ``None``, when the clients' inputs or labels differ in
        trailing shape or dtype, or when the mirror would take more than half
        of the device's free memory (``memory_stats``; a device that reports
        none, such as the CPU, takes it).

        The copy is left uncommitted, as a host array handed to a jitted
        program is: a committed one would commit every array computed from
        it, the round's params among them, and each program would then
        compile again for the committed params."""
        if device is None:
            return None
        if self.mirror is not None and self.mirror.device == device:
            return self.mirror
        first = self._clients[0]
        if any(c.inputs.shape[1:] != first.inputs.shape[1:]
               or c.inputs.dtype != first.inputs.dtype
               or c.labels.shape[1:] != first.labels.shape[1:]
               or c.labels.dtype != first.labels.dtype
               for c in self._clients):
            return None
        nbytes = sum(c.inputs.nbytes + c.labels.nbytes for c in self._clients)
        stats = device.memory_stats() or {}
        if "bytes_limit" in stats and nbytes > (
                stats["bytes_limit"] - stats.get("bytes_in_use", 0)) // 2:
            return None
        self.mirror = DeviceMirror(
            device=device,
            inputs=jax.device_put(np.concatenate(
                [_flat_rows(c.inputs) for c in self._clients])),
            labels=jax.device_put(np.concatenate(
                [_flat_rows(c.labels) for c in self._clients])),
            offsets=np.concatenate([[0], np.cumsum([len(c) for c in self._clients])]),
            input_shape=tuple(first.inputs.shape[1:]),
            label_shape=tuple(first.labels.shape[1:]))
        return self.mirror


def _flat_rows(a: np.ndarray) -> np.ndarray:
    """One row per sample, its trailing axes flattened: a wide minor axis
    keeps the device copy compact (an image's minor axis is its 3
    channels)."""
    return a.reshape(len(a), -1) if a.ndim > 2 else a


@functools.partial(jax.jit, static_argnums=(3, 4))
def _take(inputs, labels, idx, input_shape, label_shape):
    return (inputs[idx].reshape(idx.shape + input_shape),
            labels[idx].reshape(idx.shape + label_shape))


@dataclasses.dataclass(frozen=True)
class DeviceMirror:
    """A materialised population's data on one device: ``inputs`` and
    ``labels`` concatenated over clients in client order (one row per
    sample, in the host arrays' dtype), client ``i`` at rows
    ``offsets[i]:offsets[i + 1]``."""

    device: jax.Device
    inputs: jax.Array
    labels: jax.Array
    offsets: np.ndarray
    input_shape: tuple[int, ...]
    label_shape: tuple[int, ...]

    @property
    def nbytes(self) -> int:
        return self.inputs.nbytes + self.labels.nbytes

    @property
    def num_clients(self) -> int:
        return len(self.offsets) - 1

    def stack_client_batches(
        self,
        client_ids: Sequence[int],
        batch_size: int,
        epochs: int,
        seeds: Sequence[int],
        *,
        pad_clients_to: int = 1,
    ) -> tuple[list[StackedClientBatches], int]:
        """``data.pipeline.stack_client_batches`` of these clients, gathered
        on the device: the same buckets, values, shapes and dtypes, with
        device arrays for ``inputs`` and ``labels``.  Only the index arrays
        (one global row per sample) cross from the host; their bytes come
        back as the second value."""
        ids = np.asarray(client_ids, dtype=np.int64)
        starts, sizes = self.offsets[ids], np.diff(self.offsets)[ids]
        out, h2d = [], 0
        for b in plan_buckets(sizes.tolist(), batch_size, epochs, seeds,
                              pad_clients_to=pad_clients_to):
            idx = (starts[list(b.owners), None, None] + b.rows).astype(np.int32)
            inputs, labels = _take(self.inputs, self.labels, idx,
                                   self.input_shape, self.label_shape)
            out.append(StackedClientBatches(
                inputs=inputs, labels=labels, step_valid=b.step_valid,
                members=b.members))
            h2d += idx.nbytes
        return out, h2d


def as_population(clients) -> ClientPopulation:
    """The adapter seam: pass ``ClientPopulation`` through, wrap a legacy
    ``Sequence[ClientDataset]`` in ``MaterializedPopulation``."""
    if isinstance(clients, ClientPopulation):
        return clients
    return MaterializedPopulation(clients)
