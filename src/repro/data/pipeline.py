"""Batching pipeline for the FL simulation: per-client epoch iterators with
deterministic shuffling, plus a balanced held-out eval set (the paper tests
the global model on a balanced set).

Two consumers share one batch-order contract:

* the sequential engine iterates ``ClientDataset.batches`` client by client;
* the vmap engine (``repro.fl.batched``) materialises the *same* order via
  ``batch_plan`` and stacks the selected clients along a leading client axis.

Ragged clients (different dataset sizes => different step counts) are handled
by **pad-and-mask**: every client in a bucket is padded to the bucket's max
step count with repeated batches whose ``step_valid`` entry is 0 — padded
steps are computed but discarded, so results match the sequential oracle.
Clients smaller than the batch size train with ``bs = len(client)`` (exactly
like the sequential path); since a compiled program needs one static batch
shape, such clients land in their own *bucket* keyed by ``bs``.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, Sequence

import numpy as np


def batch_plan(n: int, batch_size: int, epochs: int, seed: int) -> np.ndarray:
    """Deterministic batch-index plan: ``(steps, bs)`` int array.

    ``epochs`` passes of shuffled, truncated-to-full batches (at least one
    batch per epoch even if the client has < batch_size samples).  This is
    THE batch-order contract: both engines derive their batches from it.
    """
    rng = np.random.default_rng(seed)
    bs = min(batch_size, n)
    rows = []
    for _ in range(epochs):
        order = rng.permutation(n)
        for start in range(0, max(n - bs + 1, 1), bs):
            rows.append(order[start : start + bs])
    return np.stack(rows).astype(np.int64)


@dataclasses.dataclass
class ClientDataset:
    inputs: np.ndarray      # images (N,H,W,C) or tokens (N,S)
    labels: np.ndarray      # (N,)

    def __len__(self) -> int:
        return len(self.labels)

    def batches(
        self, batch_size: int, epochs: int, seed: int
    ) -> Iterator[tuple[np.ndarray, np.ndarray]]:
        """``epochs`` passes of shuffled, truncated-to-full batches (at least
        one batch per epoch even if the client has < batch_size samples)."""
        for idx in batch_plan(len(self), batch_size, epochs, seed):
            yield self.inputs[idx], self.labels[idx]


@dataclasses.dataclass
class StackedClientBatches:
    """One bucket of same-batch-width clients, stacked along a client axis.

    ``inputs``/``labels`` carry a leading ``(clients, steps, bs, ...)`` shape;
    ``step_valid`` is ``(clients, steps)`` float32 — 0.0 marks padded steps
    whose results the batched engine discards (the pad-and-mask contract).
    ``members`` maps bucket rows back to positions in the round's picked-client
    order.  When the bucket was built with ``pad_clients_to > 1`` the client
    axis may carry trailing *padding clients* (rows ``>= len(members)``):
    copies of the first member with ``step_valid`` all zero, so they train
    nothing — the engine gives them zero aggregation weight and slices them
    off every per-client output.
    """

    inputs: np.ndarray
    labels: np.ndarray
    step_valid: np.ndarray
    members: tuple[int, ...]

    @property
    def num_clients(self) -> int:
        return self.inputs.shape[0]

    @property
    def num_real(self) -> int:
        """Clients that correspond to actual round participants."""
        return len(self.members)

    @property
    def num_steps(self) -> int:
        return self.inputs.shape[1]

    @property
    def batch_width(self) -> int:
        return self.inputs.shape[2]


@dataclasses.dataclass
class BucketPlan:
    """One bucket's batch order before any data is read.

    ``rows[c, t]`` are the indices into the dataset of round position
    ``owners[c]`` that client row ``c`` trains on at step ``t``: the
    ``batch_plan`` rows, ragged step counts padded with the first batch.
    Padding clients (rows ``>= len(members)``) repeat the first member.
    """

    members: tuple[int, ...]
    owners: tuple[int, ...]
    rows: np.ndarray          # (clients, steps, bs) int64
    step_valid: np.ndarray    # (clients, steps) float32


def plan_buckets(
    sizes: Sequence[int],
    batch_size: int,
    epochs: int,
    seeds: Sequence[int],
    *,
    pad_clients_to: int = 1,
) -> list[BucketPlan]:
    """The bucketing of ``stack_client_batches`` from the clients' sizes
    alone: one ``BucketPlan`` per effective batch width, in ascending
    width order."""
    if len(sizes) != len(seeds):
        raise ValueError("one seed per client dataset is required")
    if pad_clients_to < 1:
        raise ValueError(f"pad_clients_to must be >= 1, got {pad_clients_to}")
    buckets: dict[int, list[int]] = {}
    for pos, n in enumerate(sizes):
        buckets.setdefault(min(batch_size, n), []).append(pos)

    out = []
    for bs in sorted(buckets):
        members = buckets[bs]
        plans = [batch_plan(sizes[p], batch_size, epochs, seeds[p])
                 for p in members]
        max_steps = max(len(pl) for pl in plans)
        rows, valid = [], []
        for plan in plans:
            pad = max_steps - len(plan)
            if pad:
                plan = np.concatenate([plan, np.repeat(plan[:1], pad, axis=0)])
            rows.append(plan)
            v = np.zeros(max_steps, dtype=np.float32)
            v[: max_steps - pad] = 1.0
            valid.append(v)
        n_pad = -len(members) % pad_clients_to
        rows += [rows[0]] * n_pad
        valid += [np.zeros(max_steps, dtype=np.float32)] * n_pad
        out.append(BucketPlan(
            members=tuple(members), owners=tuple(members + members[:1] * n_pad),
            rows=np.stack(rows), step_valid=np.stack(valid)))
    return out


def stack_client_batches(
    datasets: Sequence[ClientDataset],
    batch_size: int,
    epochs: int,
    seeds: Sequence[int],
    *,
    pad_clients_to: int = 1,
) -> list[StackedClientBatches]:
    """Stack the round's clients into vmap-ready buckets.

    Clients are bucketed by effective batch width ``min(batch_size, n)`` (one
    compiled program per width); within a bucket, ragged step counts are
    padded with the client's first batch and masked out via ``step_valid``.

    ``pad_clients_to`` rounds each bucket's *client axis* up to a multiple of
    the given value by appending padding clients (first member's data,
    ``step_valid`` all zero).  The shard_map engine uses this so every device
    in the mesh receives the same per-shard client count; padding clients get
    zero aggregation weight, so results are unchanged (see
    ``StackedClientBatches``).
    """
    plans = plan_buckets([len(d) for d in datasets], batch_size, epochs,
                         seeds, pad_clients_to=pad_clients_to)
    return [StackedClientBatches(
        inputs=np.stack([datasets[o].inputs[r] for o, r in zip(b.owners, b.rows)]),
        labels=np.stack([datasets[o].labels[r] for o, r in zip(b.owners, b.rows)]),
        step_valid=b.step_valid, members=b.members) for b in plans]


def build_clients(
    inputs: np.ndarray, labels: np.ndarray, parts: list[np.ndarray]
) -> list[ClientDataset]:
    return [ClientDataset(inputs[p], labels[p]) for p in parts]


def balanced_eval_set(
    inputs: np.ndarray, labels: np.ndarray, per_class: int, seed: int = 1
) -> tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng(seed)
    picks = []
    for c in np.unique(labels):
        idx = np.where(labels == c)[0]
        picks.append(rng.choice(idx, size=min(per_class, len(idx)), replace=False))
    sel = np.concatenate(picks)
    rng.shuffle(sel)
    return inputs[sel], labels[sel]
