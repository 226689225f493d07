"""Seeded rows of packed documents over a vocabulary slice, one set per
silo (cross-silo FedPart fine-tuning, topic-skewed).

Each silo draws its tokens from a Zipf law (exponent ``zipf``) over its own
permutation of the slice's ids, so that the silos' frequent tokens, and so
the experts their tokens are routed to, differ.  Documents have lognormal
lengths (median ``doc_median``, log-sd ``doc_sigma``) clipped to
``doc_min``..``doc_max`` tokens; each begins with ``bos_id``, which no other
token takes, and they are packed one after another into rows of
``seq_len`` tokens, the last cut at the row's end.  A row's targets are its
next tokens, with ``bos_id`` at the last position (no next token in the
row): the program and the reference skip every ``bos_id`` target.

The eval rows are held out: row ``j`` is drawn as silo ``j`` draws.
Generation is vectorised numpy from the run's key (a fraction of a second
for the cell's 68 rows).
"""

from __future__ import annotations

import jax
import numpy as np


def _rows(rng, data: dict, perm: np.ndarray, probs: np.ndarray, count: int):
    s, bos = data["seq_len"], data["bos_id"]
    tokens = perm[rng.choice(len(perm), size=(count, s), p=probs)].astype(np.int32)
    for r in range(count):
        pos = 0
        while pos < s:
            tokens[r, pos] = bos
            length = rng.lognormal(np.log(data["doc_median"]), data["doc_sigma"])
            pos += int(np.clip(round(length), data["doc_min"], data["doc_max"]))
    labels = np.concatenate([tokens[:, 1:], np.full((count, 1), bos, np.int32)], axis=1)
    return tokens, labels


def make(key: jax.Array, cfg: dict) -> tuple[np.ndarray, ...]:
    """(client_x (silos, n, S) int32 tokens, client_y (silos, n, S) int32
    targets, eval_x (E, S), eval_y (E, S)) for the configuration ``cfg``
    (its ``vocab_size`` and its ``data`` section)."""
    data = cfg["data"]
    rng = np.random.default_rng(np.asarray(jax.random.key_data(key)).tolist())
    ids = np.delete(np.arange(cfg["vocab_size"]), data["bos_id"])
    probs = np.arange(1, len(ids) + 1, dtype=np.float64) ** -data["zipf"]
    probs /= probs.sum()
    perms = [rng.permutation(ids) for _ in range(data["num_clients"])]
    silos = [_rows(rng, data, perm, probs, data["samples_per_client"]) for perm in perms]
    held = [_rows(rng, data, perms[j % len(perms)], probs, 1)
            for j in range(data["eval_samples"])]
    cx, cy = (np.stack([s[i] for s in silos]) for i in (0, 1))
    ex, ey = (np.concatenate([h[i] for h in held]) for i in (0, 1))
    return cx, cy, ex, ey
