"""The comparison that decides ``correct``: the program's first rounds against
the plain reference's, on the same weights, data, cohorts and batch order.

Numbers compared (each against the limit in ``bench/limits/<cell>.json``):

- ``loss_gap``: the largest relative gap of a round's mean client loss, over
  the compared rounds.
- ``acc_gap``: the largest gap of a round's eval accuracy, in eval samples.
- ``update_gap``: by the worst leaf, the gap between the norms of the first
  round's change of the global weights (program against reference), over
  the larger of the reference's norm of that leaf and of the median leaf.
  The first round's change is what the server gets from the clients'
  optimizers after their first local round; their state never leaves them.
- ``change_gap``: the same for the change over all the compared rounds.
- ``client_loss_gap_first``: the largest relative gap of one client's mean
  loss in the first round.  The first round starts from the same weights on
  both sides, so only the forward's rounding and the first steps' drift
  separate a sound program from the reference; a client that trains on part
  of its batches reads the sampling error of its loss.
- ``update_dir_gap`` and ``change_dir_gap``: over the leaves the reference
  moved, the norm of the difference between the program's and the
  reference's weights after the first round (after all compared rounds),
  over the norm of the reference's change.  Adam's normalised steps keep the
  norms of a change whatever gradient drives it; the direction shows a
  gradient taken on other rows or in another precision.

Leaves that the reference's gradient leaves at rounding level (norm under a
thousandth of the median leaf's at the first batch) move by round-off alone
under Adam and are not counted.  Batch-norm running moments are never
compared: train-mode batch norm does not read them.
"""

from __future__ import annotations

import math

import numpy as np

TINY_GRAD = 1e-3
NUMBERS = ("loss_gap", "client_loss_gap", "client_loss_gap_first", "acc_gap",
           "update_gap", "update_gap_median", "update_gap_tree", "update_dir_gap",
           "change_gap", "change_gap_median", "change_gap_tree", "change_dir_gap")


def counted_leaves(grad_norms: dict[str, float]) -> list[str]:
    med = float(np.median(list(grad_norms.values())))
    return [k for k, g in grad_norms.items() if g >= TINY_GRAD * med]


def change_norms(params: dict, start: dict, leaves: list[str]) -> dict[str, float]:
    return {k: float(np.linalg.norm(params[k] - start[k])) for k in leaves}


def norm_gaps(dp: dict, dr: dict) -> dict[str, float]:
    """Per leaf, the gap between the program's and the reference's norms of
    the change, over the larger of the reference's norm of that leaf and of
    the median moved leaf."""
    moved = [v for v in dr.values() if v > 0]
    if not moved:
        raise ValueError("the reference moved no counted leaf")
    med = float(np.median(moved))
    return {k: abs(dp[k] - dr[k]) / max(dr[k], med) for k in dr}


def numbers(prog: dict, ref: dict, start: dict, eval_n: int) -> dict:
    """The compared numbers.  ``prog`` and ``ref`` hold ``loss`` and ``acc``
    per round, ``client_loss`` per round and client, and ``params`` (flat
    numpy) after the first round and after the last; ``ref`` also holds
    ``grad_norms``; ``start`` is the weights both began from."""
    leaves = counted_leaves(ref["grad_norms"])
    out, worst, norms = {}, {}, {}
    for name, i in (("update", 0), ("change", -1)):
        dp = change_norms(prog["params"][i], start, leaves)
        dr = change_norms(ref["params"][i], start, leaves)
        gaps = norm_gaps(dp, dr)
        moved = [k for k in leaves if dr[k] > 0]
        worst[f"{name}_gap"] = max(gaps, key=gaps.get)
        out[f"{name}_gap"] = gaps[worst[f"{name}_gap"]]
        out[f"{name}_gap_median"] = float(np.median([gaps[k] for k in moved]))
        tp, tr = (float(np.sqrt(sum(d[k] ** 2 for k in moved))) for d in (dp, dr))
        out[f"{name}_gap_tree"] = abs(tp - tr) / tr
        diff = np.sqrt(sum(float(np.sum((prog["params"][i][k] - ref["params"][i][k]) ** 2))
                           for k in moved))
        out[f"{name}_dir_gap"] = float(diff) / tr
        norms[name] = {k: (dp[k], dr[k]) for k in moved}
    out["loss_gap"] = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"]))
    out["client_loss_gap"] = max(
        abs(a - b) / abs(b) for pr, rr in zip(prog["client_loss"], ref["client_loss"])
        for a, b in zip(pr, rr))
    out["client_loss_gap_first"] = max(
        abs(a - b) / abs(b) for a, b in zip(prog["client_loss"][0], ref["client_loss"][0]))
    out["acc_gap"] = max(abs(a - b) * eval_n for a, b in zip(prog["acc"], ref["acc"]))
    if not all(math.isfinite(v) for v in out.values()):
        out = {k: float("inf") for k in out}
    return {"values": out, "leaves": worst, "norms": norms,
            "counted": len(leaves), "of": len(ref["grad_norms"])}


def verdict(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over the numbers that have a
    limit."""
    checks = {k: {"value": values[k], "limit": v["limit"]}
              for k, v in limits.items() if k in values and "limit" in v}
    ok = bool(checks) and all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
