"""Required work, counted from shapes: FLOPs of a local step and bytes of an
Adam step.  "Required" means what the round's algorithm needs, whatever the
program computes: a partial round that runs the full backward is still
credited with the truncated one, so a change that truncates it raises the
utilisation honestly and no count can pass the peak.
"""

from __future__ import annotations

FULL = -1

# Adam per trained float32 parameter and step: p, g, m and v read, p, m and
# v written.
ADAM_PASSES = 7
F32_BYTES = 4


def step_flops_per_sample(group_fwd: list[float], group: int) -> float:
    """FLOPs of one training step per sample on ``group`` (FULL for FNU).

    FNU: the forward, the backward to activations and the backward to
    weights, each as large as the forward: 3 x forward.  Partial round on
    group g: the forward, the backward to activations through the groups at
    and above g, and the weight gradient of g alone."""
    total = sum(group_fwd)
    if group == FULL:
        return 3.0 * total
    return total + sum(group_fwd[group:]) + group_fwd[group]


def adam_bytes(trained_params: int) -> int:
    """Bytes one Adam step must move for ``trained_params`` parameters.
    Frozen parameters need no traffic at all."""
    return ADAM_PASSES * F32_BYTES * int(trained_params)
