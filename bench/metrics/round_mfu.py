"""Required model FLOPs of the traced rounds over their wall seconds, the
cell's chips and the chip's bf16 peak, in percent.  A partial round is
credited with the truncated backward (``bench.counts``), whatever the
program computes; the eval is not counted."""

from bench import counts


def read(ctx):
    tr = ctx["trace"]
    if tr.window_s <= 0:
        return None
    flops = sum(counts.step_flops_per_sample(ctx["group_fwd_flops"], g)
                * ctx["samples_per_round"] for g in ctx["traced_groups"])
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["cell"].chips
    return 100.0 * flops / (tr.window_s * peak)
