"""The held experts' grouped matmuls' share of the chip's bf16 peak, in
percent: the FLOPs the traced rounds' routed rows require over the device
seconds of the ``moe_experts`` scope times the peak.

Required FLOPs, counted as ``bench.counts.step_flops_per_sample`` counts a
group: each MoE layer's ``held_assignments`` of the round (rows routed to
the held experts, summed over clients and steps) times the forward FLOPs of
a row (``routed_flops_per_row``: 2 x 3 x hidden x expert width), once for
the forward in every layer, once more for the backward to activations in
layers at and above the trained group, and once more for the weight
gradient in the trained group (three times in an FNU round).  The layer's
recomputation in the backward is not required work and is not counted.
The counter is read from the trace (``bench.layer_scopes``)."""

from bench import layer_scopes

FULL = -1


def read(ctx):
    ms = layer_scopes.scope_ms(ctx, "moe_experts")
    rounds = layer_scopes.held_assignments(ctx)
    if ms is None or rounds is None:
        return None
    ref, cfg = ctx["cell"].reference, ctx["cell"].config
    layer_groups, row = ref.moe_layer_groups(cfg), ref.routed_flops_per_row(cfg)
    flops = 0.0
    for group, held in rounds:
        for g, n in zip(layer_groups, held):
            passes = 3 if group == FULL else 1 + (g >= group) + (g == group)
            flops += passes * n * row
    seconds = 1e-3 * ms * len(ctx["trace"].rounds)
    return 100.0 * flops / (seconds * ctx["peaks"]["bf16_flops_per_s"])
