"""Device milliseconds per round of the vmap engine's aggregation program
(the weighted mean of the transmitted leaves and the splice into the global
weights): the XLA module of its jitted ``agg``."""

MODULE = "jit_agg"


def read(ctx):
    s = ctx["trace"].module_s(MODULE)
    return 1e3 * s / len(ctx["traced_groups"]) if s > 0 else None
