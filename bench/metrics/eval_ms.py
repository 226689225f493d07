"""Device milliseconds per round of the eval program (the adapter's
``evaluate`` on the balanced eval set): the XLA module of ``jit(evaluate)``."""

MODULE = "jit_evaluate"


def read(ctx):
    s = ctx["trace"].module_s(MODULE)
    return 1e3 * s / len(ctx["traced_groups"]) if s > 0 else None
