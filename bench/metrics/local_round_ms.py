"""Device milliseconds per round of the engine's local-round program (the
vmapped scan over every client's local steps), summed over chips.  The
program is the XLA module of the engine's jitted ``local_round``."""

MODULE = "local_round"


def read(ctx):
    s = ctx["trace"].module_s(MODULE)
    return 1e3 * s / len(ctx["traced_groups"]) if s > 0 else None
