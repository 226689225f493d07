"""Device milliseconds per traced round of the local round's ops under
neither ``grad`` nor ``masked_adam``: packing and unpacking, the scan's
step-valid select, pads, layout copies (``bench.scopes``)."""

from bench import scopes


def read(ctx):
    return scopes.read(ctx, "step_overhead_ms")
