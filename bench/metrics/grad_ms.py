"""Device milliseconds per traced round of the local round's ops under the
``grad`` scope: every local step's forward and backward (``bench.scopes``)."""

from bench import scopes


def read(ctx):
    return scopes.read(ctx, "grad_ms")
