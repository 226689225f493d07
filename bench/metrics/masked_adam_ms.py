"""Device milliseconds per traced round of the local round's ops under the
``masked_adam`` scope: the Pallas masked-Adam kernel (``bench.scopes``)."""

from bench import scopes


def read(ctx):
    return scopes.read(ctx, "masked_adam_ms")
