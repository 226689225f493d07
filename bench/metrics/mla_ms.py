"""Device milliseconds per traced round of the local round's ops under the
``mla`` scope: the attention of every packed decoder block (projections,
document-masked scores, weighted values; ``models.attention.mla_packed``),
forward, recomputation and backward (``bench.layer_scopes``)."""

from bench import layer_scopes


def read(ctx):
    return layer_scopes.scope_ms(ctx, "mla")
