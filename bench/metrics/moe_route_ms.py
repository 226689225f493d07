"""Device milliseconds per traced round of the local round's ops under the
``moe_route`` scope: the held-expert MoE layers' router, top-k, sort,
dispatch gather and combine (``models.moe.held_moe_apply``), forward,
recomputation and backward (``bench.layer_scopes``)."""

from bench import layer_scopes


def read(ctx):
    return layer_scopes.scope_ms(ctx, "moe_route")
