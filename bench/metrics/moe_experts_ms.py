"""Device milliseconds per traced round of the local round's ops under the
``moe_experts`` scope: the held experts' grouped matmuls (the megablox
Pallas kernels with the casts and masks around them,
``models.moe._grouped_swiglu``), forward, recomputation and backward
(``bench.layer_scopes``)."""

from bench import layer_scopes


def read(ctx):
    return layer_scopes.scope_ms(ctx, "moe_experts")
