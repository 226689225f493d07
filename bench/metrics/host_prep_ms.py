"""Host milliseconds per traced round in which the program prepares the
round's inputs: the union of its ``fl.sample``, ``fl.client_data`` and
``fl.stack`` spans (``bench.scopes``)."""

from bench import scopes


def read(ctx):
    return scopes.read(ctx, "host_prep_ms")
