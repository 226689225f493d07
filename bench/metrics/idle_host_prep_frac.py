"""Share of the traced window in which the first device is idle while the
host prepares a round's inputs (under the ``fl.sample``, ``fl.client_data``
and ``fl.stack`` spans; ``bench.scopes``)."""

from bench import scopes


def read(ctx):
    return scopes.read(ctx, "idle_host_prep_frac")
