"""Seconds of JAX's trace, lowering and compile events before the window
(persistent-cache hits included: what is left of a compile then is trace
and lowering)."""


def read(ctx):
    return ctx["setup_compile_s"]
