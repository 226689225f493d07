"""Jit'd wrapper for the SSD chunk kernel: model layout (B,S,H,·) <-> kernel
layout (B,H,S,·), lane padding for N/P, chunk selection."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.ssd_chunk.kernel import ssd_chunk_kernel
from repro.kernels.ssd_chunk.ref import ssd_ref


def _pad_last(x, mult):
    n = x.shape[-1]
    t = -(-n // mult) * mult
    if t == n:
        return x
    pad = [(0, 0)] * x.ndim
    pad[-1] = (0, t - n)
    return jnp.pad(x, pad)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    q: jax.Array,        # (B, S, H, N) — model layout
    k: jax.Array,
    v: jax.Array,        # (B, S, H, P)
    log_a: jax.Array,    # (B, S, H)
    *,
    chunk: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y: (B,S,H,P), final_state: (B,H,N,P))."""
    if interpret is None:
        interpret = default_interpret()
    n0, p0 = q.shape[-1], v.shape[-1]
    qt = _pad_last(jnp.swapaxes(q, 1, 2), 128)
    kt = _pad_last(jnp.swapaxes(k, 1, 2), 128)
    vt = _pad_last(jnp.swapaxes(v, 1, 2), 128)
    la = jnp.swapaxes(log_a, 1, 2)[..., None]      # (B,H,S,1)
    y, state = ssd_chunk_kernel(qt, kt, vt, la, chunk=chunk, interpret=interpret)
    return jnp.swapaxes(y, 1, 2)[..., :p0], state[:, :, :n0, :p0]


def ssd_reference(q, k, v, log_a):
    """(B,S,H,·)-layout oracle."""
    y, state = ssd_ref(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        jnp.swapaxes(log_a, 1, 2),
    )
    return jnp.swapaxes(y, 1, 2), state
