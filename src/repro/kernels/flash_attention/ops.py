"""Jit'd public wrapper for the flash-attention kernel.

Handles layout (the model uses (B, S, H, D); the kernel wants (B, H, S, D)),
head-dim padding to the 128-lane MXU width, ragged tails via sequence
padding, and interpret mode off-TPU (``repro.kernels.default_interpret``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.flash_attention.ref import attention_ref


def _pad_to(x: jax.Array, axis: int, mult: int) -> tuple[jax.Array, int]:
    size = x.shape[axis]
    target = -(-size // mult) * mult
    if target == size:
        return x, size
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - size)
    return jnp.pad(x, pad), size


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "interpret", "bq", "bk")
)
def flash_attention_bhsd(
    q: jax.Array,            # (B, H, Sq, D)
    k: jax.Array,            # (B, Hkv, Skv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    interpret: bool | None = None,
    bq: int = 128,
    bk: int = 128,
) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    sq0, skv0, d0 = q.shape[2], k.shape[2], q.shape[3]
    # MXU alignment: pad head dim to 128 lanes, seq to block multiples.
    q, _ = _pad_to(q, 3, 128)
    k, _ = _pad_to(k, 3, 128)
    v, _ = _pad_to(v, 3, 128)
    bq_eff = min(bq, q.shape[2])
    bk_eff = min(bk, k.shape[2])
    q, _ = _pad_to(q, 2, bq_eff)
    k, _ = _pad_to(k, 2, bk_eff)
    v, _ = _pad_to(v, 2, bk_eff)
    # Padded KV columns are masked inside the kernel via the true kv length;
    # the softmax scale uses the true head dim (zero-padded lanes contribute
    # nothing to q·k but must not change the scale).
    out = flash_attention_kernel(
        q, k, v, causal=causal, window=window, bq=bq_eff, bk=bk_eff,
        interpret=interpret, kv_len=skv0, head_dim=d0,
    )
    return out[:, :, :sq0, :d0]


def flash_attention(
    q: jax.Array,            # (B, S, H, D) — model layout
    k: jax.Array,            # (B, S, Hkv, D)
    v: jax.Array,
    *,
    mask=None,               # accepted for API parity; causal masks only
    causal: bool = True,
    window: int = 0,
    interpret: bool | None = None,
) -> jax.Array:
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention_bhsd(
        qt, kt, vt, causal=causal, window=window, interpret=interpret
    )
    return jnp.swapaxes(out, 1, 2)


def attention_reference(q, k, v, *, causal=True, window=0):
    """(B,S,H,D)-layout oracle, for tests."""
    out = attention_ref(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        causal=causal, window=window,
    )
    return jnp.swapaxes(out, 1, 2)
