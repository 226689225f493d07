"""Pallas TPU kernel for the chunked decay-weighted linear-attention scan —
the compute core of Mamba2 (SSD) and mLSTM (repro.models.ssm).

Computes, per (batch, head), with per-step decays a_t = exp(log_a_t) <= 1:

    S_t = a_t · S_{t-1} + k_t ⊗ v_t            y_t = q_t · S_t

Grid: (batch, heads, num_chunks) — the chunk axis is minor, so the running
state S (N×P, f32) lives in VMEM scratch and carries across chunk steps.
Per chunk of length Q the kernel does three MXU matmuls:

    intra  = ((q·kᵀ) ⊙ D_causal-decay) @ v          (Q,Q)·(Q,P)
    y     += (q ⊙ exp(cum)) @ S_prev                 (Q,N)·(N,P)
    S_new  = a_tot·S_prev + (k ⊙ exp(tot−cum))ᵀ @ v  (N,Q)·(Q,P)

BlockSpecs tile q/k as (1,1,Q,N), v/y as (1,1,Q,P), log_a as (1,1,Q,1) — all
VMEM.  log_a carries a trailing unit axis so that its block's last two dims
are (Q, full); a (1,1,Q) block over (B,H,S) is refused by Mosaic.  N, P, Q
should be multiples of the 128-lane MXU width for peak utilisation (the
wrapper pads).  The decay matrices are built in-register from the cumulative
log-decay (exp of differences; ≤ 1, numerically safe); the cumulative sums
are masked (Q, Q) reductions, so the column of decays never needs a
transpose.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    q_ref, k_ref, v_ref, la_ref,      # inputs (blocked per chunk)
    y_ref, s_out_ref,                  # outputs
    state_ref,                         # scratch: (N, P) f32 carried over chunks
    *,
    chunk: int,
):
    c = pl.program_id(2)
    ncs = pl.num_programs(2)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (Q, N)
    k = k_ref[0, 0].astype(jnp.float32)            # (Q, N)
    v = v_ref[0, 0].astype(jnp.float32)            # (Q, P)
    la = la_ref[0, 0].astype(jnp.float32)          # (Q, 1)

    # Inclusive cumulative log-decay as a row and as a column:
    # cum_row[j] = sum_{i<=j} la_i (sublane reduction), then its diagonal
    # read back out along lanes gives cum_col[i] = cum_row[i].
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = row >= col
    cum_row = jnp.sum(jnp.where(row <= col, la, 0.0), axis=0, keepdims=True)
    cum_col = jnp.sum(jnp.where(row == col, cum_row, 0.0), axis=1,
                      keepdims=True)                               # (Q, 1)
    total = jnp.sum(la, axis=0, keepdims=True)                     # (1, 1)

    # Intra-chunk: scores[i,j] = (q_i·k_j)·exp(cum_i − cum_j) for i >= j.
    qk = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (Q,Q)
    diff = cum_col - cum_row
    w = jnp.where(causal, qk * jnp.exp(diff), 0.0)
    y = jax.lax.dot_general(w, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)    # (Q,P)

    # Inter-chunk: y += (q ⊙ exp(cum)) @ S_prev
    q_dec = q * jnp.exp(cum_col)
    y = y + jax.lax.dot_general(q_dec, state_ref[...], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    # State update: S = exp(total)·S + (k ⊙ exp(total−cum))ᵀ @ v
    k_dec = k * jnp.exp(total - cum_col)
    s_chunk = jax.lax.dot_general(k_dec, v, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # (N,P)
    state_ref[...] = jnp.exp(total) * state_ref[...] + s_chunk

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(c == ncs - 1)
    def _final():
        s_out_ref[0, 0] = state_ref[...].astype(s_out_ref.dtype)


def ssd_chunk_kernel(
    q: jax.Array,        # (B, H, S, N)
    k: jax.Array,        # (B, H, S, N)
    v: jax.Array,        # (B, H, S, P)
    log_a: jax.Array,    # (B, H, S, 1)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y: (B,H,S,P), final_state: (B,H,N,P))."""
    b, h, s, n = q.shape
    p = v.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    grid = (b, h, nc)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    blk_n = pl.BlockSpec((1, 1, chunk, n), lambda bi, hi, ci: (bi, hi, ci, 0))
    blk_p = pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0))
    blk_a = pl.BlockSpec((1, 1, chunk, 1), lambda bi, hi, ci: (bi, hi, ci, 0))
    y, s_out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[blk_n, blk_n, blk_p, blk_a],
        out_specs=[
            blk_p,
            pl.BlockSpec((1, 1, n, p), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), v.dtype),
            jax.ShapeDtypeStruct((b, h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(q, k, v, log_a)
    return y, s_out
