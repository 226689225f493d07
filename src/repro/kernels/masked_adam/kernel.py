"""Pallas TPU fused (masked) Adam — the paper's Eq. 1 inner loop as a single
memory-bound pass.

    w ← w − γ·S ⊙ AdamDir(∇L)

Unfused, the update reads/writes p, m, v and reads g through ~9 HBM-roundtrip
intermediates; fused it is one read of each input and one write of each
output — the optimizer update runs at the HBM roofline.  The binary mask S is
*block-granular* (FedPart masks whole layers, so every block of a tensor
shares its group's bit): frozen blocks skip ALL arithmetic and just copy
through — on TPU the copy is also elided by aliasing the input and output
buffers, so frozen bytes are never touched.

Layout: parameters are packed to (rows, 128) lanes; the grid walks row-blocks
of (block_rows, 128).  The whole per-block mask is a scalar-prefetch operand
(it sits in SMEM before the grid starts and each step reads its own bit by
``program_id``).  A (1,) SMEM block per grid step is not an option: Mosaic
refuses rank-1 blocks that are neither the whole array nor a multiple of 128.
The Adam scalars [lr, bc1, bc2, eps] arrive as one (8, 128) VMEM tile, row i
holding scalar i in every lane.  They differ per client (padded steps leave
a client's step count behind), so under the engines' vmap they gain a client
axis, and a (clients, 8, 128) array still tiles where a (clients, 4) SMEM
array would not.

NOTE: on a homogeneous FedPart partial round the engines' fused step packs
only the trained group (``fl.client.LocalTrainer.make_step``): the
kernel streams those rows with every block trained, and frozen tensors never
reach it.  The per-block mask serves the whole-tree masked form, which FNU
rounds and per-client layer plans run, and any mixed-group tensor boundary.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _adam_kernel(
    mask_ref,                     # (num_blocks,) int32 — every block's S bit
    sc_ref,                       # (8, 128) f32 — rows [lr, bc1, bc2, eps, 0..]
    p_ref, g_ref, m_ref, v_ref,   # (BR, 128) blocks
    p_out, m_out, v_out,
    *,
    b1: float,
    b2: float,
):
    trained = mask_ref[pl.program_id(0)] != 0

    @pl.when(trained)
    def _update():
        lr, bc1, bc2, eps = (sc_ref[i:i + 1, :] for i in range(4))  # (1, 128)
        g = g_ref[...].astype(jnp.float32)
        m_new = b1 * m_ref[...] + (1.0 - b1) * g
        v_new = b2 * v_ref[...] + (1.0 - b2) * g * g
        mh = m_new / bc1
        vh = v_new / bc2
        p_new = p_ref[...].astype(jnp.float32) - lr * mh / (jnp.sqrt(vh) + eps)
        p_out[...] = p_new.astype(p_out.dtype)
        m_out[...] = m_new
        v_out[...] = v_new

    @pl.when(jnp.logical_not(trained))
    def _copy():
        # With input/output aliasing this is elided on TPU; kept for the
        # interpret-mode semantics.
        p_out[...] = p_ref[...]
        m_out[...] = m_ref[...]
        v_out[...] = v_ref[...]


def masked_adam_kernel(
    p: jax.Array,          # (rows, 128)
    g: jax.Array,
    m: jax.Array,          # f32
    v: jax.Array,          # f32
    block_mask: jax.Array, # (num_blocks,) int32
    scalars: jax.Array,    # (4,) f32: [lr, bias_corr1, bias_corr2, eps]
    *,
    b1: float = 0.9,
    b2: float = 0.999,
    block_rows: int = 8,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    rows, lanes = p.shape
    assert lanes == LANES and rows % block_rows == 0, (p.shape, block_rows)
    nb = rows // block_rows
    assert block_mask.shape == (nb,), (block_mask.shape, nb)

    kernel = functools.partial(_adam_kernel, b1=b1, b2=b2)
    sc_tile = jnp.zeros((8, LANES), jnp.float32).at[:4].set(
        scalars.astype(jnp.float32)[:, None])

    def blk(i, mask_ref):
        return (i, 0)

    tile = pl.BlockSpec((block_rows, LANES), blk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[pl.BlockSpec((8, LANES), lambda i, mask_ref: (0, 0)),
                  tile, tile, tile, tile],
        out_specs=[tile, tile, tile],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(p.shape, p.dtype),
            jax.ShapeDtypeStruct(m.shape, jnp.float32),
            jax.ShapeDtypeStruct(v.shape, jnp.float32),
        ],
        input_output_aliases={2: 0, 4: 1, 5: 2},
        interpret=interpret,
        # The kernel's op and a ``masked_adam`` level of its name-stack path
        # take this name, whatever program calls it (core.telemetry.SPANS).
        name="masked_adam",
    )(block_mask, sc_tile, p, g, m, v)


def masked_adam_stacked(
    p: jax.Array,           # (clients, rows, 128)
    g: jax.Array,
    m: jax.Array,           # f32
    v: jax.Array,           # f32
    block_masks: jax.Array, # (clients, num_blocks) int32
    scalars: jax.Array,     # (4,) f32, shared across clients
    *,
    b1: float = 0.9,
    b2: float = 0.999,
    block_rows: int = 8,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Client-stacked variant: fold the client axis into the row-block grid
    so one ``pallas_call`` sweeps every client's blocks.  Valid because each
    client's ``rows`` is a block multiple (``ops.pack_stacked`` guarantees
    it), so client boundaries coincide with block boundaries and the per-
    client masks concatenate to one grid-aligned mask."""
    clients, rows, lanes = p.shape
    assert rows % block_rows == 0, (p.shape, block_rows)
    assert block_masks.shape == (clients, rows // block_rows), (
        block_masks.shape, p.shape, block_rows)

    def fold(x):
        return x.reshape(clients * rows, lanes)

    out = masked_adam_kernel(
        fold(p), fold(g), fold(m), fold(v), block_masks.reshape(-1), scalars,
        b1=b1, b2=b2, block_rows=block_rows, interpret=interpret,
    )
    return tuple(x.reshape(clients, rows, lanes) for x in out)
