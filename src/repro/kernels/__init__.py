"""Pallas TPU kernels (validated with interpret=True on CPU):

- ``flash_attention``: online-softmax attention, causal/sliding-window, GQA.
- ``masked_adam``: fused Eq.-1 masked Adam (block-skip on frozen groups).
- ``ssd_chunk``: chunked decay linear-attention scan (Mamba2 SSD / mLSTM core).

Each kernel package ships ``kernel.py`` (pl.pallas_call + BlockSpec),
``ops.py`` (jit'd wrapper) and ``ref.py`` (pure-jnp oracle).  The wrappers
take ``interpret=None`` and resolve it with ``default_interpret``, so a TPU
never runs the interpreter and tests elsewhere never need a chip.
"""

import jax


def default_interpret() -> bool:
    """Pallas interpret mode off-TPU (CPU tests), compiled Mosaic on TPU."""
    return jax.default_backend() != "tpu"
