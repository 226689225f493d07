"""Pytree-level wrapper: pack a parameter pytree into the kernel's (rows, 128)
layout with block-aligned leaf boundaries, derive the per-block mask from a
layer-group partition, run the fused kernel, unpack.

Layout contract (docs/KERNELS.md): leaves are laid out in ``jax.tree.flatten``
order, each flattened and zero-padded up to a multiple of
``block_rows * 128`` elements, so every leaf starts on a block boundary and a
per-*block* mask can express any per-*leaf* (i.e. per layer-group) selection.
``pack`` asserts that ``tree_flatten_with_path`` walks leaves in the same
order — the mask builders below iterate paths, and a silent ordering mismatch
would misalign masks with the packed buffer.

The compute buffer is float32 (the kernel's accumulation dtype); ``PackMeta``
records every leaf's original dtype and ``unpack`` restores it, so
``unpack(pack(tree))`` round-trips mixed-dtype trees exactly
(f32 -> f32 and bf16 -> f32 -> bf16 are value-exact).

``pack_stacked``/``unpack_stacked`` are the client-stacked variants: trees
whose every leaf carries a leading ``clients`` axis pack to ``(clients, R,
128)`` with the same per-client layout.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.partition import Partition, path_str, tree_paths
from repro.kernels import default_interpret
from repro.kernels.masked_adam.kernel import LANES, masked_adam_kernel

PyTree = Any


@dataclasses.dataclass(frozen=True)
class PackMeta:
    shapes: tuple[tuple[int, ...], ...]
    sizes: tuple[int, ...]
    padded: tuple[int, ...]      # padded element count per leaf
    treedef: Any
    dtypes: tuple[Any, ...]      # per-leaf original dtype, restored by unpack

    @property
    def rows(self) -> int:
        return sum(self.padded) // LANES


def _block_elems(block_rows: int) -> int:
    return block_rows * LANES


def _assert_layout_order(tree: PyTree, leaves: list) -> None:
    """``pack`` lays leaves out in ``jax.tree.flatten`` order while the mask
    builders iterate ``tree_flatten_with_path``; jax guarantees these agree,
    but a silent divergence (e.g. an exotic custom pytree node) would
    misalign every mask bit — fail loudly instead."""
    path_leaves = [leaf for _, leaf in tree_paths(tree)]
    if len(path_leaves) != len(leaves) or any(
        a is not b for a, b in zip(leaves, path_leaves)
    ):
        raise AssertionError(
            "tree_flatten_with_path visits leaves in a different order than "
            "jax.tree.flatten for this pytree; block masks would be "
            "misaligned with the packed buffer"
        )


def _pad_counts(leaves, block_rows: int):
    be = _block_elems(block_rows)
    sizes, padded = [], []
    for leaf in leaves:
        n = int(np.prod(leaf.shape)) if leaf.ndim else 1
        sizes.append(n)
        padded.append(n + (-n) % be)
    return sizes, padded


def packed_rows(tree: PyTree, block_rows: int = 8) -> int:
    """Row count of ``pack(tree, block_rows)`` without materialising it."""
    leaves = jax.tree.leaves(tree)
    _, padded = _pad_counts(leaves, block_rows)
    return sum(padded) // LANES


def pack(tree: PyTree, block_rows: int = 8) -> tuple[jax.Array, PackMeta]:
    """Flatten + pad each leaf to a block multiple, concat, reshape (R,128).

    The buffer is float32 (kernel compute dtype); per-leaf dtypes are
    recorded in the returned ``PackMeta`` and restored by ``unpack``."""
    leaves, treedef = jax.tree.flatten(tree)
    _assert_layout_order(tree, leaves)
    be = _block_elems(block_rows)
    flat_parts, shapes, sizes, padded, dtypes = [], [], [], [], []
    for leaf in leaves:
        arr = leaf.reshape(-1).astype(jnp.float32)
        n = arr.shape[0]
        pad = (-n) % be
        if pad:
            arr = jnp.concatenate([arr, jnp.zeros((pad,), arr.dtype)])
        flat_parts.append(arr)
        shapes.append(tuple(leaf.shape))
        sizes.append(n)
        padded.append(n + pad)
        dtypes.append(jnp.asarray(leaf).dtype)
    flat = jnp.concatenate(flat_parts) if flat_parts else jnp.zeros((0,), jnp.float32)
    meta = PackMeta(tuple(shapes), tuple(sizes), tuple(padded), treedef,
                    tuple(dtypes))
    return flat.reshape(-1, LANES), meta


def unpack(packed: jax.Array, meta: PackMeta, dtype=None) -> PyTree:
    """Invert ``pack``: slice, reshape, and cast each leaf back to its
    recorded dtype.  ``dtype=`` (a single dtype forced onto every leaf) is
    deprecated — it was only ever a workaround for the meta not recording
    per-leaf dtypes."""
    if dtype is not None:
        warnings.warn(
            "unpack(dtype=...) is deprecated: unpack now restores each "
            "leaf's recorded dtype by default",
            DeprecationWarning, stacklevel=2,
        )
    flat = packed.reshape(-1)
    out, off = [], 0
    for shape, n, pn, dt in zip(meta.shapes, meta.sizes, meta.padded,
                                meta.dtypes):
        leaf = flat[off : off + n].reshape(shape)
        out.append(leaf.astype(dtype if dtype is not None else dt))
        off += pn
    return jax.tree.unflatten(meta.treedef, out)


def pack_stacked(tree: PyTree, block_rows: int = 8) -> tuple[jax.Array, PackMeta]:
    """``pack`` for client-stacked trees (every leaf has a leading ``clients``
    axis): returns ``(clients, R, 128)`` where each client's rows follow the
    single-tree layout exactly (``meta.shapes`` are the *per-client* shapes)."""
    leaves, treedef = jax.tree.flatten(tree)
    _assert_layout_order(tree, leaves)
    if not leaves:
        raise ValueError("pack_stacked needs at least one leaf to size the "
                         "client axis")
    clients = leaves[0].shape[0]
    be = _block_elems(block_rows)
    flat_parts, shapes, sizes, padded, dtypes = [], [], [], [], []
    for leaf in leaves:
        if leaf.shape[0] != clients:
            raise ValueError(
                f"stacked leaves disagree on the client axis: "
                f"{leaf.shape[0]} vs {clients}")
        arr = leaf.reshape(clients, -1).astype(jnp.float32)
        n = arr.shape[1]
        pad = (-n) % be
        if pad:
            arr = jnp.concatenate(
                [arr, jnp.zeros((clients, pad), arr.dtype)], axis=1)
        flat_parts.append(arr)
        shapes.append(tuple(leaf.shape[1:]))
        sizes.append(n)
        padded.append(n + pad)
        dtypes.append(jnp.asarray(leaf).dtype)
    flat = jnp.concatenate(flat_parts, axis=1)
    meta = PackMeta(tuple(shapes), tuple(sizes), tuple(padded), treedef,
                    tuple(dtypes))
    return flat.reshape(clients, -1, LANES), meta


def unpack_stacked(packed: jax.Array, meta: PackMeta) -> PyTree:
    """Invert ``pack_stacked`` (leading client axis restored on every leaf)."""
    clients = packed.shape[0]
    flat = packed.reshape(clients, -1)
    out, off = [], 0
    for shape, n, pn, dt in zip(meta.shapes, meta.sizes, meta.padded,
                                meta.dtypes):
        leaf = flat[:, off : off + n].reshape((clients,) + shape)
        out.append(leaf.astype(dt))
        off += pn
    return jax.tree.unflatten(meta.treedef, out)


# ---------------------------------------------------------------------------
# Block-mask builders (host-side, static layout)
# ---------------------------------------------------------------------------

def block_group_ids(
    tree: PyTree,
    partition: Partition,
    block_rows: int = 8,
    exclude: Callable[[str], bool] | None = None,
) -> np.ndarray:
    """Per-block layer-group id aligned with ``pack``'s layout — the bridge
    between the partition's per-*leaf* grouping and the kernel's per-*block*
    mask.  Blocks of leaves matched by ``exclude`` (e.g.
    ``aggregation.is_local_stat`` for BN running moments) get id ``-1``:
    never kernel-trained, handled by the caller's stats splice."""
    leaves = jax.tree.leaves(tree)
    _assert_layout_order(tree, leaves)
    be = _block_elems(block_rows)
    ids = []
    for path, leaf in tree_paths(tree):
        n = int(np.prod(leaf.shape)) if leaf.ndim else 1
        nblocks = -(-n // be)
        p = path_str(path)
        gid = -1 if (exclude is not None and exclude(p)) \
            else partition.group_of(p)
        ids.extend([gid] * nblocks)
    return np.asarray(ids, dtype=np.int32)


def block_mask_for_group(
    tree: PyTree, partition: Partition, groups, block_rows: int = 8,
    exclude: Callable[[str], bool] | None = None,
) -> np.ndarray:
    """Per-block int32 mask aligned with ``pack``'s layout: 1 where the
    block's leaf belongs to ``groups`` (an int or a set of group ids), 0
    elsewhere.  ``exclude`` forces matched leaves' blocks to 0."""
    sel = {groups} if isinstance(groups, (int, np.integer)) \
        else set(int(g) for g in groups)
    gids = block_group_ids(tree, partition, block_rows, exclude)
    return np.where(np.isin(gids, sorted(sel)) & (gids >= 0), 1, 0).astype(
        np.int32)


def block_masks_for_plan(
    tree: PyTree, partition: Partition, plan, block_rows: int = 8,
    exclude: Callable[[str], bool] | None = None,
) -> np.ndarray:
    """Per-client per-block masks for a ``(clients, M)`` layer-plan bitmask
    (docs/HETEROGENEITY.md): row ``c`` is ``block_mask_for_group`` of client
    ``c``'s trained group set.  Shape ``(clients, nblocks)`` int32."""
    p = np.asarray(plan, dtype=bool)
    if p.ndim != 2 or p.shape[1] != partition.num_groups:
        raise ValueError(
            f"plan shape {p.shape} does not match "
            f"{partition.num_groups} layer groups")
    gids = block_group_ids(tree, partition, block_rows, exclude)
    out = np.zeros((p.shape[0], gids.shape[0]), dtype=np.int32)
    valid = gids >= 0
    out[:, valid] = p[:, gids[valid]]
    return out


def plan_block_mask(gids: np.ndarray, gmask: jax.Array) -> jax.Array:
    """Traced per-client block mask from static per-block group ids and one
    client's traced ``(M,)`` group bitmask — the in-jit counterpart of
    ``block_masks_for_plan`` (one compiled program serves every plan row)."""
    safe = jnp.asarray(np.maximum(gids, 0))
    bits = jnp.take(gmask, safe) > 0
    return jnp.where(jnp.asarray(gids >= 0), bits, False).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret", "b1", "b2"))
def _run(packed_p, packed_g, packed_m, packed_v, block_mask, scalars,
         block_rows, interpret, b1, b2):
    return masked_adam_kernel(
        packed_p, packed_g, packed_m, packed_v, block_mask, scalars,
        b1=b1, b2=b2, block_rows=block_rows, interpret=interpret,
    )


def adam_scalars(step: jax.Array, lr: float, b1: float, b2: float,
                 eps: float) -> jax.Array:
    """The kernel's (4,) SMEM side input: [lr, bias_corr1, bias_corr2, eps]
    — bias corrections computed exactly as ``optim.adam.adam_update`` does
    (``step`` is the 1-based post-increment count)."""
    t = step.astype(jnp.float32)
    return jnp.stack(
        [jnp.float32(lr), 1.0 - b1**t, 1.0 - b2**t, jnp.float32(eps)])


def fused_masked_adam(
    params: PyTree,
    grads: PyTree,
    m: PyTree,
    v: PyTree,
    step: jax.Array,              # int32 scalar (1-based after increment)
    block_mask: np.ndarray,
    *,
    lr: float = 1e-3,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    block_rows: int = 8,
    interpret: bool | None = None,
) -> tuple[PyTree, PyTree, PyTree]:
    """Fused Eq.-1 Adam over a whole pytree.  Returns (params, m, v)."""
    if interpret is None:
        interpret = default_interpret()
    pp, meta = pack(params, block_rows)
    pg, _ = pack(grads, block_rows)
    pm, meta_m = pack(m, block_rows)
    pv, meta_v = pack(v, block_rows)
    scalars = adam_scalars(step, lr, b1, b2, eps)
    np_, nm, nv = _run(pp, pg, pm, pv, jnp.asarray(block_mask), scalars,
                       block_rows, interpret, b1, b2)
    return (
        unpack(np_, meta),
        unpack(nm, meta_m),   # m/v metas record float32 — the state dtype —
        unpack(nv, meta_v),   # independent of the params' leaf dtypes
    )
