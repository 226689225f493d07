"""Mask / split / merge utilities over partitioned parameter pytrees.

Two equivalent realisations of the paper's Eq. 1 masked update are provided:

* ``mask_tree``        — the paper's literal binary mask ``S`` (bool pytree).
* ``select``/``merge`` — the partitioned form: the trainable group is carved
  out as a *pruned subtree*, gradients are taken w.r.t. that subtree only, and
  the result is merged back.  This is the form the framework actually runs —
  XLA prunes the dead backward graph and shrinks the gradient collectives,
  turning the paper's incidental comm/comp savings into compiled ones.

``tests/test_partial_equivalence.py`` asserts the two forms produce identical
updates.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from repro.core.partition import Partition, Path, path_str

PyTree = Any

GroupSel = Sequence[int] | int


def _as_group_set(groups: GroupSel) -> frozenset[int]:
    if isinstance(groups, int):
        return frozenset((groups,))
    return frozenset(int(g) for g in groups)


# ---------------------------------------------------------------------------
# Boolean masks (paper Eq. 1 form)
# ---------------------------------------------------------------------------

def mask_tree(params: PyTree, partition: Partition, groups: GroupSel) -> PyTree:
    """Binary mask pytree: True where the leaf belongs to ``groups``."""
    sel = _as_group_set(groups)

    def _mask(path, leaf):
        p = path_str(tuple(_entry_str(e) for e in path))
        keep = partition.group_of(p) in sel
        return jnp.full(jnp.shape(leaf), keep, dtype=bool)

    return jax.tree_util.tree_map_with_path(_mask, params)


def apply_mask(update: PyTree, mask: PyTree) -> PyTree:
    """``S ⊙ update`` — elementwise masked update (paper Eq. 1)."""
    return jax.tree.map(lambda u, m: jnp.where(m, u, jnp.zeros_like(u)), update, mask)


def _entry_str(entry: Any) -> str:
    if isinstance(entry, jax.tree_util.DictKey):
        return str(entry.key)
    if isinstance(entry, jax.tree_util.SequenceKey):
        return str(entry.idx)
    if isinstance(entry, jax.tree_util.GetAttrKey):
        return str(entry.name)
    return str(entry)


# ---------------------------------------------------------------------------
# Pruned-subtree form (what the framework runs)
# ---------------------------------------------------------------------------

def select(params: PyTree, partition: Partition, groups: GroupSel) -> PyTree:
    """Return a pruned pytree holding only leaves assigned to ``groups``."""
    sel = _as_group_set(groups)
    return _filter(params, (), lambda p: partition.group_of(p) in sel)


def complement(params: PyTree, partition: Partition, groups: GroupSel) -> PyTree:
    """Return a pruned pytree holding every leaf *not* in ``groups``."""
    sel = _as_group_set(groups)
    return _filter(params, (), lambda p: partition.group_of(p) not in sel)


def select_where(params: PyTree, keep: Callable[[str], bool]) -> PyTree:
    """Return a pruned pytree holding the leaves whose path ``keep`` accepts."""
    return _filter(params, (), keep)


def _filter(node: PyTree, prefix: Path, keep) -> PyTree:
    if isinstance(node, dict):
        out = {}
        for k, v in node.items():
            sub = _filter(v, prefix + (str(k),), keep)
            if sub is not None and (not isinstance(sub, dict) or sub):
                out[k] = sub
        return out
    if isinstance(node, (list, tuple)):
        # Parameter containers are dicts in this codebase; sequences are kept
        # atomic only if every element stays.
        items = [_filter(v, prefix + (str(i),), keep) for i, v in enumerate(node)]
        kept = [it for it in items if it is not None]
        if not kept:
            return None
        if len(kept) != len(items):
            raise ValueError(
                f"Partial selection inside a sequence at {path_str(prefix)}; "
                "use dict containers for partitionable parameters."
            )
        return type(node)(items) if not isinstance(node, tuple) else tuple(items)
    return node if keep(path_str(prefix)) else None


def merge(*trees: PyTree) -> PyTree:
    """Deep-merge pruned dict pytrees back into one tree (disjoint leaves)."""
    out: PyTree = {}
    for tree in trees:
        out = _merge2(out, tree)
    return out


def _merge2(a: PyTree, b: PyTree) -> PyTree:
    if b is None:
        return a
    if isinstance(a, dict) and isinstance(b, dict):
        out = dict(a)
        for k, v in b.items():
            out[k] = _merge2(out[k], v) if k in out else v
        return out
    if isinstance(a, dict) and not a:
        return b
    if a is None or (isinstance(a, dict) and not a):
        return b
    raise ValueError("merge: overlapping leaves between pruned trees")


# ---------------------------------------------------------------------------
# Client-axis (stacked) helpers — used by the batched vmap engine
# ---------------------------------------------------------------------------

def stack_trees(trees: Sequence[PyTree]) -> PyTree:
    """Stack same-structure pytrees along a new leading *client* axis."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *trees)


def unstack_tree(stacked: PyTree, num_clients: int) -> list[PyTree]:
    """Inverse of ``stack_trees``: one pytree per client-axis index (lazy
    device slices; nothing is copied until a leaf is consumed)."""
    return [jax.tree.map(lambda x: x[i], stacked) for i in range(num_clients)]


def apply_mask_stacked(update: PyTree, mask: PyTree) -> PyTree:
    """``S ⊙ update`` where ``update`` carries a leading client axis and
    ``mask`` is an unbatched bool pytree (``mask_tree`` output): the group
    mask broadcasts across clients — the paper's Eq. 1 form under a client
    axis.  The engine itself runs the pruned-subtree form (``select``/
    ``merge``); this is the literal-mask counterpart, kept equivalent by
    tests/test_partition.py."""
    return jax.tree.map(
        lambda u, m: jnp.where(m[None, ...], u, jnp.zeros_like(u)), update, mask
    )


def tree_update(base: PyTree, patch: PyTree) -> PyTree:
    """Return ``base`` with the leaves present in (pruned) ``patch`` replaced."""
    if not isinstance(base, dict):
        return patch
    out = dict(base)
    for k, v in (patch or {}).items():
        out[k] = tree_update(base[k], v) if k in out else v
    return out
