"""Fused masked-Adam Pallas kernel vs. oracle + pytree wrapper semantics.

Also pins the pack/unpack dtype-fidelity contract (ISSUE 6): per-leaf dtypes
recorded in ``PackMeta`` and restored by ``unpack``, mixed-dtype / 0-dim /
empty-leaf round trips (hypothesis when available, seeded cases always), the
``tree_flatten_with_path`` == ``jax.tree.flatten`` layout-order assertion the
mask builders rely on, the client-stacked pack variants, and the three-way
``fused_masked_step == masked_step == partitioned_step`` equivalence at
mixed-group block boundaries.
"""

import typing
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import masking
from repro.core.aggregation import is_local_stat
from repro.core.partition import build_partition
from repro.kernels.masked_adam import ops
from repro.kernels.masked_adam.kernel import (masked_adam_kernel,
                                              masked_adam_stacked)
from repro.kernels.masked_adam.ref import masked_adam_ref
from repro.optim.adam import AdamConfig, adam_init, adam_update
from repro.optim.partial import (fused_adam_init, fused_masked_step,
                                 masked_step, partitioned_step)
from tests.conftest import small_params
from tests.test_partial_equivalence import _loss_fn

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAS_HYPOTHESIS = True
except ImportError:  # pragma: no cover - CI installs hypothesis
    HAS_HYPOTHESIS = False


@pytest.mark.parametrize("rows,br", [(32, 8), (64, 16), (128, 8)])
@pytest.mark.parametrize("step", [1, 10])
def test_kernel_matches_ref(rows, br, step):
    ks = jax.random.split(jax.random.key(rows + step), 4)
    p = jax.random.normal(ks[0], (rows, 128), jnp.float32)
    g = jax.random.normal(ks[1], (rows, 128), jnp.float32)
    m = jax.random.normal(ks[2], (rows, 128), jnp.float32) * 0.1
    v = jnp.abs(jax.random.normal(ks[3], (rows, 128))) * 0.01
    nb = rows // br
    mask = jnp.asarray(np.random.default_rng(0).integers(0, 2, nb), jnp.int32)
    sc = jnp.array([1e-3, 1 - 0.9**step, 1 - 0.999**step, 1e-8], jnp.float32)
    out_k = masked_adam_kernel(p, g, m, v, mask, sc, block_rows=br, interpret=True)
    out_r = masked_adam_ref(p, g, m, v, mask, sc, block_rows=br)
    for a, b, name in zip(out_k, out_r, "pmv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6,
                                   err_msg=name)


def test_pack_unpack_roundtrip():
    params = small_params()
    packed, meta = ops.pack(params)
    restored = ops.unpack(packed, meta)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b), atol=0)


def test_mixed_group_boundary_within_one_packed_tensor():
    """Two *adjacent* blocks of one packed tensor carrying different mask
    bits — the mixed-group tensor boundary the kernel docstring promises
    (per-client layer plans make such boundaries routine): the trained block
    must equal plain Adam, its frozen neighbour must copy through bit-exact,
    with no bleed across the block edge.  Interpret mode, kernel == ref."""
    br = 8
    ks = jax.random.split(jax.random.key(42), 4)
    # one logical tensor spanning 4 blocks; blocks 1 and 2 are adjacent with
    # different bits (0|1), as are 2 and 3 (1|0)
    rows = 4 * br
    p = jax.random.normal(ks[0], (rows, 128), jnp.float32)
    g = jax.random.normal(ks[1], (rows, 128), jnp.float32)
    m = jax.random.normal(ks[2], (rows, 128), jnp.float32) * 0.1
    v = jnp.abs(jax.random.normal(ks[3], (rows, 128))) * 0.01
    mask = jnp.asarray([0, 1, 0, 1], jnp.int32)
    sc = jnp.array([1e-3, 1 - 0.9**3, 1 - 0.999**3, 1e-8], jnp.float32)

    out_k = masked_adam_kernel(p, g, m, v, mask, sc, block_rows=br,
                               interpret=True)
    out_r = masked_adam_ref(p, g, m, v, mask, sc, block_rows=br)
    for a, b, name in zip(out_k, out_r, "pmv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6,
                                   err_msg=name)
    # frozen blocks copy through bit-exact; trained blocks move
    newp = np.asarray(out_k[0])
    orig = np.asarray(p)
    for b_idx, bit in enumerate(mask.tolist()):
        blk = slice(b_idx * br, (b_idx + 1) * br)
        if bit:
            assert np.abs(newp[blk] - orig[blk]).max() > 0
        else:
            np.testing.assert_array_equal(newp[blk], orig[blk])


def test_fused_mixed_group_blocks_in_one_leaf_pin_wrapper_vs_ref():
    """ops-level pin of the same boundary: a hand-built block mask that
    flips mid-leaf must behave exactly like running unfused Adam on the
    masked rows only — the wrapper's pack/unpack cannot smear the boundary."""
    leaf = jax.random.normal(jax.random.key(7), (16, 128), jnp.float32)
    params = {"w": leaf}
    grads = {"w": jnp.full_like(leaf, 0.02)}
    zeros = {"w": jnp.zeros_like(leaf)}
    # (16, 128) rows with block_rows=8 -> 2 blocks of one tensor: train the
    # first, freeze the second
    bm = np.asarray([1, 0], np.int32)
    newp, _, _ = ops.fused_masked_adam(
        params, grads, zeros, {"w": jnp.zeros_like(leaf)}, jnp.int32(1), bm,
        lr=1e-3, block_rows=8)
    ref_p, _ = adam_update(grads, adam_init(params), params,
                           AdamConfig(lr=1e-3))
    np.testing.assert_allclose(np.asarray(newp["w"][:8]),
                               np.asarray(ref_p["w"][:8]), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(newp["w"][8:]),
                                  np.asarray(leaf[8:]))


def test_fused_matches_unfused_adam_on_selected_group():
    """On the trainable group the fused kernel must equal plain Adam; frozen
    groups must be untouched."""
    params = small_params()
    part = build_partition(params)
    grads = jax.tree.map(lambda x: jnp.ones_like(x) * 0.01, params)
    zeros = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), params)
    bm = ops.block_mask_for_group(params, part, 2)
    newp, newm, newv = ops.fused_masked_adam(
        params, grads, zeros, jax.tree.map(jnp.copy, zeros), jnp.int32(1), bm,
        lr=1e-3,
    )
    ref_p, _ = adam_update(grads, adam_init(params), params, AdamConfig(lr=1e-3))
    for (path, a), (_, want), (_, orig) in zip(
        jax.tree_util.tree_flatten_with_path(newp)[0],
        jax.tree_util.tree_flatten_with_path(ref_p)[0],
        jax.tree_util.tree_flatten_with_path(params)[0],
    ):
        ps = "/".join(str(getattr(k, "key", k)) for k in path)
        if part.group_of(ps) == 2:
            np.testing.assert_allclose(np.asarray(a), np.asarray(want), atol=1e-6)
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(orig))


# ---------------------------------------------------------------------------
# pack/unpack dtype fidelity (per-leaf dtypes recorded and restored)
# ---------------------------------------------------------------------------

_SHAPES = [(), (0,), (1,), (5,), (3, 4), (2, 3, 2), (130,)]
_DTYPES = [jnp.float32, jnp.bfloat16, jnp.float16, jnp.int32]


def _make_tree(specs, seed):
    """Dict tree from (shape, dtype) specs; values exactly representable in
    every listed dtype's f32 round trip (small ints, normals cast down)."""
    rng = np.random.default_rng(seed)
    tree = {}
    for i, (shape, dt) in enumerate(specs):
        if dt == jnp.int32:
            arr = rng.integers(-99, 100, size=shape).astype(np.int32)
        else:
            arr = rng.normal(size=shape).astype(np.float32)
        tree[f"leaf{i:02d}"] = jnp.asarray(arr).astype(dt)
    return tree


def _assert_roundtrip(tree, block_rows=8):
    packed, meta = ops.pack(tree, block_rows)
    assert packed.dtype == jnp.float32          # kernel compute dtype
    restored = ops.unpack(packed, meta)
    for (pa, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(tree)[0],
        jax.tree_util.tree_flatten_with_path(restored)[0],
    ):
        assert b.dtype == a.dtype, f"{pa}: {a.dtype} -> {b.dtype}"
        assert b.shape == a.shape, pa
        np.testing.assert_array_equal(
            np.asarray(a.astype(jnp.float32)),
            np.asarray(b.astype(jnp.float32)), err_msg=str(pa))


@pytest.mark.parametrize("block_rows", [8, 16])
def test_pack_unpack_mixed_dtype_roundtrip_exact(block_rows):
    """The ISSUE 6 bugfix pin: bf16/f16/int32 leaves come back in their own
    dtype (not leaves[0]'s), including 0-dim scalars and empty leaves."""
    specs = list(zip(_SHAPES, [jnp.float32, jnp.bfloat16, jnp.float16,
                               jnp.int32, jnp.bfloat16, jnp.float16,
                               jnp.int32]))
    _assert_roundtrip(_make_tree(specs, seed=0), block_rows)


def test_unpack_global_dtype_override_warns():
    """``unpack(dtype=...)`` still works (casts every leaf) but is
    deprecated now that per-leaf dtypes round-trip by default."""
    tree = _make_tree([((3, 4), jnp.bfloat16), ((5,), jnp.float32)], seed=1)
    packed, meta = ops.pack(tree)
    with pytest.deprecated_call():
        forced = ops.unpack(packed, meta, dtype=jnp.float32)
    assert all(leaf.dtype == jnp.float32 for leaf in jax.tree.leaves(forced))
    # and the default path emits no warning
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        restored = ops.unpack(packed, meta)
    assert [leaf.dtype for leaf in jax.tree.leaves(restored)] == \
        [leaf.dtype for leaf in jax.tree.leaves(tree)]


if HAS_HYPOTHESIS:

    @settings(max_examples=30, deadline=None)
    @given(
        specs=st.lists(
            st.tuples(st.sampled_from(_SHAPES), st.sampled_from(_DTYPES)),
            min_size=1, max_size=6),
        seed=st.integers(0, 2**31 - 1),
        block_rows=st.sampled_from([8, 16]),
    )
    def test_pack_unpack_roundtrip_property(specs, seed, block_rows):
        _assert_roundtrip(_make_tree(specs, seed), block_rows)

else:  # seeded fallback so the property is still exercised without hypothesis

    @pytest.mark.parametrize("seed", range(10))
    def test_pack_unpack_roundtrip_property(seed):
        rng = np.random.default_rng(seed)
        specs = [
            (_SHAPES[int(rng.integers(len(_SHAPES)))],
             _DTYPES[int(rng.integers(len(_DTYPES)))])
            for _ in range(int(rng.integers(1, 7)))
        ]
        _assert_roundtrip(_make_tree(specs, seed), int(rng.choice([8, 16])))


# ---------------------------------------------------------------------------
# layout-order contract: tree_flatten_with_path == jax.tree.flatten
# ---------------------------------------------------------------------------

class _NTBlock(typing.NamedTuple):
    kernel: jax.Array
    bias: jax.Array


def test_layout_order_holds_for_dict_and_namedtuple_trees():
    tree = {
        "z": _NTBlock(kernel=jnp.ones((4, 4)), bias=jnp.zeros((4,))),
        "a": {"w": jnp.ones((2, 3)), "s": jnp.float32(1.0)},
    }
    packed, meta = ops.pack(tree)          # pack runs the assertion itself
    _assert_roundtrip(tree)
    # leaf spans in the packed buffer follow flatten order exactly
    leaves = jax.tree.leaves(tree)
    flat = np.asarray(packed).reshape(-1)
    off = 0
    for leaf, n, pn in zip(leaves, meta.sizes, meta.padded):
        np.testing.assert_array_equal(
            flat[off : off + n],
            np.asarray(leaf, np.float32).reshape(-1))
        off += pn


def test_layout_order_assertion_rejects_reordered_leaves():
    tree = {"a": jnp.ones((2,)), "b": jnp.zeros((3,))}
    leaves = jax.tree.leaves(tree)
    ops._assert_layout_order(tree, leaves)                 # agrees: fine
    with pytest.raises(AssertionError, match="different order"):
        ops._assert_layout_order(tree, leaves[::-1])       # misaligned


# ---------------------------------------------------------------------------
# client-stacked pack variants (batched-engine layout)
# ---------------------------------------------------------------------------

def test_pack_stacked_roundtrip_and_per_client_layout():
    C = 3
    rng = np.random.default_rng(11)
    tree = {
        "w": jnp.asarray(rng.normal(size=(C, 4, 5)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(C, 130)).astype(np.float32)
                         ).astype(jnp.bfloat16),
        "s": jnp.asarray(rng.normal(size=(C,)).astype(np.float32)),
    }
    packed, meta = ops.pack_stacked(tree)
    assert packed.shape[0] == C and packed.shape[2] == 128
    restored = ops.unpack_stacked(packed, meta)
    for (pa, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(tree)[0],
        jax.tree_util.tree_flatten_with_path(restored)[0],
    ):
        assert b.dtype == a.dtype and b.shape == a.shape, pa
        np.testing.assert_array_equal(
            np.asarray(a.astype(jnp.float32)),
            np.asarray(b.astype(jnp.float32)), err_msg=str(pa))
    # each client's slab equals the single-tree pack of that client's slice
    for c in range(C):
        one = jax.tree.map(lambda x: x[c], tree)
        pc, mc = ops.pack(one)
        np.testing.assert_array_equal(np.asarray(packed[c]), np.asarray(pc))
        assert mc.padded == meta.padded


def test_pack_stacked_rejects_empty_and_ragged_trees():
    with pytest.raises(ValueError, match="at least one leaf"):
        ops.pack_stacked({})
    with pytest.raises(ValueError, match="client axis"):
        ops.pack_stacked({"a": jnp.ones((3, 2)), "b": jnp.ones((4, 2))})


# ---------------------------------------------------------------------------
# plan bitmask -> per-client block masks
# ---------------------------------------------------------------------------

def test_block_masks_for_plan_matches_per_group_masks():
    params = small_params()
    part = build_partition(params)
    plan = np.zeros((3, part.num_groups), np.int32)
    plan[0, :] = 1                       # full-capacity client
    plan[1, [0, 2]] = 1                  # partial subset
    masks = ops.block_masks_for_plan(params, part, plan)
    gids = ops.block_group_ids(params, part)
    assert masks.shape == (3, len(gids))
    for c in range(3):
        sel = {g for g in range(part.num_groups) if plan[c, g]}
        want = ops.block_mask_for_group(params, part, sel)
        np.testing.assert_array_equal(masks[c], want, err_msg=f"client {c}")
        # traced builder (what the engines run under vmap) agrees too
        traced = ops.plan_block_mask(gids, jnp.asarray(plan[c]))
        np.testing.assert_array_equal(np.asarray(traced), want,
                                      err_msg=f"client {c} traced")
    assert not masks[2].any()            # all-zero plan row -> nothing trains


def test_masked_adam_stacked_matches_per_client_kernel_calls():
    C, rows, br = 3, 32, 8
    ks = jax.random.split(jax.random.key(5), 4)
    p = jax.random.normal(ks[0], (C, rows, 128), jnp.float32)
    g = jax.random.normal(ks[1], (C, rows, 128), jnp.float32)
    m = jax.random.normal(ks[2], (C, rows, 128), jnp.float32) * 0.1
    v = jnp.abs(jax.random.normal(ks[3], (C, rows, 128))) * 0.01
    masks = jnp.asarray(
        np.random.default_rng(3).integers(0, 2, (C, rows // br)), jnp.int32)
    sc = jnp.array([1e-3, 1 - 0.9**2, 1 - 0.999**2, 1e-8], jnp.float32)
    outs = masked_adam_stacked(p, g, m, v, masks, sc, block_rows=br,
                               interpret=True)
    for c in range(C):
        ref = masked_adam_kernel(p[c], g[c], m[c], v[c], masks[c], sc,
                                 block_rows=br, interpret=True)
        for a, b, name in zip(outs, ref, "pmv"):
            np.testing.assert_allclose(
                np.asarray(a[c]), np.asarray(b), atol=1e-6,
                err_msg=f"client {c} {name}")


# ---------------------------------------------------------------------------
# three-way equivalence: fused == masked == partitioned (Eq. 1, DESIGN.md §6)
# ---------------------------------------------------------------------------

def _assert_trees_close(got, want, **tol):
    tol.setdefault("rtol", 2e-5)
    tol.setdefault("atol", 2e-6)
    for (pa, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(got)[0],
        jax.tree_util.tree_flatten_with_path(want)[0],
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   err_msg=f"{pa} differs", **tol)


@pytest.mark.parametrize("groups", [
    2,
    pytest.param(0, marks=pytest.mark.slow),
    (0, 2),        # multi-group: block boundaries between trained/frozen
])
def test_three_way_fused_masked_partitioned(groups):
    """The three realisations of the paper's Eq. 1 — full-grad masked update,
    pruned-subtree update, and the fused packed-kernel update — must agree on
    real transformer leaves where trained and frozen groups share packed-block
    neighbourhoods."""
    params = small_params()
    part = build_partition(params)
    x = jax.random.randint(jax.random.key(1), (4, 6), 0, 32)
    y = jax.random.randint(jax.random.key(2), (4,), 0, 8)
    loss_fn = _loss_fn((x, y))
    cfg = AdamConfig(lr=1e-2)
    gsel = groups if isinstance(groups, int) else set(groups)

    mask = masking.mask_tree(params, part, gsel)
    p_masked, _, loss_m = masked_step(loss_fn, params, adam_init(params),
                                      mask, cfg)
    p_fused, st_fused, loss_f = fused_masked_step(
        loss_fn, params, fused_adam_init(params), part, gsel, cfg)
    assert np.allclose(float(loss_m), float(loss_f), rtol=1e-6)
    assert int(st_fused.step) == 1
    _assert_trees_close(p_fused, p_masked)

    if isinstance(groups, int):
        p_part, _, loss_p = partitioned_step(loss_fn, params, part, groups,
                                             None, cfg)
        assert np.allclose(float(loss_f), float(loss_p), rtol=1e-6)
        _assert_trees_close(p_fused, p_part)

    # frozen groups copy through bit-exact in the fused path
    sel = {gsel} if isinstance(gsel, int) else gsel
    for (path, a), (_, orig) in zip(
        jax.tree_util.tree_flatten_with_path(p_fused)[0],
        jax.tree_util.tree_flatten_with_path(params)[0],
    ):
        ps = "/".join(str(getattr(k, "key", k)) for k in path)
        if part.group_of(ps) not in sel:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(orig),
                                          err_msg=ps)


def test_fused_masked_step_rejects_weight_decay():
    params = small_params()
    part = build_partition(params)
    with pytest.raises(ValueError, match="weight_decay"):
        fused_masked_step(lambda p: jnp.float32(0.0), params,
                          fused_adam_init(params), part, 0,
                          AdamConfig(weight_decay=0.1))


# ---------------------------------------------------------------------------
# the subtree fused step (a homogeneous partial round of the fused engines)
# ---------------------------------------------------------------------------

_RN_STEPS = 3
_RN_ADAM = AdamConfig(lr=1e-2, eps=1e-3)   # Adam's linear regime (see
                                           # tests/test_engine_equivalence.py)


@pytest.fixture(scope="module")
def resnet4():
    from repro.fl import AlgoConfig, LocalTrainer, resnet_task

    adapter = resnet_task("resnet4", num_classes=4)
    params = adapter.init(jax.random.key(0))
    part = adapter.partition(params)
    trainers = {fused: LocalTrainer(adapter=adapter, partition=part,
                                    algo=AlgoConfig(), adam=_RN_ADAM,
                                    fused=fused)
                for fused in (False, True)}
    ks = jax.random.split(jax.random.key(3), 2)
    xs = jax.random.normal(ks[0], (_RN_STEPS, 6, 8, 8, 3), jnp.float32)
    ys = jax.random.randint(ks[1], (_RN_STEPS, 6), 0, 4)
    return adapter, params, part, trainers, xs, ys


def _leaf_paths(tree):
    return [("/".join(str(getattr(k, "key", k)) for k in path), leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _reference_run(adapter, params, part, group, xs, ys, fused):
    """``steps`` of the unfused ``partitioned_step`` or the whole-tree masked
    ``fused_masked_step``, with every layer's BN running moments spliced from
    the forward pass as the engines do."""
    p = params
    opt = fused_adam_init(params) if fused else None
    for x, y in zip(xs, ys):
        def loss_fn(q, x=x, y=y):
            return adapter.loss(q, x, y)

        stats = adapter.stats(p, x)
        if fused:
            p, opt, _ = fused_masked_step(loss_fn, p, opt, part, group,
                                          _RN_ADAM)
        else:
            p, opt, _ = partitioned_step(loss_fn, p, part, group, opt,
                                         _RN_ADAM)
        p = masking.tree_update(p, stats)
    return p


_SUBTREE_CASES = [
    pytest.param(group, fused, id=name if fused else f"{name}-tree_adam")
    for fused in (True, False)
    for group, name in ((0, "stem_conv"), (3, "shortcut_bns"), (5, "head"))
]


@pytest.mark.parametrize("group,fused", _SUBTREE_CASES)
def test_subtree_fused_step_matches_partitioned_and_masked_fused(resnet4,
                                                                 group, fused):
    """Over several steps, the step on the trained group's subtree
    (``LocalTrainer.make_step(group)``), with the fused kernel update or
    tree Adam, equals the unfused pruned step and the whole-tree masked
    fused step: trained leaves close, frozen leaves bit-equal to the start,
    every layer's running moments refreshed.  Its Adam state covers the
    trained leaves alone."""
    adapter, params, part, trainers, xs, ys = resnet4
    trainer = trainers[fused]
    carry, opt = trainer.init(params, group)
    if fused:
        assert opt.m.shape[0] == trainer.kernel_rows(params, group) \
            < ops.packed_rows(params)
    else:
        paths = [path for path, _ in _leaf_paths(opt.m)]
        assert paths and all(part.group_of(path) == group
                             and not is_local_stat(path) for path in paths)
    step = jax.jit(trainer.make_step(group))
    for x, y in zip(xs, ys):
        carry, opt, _ = step(carry, opt, x, y, params, params)
    assert int(opt.step) == _RN_STEPS
    got = masking.tree_update(params, carry)

    for fused in (False, True):
        want = _reference_run(adapter, params, part, group, xs, ys, fused)
        _assert_trees_close(got, want)
    for (path, a), (_, orig) in zip(_leaf_paths(got), _leaf_paths(params)):
        if is_local_stat(path):
            assert np.abs(np.asarray(a) - np.asarray(orig)).max() > 0, path
        elif part.group_of(path) == group:
            assert np.abs(np.asarray(a) - np.asarray(orig)).max() > 0, path
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(orig),
                                          err_msg=path)


def test_subtree_fused_round_discards_padded_steps(resnet4):
    """Through the vmap engine's fused local round: a client whose last step
    is padded (``step_valid`` 0) ends where the subtree step leaves it after
    its valid steps; frozen leaves come back bit-equal to the global model;
    every layer's BN running moments move."""
    from repro.fl import AlgoConfig, make_engine

    adapter, params, part, trainers, xs, ys = resnet4
    group, trainer = 3, trainers[True]
    engine = make_engine("vmap", trainer=trainer, partition=part,
                         algo=AlgoConfig())
    valid = jnp.asarray([[1, 1, 0], [1, 1, 1]], jnp.float32)
    stacked, (losses, _) = engine._local_fn(group, False)(
        params, jnp.stack([xs, xs]), jnp.stack([ys, ys]), valid, params)

    step = jax.jit(trainer.make_step(group))
    for c, n in enumerate((2, 3)):
        carry, opt = trainer.init(params, group)
        for x, y in zip(xs[:n], ys[:n]):
            carry, opt, _ = step(carry, opt, x, y, params, params)
        want = masking.tree_update(params, carry)
        got = jax.tree.map(lambda a, c=c: a[c], stacked)
        _assert_trees_close(got, want)
        for (path, a), (_, orig) in zip(_leaf_paths(got), _leaf_paths(params)):
            if is_local_stat(path):
                assert np.abs(np.asarray(a) - np.asarray(orig)).max() > 0, path
            elif part.group_of(path) != group:
                np.testing.assert_array_equal(np.asarray(a), np.asarray(orig),
                                              err_msg=path)
    assert np.all(np.isfinite(np.asarray(losses)))
