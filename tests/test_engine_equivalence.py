"""The batched engines (vmap, shard_map) must match the sequential oracle.

Same federation, same schedule, same seeds, every engine: global params,
per-round history losses, and the comm/comp cost books must agree to <=1e-5
for FNU and partial rounds, across FedAvg / FedProx / MOON, including ragged
client sizes (different step counts, and — in the bucket test — a client
smaller than the batch size, which lands in its own batch-width bucket).

The same bar holds under heterogeneous *per-client layer plans*
(``FLRunConfig(plan=..., capacity_tiers=...)``, docs/HETEROGENEITY.md): the
sequential oracle trains each client's exact pruned group set while the
batched engines run one masked plan program over the stacked cohort — the
``test_hetero_plan_*`` block pins sequential == vmap == shard_map for nested
and random plans, ragged buckets, the degenerate async runtime, and (slow
lane) a forced-2-device mesh at inflight 1 and 2.

The shard_map engine is additionally pinned against the oracle on a
*multi-device* mesh: a subprocess forces 2 simulated host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=2``, which must precede
the first jax import — hence the subprocess, same pattern as
tests/test_moe_ep.py) so the client axis is genuinely sharded, padding
clients and all.  In-process tests cover the degenerate 1-device mesh.

Note on Adam eps: with the default eps=1e-8, Adam's bias-corrected first
steps normalise near-zero gradients to ±1, so benign float reassociation
between the vmapped and per-step compiled programs can flip an update's sign
and diverge by O(lr).  The runs here pin ``adam_eps=1e-3`` to keep the
comparison in Adam's linear regime — both engines still execute identical
configs, so this tests engine equivalence, not optimizer robustness.
"""

import jax
import numpy as np
import pytest

from repro.core.schedule import FedPartSchedule, FNUSchedule
from repro.data import (VisionDatasetSpec, balanced_eval_set, build_clients,
                        make_vision_dataset)
from repro.fl import AlgoConfig, FLRunConfig, resnet_task, run_federated

BATCH = 16


def _make_setup(client_sizes):
    spec = VisionDatasetSpec(num_classes=4, image_size=8)
    X, y = make_vision_dataset(spec, sum(client_sizes), seed=0)
    Xe, ye = make_vision_dataset(spec, 64, seed=9)
    eval_set = balanced_eval_set(Xe, ye, per_class=8)
    bounds = np.cumsum((0,) + tuple(client_sizes))
    parts = [np.arange(bounds[i], bounds[i + 1]) for i in range(len(client_sizes))]
    # resnet4: same BN / shortcut / multi-group structure as resnet8 at a
    # fraction of the XLA compile cost (the dominant cost here).
    return resnet_task("resnet4", num_classes=4), build_clients(X, y, parts), eval_set


@pytest.fixture(scope="module")
def setup():
    # Ragged step counts (36 -> 2 steps/epoch, 56 -> 3, 40 -> 2) in one
    # batch-width bucket: exercises the pad-and-mask step masking.
    return _make_setup((36, 56, 40))


def _run(setup, algo: str, engine: str, rounds, **kw):
    adapter, clients, eval_set = setup
    kw.setdefault("adam_eps", 1e-3)
    cfg = FLRunConfig(local_epochs=1, batch_size=BATCH, lr=2e-3,
                      algo=AlgoConfig(name=algo), engine=engine, **kw)
    return run_federated(adapter, clients, eval_set, rounds, cfg)


def _assert_equivalent(a, b, tol=1e-5):
    flat_a = jax.tree_util.tree_flatten_with_path(a.params)[0]
    flat_b = jax.tree.leaves(b.params)
    assert len(flat_a) == len(flat_b)
    for (path, la), lb in zip(flat_a, flat_b):
        np.testing.assert_allclose(
            np.asarray(la), np.asarray(lb), rtol=tol, atol=tol,
            err_msg=f"param {jax.tree_util.keystr(path)} diverged",
        )
    la = np.array([h["loss"] for h in a.history])
    lb = np.array([h["loss"] for h in b.history])
    np.testing.assert_allclose(la, lb, rtol=tol, atol=tol)
    assert a.comm_total_bytes == b.comm_total_bytes
    assert a.comm_fnu_bytes == b.comm_fnu_bytes
    assert a.comp_total_flops == b.comp_total_flops
    assert a.comp_fnu_flops == b.comp_fnu_flops


# 1 FNU warmup + 1 partial round (group 0): covers both phases per algorithm.
MIXED = FedPartSchedule(num_groups=6, warmup_rounds=1, rounds_per_layer=1,
                        cycles=1).rounds()[:2]


@pytest.mark.parametrize("algo", ["fedavg", "fedprox", "moon"])
def test_vmap_matches_sequential_mixed_schedule(setup, algo):
    seq = _run(setup, algo, "sequential", MIXED)
    vm = _run(setup, algo, "vmap", MIXED)
    _assert_equivalent(seq, vm)


def test_vmap_matches_sequential_small_client_bucket():
    """A client below the batch size (12 < 16) trains with bs=12 in the
    sequential oracle; the vmap engine must route it through its own
    batch-width bucket and still agree.  One partial round: bucket routing is
    phase-independent, and the fresh batch shapes make this the
    compile-heaviest case in the module."""
    small = _make_setup((12, 36, 20))
    seq = _run(small, "fedavg", "sequential", MIXED[1:])
    vm = _run(small, "fedavg", "vmap", MIXED[1:])
    _assert_equivalent(seq, vm)


@pytest.mark.slow
def test_vmap_matches_sequential_deeper_schedule(setup):
    """Longer horizon (second partial group + an extra FNU): drift stays
    bounded over more rounds too."""
    rounds = FedPartSchedule(num_groups=6, warmup_rounds=1, rounds_per_layer=1,
                             cycles=1).rounds()[:4]
    for algo in ("fedavg", "moon"):
        seq = _run(setup, algo, "sequential", rounds)
        vm = _run(setup, algo, "vmap", rounds)
        _assert_equivalent(seq, vm)


def test_vmap_matches_sequential_fnu_only(setup):
    rounds = FNUSchedule(2).rounds()
    seq = _run(setup, "fedavg", "sequential", rounds)
    vm = _run(setup, "fedavg", "vmap", rounds)
    _assert_equivalent(seq, vm)


@pytest.mark.parametrize("engine", ["vmap", "shard_map"])
def test_batched_engines_reject_stepsize_tracking(setup, engine):
    adapter, clients, eval_set = setup
    cfg = FLRunConfig(local_epochs=1, batch_size=BATCH, engine=engine,
                      track_stepsizes=True)
    with pytest.raises(ValueError, match="sequential"):
        run_federated(adapter, clients, eval_set, FNUSchedule(1).rounds(), cfg)


@pytest.mark.parametrize("engine", ["vmap", "shard_map"])
def test_batched_engines_zero_weight_guard(setup, engine):
    """Degenerate round weights must raise (as the oracle does via
    tree_mean), not propagate NaN through the on-device aggregation."""
    from repro.fl import LocalTrainer, make_engine
    from repro.optim.adam import AdamConfig

    adapter, clients, _ = setup
    params = adapter.init(jax.random.key(0))
    part = adapter.partition(params)
    algo = AlgoConfig()
    trainer = LocalTrainer(adapter=adapter, partition=part, algo=algo,
                           adam=AdamConfig(lr=1e-3))
    engine = make_engine(engine, trainer=trainer, partition=part, algo=algo)
    with pytest.raises(ValueError, match="positive"):
        engine.run_round(params, MIXED[1], clients,
                         seeds=[1, 2, 3], weights=[0, 0, 0],
                         epochs=1, batch_size=BATCH)


def test_unknown_engine_rejected(setup):
    adapter, clients, eval_set = setup
    cfg = FLRunConfig(engine="pmap")
    with pytest.raises(ValueError, match="unknown engine"):
        run_federated(adapter, clients, eval_set, FNUSchedule(1).rounds(), cfg)


# -- heterogeneous per-client layer plans (docs/HETEROGENEITY.md) -----------
#
# Capacity tiers chosen so all three tiers differ on resnet4's 6 groups
# (nested prefixes ceil(c*6) = 3 / 5 / 6).  MIXED's partial round trains
# group 0 — inside every prefix, so a nested plan for it is homogeneous and
# resolve_plan would collapse it to the legacy path; HETERO_MIXED swaps in a
# *group-4* partial round instead, which tier 0 clamps to its deepest group
# (2) while the other tiers follow the schedule (4) — both rounds get
# genuinely mixed cohorts, exercising the masked plan step, the per-group
# participant-weighted aggregation, and the zero-trainer frozen fallback
# (group 5 is trained by the full-capacity tier alone on the FNU round;
# groups 0, 1, 3, 5 have no trainer on the partial round).
#
# adam_eps: unlike the homogeneous tests (the same pruned program, vmapped vs
# looped), these compare two genuinely *different* float programs — the
# oracle's pruned group-set step vs the batched engines' masked FNU-shaped
# plan step — so reassociation noise on near-zero gradients is larger and
# eps=1e-3 no longer keeps every Adam step in the linear regime on plan FNU
# rounds (fedprox drifts to ~4e-5).  eps=1e-2 restores <=1e-5 headroom; the
# configs stay identical across engines, so equivalence is still the claim.

TIERS = (0.34, 0.67, 1.0)
HETERO_EPS = 1e-2
HETERO_MIXED = [MIXED[0],
                type(MIXED[1])(index=1, phase="partial", cycle=0, group=4)]


@pytest.mark.parametrize("algo", ["fedavg", "fedprox"])
def test_hetero_plan_vmap_matches_sequential(setup, algo):
    """Nested per-client plans: the vmapped masked-plan program must match
    the oracle's per-client pruned group sets, FNU + partial."""
    seq = _run(setup, algo, "sequential", HETERO_MIXED,
               plan="nested", capacity_tiers=TIERS, adam_eps=HETERO_EPS)
    vm = _run(setup, algo, "vmap", HETERO_MIXED,
              plan="nested", capacity_tiers=TIERS, adam_eps=HETERO_EPS)
    _assert_equivalent(seq, vm)


def test_hetero_plan_shard_map_matches_sequential(setup):
    """Per-group psum'd weight sums on the (degenerate 1-device) mesh must
    agree with the oracle; the multi-device sharpening lives in the slow
    2-device subprocess test."""
    seq = _run(setup, "fedavg", "sequential", HETERO_MIXED,
               plan="nested", capacity_tiers=TIERS, adam_eps=HETERO_EPS)
    sm = _run(setup, "fedavg", "shard_map", HETERO_MIXED,
              plan="nested", capacity_tiers=TIERS, adam_eps=HETERO_EPS)
    _assert_equivalent(seq, sm)


@pytest.mark.slow
def test_hetero_plan_random_kind_engines_agree(setup):
    """Seeded random plans (arbitrary per-client group subsets) through the
    same masked program: vmap == sequential.  Slow lane: the nested tests
    above pin the same masked program in tier-1; random only changes which
    bits are set (nightly hetero-equivalence job)."""
    seq = _run(setup, "fedavg", "sequential", HETERO_MIXED,
               plan="random", capacity_tiers=TIERS, adam_eps=HETERO_EPS)
    vm = _run(setup, "fedavg", "vmap", HETERO_MIXED,
              plan="random", capacity_tiers=TIERS, adam_eps=HETERO_EPS)
    _assert_equivalent(seq, vm)


@pytest.mark.slow
def test_hetero_plan_ragged_buckets(setup):
    """A client below the batch size routes through its own bucket while the
    per-client bitmask rides along (heterogeneous version of the
    small-client bucket test).  Slow lane: bucket routing is plan-agnostic
    (`_bucket_gmask` just permutes rows) and the homogeneous bucket test
    stays tier-1; the 2-device subprocess also re-covers hetero buckets
    (nightly hetero-equivalence job)."""
    small = _make_setup((12, 36, 20))
    seq = _run(small, "fedavg", "sequential", HETERO_MIXED[1:],
               plan="nested", capacity_tiers=TIERS, adam_eps=HETERO_EPS)
    vm = _run(small, "fedavg", "vmap", HETERO_MIXED[1:],
              plan="nested", capacity_tiers=TIERS, adam_eps=HETERO_EPS)
    _assert_equivalent(seq, vm)


def test_hetero_plan_async_degenerate_matches_sync(setup):
    """Degenerate async runtime under a heterogeneous plan: the per-(client,
    group) buffered merge must reproduce the sync per-group aggregation."""
    sync = _run(setup, "fedavg", "vmap", HETERO_MIXED,
                plan="nested", capacity_tiers=TIERS, adam_eps=HETERO_EPS)
    asy = _run(setup, "fedavg", "vmap", HETERO_MIXED,
               plan="nested", capacity_tiers=TIERS, runtime="async",
               adam_eps=HETERO_EPS)
    _assert_equivalent(sync, asy)


# -- fused Pallas masked-Adam path (fused_adam=True, docs/KERNELS.md) -------
#
# The acceptance bar (ISSUE 6): the fused path — local steps through the
# packed masked-Adam kernel, interpret mode on CPU — matches the *unfused
# sequential oracle* (whose partial rounds are ``partitioned_step``'s pruned
# form) to <=1e-5 under every engine x {homogeneous, nested, random} plans,
# on the module's ragged-step-count cohort.  Baselines are cached per plan:
# the oracle runs once, each fused engine compares against it.

_FUSED_BASELINES: dict = {}


def _fused_baseline(setup, plan):
    if plan not in _FUSED_BASELINES:
        if plan == "homogeneous":
            _FUSED_BASELINES[plan] = _run(setup, "fedavg", "sequential", MIXED)
        else:
            _FUSED_BASELINES[plan] = _run(
                setup, "fedavg", "sequential", HETERO_MIXED,
                plan=plan, capacity_tiers=TIERS, adam_eps=HETERO_EPS)
    return _FUSED_BASELINES[plan]


@pytest.mark.parametrize("engine", ["sequential", "vmap", "shard_map"])
@pytest.mark.parametrize("plan", ["homogeneous", "nested", "random"])
def test_fused_adam_matches_partitioned_oracle(setup, engine, plan):
    """fused_adam=True x every engine x every plan kind == the unfused
    sequential oracle (Eq. 1 masked kernel form vs pruned partitioned form),
    params + losses + cost books."""
    if plan == "homogeneous":
        fz = _run(setup, "fedavg", engine, MIXED, fused_adam=True)
    else:
        fz = _run(setup, "fedavg", engine, HETERO_MIXED, fused_adam=True,
                  plan=plan, capacity_tiers=TIERS, adam_eps=HETERO_EPS)
    _assert_equivalent(_fused_baseline(setup, plan), fz)


def test_fused_async_degenerate_matches_sync(setup):
    """The async runtime inherits the fused path through
    ``run_local_async``: degenerate async == sync, both fused."""
    sync = _run(setup, "fedavg", "vmap", MIXED, fused_adam=True)
    asy = _run(setup, "fedavg", "vmap", MIXED, fused_adam=True,
               runtime="async")
    _assert_equivalent(sync, asy)


@pytest.mark.slow
def test_fused_ragged_small_client_bucket():
    """Fused path through a dedicated batch-width bucket (client 12 < 16):
    bucket routing is step-implementation-agnostic."""
    small = _make_setup((12, 36, 20))
    seq = _run(small, "fedavg", "sequential", MIXED[1:])
    fz = _run(small, "fedavg", "vmap", MIXED[1:], fused_adam=True)
    _assert_equivalent(seq, fz)


def test_fused_rejects_weight_decay(setup):
    """The kernel implements plain Adam; a weight-decay config must be
    refused at trainer construction, not silently ignored."""
    from repro.fl import LocalTrainer
    from repro.optim.adam import AdamConfig

    adapter, _, _ = setup
    params = adapter.init(jax.random.key(0))
    part = adapter.partition(params)
    with pytest.raises(ValueError, match="weight_decay"):
        LocalTrainer(adapter=adapter, partition=part, algo=AlgoConfig(),
                     adam=AdamConfig(weight_decay=0.1), fused=True)


def test_homogeneous_plan_is_identical_to_default(setup):
    """plan="homogeneous" (with tiers set, which it ignores) must be the
    pre-plan path exactly — same programs, same numbers, every engine
    (shard_map on the degenerate 1-device mesh; the acceptance bar is all
    three engines)."""
    for engine in ("sequential", "vmap", "shard_map"):
        base = _run(setup, "fedavg", engine, MIXED)
        homog = _run(setup, "fedavg", engine, MIXED,
                     plan="homogeneous", capacity_tiers=TIERS)
        for a, b in zip(jax.tree.leaves(base.params),
                        jax.tree.leaves(homog.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_hetero_plan_deeper_schedule_all_engines(setup):
    """Slow lane (nightly): longer horizon + FedProx across all three
    engines under nested plans — drift stays bounded as rounds accumulate.
    The partial rounds walk the *deep* groups (3, 4, 5), so every one is
    clamped differently per tier (3/4/5 vs tier-0's deepest group 2) and no
    round collapses to the homogeneous path."""
    spec_t = type(MIXED[1])
    rounds = [MIXED[0]] + [spec_t(index=i + 1, phase="partial", cycle=0,
                                  group=g) for i, g in enumerate((3, 4, 5))]
    for algo in ("fedavg", "fedprox"):
        seq = _run(setup, algo, "sequential", rounds,
                   plan="nested", capacity_tiers=TIERS, adam_eps=HETERO_EPS)
        for engine in ("vmap", "shard_map"):
            other = _run(setup, algo, engine, rounds,
                         plan="nested", capacity_tiers=TIERS, adam_eps=HETERO_EPS)
            _assert_equivalent(seq, other)


# -- shard_map engine -------------------------------------------------------


def test_shard_map_matches_sequential_single_device(setup):
    """Degenerate 1-device mesh: the shard_map machinery (client padding,
    on-mesh psum, splice) must already agree in-process before the
    multi-device subprocess test sharpens it."""
    seq = _run(setup, "fedavg", "sequential", MIXED)
    sm = _run(setup, "fedavg", "shard_map", MIXED)
    _assert_equivalent(seq, sm)


def test_shard_map_rejects_oversized_mesh(setup):
    """Asking for more mesh devices than exist must fail with the hint about
    forcing host devices, not a cryptic mesh error."""
    adapter, clients, eval_set = setup
    cfg = FLRunConfig(local_epochs=1, batch_size=BATCH, engine="shard_map",
                      sim_devices=len(jax.devices()) + 1)
    with pytest.raises(ValueError, match="host"):
        run_federated(adapter, clients, eval_set, FNUSchedule(1).rounds(), cfg)


# The multi-device run needs XLA_FLAGS before the first jax import, so it
# lives in a subprocess (the pattern test_moe_ep.py established).  2 forced
# host devices, ragged clients (3 -> padded to 4, two per device), plus a
# small-client two-bucket case; sequential vs shard_map for all three
# algorithms, vmap riding along on fedavg to pin the three-way equality.
_SHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys, json
sys.path.insert(0, "src")
import jax
import numpy as np
from repro.core.compile_cache import enable_compile_cache
enable_compile_cache()

from repro.core.schedule import FedPartSchedule
from repro.data import (VisionDatasetSpec, balanced_eval_set, build_clients,
                        make_vision_dataset)
from repro.fl import AlgoConfig, FLRunConfig, resnet_task, run_federated

assert len(jax.devices()) == 2, jax.devices()

def make_setup(client_sizes):
    spec = VisionDatasetSpec(num_classes=4, image_size=8)
    X, y = make_vision_dataset(spec, sum(client_sizes), seed=0)
    Xe, ye = make_vision_dataset(spec, 64, seed=9)
    eval_set = balanced_eval_set(Xe, ye, per_class=8)
    bounds = np.cumsum((0,) + tuple(client_sizes))
    parts = [np.arange(bounds[i], bounds[i + 1])
             for i in range(len(client_sizes))]
    return resnet_task("resnet4", num_classes=4), build_clients(X, y, parts), eval_set

def run(setup, algo, engine, rounds, runtime="sync", inflight=1):
    adapter, clients, eval_set = setup
    cfg = FLRunConfig(local_epochs=1, batch_size=16, lr=2e-3, adam_eps=1e-3,
                      algo=AlgoConfig(name=algo), engine=engine, sim_devices=2,
                      runtime=runtime, max_inflight_cohorts=inflight)
    return run_federated(adapter, clients, eval_set, rounds, cfg)

def diffs(a, b):
    pd = max(float(np.max(np.abs(np.asarray(x) - np.asarray(z))))
             for x, z in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)))
    ld = max(abs(x["loss"] - z["loss"]) for x, z in zip(a.history, b.history))
    books = (a.comm_total_bytes == b.comm_total_bytes
             and a.comm_fnu_bytes == b.comm_fnu_bytes
             and a.comp_total_flops == b.comp_total_flops
             and a.comp_fnu_flops == b.comp_fnu_flops)
    return {"param_maxdiff": pd, "loss_maxdiff": ld, "books_equal": books}

MIXED = FedPartSchedule(num_groups=6, warmup_rounds=1, rounds_per_layer=1,
                        cycles=1).rounds()[:2]
results = {}
ragged = make_setup((36, 56, 40))         # one bucket, padded 3 -> 4 clients
for algo in ("fedavg", "fedprox", "moon"):
    seq = run(ragged, algo, "sequential", MIXED)
    shard = run(ragged, algo, "shard_map", MIXED)
    results[algo] = diffs(seq, shard)
    if algo == "fedavg":
        results["fedavg_vmap_vs_shard"] = diffs(
            run(ragged, algo, "vmap", MIXED), shard)
        # degenerate async runtime on a real 2-device mesh: the event-driven
        # path (explicitly pinned at max_inflight_cohorts=1, the merge-driven
        # regime) must reproduce the sync barrier through the sharded backend
        results["fedavg_async_shard"] = diffs(
            run(ragged, algo, "shard_map", MIXED, runtime="async",
                inflight=1), shard)
        # host-parallel dispatch on the same mesh: full participation leaves
        # no idle clients for a second cohort, so inflight=2 must collapse to
        # the same barrier arithmetic -- now with the cohort programs bound
        # to width-1 submeshes of the 2-device mesh
        results["fedavg_async_shard_inflight2"] = diffs(
            run(ragged, algo, "shard_map", MIXED, runtime="async",
                inflight=2), shard)
buckets = make_setup((12, 36, 20))        # two buckets, each padded to 2
results["fedavg_buckets"] = diffs(
    run(buckets, "fedavg", "sequential", MIXED[1:]),
    run(buckets, "fedavg", "shard_map", MIXED[1:]))
print(json.dumps(results))
"""


def _run_subprocess_script(script):
    import json
    import os
    import subprocess
    import sys

    res = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(__file__)), timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_shard_map_matches_sequential_multidevice():
    out = _run_subprocess_script(_SHARD_SCRIPT)
    for case, r in out.items():
        assert r["param_maxdiff"] <= 1e-5, (case, r)
        assert r["loss_maxdiff"] <= 1e-5, (case, r)
        assert r["books_equal"], (case, r)


# Heterogeneous plans on a genuinely sharded 2-device mesh: the per-client
# bitmask crosses device boundaries with its clients (3 clients pad to 4, two
# per device — the padding client's all-zero mask and zero weights must stay
# inert), per-group weight sums psum across the mesh, and the async runtime
# dispatches plan cohorts through the same submesh-bound programs at
# inflight 1 AND 2.  Slow lane: the nightly job runs it via tier1.sh --slow.
_HETERO_SHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys, json
sys.path.insert(0, "src")
import jax
import numpy as np
from repro.core.compile_cache import enable_compile_cache
enable_compile_cache()

from repro.core.schedule import FedPartSchedule
from repro.data import (VisionDatasetSpec, balanced_eval_set, build_clients,
                        make_vision_dataset)
from repro.fl import AlgoConfig, FLRunConfig, resnet_task, run_federated

assert len(jax.devices()) == 2, jax.devices()

def make_setup(client_sizes):
    spec = VisionDatasetSpec(num_classes=4, image_size=8)
    X, y = make_vision_dataset(spec, sum(client_sizes), seed=0)
    Xe, ye = make_vision_dataset(spec, 64, seed=9)
    eval_set = balanced_eval_set(Xe, ye, per_class=8)
    bounds = np.cumsum((0,) + tuple(client_sizes))
    parts = [np.arange(bounds[i], bounds[i + 1])
             for i in range(len(client_sizes))]
    return resnet_task("resnet4", num_classes=4), build_clients(X, y, parts), eval_set

TIERS = (0.34, 0.67, 1.0)

def run(setup, algo, engine, rounds, runtime="sync", inflight=1):
    adapter, clients, eval_set = setup
    cfg = FLRunConfig(local_epochs=1, batch_size=16, lr=2e-3, adam_eps=1e-2,
                      algo=AlgoConfig(name=algo), engine=engine, sim_devices=2,
                      runtime=runtime, max_inflight_cohorts=inflight,
                      plan="nested", capacity_tiers=TIERS)
    return run_federated(adapter, clients, eval_set, rounds, cfg)

def diffs(a, b):
    pd = max(float(np.max(np.abs(np.asarray(x) - np.asarray(z))))
             for x, z in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)))
    ld = max(abs(x["loss"] - z["loss"]) for x, z in zip(a.history, b.history))
    books = (a.comm_total_bytes == b.comm_total_bytes
             and a.comp_total_flops == b.comp_total_flops)
    return {"param_maxdiff": pd, "loss_maxdiff": ld, "books_equal": books}

# warm-up FNU + a *group-4* partial round: group 4 sits outside tier 0's
# nested prefix (3), so both rounds are genuinely heterogeneous (the group-0
# partial of the fast lane's MIXED would collapse to the legacy path)
from repro.core.schedule import RoundSpec
MIXED = [FedPartSchedule(num_groups=6, warmup_rounds=1).rounds()[0],
         RoundSpec(index=1, phase="partial", cycle=0, group=4)]
results = {}
ragged = make_setup((36, 56, 40))         # one bucket, padded 3 -> 4 clients
for algo in ("fedavg", "fedprox"):
    seq = run(ragged, algo, "sequential", MIXED)
    shard = run(ragged, algo, "shard_map", MIXED)
    results[f"{algo}_hetero"] = diffs(seq, shard)
    if algo == "fedavg":
        results["fedavg_hetero_vmap_vs_shard"] = diffs(
            run(ragged, algo, "vmap", MIXED), shard)
        # degenerate async with hetero plans through the sharded backend,
        # merge-driven (inflight=1) and host-parallel (inflight=2: full
        # participation leaves no second cohort, so it must collapse to the
        # same barrier arithmetic on width-1 submesh-bound plan programs)
        results["fedavg_hetero_async_shard"] = diffs(
            run(ragged, algo, "shard_map", MIXED, runtime="async",
                inflight=1), shard)
        results["fedavg_hetero_async_shard_inflight2"] = diffs(
            run(ragged, algo, "shard_map", MIXED, runtime="async",
                inflight=2), shard)
buckets = make_setup((12, 36, 20))        # two buckets, each padded to 2
results["fedavg_hetero_buckets"] = diffs(
    run(buckets, "fedavg", "sequential", MIXED[1:]),
    run(buckets, "fedavg", "shard_map", MIXED[1:]))
print(json.dumps(results))
"""


@pytest.mark.slow
def test_hetero_plan_shard_map_multidevice():
    out = _run_subprocess_script(_HETERO_SHARD_SCRIPT)
    for case, r in out.items():
        assert r["param_maxdiff"] <= 1e-5, (case, r)
        assert r["loss_maxdiff"] <= 1e-5, (case, r)
        assert r["books_equal"], (case, r)


# -- compressed transmitted subtrees (compression=..., docs/COMPRESSION.md) --
#
# The quantize->dequantize transmission step (core.compress) runs in three
# places — the sequential oracle's host loop, the vmap engine's jitted tx
# stage, and *inside* the shard_map device program before the weight-scale
# psum — plus host-side at async update resolution.  All four must agree to
# <=1e-5 on params/losses and exactly on the byte books (the ledger prices
# the encoded wire format).  Error-feedback residuals are keyed by real
# client id (``run_round(client_ids=...)``), so engine equivalence here also
# pins the residual threading.
#
# Tolerance note: quantization amplifies the usual cross-engine float noise
# only when a ~1e-7 pre-quantization difference flips a rounding decision
# (one int8 step = scale/127) or a top-k threshold tie.  At this module's
# scale (lr=2e-3, 2 rounds) the measured cross-engine divergence stays at
# ~1e-7 for almost every element, but a single near-boundary element can
# flip a bin and surface at ~1e-5 — trajectory luck, not an engine bug
# (error feedback repays the flip on the next transmission).  The
# compressed-path tests therefore run at COMPRESS_TOL; every uncompressed
# test keeps the strict 1e-5 bar.

COMPRESS_KINDS = ("int8", "topk")
COMPRESS_TOL = 5e-5


@pytest.mark.parametrize("kind", COMPRESS_KINDS)
def test_compress_vmap_matches_sequential(setup, kind):
    seq = _run(setup, "fedavg", "sequential", MIXED, compression=kind)
    vm = _run(setup, "fedavg", "vmap", MIXED, compression=kind)
    _assert_equivalent(seq, vm, tol=COMPRESS_TOL)


def test_compress_shard_map_matches_sequential(setup):
    """Compressed tx inside the device program (degenerate 1-device mesh);
    the multi-device sharpening lives in the slow 2-device subprocess."""
    seq = _run(setup, "fedavg", "sequential", MIXED, compression="int8")
    sm = _run(setup, "fedavg", "shard_map", MIXED, compression="int8")
    _assert_equivalent(seq, sm, tol=COMPRESS_TOL)


def test_compress_hetero_plan_engines_agree(setup):
    """int8 under nested per-client plans: the traced-bitmask tx variant
    (transmit_tree_plan) must match the oracle's structural selection."""
    seq = _run(setup, "fedavg", "sequential", HETERO_MIXED, compression="int8",
               plan="nested", capacity_tiers=TIERS, adam_eps=HETERO_EPS)
    vm = _run(setup, "fedavg", "vmap", HETERO_MIXED, compression="int8",
              plan="nested", capacity_tiers=TIERS, adam_eps=HETERO_EPS)
    _assert_equivalent(seq, vm, tol=COMPRESS_TOL)


@pytest.mark.slow
def test_compress_hetero_plan_shard_map(setup):
    """Plan + compression through the shard_map plan program (per-group
    eff-weight epilogue on the compressed view).  Slow lane: the vmap test
    above pins the same transmit_tree_plan arithmetic in tier-1 (nightly
    compress-equivalence job)."""
    seq = _run(setup, "fedavg", "sequential", HETERO_MIXED, compression="int8",
               plan="nested", capacity_tiers=TIERS, adam_eps=HETERO_EPS)
    sm = _run(setup, "fedavg", "shard_map", HETERO_MIXED, compression="int8",
              plan="nested", capacity_tiers=TIERS, adam_eps=HETERO_EPS)
    _assert_equivalent(seq, sm, tol=COMPRESS_TOL)


@pytest.mark.slow
def test_compress_random_plan_and_topk_shard_map(setup):
    """Random plan kind + top-k through all three engines (nightly): the
    sparsification threshold is the tie-sensitive case, so it gets the
    broader sweep in the slow lane."""
    for engine in ("vmap", "shard_map"):
        seq = _run(setup, "fedavg", "sequential", HETERO_MIXED,
                   compression="topk", plan="random", capacity_tiers=TIERS,
                   adam_eps=HETERO_EPS)
        other = _run(setup, "fedavg", engine, HETERO_MIXED,
                     compression="topk", plan="random", capacity_tiers=TIERS,
                     adam_eps=HETERO_EPS)
        _assert_equivalent(seq, other, tol=COMPRESS_TOL)


@pytest.mark.slow
def test_compress_ragged_buckets():
    """A client below the batch size (12 < 16) routes through its own
    batch-width bucket with its EF residual riding along — residual stacking
    is bucket-local but keyed by real client id."""
    small = _make_setup((12, 36, 20))
    seq = _run(small, "fedavg", "sequential", MIXED[1:], compression="int8")
    vm = _run(small, "fedavg", "vmap", MIXED[1:], compression="int8")
    _assert_equivalent(seq, vm, tol=COMPRESS_TOL)


def test_compress_async_degenerate_matches_sync(setup):
    """Degenerate async == sync under int8: the runtime's host-side
    compression at update resolution (against the dispatch-version model,
    with its own residual store) must reproduce the sync engines' in-round
    tx step, and the encoded byte books must match."""
    sync = _run(setup, "fedavg", "vmap", MIXED, compression="int8")
    asy = _run(setup, "fedavg", "vmap", MIXED, compression="int8",
               runtime="async")
    _assert_equivalent(sync, asy)


def test_compress_none_is_identical_to_default(setup):
    """compression="none" must be structurally absent: bit-for-bit equal to
    the pre-compression path on every engine, with no residual state ever
    allocated and no client-id requirement."""
    from repro.fl import LocalTrainer, make_engine
    from repro.optim.adam import AdamConfig

    for engine in ("sequential", "vmap", "shard_map"):
        base = _run(setup, "fedavg", engine, MIXED)
        none = _run(setup, "fedavg", engine, MIXED, compression="none")
        for a, b in zip(jax.tree.leaves(base.params),
                        jax.tree.leaves(none.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert base.comm_total_bytes == none.comm_total_bytes

    adapter, clients, _ = setup
    params = adapter.init(jax.random.key(0))
    part = adapter.partition(params)
    eng = make_engine(
        "vmap", trainer=LocalTrainer(adapter=adapter, partition=part,
                                     algo=AlgoConfig(),
                                     adam=AdamConfig(lr=1e-3)),
        partition=part, algo=AlgoConfig())
    assert eng.compression is None and eng._residuals == {}
    eng.run_round(params, MIXED[1], clients, seeds=[1, 2, 3],
                  weights=[1, 1, 1], epochs=1, batch_size=BATCH)
    assert eng._residuals == {}


def test_compress_requires_client_ids(setup):
    """Engines built with compression must refuse an id-less run_round —
    silently keying residuals by cohort position would corrupt error
    feedback under partial participation."""
    from repro.core import compress
    from repro.fl import LocalTrainer, make_engine
    from repro.optim.adam import AdamConfig

    adapter, clients, _ = setup
    params = adapter.init(jax.random.key(0))
    part = adapter.partition(params)
    eng = make_engine(
        "sequential", trainer=LocalTrainer(adapter=adapter, partition=part,
                                           algo=AlgoConfig(),
                                           adam=AdamConfig(lr=1e-3)),
        partition=part, algo=AlgoConfig(),
        compression=compress.make_config("int8"))
    with pytest.raises(ValueError, match="client_ids"):
        eng.run_round(params, MIXED[1], clients, seeds=[1, 2, 3],
                      weights=[1, 1, 1], epochs=1, batch_size=BATCH)


def test_compress_zero_trainer_groups_stay_frozen(setup):
    """Acceptance bar: on a partial round where some groups have no trainer
    (nested tiers on HETERO_MIXED's group-4 round leave groups 0/1/3/5
    untrained), those groups must stay bit-identical to the pre-round global
    even while other groups' error-feedback residuals are active."""
    from repro.core import masking

    adapter, clients, eval_set = setup
    untrained = (0, 1, 3, 5)
    for engine in ("sequential", "vmap", "shard_map"):
        cfg = FLRunConfig(local_epochs=1, batch_size=BATCH, lr=2e-3,
                          adam_eps=HETERO_EPS, engine=engine,
                          plan="nested", capacity_tiers=TIERS,
                          compression="onebit")
        res = run_federated(adapter, clients, eval_set, HETERO_MIXED[1:], cfg)
        init = adapter.init(jax.random.key(cfg.seed))
        frozen = masking.select(init, res.partition, untrained)
        got = masking.select(res.params, res.partition, untrained)
        for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(frozen)[0],
                                jax.tree.leaves(got)):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"{engine}: frozen {jax.tree_util.keystr(path)} moved")


# Compressed transmission on a genuinely sharded 2-device mesh: the tx step
# runs inside the device program (before the weight-scale psum), padding
# clients carry zero residuals, and the async runtime compresses host-side
# at resolution against the same dispatch-version model.  Slow lane: the
# nightly compress-equivalence job runs it via tier1.sh --slow.
_COMPRESS_SHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys, json
sys.path.insert(0, "src")
import jax
import numpy as np
from repro.core.compile_cache import enable_compile_cache
enable_compile_cache()

from repro.core.schedule import FedPartSchedule
from repro.data import (VisionDatasetSpec, balanced_eval_set, build_clients,
                        make_vision_dataset)
from repro.fl import AlgoConfig, FLRunConfig, resnet_task, run_federated

assert len(jax.devices()) == 2, jax.devices()

def make_setup(client_sizes):
    spec = VisionDatasetSpec(num_classes=4, image_size=8)
    X, y = make_vision_dataset(spec, sum(client_sizes), seed=0)
    Xe, ye = make_vision_dataset(spec, 64, seed=9)
    eval_set = balanced_eval_set(Xe, ye, per_class=8)
    bounds = np.cumsum((0,) + tuple(client_sizes))
    parts = [np.arange(bounds[i], bounds[i + 1])
             for i in range(len(client_sizes))]
    return resnet_task("resnet4", num_classes=4), build_clients(X, y, parts), eval_set

def run(setup, engine, rounds, compression, runtime="sync"):
    adapter, clients, eval_set = setup
    cfg = FLRunConfig(local_epochs=1, batch_size=16, lr=2e-3, adam_eps=1e-3,
                      algo=AlgoConfig(), engine=engine, sim_devices=2,
                      runtime=runtime, compression=compression)
    return run_federated(adapter, clients, eval_set, rounds, cfg)

def diffs(a, b):
    pd = max(float(np.max(np.abs(np.asarray(x) - np.asarray(z))))
             for x, z in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)))
    ld = max(abs(x["loss"] - z["loss"]) for x, z in zip(a.history, b.history))
    books = a.comm_total_bytes == b.comm_total_bytes
    return {"param_maxdiff": pd, "loss_maxdiff": ld, "books_equal": books}

MIXED = FedPartSchedule(num_groups=6, warmup_rounds=1, rounds_per_layer=1,
                        cycles=1).rounds()[:2]
results = {}
ragged = make_setup((36, 56, 40))         # one bucket, padded 3 -> 4 clients
for kind in ("int8", "topk"):
    seq = run(ragged, "sequential", MIXED, kind)
    shard = run(ragged, "shard_map", MIXED, kind)
    results[kind] = diffs(seq, shard)
# none bitwise: explicit "none" == the default pre-compression config
base = run(ragged, "shard_map", MIXED, "none")
none = run(ragged, "shard_map", MIXED, "none")
r = diffs(base, none)
results["none_bitwise"] = dict(r, books_equal=(r["param_maxdiff"] == 0.0
                                               and r["books_equal"]))
# degenerate async on the sharded backend, int8: host-side resolution
# compression must match the in-program tx of the sync path
results["int8_async"] = diffs(
    run(ragged, "shard_map", MIXED, "int8", runtime="async"),
    run(ragged, "shard_map", MIXED, "int8"))
print(json.dumps(results))
"""


@pytest.mark.slow
def test_compress_shard_map_multidevice():
    out = _run_subprocess_script(_COMPRESS_SHARD_SCRIPT)
    for case, r in out.items():
        tol = 0.0 if case == "none_bitwise" else 1e-5
        assert r["param_maxdiff"] <= tol, (case, r)
        assert r["loss_maxdiff"] <= tol, (case, r)
        assert r["books_equal"], (case, r)
