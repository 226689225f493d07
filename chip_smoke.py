"""Smoke check: the FedPart federated round runs end to end on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the shard_map engine over four chips

With no option it runs, in one process:

1. the compiled masked-Adam Pallas kernel at ResNet-18's packed size with a
   mixed block mask, against the pure-jnp reference;
2. three federated rounds of ResNet-18 at its published widths on
   CIFAR-100-shaped synthetic data (8 clients x 128 samples, batch 32) through
   ``run_federated`` with the ``vmap`` engine and ``fused_adam=True``: one
   full-network warm-up round, then partial rounds on layer groups 0 and 1;
3. one partial round at 2 clients on the ``vmap`` and ``sequential`` engines,
   compared leaf by leaf at default and at highest matmul precision
   (``adam_eps=1e-3``, see ``ENGINE_RTOL``);
4. the device's peak memory.

``--four-chips`` runs only the ``shard_map`` engine over four chips (2 clients
per chip) for one full-network and one partial round, compares it with the
``vmap`` engine on one chip in the same process (both at highest matmul
precision), and prints where the stacked client arrays live.

The times printed are smoke timings of a cold run (compiles included), not a
benchmark.  The script exits non-zero when JAX finds no TPU or any phase
fails.  Its last line is one JSON object naming the device it ran on.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import time
from dataclasses import dataclass

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.aggregation import is_local_stat  # noqa: E402
from repro.core.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.schedule import FedPartSchedule  # noqa: E402
from repro.data import (VisionDatasetSpec, balanced_eval_set,  # noqa: E402
                        build_clients, iid_partition, make_vision_dataset)
from repro.fl import (AlgoConfig, FLRunConfig, LocalTrainer,  # noqa: E402
                      make_engine, resnet_task, run_federated)
from repro.kernels.masked_adam import ops as madam_ops  # noqa: E402
from repro.kernels.masked_adam.kernel import masked_adam_kernel  # noqa: E402
from repro.kernels.masked_adam.ref import masked_adam_ref  # noqa: E402
from repro.optim.adam import AdamConfig  # noqa: E402

# Kernel vs reference: both do the same float32 elementwise arithmetic, so
# they may differ only in how division, sqrt and fused multiply-adds round —
# a few ulps of O(1) moments and of O(1e-2) parameter updates.  Frozen blocks
# are copied through and must match exactly.
KERNEL_ATOL = 1e-5

# Engine vs engine: ||a - b|| / ||b - init|| over the whole tree, the gap
# between two engines' aggregated params as a share of the round's update.
# Two things make the engines differ without a fault:
# - Adam's first steps normalise a near-zero gradient to +-1, so a change of
#   summation order can flip an update's sign.  The stem's BN bias has such
#   gradients (the batch-norms after it cancel most of its effect): with eps
#   1e-8 that leaf differed by 0.25 of its norm at default precision and by
#   0.068 at highest, on a v5e.  As in tests/test_engine_equivalence.py, the
#   comparison runs take adam_eps=1e-3, which keeps near-zero gradients in
#   Adam's linear regime; both engines still run one config.
# - At the TPU's default precision, float32 matmul operands are rounded to
#   bfloat16, and the engines round different intermediates (the vmap engine
#   folds the clients into grouped convolutions).  That gap is printed; the
#   check runs at "highest" precision, where summation order is what is left.
# What remains on a v5e, nearly all of it in that BN bias and the same in
# every run: 2.17e-2 of the update for one round of 2 clients (0.255 at
# default precision), 5.50e-2 for two rounds of 8 clients, shard_map on four
# chips against vmap on one.  A fault is far larger (CPU, same configs):
# dropping one of the 2 clients moves the update by 0.94 of itself, dropping
# one chip's 2 of the 8 clients by 0.51.  0.15 sits between the two.
ENGINE_RTOL = 0.15
COMPARE_ADAM_EPS = 1e-3
PRECISIONS = ("default", "highest")

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


@dataclass(frozen=True)
class Job:
    """One federated job: model, data shape and cohort."""
    depth: str = "resnet18"
    num_classes: int = 100
    image_size: int = 32
    clients: int = 8
    samples_per_client: int = 128
    batch_size: int = 32
    local_epochs: int = 1
    seed: int = 0


class CompileClock:
    """Host-clock stamps of JAX's trace/lower/compile events, so a window of
    wall time can say how much of it was compilation."""

    def __init__(self):
        self.events: list[tuple[float, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event in COMPILE_EVENTS:
            self.events.append((time.perf_counter(), duration))

    def within(self, t0: float, t1: float) -> float:
        return sum(d for t, d in self.events if t0 <= t < t1)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"[smoke] FAILED: {msg}")


def make_setup(job: Job, clients: int | None = None):
    """Adapter, client shards and balanced eval set, all from ``job.seed``."""
    n = clients or job.clients
    spec = VisionDatasetSpec(num_classes=job.num_classes,
                             image_size=job.image_size)
    x, y = make_vision_dataset(spec, n * job.samples_per_client, seed=job.seed)
    xe, ye = make_vision_dataset(spec, 6 * job.num_classes, seed=job.seed + 1)
    eval_set = balanced_eval_set(xe, ye, per_class=3)
    clients_data = build_clients(x, y, iid_partition(len(y), n, seed=job.seed))
    return resnet_task(job.depth, num_classes=job.num_classes), clients_data, eval_set


def schedule(adapter, job: Job):
    """Warm-up full-network round, then partial rounds on groups 0 and 1."""
    params = adapter.init(jax.random.key(job.seed))
    groups = adapter.partition(params).num_groups
    return FedPartSchedule(num_groups=groups, warmup_rounds=1,
                           rounds_per_layer=1).rounds()[:3]


def run_config(job: Job, engine: str, **kw) -> FLRunConfig:
    return FLRunConfig(local_epochs=job.local_epochs, batch_size=job.batch_size,
                       engine=engine, fused_adam=True, seed=job.seed, **kw)


def kernel_phase(job: Job, *, interpret: bool) -> dict:
    """The masked-Adam kernel at the job model's packed size, a mixed block
    mask from the model's own partition, against ``masked_adam_ref``."""
    adapter = resnet_task(job.depth, num_classes=job.num_classes)
    params = adapter.init(jax.random.key(job.seed))
    partition = adapter.partition(params)
    block_rows = 8
    trained = tuple(range(0, partition.num_groups, 3))
    mask = madam_ops.block_mask_for_group(params, partition, trained,
                                          block_rows, exclude=is_local_stat)
    p, _ = madam_ops.pack(params, block_rows)
    rows = p.shape[0]
    kg, km, kv = jax.random.split(jax.random.key(job.seed + 7), 3)
    g = jax.random.normal(kg, p.shape, jnp.float32)
    m = 0.1 * jax.random.normal(km, p.shape, jnp.float32)
    v = jax.random.uniform(kv, p.shape, jnp.float32, 0.01, 1.0)
    scalars = madam_ops.adam_scalars(jnp.int32(3), 1e-3, 0.9, 0.999, 1e-8)
    mask_dev = jnp.asarray(mask)
    kernel = jax.jit(lambda *a: masked_adam_kernel(
        *a, block_rows=block_rows, interpret=interpret))
    ref = jax.jit(lambda *a: masked_adam_ref(*a, block_rows=block_rows))
    out = kernel(p, g, m, v, mask_dev, scalars)
    want = ref(p, g, m, v, mask_dev, scalars)
    jax.block_until_ready(out)
    frozen = np.repeat(mask == 0, block_rows)
    diffs = {}
    frozen_exact = True
    for name, a, b, before in zip("pmv", out, want, (p, m, v)):
        a, b, before = np.asarray(a), np.asarray(b), np.asarray(before)
        diffs[name] = float(np.max(np.abs(a - b)))
        frozen_exact &= bool(np.array_equal(a[frozen], before[frozen]))
    res = {"rows": rows, "blocks": int(mask.size),
           "trained_blocks": int(mask.sum()), "max_abs_diff": diffs,
           "frozen_exact": frozen_exact}
    say(f"kernel masked_adam rows={rows} blocks={mask.size} "
        f"trained_blocks={int(mask.sum())} max_abs_diff "
        + " ".join(f"{k}={x:.3e}" for k, x in diffs.items())
        + f" frozen_exact={frozen_exact} (tol {KERNEL_ATOL:g})")
    check(0 < mask.sum() < mask.size, "block mask is not mixed")
    check(all(x <= KERNEL_ATOL for x in diffs.values()),
          f"kernel disagrees with masked_adam_ref: {diffs}")
    check(frozen_exact, "frozen blocks were not copied through exactly")
    return res


def rounds_phase(job: Job, clock: CompileClock | None) -> list[dict]:
    """The three FedPart rounds through ``run_federated`` on the vmap engine."""
    adapter, clients, eval_set = make_setup(job)
    rounds = schedule(adapter, job)
    t0 = time.perf_counter()
    res = run_federated(adapter, clients, eval_set, rounds,
                        run_config(job, "vmap"))
    t_end = time.perf_counter()
    # Round windows, back from the end: the bookkeeping after the last round
    # is host arithmetic of milliseconds.
    ends = t_end - np.cumsum([0.0] + [h["seconds"] for h in res.history[::-1]])
    bounds = ends[::-1]
    if clock is not None:
        say(f"setup (init, first compiles) wall_s={bounds[0] - t0:.3f} "
            f"compile_s={clock.within(t0, bounds[0]):.3f}")
    for i, h in enumerate(res.history):
        comp = (f"{clock.within(bounds[i], bounds[i + 1]):.3f}"
                if clock is not None else "n/a")
        say(f"round {h['round']} {h['phase']} group={h['group']} "
            f"loss={h['loss']:.4f} acc={h['acc']:.4f} "
            f"wall_s={h['seconds']:.3f} compile_s={comp} "
            f"(smoke timing, not a benchmark)")
        check(math.isfinite(h["loss"]), f"round {h['round']} loss is not finite")
        check(0.0 <= h["acc"] <= 1.0, f"round {h['round']} accuracy out of range")
    check(all(np.all(np.isfinite(np.asarray(x)))
              for x in jax.tree.leaves(res.params)), "non-finite params")
    return res.history


def compare(a, b, base) -> dict:
    """How far param tree ``a`` is from ``b``, both trained from ``base``:
    ``update_rel_l2`` = ||a - b|| / ||b - base|| over the whole tree, and
    the largest per-leaf ||a - b|| / ||b|| with its leaf."""
    rel, worst, absd, diff2, upd2 = 0.0, "", 0.0, 0.0, 0.0
    for (path, x), y, x0 in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                                jax.tree.leaves(b), jax.tree.leaves(base)):
        x, y, x0 = (np.asarray(t, np.float64) for t in (x, y, x0))
        d2 = float(np.sum((x - y) ** 2))
        diff2 += d2
        upd2 += float(np.sum((y - x0) ** 2))
        r = math.sqrt(d2 / max(float(np.sum(y ** 2)), 1e-60))
        if r > rel:
            rel, worst = r, jax.tree_util.keystr(path)
        absd = max(absd, float(np.max(np.abs(x - y))) if x.size else 0.0)
    return {"update_rel_l2": math.sqrt(diff2 / max(upd2, 1e-60)),
            "max_leaf_rel_l2": rel, "worst_leaf": worst,
            "max_abs_diff": absd}


def show(d: dict) -> str:
    return (f"update_rel_l2={d['update_rel_l2']:.3e} "
            f"max_leaf_rel_l2={d['max_leaf_rel_l2']:.3e} ({d['worst_leaf']}) "
            f"max_abs_diff={d['max_abs_diff']:.3e}")


def precision(name: str):
    return (contextlib.nullcontext() if name == "default"
            else jax.default_matmul_precision(name))


def engines_phase(job: Job) -> dict:
    """One partial round at 2 clients: vmap vs the sequential oracle, at
    default and at highest matmul precision; the check is at highest."""
    adapter, clients, eval_set = make_setup(job, clients=2)
    partial = schedule(adapter, job)[1:2]
    base = adapter.init(jax.random.key(job.seed))
    out = {}
    for name in PRECISIONS:
        with precision(name):
            vm = run_federated(adapter, clients, eval_set, partial,
                               run_config(job, "vmap",
                                          adam_eps=COMPARE_ADAM_EPS))
            seq = run_federated(adapter, clients, eval_set, partial,
                                run_config(job, "sequential",
                                           adam_eps=COMPARE_ADAM_EPS))
        out[name] = compare(vm.params, seq.params, base)
        say(f"engines vmap vs sequential, 2 clients, partial group "
            f"{partial[0].group}, {name} precision: {show(out[name])}")
    say(f"engines check at highest precision: tol {ENGINE_RTOL:g}")
    check(out["highest"]["update_rel_l2"] <= ENGINE_RTOL,
          f"vmap and sequential disagree: {out['highest']}")
    return out["highest"]


def four_chip_phase(job: Job, chips: int = 4) -> dict:
    """shard_map over ``chips`` devices vs vmap on one device, same cohort,
    at highest matmul precision (see ``ENGINE_RTOL``)."""
    check(len(jax.devices()) >= chips,
          f"--four-chips needs {chips} devices, found {len(jax.devices())}")
    adapter, clients, eval_set = make_setup(job)
    rounds = schedule(adapter, job)[:2]
    with precision("highest"):
        sm = run_federated(adapter, clients, eval_set, rounds,
                           run_config(job, "shard_map", sim_devices=chips,
                                      adam_eps=COMPARE_ADAM_EPS))
        vm = run_federated(adapter, clients, eval_set, rounds,
                           run_config(job, "vmap", adam_eps=COMPARE_ADAM_EPS))
    for h in sm.history:
        say(f"shard_map round {h['round']} {h['phase']} group={h['group']} "
            f"loss={h['loss']:.4f} acc={h['acc']:.4f} "
            f"wall_s={h['seconds']:.3f} (smoke timing, not a benchmark)")
        check(math.isfinite(h["loss"]), "shard_map loss is not finite")
    d = compare(sm.params, vm.params, adapter.init(jax.random.key(job.seed)))
    say(f"engines shard_map({chips} chips) vs vmap(1 chip), 2 rounds, "
        f"highest precision: {show(d)} (tol {ENGINE_RTOL:g})")
    check(d["update_rel_l2"] <= ENGINE_RTOL,
          f"shard_map and vmap disagree: {d}")

    # Where the stacked client arrays live: one cohort of the partial round
    # through the same engine, its per-client outputs left on the mesh.
    params = adapter.init(jax.random.key(job.seed))
    partition = adapter.partition(params)
    trainer = LocalTrainer(adapter=adapter, partition=partition,
                           algo=AlgoConfig(), adam=AdamConfig())
    engine = make_engine("shard_map", trainer=trainer, partition=partition,
                         algo=AlgoConfig(), sim_devices=chips, fused_adam=True)
    stacked, losses = engine.run_local_async(
        params, rounds[1], clients, seeds=list(range(len(clients))),
        epochs=job.local_epochs, batch_size=job.batch_size)
    leaf = jax.tree.leaves(stacked)[0]
    shards = sorted((s.device.id, s.data.shape[0]) for s in leaf.addressable_shards)
    say(f"stacked client params {leaf.shape} on devices "
        + ", ".join(f"{dev}:{n} clients" for dev, n in shards))
    check(len({dev for dev, _ in shards}) == chips,
          f"stacked clients are not spread over {chips} devices: {shards}")
    check(all(n == len(clients) // chips for _, n in shards),
          f"clients are not split evenly: {shards}")
    check(bool(np.all(np.isfinite(np.asarray(losses)))), "cohort loss not finite")
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        say(f"device {dev.id} peak_bytes_in_use="
            f"{stats.get('peak_bytes_in_use', 'not reported')}")
    return d


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the shard_map engine over four chips "
                         "against vmap on one")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[smoke] FAILED: no TPU found (jax sees {dev.platform}); "
              "this script only runs on the chip", file=sys.stderr)
        return 1
    enable_compile_cache()
    say(f"device {dev.device_kind} x{len(jax.devices())}, jax {jax.__version__}")
    job = Job()
    if args.four_chips:
        four_chip_phase(job)
    else:
        clock = CompileClock()
        kernel_phase(job, interpret=False)
        rounds_phase(job, clock)
        engines_phase(job)
        say(f"peak_bytes_in_use={dev.memory_stats()['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
