"""Client-engine bench: sequential vs vmap vs shard_map wall-clock + traces.

The sequential oracle dispatches one jitted call per (client, step) and syncs
the host on every loss; the vmap engine runs the whole round as one vmapped
program plus one on-device aggregation; the shard_map engine spreads the
client axis over a device mesh (``--sim-devices``) and psums the aggregate.
This bench measures steady-state *per-round* wall-clock (compile excluded —
each engine gets one warmup round per phase), the number of XLA traces each
engine built, and — for shard_map — per-device client throughput, for a
partial round and an FNU round.

The default workload is the cross-device regime the batched engines target —
many small clients on a tiny transformer — where per-dispatch overhead
dominates per-step compute and vmap amortises it across the client axis
(>=3x at 8 clients on this container's 2 CPU cores).  ``--task vision``
switches to the paper's conv model: there, per-client conv weights lower to
grouped convolutions that XLA:CPU executes poorly, so the batched engines
only pay off on accelerator backends — the bench reports it honestly either
way.  CPU "devices" forced via --sim-devices share the same physical cores:
shard_map numbers there measure engine overhead, not real parallel speedup
(docs/ENGINES.md).

The batched engines donate the global params into their aggregation jit by
default (in-place splice; ``make_engine(donate=...)``): each batched-engine
timing is taken both ways and a ``*_donate_delta`` row records the
throughput change and the live-device-buffer delta.

    PYTHONPATH=src python benchmarks/engine_bench.py --clients 8 --reps 5
    PYTHONPATH=src python benchmarks/engine_bench.py \
        --engine shard_map --sim-devices 4
    PYTHONPATH=src python benchmarks/engine_bench.py --json bench.json

``--json PATH`` additionally writes the rows as machine-readable JSON (the
``BENCH_*.json`` trajectory format).  Also exposes ``run(quick=True)`` for
``python -m benchmarks.run``.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, "src")
# repo root, so `benchmarks.common` resolves when run as a script too
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    # shard_map on CPU: simulate N host devices (XLA reads the flag at
    # first-import time, so set it before the jax import below).
    from repro.launch._simdev import force_sim_devices
    force_sim_devices()

import jax
import numpy as np

from repro.configs.base import get_config
from repro.core.schedule import FULL_NETWORK, RoundSpec
from repro.data import (TextDatasetSpec, VisionDatasetSpec, build_clients,
                        iid_partition, make_text_dataset, make_vision_dataset)
from repro.fl import AlgoConfig, LocalTrainer, make_engine, nlp_task, resnet_task
from repro.optim.adam import AdamConfig

PARTIAL_GROUP = 1


def _setup(task: str, clients: int, samples_per_client: int):
    if task == "nlp":
        cfg = get_config("nlp-transformer", smoke=True).with_(
            num_layers=1, d_model=32, num_heads=2, num_kv_heads=2, d_ff=64,
            vocab_size=256, max_position_embeddings=12)
        spec = TextDatasetSpec(num_classes=4, vocab_size=256, seq_len=12)
        X, y = make_text_dataset(spec, samples_per_client * clients, seed=0)
        adapter = nlp_task(num_classes=4, cfg=cfg)
        batch_size = 8
    elif task == "vision":
        spec = VisionDatasetSpec(num_classes=8, image_size=12)
        X, y = make_vision_dataset(spec, samples_per_client * clients, seed=0)
        adapter = resnet_task("resnet8", num_classes=8)
        batch_size = 32
    else:
        raise ValueError(f"unknown task {task!r}")
    data = build_clients(X, y, iid_partition(len(y), clients, seed=0))
    params = adapter.init(jax.random.key(0))
    return adapter, data, params, adapter.partition(params), batch_size


def _live_bytes() -> int:
    import gc
    gc.collect()
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.live_arrays())


def _time_engine(engine_name, adapter, data, params, partition, spec,
                 *, epochs, batch_size, reps, sim_devices=0, donate=True,
                 fused=False):
    """Fresh trainer+engine; one warmup round (compile), then ``reps`` timed
    rounds.  Returns (seconds_per_round, traces, mesh_devices, live_bytes).

    With donation on, ``run_round`` consumes its params argument, so the
    timed loop threads the returned tree through a private copy (identical
    shapes every round — no retraces, same per-round work either way)."""
    algo = AlgoConfig()
    trainer = LocalTrainer(adapter=adapter, partition=partition, algo=algo,
                           adam=AdamConfig(lr=1e-3), fused=fused)
    engine = make_engine(engine_name, trainer=trainer, partition=partition,
                         algo=algo, sim_devices=sim_devices, donate=donate)
    seeds = list(range(len(data)))
    weights = [len(d) for d in data]
    import jax.numpy as jnp
    p = jax.tree.map(jnp.copy, params)   # donation-safe private copy

    def one_round(p):
        new_params, _, _ = engine.run_round(
            p, spec, data, seeds=seeds, weights=weights,
            epochs=epochs, batch_size=batch_size)
        jax.block_until_ready(jax.tree.leaves(new_params))
        return new_params

    p = one_round(p)                 # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        p = one_round(p)
    per_round = (time.perf_counter() - t0) / reps
    live = _live_bytes()
    devices = getattr(engine, "num_devices", 1)
    return per_round, engine.trace_count, devices, live


def bench(task="nlp", clients=8, samples_per_client=32, epochs=1, reps=5,
          engines=("sequential", "vmap"), sim_devices=0, fused=False,
          verbose=True):
    adapter, data, params, partition, batch_size = _setup(
        task, clients, samples_per_client)
    # Opt-in fused masked-Adam local steps (docs/KERNELS.md).  Row names get
    # a `_fused` tag so a fused run never collides with the pinned unfused
    # rows the CI baseline gates — the fused lane is exploratory, not gated
    # (on CPU the kernel runs in interpret mode, so its absolute numbers
    # measure the emulator, not the TPU path).
    tag = "_fused" if fused else ""
    task_tag = f"{task}{tag}"
    rows = []
    for phase, spec in [
        ("partial", RoundSpec(0, "partial", 0, PARTIAL_GROUP)),
        ("fnu", RoundSpec(0, "warmup", -1, FULL_NETWORK)),
    ]:
        times, traces = {}, {}
        for name in engines:
            sec, tr, ndev, live = _time_engine(
                name, adapter, data, params, partition, spec, epochs=epochs,
                batch_size=batch_size, reps=reps, sim_devices=sim_devices,
                fused=fused)
            times[name], traces[name] = sec, tr
            derived = f"traces={tr}"
            extra = ""
            row = {
                "name": f"engine_{task_tag}_{phase}_{name}_c{clients}",
                "us_per_call": sec * 1e6,
                "traces": tr,
            }
            if name == "shard_map":
                # per-device client throughput: the scaling quantity this
                # engine exists for (clients processed per second per device)
                thr = clients / (sec * ndev)
                derived += f" devices={ndev} {thr:.1f} clients/s/dev"
                extra = f" [{ndev} dev, {thr:.1f} clients/s/dev]"
                row["devices"] = ndev
                row["clients_per_sec_per_device"] = thr
            row["derived"] = derived
            rows.append(row)
            if verbose:
                print(f"[{task}:{phase:7s}] clients={clients:3d} "
                      f"{name}={sec*1e3:8.1f} ms/round "
                      f"(traces={tr}){extra}")
            if name != "sequential":
                # Buffer-donation delta: same engine with donate=False (the
                # pre-donation behavior) vs the donate=True timing above.
                sec_nd, _, _, live_nd = _time_engine(
                    name, adapter, data, params, partition, spec,
                    epochs=epochs, batch_size=batch_size, reps=reps,
                    sim_devices=sim_devices, donate=False, fused=fused)
                thr_delta = (sec_nd / sec - 1.0) * 100.0
                mem_delta = (live_nd - live) / 1e6
                rows.append({
                    "name": f"engine_{task_tag}_{phase}_{name}_donate_delta_c{clients}",
                    "us_per_call": (sec_nd - sec) * 1e6,
                    "derived": (f"donate {thr_delta:+.1f}% throughput "
                                f"{mem_delta:+.2f}MB live saved"),
                    "throughput_delta_pct": thr_delta,
                    "live_mb_delta": mem_delta,
                })
                if verbose:
                    print(f"[{task}:{phase:7s}] clients={clients:3d} "
                          f"{name} donation: {thr_delta:+.1f}% throughput, "
                          f"live buffers {mem_delta:+.2f} MB vs no-donate")
        if "sequential" in times:
            for name in engines:
                if name == "sequential":
                    continue
                speedup = times["sequential"] / times[name]
                rows.append({
                    "name": f"engine_{task_tag}_{phase}_{name}_speedup_c{clients}",
                    "us_per_call": 0.0,
                    "derived": f"{speedup:.2f}x",
                    "speedup": speedup,
                })
                if verbose:
                    print(f"[{task}:{phase:7s}] clients={clients:3d} "
                          f"{name} speedup vs sequential: {speedup:.2f}x")
    return rows


def run(quick: bool = True):
    """Harness hook: one point in quick mode, a client sweep in full."""
    rows = []
    for clients in ((8,) if quick else (4, 8, 16, 32)):
        rows.extend(bench(clients=clients, reps=3 if quick else 5,
                          verbose=False))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", choices=["nlp", "vision"], default="nlp")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--samples-per-client", type=int, default=32)
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--engine",
                    choices=["all", "sequential", "vmap", "shard_map"],
                    default="all",
                    help="bench one engine (always paired with the "
                         "sequential baseline) or the default seq+vmap pair")
    ap.add_argument("--sim-devices", type=int, default=0,
                    help="shard_map mesh size; on CPU, N>1 forces N "
                         "simulated host devices (must be first jax use)")
    ap.add_argument("--fused", action="store_true",
                    help="opt-in: fused Pallas masked-Adam local steps "
                         "(docs/KERNELS.md); rows are tagged `_fused` and "
                         "are NOT part of the pinned CI baseline — on CPU "
                         "the kernel runs in interpret mode")
    ap.add_argument("--json", default="",
                    help="also write rows as machine-readable JSON to PATH")
    args = ap.parse_args(argv)
    from repro.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.engine == "all":
        engines = ("sequential", "vmap")
    elif args.engine == "sequential":
        engines = ("sequential",)
    else:
        engines = ("sequential", args.engine)
    rows = bench(task=args.task, clients=args.clients,
                 samples_per_client=args.samples_per_client,
                 epochs=args.epochs, reps=args.reps, engines=engines,
                 sim_devices=args.sim_devices, fused=args.fused)
    if args.json:
        from benchmarks.common import write_json_rows
        write_json_rows(args.json, rows, bench="engine_bench",
                        task=args.task, clients=args.clients,
                        reps=args.reps, engines=list(engines),
                        sim_devices=args.sim_devices, fused=args.fused)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
