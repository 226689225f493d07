"""Async-runtime bench: staleness x participation time-to-accuracy sweep,
plus the host-parallel in-flight-cohort sweep.

For a fixed FedPart schedule on the tiny-transformer NLP task (the regime
where the batched engines win on CPU — docs/ENGINES.md), sweep the async
runtime's levers against a heterogeneous, jittery fleet:

* **participation** — the fraction of the fleet sampled per dispatch
  (``FLRunConfig.sample_fraction``);
* **staleness exponent** — the polynomial discount ``(1+s)^-a`` FedBuff
  applies to late updates (0 = no discount);
* **max in-flight cohorts** — host-parallel dispatch
  (``FLRunConfig.max_inflight_cohorts``, default sweep {1, 2, 4}): how many
  cohorts train concurrently on disjoint device submeshes.  These rows
  report host *wall-clock*, per-device client throughput, and the virtual
  overlap actually achieved, plus a scale-free ``speedup`` row (inflight=N
  vs inflight=1 wall-clock) that the CI bench lane gates on
  (``benchmarks/compare.py``).

* **server control loop** — a pinned straggler config run twice, with
  ``controller="static"`` and ``controller="adaptive"`` (docs/CONTROL.md),
  plus a scale-free ratio row (static clipped time-to-accuracy / adaptive
  clipped time-to-accuracy, virtual-clock only so it is deterministic and
  machine-independent) that the CI bench lane gates on: adaptive must not
  reach the threshold later than static.

* **trace-driven participation** — the same A/B on a *skewed diurnal
  availability trace* (wide per-client duty-cycle spread,
  ``participation_sampling="biased"`` + inverse-probability debiased
  merges, docs/ASYNC.md), with the participation controller off vs on
  (``controller_participation_target``).  Same clipped-tta ratio row,
  same CI gate.

plus the sync-barrier oracle as the reference row.  Each cell reports final
and best accuracy, *virtual* total time, time-to-accuracy at the threshold,
and the max staleness actually observed — the trade the async literature
cares about (fast virtual clock vs degraded merges).  Results are printed as
the usual CSV rows and, with ``--json``, written machine-readable for the
``BENCH_*.json`` trajectory.

    PYTHONPATH=src python benchmarks/async_bench.py --clients 8 --rounds 12
    PYTHONPATH=src python benchmarks/async_bench.py --sim-devices 4 \
        --inflight 1 2 4 --json async.json

``--sim-devices N`` (N > 1) forces N simulated CPU host devices so the
in-flight cohorts have disjoint submeshes to land on (must precede the first
jax import — handled below).  Also exposes ``run(quick=True)`` for
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
    # host-parallel dispatch on CPU: simulate N host devices (XLA reads the
    # flag at first-import time, so set it before the jax import below).
    from repro.launch._simdev import force_sim_devices
    force_sim_devices()

import jax
import numpy as np

from repro.configs.base import get_config
from repro.core.schedule import FedPartSchedule
from repro.data import (TextDatasetSpec, balanced_eval_set, build_clients,
                        iid_partition, make_text_dataset)
from repro.fl import AvailabilityConfig, FLRunConfig, nlp_task, run_federated


def _setup(clients: int, samples_per_client: int):
    cfg = get_config("nlp-transformer", smoke=True).with_(
        num_layers=1, d_model=32, num_heads=2, num_kv_heads=2, d_ff=64,
        vocab_size=256, max_position_embeddings=12)
    spec = TextDatasetSpec(num_classes=4, vocab_size=256, seq_len=12)
    X, y = make_text_dataset(spec, samples_per_client * clients, seed=0)
    Xe, ye = make_text_dataset(spec, 320, seed=99)
    eval_set = balanced_eval_set(Xe, ye, per_class=32)
    data = build_clients(X, y, iid_partition(len(y), clients, seed=0))
    adapter = nlp_task(num_classes=4, cfg=cfg)
    num_groups = adapter.partition(adapter.init(jax.random.key(0))).num_groups
    return adapter, data, eval_set, num_groups


def _devices_used(engine: str, sim_devices: int, inflight: int) -> int:
    """Devices a config's in-flight cohorts can actually occupy."""
    if engine == "sequential":
        return 1
    n = jax.device_count()
    if engine == "shard_map":
        return sim_devices if sim_devices > 0 else n
    return min(max(inflight, 1), n)          # vmap: width-1 submeshes


def bench(clients=8, samples_per_client=32, rounds=12, threshold=0.4,
          participations=(1.0, 0.5), staleness_exps=(0.0, 0.5, 2.0),
          inflights=(1, 2, 4), inflight_reps=3, speed_spread=3.0,
          engine="vmap", sim_devices=0, verbose=True):
    adapter, data, eval_set, num_groups = _setup(clients, samples_per_client)
    sched = FedPartSchedule(num_groups=num_groups, warmup_rounds=2,
                            rounds_per_layer=1, cycles=3, bridge_rounds=1)
    specs = sched.rounds()[:rounds]
    fleet = AvailabilityConfig(speed_spread=speed_spread, latency_jitter=0.2,
                               seed=7)
    base = dict(local_epochs=1, batch_size=8, lr=3e-3, engine=engine,
                sim_devices=sim_devices, availability=fleet)

    configs = [("sync_oracle", dict(runtime="async", async_policy="sync",
                                    sample_fraction=1.0))]
    for part in participations:
        for a in staleness_exps:
            configs.append((
                f"fedbuff_p{part:g}_a{a:g}",
                dict(runtime="async", async_policy="fedbuff",
                     buffer_k=max(1, int(round(part * clients)) // 2),
                     staleness_exponent=a, sample_fraction=part),
            ))
    # Host-parallel sweep: small cohorts (quarter of the fleet) so inflight
    # cohorts have idle clients to sample; goal = cohort size.
    for mi in inflights:
        configs.append((
            f"inflight{mi}",
            dict(runtime="async", async_policy="fedbuff", buffer_k=0,
                 staleness_exponent=0.5, sample_fraction=0.25,
                 max_inflight_cohorts=mi),
        ))

    # Adaptive-controller A/B (docs/CONTROL.md): the same straggler-bound
    # config (merge-driven dispatch, small cohorts, discounted staleness)
    # with the control loop off vs on.  Gated on *virtual* time-to-accuracy,
    # so the ratio row below is seed-deterministic and machine-independent.
    ab_base = dict(runtime="async", async_policy="fedbuff", buffer_k=0,
                   staleness_exponent=0.5, sample_fraction=0.25,
                   max_inflight_cohorts=1)
    configs.append(("ab_static", dict(ab_base)))
    configs.append(("ab_adaptive", dict(ab_base, controller="adaptive",
                                        controller_inflight_bounds=(1, 4))))

    # Trace-driven participation A/B (docs/ASYNC.md): the same fleet behind
    # a skewed diurnal availability trace, cohorts selected biased-by-
    # availability with inverse-probability debiased merges, with the
    # participation controller off vs on.  trace_period=2.0 puts several
    # on/off cycles inside the run's virtual span at this scale.
    trace_fleet = AvailabilityConfig(
        speed_spread=speed_spread, latency_jitter=0.2, seed=7,
        trace="diurnal", trace_period=2.0, duty_cycle=(0.25, 0.9))
    tr_base = dict(runtime="async", async_policy="fedbuff", buffer_k=0,
                   staleness_exponent=0.5, sample_fraction=0.25,
                   participation_sampling="biased", availability=trace_fleet)
    configs.append(("trace_static", dict(tr_base)))
    configs.append(("trace_adaptive", dict(
        tr_base, controller="adaptive",
        controller_participation_target=0.5,
        controller_cohort_bounds=(1, max(2, clients // 2)))))

    rows, inflight_walls, ab_tta = [], {}, {}
    for name, kw in configs:
        cfg = FLRunConfig(**{**base, **kw})
        # The inflight rows feed the CI regression gate, so their host
        # wall-clock is measured as the min over ``inflight_reps`` runs (the
        # virtual-time results are seed-deterministic and identical across
        # reps; min is the standard robust timing estimator and absorbs the
        # per-process warm-up rep).
        reps = inflight_reps if name.startswith("inflight") else 1
        wall = float("inf")
        for _ in range(max(1, reps)):
            t0 = time.time()
            res = run_federated(adapter, data, eval_set, specs, cfg)
            wall = min(wall, time.time() - t0)
        tl = res.timeline
        tta = tl.time_to_accuracy(threshold)
        stale = max((h["staleness_max"] for h in res.history), default=0)
        mi = kw.get("max_inflight_cohorts", 1)
        trained = len(tl.of_kind("complete")) + len(tl.of_kind("drop"))
        ndev = _devices_used(engine, sim_devices, mi)
        row = {
            "name": f"async_{name}_c{clients}",
            "us_per_call": 1e6 * wall / max(len(specs), 1),
            "derived": (f"best_acc={res.best_acc:.4f} "
                        f"vtime={tl.total_seconds:.2f}s "
                        f"tta@{threshold:g}="
                        f"{'inf' if np.isinf(tta) else f'{tta:.2f}'} "
                        f"max_stale={stale}"),
            "best_acc": res.best_acc,
            "final_acc": res.final_acc,
            "virtual_seconds": tl.total_seconds,
            "time_to_accuracy": None if np.isinf(tta) else tta,
            "accuracy_curve": tl.accuracy_curve(),
            "max_staleness": stale,
            "delivered_comm_bytes": tl.delivered_comm_bytes,
            "spent_comp_flops": tl.spent_comp_flops,
            "participation": kw.get("sample_fraction", 1.0),
            "staleness_exponent": kw.get("staleness_exponent", 0.0),
            "buffer_k": kw.get("buffer_k", 0),
            "policy": kw["async_policy"],
            "max_inflight": mi,
            "controller": kw.get("controller", "static"),
            "participation_sampling": kw.get("participation_sampling",
                                             "blind"),
            "wall_seconds": wall,
            "clients_trained": trained,
            "devices_used": ndev,
            "clients_per_sec_per_device": trained / max(wall * ndev, 1e-9),
            "virtual_overlap_seconds": tl.overlap_seconds(),
        }
        rows.append(row)
        if name.startswith(("ab_", "trace_")):
            # Clipped tta: a run that never reaches the threshold counts as
            # its full virtual span, so the ratio below stays finite and
            # still rewards finishing the same rounds in less virtual time.
            ab_tta[name] = min(tta, tl.total_seconds)
            row["derived"] += (" control="
                               f"{len(tl.of_kind('control'))} events")
        if name.startswith("inflight"):
            inflight_walls[mi] = wall
            row["derived"] += (f" wall={wall:.1f}s "
                               f"{row['clients_per_sec_per_device']:.2f} "
                               f"clients/s/dev "
                               f"overlap={row['virtual_overlap_seconds']:.2f}s")
        if verbose:
            print(f"[{name:20s}] wall={wall:5.1f}s {row['derived']}")

    # Scale-free host-overlap speedups: same config, inflight N vs 1 — the
    # metric the CI bench lane gates on (machine-speed independent).
    if 1 in inflight_walls:
        for mi, wall in sorted(inflight_walls.items()):
            if mi == 1:
                continue
            speedup = inflight_walls[1] / max(wall, 1e-9)
            rows.append({
                "name": f"async_inflight{mi}_speedup_c{clients}",
                "us_per_call": 0.0,
                "derived": f"{speedup:.2f}x wall vs inflight=1",
                "speedup": speedup,
                "max_inflight": mi,
            })
            if verbose:
                print(f"[inflight{mi} speedup   ] {speedup:.2f}x wall-clock "
                      f"vs inflight=1")

    # Adaptive-control gate: static clipped tta / adaptive clipped tta, as a
    # scale-free "speedup" row (>= 1 means the control loop pays its way).
    if {"ab_static", "ab_adaptive"} <= ab_tta.keys():
        ratio = ab_tta["ab_static"] / max(ab_tta["ab_adaptive"], 1e-9)
        rows.append({
            "name": f"async_adaptive_tta_ratio_c{clients}",
            "us_per_call": 0.0,
            "derived": (f"{ratio:.2f}x virtual tta vs static control "
                        f"(static={ab_tta['ab_static']:.2f}s "
                        f"adaptive={ab_tta['ab_adaptive']:.2f}s)"),
            "speedup": ratio,
            "controller": "adaptive",
        })
        if verbose:
            print(f"[adaptive tta ratio  ] {ratio:.2f}x virtual "
                  f"time-to-accuracy vs static control")

    # Trace-participation gate: same clipped-tta ratio on the skewed diurnal
    # trace — the participation controller must not slow the run down.
    if {"trace_static", "trace_adaptive"} <= ab_tta.keys():
        ratio = ab_tta["trace_static"] / max(ab_tta["trace_adaptive"], 1e-9)
        rows.append({
            "name": f"async_trace_adaptive_tta_ratio_c{clients}",
            "us_per_call": 0.0,
            "derived": (f"{ratio:.2f}x virtual tta vs static participation "
                        f"(static={ab_tta['trace_static']:.2f}s "
                        f"adaptive={ab_tta['trace_adaptive']:.2f}s)"),
            "speedup": ratio,
            "controller": "adaptive",
            "participation_sampling": "biased",
            "trace": "diurnal",
        })
        if verbose:
            print(f"[trace tta ratio     ] {ratio:.2f}x virtual "
                  f"time-to-accuracy vs static participation control")
    return rows


def run(quick: bool = True):
    """Harness hook: a reduced sweep in quick mode."""
    if quick:
        return bench(clients=6, rounds=8, participations=(0.5,),
                     staleness_exps=(0.0, 2.0), inflights=(1, 2),
                     verbose=False)
    return bench(clients=16, rounds=24, verbose=False)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--samples-per-client", type=int, default=32)
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--threshold", type=float, default=0.4,
                    help="accuracy threshold for time-to-accuracy")
    ap.add_argument("--speed-spread", type=float, default=3.0)
    ap.add_argument("--engine", choices=["sequential", "vmap", "shard_map"],
                    default="vmap")
    ap.add_argument("--sim-devices", type=int, default=0,
                    help="forced CPU host devices / shard_map mesh size "
                         "(must be the first jax use; gives inflight "
                         "cohorts disjoint submeshes to land on)")
    ap.add_argument("--inflight", type=int, nargs="+", default=[1, 2, 4],
                    help="max_inflight_cohorts values to sweep")
    ap.add_argument("--participations", type=float, nargs="*", default=None,
                    help="participation grid (empty list skips the "
                         "staleness sweep — the CI bench lane's pinned "
                         "config)")
    ap.add_argument("--staleness-exps", type=float, nargs="*", default=None)
    ap.add_argument("--json", default="",
                    help="also write rows as machine-readable JSON to PATH")
    args = ap.parse_args(argv)
    from repro.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    parts = ((1.0, 0.5) if args.participations is None
             else tuple(args.participations))
    exps = ((0.0, 0.5, 2.0) if args.staleness_exps is None
            else tuple(args.staleness_exps))
    rows = bench(clients=args.clients,
                 samples_per_client=args.samples_per_client,
                 rounds=args.rounds, threshold=args.threshold,
                 speed_spread=args.speed_spread, engine=args.engine,
                 sim_devices=args.sim_devices, participations=parts,
                 staleness_exps=exps, inflights=tuple(args.inflight))
    if args.json:
        from benchmarks.common import write_json_rows
        write_json_rows(args.json, rows, bench="async_bench",
                        clients=args.clients, rounds=args.rounds,
                        threshold=args.threshold,
                        speed_spread=args.speed_spread,
                        engine=args.engine, sim_devices=args.sim_devices,
                        inflight=list(args.inflight))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
