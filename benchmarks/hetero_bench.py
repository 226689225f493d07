"""Heterogeneity bench: per-client layer plans across engines and tiers.

Under a heterogeneous plan (``FLRunConfig.plan``, docs/HETEROGENEITY.md) a
mixed cohort stops sharing one pruned single-group program: the batched
engines switch to the masked plan program (the per-client group bitmask is a
stacked batch input) and aggregation runs per-group participant-weighted
averaging.  This bench prices that machinery on the tiny-transformer NLP
regime (where the batched engines win on CPU — docs/ENGINES.md):

* per-round wall-clock for each plan kind (homogeneous / nested / random)
  under the vmap engine, with the homogeneous row doubling as the legacy
  baseline;
* ``speedup`` rows the CI bench lane gates (scale-free, benchmarks/compare.py):
  vmap vs sequential *under a nested plan*, and the plan-overhead ratio
  (homogeneous vs nested wall-clock — what switching the masked program on
  costs);
* **per-tier clients/s**: for the nested plan, each capacity tier's clients
  processed per second per device (``clients_per_sec_per_device``) — the
  scale-free throughput split the hetero scheduler actually delivers per
  tier.

    PYTHONPATH=src python benchmarks/hetero_bench.py --clients 8 --reps 3
    PYTHONPATH=src python benchmarks/hetero_bench.py --json hetero.json

``--json PATH`` writes the rows machine-readable (the ``BENCH_*.json``
trajectory format; BENCH_hetero.json is the committed baseline the bench CI
lane compares against).  Also exposes ``run(quick=True)`` for
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
    from repro.launch._simdev import force_sim_devices
    force_sim_devices()

import jax

from repro.configs.base import get_config
from repro.core.schedule import FULL_NETWORK, PlanAssigner, RoundSpec
from repro.data import (TextDatasetSpec, build_clients, iid_partition,
                        make_text_dataset)
from repro.fl import AlgoConfig, LocalTrainer, make_engine, nlp_task
from repro.optim.adam import AdamConfig

TIERS = (0.3, 0.6, 1.0)
PARTIAL_GROUP = 1


def _setup(clients: int, samples_per_client: int):
    cfg = get_config("nlp-transformer", smoke=True).with_(
        num_layers=1, d_model=32, num_heads=2, num_kv_heads=2, d_ff=64,
        vocab_size=256, max_position_embeddings=12)
    spec = TextDatasetSpec(num_classes=4, vocab_size=256, seq_len=12)
    X, y = make_text_dataset(spec, samples_per_client * clients, seed=0)
    adapter = nlp_task(num_classes=4, cfg=cfg)
    data = build_clients(X, y, iid_partition(len(y), clients, seed=0))
    params = adapter.init(jax.random.key(0))
    return adapter, data, params, adapter.partition(params)


def _time_plan_round(engine_name, adapter, data, params, partition, spec,
                     plan_kind, *, reps, batch_size=8, sim_devices=0):
    """Fresh trainer+engine, one warmup round (compile) then ``reps`` timed
    rounds of ``spec`` under ``plan_kind``.  Returns (sec/round, devices)."""
    algo = AlgoConfig()
    trainer = LocalTrainer(adapter=adapter, partition=partition, algo=algo,
                           adam=AdamConfig(lr=1e-3))
    engine = make_engine(engine_name, trainer=trainer, partition=partition,
                         algo=algo, sim_devices=sim_devices)
    assigner = PlanAssigner(num_groups=partition.num_groups, kind=plan_kind,
                            capacity_tiers=TIERS)
    plan = assigner.assign(spec, list(range(len(data))))
    seeds = list(range(len(data)))
    weights = [len(d) for d in data]
    import jax.numpy as jnp
    p = jax.tree.map(jnp.copy, params)   # donation-safe private copy

    def one_round(p):
        new_params, _, _ = engine.run_round(
            p, spec, data, seeds=seeds, weights=weights,
            epochs=1, batch_size=batch_size, plan=plan)
        jax.block_until_ready(jax.tree.leaves(new_params))
        return new_params

    p = one_round(p)                 # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        p = one_round(p)
    return (time.perf_counter() - t0) / reps, getattr(engine, "num_devices", 1)


def bench(clients=8, samples_per_client=32, reps=3, sim_devices=0,
          verbose=True):
    adapter, data, params, partition = _setup(clients, samples_per_client)
    assigner = PlanAssigner(num_groups=partition.num_groups, kind="nested",
                            capacity_tiers=TIERS)
    rows = []
    # Mixed phases like the equivalence tests: the FNU round is where nested
    # plans diverge most (every tier trains a different prefix).
    for phase, spec in [
        ("partial", RoundSpec(0, "partial", 0, PARTIAL_GROUP)),
        ("fnu", RoundSpec(0, "warmup", -1, FULL_NETWORK)),
    ]:
        times = {}
        for kind in ("homogeneous", "nested", "random"):
            sec, ndev = _time_plan_round(
                "vmap", adapter, data, params, partition, spec, kind,
                reps=reps, sim_devices=sim_devices)
            times[kind] = sec
            thr = clients / (sec * ndev)
            rows.append({
                "name": f"hetero_nlp_{phase}_{kind}_vmap_c{clients}",
                "us_per_call": sec * 1e6,
                "clients_per_sec_per_device": thr,
                "derived": f"{thr:.1f} clients/s/dev",
            })
            if verbose:
                print(f"[hetero:{phase:7s}] {kind:12s} vmap "
                      f"{sec*1e3:8.1f} ms/round {thr:.1f} clients/s/dev")
        # plan overhead: what the masked plan program costs vs the legacy
        # single-group program on the SAME cohort (scale-free, gated)
        overhead = times["homogeneous"] / times["nested"]
        rows.append({
            "name": f"hetero_nlp_{phase}_plan_overhead_vmap_c{clients}",
            "us_per_call": (times["nested"] - times["homogeneous"]) * 1e6,
            "speedup": overhead,
            "derived": f"homog/nested={overhead:.2f}x",
        })
        if verbose:
            print(f"[hetero:{phase:7s}] plan overhead: nested is "
                  f"{1/overhead:.2f}x homogeneous wall-clock")
        # vmap vs sequential under the nested plan (scale-free, gated):
        # batching must keep paying once cohorts are heterogeneous
        seq_sec, _ = _time_plan_round(
            "sequential", adapter, data, params, partition, spec, "nested",
            reps=reps)
        speedup = seq_sec / times["nested"]
        rows.append({
            "name": f"hetero_nlp_{phase}_nested_vmap_speedup_c{clients}",
            "us_per_call": 0.0,
            "speedup": speedup,
            "derived": f"{speedup:.2f}x vs sequential",
        })
        if verbose:
            print(f"[hetero:{phase:7s}] nested vmap speedup vs sequential: "
                  f"{speedup:.2f}x")
        # per-tier clients/s: the round processes every tier together; each
        # tier's share of the cohort divided by the same round wall-clock —
        # the throughput the scheduler delivers per capacity class
        ndev = max(sim_devices, 1)
        tier_of = [assigner.tier_of(ci) for ci in range(clients)]
        for t, cap in enumerate(TIERS):
            n_tier = sum(1 for x in tier_of if x == t)
            if n_tier == 0:
                continue
            thr = n_tier / (times["nested"] * ndev)
            rows.append({
                "name": f"hetero_nlp_{phase}_nested_tier{cap}_c{clients}",
                "us_per_call": times["nested"] * 1e6,
                "clients_per_sec_per_device": thr,
                "derived": f"{n_tier} clients @ cap {cap}: "
                           f"{thr:.1f} clients/s/dev",
            })
            if verbose:
                print(f"[hetero:{phase:7s}] tier cap={cap}: {n_tier} clients "
                      f"-> {thr:.1f} clients/s/dev")
    return rows


def run(quick: bool = True):
    """Harness hook for ``python -m benchmarks.run``."""
    return bench(clients=8, reps=2 if quick else 5, verbose=False)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--samples-per-client", type=int, default=32)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--sim-devices", type=int, default=0,
                    help="forced CPU host devices (also the shard_map mesh)")
    ap.add_argument("--json", default="",
                    help="write rows as machine-readable JSON (BENCH_*.json)")
    args = ap.parse_args(argv)

    from benchmarks.common import write_json_rows
    from repro.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = bench(clients=args.clients,
                 samples_per_client=args.samples_per_client,
                 reps=args.reps, sim_devices=args.sim_devices)
    print("\nname,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    if args.json:
        write_json_rows(args.json, rows, bench="hetero_bench",
                        clients=args.clients, reps=args.reps,
                        tiers=list(TIERS))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
