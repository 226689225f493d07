"""Population-scale bench: per-round overhead and peak host memory when the
fleet goes from thousands (materialised) to a million (streamed).

Three variants of the same federation — identical model, cohort size, and
round count; only the client store changes:

* ``mat_nS``      — the legacy path: every shard materialised up front
                    (``build_clients`` over one global array);
* ``stream_nS``   — a ``fl.population.SyntheticPopulation`` of the same S
                    clients, shards derived on demand from (seed, id);
* ``stream_nL``   — the same streaming store at L = 10^6 clients: the
                    population the legacy path cannot even allocate.

Each row reports per-round wall-clock (warm compile cache; the cohort's
training cost is identical across variants, so wall differences isolate the
client-store overhead) and the tracemalloc peak of host allocations across
the run (device buffers are out of scope — the population machinery is
host-side numpy by design).

Two scale-free ratios feed the CI regression gate (``benchmarks/compare.py``,
``bench.yml``):

* ``overhead_ratio``  = per-round wall at L-stream / S-stream.  O(cohort)
  dispatch means the population size must not show up in the round loop —
  the ratio stays ~1 and a regression means an O(N) scan crept back in;
* ``peak_ratio``      = peak host bytes at L-stream / S-materialised.  The
  million-client run must stay *cheaper* than materialising thousands —
  the ratio sits well below 1 and a regression means the store started
  retaining O(population) state.

    PYTHONPATH=src python benchmarks/population_bench.py --json population.json
    PYTHONPATH=src python benchmarks/population_bench.py --population 1000000
"""

from __future__ import annotations

import argparse
import os
import sys
import time
import tracemalloc

sys.path.insert(0, "src")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from repro.core.schedule import FedPartSchedule
from repro.data import (VisionDatasetSpec, balanced_eval_set, build_clients,
                        iid_partition, make_vision_dataset)
from repro.fl import FLRunConfig, resnet_task, run_federated
from repro.fl.population import SyntheticPopulation


def _setup(num_classes=4, image_size=8):
    spec = VisionDatasetSpec(num_classes=num_classes, image_size=image_size)
    Xe, ye = make_vision_dataset(spec, 128, seed=99)
    eval_set = balanced_eval_set(Xe, ye, per_class=16)
    return spec, resnet_task("resnet4", num_classes=num_classes), eval_set


def _measure(adapter, clients, eval_set, rounds, cfg):
    """(per-round wall seconds, peak host bytes) for one federated run.

    tracemalloc wraps the whole run — including, for the materialised
    variant, nothing (its arrays were built outside) — so builders are
    passed as thunks: the O(N) materialisation cost must land inside the
    traced region it belongs to."""
    tracemalloc.start()
    data = clients() if callable(clients) else clients
    t0 = time.time()
    res = run_federated(adapter, data, eval_set, rounds, cfg)
    wall = time.time() - t0
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert res.history, "bench run produced no rounds"
    return wall / max(len(rounds), 1), peak


def bench(population_small=2000, population_large=1_000_000, cohort=4,
          rounds=3, samples_per_client=16, verbose=True):
    spec, adapter, eval_set = _setup()
    sched = FedPartSchedule(num_groups=4, warmup_rounds=1, rounds_per_layer=1,
                            cycles=1)
    specs = sched.rounds()[:rounds]
    cfg = FLRunConfig(local_epochs=1, batch_size=16, lr=2e-3, adam_eps=1e-3,
                      engine="sequential", cohort_size=cohort)

    # Warm the XLA compiles on a throwaway fleet so every measured run pays
    # only the client-store costs the bench is about.
    warm = SyntheticPopulation(spec=spec, population=8,
                               samples_per_client=samples_per_client, seed=1)
    run_federated(adapter, warm, eval_set, specs, cfg)

    def mat_clients():
        X, y = make_vision_dataset(
            spec, samples_per_client * population_small, seed=0)
        return build_clients(
            X, y, iid_partition(len(y), population_small, seed=0))

    variants = [
        (f"mat_n{population_small}", mat_clients),
        (f"stream_n{population_small}", lambda: SyntheticPopulation(
            spec=spec, population=population_small,
            samples_per_client=samples_per_client, seed=0)),
        (f"stream_n{population_large}", lambda: SyntheticPopulation(
            spec=spec, population=population_large,
            samples_per_client=samples_per_client, seed=0)),
    ]

    rows, stats = [], {}
    for name, clients in variants:
        per_round, peak = _measure(adapter, clients, eval_set, specs, cfg)
        stats[name] = (per_round, peak)
        row = {
            "name": f"population_{name}",
            "us_per_call": 1e6 * per_round,
            "derived": (f"per_round={per_round:.3f}s "
                        f"peak_host={peak / 1e6:.1f}MB"),
            "wall_seconds": per_round * len(specs),
            "per_round_seconds": per_round,
            "peak_host_bytes": peak,
            "cohort": cohort,
            "rounds": len(specs),
        }
        rows.append(row)
        if verbose:
            print(f"[{name:16s}] {row['derived']}")

    small, large = (f"stream_n{population_small}",
                    f"stream_n{population_large}")
    mat = f"mat_n{population_small}"
    overhead = stats[large][0] / max(stats[small][0], 1e-9)
    peak_ratio = stats[large][1] / max(stats[mat][1], 1)
    rows.append({
        "name": f"population_overhead_n{population_large}",
        "us_per_call": 0.0,
        "derived": f"{overhead:.2f}x per-round wall vs n={population_small}",
        "overhead_ratio": overhead,
    })
    rows.append({
        "name": f"population_peak_n{population_large}",
        "us_per_call": 0.0,
        "derived": (f"{peak_ratio:.3f}x peak host memory vs materialised "
                    f"n={population_small}"),
        "peak_ratio": peak_ratio,
    })
    if verbose:
        print(f"[overhead_ratio  ] {overhead:.2f}x per-round "
              f"(1M stream vs {population_small} stream)")
        print(f"[peak_ratio      ] {peak_ratio:.3f}x peak host bytes "
              f"(1M stream vs {population_small} materialised)")
    return rows


def run(quick: bool = True):
    """Harness hook for ``python -m benchmarks.run``."""
    if quick:
        return bench(population_small=1000, rounds=2, verbose=False)
    return bench(verbose=False)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--population-small", type=int, default=2000)
    ap.add_argument("--population", type=int, default=1_000_000,
                    help="large (streamed) population size")
    ap.add_argument("--cohort-size", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--samples-per-client", type=int, default=16)
    ap.add_argument("--json", default="",
                    help="also write rows as machine-readable JSON to PATH")
    args = ap.parse_args(argv)
    from repro.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = bench(population_small=args.population_small,
                 population_large=args.population,
                 cohort=args.cohort_size, rounds=args.rounds,
                 samples_per_client=args.samples_per_client)
    if args.json:
        from benchmarks.common import write_json_rows
        write_json_rows(args.json, rows, bench="population_bench",
                        population_small=args.population_small,
                        population_large=args.population,
                        cohort=args.cohort_size, rounds=args.rounds)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
