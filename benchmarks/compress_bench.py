"""Compression bench: transmitted-subtree encodings across a federated run.

Sweeps ``FLRunConfig.compression`` (docs/COMPRESSION.md) on the tiny-NLP
vmap regime (where the batched engines win on CPU — docs/ENGINES.md) with a
short FedPart schedule, and prices what each wire format actually moves:

* per-round wall-clock + accuracy-at-budget for each kind
  (none / int8 / onebit / topk) — the lossy channel must not cost accuracy
  at this scale, and the qdq epilogue must stay noise-level on wall-clock;
* ``byte_ratio`` rows the CI bench lane gates (scale-free, carried in the
  ``speedup`` key for benchmarks/compare.py): dense transmitted bytes over
  encoded transmitted bytes, measured from the runs' own comm ledgers.
  These are deterministic functions of the parameter shapes and schedule,
  so the gate is tight even across runner classes.

The int8 ratio is asserted ≥ 3.9 in-bench: with one f32 scale per leaf the
exact ceiling is 4·n/(n+4L) ≈ 4× (never quite 4); onebit and topk clear 4×
with a wide margin.  See docs/COMPRESSION.md for the byte model.

    PYTHONPATH=src python benchmarks/compress_bench.py --reps 2
    PYTHONPATH=src python benchmarks/compress_bench.py --json compress.json

``--json PATH`` writes the rows machine-readable (the ``BENCH_*.json``
trajectory format; BENCH_compress.json is the committed baseline the bench
CI lane compares against).  Also exposes ``run(quick=True)`` for
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

from repro.configs.base import get_config
from repro.core.schedule import FedPartSchedule
from repro.data import (TextDatasetSpec, balanced_eval_set, build_clients,
                        iid_partition, make_text_dataset)
from repro.fl import FLRunConfig, nlp_task, run_federated

KINDS = ("none", "int8", "onebit", "topk")
INT8_MIN_RATIO = 3.9     # per-leaf-scale ceiling is 4·n/(n+4L) < 4


def _setup(clients: int, samples_per_client: int):
    cfg = get_config("nlp-transformer", smoke=True).with_(
        num_layers=1, d_model=32, num_heads=2, num_kv_heads=2, d_ff=64,
        vocab_size=256, max_position_embeddings=12)
    spec = TextDatasetSpec(num_classes=4, vocab_size=256, seq_len=12)
    X, y = make_text_dataset(spec, samples_per_client * clients, seed=0)
    Xe, ye = make_text_dataset(spec, 256, seed=7)
    eval_set = balanced_eval_set(Xe, ye, per_class=16)
    adapter = nlp_task(num_classes=4, cfg=cfg)
    data = build_clients(X, y, iid_partition(len(y), clients, seed=0))
    return adapter, data, eval_set


def bench(clients=8, samples_per_client=32, reps=2, verbose=True):
    adapter, data, eval_set = _setup(clients, samples_per_client)
    import jax
    num_groups = adapter.partition(adapter.init(jax.random.key(0))).num_groups
    # warmup + one pass over the groups: mixes an FNU round (worst case for
    # compression savings) with the partial rounds the paper runs on.
    sched = FedPartSchedule(num_groups=num_groups, warmup_rounds=1,
                            rounds_per_layer=1, cycles=1)
    rounds = sched.rounds()

    rows, bytes_by_kind, acc_by_kind = [], {}, {}
    for kind in KINDS:
        run_cfg = FLRunConfig(local_epochs=1, batch_size=8, lr=1e-3,
                              engine="vmap", compression=kind)
        secs = []
        for _ in range(reps):
            t0 = time.perf_counter()
            res = run_federated(adapter, data, eval_set, rounds, run_cfg)
            secs.append(time.perf_counter() - t0)
        sec = min(secs) / len(rounds)
        bytes_by_kind[kind] = int(res.comm_total_bytes)
        acc_by_kind[kind] = float(res.best_acc)
        rows.append({
            "name": f"compress_nlp_{kind}_vmap_c{clients}",
            "us_per_call": sec * 1e6,
            "best_acc": acc_by_kind[kind],
            "comm_bytes": bytes_by_kind[kind],
            "derived": f"best_acc={acc_by_kind[kind]:.4f} "
                       f"bytes={bytes_by_kind[kind]}",
        })
        if verbose:
            print(f"[compress] {kind:6s} vmap {sec*1e3:8.1f} ms/round "
                  f"acc={acc_by_kind[kind]:.4f} "
                  f"bytes={bytes_by_kind[kind]}")

    dense = bytes_by_kind["none"]
    for kind in KINDS[1:]:
        ratio = dense / bytes_by_kind[kind]
        # byte ratio rides the gated scale-free `speedup` key: it is a pure
        # shape/schedule function, so any drift is a real ledger regression
        rows.append({
            "name": f"compress_nlp_{kind}_byte_ratio_c{clients}",
            "us_per_call": 0.0,
            "speedup": ratio,
            "derived": f"{ratio:.2f}x fewer bytes than dense",
        })
        if verbose:
            print(f"[compress] {kind:6s} byte ratio: {ratio:.2f}x vs dense")
    int8_ratio = dense / bytes_by_kind["int8"]
    assert int8_ratio >= INT8_MIN_RATIO, (
        f"int8 byte ratio {int8_ratio:.3f} below {INT8_MIN_RATIO} — "
        "scale overhead grew past one f32 per leaf-equivalent block")
    return rows


def run(quick: bool = True):
    """Harness hook for ``python -m benchmarks.run``."""
    return bench(clients=8, reps=1 if quick else 3, verbose=False)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--samples-per-client", type=int, default=32)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--json", default="",
                    help="write rows as machine-readable JSON (BENCH_*.json)")
    args = ap.parse_args(argv)

    from benchmarks.common import write_json_rows
    from repro.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = bench(clients=args.clients,
                 samples_per_client=args.samples_per_client, reps=args.reps)
    print("\nname,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    if args.json:
        write_json_rows(args.json, rows, bench="compress_bench",
                        clients=args.clients, reps=args.reps,
                        kinds=list(KINDS))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
