"""Shared benchmark scaffolding: reduced-scale federated setups mirroring the
paper's experiment grid, with per-round timing.

Every ``table*.py`` module exposes ``run(quick=True) -> list[dict]`` where
each row has at least {"name", "us_per_call", "derived"} — ``benchmarks.run``
prints them as CSV.  ``us_per_call`` is wall-time per communication round.
"""

from __future__ import annotations

import json
import platform
import time

from repro.core.schedule import FedPartSchedule, matched_fnu
from repro.data import (TextDatasetSpec, VisionDatasetSpec, balanced_eval_set,
                        build_clients, dirichlet_partition, iid_partition,
                        make_text_dataset, make_vision_dataset)
from repro.fl import nlp_task, resnet_task, run_federated


def vision_setup(num_classes=16, image_size=16, samples=800, clients=4,
                 alpha=0.0, seed=0, depth="resnet8", noise=1.2):
    """Calibrated so FedAvg-FNU lands mid-range after ~10 rounds — strategies
    can then separate (noise 1.2 / 16 classes; see EXPERIMENTS.md §Claims)."""
    spec = VisionDatasetSpec(num_classes=num_classes, image_size=image_size,
                             noise=noise)
    X, y = make_vision_dataset(spec, samples, seed=seed)
    Xe, ye = make_vision_dataset(spec, samples // 2, seed=seed + 99)
    eval_set = balanced_eval_set(Xe, ye, per_class=16)
    if alpha > 0:
        parts = dirichlet_partition(y, clients, alpha, seed=seed)
    else:
        parts = iid_partition(len(y), clients, seed=seed)
    adapter = resnet_task(depth, num_classes=num_classes)
    return adapter, build_clients(X, y, parts), eval_set


def text_setup(samples=1200, clients=4, seed=0):
    spec = TextDatasetSpec(num_classes=4, vocab_size=512, seq_len=48)
    X, y = make_text_dataset(spec, samples, seed=seed)
    Xe, ye = make_text_dataset(spec, samples // 2, seed=seed + 7)
    eval_set = balanced_eval_set(Xe, ye, per_class=32)
    adapter = nlp_task(num_classes=4, smoke=True)
    return adapter, build_clients(X, y, iid_partition(len(y), clients, seed)), eval_set


def fedpart_schedule(num_groups, quick=True, cycles=1, rl=1, warmup=2,
                     order="sequential", bridge=1, seed=0):
    return FedPartSchedule(num_groups=num_groups, warmup_rounds=warmup,
                           rounds_per_layer=rl, cycles=cycles,
                           bridge_rounds=bridge, order=order, seed=seed)


def write_json_rows(path: str, rows: list[dict], **meta) -> None:
    """Write bench rows as machine-readable JSON (the ``BENCH_*.json``
    trajectory format): ``{"meta": {...}, "rows": [...]}`` with enough
    environment context to compare runs across commits."""
    import jax

    payload = {
        "meta": {
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "python": platform.python_version(),
            **meta,
        },
        "rows": rows,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=False)
        f.write("\n")
    print(f"[json] wrote {len(rows)} rows -> {path}")


def timed_run(name, adapter, clients, eval_set, rounds, run_cfg):
    t0 = time.time()
    res = run_federated(adapter, clients, eval_set, rounds, run_cfg)
    elapsed = time.time() - t0
    return res, {
        "name": name,
        "us_per_call": 1e6 * elapsed / max(len(rounds), 1),
        "derived": f"best_acc={res.best_acc:.4f}",
        "best_acc": res.best_acc,
        "comm_ratio": res.comm_total_bytes / max(res.comm_fnu_bytes, 1),
        "comp_ratio": res.comp_total_flops / max(res.comp_fnu_flops, 1),
    }


def compare_fnu_fedpart(name, adapter, clients, eval_set, schedule, run_cfg):
    rows = []
    fp, row = timed_run(f"{name}/fedpart", adapter, clients, eval_set,
                        schedule.rounds(), run_cfg)
    rows.append(row)
    fnu, row = timed_run(f"{name}/fnu", adapter, clients, eval_set,
                         matched_fnu(schedule).rounds(), run_cfg)
    rows.append(row)
    rows[0]["derived"] += (
        f" comm={rows[0]['comm_ratio']:.2f}xFNU comp={rows[0]['comp_ratio']:.2f}xFNU"
        f" vs_fnu_acc={fnu.best_acc:.4f}"
    )
    return rows
