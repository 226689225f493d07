"""Benchmark entry point — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # quick (CI) mode
    PYTHONPATH=src python -m benchmarks.run --full     # paper-scale grid
    PYTHONPATH=src python -m benchmarks.run --only table1,fig1

Prints ``name,us_per_call,derived`` CSV per the harness convention.
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback

BENCHES = [
    "table1_algorithms",
    "table2_resnet18",
    "table3_nlp",
    "table4_heterogeneity",
    "table5_rounds_per_layer",
    "table6_warmup",
    "table7_order",
    "table9_privacy",
    "table13_kvalue",
    "fig1_stepsizes",
    "engine_bench",
    "async_bench",
    "hetero_bench",
    "population_bench",
    "compress_bench",
    "kernels_bench",
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale grid")
    ap.add_argument("--only", default="", help="comma list of bench prefixes")
    args = ap.parse_args(argv)

    selected = BENCHES
    if args.only:
        prefixes = [p.strip() for p in args.only.split(",")]
        selected = [b for b in BENCHES if any(b.startswith(p) for p in prefixes)]

    print("name,us_per_call,derived")
    failures = 0
    for mod_name in selected:
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            rows = mod.run(quick=not args.full)
            for row in rows:
                print(f"{row['name']},{row['us_per_call']:.1f},{row['derived']}")
            sys.stdout.flush()
        except Exception:
            failures += 1
            print(f"{mod_name},0,FAILED", flush=True)
            traceback.print_exc(file=sys.stderr)
        print(f"# {mod_name} done in {time.time()-t0:.0f}s", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
