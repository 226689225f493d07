"""Readings that the limits of a cell's comparison are set from, on the chip
at the cell's own size.  Not part of a benchmark run.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --faults 3 --out <file>

For each seed it drives the program's first rounds through the cell's own
path (``run_federated`` with the cell's engine and programs, three rounds and
one more) and compares them with the float32 reference: the sound readings.
For the first ``--faults`` seeds it also puts the reference in the program's
place, computed in bfloat16 (the control), training on half of every batch
(a fault) and averaging half of the cohort (a fault); and it reads the
program with its eval answer altered by an eighth of the eval set (a
fault).  A run that leaves the state
unchanged reads 1 on the norm and direction gaps (``UNCHANGED_READS_ONE``) by
their definition.

Each reading is one JSON line in ``--out``; the last line on standard output
gives, per number, the readings its limit is set from and the limit
(``derive_limits``), which ``--limits-out`` writes as the cell's limits
file.

A look at where the sound gaps come from runs the program's matmuls and
convolutions at another precision: ``JAX_DEFAULT_MATMUL_PRECISION=highest``
in the environment (the reference states its precision itself).
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))
# JAX's persistent compile cache lives inside the checkout, where the
# program's cache helper puts it when this variable is unset; a directory
# named from outside could be shared with another checkout's runs.
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
# libtpu would otherwise write its logs under a fixed path in /tmp.
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ALTERED_ANSWER = 0.125      # added to each compared round's eval accuracy
# Numbers that read exactly 1 when the program leaves its state unchanged.
UNCHANGED_READS_ONE = ("update_gap", "change_gap", "update_gap_tree",
                       "change_gap_tree", "update_dir_gap", "change_dir_gap")


def readings(cell, seed: int, faults: bool) -> list[dict]:
    import jax.numpy as jnp

    from bench import correct, harness

    record = harness.drive(cell, seed, 0.0, False, setup=harness.COMPARED_ROUNDS,
                           whole_cycles=False)
    run, prog = record["run"], record["prog"]
    groups = [s.group for s in record["rounds"].issued[:harness.COMPARED_ROUNDS]]
    start = cell.reference.flatten(run["start"])
    eval_n = len(run["eval_set"][1])

    def first_last(out):
        return dict(out, params=[out["params"][0], out["params"][-1]])

    t = time.perf_counter()
    ref = first_last(harness.reference_run(cell, run, seed, groups))
    ref_s = time.perf_counter() - t
    rows = [dict(kind="program", seed=seed, reference_s=ref_s,
                 **correct.numbers(prog, ref, start, eval_n))]
    if faults:
        altered = dict(prog, acc=[a + ALTERED_ANSWER for a in prog["acc"]])
        rows.append(dict(kind="altered_answer", seed=seed,
                         **correct.numbers(altered, ref, start, eval_n)))
        for kind, kw in (("control_bf16", {"dtype": jnp.bfloat16}),
                         ("half_batch", {"half_batch": True}),
                         ("half_cohort", {"half_cohort": True})):
            other = first_last(harness.reference_run(cell, run, seed, groups, **kw))
            rows.append(dict(kind=kind, seed=seed,
                             **correct.numbers(other, ref, start, eval_n)))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--out", required=True)
    ap.add_argument("--limits-out", default=None,
                    help="write the derived limits here (bench/limits/<cell>.json)")
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    from repro.core.compile_cache import enable_compile_cache

    enable_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    cell = harness.load_cell(args.workload)
    rows = []
    with open(args.out, "w") as f:
        for i in range(args.seeds):
            for row in readings(cell, args.first_seed + 7919 * i, i < args.faults):
                print(json.dumps(row), file=f, flush=True)
                print(row["kind"], row["seed"], row["values"], flush=True)
                rows.append(row)
    limits = derive_limits(rows)
    if args.limits_out:
        with open(args.limits_out, "w") as f:
            json.dump(limits, f, indent=1)
    print(json.dumps(limits))
    return 0


def derive_limits(rows: list[dict]) -> dict:
    """Per compared number: the lower reading (the largest over the sound
    seeds), the readings of the control and of each fault (the smallest
    over their seeds), the upper reading, and the limit.

    The upper reading is the smallest of the control's reading where it is
    three times the lower or more, of each fault's where it is ten times
    the lower or more, and, for the worst-leaf and whole-tree norm gaps, of
    1 (what a state left unchanged reads) where that is three times the
    lower or more.  A number with
    no upper reading gets no limit.  The limit sits between the two, at
    lower^(1/3) x upper^(2/3): more room above the lower reading than
    below the upper."""
    out = {}
    for name in rows[0]["values"]:
        by: dict[str, list[float]] = {}
        for r in rows:
            by.setdefault(r["kind"], []).append(r["values"][name])
        lower = max(by.pop("program"))
        readings = {k: min(v) for k, v in by.items()}
        if name in UNCHANGED_READS_ONE:
            readings["state_unchanged"] = 1.0
        uppers = [v for k, v in readings.items()
                  if v >= (3 if k in ("control_bf16", "state_unchanged") else 10) * lower]
        entry = {"lower": lower, "readings": readings}
        if uppers and lower > 0:
            entry["upper"] = min(uppers)
            entry["limit"] = lower ** (1 / 3) * entry["upper"] ** (2 / 3)
            entry["failed_by"] = sorted(k for k, v in readings.items()
                                        if v > entry["limit"])
        out[name] = entry
    return out


if __name__ == "__main__":
    sys.exit(main())
