"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  ``--trace 0`` measures the cell's end-to-end
metrics over a window of ``--seconds``; ``--trace 1`` traces a fixed number
of rounds under the profiler and reports the cell's per-layer metrics.
Either way the program's first rounds are checked against the plain
reference after the window.  Lines before the last say what the run did;
the last lines on standard error give each compared number beside its
limit; the last line on standard output is the result as one JSON object.

Exits non-zero, with no result, when JAX finds no TPU or fewer chips than
the cell asks for, or when anything compiles inside the window.
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
# The checkout root, not this directory, heads the path: the benchmark's
# modules are imported as ``bench.*`` and the program from ``src``.
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))
# JAX's persistent compile cache lives inside the checkout, where the
# program's cache helper puts it when this variable is unset; a directory
# named from outside could be shared with another checkout's runs.
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
# libtpu would otherwise write its logs under a fixed path in /tmp.
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from bench import harness
    from repro.core.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}", file=sys.stderr)
        return 2
    peaks = harness.load_json(harness.BENCH / "peaks.json")
    kind = devices[0].device_kind
    if kind not in peaks:
        print(f"no peaks for device kind {kind!r} in bench/peaks.json",
              file=sys.stderr)
        return 2
    cell = harness.load_cell(args.workload)
    if len(devices) < cell.chips:
        print(f"{args.workload} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace), T0,
                         peaks=peaks[kind])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
