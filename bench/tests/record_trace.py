"""Record the small trace that ``test_bench_scopes.py`` reads, on a TPU.

    python3 bench/tests/record_trace.py bench/tests/data/tiny_spans_tpu.xplane.pb.gz

Two FNU rounds of ResNet-4 (3 clients of 20 images, batch 10, fused masked
Adam, vmap engine) after three set-up rounds, through the benchmark's own
``harness.drive`` with tracing on, so the trace holds the benchmark's round
annotations, the program's host spans and its device scopes.  Writes the
trace gzipped and prints ``bench/scopes.py``'s readings of it.
"""

from __future__ import annotations

import gzip
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main(out: str) -> int:
    import jax

    from bench import harness, scopes
    from bench.tests.test_bench_rehearsal import TINY

    if jax.devices()[0].platform != "tpu":
        print(f"no TPU: JAX found {jax.devices()[0].platform}", file=sys.stderr)
        return 2
    traffic = harness.load_json(ROOT / "bench" / "traffic" / "fedpart-b50-e1-fused.json")
    traffic.update(schedule="fnu", cohort=3, batch=10, eval_batch=256, trace_rounds=2)
    cell = harness.Cell("tiny", 1, TINY, traffic, {}, [],
                        harness.load_module(ROOT, "reference", "resnet"),
                        harness.load_module(ROOT, "data", "vision"), {})
    harness.drive(cell, 2**31 + 7, 0.0, trace=True)
    path = scopes.latest_trace(harness.TRACE_DIR)
    Path(out).write_bytes(gzip.compress(path.read_bytes(), 9))
    scopes.main([str(path)])
    print(json.dumps({"written": out, "bytes": Path(out).stat().st_size}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
