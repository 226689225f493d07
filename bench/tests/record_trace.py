"""Record a small trace that the benchmark's tests read, on a TPU.

    python3 bench/tests/record_trace.py bench/tests/data/<name>.xplane.pb.gz

The run is described by ``<name>.ctx.json`` beside the output: a test-size
model (``config``, with its ``reference`` and data ``kind``), its
``traffic``, the ``chips`` and the ``seed``.  It runs through the
benchmark's own ``harness.drive`` with tracing on (set-up rounds, then at
least one whole cycle of the schedule under the profiler), so the trace
holds the benchmark's round annotations, the program's host spans and its
device scopes.  Writes the trace gzipped, adds to the ``.ctx.json`` what
the per-layer metric readers need of the run (``device_kind``,
``traced_groups``, ``setup_compile_s``), and prints ``bench/scopes.py``'s
readings of the trace.

``<config>.xplane.pb.gz`` with its ``<config>.ctx.json`` is the pair on
which ``test_bench_traces.py`` checks every per-layer metric of the
configuration's cells.
"""

from __future__ import annotations

import gzip
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))
SUFFIX = ".xplane.pb.gz"


def main(out: str) -> int:
    import jax

    from bench import harness, scopes

    if jax.devices()[0].platform != "tpu":
        print(f"no TPU: JAX found {jax.devices()[0].platform}", file=sys.stderr)
        return 2
    if not out.endswith(SUFFIX):
        print(f"the output must end in {SUFFIX}", file=sys.stderr)
        return 2
    meta_path = Path(out[: -len(SUFFIX)] + ".ctx.json")
    meta = harness.load_json(meta_path)
    cfg = meta["config"]
    cell = harness.Cell("recorded", meta["chips"], cfg, meta["traffic"], {}, [],
                        harness.load_module(ROOT, "reference", cfg["reference"]),
                        harness.load_module(ROOT, "data", cfg["data"]["kind"]), {})
    clock = harness.CompileClock()
    record = harness.drive(cell, meta["seed"], 0.0, trace=True)
    rounds = record["rounds"]
    path = scopes.latest_trace(harness.TRACE_DIR)
    Path(out).write_bytes(gzip.compress(path.read_bytes(), 9))
    meta.update(device_kind=jax.devices()[0].device_kind,
                traced_groups=[s.group for s in rounds.window_rounds],
                setup_compile_s=clock.seconds(0.0, rounds.window_start))
    meta_path.write_text(json.dumps(meta, indent=1) + "\n")
    scopes.main([str(path)])
    print(json.dumps({"written": [out, str(meta_path)],
                      "bytes": Path(out).stat().st_size}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
