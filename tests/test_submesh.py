"""The submesh allocator, the occupancy ledger, and the trace-sharing claim
behind host-parallel async dispatch (docs/ASYNC.md "Host-parallel dispatch").

In-process tests cover the allocator's acquire/release/exhaustion contract on
whatever devices exist (a 1-device pool still exercises every invariant) plus
the pure-python occupancy and timeline arithmetic.  The multi-device
invariants — equal-width partition with no device overlap, and one shared
trace serving two disjoint submeshes through an AbstractMesh — need real
(forced) host devices, so they run in a subprocess, same pattern as
tests/test_engine_equivalence.py.
"""

import jax
import pytest

from repro.core.costs import SubmeshOccupancy, VirtualTimeModel
from repro.core.telemetry import Timeline
from repro.launch.mesh import SubmeshPool


# -- allocator contract (any device count) ----------------------------------


def test_pool_acquire_release_exhaustion():
    pool = SubmeshPool(1)
    assert pool.num_submeshes == 1 and pool.width >= 1
    sm = pool.acquire()
    assert sm is not None and sm.index == 0
    assert pool.acquire() is None          # exhausted: caller queues
    assert pool.free_count == 0
    pool.release(sm)
    assert pool.free_count == 1
    assert pool.acquire() is sm            # same lease comes back


def test_pool_release_validation():
    pool = SubmeshPool(1)
    sm = pool.acquire()
    pool.release(sm)
    with pytest.raises(ValueError, match="twice"):
        pool.release(sm)
    import dataclasses
    foreign = dataclasses.replace(sm, index=5)
    with pytest.raises(ValueError, match="not from this pool"):
        pool.release(foreign)


def test_pool_construction_validation():
    with pytest.raises(ValueError, match="num_submeshes"):
        SubmeshPool(0)
    with pytest.raises(ValueError, match="cannot cut"):
        SubmeshPool(1, width=len(jax.devices()) + 1)


def test_pool_clamps_to_visible_devices():
    # asking for more submeshes than devices yields one per device, not an
    # error — the runtime then simply runs fewer cohorts concurrently
    pool = SubmeshPool(len(jax.devices()) + 7)
    assert pool.num_submeshes == len(jax.devices())
    assert pool.width == 1


def test_engine_pools_none_for_single_inflight():
    """max_inflight=1 keeps the engines' default placement (the PR 3 path)."""
    from repro.fl.batched import VmapEngine

    assert VmapEngine.cohort_pool.__qualname__  # exists
    # cohort_pool is an instance method but doesn't touch engine state for
    # the max_inflight<=1 early-out, so probe it through a bare instance.
    eng = object.__new__(VmapEngine)
    assert eng.cohort_pool(1) is None
    assert eng.cohort_pool(0) is None


# -- occupancy ledger (pure python) -----------------------------------------


def test_occupancy_booking_and_overlap():
    occ = VirtualTimeModel().occupancy()
    assert isinstance(occ, SubmeshOccupancy)
    occ.book(0, 0.0, 2.0)
    occ.book(1, 1.0, 3.0)       # overlaps [1, 2] with submesh 0
    occ.book(0, 4.0, 5.0)
    assert occ.busy_seconds(0) == pytest.approx(3.0)
    assert occ.busy_seconds(1) == pytest.approx(2.0)
    assert occ.busy_seconds() == pytest.approx(4.0)   # union, not sum
    assert occ.overlap_seconds() == pytest.approx(1.0)
    assert occ.max_concurrency() == 2
    s = occ.summary()
    assert s["cohorts"] == 3 and s["submeshes"] == 2
    assert s["busy_seconds"][0] == pytest.approx(3.0)
    assert s["max_concurrency"] == 2


def test_occupancy_rejects_negative_span():
    occ = SubmeshOccupancy()
    with pytest.raises(ValueError, match="before it starts"):
        occ.book(0, 2.0, 1.0)


def test_occupancy_adjacent_spans_not_concurrent():
    occ = SubmeshOccupancy()
    occ.book(0, 0.0, 1.0)
    occ.book(1, 1.0, 2.0)       # back-to-back: no overlap
    assert occ.overlap_seconds() == 0.0
    assert occ.max_concurrency() == 1


def test_timeline_cohort_spans_and_overlap():
    tl = Timeline()
    tl.record(0.0, "dispatch", version=0, group=0, clients=[0], t_end=2.0,
              submesh=0)
    tl.record(0.5, "dispatch", version=0, group=0, clients=[1], t_end=1.5,
              submesh=1)
    tl.record(3.0, "dispatch", version=1, group=1, clients=[0], t_end=4.0)
    tl.record(0.0, "merge", version=0)      # no t_end: not a cohort span
    assert tl.cohort_spans() == [(0, 0.0, 2.0), (1, 0.5, 1.5), (-1, 3.0, 4.0)]
    assert tl.overlap_seconds() == pytest.approx(1.0)


# -- multi-device invariants (forced host devices => subprocess) -------------


_POOL_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys, json
sys.path.insert(0, "src")
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.compat import (SHARD_MAP_NO_CHECK_KW, abstract_client_mesh,
                               shard_map)
from repro.launch.mesh import SubmeshPool

out = {}
pool = SubmeshPool(2)
out["num"] = pool.num_submeshes
out["widths"] = [sm.width for sm in pool.submeshes]
devs = [tuple(str(d) for d in sm.devices) for sm in pool.submeshes]
out["disjoint"] = len(set(devs[0]) & set(devs[1])) == 0
out["mesh_axes"] = [sm.mesh.axis_names for sm in pool.submeshes]

# leftover devices stay unused when widths don't divide evenly
pool3 = SubmeshPool(3)
out["num3"] = pool3.num_submeshes
out["widths3"] = [sm.width for sm in pool3.submeshes]
covered = [d for sm in pool3.submeshes for d in sm.devices]
out["disjoint3"] = len(set(covered)) == len(covered)

# one AbstractMesh trace serves both equal-width submeshes
am = abstract_client_mesh(2)
out["abstract_mesh"] = isinstance(am, jax.sharding.AbstractMesh)
traces = [0]
def body(x):
    traces[0] += 1
    return jax.lax.psum(x, "clients")
fn = jax.jit(shard_map(body, mesh=am, in_specs=P("clients"),
                       out_specs=P(), **SHARD_MAP_NO_CHECK_KW))
import jax.numpy as jnp
for sm in pool.submeshes:
    x = jax.device_put(jnp.arange(4.0),
                       NamedSharding(sm.mesh, P("clients")))
    fn(x).block_until_ready()
out["traces"] = traces[0]
print(json.dumps(out))
"""


def test_pool_partition_and_trace_sharing_multidevice():
    import json
    import os
    import subprocess
    import sys

    res = subprocess.run(
        [sys.executable, "-c", _POOL_SCRIPT], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(__file__)), timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["num"] == 2 and out["widths"] == [2, 2]
    assert out["disjoint"]
    assert out["mesh_axes"] == [["clients"], ["clients"]]
    assert out["num3"] == 3 and out["widths3"] == [1, 1, 1]
    assert out["disjoint3"]
    assert out["abstract_mesh"], "this jax should build an AbstractMesh"
    assert out["traces"] == 1, "equal-width submeshes must share one trace"
