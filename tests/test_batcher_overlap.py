"""Tier-1-safe throughput smoke test for the pipelined micro-batch executor.

No TPU needed: a fake "device" fn charges a fixed dispatch cost in the
collector lane (host stack + transfer + dispatch) and a fixed fetch cost in
the settle lane (the ``__array__`` hook is exactly where the fetch worker's
``jax.device_get`` blocks on a real device->host transfer). With
``inflight=2`` the two lanes must overlap: wall time for N batches has to
land measurably below the synchronous sum ``N * (dispatch + fetch)``. If a
refactor quietly re-serializes the lanes (e.g. fetching inside the
collector again), this fails fast on any CPU.
"""

import time

import numpy as np

from tests.batcher_fakes import SlowFetch

from lumen_tpu.runtime.batcher import MicroBatcher

DISPATCH_S = 0.03  # collector-lane cost per batch
FETCH_S = 0.03     # settle-lane cost per batch
N_BATCHES = 10


def sleepy_device_fn(tree, n):
    time.sleep(DISPATCH_S)
    return SlowFetch(tree, FETCH_S)


def test_pipelined_batcher_overlaps_dispatch_and_fetch():
    b = MicroBatcher(
        sleepy_device_fn, max_batch=1, max_latency_ms=0.5, inflight=2,
        name="overlap-smoke",
    ).start()
    try:
        futs = [b.submit(np.array([float(i)])) for i in range(N_BATCHES)]
        t0 = time.perf_counter()
        vals = [float(np.asarray(f.result(timeout=30))[0]) for f in futs]
        wall = time.perf_counter() - t0
    finally:
        b.close()
    assert vals == [float(i) for i in range(N_BATCHES)]
    synchronous = N_BATCHES * (DISPATCH_S + FETCH_S)
    # Pipelined ≈ dispatch + N * max(dispatch, fetch) ≈ 55% of synchronous
    # here; 0.75 leaves slack for scheduler jitter while still failing any
    # actually-serial execution (which cannot beat ~1.0).
    assert wall < 0.75 * synchronous, (
        f"no dispatch/fetch overlap: wall {wall:.3f}s vs synchronous "
        f"{synchronous:.3f}s for {N_BATCHES} batches"
    )


def test_inflight_one_serializes_dispatch():
    """inflight=1 is the no-pipelining escape hatch for HBM-tight
    deployments: at most ONE un-fetched device result exists at any
    instant, so dispatch of batch k+1 waits for batch k's fetch and wall
    time degrades to ~the synchronous sum (collection/stacking still
    overlap, but they're ~free here)."""
    b = MicroBatcher(
        sleepy_device_fn, max_batch=1, max_latency_ms=0.5, inflight=1,
        name="overlap-smoke-1",
    ).start()
    try:
        futs = [b.submit(np.array([float(i)])) for i in range(N_BATCHES)]
        t0 = time.perf_counter()
        for f in futs:
            f.result(timeout=30)
        wall = time.perf_counter() - t0
    finally:
        b.close()
    synchronous = N_BATCHES * (DISPATCH_S + FETCH_S)
    # Lower bound only (sleeps can stretch, never shrink): serialized
    # execution cannot meaningfully beat the synchronous sum.
    assert wall > 0.85 * synchronous, (
        f"inflight=1 pipelined anyway: wall {wall:.3f}s vs synchronous "
        f"{synchronous:.3f}s"
    )


def test_cumulative_collect_wait_and_device_envelope():
    """The always-on sums behind ``batcher:{name}``: every item picked into
    a batch books its wait since submit, every settled batch its
    dispatch->settle envelope; they only grow, and they hold what the fake
    device charges."""
    b = MicroBatcher(
        sleepy_device_fn, max_batch=2, max_latency_ms=5, inflight=2,
        name="overlap-sums",
    ).start()
    seen = []
    try:
        for burst in (4, 2):
            futs = [b.submit(np.array([float(i)])) for i in range(burst)]
            for f in futs:
                f.result(timeout=30)
            time.sleep(0.05)  # the fetch worker books the batch after it settles its futures
            seen.append(dict(b.stats))
    finally:
        b.close()
    a, c = seen
    assert (a["collect_items"], c["collect_items"]) == (4, 6) == (a["items"], c["items"])
    assert a["device_batches"] == a["batches"] and c["device_batches"] == c["batches"]
    for key in ("collect_wait_ms_sum", "device_ms_sum"):
        assert 0 < a[key] < c[key]
    # an envelope holds one dispatch and one fetch of the fake device
    assert a["device_ms_sum"] >= a["device_batches"] * (DISPATCH_S + FETCH_S) * 1e3 * 0.9
    # the second batch's items waited at least the first batch's dispatch
    assert a["collect_wait_ms_sum"] >= 2 * DISPATCH_S * 1e3 * 0.9
