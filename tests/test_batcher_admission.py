"""MicroBatcher admission control + deadline semantics: bounded queue sheds
with a counter, expired entries drop before the device call, the
``batch_execute`` fault point fans out to waiting callers, and the
pipelined executor (bounded in-flight deque + fetch/settle worker)
preserves all of the above with multiple batches in flight."""

import time

import numpy as np
import pytest

from tests.batcher_fakes import SlowFetch

from lumen_tpu.runtime.batcher import MicroBatcher, batch_inflight, batch_queue_depth
from lumen_tpu.testing import FaultInjected, faults
from lumen_tpu.utils import deadline as request_deadline
from lumen_tpu.utils.deadline import DeadlineExpired, QueueFull
from lumen_tpu.utils.metrics import metrics


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def identity(tree, n):
    return tree


class KillFetch:
    """BaseException out of __array__ escapes the fetch loop's
    `except Exception` and kills the fetch thread."""

    def __init__(self, arr):
        self.arr = np.asarray(arr)

    def __array__(self, dtype=None, copy=None):
        raise SystemExit("fetch thread killed")


class TestAdmissionControl:
    def test_depth_limit_sheds_next_submit(self):
        b = MicroBatcher(identity, max_batch=4, max_queue=2)  # NOT started: queue holds
        before = metrics.counter_value("sheds")
        b.submit(np.zeros(1))
        b.submit(np.zeros(1))
        with pytest.raises(QueueFull) as ei:
            b.submit(np.zeros(1))
        assert "request shed" in str(ei.value)
        assert b.stats["shed"] == 1
        assert metrics.counter_value("sheds") == before + 1
        assert metrics.counter_value("sheds:batcher") >= 1
        b.close()

    def test_queue_drains_admit_again(self):
        b = MicroBatcher(identity, max_batch=4, max_latency_ms=1, max_queue=2)
        f1, f2 = b.submit(np.zeros(1)), b.submit(np.zeros(1))  # at the limit
        b.start()
        f1.result(timeout=5), f2.result(timeout=5)
        # Drained: admission opens again.
        assert np.asarray(b(np.zeros(1), timeout=5)).shape == (1,)
        b.close()

    def test_unbounded_by_default(self):
        b = MicroBatcher(identity, max_batch=2)
        assert b.max_queue == 0
        for _ in range(64):
            b.submit(np.zeros(1))
        b.close()

    def test_env_default_depth(self, monkeypatch):
        monkeypatch.setenv("LUMEN_BATCH_QUEUE_DEPTH", "7")
        assert batch_queue_depth() == 7
        assert MicroBatcher(identity).max_queue == 7
        monkeypatch.setenv("LUMEN_BATCH_QUEUE_DEPTH", "nope")
        assert batch_queue_depth() == 0


class TestDeadlineDrops:
    def test_expired_at_submit_rejected(self):
        b = MicroBatcher(identity, max_batch=2)
        before = metrics.counter_value("deadline_drops")
        with pytest.raises(DeadlineExpired):
            b.submit(np.zeros(1), deadline=time.monotonic() - 0.1)
        assert metrics.counter_value("deadline_drops") == before + 1
        b.close()

    def test_expired_while_queued_dropped_before_device_call(self):
        device_calls = []

        def fn(tree, n):
            device_calls.append(n)
            return tree

        b = MicroBatcher(fn, max_batch=4, max_latency_ms=1, name="dl-t")
        # Enqueue while the collector is not running, so expiry is
        # deterministic: one doomed entry, one live entry.
        doomed = b.submit(np.zeros(1), deadline=time.monotonic() + 0.01)
        live = b.submit(np.zeros(1))
        time.sleep(0.05)
        before = metrics.counter_value("deadline_drops")
        b.start()
        assert np.asarray(live.result(timeout=5)).shape == (1,)
        with pytest.raises(DeadlineExpired):
            doomed.result(timeout=5)
        # The batch ran once, with only the live row.
        assert device_calls == [1]
        assert b.stats["expired"] == 1
        assert metrics.counter_value("deadline_drops") == before + 1
        assert metrics.counter_value("deadline_drops:dl-t") >= 1
        b.close()

    def test_all_expired_skips_device_call(self):
        device_calls = []

        def fn(tree, n):
            device_calls.append(n)
            return tree

        b = MicroBatcher(fn, max_batch=2, max_latency_ms=1)
        f1 = b.submit(np.zeros(1), deadline=time.monotonic() + 0.01)
        f2 = b.submit(np.zeros(1), deadline=time.monotonic() + 0.01)
        time.sleep(0.05)
        b.start()
        for f in (f1, f2):
            with pytest.raises(DeadlineExpired):
                f.result(timeout=5)
        b.close()
        assert device_calls == []

    def test_ambient_context_deadline_inherited(self):
        b = MicroBatcher(identity, max_batch=2)
        token = request_deadline.set_deadline(time.monotonic() - 0.1)
        try:
            with pytest.raises(DeadlineExpired):
                b.submit(np.zeros(1))  # no explicit deadline: reads contextvar
        finally:
            request_deadline.reset(token)
        b.close()

    def test_call_timeout_bounded_by_ambient_deadline(self):
        b = MicroBatcher(identity, max_batch=1, max_latency_ms=1).start()
        token = request_deadline.set_deadline(time.monotonic() + 30.0)
        try:
            out = b(np.zeros(2))  # plenty of budget: normal result
        finally:
            request_deadline.reset(token)
        assert np.asarray(out).shape == (2,)
        b.close()


class TestPipelinedExecutor:
    """The dispatch/fetch split: ≥2 batches in flight, submission-order
    settle, deadline + fault + close semantics preserved under overlap."""

    def test_settles_in_submission_order_across_inflight_batches(self):
        b = MicroBatcher(lambda t, n: SlowFetch(t, 0.02), max_batch=1,
                         max_latency_ms=0.5, inflight=3).start()
        futs, settled = [], []
        for i in range(9):
            fut = b.submit(np.array([i], np.int64))
            fut.add_done_callback(lambda _, i=i: settled.append(i))
            futs.append(fut)
        high_water = 0
        deadline = time.monotonic() + 10
        while any(not f.done() for f in futs) and time.monotonic() < deadline:
            high_water = max(high_water, len(b._inflight))
            time.sleep(0.001)
        vals = [int(np.asarray(f.result(timeout=10))[0]) for f in futs]
        assert vals == list(range(9))  # each caller got ITS row back
        assert settled == list(range(9))  # settle order == submission order
        # The slow fetch really did pile up ≥3 dispatched batches at once.
        assert high_water >= 3
        assert b.stats["batches"] == 9 and b.stats["items"] == 9
        b.close()

    def test_inflight_bound_respected(self):
        b = MicroBatcher(lambda t, n: SlowFetch(t, 0.03), max_batch=1,
                         max_latency_ms=0.5, inflight=2).start()
        futs = [b.submit(np.zeros(1)) for _ in range(8)]
        high_water = 0
        deadline = time.monotonic() + 5
        while any(not f.done() for f in futs) and time.monotonic() < deadline:
            high_water = max(high_water, len(b._inflight))
            time.sleep(0.002)
        for f in futs:
            f.result(timeout=10)
        assert high_water <= 2  # backpressure held the dispatch lane
        b.close()

    def test_deadline_expiry_while_batch_in_flight(self):
        calls = []

        def fn(tree, n):
            calls.append(n)
            time.sleep(0.15)  # batch A occupies the dispatch lane
            return tree

        b = MicroBatcher(fn, max_batch=1, max_latency_ms=1, inflight=2,
                         name="dl-inflight").start()
        a = b.submit(np.zeros(1))
        time.sleep(0.03)  # A is now dispatching/computing
        doomed = b.submit(np.zeros(1), deadline=time.monotonic() + 0.02)
        assert np.asarray(a.result(timeout=5)).shape == (1,)
        with pytest.raises(DeadlineExpired):
            doomed.result(timeout=5)
        assert calls == [1]  # the expired entry never reached the device
        assert b.stats["expired"] == 1
        b.close()

    def test_deadline_expiry_during_backpressure_wait(self):
        calls = []

        def fn(tree, n):
            calls.append(n)
            return SlowFetch(tree, 0.25)

        b = MicroBatcher(fn, max_batch=1, max_latency_ms=1, inflight=1,
                         name="bp-dl").start()
        a = b.submit(np.zeros(1))
        time.sleep(0.03)  # A dispatched; its slow fetch holds the only slot
        doomed = b.submit(np.zeros(1), deadline=time.monotonic() + 0.05)
        assert np.asarray(a.result(timeout=5)).shape == (1,)
        with pytest.raises(DeadlineExpired):
            doomed.result(timeout=5)
        # The gate runs AFTER the in-flight slot wait: an entry that
        # expires while the collector blocks on backpressure never burns
        # the device batch it no longer wants.
        assert calls == [1]
        b.close()

    def test_fault_fans_to_its_batch_only_with_inflight(self):
        b = MicroBatcher(lambda t, n: SlowFetch(t, 0.1), max_batch=1,
                         max_latency_ms=1, inflight=3, name="multi").start()
        f1 = b.submit(np.array([1.0]))
        time.sleep(0.04)  # f1 dispatched; its fetch is still in flight
        faults.configure("batch_execute", times=1, match="multi")
        f2 = b.submit(np.array([2.0]))  # faults at dispatch
        f3 = b.submit(np.array([3.0]))  # fault exhausted: clean batch
        assert float(np.asarray(f1.result(timeout=5))[0]) == 1.0
        with pytest.raises(FaultInjected):
            f2.result(timeout=5)
        assert float(np.asarray(f3.result(timeout=5))[0]) == 3.0
        b.close()

    def test_close_settles_every_inflight_batch(self):
        b = MicroBatcher(lambda t, n: SlowFetch(t, 0.04), max_batch=1,
                         max_latency_ms=1, inflight=4).start()
        futs = [b.submit(np.array([float(i)])) for i in range(6)]
        # Wait until ≥2 batches are genuinely dispatched (fetched or in
        # the in-flight deque) — a fixed sleep is a scheduling-dependent
        # flake on a loaded machine.
        deadline = time.monotonic() + 5
        while (b.stats["batches"] + len(b._inflight)) < 2 and time.monotonic() < deadline:
            time.sleep(0.002)
        b.close()
        # close() returns only after EVERY future settled: dispatched
        # batches drain through the fetch worker with their real rows;
        # still-queued items get the explicit closed error — none hang.
        results, closed = 0, 0
        for i, f in enumerate(futs):
            assert f.done()
            try:
                assert float(np.asarray(f.result(timeout=0))[0]) == float(i)
                results += 1
            except RuntimeError as e:
                assert "closed" in str(e)
                closed += 1
        # The batches that were in flight at close() settled with results
        # (fetch worker drained them) rather than being dropped.
        assert results >= 2
        assert results + closed == 6

    def test_dead_fetch_worker_fails_loud(self):
        b = MicroBatcher(lambda t, n: KillFetch(t), max_batch=1,
                         max_latency_ms=1, inflight=2, name="dead-fetch").start()
        f1 = b.submit(np.zeros(1))  # its fetch kills the worker; entry stranded
        b._fetch_thread.join(timeout=5)  # a fixed 50-ms sleep lost this race under load
        f2 = b.submit(np.zeros(1))  # next dispatch detects the dead worker
        # BOTH settle loudly instead of riding out the 300s batch-wait.
        with pytest.raises(RuntimeError, match="fetch worker died"):
            f2.result(timeout=5)
        with pytest.raises(RuntimeError, match="fetch worker died"):
            f1.result(timeout=5)
        b.close()

    def test_dead_fetch_worker_close_settles_stranded(self):
        b = MicroBatcher(lambda t, n: KillFetch(t), max_batch=1,
                         max_latency_ms=1, inflight=2,
                         name="dead-fetch-close").start()
        f1 = b.submit(np.zeros(1))  # fetch dies on this batch; NO more traffic
        deadline = time.monotonic() + 5
        while not b._inflight and time.monotonic() < deadline:
            time.sleep(0.002)  # wait until the batch is dispatched/appended
        b.close()  # quiet period: only close() can settle the stranded batch
        with pytest.raises(RuntimeError, match="fetch worker died"):
            f1.result(timeout=0)

    def test_env_default_inflight(self, monkeypatch):
        monkeypatch.setenv("LUMEN_BATCH_INFLIGHT", "5")
        assert batch_inflight() == 5
        assert MicroBatcher(identity).inflight == 5
        monkeypatch.setenv("LUMEN_BATCH_INFLIGHT", "0")
        assert batch_inflight() == 1  # floor: at least one batch in flight
        monkeypatch.setenv("LUMEN_BATCH_INFLIGHT", "nope")
        assert batch_inflight() == 2
        monkeypatch.delenv("LUMEN_BATCH_INFLIGHT")
        assert MicroBatcher(identity, inflight=3).inflight == 3


class TestBatchExecuteFault:
    def test_fault_fans_out_to_callers(self):
        faults.configure("batch_execute", times=1, match="flaky")
        b = MicroBatcher(identity, max_batch=2, max_latency_ms=1, name="flaky").start()
        fut = b.submit(np.zeros(1))
        with pytest.raises(FaultInjected):
            fut.result(timeout=5)
        # Fault exhausted: next batch succeeds (the batcher survives).
        assert np.asarray(b(np.zeros(1), timeout=5)).shape == (1,)
        b.close()

    def test_unmatched_batcher_unaffected(self):
        faults.configure("batch_execute", match="other-batcher")
        b = MicroBatcher(identity, max_batch=2, max_latency_ms=1, name="steady").start()
        assert np.asarray(b(np.zeros(1), timeout=5)).shape == (1,)
        b.close()
