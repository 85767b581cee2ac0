"""Shared host-decode pool: sizing, ordering, nested-call safety, telemetry,
and the IngestPipeline handoff to it."""

import threading
import time

import pytest

from lumen_tpu.runtime import decode_pool as dp
from lumen_tpu.runtime.decode_pool import (
    DecodePool,
    decode_workers,
    get_decode_pool,
    shutdown_decode_pool,
)
from lumen_tpu.utils.metrics import metrics


class TestSizing:
    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("LUMEN_DECODE_WORKERS", "3")
        assert decode_workers() == 3
        assert DecodePool(name="t-env").workers == 3

    def test_malformed_and_unset_fall_back(self, monkeypatch):
        monkeypatch.setenv("LUMEN_DECODE_WORKERS", "lots")
        assert decode_workers() >= 1
        monkeypatch.delenv("LUMEN_DECODE_WORKERS")
        assert decode_workers() >= 1

    def test_explicit_workers_win(self, monkeypatch):
        monkeypatch.setenv("LUMEN_DECODE_WORKERS", "7")
        assert DecodePool(workers=2, name="t-exp").workers == 2


class TestExecution:
    def test_map_preserves_order(self):
        pool = DecodePool(workers=4, name="t-map")
        try:
            out = pool.map(lambda x: x * x, range(50))
            assert out == [x * x for x in range(50)]
        finally:
            pool.close()

    def test_run_propagates_exceptions(self):
        pool = DecodePool(workers=2, name="t-exc")
        try:
            with pytest.raises(ValueError, match="bad payload"):
                pool.run(lambda: (_ for _ in ()).throw(ValueError("bad payload")))
        finally:
            pool.close()

    def test_run_passes_kwargs(self):
        pool = DecodePool(workers=2, name="t-kw")
        try:
            assert pool.run(lambda a, b=0: a + b, 1, b=2) == 3
        finally:
            pool.close()

    def test_nested_run_does_not_deadlock(self):
        # A pooled task that fans out again must run inline, or a
        # 1-worker pool would wait on itself forever.
        pool = DecodePool(workers=1, name="t-nest")
        try:
            def outer():
                return pool.run(lambda: threading.current_thread().name)

            name = pool.run(outer)
            assert "t-nest" in name  # inner ran ON the single pool thread
        finally:
            pool.close()

    def test_map_from_pool_thread_runs_inline(self):
        pool = DecodePool(workers=1, name="t-nestmap")
        try:
            assert pool.run(lambda: pool.map(lambda x: x + 1, [1, 2, 3])) == [2, 3, 4]
        finally:
            pool.close()

    def test_expired_deadline_skips_decode(self):
        import time as _time

        from lumen_tpu.utils import deadline as request_deadline
        from lumen_tpu.utils.deadline import DeadlineExpired

        pool = DecodePool(workers=1, name="t-dl")
        calls = []
        try:
            # Occupy the single worker so the next task genuinely queues
            # past its caller's deadline.
            blocker = pool.submit(_time.sleep, 0.15)
            token = request_deadline.set_deadline(_time.monotonic() + 0.05)
            try:
                fut = pool.submit(lambda: calls.append(1))
            finally:
                request_deadline.reset(token)
            blocker.result(timeout=5)
            with pytest.raises(DeadlineExpired):
                fut.result(timeout=5)
            assert calls == []  # the dead request never burned a worker
            before = metrics.counter_value("deadline_drops:t-dl")
            assert before >= 1
        finally:
            pool.close()


class TestTelemetry:
    def test_gauges_registered_and_counting(self):
        pool = DecodePool(workers=2, name="t-gauge")
        try:
            pool.map(lambda x: time.sleep(0.001) or x, range(8))
            snap = metrics.snapshot()
            g = snap["gauges"]["t-gauge"]
            assert g["workers"] == 2
            assert g["tasks"] == 8
            assert g["queue_depth"] == 0  # drained
            assert g["wait_ms_p50"] >= 0.0
        finally:
            pool.close()
        assert "t-gauge" not in metrics.snapshot().get("gauges", {})

    def test_cumulative_wait_and_run_sums(self):
        """A window's mean wait is a ratio of deltas: the sums only grow,
        every task that left the queue has one wait, and two tasks behind
        one worker waited at least as long as the first ran."""
        pool = DecodePool(workers=1, name="t-sums")
        try:
            # All three are queued before the first may finish (it waits
            # for the gate), so what the other two waited does not hang on
            # how fast this thread submits under load.
            gate = threading.Event()

            def work(x):
                if x == 0:
                    gate.wait(10)
                return time.sleep(0.01) or x

            futs = [pool.submit(work, i) for i in range(3)]
            gate.set()
            assert [f.result() for f in futs] == [0, 1, 2]
            a = pool.gauges()
            pool.map(lambda x: time.sleep(0.01) or x, range(2))
            b = pool.gauges()
            assert a["wait_count"] == a["tasks"] == 3 and b["wait_count"] == b["tasks"] == 5
            for key in ("wait_ms_sum", "run_ms_sum"):
                assert 0 < a[key] < b[key]
            assert a["run_ms_sum"] >= 3 * 10 * 0.9
            assert a["wait_ms_sum"] >= (10 + 20) * 0.9  # the 2nd waited one run, the 3rd two
        finally:
            pool.close()

    def test_shared_pool_is_singleton(self):
        shutdown_decode_pool()
        try:
            a = get_decode_pool()
            assert get_decode_pool() is a
            assert a.name == "decode_pool"
        finally:
            shutdown_decode_pool()

    def test_shutdown_builds_fresh_from_env(self, monkeypatch):
        shutdown_decode_pool()
        monkeypatch.setenv("LUMEN_DECODE_WORKERS", "2")
        try:
            assert get_decode_pool().workers == 2
        finally:
            shutdown_decode_pool()


class TestIngestHandoff:
    def test_pipeline_defaults_to_shared_pool(self):
        import jax
        from lumen_tpu.pipeline.ingest import IngestPipeline, Stage
        from lumen_tpu.runtime.mesh import build_mesh

        mesh = build_mesh(devices=jax.devices("cpu")[:1])
        stage = Stage("s", preprocess=lambda x: {"v": [float(x)]},
                      device_fn=lambda tree: tree)
        pipe = IngestPipeline(mesh, [stage], batch_size=4)
        assert pipe.pool is get_decode_pool()
        records = pipe.run_all(range(6))
        assert [r["_index"] for r in records] == list(range(6))
        stats = pipe.stats.as_dict()
        assert stats["max_inflight"] >= 1
        assert stats["pool"]["workers"] == pipe.pool.workers

    def test_pipeline_private_pool_when_workers_pinned(self):
        import threading

        import jax
        from lumen_tpu.pipeline.ingest import IngestPipeline, Stage
        from lumen_tpu.runtime.mesh import build_mesh

        mesh = build_mesh(devices=jax.devices("cpu")[:1])
        thread_names = set()

        def preprocess(x):
            thread_names.add(threading.current_thread().name)
            return {"v": [float(x)]}

        stage = Stage("s", preprocess=preprocess, device_fn=lambda tree: tree)
        pipe = IngestPipeline(mesh, [stage], batch_size=4, workers=2)
        assert pipe.pool is None  # private pool is run-scoped, not held
        assert pipe.workers == 2
        assert len(pipe.run_all(range(5))) == 5
        assert any("ingest-prep" in n for n in thread_names)  # private pool ran it
        assert pipe.stats.as_dict()["pool"]["workers"] == 2
        # Run-scoped teardown: no leaked gauge registration after run().
        assert not any(
            "ingest-prep" in name
            for name in metrics.snapshot().get("gauges", {})
        )
