"""Bulk streaming lane (ISSUE 5 tentpole): N tagged items on ONE Infer
stream fan out concurrently, come back tagged (out of order is fine), and
preserve the per-item cache / quarantine / error-isolation semantics of
the unary path. A client disconnect mid-stream cancels the not-yet-started
remainder of the fan-out.
"""

import json
import threading
import time
import uuid
from concurrent.futures import ThreadPoolExecutor

import grpc
import numpy as np
import pytest

from lumen_tpu.runtime.batcher import MicroBatcher
from lumen_tpu.runtime.quarantine import get_quarantine, guarded_key
from lumen_tpu.runtime.result_cache import (
    get_result_cache,
    make_key,
    reset_result_cache,
)
from lumen_tpu.serving import (
    BaseService,
    HubRouter,
    ServiceError,
    TaskDefinition,
    TaskRegistry,
)
from lumen_tpu.serving.proto import ml_service_pb2 as pb
from lumen_tpu.serving.proto.ml_service_pb2_grpc import (
    InferenceStub,
    add_InferenceServicer_to_server,
)


@pytest.fixture()
def cache_on(monkeypatch):
    monkeypatch.setenv("LUMEN_CACHE_BYTES", str(64 << 20))
    reset_result_cache()
    yield
    monkeypatch.setenv("LUMEN_CACHE_BYTES", "0")
    reset_result_cache()


class EmbedService(BaseService):
    """Manager-shaped test service: content-addressed cache + quarantine
    gate + a real MicroBatcher behind the handler, so the bulk lane is
    proven against the semantics that matter, not an echo stub."""

    def __init__(self, name="bulk"):
        registry = TaskRegistry(name)
        registry.register(TaskDefinition(name=f"{name}_embed", handler=self._embed))
        super().__init__(registry)
        self.ns = f"bulktest/embed/m@{uuid.uuid4().hex[:8]}"
        self.batcher = MicroBatcher(
            self._fn, max_batch=8, max_latency_ms=10, name=f"bulk-{uuid.uuid4().hex[:6]}"
        ).start()
        self.batch_sizes: list[int] = []
        self.device_payloads: list[bytes] = []
        self._lock = threading.Lock()

    def capability(self):
        return self.registry.build_capability(model_ids=["bulk-v0"], runtime="jax-cpu")

    def close(self):
        self.batcher.close()

    def _fn(self, tree, n):
        self.batch_sizes.append(n)
        return tree

    def _embed(self, payload, mime, meta):
        key = guarded_key(self.ns, None, payload)  # quarantine gate, ONE hash

        def compute():
            arr = np.frombuffer(payload.ljust(8, b"\0")[:8], np.uint8).astype(np.float32)
            row = self.batcher(arr, fingerprint=key)
            with self._lock:
                self.device_payloads.append(bytes(payload))
            return row

        out = get_result_cache().get_or_compute(
            self.ns, None, payload, compute, clone=np.copy, key=key
        )
        body = json.dumps({"v": np.asarray(out).tolist()}).encode()
        return body, "application/json", {}


@pytest.fixture()
def bulk_hub(cache_on):
    svc = EmbedService("bulk")
    server = grpc.server(ThreadPoolExecutor(max_workers=4))
    add_InferenceServicer_to_server(HubRouter({"bulk": svc}), server)
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    channel = grpc.insecure_channel(f"127.0.0.1:{port}")
    yield InferenceStub(channel), svc
    channel.close()
    server.stop(0)
    svc.close()


def expected_vec(payload: bytes) -> list[float]:
    return np.frombuffer(payload.ljust(8, b"\0")[:8], np.uint8).astype(np.float32).tolist()


@pytest.mark.integration
class TestBulkStream:
    def test_poison_and_cache_hit_interleaved(self, bulk_hub):
        """ISSUE 5 acceptance: N items, one pre-quarantined poison and one
        cache hit interleaved — tagged correct results, the poison fails
        ALONE (INVALID_ARGUMENT + quarantined meta), and the hit never
        reaches the batcher."""
        from lumen_tpu.client import infer_bulk

        stub, svc = bulk_hub
        payloads = [f"item-{i}".encode() for i in range(8)]
        poison, hit = payloads[2], payloads[5]
        get_quarantine().add(make_key(svc.ns, None, poison), "test poison")
        # Warm ONE unary request so payload[5] is a cache hit inside bulk.
        resps = list(stub.Infer(iter([pb.InferRequest(
            correlation_id="warm", task="bulk_embed", payload=hit,
            payload_mime="application/octet-stream",
        )])))
        assert not resps[-1].HasField("error")
        with svc._lock:
            svc.device_payloads.clear()

        results = dict(infer_bulk(stub, "bulk_embed", payloads))
        assert set(results) == set(range(8))  # every item answered, tagged
        for i, payload in enumerate(payloads):
            if i == 2:
                err = results[i]
                assert isinstance(err, ServiceError)
                assert err.code == pb.ERROR_CODE_INVALID_ARGUMENT
                assert "quarantined" in str(err)
            else:
                data, _mime, meta = results[i]
                assert json.loads(data)["v"] == expected_vec(payload)
                if i == 5:
                    assert meta.get("cache_hit") == "1"
        with svc._lock:
            seen = list(svc.device_payloads)
        assert hit not in seen  # the hit never touched the batcher
        assert poison not in seen  # rejected before the device
        assert sorted(seen) == sorted(p for i, p in enumerate(payloads) if i not in (2, 5))

    def test_bulk_coalesces_into_batches(self, bulk_hub):
        """The whole point of the lane: concurrent fan-out must feed the
        MicroBatcher multi-item batches, not 16 singletons."""
        from lumen_tpu.client import infer_bulk

        stub, svc = bulk_hub
        payloads = [f"co-{i}".encode() for i in range(16)]
        results = dict(infer_bulk(stub, "bulk_embed", payloads))
        assert set(results) == set(range(16))
        assert sum(svc.batch_sizes) == 16
        assert max(svc.batch_sizes) >= 2  # real coalescing happened
        assert len(svc.batch_sizes) <= 12

    def test_bulk_lane_counts_every_item_and_its_wait(self, bulk_hub):
        """``bulk-lane`` (process-wide): one item for every item sent, the
        sums only grow, nothing left queued once the stream has drained; a
        stream without the bulk meta does not pass through it."""
        from lumen_tpu.client import infer_bulk
        from lumen_tpu.utils.metrics import metrics

        stub, _svc = bulk_hub
        list(infer_bulk(stub, "bulk_embed", [b"lane-warm"]))  # the provider exists from the first item on
        seen = [metrics.snapshot()["gauges"]["bulk-lane"]]
        for burst in (12, 5):
            payloads = [f"lane-{burst}-{i}".encode() for i in range(burst)]
            assert len(dict(infer_bulk(stub, "bulk_embed", payloads))) == burst
            seen.append(metrics.snapshot()["gauges"]["bulk-lane"])
        a, b, c = seen
        assert (b["items"] - a["items"], c["items"] - b["items"]) == (12, 5)
        for key in ("queue_ms_sum", "run_ms_sum"):
            assert a[key] <= b[key] <= c[key]
        assert c["run_ms_sum"] > a["run_ms_sum"] and c["queue_ms_sum"] > a["queue_ms_sum"]
        assert c["queued"] == 0 and c["workers"] >= 8
        list(stub.Infer(iter([pb.InferRequest(
            correlation_id="u9", task="bulk_embed", payload=b"lane-unary",
            payload_mime="application/octet-stream",
        )])))
        assert metrics.snapshot()["gauges"]["bulk-lane"]["items"] == c["items"]

    def test_mixed_unary_stream_unaffected(self, bulk_hub):
        """A stream WITHOUT the bulk meta keeps the sequential unary path."""
        stub, _svc = bulk_hub
        payload = b"unary-1"
        resps = list(stub.Infer(iter([pb.InferRequest(
            correlation_id="u1", task="bulk_embed", payload=payload,
            payload_mime="application/octet-stream",
        )])))
        assert len(resps) == 1 and resps[0].is_final
        assert json.loads(resps[0].result)["v"] == expected_vec(payload)


class TestBulkCancellation:
    def test_disconnect_cancels_remaining_fanout(self, monkeypatch, cache_on):
        """Client disconnect mid-stream (the request iterator raising, which
        is what gRPC surfaces) cancels the not-yet-started remainder: with
        a 1-worker pool, items queued behind a blocked first item must
        never run their handlers."""
        from lumen_tpu.serving import base_service

        pool = ThreadPoolExecutor(1, thread_name_prefix="bulk-cancel-t")
        monkeypatch.setattr(base_service, "_bulk_pool", pool)
        started: list[str] = []
        release = threading.Event()

        class BlockingService(BaseService):
            def __init__(self):
                registry = TaskRegistry("blk")
                registry.register(TaskDefinition(name="blk_slow", handler=self._slow))
                super().__init__(registry)

            def capability(self):
                return self.registry.build_capability(model_ids=["blk"], runtime="jax-cpu")

            def _slow(self, payload, mime, meta):
                started.append(bytes(payload).decode())
                release.wait(10)
                return payload, "application/octet-stream", {}

        svc = BlockingService()
        raised = threading.Event()

        def requests():
            for i in range(4):
                yield pb.InferRequest(
                    correlation_id=str(i), task="blk_slow",
                    payload=f"p{i}".encode(), meta={"bulk": "1"},
                )
            raised.set()
            raise RuntimeError("client disconnected")

        responses: list = []
        consumer = threading.Thread(
            target=lambda: responses.extend(svc.Infer(requests(), None)), daemon=True
        )
        consumer.start()
        assert raised.wait(5)
        # Give the reader's except-path a beat to latch the stop flag
        # (a few bytecodes after `raised` fires), then let item 0 finish.
        time.sleep(0.2)
        release.set()
        consumer.join(timeout=10)
        assert not consumer.is_alive()
        # Item 0 ran; items 1-3 were fanned out but cancelled before start.
        assert started == ["p0"]
        # After the disconnect nothing is yielded — even the completed
        # item's response goes nowhere (the client is gone).
        assert responses == []
        pool.shutdown(wait=False)
        # The cancelled remainder left the lane's queue without running.
        from lumen_tpu.utils.metrics import metrics

        assert metrics.snapshot()["gauges"]["bulk-lane"]["queued"] == 0
