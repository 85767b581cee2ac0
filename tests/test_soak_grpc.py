"""Live-load soak of the continuous scheduler through the real gRPC server.

Round-2 verdict: the admit/retire unit tests cover the scheduler's logic,
but nothing drove the actual server with concurrent mixed traffic long
enough to catch slot/future-leak regressions under real threading — the
exact class of bug ``continuous.py``'s own ``_fail`` docstring worries
about. This soak fires 200+ mixed ``vlm_generate``/``vlm_generate_stream``
requests (varied lengths, some with images) from 16 client threads at a
server running the continuous scheduler, then asserts nothing is stuck,
the slot pool has returned to all-free, and the metrics counters moved
exactly as many times as requests were sent.
"""

from __future__ import annotations

import json
import threading
from concurrent.futures import ThreadPoolExecutor

import grpc
import pytest

from lumen_tpu.models.vlm import VLMManager
from lumen_tpu.serving.proto import ml_service_pb2 as pb
from lumen_tpu.serving.proto.ml_service_pb2_grpc import (
    InferenceStub,
    add_InferenceServicer_to_server,
)
from lumen_tpu.serving.router import HubRouter
from lumen_tpu.serving.services.vlm_service import VlmService
from lumen_tpu.utils.metrics import metrics
from tests.test_vlm import make_vlm_model_dir, png_bytes

N_REQUESTS = 208
N_CLIENT_THREADS = 16


@pytest.fixture(scope="module")
def soak_server(tmp_path_factory):
    model_dir = make_vlm_model_dir(tmp_path_factory.mktemp("soak"))
    manager = VLMManager(
        model_dir,
        dtype="float32",
        max_seq=128,
        max_new_cap=16,
        prefill_buckets=(16, 32),
        gen_slots=4,
        gen_block=4,
    )
    manager.initialize()
    svc = VlmService(manager)
    server = grpc.server(ThreadPoolExecutor(max_workers=10))
    add_InferenceServicer_to_server(HubRouter({"vlm": svc}), server)
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    channel = grpc.insecure_channel(f"127.0.0.1:{port}")
    yield InferenceStub(channel), manager
    channel.close()
    server.stop(grace=1.0)
    svc.close()


def _request(i: int) -> pb.InferRequest:
    prompts = [
        "describe the image",
        "a cat",
        "the quick dog image describe the cat",
        "count to three the image a dog describe",
    ]
    meta = {
        "messages": json.dumps(
            [{"role": "user", "content": prompts[i % len(prompts)]}]
        ),
        "max_new_tokens": str(1 + (i % 12)),
    }
    payload = png_bytes(size=32, seed=i) if i % 5 == 0 else b""
    task = "vlm_generate_stream" if i % 2 else "vlm_generate"
    return pb.InferRequest(
        correlation_id=f"soak-{i}",
        task=task,
        payload=payload,
        payload_mime="image/png" if payload else "",
        meta=meta,
    )


class TestContinuousSoak:
    def test_soak_mixed_traffic(self, soak_server):
        stub, manager = soak_server
        before = metrics.snapshot()["tasks"]

        ok = [0]
        failures: list[str] = []
        lock = threading.Lock()
        counter = iter(range(N_REQUESTS))

        def worker() -> None:
            while True:
                with lock:
                    i = next(counter, None)
                if i is None:
                    return
                try:
                    resps = list(stub.Infer(iter([_request(i)])))
                    assert resps, "no responses"
                    final = resps[-1]
                    assert final.is_final
                    if final.HasField("error"):
                        raise RuntimeError(final.error.message)
                    body = json.loads(final.result.decode())
                    if _request(i).task == "vlm_generate_stream":
                        # streamed text chunks then a final V1 body
                        assert body["finish_reason"]
                    with lock:
                        ok[0] += 1
                except Exception as e:  # noqa: BLE001 - collect, assert at end
                    with lock:
                        failures.append(f"req {i}: {type(e).__name__}: {e}")

        threads = [threading.Thread(target=worker) for _ in range(N_CLIENT_THREADS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        assert not any(t.is_alive() for t in threads), "client threads stuck"
        assert not failures, failures[:5]
        assert ok[0] == N_REQUESTS

        # Pool drained: no live slots, no pending queue, worker alive.
        sched = manager._continuous
        assert sched is not None
        with sched._cond:
            assert sched._slots == {}, "slots leaked"
            assert sched._pending == [], "requests stranded in queue"
        assert not sched._closed

        # Metrics moved exactly once per request, with zero new errors.
        after = metrics.snapshot()["tasks"]
        sent = {"vlm_generate": 0, "vlm_generate_stream": 0}
        for i in range(N_REQUESTS):
            sent[_request(i).task] += 1
        for task, n in sent.items():
            prev = before.get(task, {"count": 0, "errors": 0})
            assert after[task]["count"] - prev["count"] == n
            assert after[task]["errors"] - prev["errors"] == 0

    def test_pool_reusable_after_soak(self, soak_server):
        """The same server keeps serving after the storm (no poisoned
        state): one more request of each kind round-trips clean."""
        stub, _ = soak_server
        for i in (0, 1):
            resps = list(stub.Infer(iter([_request(i)])))
            final = resps[-1]
            assert final.is_final and not final.HasField("error")
