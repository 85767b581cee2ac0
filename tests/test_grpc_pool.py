"""The gRPC handler pool is sized from the decode slots the services
report: a streaming caption holds its handler thread from admission to its
last token, so a pool smaller than the slots leaves slots empty while
streams queue in front of the server. The ``grpc-pool`` gauge counts the
Infer RPCs inside a handler."""

from __future__ import annotations

import threading
import time
from types import SimpleNamespace

import grpc
import pytest

from lumen_tpu.core.config import validate_config_dict
from lumen_tpu.serving import server as srv
from lumen_tpu.serving.base_service import BaseService
from lumen_tpu.serving.echo import EchoService
from lumen_tpu.serving.proto import ml_service_pb2 as pb
from lumen_tpu.serving.proto.ml_service_pb2_grpc import InferenceStub
from lumen_tpu.serving import router
from lumen_tpu.serving.registry import TaskDefinition, TaskRegistry
from lumen_tpu.serving.router import (
    FED_CACHE_TASK,
    FederationRouter,
    HandlerPool,
    HubRouter,
    handler_pool,
    install_handler_pool,
)
from lumen_tpu.utils.metrics import metrics

SLOTS = 16


@pytest.fixture(autouse=True)
def _own_pool(monkeypatch):
    """Each case starts with no pool installed and leaves the process's
    pool, and the ``grpc-pool`` gauge, as it found them."""
    monkeypatch.setattr(router, "_HANDLERS", None)
    yield
    metrics.unregister_gauges("grpc-pool")


def _free_port() -> int:
    """gRPC binds with SO_REUSEPORT on Linux: each test server needs a
    port of its own."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class SlotsService(BaseService):
    """A streaming service that reports one engine of ``slots`` decode
    slots. Its ``hold`` task's handler runs ``gate()`` on the handler
    thread before it streams its one chunk, and counts how many handlers
    are inside at once."""

    def __init__(self, slots: int, gate):
        self.slots, self.gate = slots, gate
        self.inside = self.inside_peak = 0
        self._lock = threading.Lock()
        registry = TaskRegistry("slots")
        registry.register(
            TaskDefinition(
                name="hold",
                handler=self._hold,
                description="wait at the gate, then stream one chunk",
                input_mimes=("text/plain",),
                output_mime="text/plain",
            )
        )
        super().__init__(registry)

    def decode_slots(self) -> int:
        return self.slots

    def capability(self):
        return self.registry.build_capability(model_ids=["slots"], runtime="none")

    def _hold(self, payload: bytes, mime: str, meta: dict[str, str]):  # noqa: ARG002
        def chunks():
            with self._lock:
                self.inside += 1
                self.inside_peak = max(self.inside_peak, self.inside)
            try:
                self.gate()
            finally:
                with self._lock:
                    self.inside -= 1
            yield payload, "text/plain", {}

        return chunks()


def _config(tmp_path) -> dict:
    return {
        "metadata": {"version": "1.0.0", "region": "other", "cache_dir": str(tmp_path / "cache")},
        "deployment": {"mode": "hub", "services": ["echo"]},
        "server": {"port": _free_port(), "host": "127.0.0.1"},
        "services": {
            "echo": {
                "enabled": True,
                "package": "lumen_tpu",
                "import_info": {"registry_class": "lumen_tpu.serving.echo.EchoService"},
                "models": {"echo": {"model": "test/model-echo"}},
            },
        },
    }


def _set_override(monkeypatch, workers: str | None) -> None:
    if workers is None:
        monkeypatch.delenv("LUMEN_GRPC_WORKERS", raising=False)
    else:
        monkeypatch.setenv("LUMEN_GRPC_WORKERS", workers)


def _serve(tmp_path, monkeypatch, services: dict):
    monkeypatch.setattr(srv, "build_services", lambda config, failed=None: dict(services))
    return srv.serve(validate_config_dict(_config(tmp_path)), skip_download=True)


def _streams(port: int, n: int, deadline_s: float) -> list:
    """``n`` concurrent ``hold`` streams; each entry is its responses or
    the RPC error it ended in."""
    out: list = [None] * n
    chan = grpc.insecure_channel(f"127.0.0.1:{port}")
    try:
        grpc.channel_ready_future(chan).result(timeout=10)
        stub = InferenceStub(chan)

        def one(i: int) -> None:
            req = pb.InferRequest(correlation_id=f"c{i}", task="hold", payload=b"x", payload_mime="text/plain")
            try:
                out[i] = list(stub.Infer(iter([req]), timeout=deadline_s))
            except grpc.RpcError as e:
                out[i] = e

        threads = [threading.Thread(target=one, args=(i,), daemon=True) for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=deadline_s + 5)
    finally:
        chan.close()
    return out


def _pool_gauge() -> dict:
    return metrics.snapshot()["gauges"]["grpc-pool"]


def _vlm(*slots: int):
    from lumen_tpu.serving.services.vlm_service import VlmService

    return VlmService(SimpleNamespace(_engines=[SimpleNamespace(n_slots=n) for n in slots]))


@pytest.mark.parametrize(
    "services, override, size",
    [
        pytest.param(lambda: {"vlm": _vlm(16), "clip": EchoService()}, None, 26, id="one-engine"),
        pytest.param(lambda: {"vlm": _vlm(16, 8)}, None, 34, id="sum-over-two-engines"),
        pytest.param(lambda: {"echo": EchoService()}, None, 10, id="no-caption-engine"),
        pytest.param(lambda: {}, None, 10, id="front-tier"),
        pytest.param(lambda: {"vlm": _vlm(16)}, "7", 7, id="override-wins"),
    ],
)
def test_pool_size_rule(monkeypatch, services, override, size):
    _set_override(monkeypatch, override)
    assert srv.grpc_pool_size(srv.decode_slots(services())) == size


@pytest.mark.integration
@pytest.mark.parametrize(
    "override, all_enter",
    [pytest.param(None, True, id="derived"), pytest.param(str(SLOTS - 1), False, id="one-short")],
)
def test_every_slot_holds_a_live_stream(tmp_path, monkeypatch, override, all_enter):
    """N streams whose handlers wait at a barrier of N all enter at once
    under the derived pool; a pool one short stops at its size (the old
    fixed ten stopped at ten). Every wait has its deadline: the test ends
    within ~10 s either way."""
    _set_override(monkeypatch, override)
    barrier = threading.Barrier(SLOTS)
    svc = SlotsService(SLOTS, gate=lambda: barrier.wait(timeout=2.0 if override else 8.0))
    handle = _serve(tmp_path, monkeypatch, {"slots": svc})
    try:
        assert srv.grpc_workers() == handler_pool().size == _pool_gauge()["size"]
        out = _streams(handle.port, SLOTS, deadline_s=15.0)
    finally:
        handle.stop(grace=0.2)
    ok = [r for r in out if isinstance(r, list) and r and not r[0].HasField("error")]
    if all_enter:
        assert len(ok) == SLOTS, out
        assert svc.inside_peak == SLOTS
        assert handler_pool().size == 10 + SLOTS
        assert _pool_gauge()["busy_peak"] >= SLOTS
    else:
        assert handler_pool().size == SLOTS - 1
        assert not ok
        assert svc.inside_peak == SLOTS - 1


@pytest.mark.integration
@pytest.mark.parametrize("override, size", [(None, 10 + SLOTS), ("7", 7)])
def test_federation_wait_slots_are_half_the_pool_in_use(tmp_path, monkeypatch, override, size):
    _set_override(monkeypatch, override)
    handle = _serve(tmp_path, monkeypatch, {"slots": SlotsService(SLOTS, gate=lambda: None)})
    try:
        assert srv.grpc_workers() == size
        slots = handler_pool().wait_slots
        taken = 0
        while taken <= size and slots.acquire(blocking=False):
            taken += 1
        for _ in range(taken):
            slots.release()
        assert taken == size // 2
    finally:
        handle.stop(grace=0.2)


@pytest.mark.integration
def test_gauge_follows_streams_held_open(tmp_path, monkeypatch):
    """``busy``, ``busy_peak`` and ``busy_ms_sum`` follow three streams
    held open and then released; over a known window the integral's
    delta over the wall clock's reads the three within 5%."""
    _set_override(monkeypatch, None)
    release = threading.Event()
    held = 3
    handle = _serve(tmp_path, monkeypatch, {"slots": SlotsService(SLOTS, gate=lambda: release.wait(10.0))})
    try:
        idle = _pool_gauge()
        assert (idle["busy"], idle["busy_peak"], idle["size"]) == (0, 0, 10 + SLOTS)
        done: list = []
        runner = threading.Thread(target=lambda: done.append(_streams(handle.port, held, deadline_s=15.0)), daemon=True)
        runner.start()
        deadline = time.monotonic() + 10
        while _pool_gauge()["busy"] < held and time.monotonic() < deadline:
            time.sleep(0.01)
        g0 = _pool_gauge()
        time.sleep(0.4)
        g1 = _pool_gauge()
        release.set()
        runner.join(timeout=15)
        deadline = time.monotonic() + 5
        while _pool_gauge()["busy"] and time.monotonic() < deadline:
            time.sleep(0.01)
        g2 = _pool_gauge()
    finally:
        release.set()
        handle.stop(grace=0.2)
    assert g0["busy"] == g1["busy"] == held
    mean = (g1["busy_ms_sum"] - g0["busy_ms_sum"]) / (g1["wall_ms"] - g0["wall_ms"])
    assert mean == pytest.approx(held, rel=0.05)
    assert (g2["busy"], g2["busy_peak"]) == (0, held)
    assert g2["busy_ms_sum"] > g1["busy_ms_sum"]
    assert all(not r[0].HasField("error") for r in done[0])


@pytest.mark.parametrize("router", [lambda: HubRouter({}), lambda: FederationRouter(SimpleNamespace())])
def test_both_routers_count_an_infer_while_it_runs(router):
    pool = install_handler_pool(4)
    stream = router().Infer(iter([pb.InferRequest(correlation_id="c", task=FED_CACHE_TASK, payload=b"k")]), None)
    first = next(stream)  # the handler is inside, suspended at its answer
    assert first.meta["fed_cache"] == "miss"
    assert (pool.gauges()["busy"], pool.gauges()["busy_peak"]) == (1, 1)
    assert list(stream) == []
    g = pool.gauges()
    assert (g["busy"], g["busy_peak"]) == (0, 1)
    assert g["busy_ms_sum"] <= g["wall_ms"]


def test_busy_integral_counts_each_rpc_for_its_time():
    pool = HandlerPool(3)
    pool.enter()
    pool.enter()
    time.sleep(0.05)
    pool.leave()
    g = pool.gauges()
    pool.leave()
    assert g["busy"] == 1 and g["busy_peak"] == 2 and g["size"] == 3
    # two RPCs held ~all of the window so far: the mean lies near 2
    assert 1.5 < g["busy_ms_sum"] / g["wall_ms"] <= 2.0
