"""Resilience-layer tests: retry/backoff utilities, fault injection,
downloader retries, deadline propagation into dispatch, and the
degraded-boot -> background-recovery lifecycle of the hub server —
every failure forced deterministically through ``lumen_tpu.testing.faults``.
"""

import json
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import grpc
import pytest
from google.protobuf import empty_pb2

from lumen_tpu.core.config import validate_config_dict
from lumen_tpu.core.exceptions import DownloadError
from lumen_tpu.testing import FaultInjected, FaultInjector, faults
from lumen_tpu.utils import deadline as request_deadline
from lumen_tpu.utils.deadline import DeadlineExpired, QueueFull
from lumen_tpu.utils.metrics import metrics
from lumen_tpu.utils.retry import RetryPolicy, policy_from_env, retry_call


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


# ---------------------------------------------------------------------------
# retry utility
# ---------------------------------------------------------------------------


class TestRetryPolicy:
    def test_delay_caps_and_grows(self):
        p = RetryPolicy(attempts=5, base_delay_s=1.0, max_delay_s=4.0, jitter=False)
        assert [p.delay(a) for a in range(4)] == [1.0, 2.0, 4.0, 4.0]

    def test_full_jitter_bounded(self):
        import random

        p = RetryPolicy(base_delay_s=1.0, max_delay_s=8.0, jitter=True)
        rng = random.Random(7)
        for a in range(6):
            d = p.delay(a, rng)
            assert 0.0 <= d <= min(8.0, 2.0**a)

    def test_policy_from_env(self, monkeypatch):
        monkeypatch.setenv("LUMEN_X_RETRIES", "4")
        monkeypatch.setenv("LUMEN_X_BACKOFF_S", "0.25")
        p = policy_from_env("X", RetryPolicy())
        assert p.attempts == 5 and p.base_delay_s == 0.25

    def test_policy_from_env_malformed_degrades(self, monkeypatch):
        monkeypatch.setenv("LUMEN_X_RETRIES", "many")
        p = policy_from_env("X", RetryPolicy(attempts=2))
        assert p.attempts == 2


class TestRetryCall:
    def test_retries_then_succeeds(self):
        calls, sleeps = [], []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise ConnectionError("transient")
            return "ok"

        before = metrics.counter_value("retries")
        out = retry_call(
            flaky,
            policy=RetryPolicy(attempts=5, base_delay_s=0.01, jitter=False),
            retryable=ConnectionError,
            scope="test_scope",
            sleep=sleeps.append,
        )
        assert out == "ok" and len(calls) == 3
        assert len(sleeps) == 2
        assert metrics.counter_value("retries") == before + 2
        assert metrics.counter_value("retries:test_scope") >= 2

    def test_non_retryable_propagates_immediately(self):
        calls = []

        def bad():
            calls.append(1)
            raise ValueError("permanent")

        with pytest.raises(ValueError):
            retry_call(bad, retryable=ConnectionError, sleep=lambda s: None)
        assert len(calls) == 1

    def test_attempts_exhausted_raises_last_error(self):
        def always():
            raise ConnectionError("still down")

        with pytest.raises(ConnectionError):
            retry_call(
                always,
                policy=RetryPolicy(attempts=3, base_delay_s=0, jitter=False),
                retryable=ConnectionError,
                sleep=lambda s: None,
            )

    def test_predicate_spec(self):
        attempts = []

        def fn():
            attempts.append(1)
            raise RuntimeError("code=503")

        with pytest.raises(RuntimeError):
            retry_call(
                fn,
                policy=RetryPolicy(attempts=3, base_delay_s=0, jitter=False),
                retryable=lambda e: "503" in str(e) and len(attempts) < 2,
                sleep=lambda s: None,
            )
        assert len(attempts) == 2


# ---------------------------------------------------------------------------
# fault injection harness
# ---------------------------------------------------------------------------


class TestFaultInjector:
    def test_disarmed_is_noop(self):
        inj = FaultInjector()
        inj.clear()  # mark env as consumed
        inj.check("download", "whatever")

    def test_times_cap_then_clears(self):
        inj = FaultInjector()
        inj.clear()
        inj.configure("download", times=2)
        for _ in range(2):
            with pytest.raises(FaultInjected):
                inj.check("download")
        inj.check("download")  # exhausted -> healthy again
        assert inj.rule("download").fired == 2
        assert not inj.active()

    def test_match_filters_detail(self):
        inj = FaultInjector()
        inj.clear()
        inj.configure("download", match="bad-model")
        inj.check("download", "good-model")  # no match, no fault
        with pytest.raises(FaultInjected):
            inj.check("download", "repo/bad-model")

    def test_rate_deterministic_with_seed(self):
        inj = FaultInjector(seed=1234)
        inj.clear()
        inj.configure("batch_execute", rate=0.5)
        outcomes = []
        for _ in range(50):
            try:
                inj.check("batch_execute")
                outcomes.append(False)
            except FaultInjected:
                outcomes.append(True)
        assert any(outcomes) and not all(outcomes)

    def test_env_spec_parsing(self):
        inj = FaultInjector()
        inj.load_env("download:1:2,model_load:0.5,@oops,batch_execute@vlm")
        assert inj.rule("download").times == 2
        assert inj.rule("model_load").rate == 0.5
        batch = inj.rule("batch_execute")
        assert batch.match == "vlm" and batch.rate == 1.0
        assert inj.rule("@oops") is None  # malformed entry skipped

    def test_env_loaded_on_first_check(self, monkeypatch):
        monkeypatch.setenv("LUMEN_FAULTS", "model_load")
        inj = FaultInjector()
        with pytest.raises(FaultInjected):
            inj.check("model_load")

    def test_injected_error_is_resource_error(self):
        from lumen_tpu.core.exceptions import ResourceError

        assert issubclass(FaultInjected, ResourceError)


# ---------------------------------------------------------------------------
# downloader: retries + fault point
# ---------------------------------------------------------------------------


def make_hub_config(tmp_path, services=("good", "bad")):
    registry = {
        "good": "lumen_tpu.serving.echo.EchoService",
        "bad": "lumen_tpu.testing.services.SecondaryEchoService",
    }
    return validate_config_dict(
        {
            "metadata": {
                "version": "1.0.0",
                "region": "other",
                "cache_dir": str(tmp_path / "cache"),
            },
            "deployment": {"mode": "hub", "services": list(services)},
            "server": {"port": 50951, "host": "127.0.0.1"},
            "services": {
                name: {
                    "enabled": True,
                    "package": "lumen_tpu",
                    "import_info": {"registry_class": registry[name]},
                    "models": {name: {"model": f"test/model-{name}"}},
                }
                for name in services
            },
        }
    )


class FakePlatform:
    """Offline stand-in for the HF/ModelScope snapshot platform: 'fetching'
    materializes a minimal valid model dir on disk."""

    def __init__(self, region, cache_dir):  # same signature as Platform
        self.root = os.path.join(str(cache_dir), "models")
        self.downloads = []

    def local_dir(self, repo_name: str) -> str:
        return os.path.join(self.root, repo_name.split("/")[-1])

    def is_cached(self, repo_name: str) -> bool:
        return os.path.isdir(self.local_dir(repo_name))

    def download(self, repo_name: str, allow_patterns=None, update: bool = False) -> str:
        self.downloads.append(repo_name)
        d = self.local_dir(repo_name)
        os.makedirs(d, exist_ok=True)
        manifest = {
            "name": repo_name.split("/")[-1],
            "version": "1.0.0",
            "description": "offline test model",
            "model_type": "test",
            "source": {"format": "custom", "repo_id": repo_name},
            "runtimes": {"jax": {"available": True, "files": []}},
        }
        with open(os.path.join(d, "model_info.json"), "w", encoding="utf-8") as f:
            json.dump(manifest, f)
        return d


@pytest.fixture()
def fake_platform(monkeypatch):
    import lumen_tpu.core.downloader as dl

    monkeypatch.setattr(dl, "Platform", FakePlatform)
    # Keep retry waits out of the test clock.
    monkeypatch.setenv("LUMEN_DOWNLOAD_BACKOFF_S", "0")
    monkeypatch.setenv("LUMEN_DOWNLOAD_BACKOFF_MAX_S", "0")


class TestDownloaderResilience:
    def test_transient_fault_retried_to_success(self, tmp_path, fake_platform, monkeypatch):
        from lumen_tpu.core.downloader import Downloader

        monkeypatch.setenv("LUMEN_DOWNLOAD_RETRIES", "2")  # 3 attempts per fetch
        faults.configure("download", times=2)
        report = Downloader(make_hub_config(tmp_path, services=("good",))).download_all()
        assert report.ok, [r.error for r in report.failures()]

    def test_fault_beyond_retries_reported_not_raised(self, tmp_path, fake_platform, monkeypatch):
        from lumen_tpu.core.downloader import Downloader

        monkeypatch.setenv("LUMEN_DOWNLOAD_RETRIES", "0")
        faults.configure("download", times=100)
        report = Downloader(make_hub_config(tmp_path, services=("good",))).download_all()
        assert not report.ok
        assert "injected fault" in report.failures()[0].error

    def test_download_service_scopes_to_one_service(self, tmp_path, fake_platform):
        from lumen_tpu.core.downloader import Downloader

        d = Downloader(make_hub_config(tmp_path))
        report = d.download_service("bad")
        assert report.ok and [r.service for r in report.results] == ["bad"]
        assert d.platform.downloads == ["test/model-bad"]

    def test_download_service_unknown_name(self, tmp_path, fake_platform):
        from lumen_tpu.core.downloader import Downloader

        report = Downloader(make_hub_config(tmp_path)).download_service("nope")
        assert not report.ok and "not enabled" in report.failures()[0].error


# ---------------------------------------------------------------------------
# deadline propagation into dispatch
# ---------------------------------------------------------------------------


class _Ctx:
    """gRPC context stub with a deadline."""

    def __init__(self, remaining):
        self._remaining = remaining

    def time_remaining(self):
        return self._remaining


def _req(task, cid="c1", payload=b"x"):
    from lumen_tpu.serving.proto import ml_service_pb2 as pb

    return pb.InferRequest(correlation_id=cid, task=task, payload=payload, payload_mime="text/plain")


class TestDispatchDeadline:
    def _service(self, handler):
        from lumen_tpu.serving import BaseService, TaskDefinition, TaskRegistry

        class Svc(BaseService):
            def __init__(self):
                reg = TaskRegistry("t")
                reg.register(TaskDefinition(name="task", handler=handler))
                super().__init__(reg)

            def capability(self):  # pragma: no cover - unused
                raise NotImplementedError

        return Svc()

    def test_expired_deadline_rejected_before_handler(self):
        from lumen_tpu.serving.proto import ml_service_pb2 as pb

        calls = []
        svc = self._service(lambda p, m, meta: (calls.append(1), (b"", "", {}))[1])
        before = metrics.counter_value("deadline_drops")
        (resp,) = svc.Infer(iter([_req("task")]), _Ctx(remaining=-0.5))
        assert resp.error.code == pb.ERROR_CODE_DEADLINE_EXCEEDED
        assert calls == []  # model never touched
        assert metrics.counter_value("deadline_drops") == before + 1

    def test_live_deadline_visible_to_handler(self):
        seen = {}

        def handler(p, m, meta):
            seen["remaining"] = request_deadline.remaining()
            return b"ok", "text/plain", {}

        svc = self._service(handler)
        (resp,) = svc.Infer(iter([_req("task")]), _Ctx(remaining=30.0))
        assert resp.result == b"ok"
        assert seen["remaining"] is not None and 0 < seen["remaining"] <= 30.0
        # context cleaned up after dispatch
        assert request_deadline.get_deadline() is None

    def test_no_deadline_context_passes_none(self):
        seen = {}

        def handler(p, m, meta):
            seen["deadline"] = request_deadline.get_deadline()
            return b"ok", "text/plain", {}

        svc = self._service(handler)
        (resp,) = svc.Infer(iter([_req("task")]), _Ctx(remaining=None))
        assert resp.result == b"ok" and seen["deadline"] is None

    def test_queue_full_maps_to_unavailable_with_hint(self):
        from lumen_tpu.serving.proto import ml_service_pb2 as pb

        def handler(p, m, meta):
            raise QueueFull("batcher: admission queue full (2 waiting); request shed")

        svc = self._service(handler)
        (resp,) = svc.Infer(iter([_req("task")]), _Ctx(remaining=None))
        assert resp.error.code == pb.ERROR_CODE_UNAVAILABLE
        assert "queue full" in resp.error.message
        assert "backoff" in resp.error.detail

    def test_deadline_expired_maps_to_wire_code(self):
        from lumen_tpu.serving.proto import ml_service_pb2 as pb

        def handler(p, m, meta):
            raise DeadlineExpired("expired while queued")

        svc = self._service(handler)
        (resp,) = svc.Infer(iter([_req("task")]), _Ctx(remaining=None))
        assert resp.error.code == pb.ERROR_CODE_DEADLINE_EXCEEDED


# ---------------------------------------------------------------------------
# degraded boot + background recovery (acceptance path)
# ---------------------------------------------------------------------------


@pytest.mark.integration
class TestDegradedHub:
    @pytest.fixture()
    def fast_recovery(self, monkeypatch):
        monkeypatch.setenv("LUMEN_DOWNLOAD_RETRIES", "0")
        monkeypatch.setenv("LUMEN_RECOVERY_BACKOFF_S", "0.01")
        monkeypatch.setenv("LUMEN_RECOVERY_BACKOFF_MAX_S", "0.05")

    def _infer(self, stub, task):
        return list(stub.Infer(iter([_req(task)])))

    def test_hub_boots_serves_degrades_and_recovers(
        self, tmp_path, fake_platform, fast_recovery
    ):
        from lumen_tpu.serving.proto import ml_service_pb2 as pb
        from lumen_tpu.serving.proto.ml_service_pb2_grpc import InferenceStub
        from lumen_tpu.serving.resilience import DegradedService
        from lumen_tpu.serving.server import serve

        config = make_hub_config(tmp_path)
        # The 'bad' service's download fails at boot and at every recovery
        # attempt until the degraded state has been looked at: the test, not
        # a 0.01-s backoff, decides when the fault clears.
        faults.configure("download", times=10_000, match="model-bad")
        recoveries_before = metrics.counter_value("recoveries")

        handle = serve(config)
        try:
            assert handle.port > 0
            assert isinstance(handle.services["bad"], DegradedService)
            chan = grpc.insecure_channel(f"127.0.0.1:{handle.port}")
            grpc.channel_ready_future(chan).result(timeout=10)
            stub = InferenceStub(chan)

            # Healthy sibling serves.
            (r,) = self._infer(stub, "echo")
            assert r.result == b"x" and not r.HasField("error")

            # Degraded service's task answers UNAVAILABLE + recovery hint.
            (r,) = self._infer(stub, "echo2")
            assert r.error.code == pb.ERROR_CODE_UNAVAILABLE
            assert "degraded" in r.error.message
            assert "retry" in r.error.detail

            # Health: hub stays OK, per-service status in trailing metadata.
            health = stub.Health.with_call(empty_pb2.Empty())
            trailing = dict(health[1].trailing_metadata() or [])
            statuses = json.loads(trailing["lumen-service-status"])
            assert statuses == {"good": "healthy", "bad": "degraded"}

            # Background recovery: fault cleared, service hot-swaps in.
            assert isinstance(handle.services["bad"], DegradedService)
            faults.clear("download")
            assert handle.recovery is not None
            assert handle.recovery.wait_idle(timeout=15)
            (r,) = self._infer(stub, "echo2")
            assert r.result == b"x" and not r.HasField("error")
            assert not isinstance(handle.services["bad"], DegradedService)
            assert metrics.counter_value("recoveries") == recoveries_before + 1

            health = stub.Health.with_call(empty_pb2.Empty())
            statuses = json.loads(
                dict(health[1].trailing_metadata() or [])["lumen-service-status"]
            )
            assert statuses == {"good": "healthy", "bad": "healthy"}
            chan.close()
        finally:
            handle.stop(grace=0.2)

    def test_strict_boot_env_restores_abort(self, tmp_path, fake_platform, monkeypatch):
        from lumen_tpu.serving.server import ensure_models

        monkeypatch.setenv("LUMEN_DOWNLOAD_RETRIES", "0")
        monkeypatch.setenv("LUMEN_STRICT_BOOT", "1")
        faults.configure("download", times=100)
        with pytest.raises(SystemExit):
            ensure_models(make_hub_config(tmp_path))

    def test_model_load_failure_degrades_not_kills(self, tmp_path, fake_platform):
        from lumen_tpu.serving.resilience import DegradedService
        from lumen_tpu.serving.server import build_services

        faults.configure("model_load", times=100, match="bad")
        services = build_services(make_hub_config(tmp_path))
        assert not isinstance(services["good"], DegradedService)
        bad = services["bad"]
        assert isinstance(bad, DegradedService)
        # Expected tasks still routed, answering UNAVAILABLE.
        assert bad.registry.task_names() == ["echo2", "echo2_meta"]

    def test_recovery_gives_up_at_cap(self, tmp_path, fake_platform, monkeypatch):
        from lumen_tpu.serving import HubRouter
        from lumen_tpu.serving.resilience import DegradedService, RecoveryManager
        from lumen_tpu.utils.retry import RetryPolicy

        placeholder = DegradedService("bad", "boom", tasks=["echo2"])
        router = HubRouter({"bad": placeholder})
        attempts = []

        def rebuild(name):
            attempts.append(name)
            raise DownloadError("still broken")

        gave_up_before = metrics.counter_value("recovery_gave_up")
        mgr = RecoveryManager(
            router,
            rebuild,
            policy=RetryPolicy(attempts=0, base_delay_s=0.0, max_delay_s=0.0, jitter=False),
            max_attempts=3,
            poll_interval_s=0.01,
        )
        mgr.register("bad")
        mgr.start()
        assert mgr.wait_idle(timeout=10)
        mgr.stop()
        assert len(attempts) == 3
        assert metrics.counter_value("recovery_gave_up") == gave_up_before + 1
        assert placeholder.status() == "failed"
        assert "operator action" in placeholder._hint()

    def test_swap_conflict_marks_failed_without_killing_thread(self):
        """A rebuilt service that cannot swap in (duplicate task) must not
        kill the recovery thread: the service goes to 'failed' (operator
        action) and other pending recoveries keep running."""
        from lumen_tpu.serving import HubRouter
        from lumen_tpu.serving.echo import EchoService
        from lumen_tpu.serving.resilience import DegradedService, RecoveryManager
        from lumen_tpu.utils.retry import RetryPolicy

        placeholder = DegradedService("bad", "boom", tasks=["b_task"])
        router = HubRouter({"a": EchoService("a"), "bad": placeholder})
        gave_up_before = metrics.counter_value("recovery_gave_up")
        mgr = RecoveryManager(
            router,
            rebuild=lambda name: EchoService("bad"),  # tasks collide with 'a'
            policy=RetryPolicy(attempts=0, base_delay_s=0.0, jitter=False),
            max_attempts=0,
            poll_interval_s=0.01,
        )
        mgr.register("bad")
        mgr.start()
        assert mgr.wait_idle(timeout=10)  # thread retires instead of dying mid-swap
        mgr.stop()
        assert metrics.counter_value("recovery_gave_up") == gave_up_before + 1
        assert router.services["bad"] is placeholder and placeholder.status() == "failed"
        assert router._route("echo") is not None  # sibling routing intact

    def test_replace_service_rolls_back_on_duplicate_task(self):
        from lumen_tpu.serving import HubRouter
        from lumen_tpu.serving.echo import EchoService
        from lumen_tpu.serving.resilience import DegradedService

        router = HubRouter(
            {"a": EchoService("a"), "b": DegradedService("b", "x", tasks=["b_task"])}
        )
        with pytest.raises(ValueError):
            router.replace_service("b", EchoService("b"))  # duplicates a's tasks
        # Old routing intact.
        assert router._route("b_task") is not None
        assert router._route("echo") is not None


# ---------------------------------------------------------------------------
# client: stream-setup retries
# ---------------------------------------------------------------------------


class _FakeRpcError(grpc.RpcError):
    def __init__(self, code):
        self._code = code

    def code(self):
        return self._code


class _FlakyStub:
    """Raises a transient RpcError (or answers an in-band wire error) on
    the first N Infer calls, then serves."""

    def __init__(self, fail_times, code=grpc.StatusCode.UNAVAILABLE, inband_code=None):
        self.fail_times = fail_times
        self.code = code
        self.inband_code = inband_code
        self.calls = 0

    def Infer(self, requests, timeout=None):  # noqa: ARG002
        list(requests)  # drain, like a real channel would
        from lumen_tpu.serving.proto import ml_service_pb2 as pb

        self.calls += 1
        if self.calls <= self.fail_times:
            if self.inband_code is None:
                raise _FakeRpcError(self.code)
            return iter(
                [
                    pb.InferResponse(
                        correlation_id="cli",
                        is_final=True,
                        error=pb.Error(code=self.inband_code, message="shed"),
                    )
                ]
            )
        return iter(
            [
                pb.InferResponse(
                    correlation_id="cli", is_final=True, result=b'{"ok": 1}', total=1
                )
            ]
        )


class TestClientRetries:
    @pytest.fixture(autouse=True)
    def _fast(self, monkeypatch):
        monkeypatch.setenv("LUMEN_CLIENT_BACKOFF_S", "0")
        monkeypatch.setenv("LUMEN_CLIENT_BACKOFF_MAX_S", "0")
        monkeypatch.setenv("LUMEN_CLIENT_RETRIES", "2")

    def test_transient_setup_failure_retried(self):
        from lumen_tpu.client import _infer

        stub = _FlakyStub(fail_times=2)
        out = _infer(stub, "echo", b"x", "text/plain", {}, timeout=5.0)
        assert out == {"ok": 1} and stub.calls == 3

    def test_non_transient_code_propagates(self):
        from lumen_tpu.client import _infer

        stub = _FlakyStub(fail_times=99, code=grpc.StatusCode.INVALID_ARGUMENT)
        with pytest.raises(grpc.RpcError):
            _infer(stub, "echo", b"x", "text/plain", {}, timeout=5.0)
        assert stub.calls == 1

    def test_exhausted_retries_propagate(self):
        from lumen_tpu.client import _infer

        stub = _FlakyStub(fail_times=99)
        with pytest.raises(grpc.RpcError):
            _infer(stub, "echo", b"x", "text/plain", {}, timeout=5.0)
        assert stub.calls == 3  # LUMEN_CLIENT_RETRIES=2 -> 3 attempts

    def test_inband_shed_retried(self):
        """A load shed / degraded answer (in-band ERROR_CODE_UNAVAILABLE)
        is the server saying 'safe to retry' — the client must."""
        from lumen_tpu.client import _infer
        from lumen_tpu.serving.proto import ml_service_pb2 as pb

        stub = _FlakyStub(fail_times=2, inband_code=pb.ERROR_CODE_UNAVAILABLE)
        out = _infer(stub, "echo", b"x", "text/plain", {}, timeout=5.0)
        assert out == {"ok": 1} and stub.calls == 3

    def test_inband_shed_exhausted_exits_with_server_message(self):
        from lumen_tpu.client import _infer
        from lumen_tpu.serving.proto import ml_service_pb2 as pb

        stub = _FlakyStub(fail_times=99, inband_code=pb.ERROR_CODE_UNAVAILABLE)
        with pytest.raises(SystemExit, match="shed"):
            _infer(stub, "echo", b"x", "text/plain", {}, timeout=5.0)
        assert stub.calls == 3

    def test_inband_permanent_error_not_retried(self):
        from lumen_tpu.client import _infer
        from lumen_tpu.serving.proto import ml_service_pb2 as pb

        stub = _FlakyStub(fail_times=99, inband_code=pb.ERROR_CODE_INVALID_ARGUMENT)
        with pytest.raises(SystemExit):
            _infer(stub, "echo", b"x", "text/plain", {}, timeout=5.0)
        assert stub.calls == 1


# ---------------------------------------------------------------------------
# router: degraded-aware unknown tasks
# ---------------------------------------------------------------------------


class TestRouterDegradedSemantics:
    def test_unknown_task_hints_degraded_services(self):
        from lumen_tpu.serving import HubRouter
        from lumen_tpu.serving.echo import EchoService
        from lumen_tpu.serving.proto import ml_service_pb2 as pb
        from lumen_tpu.serving.resilience import DegradedService

        # 'bad' failed so early it could not even declare its tasks.
        router = HubRouter(
            {"good": EchoService(), "bad": DegradedService("bad", "boom", tasks=[])}
        )
        (resp,) = router.Infer(iter([_req("mystery_task")]), None)
        assert resp.error.code == pb.ERROR_CODE_UNAVAILABLE
        assert "bad" in resp.error.message

    def test_unknown_task_without_degraded_stays_invalid(self):
        from lumen_tpu.serving import HubRouter
        from lumen_tpu.serving.echo import EchoService
        from lumen_tpu.serving.proto import ml_service_pb2 as pb

        router = HubRouter({"good": EchoService()})
        (resp,) = router.Infer(iter([_req("mystery_task")]), None)
        assert resp.error.code == pb.ERROR_CODE_INVALID_ARGUMENT
