"""Request-tracing layer tests (ISSUE 6): span recording, tail-sampling
retention rules, cross-thread span stitching through the pipelined
batcher / decode pool / ingest pipeline, gRPC metadata propagation,
Perfetto export shape, log correlation, and the disabled-path overhead
guard that lets the layer stay wired into the hot path permanently."""

import json
import logging
import threading
import time

import pytest

from lumen_tpu.utils import trace as utrace
from lumen_tpu.utils.trace import (
    Trace,
    TraceRecorder,
    perfetto_export,
)


@pytest.fixture()
def traced_env(monkeypatch):
    """Tracing on at sample=1 with a fresh recorder; cleaned up after."""
    monkeypatch.setenv("LUMEN_TRACE_SAMPLE", "1")
    utrace.reset_recorder()
    yield utrace.get_recorder()
    utrace.reset_recorder()


class TestSpanBasics:
    def test_disabled_by_default(self, monkeypatch):
        monkeypatch.delenv("LUMEN_TRACE_SAMPLE", raising=False)
        assert not utrace.enabled()
        assert utrace.begin_request("t") is None
        assert utrace.current_trace() is None
        with utrace.span("x") as h:
            assert h is None  # no-op outside a trace

    def test_sample_rate_parsing(self, monkeypatch):
        monkeypatch.setenv("LUMEN_TRACE_SAMPLE", "0.25")
        assert utrace.sample_rate() == 0.25
        monkeypatch.setenv("LUMEN_TRACE_SAMPLE", "7")  # clamped
        assert utrace.sample_rate() == 1.0
        monkeypatch.setenv("LUMEN_TRACE_SAMPLE", "bogus")  # degrade to off
        assert utrace.sample_rate() == 0.0

    def test_span_recording_and_envelope(self):
        tr = Trace("task_a")
        with tr.span("s1"):
            time.sleep(0.002)
        h = tr.begin("s2", {"k": "v"})
        time.sleep(0.001)
        h.end(extra="1")
        h.end()  # idempotent: second end records nothing
        rec = tr.to_record()
        names = [s["name"] for s in rec["spans"]]
        assert names == ["s1", "s2"]
        assert rec["spans"][1]["meta"] == {"k": "v", "extra": "1"}
        # duration is the span envelope: teardown after the last span
        # must not count.
        last_end = rec["spans"][-1]["start_ms"] + rec["spans"][-1]["dur_ms"]
        assert rec["duration_ms"] == pytest.approx(last_end, abs=0.05)

    def test_explicit_timestamps_and_error(self):
        tr = Trace("task_b", trace_id="deadbeef")
        t0 = time.perf_counter()
        tr.add_span("recv", t0 - 0.010, t0)
        tr.set_error("boom")
        tr.set_error("later")  # first error wins
        rec = tr.to_record()
        assert rec["trace_id"] == "deadbeef"
        assert rec["error"] == "boom"
        assert rec["spans"][0]["dur_ms"] == pytest.approx(10.0, rel=0.3)

    def test_contextvar_activation(self):
        tr = Trace("task_c")
        token = utrace.activate(tr)
        try:
            assert utrace.current_trace() is tr
            with utrace.span("inner"):
                pass
        finally:
            utrace.deactivate(token)
        assert utrace.current_trace() is None
        assert [s[0] for s in tr.spans] == ["inner"]


class TestTailSampling:
    def _finish(self, rec: TraceRecorder, task="t", dur_s=0.0, error=None):
        tr = Trace(task)
        tr.t0 = time.perf_counter() - dur_s  # back-date for a known duration
        tr.add_span("s", tr.t0, tr.t0 + dur_s)
        return rec.finish(tr, error=error)

    def test_errors_and_slowest_always_retained(self, monkeypatch):
        monkeypatch.setenv("LUMEN_TRACE_SAMPLE", "0.000001")
        rec = TraceRecorder(capacity=8, slow_n=2)
        rec._rng = type("R", (), {"random": staticmethod(lambda: 0.999)})()
        # Decreasing durations: the first two own the slowest-N lane and
        # every later (faster) trace is sampled out with no residue.
        for i in range(50):
            self._finish(rec, dur_s=0.001 * (50 - i))
        self._finish(rec, dur_s=0.0001, error="exploded")
        kept = rec.traces()
        # 2 slowest + the errored one survive; the other 48 leave no residue
        assert len(kept) == 3
        durs = sorted(r["duration_ms"] for r in kept)
        assert any(r.get("error") == "exploded" for r in kept)
        assert durs[-1] == pytest.approx(50.0, rel=0.3)
        assert rec.counters["finished"] == 51
        assert rec.counters["sampled_out"] == 48

    def test_sampled_in_retained(self, monkeypatch):
        monkeypatch.setenv("LUMEN_TRACE_SAMPLE", "0.5")
        rec = TraceRecorder(capacity=8, slow_n=0)
        rec._rng = type("R", (), {"random": staticmethod(lambda: 0.0)})()
        for _ in range(20):
            self._finish(rec)
        assert len(rec.traces()) == 8  # ring-bounded
        assert rec.counters["retained"] == 20

    def test_slowest_accessor(self, monkeypatch):
        monkeypatch.setenv("LUMEN_TRACE_SAMPLE", "1")
        rec = TraceRecorder(capacity=8, slow_n=4)
        for d in (0.001, 0.005, 0.002):
            self._finish(rec, dur_s=d)
        assert rec.slowest()["duration_ms"] == pytest.approx(5.0, rel=0.3)

    def test_stage_histograms_fed_for_every_trace(self, monkeypatch):
        from lumen_tpu.utils.metrics import metrics

        monkeypatch.setenv("LUMEN_TRACE_SAMPLE", "0.000001")
        rec = TraceRecorder(capacity=4, slow_n=0)
        rec._rng = type("R", (), {"random": staticmethod(lambda: 0.999)})()
        before = metrics.snapshot()["tasks"].get("stage:histest/s", {}).get("count", 0)
        for _ in range(5):
            self._finish(rec, task="histest", dur_s=0.001)
        tasks = metrics.snapshot()["tasks"]
        # Aggregates are kept for EVERY request even when the trace body
        # is sampled out of the ring.
        assert tasks["stage:histest/s"]["count"] == before + 5
        assert tasks["stage:histest/_total"]["count"] >= 5
        assert not rec.traces()


class TestDisabledOverhead:
    def test_disabled_path_under_2us(self, monkeypatch):
        """The tier-1 micro-assertion from ISSUE 6: with tracing off the
        per-request cost is a single cached env check + contextvar reads
        — small enough to stay wired into the hot path permanently."""
        monkeypatch.delenv("LUMEN_TRACE_SAMPLE", raising=False)
        utrace.sample_rate()  # warm the parse cache

        def one_request():
            # The full disabled-path footprint of one served request:
            # the dispatch gate plus the span sites it would cross.
            if utrace.enabled():
                utrace.begin_request("t")
            utrace.current_trace()  # cache.lookup site
            utrace.current_trace()  # quarantine site
            utrace.current_trace()  # decode-pool submit site
            utrace.current_trace()  # batcher submit site

        n = 20000
        best = float("inf")
        for _ in range(3):  # best-of-3 to shrug off CI scheduler noise
            t0 = time.perf_counter()
            for _ in range(n):
                one_request()
            best = min(best, (time.perf_counter() - t0) / n)
        assert best < 2e-6, f"disabled-path cost {best * 1e6:.2f}µs/request"


class TestBatcherStitching:
    def test_collect_and_device_spans_cross_threads(self, traced_env):
        from lumen_tpu.runtime.batcher import MicroBatcher

        b = MicroBatcher(lambda tree, n: tree, max_batch=4, name="trace-b").start()
        tr = utrace.begin_request("batched_task")
        token = utrace.activate(tr)
        try:
            assert b([1.0]) is not None
        finally:
            utrace.deactivate(token)
            b.close()
        utrace.finish_request(tr)
        rec = traced_env.traces()[-1]
        spans = {s["name"]: s for s in rec["spans"]}
        assert {"batch.collect", "batch.device", "batch.wake"} <= set(spans)
        # Both sides of the thread hop are recorded: collect begins on
        # this (submitting) thread and ends on the collector; the device
        # span begins on the collector and ends on the fetch worker.
        me = threading.current_thread().name
        assert spans["batch.collect"]["begin_thread"] == me
        assert spans["batch.collect"]["end_thread"] == "trace-b"
        assert spans["batch.device"]["begin_thread"] == "trace-b"
        assert spans["batch.device"]["end_thread"] == "trace-b-fetch"
        assert spans["batch.wake"]["begin_thread"] == me

    def test_error_marks_device_span(self, traced_env):
        from lumen_tpu.runtime.batcher import MicroBatcher

        def boom(tree, n):
            raise RuntimeError("device exploded")

        b = MicroBatcher(boom, max_batch=2, bisect_depth=0, name="trace-err").start()
        tr = utrace.begin_request("errored_task")
        token = utrace.activate(tr)
        try:
            with pytest.raises(RuntimeError):
                b([1.0])
        finally:
            utrace.deactivate(token)
            b.close()
        utrace.finish_request(tr, error="RuntimeError: device exploded")
        rec = traced_env.traces()[-1]
        assert rec["error"]
        spans = {s["name"]: s for s in rec["spans"]}
        assert spans["batch.device"]["meta"]["error"] == "RuntimeError"

    def test_untraced_submit_attaches_nothing(self, monkeypatch):
        from lumen_tpu.runtime.batcher import MicroBatcher

        monkeypatch.delenv("LUMEN_TRACE_SAMPLE", raising=False)
        b = MicroBatcher(lambda tree, n: tree, max_batch=2, name="trace-off").start()
        try:
            fut = b.submit([1.0])
            fut.result(timeout=10)
            assert not hasattr(fut, "_lumen_collect")
            assert not hasattr(fut, "_lumen_trace")
        finally:
            b.close()


class TestDecodePoolStitching:
    def test_queue_and_decode_spans(self, traced_env):
        from lumen_tpu.runtime.decode_pool import DecodePool

        pool = DecodePool(workers=2, name="trace-pool")
        tr = utrace.begin_request("decode_task")
        token = utrace.activate(tr)
        try:
            assert pool.run(lambda x: x + 1, 41) == 42
        finally:
            utrace.deactivate(token)
            pool.close()
        utrace.finish_request(tr)
        rec = traced_env.traces()[-1]
        spans = {s["name"]: s for s in rec["spans"]}
        assert {"decode.queue", "decode", "decode.wake"} <= set(spans)
        me = threading.current_thread().name
        assert spans["decode.queue"]["begin_thread"] == me
        assert spans["decode.queue"]["end_thread"].startswith("trace-pool")
        assert spans["decode"]["begin_thread"].startswith("trace-pool")
        assert spans["decode.wake"]["begin_thread"] == me

    def test_decode_error_marked(self, traced_env):
        from lumen_tpu.runtime.decode_pool import DecodePool

        pool = DecodePool(workers=1, name="trace-pool-err")
        tr = utrace.begin_request("decode_err")
        token = utrace.activate(tr)
        try:
            with pytest.raises(ValueError):
                pool.run(lambda: (_ for _ in ()).throw(ValueError("bad jpeg")))
        finally:
            utrace.deactivate(token)
            pool.close()
        utrace.finish_request(tr)
        rec = traced_env.traces()[-1]
        spans = {s["name"]: s for s in rec["spans"]}
        assert spans["decode"]["meta"]["error"] == "ValueError"


class TestGrpcPropagation:
    @pytest.fixture()
    def hub(self):
        import grpc
        from concurrent.futures import ThreadPoolExecutor

        from lumen_tpu.serving.proto.ml_service_pb2_grpc import (
            InferenceStub,
            add_InferenceServicer_to_server,
        )
        from lumen_tpu.serving.router import HubRouter
        from tests.test_serving_grpc import EchoService

        server = grpc.server(ThreadPoolExecutor(max_workers=4))
        router = HubRouter({"echo": EchoService("techo")})
        add_InferenceServicer_to_server(router, server)
        port = server.add_insecure_port("127.0.0.1:0")
        server.start()
        channel = grpc.insecure_channel(f"127.0.0.1:{port}")
        yield InferenceStub(channel)
        channel.close()
        server.stop(0)

    def test_metadata_roundtrip(self, traced_env, hub):
        from lumen_tpu.serving.proto import ml_service_pb2 as pb

        req = pb.InferRequest(
            correlation_id="c1", task="techo_echo", payload=b"hi",
            payload_mime="text/plain",
        )
        (resp,) = hub.Infer(iter([req]), metadata=(("lumen-trace", "cafe1234"),))
        # server echoes the propagated id back as trailing meta...
        assert resp.meta["trace_id"] == "cafe1234"
        # ...and its retained trace carries the same id + server spans.
        recs = [r for r in traced_env.traces() if r["trace_id"] == "cafe1234"]
        assert len(recs) == 1
        names = {s["name"] for s in recs[0]["spans"]}
        assert {"rpc.recv", "serialize"} <= names
        assert recs[0]["task"] == "techo_echo"

    def test_server_generates_id_without_metadata(self, traced_env, hub):
        from lumen_tpu.serving.proto import ml_service_pb2 as pb

        req = pb.InferRequest(
            correlation_id="c2", task="techo_echo", payload=b"hi",
            payload_mime="text/plain",
        )
        (resp,) = hub.Infer(iter([req]))
        assert len(resp.meta["trace_id"]) == 16  # generated hex id

    def test_error_responses_retained_as_errored_traces(self, traced_env, hub):
        from lumen_tpu.serving.proto import ml_service_pb2 as pb

        req = pb.InferRequest(correlation_id="c3", task="techo_fail", payload=b"x")
        (resp,) = hub.Infer(iter([req]), metadata=(("lumen-trace", "badbadbad"),))
        assert resp.error.message
        recs = [r for r in traced_env.traces() if r["trace_id"] == "badbadbad"]
        assert recs and recs[0]["error"]

    def test_untraced_requests_add_no_meta(self, monkeypatch, hub):
        from lumen_tpu.serving.proto import ml_service_pb2 as pb

        monkeypatch.delenv("LUMEN_TRACE_SAMPLE", raising=False)
        req = pb.InferRequest(correlation_id="c4", task="techo_echo", payload=b"hi")
        (resp,) = hub.Infer(iter([req]))
        assert "trace_id" not in resp.meta


class TestIngestTracing:
    def test_batch_trace_spans_producer_consumer_hop(self, traced_env):
        import jax
        import numpy as np

        from lumen_tpu.pipeline.ingest import IngestPipeline, Stage
        from lumen_tpu.runtime.mesh import build_mesh

        mesh = build_mesh()
        dp = mesh.shape.get("data", 1)
        batch = 4 * dp
        stage = Stage(
            name="s",
            preprocess=lambda x: np.asarray([float(x)], np.float32),
            device_fn=jax.jit(lambda t: t * 2),
        )
        pipe = IngestPipeline(mesh, [stage], batch_size=batch)
        records = pipe.run_all(list(range(batch * 2)))
        assert len(records) == batch * 2
        recs = [r for r in traced_env.traces() if r["task"] == "ingest"]
        assert len(recs) >= 2
        spans = {s["name"]: s for s in recs[0]["spans"]}
        assert {"decode", "queue", "device.dispatch", "fetch", "post"} <= set(spans)
        # The queue span hops producer -> consumer.
        assert spans["queue"]["begin_thread"] == "ingest-producer"
        assert spans["queue"]["end_thread"] != "ingest-producer"


class TestPerfettoExport:
    def _record(self):
        tr = Trace("perf_task", trace_id="abc")
        with tr.span("stage1"):
            time.sleep(0.001)
        with tr.span("stage2"):
            pass
        return tr.to_record()

    def test_chrome_trace_event_shape(self):
        doc = json.loads(json.dumps(perfetto_export([self._record()])))
        events = doc["traceEvents"]
        assert doc["displayTimeUnit"] == "ms"
        xs = [e for e in events if e["ph"] == "X"]
        ms = [e for e in events if e["ph"] == "M"]
        # envelope event + 2 spans, and thread-name metadata
        assert {e["name"] for e in xs} == {"request:perf_task", "stage1", "stage2"}
        assert all({"name", "ph", "ts", "dur", "pid", "tid"} <= set(e) for e in xs)
        assert ms and ms[0]["name"] == "thread_name"
        s1 = next(e for e in xs if e["name"] == "stage1")
        assert s1["args"]["trace_id"] == "abc"
        assert s1["dur"] >= 900  # ~1ms in µs

    def test_recorder_export_endpoints_shape(self, traced_env):
        tr = utrace.begin_request("export_task")
        with tr.span("only"):
            pass
        utrace.finish_request(tr)
        out = traced_env.export()
        assert out["enabled"] and out["sample_rate"] == 1.0
        assert out["counters"]["finished"] == 1
        assert out["traces"][0]["task"] == "export_task"
        doc = traced_env.perfetto()
        assert any(e["name"] == "request:export_task" for e in doc["traceEvents"])

    def test_http_sidecar_serves_traces(self, traced_env):
        import urllib.request

        from lumen_tpu.serving.observability import MetricsServer

        tr = utrace.begin_request("http_task")
        with tr.span("only"):
            pass
        utrace.finish_request(tr)
        srv = MetricsServer(port=0, host="127.0.0.1")
        port = srv.start()
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/traces", timeout=10
            ) as r:
                body = json.loads(r.read().decode())
            assert any(t["task"] == "http_task" for t in body["traces"])
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/traces/perfetto", timeout=10
            ) as r:
                doc = json.loads(r.read().decode())
            assert "traceEvents" in doc
        finally:
            srv.stop()


class TestLogCorrelation:
    def test_filter_injects_trace_id(self, traced_env):
        import io

        from lumen_tpu.utils.logger import TraceContextFilter, _ColorFormatter

        stream = io.StringIO()
        handler = logging.StreamHandler(stream)
        handler.addFilter(TraceContextFilter())
        handler.setFormatter(
            _ColorFormatter("%(name)s%(trace_tag)s: %(message)s")
        )
        log = logging.getLogger("trace_corr_test")
        log.addHandler(handler)
        log.setLevel(logging.INFO)
        try:
            tr = utrace.begin_request("logged_task", trace_id="feedface")
            token = utrace.activate(tr)
            try:
                log.info("inside")
            finally:
                utrace.deactivate(token)
            log.info("outside")
        finally:
            log.removeHandler(handler)
        lines = stream.getvalue().splitlines()
        assert lines[0] == "trace_corr_test [trace=feedface]: inside"
        assert lines[1] == "trace_corr_test: outside"

    def test_formatter_tolerates_foreign_records(self):
        from lumen_tpu.utils.logger import _ColorFormatter

        fmt = _ColorFormatter("%(name)s%(trace_tag)s: %(message)s")
        rec = logging.LogRecord("x", logging.INFO, "p", 1, "m", (), None)
        assert fmt.format(rec) == "x: m"


class TestParentAndSelfTime:
    def test_parent_is_the_innermost_open_span_on_the_beginning_thread(self):
        tr = Trace("nested")
        with tr.span("device.dispatch"):
            with tr.span("stage.clip"):
                time.sleep(0.001)
            with tr.span("stage.face"):
                time.sleep(0.001)
        with tr.span("fetch"):
            pass
        rec = tr.to_record()
        by_name = {s["name"]: s for s in rec["spans"]}
        names = [s["name"] for s in rec["spans"]]
        assert by_name["device.dispatch"]["parent"] is None
        assert by_name["fetch"]["parent"] is None
        for child in ("stage.clip", "stage.face"):
            assert names[by_name[child]["parent"]] == "device.dispatch"
        # self time = the span less what its children cover
        kids = sum(s["dur_ms"] for s in rec["spans"] if s["parent"] == names.index("device.dispatch"))
        assert 0 <= by_name["device.dispatch"]["dur_ms"] - kids < by_name["device.dispatch"]["dur_ms"]

    def test_a_span_begun_on_another_thread_is_top_level_there(self):
        tr = Trace("hop")
        outer = tr.begin("batch.collect")  # begins here, ends on the "collector"
        box = {}

        def collector():
            outer.end()
            box["h"] = tr.begin("batch.device")
            time.sleep(0.001)
            box["h"].end()

        t = threading.Thread(target=collector, name="collector")
        t.start()
        t.join(5)
        assert not t.is_alive()
        t0 = time.perf_counter()
        tr.add_span("decode.queue", t0, t0 + 0.001)
        tr.add_span("decode", t0 + 0.001, t0 + 0.002)  # starts where its sibling ends
        rec = tr.to_record()
        assert [s["parent"] for s in rec["spans"]] == [None] * 4
        assert {s["name"]: s["begin_thread"] for s in rec["spans"]}["batch.device"] == "collector"


class TestPhase:
    def test_phase_never_imports_jax(self):
        """``utils/trace.py`` is imported by the client and by load
        generators that must stay off JAX: ``phase`` is a no-op there."""
        import subprocess
        import sys

        code = (
            "import sys\n"
            "from lumen_tpu.utils import trace\n"
            "with trace.phase('vlm.block.dispatch', step=1, rows=4) as p:\n"
            "    assert p is None\n"
            "assert not [m for m in sys.modules if m == 'jax' or m.startswith('jax.')]\n"
        )
        out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr[-2000:]

    def test_phase_without_a_session_under_2us(self):
        """No profiler session is live: a phase is an annotation's flag
        check. Same method as the disabled-path guard above."""
        import jax  # noqa: F401 - the loaded-JAX path is the one that costs

        with utrace.phase("warm"):
            pass
        n = 20000
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(n):
                with utrace.phase("batch.stack"):
                    pass
            best = min(best, (time.perf_counter() - t0) / n)
        assert best < 2e-6, f"phase cost {best * 1e6:.2f}µs with no session"


class TestBatcherSequenceNumbers:
    def test_collect_and_device_spans_carry_the_batch_number(self, traced_env):
        from lumen_tpu.runtime.batcher import MicroBatcher

        b = MicroBatcher(lambda tree, n: tree, max_batch=4, max_latency_ms=1, name="trace-seq").start()
        try:
            for want in (1, 2):
                tr = utrace.begin_request("batched_task")
                token = utrace.activate(tr)
                try:
                    b([1.0])
                finally:
                    utrace.deactivate(token)
                utrace.finish_request(tr)
                spans = {s["name"]: s for s in traced_env.traces()[-1]["spans"]}
                assert spans["batch.collect"]["meta"]["seq"] == want
                assert spans["batch.device"]["meta"]["seq"] == want
                assert spans["batch.device"]["meta"]["batcher"] == "trace-seq"
        finally:
            b.close()


class TestBulkQueueSpan:
    def test_recv_ends_at_assembly_and_bulk_queue_follows(self, traced_env, monkeypatch):
        """On a bulk stream the wait for a ``bulk-infer`` worker is its own
        span: with one worker and a 30-ms handler, the third item's
        ``bulk.queue`` holds the two handlers before it and its
        ``rpc.recv`` holds none of them."""
        from concurrent.futures import ThreadPoolExecutor

        from lumen_tpu.serving import BaseService, TaskDefinition, TaskRegistry, base_service
        from lumen_tpu.serving.proto import ml_service_pb2 as pb

        pool = ThreadPoolExecutor(1, thread_name_prefix="bulk-one")
        monkeypatch.setattr(base_service, "_bulk_pool", pool)

        class Slow(BaseService):
            def __init__(self):
                registry = TaskRegistry("slow")
                registry.register(TaskDefinition(name="slow_echo", handler=self._echo))
                super().__init__(registry)

            def capability(self):
                return self.registry.build_capability(model_ids=["slow"], runtime="jax-cpu")

            def _echo(self, payload, mime, meta):
                time.sleep(0.03)
                return payload, "application/octet-stream", {}

        reqs = [pb.InferRequest(correlation_id=str(i), task="slow_echo", payload=b"p",
                                meta={"bulk": "1"}) for i in range(3)]
        try:
            resps = list(Slow().Infer(iter(reqs), None))
        finally:
            pool.shutdown(wait=True)
        assert len(resps) == 3 and not any(r.HasField("error") for r in resps)
        recs = sorted(traced_env.traces(), key=lambda r: r["start_unix_ms"])
        assert len(recs) == 3
        for rec in recs:
            spans = {s["name"]: s for s in rec["spans"]}
            recv, queued = spans["rpc.recv"], spans["bulk.queue"]
            assert recv["start_ms"] == 0 and recv["dur_ms"] < 20
            assert queued["start_ms"] == pytest.approx(recv["dur_ms"], abs=0.01)
            assert queued["parent"] is None
        assert {s["name"]: s for s in recs[-1]["spans"]}["bulk.queue"]["dur_ms"] >= 50

    def test_a_direct_request_has_no_bulk_queue_span(self, traced_env):
        from lumen_tpu.serving.proto import ml_service_pb2 as pb
        from tests.test_serving_grpc import EchoService

        req = pb.InferRequest(correlation_id="d1", task="techo_echo", payload=b"hi",
                              payload_mime="text/plain")
        (resp,) = EchoService("techo").Infer(iter([req]), None)
        assert not resp.HasField("error")
        names = [s["name"] for s in traced_env.traces()[-1]["spans"]]
        assert "rpc.recv" in names and "bulk.queue" not in names
