"""Host-lane tests: process-parallel decode (shared-memory arenas, crash
containment, bitwise thread/process parity) and the zero-copy
``tensor/raw`` wire path (validation gate, byte-identical results, trace
proof that the decode pool is never entered)."""

import glob
import json
import os
import time

import numpy as np
import pytest

from lumen_tpu.runtime.decode_pool import (
    DecodePool,
    decode_procs,
    decode_workers,
)
from lumen_tpu.utils import host_decode, tensorwire
from lumen_tpu.utils.deadline import QueueFull, set_deadline, reset
from lumen_tpu.utils.shm_arena import ShmArena


def _jpeg(seed=0, h=240, w=320) -> bytes:
    import cv2

    rng = np.random.default_rng(seed)
    # Smooth gradient + noise: compresses like a photo, not like static.
    base = np.linspace(0, 200, w, dtype=np.uint8)[None, :, None]
    img = np.clip(base + rng.integers(0, 40, (h, w, 3)), 0, 255).astype(np.uint8)
    ok, buf = cv2.imencode(".jpg", img)
    assert ok
    return buf.tobytes()


def _leaked_segments(pool_name: str) -> list[str]:
    return glob.glob(f"/dev/shm/lumendec_{pool_name.replace('-', '')}_*")


# ---------------------------------------------------------------------------
# worker sizing knobs
# ---------------------------------------------------------------------------

class TestSizing:
    def test_thread_default_reserves_one_core(self, monkeypatch):
        monkeypatch.delenv("LUMEN_DECODE_WORKERS", raising=False)
        monkeypatch.setattr(os, "cpu_count", lambda: 8)
        assert decode_workers() == 7
        monkeypatch.setattr(os, "cpu_count", lambda: 1)
        assert decode_workers() == 1  # floor

    def test_thread_env_override_and_malformed(self, monkeypatch):
        monkeypatch.setenv("LUMEN_DECODE_WORKERS", "3")
        assert decode_workers() == 3
        monkeypatch.setenv("LUMEN_DECODE_WORKERS", "lots")
        assert decode_workers() >= 1  # degrade-don't-crash

    def test_procs_auto_needs_more_than_two_cores(self, monkeypatch):
        monkeypatch.delenv("LUMEN_DECODE_PROCS", raising=False)
        monkeypatch.setattr(os, "cpu_count", lambda: 8)
        assert decode_procs() == 7
        monkeypatch.setattr(os, "cpu_count", lambda: 2)
        assert decode_procs() == 0  # spawn/IPC overhead buys nothing here
        monkeypatch.setattr(os, "cpu_count", lambda: 1)
        assert decode_procs() == 0

    def test_procs_env_pin(self, monkeypatch):
        monkeypatch.setenv("LUMEN_DECODE_PROCS", "0")
        assert decode_procs() == 0
        monkeypatch.setenv("LUMEN_DECODE_PROCS", "4")
        assert decode_procs() == 4


# ---------------------------------------------------------------------------
# shared-memory arena
# ---------------------------------------------------------------------------

class TestShmArena:
    def test_acquire_release_recycles_segments(self):
        arena = ShmArena(name="t1")
        try:
            a = arena.acquire(1000)
            name_a = a.name
            a.release()
            b = arena.acquire(1000)  # same size class -> same segment back
            assert b.name == name_a
            b.release()
            stats = arena.stats()
            assert stats["segments"] == 1
            assert stats["acquired"] == 2 and stats["recycled"] == 2
            assert stats["live"] == 0
        finally:
            arena.close()
        assert _leaked_segments("t1") == []

    def test_size_classes_are_pow2(self):
        arena = ShmArena(name="t2")
        try:
            small = arena.acquire(10)
            big = arena.acquire(100_000)
            assert small.capacity == 1 << 16
            assert big.capacity == 1 << 17
            small.release(), big.release()
        finally:
            arena.close()

    def test_budget_denial_spills(self):
        arena = ShmArena(name="t3", max_bytes=1 << 17)
        try:
            a = arena.acquire(1 << 16)
            b = arena.acquire(1 << 16)
            assert a is not None and b is not None
            assert arena.acquire(1 << 16) is None  # over budget -> caller spills
            assert arena.stats()["denied"] == 1
            a.release(), b.release()
        finally:
            arena.close()

    def test_double_release_is_idempotent(self):
        arena = ShmArena(name="t4")
        try:
            slot = arena.acquire(64)
            slot.release()
            slot.release()
            assert arena.stats()["recycled"] == 1
        finally:
            arena.close()

    def test_view_round_trips_pixels(self):
        arena = ShmArena(name="t5")
        try:
            slot = arena.acquire(4 * 4 * 3)
            want = np.arange(48, dtype=np.uint8).reshape(4, 4, 3)
            slot.view((4, 4, 3), np.uint8)[:] = want
            np.testing.assert_array_equal(slot.view((4, 4, 3), "|u1"), want)
            slot.release()
        finally:
            arena.close()

    def test_offset_views_pack_one_lease(self):
        """The KV spill tier lays several arrays back to back in ONE
        lease; offset views must address them without overlap."""
        arena = ShmArena(name="t6")
        try:
            a = np.arange(64, dtype=np.float32)
            b = np.ones(100, dtype=bool)
            slot = arena.acquire(a.nbytes + b.nbytes)
            slot.view(a.shape, a.dtype, offset=0)[:] = a
            slot.view(b.shape, b.dtype, offset=a.nbytes)[:] = b
            np.testing.assert_array_equal(slot.view(a.shape, a.dtype), a)
            np.testing.assert_array_equal(
                slot.view(b.shape, b.dtype, offset=a.nbytes), b
            )
            slot.release()
        finally:
            arena.close()

    def test_spill_load_balance_under_budget_pressure(self):
        """Concurrent spill-shaped traffic against a tight budget: some
        acquires are denied (callers fall back to the pickled path), the
        rest recycle, and at drain acquired == released with zero live
        leases and no leaked segments."""
        import threading

        arena = ShmArena(name="t7", max_bytes=4 << 16)  # 4 min-class slots
        try:
            def churn(seed: int) -> None:
                rng = np.random.default_rng(seed)
                for _ in range(50):
                    slot = arena.acquire(int(rng.integers(1, 1 << 16)))
                    if slot is None:
                        continue  # budget denial — the fallback path
                    slot.view((16,), np.uint8)[:] = seed
                    slot.release()

            threads = [
                threading.Thread(target=churn, args=(i,)) for i in range(8)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            stats = arena.stats()
            assert stats["live"] == 0
            assert stats["acquired"] == stats["recycled"] > 0
            assert stats["bytes"] <= 4 << 16
        finally:
            arena.close()
        assert _leaked_segments("t7") == []

    def test_sigkill_during_spill_leaves_no_segments(self):
        """A process SIGKILLed mid-spill (lease acquired, bytes half
        written, never released) must not leak /dev/shm segments: the
        multiprocessing resource tracker outlives the corpse and unlinks
        everything it registered."""
        import signal
        import subprocess
        import sys

        code = (
            "import os, signal\n"
            "import numpy as np\n"
            "from lumen_tpu.utils.shm_arena import ShmArena\n"
            "arena = ShmArena(name='sigkill')\n"
            "slots = [arena.acquire(1 << 16) for _ in range(3)]\n"
            "for s in slots:\n"
            "    s.view((64,), np.uint8)[:] = 7  # mid-write\n"
            "print('\\n'.join(s.name for s in slots), flush=True)\n"
            "os.kill(os.getpid(), signal.SIGKILL)\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert proc.returncode == -signal.SIGKILL
        names = [n for n in proc.stdout.split() if n]
        assert len(names) == 3  # the spills really were in flight
        deadline = time.time() + 30
        while time.time() < deadline:
            left = [n for n in names if os.path.exists(f"/dev/shm/{n.lstrip('/')}")]
            if not left:
                break
            time.sleep(0.2)
        assert not left, f"SIGKILL leaked shm segments: {left}"

    def test_unclosed_arena_cleans_up_at_exit(self):
        """Dropping an arena without close() (crashed owner) still unlinks
        its segments — weakref.finalize doubles as the atexit hook."""
        import subprocess
        import sys

        code = (
            "from lumen_tpu.utils.shm_arena import ShmArena\n"
            "arena = ShmArena(name='noclose')\n"
            "slot = arena.acquire(1 << 16)\n"
            "print(slot.name, flush=True)\n"
            # exit without release() or close(): finalize/atexit must run
        )
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert proc.returncode == 0, proc.stderr
        name = proc.stdout.strip()
        assert name
        assert not os.path.exists(f"/dev/shm/{name.lstrip('/')}")


# ---------------------------------------------------------------------------
# process-mode decode pool
# ---------------------------------------------------------------------------

@pytest.fixture(scope="class")
def proc_pool():
    pool = DecodePool(workers=2, name="hl-proc", procs=2)
    yield pool
    pool.close()
    assert _leaked_segments("hl-proc") == []


@pytest.fixture(scope="class")
def thread_pool():
    pool = DecodePool(workers=2, name="hl-thread", procs=0)
    yield pool
    pool.close()


class TestProcessDecode:
    def test_bitwise_parity_with_thread_mode(self, proc_pool, thread_pool):
        """Acceptance: process-mode decoded tensors are bitwise identical
        to thread mode, across the fixed-shape and provenance specs."""
        jpeg = _jpeg(1)
        for spec, params in (
            ("clip_resize", {"size": 224}),
            ("decode", {"color": "rgb"}),
            ("decode_scaled", {"max_edge": 128}),
            ("photo", {"max_edge": 128, "on_error": "record"}),
        ):
            t = thread_pool.run_decode(spec, jpeg, params)
            p = proc_pool.run_decode(spec, jpeg, params)
            try:
                assert np.array_equal(t.array, p.array), spec
                assert t.extras == p.extras, spec
            finally:
                t.release(), p.release()

    def test_map_decode_order_and_balance(self, proc_pool):
        payloads = [_jpeg(i) for i in range(5)]
        singles = [proc_pool.run_decode("decode", p) for p in payloads]
        mapped = proc_pool.map_decode("decode", payloads)
        try:
            for s, m in zip(singles, mapped):
                assert np.array_equal(s.array, m.array)
        finally:
            for r in singles + mapped:
                r.release()
        g = proc_pool.gauges()
        assert g["arena_live"] == 0
        assert g["arena_acquired"] == g["arena_recycled"]

    def test_worker_crash_is_retryable_shed_not_poison(self, proc_pool):
        """Satellite: a worker SIGKILLed mid-decode fails the item as a
        retryable shed (QueueFull -> UNAVAILABLE + retry hint on the
        wire), never a poison/quarantine verdict; the pool spawns a
        fresh worker for the next request and no shm leaks."""
        from lumen_tpu.runtime.quarantine import get_quarantine
        from lumen_tpu.serving.base_service import BaseService
        from lumen_tpu.serving.proto import ml_service_pb2 as pb

        quarantined_before = len(get_quarantine())
        with pytest.raises(QueueFull):
            proc_pool.run_decode("_test_kill", b"x")
        # The wire mapping of that exception is a retryable UNAVAILABLE,
        # not the INVALID_ARGUMENT a PoisonInput would earn — and the
        # process-wide quarantine registry must not have grown (a dead
        # worker is never a verdict on the payload).
        resp = BaseService._overload_error("c1", "clip_image_embed",
                                           QueueFull("worker died"))
        assert resp.error.code == pb.ERROR_CODE_UNAVAILABLE
        assert len(get_quarantine()) == quarantined_before
        # Arena balanced, nothing leaked, and the lane still serves.
        assert proc_pool.gauges()["arena_live"] == 0
        out = proc_pool.run_decode("decode", _jpeg(2))
        assert out.array.ndim == 3
        out.release()
        assert proc_pool.gauges()["proc_crashes"] == 1

    def test_crash_streak_downgrades_to_thread_mode(self):
        pool = DecodePool(workers=1, name="hl-streak", procs=1)
        try:
            for _ in range(3):
                with pytest.raises(QueueFull):
                    pool.run_decode("_test_kill", b"x")
            assert pool.procs == 0  # permanent downgrade
            # ...and the same spec now serves from the thread lane.
            out = pool.run_decode("decode", _jpeg(3))
            assert out.array.ndim == 3
            out.release()
        finally:
            pool.close()
        assert _leaked_segments("hl-streak") == []

    def test_undecodable_payload_raises_valueerror(self, proc_pool):
        with pytest.raises(ValueError):
            proc_pool.run_decode("decode", b"definitely not an image")

    def test_deadline_expired_in_queue(self, proc_pool):
        token = set_deadline(time.monotonic() - 0.001)
        try:
            from lumen_tpu.utils.deadline import DeadlineExpired

            with pytest.raises(DeadlineExpired):
                proc_pool.run_decode("decode", _jpeg(4))
        finally:
            reset(token)

    def test_spill_path_when_estimate_lowballs(self, proc_pool, monkeypatch):
        """An estimate that comes in under the decoded size must degrade
        to the pickled spill path — correct pixels, spill counted."""
        monkeypatch.setitem(host_decode._SPEC_EST, "decode", lambda p, _: 1)
        jpeg = _jpeg(5)
        out = proc_pool.run_decode("decode", jpeg, {"color": "rgb"})
        want = host_decode.decode_image_bytes(jpeg, color="rgb")
        try:
            assert np.array_equal(out.array, want)
        finally:
            out.release()
        assert proc_pool.gauges().get("shm_spills", 0) >= 1

    def test_trace_spans_stitch_across_the_process_hop(self, proc_pool, monkeypatch):
        """Satellite: decode.queue / decode / decode.wake report in
        process mode exactly like thread mode (worker clock stamps are
        CLOCK_MONOTONIC, stitched parent-side)."""
        from lumen_tpu.utils import trace as utrace

        monkeypatch.setenv("LUMEN_TRACE_SAMPLE", "1")
        utrace.reset_recorder()
        try:
            tr = utrace.begin_request("hl")
            token = utrace.activate(tr)
            try:
                out = proc_pool.run_decode("clip_resize", _jpeg(6), {"size": 64})
                out.release()
            finally:
                utrace.deactivate(token)
                utrace.finish_request(tr)
            rec = utrace.get_recorder().traces()[0]
            spans = {s["name"]: s for s in rec["spans"]}
            for name in ("decode.queue", "decode", "decode.wake"):
                assert name in spans, rec["spans"]
                assert spans[name]["dur_ms"] >= 0.0
            assert spans["decode"]["meta"]["proc"] == "1"
        finally:
            utrace.reset_recorder()

    def test_crop_face_owns_its_pixels(self, proc_pool, monkeypatch):
        """A full-width crop slice of an arena view is C-contiguous, so a
        copy-on-demand would hand back the VIEW — the crop must survive
        the slot being recycled and overwritten by the next decode."""
        import cv2

        from lumen_tpu.models.face.manager import FaceManager
        from lumen_tpu.runtime import decode_pool as dp_mod

        monkeypatch.setattr(dp_mod, "_shared", proc_pool)
        rng = np.random.default_rng(11)
        img_a = rng.integers(0, 255, (64, 64, 3)).astype(np.uint8)
        img_b = np.zeros((64, 64, 3), np.uint8)
        png = lambda im: cv2.imencode(".png", im[:, :, ::-1])[1].tobytes()  # noqa: E731
        crop = FaceManager.crop_face(png(img_a), np.array([0, 0, 64, 64]))
        want = crop.copy()
        # Recycle the slot with different pixels; the crop must not move.
        other = proc_pool.run_decode("decode", png(img_b))
        try:
            np.testing.assert_array_equal(crop, want)
        finally:
            other.release()
            monkeypatch.setattr(dp_mod, "_shared", None)

    def test_process_lane_feeds_the_cumulative_sums(self, proc_pool):
        """Both paths that feed the rolling ``wait_ms_p50`` feed the sums:
        a process-lane decode books one wait and the worker's own run."""
        before = proc_pool.gauges()
        out = proc_pool.map_decode("decode", [_jpeg(i) for i in range(3)])
        for r in out:
            r.release()
        after = proc_pool.gauges()
        assert after["wait_count"] == after["tasks"] == before["tasks"] + 3
        assert after["wait_ms_sum"] >= before["wait_ms_sum"]
        assert after["run_ms_sum"] > before["run_ms_sum"]

    def test_gauges_report_mode_and_arena(self, proc_pool):
        """Gauge values are numeric-only — the metrics registry drops
        strings/dicts at snapshot, and the arena invariant must survive
        onto /metrics."""
        g = proc_pool.gauges()
        assert g["process_mode"] == 1
        assert g["procs"] == 2
        assert "arena_acquired" in g and "arena_live" in g
        assert all(isinstance(v, (int, float)) for v in g.values())


# ---------------------------------------------------------------------------
# tensor/raw wire format
# ---------------------------------------------------------------------------

class TestTensorWire:
    SPEC = tensorwire.TensorSpec("uint8", (32, 32, 3))

    def _meta(self, **over):
        meta = {"dtype": "uint8", "shape": "32x32x3"}
        meta.update(over)
        return meta

    def test_spec_wire_round_trip(self):
        spec = tensorwire.TensorSpec("uint8", (None, None, 3))
        assert spec.wire() == "uint8:*x*x3"
        assert tensorwire.TensorSpec.from_wire("uint8:*x*x3") == spec

    def test_valid_tensor_passes(self):
        dtype, shape = tensorwire.validate_tensor_meta(
            self._meta(), 32 * 32 * 3, self.SPEC
        )
        assert dtype == np.uint8 and shape == (32, 32, 3)

    @pytest.mark.parametrize(
        "meta_over,nbytes,needle",
        [
            ({"dtype": ""}, 3072, "requires the 'dtype'"),
            ({"shape": ""}, 3072, "requires the 'shape'"),
            ({"dtype": "nonsense"}, 3072, "unknown tensor dtype"),
            ({"dtype": "float32"}, 32 * 32 * 3 * 4, "does not match the advertised"),
            ({"shape": "32xbogus"}, 3072, "must be integers"),
            ({"shape": "32x32"}, 2048, "does not match the advertised"),
            ({"shape": "16x16x3"}, 768, "does not match the advertised"),
        ],
    )
    def test_invalid_meta_messages(self, meta_over, nbytes, needle):
        with pytest.raises(ValueError, match=needle):
            tensorwire.validate_tensor_meta(self._meta(**meta_over), nbytes, self.SPEC)

    def test_byte_length_mismatch(self):
        with pytest.raises(ValueError, match="needs 3072"):
            tensorwire.validate_tensor_meta(self._meta(), 3000, self.SPEC)

    def test_huge_dims_cannot_wrap_past_the_length_check(self):
        """Attacker-chosen dims whose int64 product wraps to 0 must still
        fail the byte-length check (math.prod is arbitrary precision)."""
        spec = tensorwire.TensorSpec("uint8", (None, None, 3))
        meta = {"dtype": "uint8", "shape": f"{2**32}x{2**32}x3"}  # 3*2^64 ≡ 0 mod 2^64
        with pytest.raises(ValueError, match="needs"):
            tensorwire.validate_tensor_meta(meta, 0, spec)

    def test_payload_round_trip_is_lossless(self):
        arr = np.random.default_rng(0).integers(0, 255, (7, 5, 3)).astype(np.uint8)
        buf, meta = tensorwire.tensor_payload(arr)
        back = tensorwire.tensor_from_payload(bytes(buf), meta)
        np.testing.assert_array_equal(back, arr)

    def test_wildcard_dims_accept_any_extent(self):
        spec = tensorwire.TensorSpec("uint8", (None, None, 3))
        meta = {"dtype": "uint8", "shape": "480x640x3"}
        tensorwire.validate_tensor_meta(meta, 480 * 640 * 3, spec)

    def test_client_requests_carry_tensor_meta(self):
        from lumen_tpu.client import _requests, _tensor_item

        arr = np.random.default_rng(1).integers(0, 255, (8, 8, 3)).astype(np.uint8)
        payload, mime, meta = _tensor_item(arr, {})
        assert mime == tensorwire.TENSOR_MIME
        reqs = list(_requests("clip_image_embed", payload, mime, meta))
        assert len(reqs) == 1
        r = reqs[0]
        assert r.payload_mime == tensorwire.TENSOR_MIME
        assert dict(r.meta)["shape"] == "8x8x3"
        np.testing.assert_array_equal(
            np.frombuffer(r.payload, np.uint8).reshape(8, 8, 3), arr
        )

    def test_client_chunked_tensor_single_copy_path(self):
        from lumen_tpu.client import _requests, _tensor_item

        big = np.zeros((1200, 1200, 3), np.uint8)  # > 1 MiB -> chunked
        big[0, 0] = (1, 2, 3)
        payload, mime, meta = _tensor_item(big, {})
        reqs = list(_requests("t", payload, mime, meta))
        assert len(reqs) > 1
        joined = b"".join(r.payload for r in reqs)
        np.testing.assert_array_equal(
            np.frombuffer(joined, np.uint8).reshape(big.shape), big
        )
        assert all(r.payload_mime == tensorwire.TENSOR_MIME for r in reqs)


# ---------------------------------------------------------------------------
# tensor/raw end-to-end: CLIP + face over a real gRPC server
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def clip_grpc(tmp_path_factory):
    import grpc

    from lumen_tpu.models.clip.manager import CLIPManager
    from lumen_tpu.serving.proto.ml_service_pb2_grpc import (
        InferenceStub,
        add_InferenceServicer_to_server,
    )
    from lumen_tpu.serving.services.clip_service import ClipService
    from tests.clip_fixtures import make_clip_model_dir

    from concurrent.futures import ThreadPoolExecutor

    tmp = tmp_path_factory.mktemp("hl_clip")
    mgr = CLIPManager(
        make_clip_model_dir(tmp, with_dataset=False),
        dtype="float32", batch_size=4, max_batch_latency_ms=2.0,
    )
    svc = ClipService({"clip": mgr})
    mgr.initialize()
    server = grpc.server(ThreadPoolExecutor(max_workers=4))
    add_InferenceServicer_to_server(svc, server)
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    channel = grpc.insecure_channel(f"127.0.0.1:{port}")
    yield InferenceStub(channel), svc, mgr
    channel.close()
    server.stop(0)
    svc.close()


class TestTensorEndToEndClip:
    def test_capability_advertises_tensor_spec(self, clip_grpc):
        _, svc, mgr = clip_grpc
        cap = svc.capability()
        extra = dict(cap.extra)
        assert extra["tensor_input:clip_image_embed"] == "uint8:32x32x3"
        embed = next(t for t in cap.tasks if t.name == "clip_image_embed")
        assert tensorwire.TENSOR_MIME in list(embed.input_mimes)

    def test_tensor_result_byte_identical_to_jpeg_path(self, clip_grpc):
        """Acceptance: client.infer(ndarray) == the JPEG path byte for
        byte, with trace proof the decode pool was never entered."""
        from lumen_tpu.client import infer
        from lumen_tpu.utils import trace as utrace

        stub, svc, mgr = clip_grpc
        jpeg = _jpeg(7, h=100, w=80)
        # The exact tensor the server's own decode would produce:
        pixels = host_decode._SPECS["clip_resize"](jpeg, {"size": 32})

        os.environ["LUMEN_TRACE_SAMPLE"] = "1"
        utrace.reset_recorder()
        try:
            via_jpeg = infer(stub, "clip_image_embed", jpeg, mime="image/jpeg")
            via_tensor = infer(stub, "clip_image_embed", pixels)
        finally:
            os.environ.pop("LUMEN_TRACE_SAMPLE", None)
        assert via_tensor == via_jpeg  # identical parsed JSON == same bytes
        assert via_tensor["vector"] == via_jpeg["vector"]

        # Trace proof: the JPEG request decoded; the tensor request shows
        # no decode/decode.queue span anywhere in its trace. The server
        # records a trace at stream teardown, which can land a beat after
        # the client saw its final message — poll briefly.
        deadline = time.monotonic() + 5.0
        while True:
            recs = utrace.get_recorder().traces()
            server_recs = [r for r in recs if r["task"] == "clip_image_embed"]
            if len(server_recs) >= 2 or time.monotonic() > deadline:
                break
            time.sleep(0.05)
        assert len(server_recs) == 2
        by_decode = {
            any(s["name"].startswith("decode") for s in r["spans"]): r
            for r in server_recs
        }
        assert True in by_decode and False in by_decode
        utrace.reset_recorder()

    def test_invalid_tensor_answers_invalid_argument(self, clip_grpc, monkeypatch):
        """Satellite: wrong dtype/shape/length -> INVALID_ARGUMENT with a
        precise message; the manager (and therefore batcher/cache) is
        never touched."""
        import grpc as _grpc

        stub, svc, mgr = clip_grpc
        calls = []
        monkeypatch.setattr(
            mgr, "encode_image_tensor",
            lambda *a, **k: calls.append(1) or (_ for _ in ()).throw(AssertionError),
        )
        arr = np.zeros((16, 16, 3), np.uint8)  # wrong H/W for the 32px spec
        buf, meta = tensorwire.tensor_payload(arr)
        from lumen_tpu.serving.proto import ml_service_pb2 as pb

        req = pb.InferRequest(
            correlation_id="bad", task="clip_image_embed",
            payload=bytes(buf), payload_mime=tensorwire.TENSOR_MIME, meta=meta,
        )
        resps = list(stub.Infer(iter([req])))
        assert len(resps) == 1
        err = resps[0].error
        assert err.code == pb.ERROR_CODE_INVALID_ARGUMENT
        assert "does not match the advertised" in err.message
        assert "uint8:32x32x3" in err.message
        assert not calls

    def test_wrong_byte_length_named_precisely(self, clip_grpc):
        stub, svc, mgr = clip_grpc
        from lumen_tpu.serving.proto import ml_service_pb2 as pb

        req = pb.InferRequest(
            correlation_id="short", task="clip_image_embed",
            payload=b"\x00" * 100, payload_mime=tensorwire.TENSOR_MIME,
            meta={"dtype": "uint8", "shape": "32x32x3"},
        )
        resps = list(stub.Infer(iter([req])))
        assert resps[0].error.code == pb.ERROR_CODE_INVALID_ARGUMENT
        assert "100 bytes" in resps[0].error.message
        assert "needs 3072" in resps[0].error.message

    def test_task_without_tensor_spec_rejects_mime(self, clip_grpc):
        stub, svc, mgr = clip_grpc
        from lumen_tpu.serving.proto import ml_service_pb2 as pb

        req = pb.InferRequest(
            correlation_id="t", task="clip_text_embed",
            payload=b"\x00" * 12, payload_mime=tensorwire.TENSOR_MIME,
            meta={"dtype": "uint8", "shape": "2x2x3"},
        )
        resps = list(stub.Infer(iter([req])))
        assert resps[0].error.code == pb.ERROR_CODE_INVALID_ARGUMENT
        assert "does not accept tensor/raw" in resps[0].error.message

    def test_tensor_cache_hits_on_raw_buffer_single_hash(self, clip_grpc, monkeypatch):
        """Satellite: tensor/raw payloads are cached keyed on sha256 of
        the raw buffer, hashed exactly once per request; an identical
        re-send answers from cache (cache_hit meta) without touching the
        batcher."""
        from lumen_tpu.runtime import result_cache as rc_mod
        from lumen_tpu.runtime.result_cache import reset_result_cache

        stub, svc, mgr = clip_grpc
        monkeypatch.setenv("LUMEN_CACHE_BYTES", str(16 << 20))
        reset_result_cache()
        counts = {"n": 0}
        real_make_key = rc_mod.make_key

        def counting_make_key(ns, options, payload):
            counts["n"] += 1
            return real_make_key(ns, options, payload)

        # guarded_key resolves make_key through the result_cache module
        # attribute at call time, so one patch covers both gates.
        monkeypatch.setattr(rc_mod, "make_key", counting_make_key)
        try:
            pixels = host_decode._SPECS["clip_resize"](_jpeg(8, h=90, w=90), {"size": 32})
            from lumen_tpu.client import _tensor_item
            from lumen_tpu.serving.proto import ml_service_pb2 as pb

            payload, mime, meta = _tensor_item(pixels, {})

            def send(cid):
                req = pb.InferRequest(
                    correlation_id=cid, task="clip_image_embed",
                    payload=bytes(payload), payload_mime=mime, meta=meta,
                )
                return list(stub.Infer(iter([req])))[0]

            counts["n"] = 0
            cold = send("cold")
            assert counts["n"] == 1  # ONE hash for quarantine gate + cache
            warm = send("warm")
            assert warm.result == cold.result
            assert dict(warm.meta).get("cache_hit") == "1"
        finally:
            reset_result_cache()

    def test_bulk_tensors_round_trip(self, clip_grpc):
        from lumen_tpu.client import infer_bulk

        stub, svc, mgr = clip_grpc
        tensors = [
            host_decode._SPECS["clip_resize"](_jpeg(20 + i, h=64, w=64), {"size": 32})
            for i in range(3)
        ]
        results = dict(infer_bulk(stub, "clip_image_embed", tensors=tensors))
        assert set(results) == {0, 1, 2}
        for i, res in results.items():
            data, mime, meta = res
            out = json.loads(data)
            assert len(out["vector"]) == 32


@pytest.fixture(scope="module")
def face_grpc(tmp_path_factory):
    import grpc

    from concurrent.futures import ThreadPoolExecutor

    from lumen_tpu.models.face import FaceManager
    from lumen_tpu.serving.proto.ml_service_pb2_grpc import (
        InferenceStub,
        add_InferenceServicer_to_server,
    )
    from lumen_tpu.serving.services.face_service import FaceService
    from tests.test_face import make_face_model_dir

    tmp = tmp_path_factory.mktemp("hl_face")
    model_dir, det_cfg, rec_cfg = make_face_model_dir(tmp)
    mgr = FaceManager(
        model_dir, dtype="float32", batch_size=4,
        detector_cfg=det_cfg, embedder_cfg=rec_cfg,
    )
    mgr.initialize()
    svc = FaceService(mgr)
    server = grpc.server(ThreadPoolExecutor(max_workers=4))
    add_InferenceServicer_to_server(svc, server)
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    channel = grpc.insecure_channel(f"127.0.0.1:{port}")
    yield InferenceStub(channel), svc, mgr
    channel.close()
    server.stop(0)
    svc.close()


class TestTensorEndToEndFace:
    def test_capability_advertises_wildcard_spec(self, face_grpc):
        _, svc, mgr = face_grpc
        extra = dict(svc.capability().extra)
        assert extra["tensor_input:face_detect"] == "uint8:*x*x3"
        assert extra["tensor_input:face_detect_and_embed"] == "uint8:*x*x3"

    def test_face_tensor_byte_identical_to_jpeg_path(self, face_grpc):
        """Acceptance (face half): detect via tensor == detect via image
        bytes for the same pixels. The source image is small enough that
        scaled decode never engages, so the JPEG path's decoded pixels
        are exactly the tensor we send."""
        from lumen_tpu.client import infer

        stub, svc, mgr = face_grpc
        import cv2

        rng = np.random.default_rng(9)
        img = rng.integers(0, 255, (96, 96, 3)).astype(np.uint8)
        # imencode reads BGR; the server decodes to RGB — encode the
        # swapped view so the lossless decode reproduces `img` exactly.
        ok, buf = cv2.imencode(".png", img[:, :, ::-1])
        assert ok
        png = buf.tobytes()
        np.testing.assert_array_equal(
            host_decode.decode_image_bytes(png, color="rgb"), img
        )

        via_bytes = infer(stub, "face_detect", png, mime="image/png")
        via_tensor = infer(stub, "face_detect", img)
        assert via_tensor == via_bytes


# ---------------------------------------------------------------------------
# ingest: process-parallel decode with lease hygiene
# ---------------------------------------------------------------------------

@pytest.mark.multichip
class TestIngestProcessDecode:
    def test_process_decode_matches_thread_and_balances_arena(self, monkeypatch):
        import jax

        from lumen_tpu.pipeline import IngestPipeline, Stage
        from lumen_tpu.runtime import decode_pool as dp_mod
        from lumen_tpu.runtime.mesh import build_mesh

        mesh = build_mesh({"data": -1})
        stage = Stage(
            name="sum",
            preprocess=lambda d: np.asarray(
                [np.asarray(d["img"], np.float32).sum()], np.float32
            ),
            device_fn=jax.jit(lambda x: x),
            postprocess=lambda d, row: float(row[0]),
        )

        def build(pipe_pool):
            monkeypatch.setattr(dp_mod, "_shared", pipe_pool)
            return IngestPipeline(
                mesh, [stage],
                decode=lambda item: {
                    "img": host_decode.decode_image_bytes(item, color="rgb"),
                    "meta": {},
                },
                batch_size=8,
                decode_spec=("photo", {"max_edge": 0, "on_error": "record"}),
                decode_adapter=lambda r: {"img": r.array, "meta": {}},
            )

        items = [_jpeg(40 + i, h=60, w=60) for i in range(10)]
        tpool = DecodePool(workers=2, name="hl-ing-t", procs=0)
        try:
            thread_records = build(tpool).run_all(items)
        finally:
            monkeypatch.setattr(dp_mod, "_shared", None)
            tpool.close()
        ppool = DecodePool(workers=2, name="hl-ing-p", procs=2)
        try:
            proc_records = build(ppool).run_all(items)
            g = ppool.gauges()
            assert g["arena_live"] == 0, g
            assert g["arena_acquired"] == g["arena_recycled"] > 0
        finally:
            monkeypatch.setattr(dp_mod, "_shared", None)
            ppool.close()
        assert [r["sum"] for r in proc_records] == [r["sum"] for r in thread_records]
        assert _leaked_segments("hl-ing-p") == []

    def test_worker_crash_falls_back_to_thread_decode(self, monkeypatch):
        """A decode-worker crash mid-chunk must not abort a bulk run: the
        chunk re-decodes on the thread lane (via the ``decode`` callable)
        and the run completes with real records."""
        import jax

        from lumen_tpu.pipeline import IngestPipeline, Stage
        from lumen_tpu.runtime import decode_pool as dp_mod
        from lumen_tpu.runtime.mesh import build_mesh

        mesh = build_mesh({"data": -1})
        stage = Stage(
            name="n",
            preprocess=lambda d: np.asarray([float(len(d["img"]))], np.float32),
            device_fn=jax.jit(lambda x: x),
            postprocess=lambda d, row: float(row[0]),
        )
        pool = DecodePool(workers=2, name="hl-ing-crash", procs=1)
        monkeypatch.setattr(dp_mod, "_shared", pool)
        try:
            pipe = IngestPipeline(
                mesh, [stage],
                decode=lambda item: {"img": np.frombuffer(item, np.uint8), "meta": {}},
                batch_size=8,
                decode_spec=("_test_kill", {}),  # every proc decode dies
                decode_adapter=lambda r: {"img": r.array, "meta": {}},
            )
            records = pipe.run_all([b"abc", b"defg", b"hi"])
            assert [r["n"] for r in records] == [3.0, 4.0, 2.0]
        finally:
            monkeypatch.setattr(dp_mod, "_shared", None)
            pool.close()
