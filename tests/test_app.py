"""Control-plane app tests: REST surface, WS log stream, install task
machine, server manager lifecycle. Runs fully offline — the managed-server
test uses the echo service so no model weights or TPU are needed.

pytest-asyncio isn't in the image, so each test drives its own event loop
via a small ``run_async`` helper around aiohttp's TestServer/TestClient.
"""

import asyncio
import json
import os

import pytest
import yaml

from lumen_tpu.app.api import STATE_KEY, build_app
from lumen_tpu.app.install import InstallOptions, InstallOrchestrator, StepStatus
from lumen_tpu.app.presets import PRESETS, detect_preset, supported_presets
from lumen_tpu.app.state import AppState


def run_async(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


async def make_client(app):
    from aiohttp.test_utils import TestClient, TestServer

    client = TestClient(TestServer(app))
    await client.start_server()
    return client


def with_client(fn):
    """Run ``fn(client)`` against a fresh app; closes everything after."""

    async def runner():
        client = await make_client(build_app())
        try:
            return await fn(client)
        finally:
            await client.close()

    return run_async(runner())


class TestPresets:
    def test_detect_tpu_generation_aware(self):
        # The jax device_kind string pins the generation.
        assert detect_preset("tpu", 8, "TPU v5 lite").name == "tpu_v5e_8"
        assert detect_preset("tpu", 16, "TPU v5 lite").name == "tpu_v5e_16_dp_tp"
        assert detect_preset("tpu", 1, "TPU v5 lite").name == "tpu_v5e_1"
        assert detect_preset("tpu", 8, "TPU v6 lite").name == "tpu_v6e_8"
        assert detect_preset("tpu", 8, "TPU v4").name == "tpu_v4_8"
        assert detect_preset("tpu", 8, "TPU v3").name == "tpu_v3_8"
        assert detect_preset("tpu", 8, "TPU v5p").name == "tpu_v5p_8"
        assert detect_preset("cpu", 0).name == "cpu"

    def test_known_generation_without_size_match_keeps_tpu(self):
        """v4-4 / v5p-1 etc. must still get a TPU preset (review finding:
        no regression to the float32 cpu tier)."""
        p = detect_preset("tpu", 4, "TPU v4")
        assert p.platform == "tpu" and p.chips == 4  # all 4 chips used
        p = detect_preset("tpu", 1, "TPU v5p")
        assert p.platform == "tpu" and p.chips == 1

    def test_detect_unknown_kind_falls_back_to_any(self):
        # Unknown kind string: any-TPU matching, most capable first.
        assert detect_preset("tpu", 1).platform == "tpu"
        assert detect_preset("tpu", 16).chips <= 16

    def test_detection_never_idles_chips(self):
        """Within any slice size, the detected preset uses every chip that
        some preset of that size could use (review finding: a 4-chip slice
        must not pick a 1-chip preset)."""
        from lumen_tpu.app.presets import parse_generation

        for kind in ("", "TPU v4", "TPU v5p", "TPU v5 lite", "TPU v6 lite"):
            gen = parse_generation(kind)
            for count in (1, 4, 8, 16):
                best = detect_preset("tpu", count, kind)
                same_gen = [
                    p.chips
                    for p in PRESETS.values()
                    if p.platform == "tpu" and 0 < p.chips <= count and p.generation == gen
                ]
                any_gen = [
                    p.chips
                    for p in PRESETS.values()
                    if p.platform == "tpu" and 0 < p.chips <= count
                ]
                want = max(same_gen) if same_gen else max(any_gen)
                assert best.chips == want, (kind, count, best.name)

    def test_generation_parsing(self):
        from lumen_tpu.app.presets import parse_generation

        assert parse_generation("TPU v5 lite") == "v5e"
        assert parse_generation("TPU v6 lite") == "v6e"
        assert parse_generation("TPU v5p") == "v5p"
        assert parse_generation("TPU v5") == "v5p"
        assert parse_generation("TPU v4") == "v4"
        assert parse_generation("TPU v2") == "v2"
        assert parse_generation("") is None
        assert parse_generation("NVIDIA H100") is None

    def test_supported_filters_generation(self):
        names = [p.name for p in supported_presets("tpu", 16, "TPU v5 lite")]
        assert "tpu_v5e_16_dp_tp" in names
        assert all("v6e" not in n for n in names if n != "cpu")

    def test_supported_contains_cpu_always(self):
        for plat, n in [("tpu", 4), ("cpu", 0)]:
            names = [p.name for p in supported_presets(plat, n)]
            assert "cpu" in names

    def test_presets_have_valid_mesh(self):
        for p in PRESETS.values():
            assert sum(1 for v in p.mesh_axes.values() if v == -1) <= 1

    def test_batch_scales_with_slice(self):
        assert PRESETS["tpu_v5e_8"].batch_size > PRESETS["tpu_v5e_1"].batch_size
        # tp=2 halves the data-parallel width on the 16-chip preset
        assert (
            PRESETS["tpu_v5e_16_dp_tp"].batch_size
            == PRESETS["tpu_v5e_1"].batch_size * 8
        )

    def test_chip_specs_cover_all_tpu_presets(self):
        from lumen_tpu.app.presets import chip_spec

        for p in PRESETS.values():
            if p.platform == "tpu":
                assert chip_spec(p.generation) is not None, p.name


class TestConfigApi:
    def test_generate_validate_yaml_roundtrip(self):
        async def fn(client):
            r = await client.post(
                "/api/v1/config/generate",
                json={"preset": "tpu_v5e_8", "tier": "full", "region": "other"},
            )
            assert r.status == 200
            cfg = await r.json()
            assert set(cfg["services"]) == {"clip", "face", "ocr", "vlm"}
            assert cfg["services"]["clip"]["backend_settings"]["dtype"] == "bfloat16"

            r = await client.get("/api/v1/config/current")
            assert r.status == 200

            r = await client.get("/api/v1/config/yaml")
            text = await r.text()
            parsed = yaml.safe_load(text)
            assert parsed["deployment"]["mode"] == "hub"

            r = await client.post("/api/v1/config/validate", json={"config": parsed})
            assert (await r.json())["valid"] is True
            return True

        assert with_client(fn)

    def test_generate_rejects_bad_preset_and_tier(self):
        async def fn(client):
            r = await client.post("/api/v1/config/generate", json={"preset": "nope"})
            assert r.status == 400
            # cpu preset is capped below the full tier
            r = await client.post(
                "/api/v1/config/generate", json={"preset": "cpu", "tier": "full"}
            )
            assert r.status == 400
            return True

        assert with_client(fn)

    def test_current_404_before_generate(self):
        async def fn(client):
            r = await client.get("/api/v1/config/current")
            assert r.status == 404
            return True

        assert with_client(fn)

    def test_region_cn_selects_cn_clip(self):
        async def fn(client):
            r = await client.post(
                "/api/v1/config/generate",
                json={"preset": "tpu_v5e_4", "tier": "light_weight", "region": "cn"},
            )
            cfg = await r.json()
            assert "CN-CLIP" in cfg["services"]["clip"]["models"]["clip"]["model"]
            return True

        assert with_client(fn)

    def test_presets_endpoint(self):
        async def fn(client):
            r = await client.get("/api/v1/config/presets")
            data = await r.json()
            assert "tpu_v5e_8" in data["presets"]
            assert data["tiers"] == ["minimal", "light_weight", "full"]
            return True

        assert with_client(fn)

    def test_save_writes_yaml(self, tmp_path):
        async def fn(client):
            await client.post("/api/v1/config/generate", json={"preset": "cpu"})
            path = str(tmp_path / "cfg.yaml")
            r = await client.post("/api/v1/config/save", json={"path": path})
            assert r.status == 200
            assert os.path.exists(path)
            from lumen_tpu.core.config import load_config

            cfg = load_config(path)
            assert "ocr" in cfg.services
            return True

        assert with_client(fn)


class TestHardwareApi:
    def test_tpu_platform_and_device_kind_pick_v5e_preset(self):
        """platform 'tpu' + the v5e's device_kind as JAX reports it must
        recommend the one-chip v5e preset, not cpu."""
        from lumen_tpu.app.hardware import HardwareInfo, hardware_report

        hw = HardwareInfo(platform="tpu", device_kind="TPU v5 lite", device_count=1)
        report = hardware_report(hw)
        assert report["generation"] == "v5e"
        assert report["recommended_preset"] == "tpu_v5e_1"

    def test_probe_timeout_on_declared_tpu_host_stays_tpu(self, monkeypatch):
        """Another process holding the chip blocks the probe; a host whose
        TPU_ACCELERATOR_TYPE declares a TPU must not be detected as
        cpu-only."""
        import subprocess as sp

        from lumen_tpu.app import hardware as hw_mod

        def boom(*a, **k):
            raise sp.TimeoutExpired(cmd="probe", timeout=1)

        monkeypatch.setattr(hw_mod.subprocess, "run", boom)
        monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
        hw = hw_mod.detect_hardware(timeout=1)
        assert hw.platform == "tpu"
        assert hw.device_kind == "v5litepod-4"
        assert hw.device_count == 4
        assert "busy" in (hw.error or "")
        report = hw_mod.hardware_report(hw)
        assert report["recommended_preset"] == "tpu_v5e_4"

    def test_probe_timeout_without_tpu_env_reports_none(self, monkeypatch):
        import subprocess as sp

        from lumen_tpu.app import hardware as hw_mod

        def boom(*a, **k):
            raise sp.TimeoutExpired(cmd="probe", timeout=1)

        monkeypatch.setattr(hw_mod.subprocess, "run", boom)
        monkeypatch.delenv("TPU_ACCELERATOR_TYPE", raising=False)
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        hw = hw_mod.detect_hardware(timeout=1)
        assert hw.platform == "none"

    def test_config_generate_auto_uses_probe(self, monkeypatch):
        """preset='auto' picks mesh axes + batch defaults from the
        hardware probe (VERDICT r2 item 9)."""
        import lumen_tpu.app.api as api_mod

        monkeypatch.setattr(
            api_mod, "hardware_report",
            lambda: {"recommended_preset": "tpu_v5e_16_dp_tp"},
        )

        async def fn(client):
            r = await client.post(
                "/api/v1/config/generate",
                json={"preset": "auto", "tier": "full"},
            )
            assert r.status == 200
            cfg = await r.json()
            mesh = cfg["services"]["clip"]["backend_settings"]["mesh"]["axes"]
            assert mesh == {"data": -1, "model": 2}
            return True

        assert with_client(fn)

    def test_detect_reports_preset(self):
        async def fn(client):
            r = await client.get("/api/v1/hardware/detect")
            data = await r.json()
            assert "recommended_preset" in data
            assert data["recommended_preset"] in PRESETS
            assert data["hardware"]["cpu_count"] >= 1
            return True

        assert with_client(fn)


class TestInstallOrchestrator:
    def test_full_run_offline(self):
        async def fn():
            state = AppState()
            state.bind_loop(asyncio.get_running_loop())
            orch = InstallOrchestrator(state)
            task = orch.create_task(InstallOptions(verify_imports=["json", "os"]))
            await orch.run(task)
            assert task.status == StepStatus.COMPLETED
            assert task.progress == 100
            names = [s.name for s in task.steps]
            assert names == ["check_python", "verify_imports"]
            return True

        assert run_async(fn())

    def test_failed_import_marks_task_failed(self):
        async def fn():
            state = AppState()
            state.bind_loop(asyncio.get_running_loop())
            orch = InstallOrchestrator(state)
            task = orch.create_task(
                InstallOptions(verify_imports=["definitely_not_a_module_xyz"])
            )
            await orch.run(task)
            assert task.status == StepStatus.FAILED
            assert task.error
            return True

        assert run_async(fn())

    def test_cancel_clears_cache_dir_it_created(self, tmp_path):
        async def fn():
            cache = tmp_path / "cache"
            state = AppState()
            state.bind_loop(asyncio.get_running_loop())
            orch = InstallOrchestrator(state)
            # Dir does not exist at task creation: create_task makes it and
            # stamps ownership, so cancellation wipes the partial contents
            # (reference semantics).
            task = orch.create_task(
                InstallOptions(cache_dir=str(cache), verify_imports=["time"])
            )
            assert cache.exists()  # created + owned by the task
            (cache / "partial.bin").write_bytes(b"x")
            task._cancelled = True
            await orch.run(task)
            assert task.status == StepStatus.CANCELLED
            assert not cache.exists()
            return True

        assert run_async(fn())

    def test_cancel_spares_preexisting_cache_dir(self, tmp_path):
        async def fn():
            # A request-supplied path that already existed must survive
            # cancellation: the unauthenticated control plane must not be a
            # delete-any-directory primitive (ADVICE r1).
            cache = tmp_path / "precious"
            cache.mkdir()
            (cache / "keep.bin").write_bytes(b"x")
            state = AppState()
            state.bind_loop(asyncio.get_running_loop())
            orch = InstallOrchestrator(state)
            task = orch.create_task(
                InstallOptions(cache_dir=str(cache), verify_imports=["time"])
            )
            task._cancelled = True
            await orch.run(task)
            assert task.status == StepStatus.CANCELLED
            assert (cache / "keep.bin").exists()
            return True

        assert run_async(fn())

    def test_install_api_roundtrip(self):
        async def fn(client):
            r = await client.post(
                "/api/v1/install/setup", json={"packages": []}
            )
            assert r.status == 202
            task_id = (await r.json())["task_id"]
            for _ in range(100):
                r = await client.get(f"/api/v1/install/status/{task_id}")
                data = await r.json()
                if data["status"] in ("completed", "failed"):
                    break
                await asyncio.sleep(0.1)
            assert data["status"] == "completed"
            r = await client.get("/api/v1/install/tasks")
            assert len((await r.json())["tasks"]) == 1
            return True

        assert with_client(fn)


def make_echo_config(tmp_path) -> str:
    cfg = {
        "metadata": {"version": "1.0.0", "region": "other", "cache_dir": str(tmp_path)},
        "deployment": {"mode": "hub", "services": ["echo"]},
        "server": {"port": 50999, "host": "127.0.0.1"},
        "services": {
            "echo": {
                "enabled": True,
                "package": "lumen_tpu.serving",
                "import_info": {
                    "registry_class": "lumen_tpu.serving.echo.EchoService"
                },
                "models": {"echo": {"model": "echo", "runtime": "jax"}},
            }
        },
    }
    path = tmp_path / "echo.yaml"
    path.write_text(yaml.safe_dump(cfg))
    return str(path)


class TestServerStatusBeforeStart:
    def test_status_and_metrics_before_any_start(self):
        """A fresh ServerManager must answer status/metrics/stop without a
        prior start (ADVICE r1: metrics_port was unset until first start)."""

        from lumen_tpu.app.server_manager import ServerManager

        info = ServerManager(AppState()).info()
        assert info["status"] == "stopped"
        assert info["metrics_port"] is None

        async def fn(client):
            r = await client.get("/api/v1/server/status")
            assert r.status == 200
            data = await r.json()
            assert data["status"] == "stopped"
            r = await client.get("/api/v1/metrics")
            assert r.status == 200
            r = await client.post("/api/v1/server/stop")
            assert r.status == 200
            return True

        assert with_client(fn)


class TestSessionStatus:
    """`/session/status` — the reference SessionHub's resume flow: an
    opened config is offline-checked against the cache and the endpoint
    recommends start-existing vs run-installer vs open-config."""

    def _write_config(self, tmp_path, cache_dir):
        from tests.test_core_config import make_raw

        raw = make_raw()
        raw["metadata"]["cache_dir"] = str(cache_dir)
        # No dataset requirement: the presence check then only needs the
        # declared runtime files.
        raw["services"]["clip"]["models"]["clip"].pop("dataset")
        path = tmp_path / "cfg.yaml"
        path.write_text(yaml.safe_dump(raw))
        return str(path)

    def test_recommendations(self, tmp_path):
        from tests.test_core_resources import make_model_info

        async def fn(client):
            # no config anywhere -> open_config
            r = await client.post("/api/v1/session/status", json={})
            d = await r.json()
            assert d["recommended_action"] == "open_config"

            # unparseable config path -> open_config with the reason
            bad = tmp_path / "bad.yaml"
            bad.write_text("nope: [")
            r = await client.post(
                "/api/v1/session/status", json={"config_path": str(bad)}
            )
            d = await r.json()
            assert d["config_valid"] is False
            assert d["recommended_action"] == "open_config"

            # valid config, empty cache -> run_install naming the model
            cfg_path = self._write_config(tmp_path, tmp_path / "cache")
            r = await client.post(
                "/api/v1/session/status", json={"config_path": cfg_path}
            )
            d = await r.json()
            assert d["config_valid"] is True
            assert d["ready_to_start"] is False
            assert d["recommended_action"] == "run_install"
            assert [m["model"] for m in d["models"] if not m["present"]] == ["ViT-B-32"]

            # model present with its declared files -> start_existing
            model_dir = tmp_path / "cache" / "models" / "ViT-B-32"
            model_dir.mkdir(parents=True)
            (model_dir / "model_info.json").write_text(json.dumps(make_model_info()))
            (model_dir / "model.safetensors").write_bytes(b"x")
            r = await client.post(
                "/api/v1/session/status", json={"config_path": cfg_path}
            )
            d = await r.json()
            assert d["ready_to_start"] is True
            assert d["recommended_action"] == "start_existing"
            assert d["services"] == ["clip"]
            return True

        assert with_client(fn)


@pytest.mark.integration
class TestServerManagerApi:
    def test_start_status_health_stop(self, tmp_path):
        config_path = make_echo_config(tmp_path)

        async def fn(client):
            r = await client.post(
                "/api/v1/server/start",
                json={
                    "config_path": config_path,
                    "extra_args": ["--skip-download", "--port", "0", "--metrics-port", "0"],
                },
            )
            assert r.status == 200, await r.text()
            info = await r.json()
            assert info["status"] == "running"
            assert info["port"]

            r = await client.get("/api/v1/server/status")
            status = await r.json()
            assert status["healthy"] is True
            assert status["pid"]

            # double-start conflicts
            r = await client.post(
                "/api/v1/server/start", json={"config_path": config_path}
            )
            assert r.status == 409

            # inference metrics flow: run one echo Infer against the managed
            # server, then read its latency histogram through the app
            import grpc

            from lumen_tpu.serving.proto import ml_service_pb2 as pb
            from lumen_tpu.serving.proto import ml_service_pb2_grpc

            def infer_once(port):
                with grpc.insecure_channel(f"127.0.0.1:{port}") as chan:
                    stub = ml_service_pb2_grpc.InferenceStub(chan)
                    req = pb.InferRequest(correlation_id="m1", task="echo", payload=b"hi")
                    return list(stub.Infer(iter([req]), timeout=30))

            responses = await asyncio.to_thread(infer_once, info["port"])
            assert responses and responses[-1].is_final

            r = await client.get("/api/v1/metrics")
            m = await r.json()
            assert m["server"]["metrics_port"]
            assert m["inference"]["tasks"]["echo"]["count"] >= 1

            # restart reuses the original extra_args (skip-download, port 0)
            r = await client.post("/api/v1/server/restart")
            assert r.status == 200, await r.text()
            assert (await r.json())["status"] == "running"

            r = await client.post("/api/v1/server/stop")
            assert (await r.json())["status"] == "stopped"
            return True

        assert with_client(fn)

    def test_crash_reports_exit_code_and_restart_recovers(self, tmp_path):
        """A crashed managed server must land in ``failed`` with the exit
        code recorded (the server view's crash banner reads it), and
        restart must relaunch from that state — the UI's two recovery
        affordances."""
        import signal

        config_path = make_echo_config(tmp_path)

        async def fn(client):
            r = await client.post(
                "/api/v1/server/start",
                json={
                    "config_path": config_path,
                    "extra_args": ["--skip-download", "--port", "0", "--metrics-port", "0"],
                },
            )
            assert r.status == 200, await r.text()
            status = await (await client.get("/api/v1/server/status")).json()
            os.kill(status["pid"], signal.SIGKILL)
            for _ in range(100):
                status = await (await client.get("/api/v1/server/status")).json()
                if status["status"] in ("failed", "stopped"):
                    break
                await asyncio.sleep(0.1)
            assert status["status"] == "failed"
            assert status["exit_code"] not in (None, 0)
            assert status["pid"] is None

            r = await client.post("/api/v1/server/restart")
            assert r.status == 200, await r.text()
            info = await r.json()
            assert info["status"] == "running"
            assert info["exit_code"] is None  # fresh start clears the crash

            r = await client.post("/api/v1/server/stop")
            assert (await r.json())["status"] == "stopped"
            return True

        assert with_client(fn)


class TestWsLogs:
    def test_connected_log_heartbeat_frames(self):
        async def fn(client):
            app_state = client.app[STATE_KEY]
            ws = await client.ws_connect("/ws/logs")
            first = json.loads((await ws.receive()).data)
            assert first["type"] == "connected"
            app_state.broadcast_log("hello-ws", source="test")
            got_log = got_heartbeat = False
            for _ in range(5):
                msg = json.loads((await ws.receive()).data)
                if msg["type"] == "log" and msg["message"] == "hello-ws":
                    got_log = True
                if msg["type"] == "heartbeat":
                    got_heartbeat = True
                if got_log and got_heartbeat:
                    break
            await ws.close()
            assert got_log and got_heartbeat
            return True

        assert with_client(fn)

    def test_unsubscribe_on_close(self):
        async def fn(client):
            app_state = client.app[STATE_KEY]
            ws = await client.ws_connect("/ws/logs")
            await ws.receive()  # connected
            assert app_state.subscriber_count == 1
            await ws.close()
            for _ in range(20):
                if app_state.subscriber_count == 0:
                    break
                await asyncio.sleep(0.05)
            assert app_state.subscriber_count == 0
            return True

        assert with_client(fn)


class TestEnvCheck:
    def test_environment_report_on_this_image(self):
        import sys

        from lumen_tpu.app.env_check import environment_report

        # need_gb tiny so the verdict doesn't depend on this host's free disk
        report = environment_report(cache_dir="/tmp", need_gb=0.001)
        names = {c["name"] for c in report["checks"]}
        assert {"python", "jax", "flax", "disk_space"} <= names
        by_name = {c["name"]: c for c in report["checks"]}
        # Interpreter-relative: the python check is ok exactly when THIS
        # interpreter meets the >=3.11 floor, and it is the only required
        # check whose verdict varies by image — so the aggregate ok must
        # equal it here (the rest of the stack ships in the image).
        python_ok = sys.version_info[:2] >= (3, 11)
        assert by_name["python"]["ok"] is python_ok
        assert report["ok"] is python_ok
        assert by_name["jax"]["ok"] and "jax" in by_name["jax"]["detail"]
        # Optional checks never gate ok.
        assert by_name["tpu_devices"]["required"] is False
        assert by_name["libtpu"]["required"] is False

    def test_disk_check_walks_to_existing_parent(self):
        from lumen_tpu.app.env_check import check_disk

        c = check_disk("/tmp/does/not/exist/yet", need_gb=0.001)
        assert c.ok and "/tmp" in c.detail

    def test_pip_index_by_region(self):
        from lumen_tpu.app.env_check import pip_index_url
        from lumen_tpu.app.package_resolver import PYPI_MIRROR_CN

        assert pip_index_url("cn") == PYPI_MIRROR_CN
        assert pip_index_url("other") is None
        assert pip_index_url("unknown-region") is None

    def test_hardware_check_endpoint(self):
        import sys

        async def fn(client):
            r = await client.get("/api/v1/hardware/check?cache_dir=/tmp")
            assert r.status == 200
            data = await r.json()
            # ok depends on this host's free disk; assert the structure and
            # the stack checks instead. The python check is
            # interpreter-relative (>=3.11 floor), not image-invariant.
            assert isinstance(data["ok"], bool)
            for name in ("jax", "flax", "grpcio"):
                assert any(c["name"] == name and c["ok"] for c in data["checks"])
            python_ok = sys.version_info[:2] >= (3, 11)
            assert any(
                c["name"] == "python" and c["ok"] is python_ok
                for c in data["checks"]
            )
            return True

        assert with_client(fn)

    def test_install_region_selects_mirror_flag(self):
        """region=cn routes the pip step through the mirror index; the
        default region does not (reference MirrorSelector semantics).
        _exec is stubbed to capture argv — no real pip run."""
        from lumen_tpu.app.install import InstallOptions, InstallStep, InstallTask

        async def fn():
            state = AppState()
            state.bind_loop(asyncio.get_running_loop())
            orch = InstallOrchestrator(state)
            calls = []

            async def fake_exec(task, *cmd):
                calls.append(cmd)
                return 0, ""

            orch._exec = fake_exec
            for region, expects_mirror in (("cn", True), ("other", False)):
                task = InstallTask(
                    task_id="t-" + region,
                    options=InstallOptions(packages=["einops"], region=region),
                    steps=[InstallStep("install_packages")],
                )
                await orch._step_install_packages(task, task.steps[0])
                argv = calls[-1]
                assert ("--index-url" in argv) == expects_mirror
                assert argv[-1] == "einops"
            return True

        assert run_async(fn())


class TestRestParityEndpoints:
    """The reference's remaining router surface: config load/validate-path,
    install check-path/logs, server logs (api/{config,install,server}.py)."""

    def test_config_validate_path_and_load(self, tmp_path):
        import yaml as _yaml

        from lumen_tpu.app.config_gen import config_to_yaml, generate_config

        cfg = generate_config("cpu", tier="minimal", region="other", cache_dir=str(tmp_path))
        p = tmp_path / "ok.yaml"
        p.write_text(config_to_yaml(cfg))
        bad = tmp_path / "bad.yaml"
        bad.write_text("deployment: [not, a, mapping]")

        async def fn(client):
            r = await client.post("/api/v1/config/validate-path", json={"path": str(p)})
            assert (await r.json())["valid"] is True
            r = await client.post("/api/v1/config/validate-path", json={"path": str(bad)})
            assert (await r.json())["valid"] is False
            r = await client.post("/api/v1/config/load", json={"path": str(p)})
            assert r.status == 200
            assert (await r.json())["services"] == ["ocr"]
            # loaded config becomes current
            r = await client.get("/api/v1/config/current")
            assert r.status == 200
            r = await client.post("/api/v1/config/load", json={"path": str(bad)})
            assert r.status == 400
            return True

        assert with_client(fn)

    def test_install_check_path(self, tmp_path):
        async def fn(client):
            r = await client.post(
                "/api/v1/install/check-path", json={"path": str(tmp_path / "new" / "cache")}
            )
            data = await r.json()
            assert data["ok"] is True and data["writable"] is True
            assert data["exists"] is False and data["free_gb"] > 0
            r = await client.post("/api/v1/install/check-path", json={})
            assert r.status == 400
            return True

        assert with_client(fn)

    def test_install_logs_endpoint(self):
        async def fn(client):
            r = await client.post("/api/v1/install/setup", json={})
            task_id = (await r.json())["task_id"]
            for _ in range(100):
                s = await (await client.get(f"/api/v1/install/status/{task_id}")).json()
                if s["status"] in ("completed", "failed"):
                    break
                await asyncio.sleep(0.05)
            r = await client.get(f"/api/v1/install/logs/{task_id}")
            lines = (await r.json())["lines"]
            assert any("check_python" in l for l in lines)
            r = await client.get("/api/v1/install/logs/nope")
            assert r.status == 404
            return True

        assert with_client(fn)

    def test_server_logs_endpoint(self):
        async def fn(client):
            state = client.server.app[STATE_KEY]
            state.broadcast_log("hello from the managed server", source="server")
            state.broadcast_log("app line must not appear", source="app")
            r = await client.get("/api/v1/server/logs")
            lines = (await r.json())["lines"]
            assert any("hello from the managed server" in l["message"] for l in lines)
            assert not any("app line" in l["message"] for l in lines)
            return True

        assert with_client(fn)

    def test_check_path_rejects_existing_file(self, tmp_path):
        f = tmp_path / "a-file"
        f.write_text("x")

        async def fn(client):
            r = await client.post("/api/v1/install/check-path", json={"path": str(f)})
            data = await r.json()
            assert data["ok"] is False
            # a path UNDER a file is blocked too
            r = await client.post(
                "/api/v1/install/check-path", json={"path": str(f / "sub")}
            )
            assert (await r.json())["ok"] is False
            return True

        assert with_client(fn)

    def test_logs_limit_validation(self):
        async def fn(client):
            r = await client.get("/api/v1/server/logs?limit=abc")
            assert r.status == 400
            state = client.server.app[STATE_KEY]
            state.broadcast_log("srv", source="server")
            # limit=0 means "all lines" (not "no lines").
            r = await client.get("/api/v1/server/logs?limit=0")
            lines = (await r.json())["lines"]
            assert [e["message"] for e in lines] == ["srv"]
            return True

        assert with_client(fn)
