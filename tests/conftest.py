"""Test harness configuration.

All tests run on CPU with a simulated 8-device mesh so that multi-chip
sharding logic (DP/TP/SP over a ``jax.sharding.Mesh``) is exercised without
TPU hardware, mirroring the strategy described in SURVEY.md §4.
"""

import os
import sys

# Must be set before jax initializes a backend. The suite runs on the CPU
# only; what needs the chip is checked by ``chip_smoke.py``.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Some pytest entry-point plugins (jaxtyping) import jax BEFORE conftest
# runs, latching jax_platforms from the shell environment (a real TPU under
# the driver). Re-point the already-imported config at CPU; backends are
# initialized lazily, so this sticks as long as no devices were touched yet.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Repo root on sys.path so `import lumen_tpu` works without installation.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

# Result cache: OFF for the suite, unconditionally (a developer's exported
# LUMEN_CACHE_BYTES must not leak in). Tests routinely drive identical
# payload bytes at managers built from DIFFERENT random-init weights but
# identical model_info name@version — a process-global content-addressed
# cache would serve one test's results to another. Cache tests opt back in
# explicitly (monkeypatched env + reset_result_cache()).
os.environ["LUMEN_CACHE_BYTES"] = "0"
os.environ.pop("LUMEN_CACHE_DIR", None)

# Request tracing: OFF for the suite (a developer's exported
# LUMEN_TRACE_* must not leak in — traced requests allocate per-request
# and the overhead-guard test asserts the disabled path). Tracing tests
# opt back in with monkeypatched env + reset_recorder().
for _k in ("LUMEN_TRACE_SAMPLE", "LUMEN_TRACE_RING", "LUMEN_TRACE_SLOW_N"):
    os.environ.pop(_k, None)

# Capacity telemetry / SLO / flight-recorder knobs must not leak in from a
# developer's environment: a configured SLO objective would make unrelated
# serving tests trip breach transitions, and a nonstandard bucket width
# breaks the fake-clock telemetry tests' window math. The layer itself
# stays default-ON (it is always-on in production and bounded); telemetry
# tests install their own hub (install_hub) for isolation.
for _k in [k for k in os.environ if k.startswith("LUMEN_SLO_")] + [
    "LUMEN_TELEMETRY", "LUMEN_TELEMETRY_BUCKET_S", "LUMEN_TELEMETRY_RETAIN_S",
    "LUMEN_EVENTS_RING", "LUMEN_INCIDENTS_MAX", "LUMEN_INCIDENT_COOLDOWN_S",
]:
    os.environ.pop(_k, None)

# Autopilot: OFF for the suite (its own tier-1 default), plus no leaked
# threshold/drain knobs — a developer's armed controller would park
# replicas and force brownout rungs under unrelated serving tests.
# Autopilot tests opt in with monkeypatched env or explicit constructor
# args (tests/test_autopilot.py).
for _k in [k for k in os.environ if k.startswith("LUMEN_AUTOPILOT")] + [
    "LUMEN_DRAIN_S",
]:
    os.environ.pop(_k, None)

# Fleet federation: OFF for the suite — a leaked LUMEN_FED_PEERS would
# make every serve()-based test boot a peer poller (and a leaked
# LUMEN_FED_SELF would route its cache misses at phantom hosts).
# Federation tests opt in with monkeypatched env or explicit constructor
# args (tests/test_federation.py).
for _k in [k for k in os.environ if k.startswith("LUMEN_FED_")]:
    os.environ.pop(_k, None)

# Prefix KV reuse + speculative decoding: OFF for the suite (their tier-1
# defaults) — a leaked budget/K would flip the continuous engine's
# admission and decode dispatch under every parity test. Feature tests
# opt in with monkeypatched env (tests/test_vlm_continuous.py).
for _k in [
    k for k in os.environ
    if k.startswith("LUMEN_VLM_PREFIX_") or k.startswith("LUMEN_VLM_SPEC_")
]:
    os.environ.pop(_k, None)

# Decode pool: THREAD mode for the suite (LUMEN_DECODE_PROCS=0). On a
# multi-core CI host the auto default would switch the shared pool to
# process mode — correct, but every first decode would pay worker spawns
# and the suite's timing-sensitive tests (batch windows, overhead guards)
# would absorb that noise. Process-mode tests build their own pools with
# an explicit ``procs=`` (tests/test_host_lane.py).
os.environ["LUMEN_DECODE_PROCS"] = "0"

# Circuit breakers: OFF for the suite (LUMEN_BREAKER_FAILURES=0). Several
# tests drive deliberate failure bursts through serve()-built services; a
# default-on breaker would flip their expected error codes to UNAVAILABLE
# partway through. Breaker tests opt back in with explicit constructor
# args or a monkeypatched env (tests/test_fault_containment.py).
os.environ["LUMEN_BREAKER_FAILURES"] = "0"

# Persistent XLA compile cache shared across the whole suite and across
# runs (round-4 verdict item 8: >10 min of repeated CPU compiles), placed
# by the program's own helper: JAX_COMPILATION_CACHE_DIR, else
# <repo>/.jax_cache. XLA:CPU AOT-loads cached executables; the loader logs
# noisy E-level warnings about the two `prefer-no-*` pseudo-features not
# appearing in host detection — same machine, benign. Opt out with
# LUMEN_TEST_NO_COMPILE_CACHE=1 if a cache entry is ever suspect. (Last of
# the environment set-up: importing the package reads some of the above.)
if not os.environ.get("LUMEN_TEST_NO_COMPILE_CACHE"):
    from lumen_tpu.runtime.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# Compile-heavy tests (>~15s each on this 1-core host, measured full-suite
# run 2026-08-01: 511 tests, 13:47 hot-cache) are auto-marked ``slow`` so
# the default verification tier — ``pytest -m "not slow" tests/`` — stays
# under 3 minutes (round-4 verdict item 8). Everything here still runs in
# the full suite (plain ``pytest tests/``) and nothing it covers is
# default-tier-only: each entry's fast counterpart is noted.
_SLOW = (
    # full-size torch-parity forwards; arch-level parity is gated by
    # tests/test_arch_parity.py's artifact checks (fast)
    "test_clip.py::TestTorchParity",
    "test_clip.py::TestMeshServing",
    "test_clip_cn.py::TestChineseClipParity",
    # hypothesis property sweeps; example-based oracles run in test_parallel
    "test_parallel_props.py",
    # multi-step browserless UI flows; asset/module checks stay default
    "test_web.py::TestWizardFlow",
    "test_web.py::TestConfigYamlEditing",
    "test_app.py::TestHardwareApi::test_detect_reports_preset",
    "test_app.py::TestServerManagerApi",
    # full-res / full-pipeline model forwards; bucket-sized paths stay
    "test_face.py::TestDecodeMath::test_decode_detections_shapes",
    "test_ocr.py::TestModeling::test_dbnet_full_res_prob_map",
    "test_training.py",
    "test_multihost.py",
    "test_soak_grpc.py",
    "test_ingest_cli.py",
    "test_parallel.py::TestLogitScaleClamp",
    "test_parallel.py::TestMoE",
    # MoE sharded-forward coverage also lives in the driver's
    # dryrun_multichip gate, which exercises ep rules every round
    "test_parallel.py::TestMoEModelSharding",
    "test_serving_tp.py::TestVlmTensorParallelInt8",
    "test_serving_tp.py::TestVlmExpertParallel",
    "test_vlm_quant.py::TestQuantServing",
    # second pass (hot-cache tier profile, 4:42 -> target <3:00): heavy
    # manager fixtures and full-model parity forwards; each family keeps
    # a fast graph/service smoke in the default tier
    "test_clip.py::TestManager",
    "test_ocr.py::TestManager",
    "test_pipeline.py::TestPhotoCaptioning",
    "test_face.py::TestIResNet",
    "test_face.py::TestManagerPipeline",
    "test_vlm.py::TestGenerate",
    "test_vlm.py::TestDecodeParity",
    "test_golden.py::TestFaceDecodeGolden",
    "test_vlm_continuous.py::TestBatchedAdmission",
    "test_face_graph.py::TestGraphFacePipeline::test_decode_golden_parity_vs_numpy_reference",
    "test_parallel.py::TestUlyssesAttention",
    "test_parallel.py::TestRingAttention",
    "test_parallel.py::TestPipelineParallel",
    "test_vlm_quant.py::TestUntiedLmHead",
    "test_vlm_moe.py",
    "test_app.py::TestInstallOrchestrator",
    "test_app.py::TestRestParityEndpoints",
    # round-5 additions: TP-mesh compiles and double manager inits; the
    # fast QDense/pattern coverage stays default
    "test_serving_tp.py::TestClipTensorParallelInt8",
    "test_clip_quant.py::TestQuantizedManager",
    "test_clip_quant.py::TestQuantizedTowers",
    "test_ocr.py::TestNativeAngleCls",
)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "multidevice: needs >=8 simulated CPU devices; the fixture re-runs "
        "the test in a subprocess under --xla_force_host_platform_device_count=8 "
        "when the current backend cannot provide them",
    )


def pytest_collection_modifyitems(config, items):
    """Auto-mark the ``_SLOW`` list so the default tier (``-m "not slow"``)
    stays fast."""
    import pytest

    slow = pytest.mark.slow
    matched = set()
    for item in items:
        nodeid = item.nodeid.split("tests/")[-1]
        for pat in _SLOW:
            # Segment-exact: "TestMoE" must not also catch
            # "TestMoEModelSharding" (prefix matching silently dropped the
            # fast MoE sharding coverage from the default tier).
            if nodeid == pat or nodeid.startswith(pat + "::"):
                item.add_marker(slow)
                matched.add(pat)
                break
    # A stale pattern (renamed/deleted test) must fail collection loudly,
    # not silently stop tiering anything. Guard only full runs: a file- or
    # node-scoped invocation legitimately collects a subset. One excuse: a
    # pattern whose file EXISTS on disk but yielded no items at all is an
    # import-broken module running under --continue-on-collection-errors
    # (e.g. a jax version missing shard_map) — pytest reports that error
    # itself, and aborting the tolerated run here would hide it. A file
    # absent from disk (deleted/renamed) is still flagged stale.
    collected_files = {item.nodeid.split("tests/")[-1].split("::")[0] for item in items}
    here = os.path.dirname(__file__)
    unmatched = {
        p
        for p in set(_SLOW) - matched
        if p.split("::")[0] in collected_files
        or not os.path.exists(os.path.join(here, p.split("::")[0]))
    }
    if len(items) > 400 and unmatched:
        raise pytest.UsageError(f"stale _SLOW patterns in conftest: {sorted(unmatched)}")


import pytest  # noqa: E402 (fixtures below; top of file must run pre-jax)


@pytest.fixture
def multidevice(request):
    """Guarantee the test sees >= 8 CPU devices (the fleet/mesh planners
    partition ``jax.local_devices()``).

    The tier-1 suite already forces an 8-device CPU backend at the top of
    this conftest, so the common case is a no-op that returns the live
    device count. When the current backend CANNOT provide them — a dev
    shell with its own XLA_FLAGS — the test is
    re-run in a subprocess under ``JAX_PLATFORMS=cpu`` +
    ``--xla_force_host_platform_device_count=8`` and this invocation
    reports the subprocess verdict (skip on pass, fail on fail) instead
    of perturbing the live backend.
    """
    import jax

    if os.environ.get("LUMEN_MULTIDEVICE_INNER") == "1" or (
        jax.default_backend() == "cpu" and jax.device_count() >= 8
    ):
        return jax.device_count()

    import subprocess

    env = {
        **os.environ,
        "LUMEN_MULTIDEVICE_INNER": "1",
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    }
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         request.node.nodeid],
        cwd=_ROOT,
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    if proc.returncode == 0:
        pytest.skip("passed in an 8-device CPU subprocess (live backend lacks devices)")
    pytest.fail(
        f"multidevice subprocess failed (rc={proc.returncode}):\n"
        f"{(proc.stdout or '')[-2000:]}\n{(proc.stderr or '')[-1000:]}"
    )
