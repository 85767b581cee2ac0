"""Hardware detection for the control plane.

Reference equivalent: per-accelerator subprocess probes (nvidia-smi, NPU
driver checks, ``utils/env_checker.py:60-457``). On a TPU VM the authority
is JAX itself: the platform/device-kind/count of ``jax.devices()``, read in
a SUBPROCESS so the control plane never holds the TPU (initializing a
backend in-process would lock the chip away from the server it spawns).
"""

from __future__ import annotations

import json
import logging
import os
import subprocess
import sys
from dataclasses import dataclass, field

from lumen_tpu.app.presets import (
    chip_spec,
    detect_preset,
    parse_generation,
    supported_presets,
)

logger = logging.getLogger(__name__)

_PROBE = r"""
import json
try:
    import jax
    devs = jax.devices()
    print(json.dumps({
        "platform": devs[0].platform if devs else "none",
        "device_kind": devs[0].device_kind if devs else "",
        "device_count": len(devs),
        "process_count": jax.process_count(),
    }))
except Exception as e:
    print(json.dumps({"platform": "none", "device_kind": "", "device_count": 0,
                      "process_count": 0, "error": str(e)}))
"""


@dataclass
class HardwareInfo:
    platform: str  # "tpu" | "cpu" | "none"
    device_kind: str
    device_count: int
    process_count: int = 1
    cpu_count: int = 1
    memory_gb: float = 0.0
    error: str | None = None
    env: dict[str, str] = field(default_factory=dict)

    def as_dict(self) -> dict:
        return {
            "platform": self.platform,
            "device_kind": self.device_kind,
            "device_count": self.device_count,
            "process_count": self.process_count,
            "cpu_count": self.cpu_count,
            "memory_gb": round(self.memory_gb, 2),
            "error": self.error,
            "env": self.env,
        }


def _host_memory_gb() -> float:
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal"):
                    return int(line.split()[1]) / 1e6
    except OSError:
        pass
    return 0.0


def _env_declared_tpu() -> tuple[str, str, int] | None:
    """(platform, device_kind, device_count) from the TPU VM's own
    environment declaration — used when the live probe can't answer. A
    chip belongs to one process at a time and backend init BLOCKS while
    another holds it, so a probe timeout on a TPU host means 'TPU present
    but busy', not 'no TPU'."""
    accel = os.environ.get("TPU_ACCELERATOR_TYPE")
    if not accel:
        return None
    # Topology suffix carries the slice size ("v5litepod-8" -> 8); a
    # busy 8-chip slice must not get a 1-chip preset recommendation.
    count = 1
    tail = accel.rsplit("-", 1)
    if len(tail) == 2 and tail[1].isdigit():
        count = max(1, int(tail[1]))
    return "tpu", accel, count


def detect_hardware(timeout: float = 60.0) -> HardwareInfo:
    """Probe accelerators in a subprocess; never initializes a backend in
    the control-plane process. A probe that times out while the
    environment declares a TPU (another process holds the chip) still
    reports the TPU with the declared device count and the timeout
    recorded in ``error``, so preset auto-detection doesn't regress to
    the cpu tier on a momentarily-contended host."""
    probe = {"platform": "none", "device_kind": "", "device_count": 0, "process_count": 0}
    try:
        out = subprocess.run(
            [sys.executable, "-c", _PROBE],
            capture_output=True,
            text=True,
            timeout=timeout,
            env={**os.environ},
        )
        for line in (out.stdout or "").strip().splitlines():
            try:
                probe = json.loads(line)
                break
            except json.JSONDecodeError:
                continue
    except subprocess.TimeoutExpired:
        # Only a TIMEOUT means "chip busy" — a spawn failure (OSError
        # below) keeps its real message instead of a misdiagnosis.
        declared = _env_declared_tpu()
        if declared is not None:
            probe["platform"], probe["device_kind"], probe["device_count"] = declared
            probe["error"] = (
                f"live probe timed out after {timeout:.0f}s (chip busy); "
                "platform taken from environment declaration"
            )
        else:
            probe["error"] = f"probe timed out after {timeout:.0f}s"
    except OSError as e:
        probe["error"] = str(e)

    tpu_env = {
        k: v
        for k, v in os.environ.items()
        if k.startswith(("TPU_", "JAX_")) and "KEY" not in k and "TOKEN" not in k
    }
    return HardwareInfo(
        platform=probe.get("platform", "none"),
        device_kind=probe.get("device_kind", ""),
        device_count=int(probe.get("device_count", 0)),
        process_count=int(probe.get("process_count", 0) or 1),
        cpu_count=os.cpu_count() or 1,
        memory_gb=_host_memory_gb(),
        error=probe.get("error"),
        env=tpu_env,
    )


def hardware_report(hw: HardwareInfo | None = None) -> dict:
    """Detection + the preset recommendation the wizard shows."""
    hw = hw or detect_hardware()
    # The env-declared fallback reports the accelerator type
    # ("v5litepod-8") as device_kind; anything with a recognizable TPU
    # kind is a TPU.
    plat = "tpu" if hw.platform == "tpu" or parse_generation(hw.device_kind) else "cpu"
    supported = supported_presets(plat, hw.device_count, hw.device_kind)
    best = supported[0] if supported else detect_preset(plat, hw.device_count)
    generation = parse_generation(hw.device_kind)
    spec = chip_spec(generation) if generation else None
    return {
        "hardware": hw.as_dict(),
        "generation": generation,
        "chip": (
            {
                "hbm_gb": spec.hbm_gb,
                "bf16_tflops": spec.bf16_tflops,
                "slice_bf16_tflops": spec.bf16_tflops * max(hw.device_count, 1),
            }
            if spec
            else None
        ),
        "recommended_preset": best.name,
        "supported_presets": [p.name for p in supported],
    }
