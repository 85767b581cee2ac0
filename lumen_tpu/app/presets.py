"""Hardware presets: TPU topologies instead of GPU driver stacks.

Reference equivalent: 9 ``DeviceConfig`` classmethod presets carrying
runtime + onnx-providers + batch size + micromamba yaml + driver plans
(``lumen-app/src/lumen_app/services/config.py:41-279``) and the
``PresetRegistry`` platform-support/detection-order rules
(``utils/preset_registry.py:16-244``). Here a preset carries what a TPU
deployment actually varies on: chip generation (HBM / peak bf16 FLOPs),
slice topology, mesh axes, compute dtype, and per-service batch + latency
knobs sized to the hardware.
"""

from __future__ import annotations

from dataclasses import dataclass, field


@dataclass(frozen=True)
class ChipSpec:
    """One TPU generation, keyed by the ``device_kind`` strings JAX
    reports. Peak figures are public per-chip numbers, used for batch
    sizing here and for the MFU metrics of ``benchmark/``."""

    generation: str
    kind_patterns: tuple[str, ...]  # matched against jax device_kind, lowercased
    hbm_gb: float
    bf16_tflops: float
    base_batch: int  # comfortable per-chip batch the service knobs derive from
    #: measured per-chip CLIP batch override (None -> base_batch); only set
    #: for generations with an on-chip measurement behind the number
    clip_batch: int | None = None


# Ordered so more-specific patterns ("lite") are tested before bare "v5".
CHIP_SPECS: tuple[ChipSpec, ...] = (
    ChipSpec("v6e", ("v6 lite", "v6e"), 32.0, 918.0, base_batch=64),
    # v5e clip_batch=128: a round-3 on-chip run put the ViT-B/32 embed at
    # batch 256 / 5322 images/sec (round 3, git history; provisional provenance,
    # but the implied 23.5% MFU is exactly where this shape lands on a
    # 197-TFLOP chip), and first principles agree — batch-128 ViT-B/32
    # activations are tens of MB against 16 GB HBM, so 32 was simply
    # starving the MXU. base_batch (which face/OCR batches derive from)
    # stays conservative — those paths haven't been measured on chip yet,
    # and other generations keep the old sizing until measured.
    ChipSpec(
        "v5e", ("v5 lite", "v5litepod", "v5e"), 16.0, 197.0,
        base_batch=32, clip_batch=128,
    ),
    ChipSpec("v5p", ("v5p", "v5"), 95.0, 459.0, base_batch=96),
    ChipSpec("v4", ("v4",), 32.0, 275.0, base_batch=64),
    ChipSpec("v3", ("v3",), 32.0, 123.0, base_batch=32),
    ChipSpec("v2", ("v2",), 16.0, 46.0, base_batch=16),
)


def parse_generation(device_kind: str) -> str | None:
    """``jax.devices()[0].device_kind`` -> generation tag (None if not a
    recognized TPU string)."""
    kind = (device_kind or "").lower()
    if "tpu" not in kind and not kind.startswith("v"):
        return None
    for spec in CHIP_SPECS:
        if any(p in kind for p in spec.kind_patterns):
            return spec.generation
    return None


def chip_spec(generation: str) -> ChipSpec | None:
    for spec in CHIP_SPECS:
        if spec.generation == generation:
            return spec
    return None


@dataclass(frozen=True)
class DevicePreset:
    name: str
    description: str
    platform: str  # "tpu" | "cpu"
    generation: str | None  # chip generation (None = any / cpu)
    chips: int  # devices the mesh expects (0 = use all present)
    mesh_axes: dict[str, int] = field(default_factory=lambda: {"data": -1})
    dtype: str = "bfloat16"
    batch_size: int = 32  # headline (CLIP-class) global batch
    # Per-service knobs (reference presets carry per-device batch sizes;
    # TPU presets also size the static-shape buckets that control compile
    # count and the batching-window latency).
    face_batch: int = 16
    ocr_batch: int = 8
    ocr_det_buckets: tuple[int, ...] = (320, 640, 960)
    vlm_gen_batch: int = 4
    vlm_prefill_buckets: tuple[int, ...] = (64, 128, 256, 512)
    max_batch_latency_ms: float = 5.0
    # Service tiers this preset can comfortably run.
    max_tier: str = "full"


def _tpu_preset(
    name: str,
    generation: str,
    chips: int,
    description: str,
    mesh_axes: dict[str, int] | None = None,
    tier: str = "full",
) -> DevicePreset:
    spec = chip_spec(generation)
    assert spec is not None
    dp = chips
    if mesh_axes and "model" in mesh_axes:
        dp = max(1, chips // mesh_axes["model"])
    return DevicePreset(
        name=name,
        description=description,
        platform="tpu",
        generation=generation,
        chips=chips,
        mesh_axes=dict(mesh_axes or {"data": -1}),
        batch_size=(spec.clip_batch or spec.base_batch) * dp,
        face_batch=max(8, spec.base_batch // 2) * dp,
        ocr_batch=max(4, spec.base_batch // 4),
        vlm_gen_batch=8 if spec.hbm_gb >= 32 else 4,
        # Small-HBM chips trade the longest prompt bucket for KV headroom;
        # the manager additionally drops any bucket that cannot fit its
        # max_seq KV buffer (vlm/manager.py bucket filter).
        vlm_prefill_buckets=(
            (64, 128, 256, 512, 1024) if spec.hbm_gb >= 32 else (64, 128, 256, 512)
        ),
        max_batch_latency_ms=3.0 if spec.bf16_tflops >= 400 else 5.0,
        max_tier=tier,
    )


PRESETS: dict[str, DevicePreset] = {
    p.name: p
    for p in [
        DevicePreset(
            name="cpu",
            description="CPU-only (JAX CPU backend); correctness/dev tier",
            platform="cpu",
            generation=None,
            chips=0,
            dtype="float32",
            batch_size=4,
            face_batch=4,
            ocr_batch=2,
            vlm_gen_batch=2,
            vlm_prefill_buckets=(64, 128),
            max_tier="light_weight",
        ),
        _tpu_preset("tpu_v2_8", "v2", 8, "v2-8 board, data-parallel mesh", tier="light_weight"),
        _tpu_preset("tpu_v3_8", "v3", 8, "v3-8 board, data-parallel mesh"),
        _tpu_preset("tpu_v4_8", "v4", 8, "v4-8 slice, data-parallel mesh"),
        _tpu_preset("tpu_v5e_1", "v5e", 1, "Single v5e chip"),
        _tpu_preset("tpu_v5e_4", "v5e", 4, "v5e-4 slice, data-parallel mesh"),
        _tpu_preset("tpu_v5e_8", "v5e", 8, "v5e-8 slice, data-parallel mesh"),
        _tpu_preset(
            "tpu_v5e_16_dp_tp",
            "v5e",
            16,
            "v5e-16 pod slice, 8-way data x 2-way tensor mesh",
            mesh_axes={"data": -1, "model": 2},
        ),
        _tpu_preset("tpu_v5p_8", "v5p", 8, "v5p-8 slice, data-parallel mesh"),
        _tpu_preset("tpu_v6e_1", "v6e", 1, "Single v6e chip"),
        _tpu_preset("tpu_v6e_8", "v6e", 8, "v6e-8 slice, data-parallel mesh"),
        _tpu_preset(
            "tpu_v6e_16_dp_tp",
            "v6e",
            16,
            "v6e-16 pod slice, 8-way data x 2-way tensor mesh",
            mesh_axes={"data": -1, "model": 2},
        ),
    ]
}

# Order presets are tried during auto-detection: larger slices strictly
# before smaller ones (a 4-chip slice must never auto-pick a single-chip
# preset and idle 3 chips), newer generations first within a size.
DETECTION_ORDER = [
    "tpu_v6e_16_dp_tp",
    "tpu_v5e_16_dp_tp",
    "tpu_v6e_8",
    "tpu_v5p_8",
    "tpu_v4_8",
    "tpu_v5e_8",
    "tpu_v3_8",
    "tpu_v2_8",
    "tpu_v5e_4",
    "tpu_v6e_1",
    "tpu_v5e_1",
    "cpu",
]


def supported_presets(
    platform: str, device_count: int, device_kind: str = ""
) -> list[DevicePreset]:
    """Presets runnable on the detected hardware (reference platform-support
    matrix, ``preset_registry.py:118-170``). When the chip generation is
    recognized, only same-generation presets (plus cpu) qualify; unknown
    kinds fall back to any-TPU matching."""
    generation = parse_generation(device_kind)
    same_gen: list[DevicePreset] = []
    any_gen: list[DevicePreset] = []
    cpu: list[DevicePreset] = []
    for name in DETECTION_ORDER:
        p = PRESETS[name]
        if p.platform == "cpu":
            cpu.append(p)
        elif p.platform == platform and 0 < p.chips <= device_count:
            any_gen.append(p)
            if generation is not None and p.generation == generation:
                same_gen.append(p)
    # A recognized generation narrows the list — but a slice size with no
    # same-generation preset (e.g. v4-4) must still get a TPU preset, not
    # regress to the float32 cpu tier.
    return (same_gen or any_gen) + cpu


def detect_preset(platform: str, device_count: int, device_kind: str = "") -> DevicePreset:
    """Best preset for the hardware; falls back to cpu."""
    matches = supported_presets(platform, device_count, device_kind)
    return matches[0] if matches else PRESETS["cpu"]
