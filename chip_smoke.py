#!/usr/bin/env python3
"""The quickest proof that the serving path still starts on the chip.

    python chip_smoke.py            # one TPU chip: device, kernels, serve
    python chip_smoke.py --chips 4  # four chips: the data-parallel CLIP path only

One process, the only one that touches the chip. It writes seeded
random-weight model directories at published widths (CLIP ViT-B/32;
Qwen2-0.5B at full depth and vocabulary with the 1024-px tower) into a
temporary directory, boots the hub from the ``tpu_v5e_1`` preset through
``serve()``, drives it over real gRPC on localhost, and checks what comes
back against references computed on the same device. Every phase prints
one JSON object; a phase that fails ends the run with ``"ok": false`` and a
non-zero exit code. The last line of a good run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

It is a smoke, not a benchmark: the seconds and rates it prints are
information about this run, never claims. Without a TPU it fails in the
``device`` phase — there is no CPU continuation. ``--rehearse`` is a
switch of this script (not of the program) for finding wrong paths and
arguments in a sandbox: tiny sizes on whatever backend JAX has, Pallas
kernels in interpret mode through the test switches that already exist.
"""

from __future__ import annotations

import argparse
import io
import json
import logging
import os
import shutil
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

#: Served bf16 CLIP vectors against an f32 ``model.apply`` of the same
#: pixels: bf16 keeps 8 mantissa bits through 12 layers, which moves a
#: unit vector by well under a degree; a wrong weight, a wrong resize or a
#: mixed-up batch row lands near cosine 0.
CLIP_MIN_COSINE = 0.99
#: Data-parallel against one device: the same bf16 program at another
#: batch shape — only reduction order may differ.
DP_MIN_COSINE = 0.999
#: A kernel against its XLA reference on bf16 inputs and outputs: one bf16
#: ulp is 2^-8 = 3.9e-3 relative, and the MXU's passes over an f32 operand
#: may round the softmax weights to bf16 once more. The f32 bound that
#: bf16 compute cannot meet is held in tier-1 (tests/test_paged_attention.py).
KERNEL_TOL = 2e-2
#: A served greedy token, scored by an f32 teacher-forced forward of the
#: same sequence: its logit may sit this many standard deviations of the
#: position's logits below the maximum (bf16 flips near-ties; an unrelated
#: token sits ~4.5 below at a 151,936-word vocabulary).
VLM_MAX_LOGIT_GAP_STD = 1.0


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


@dataclass(frozen=True)
class Sizes:
    """What a run drives: the published sizes, or the rehearsal's."""

    clip: str  # lumen_tpu.testing.model_dirs.write_clip_dir size
    vlm_tiny: bool
    kernel_batch: int
    kernel_pages: int  # block-table width (pages of the serving default a row)
    kernel_seq: int  # KV length of the flash / ragged cases
    clip_images: int
    clip_streams: int
    vlm_requests: int
    vlm_new_tokens: int
    dp_images: int
    jpeg_edge: int


REAL = Sizes(
    clip="vitb32", vlm_tiny=False, kernel_batch=8, kernel_pages=32, kernel_seq=2048,
    clip_images=64, clip_streams=16, vlm_requests=8, vlm_new_tokens=32, dp_images=256,
    jpeg_edge=256,
)
REHEARSAL = Sizes(
    clip="tiny", vlm_tiny=True, kernel_batch=2, kernel_pages=8, kernel_seq=512,
    clip_images=16, clip_streams=4, vlm_requests=3, vlm_new_tokens=8, dp_images=32,
    jpeg_edge=64,
)


# -- device -----------------------------------------------------------------


def phase_device(args) -> dict:
    import jax

    devices = jax.devices()  # a backend that cannot start raises here
    first = devices[0]
    device = {"platform": first.platform, "kind": first.device_kind, "count": len(devices)}
    if not args.rehearse:
        check(first.platform == "tpu", f"JAX found no TPU: {device}")
    check(
        len(devices) == args.chips,
        f"--chips {args.chips} but JAX reports {len(devices)} device(s)",
    )

    from lumen_tpu import native
    from lumen_tpu.runtime import enable_persistent_cache
    from lumen_tpu.runtime.decode_pool import get_decode_pool

    cache_dir = enable_persistent_cache()
    lib = native.provenance()
    check(
        lib["compiler"] is None or lib["digest_keyed"],
        f"a compiler is present but the native library is not the digest-keyed one: {lib}",
    )
    pool = get_decode_pool()
    emit(
        "device", ok=True, **device, jax=jax.__version__,
        compile_cache={
            "dir": cache_dir,
            "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        },
        native_host_ops=lib,
        decode_lane={
            "mode": "processes" if pool.process_mode else "threads",
            "procs": pool.procs, "threads": pool.workers,
        },
    )
    return device


class JaxEvents:
    """JAX's own monitoring events in this process: persistent-cache hits
    and misses, and the name of every program handed to the compiler."""

    def __init__(self):
        from jax import monitoring

        self.hits = self.misses = 0
        self.compiled: list[str] = []
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event: str, _secs: float, fun_name: str = "?", **_) -> None:
        if event.endswith("backend_compile_duration"):
            self.compiled.append(fun_name)


def xla_compiles() -> int:
    from lumen_tpu.utils.metrics import metrics

    return int(metrics.snapshot()["counters"].get("xla_compiles", 0))


# -- kernels ----------------------------------------------------------------


def phase_kernels(sizes: Sizes, rehearse: bool, seed: int) -> None:
    """Each Pallas kernel, compiled (interpret mode only in a rehearsal), at
    Qwen2-0.5B widths against its XLA reference on the same device; then
    the XLA routes that earlier on-chip tests covered."""
    import importlib

    import jax
    import jax.numpy as jnp

    att = importlib.import_module("lumen_tpu.ops.attention")
    from lumen_tpu.models.vlm.paged_kv import DEFAULT_PAGE_SIZE as page
    from lumen_tpu.ops import quant_matmul

    interpret = rehearse
    rng = np.random.default_rng(seed)
    b, heads, kv_heads, dh = sizes.kernel_batch, 14, 2, 64
    maxp, seq = sizes.kernel_pages, sizes.kernel_seq
    bf16 = jnp.bfloat16

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), bf16)

    n_pages = b * maxp + 1
    k_pages, v_pages = normal(n_pages, kv_heads, page, dh), normal(n_pages, kv_heads, page, dh)
    tables = jnp.asarray(rng.permutation(n_pages - 1)[: b * maxp].reshape(b, maxp) + 1, jnp.int32)
    window = 5
    kv_lens = jnp.asarray(rng.integers(1, maxp * page - window, size=b), jnp.int32)
    chunk = min(256, seq // 2)
    kv_valid = jnp.asarray(rng.integers(chunk, seq + 1, size=b), jnp.int32)
    k_seq, v_seq = normal(b, heads, seq, dh), normal(b, heads, seq, dh)
    hidden, mlp = 896, 4864
    w8 = jnp.asarray(rng.integers(-127, 128, size=(hidden, mlp)), jnp.int8)
    w_scale = jnp.asarray(rng.uniform(0.5, 1.5, size=mlp) / 127.0, jnp.float32)

    # the Mamba-2 kernels at granite-4.0-h-small's dimensions (128 heads of 64,
    # d_state 128): a 320-token prompt's scan from a state already there, a
    # padded tail that must not move it; sixteen slots of which nine step
    from lumen_tpu.ops import ssm

    mh, mp, mn, mseq, mslots = (8, 16, 32, 40, 4) if rehearse else (128, 64, 128, 320, 16)
    dt = jnp.asarray(np.log1p(np.exp(rng.standard_normal((mslots, mseq, mh)) - 3.0)), jnp.float32)
    dt = dt.at[0, -7:].set(0.0)
    a_neg = jnp.asarray(-np.exp(rng.standard_normal(mh)), jnp.float32)
    d_skip = jnp.asarray(rng.standard_normal(mh), jnp.float32)
    m_state = jnp.asarray(rng.standard_normal((mslots, mn, mh * mp)), jnp.float32)
    m_x, m_b, m_c = normal(mslots, mseq, mh * mp), normal(mslots, mseq, mn), normal(mslots, mseq, mn)
    stepping = jnp.asarray(np.arange(mslots) % 16 < 9)
    flat = lambda y, state: jnp.concatenate([y.astype(jnp.float32).ravel(), state.ravel()])

    # the latent decode kernel as a decoder without indexer or window calls it
    # (A.X-K1: 64 heads over a 512-value latent and a 64-value position key):
    # sixteen rows, every page of each row's table, no selection, start 0
    from lumen_tpu.ops import latent_attention as lat

    lh, lc, lr, lrows, lmaxp = (4, 32, 8, 3, 6) if rehearse else (64, 512, 64, 16, 72)
    l_pages = lrows * lmaxp + 1
    l_tables = jnp.asarray(rng.permutation(l_pages - 1)[: lrows * lmaxp].reshape(lrows, lmaxp) + 1, jnp.int32)
    l_lens = jnp.asarray(rng.integers(1, lmaxp * page, size=lrows), jnp.int32)

    # (name, kernel, reference, arguments, holds a tpu_custom_call)
    cases = [
        ("latent_paged_all_keys",
         lambda *a: lat.latent_paged_attention_kernel(*a, None, scale=0.13, interpret=interpret),
         lambda *a: lat.latent_paged_attention_reference(*a, None, scale=0.13),
         (normal(lrows, lh, lc), normal(lrows, lh, lr), normal(l_pages, page, lc), normal(l_pages, page, lr),
          l_tables, l_lens, jnp.zeros((lrows,), jnp.int32)), True),
        ("ssd_chunk_scan",
         lambda *a: flat(*ssm.ssd_chunk_scan_kernel(*a, interpret=interpret)),
         lambda *a: flat(*ssm.ssd_chunk_scan_reference(*a)),
         (m_x[:1], dt[:1], a_neg, m_b[:1], m_c[:1], d_skip, m_state[:1]), True),
        ("ssm_state_update",
         lambda *a: flat(*ssm.ssm_state_update_kernel(*a, interpret=interpret)),
         lambda *a: flat(*ssm.ssm_state_update_reference(*a)),
         (m_x[:, 0], dt[:, 1], a_neg, m_b[:, 0], m_c[:, 0], d_skip, m_state, stepping), True),
        ("paged_decode",
         lambda *a: att.paged_attention_kernel(*a, interpret=interpret),
         att.paged_attention_reference,
         (normal(b, heads, dh), k_pages, v_pages, tables, kv_lens), True),
        ("paged_varq",
         lambda *a: att.paged_attention_varq_kernel(*a, interpret=interpret),
         att.paged_attention_varq_reference,
         (normal(b, window, heads, dh), k_pages, v_pages, tables, kv_lens), True),
        ("flash_prefill",
         lambda q, k, v: att.flash_attention(q, k, v, causal=True, interpret=interpret),
         lambda q, k, v: att.attention_reference(q, k, v, causal=True),
         tuple(normal(b, heads, chunk * 2, dh) for _ in range(3)), True),
        # the last chunk of a prefill: queries at the row's final positions
        ("flash_cache_chunk",
         lambda *a: att.flash_attention_cache(*a, interpret=interpret),
         lambda *a: att._decode_masked(*a, None),
         (normal(b, heads, chunk, dh), k_seq, v_seq, kv_valid - chunk, kv_valid), True),
        ("w8a16",
         lambda x, q, s: quant_matmul._w8a16_2d(x, q, s, block_n=256, interpret=interpret),
         lambda x, q, s: (x.astype(jnp.float32) @ q.astype(jnp.float32)) * s,
         (normal(b, hidden), w8, w_scale), True),
        # One-token decode over a long cache: the doubling ladder of KV
        # prefixes (an XLA switch, no kernel) against the unbucketed mask.
        ("ragged_decode_route",
         att.attention_cached,
         lambda *a: att._decode_masked(*a, None),
         (normal(b, heads, 1, dh), k_seq, v_seq, kv_valid - 1, kv_valid), False),
    ]
    results = []
    for name, kernel, reference, arguments, custom_call in cases:
        jitted = jax.jit(kernel)
        if custom_call and not interpret:
            check(
                "tpu_custom_call" in jitted.lower(*arguments).as_text(),
                f"{name}: no tpu_custom_call in the lowered program",
            )
        got = np.asarray(jitted(*arguments), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(reference)(*arguments), np.float32)
        check(got.shape == want.shape, f"{name}: shape {got.shape} != {want.shape}")
        check(np.isfinite(got).all(), f"{name}: non-finite output")
        err = float(np.max(np.abs(got - want) / (1.0 + np.abs(want))))
        check(err <= KERNEL_TOL, f"{name}: error {err:.3g} above {KERNEL_TOL}")
        results.append({"kernel": name, "max_err": err, "tpu_custom_call": custom_call and not interpret})

    # The two XLA device paths the retired on-chip pytest subset also held:
    # the int8 dequant dot and the MoE grouped GEMM.
    x = normal(16, 512)
    w = rng.normal(size=(512, 1024)).astype(np.float32)
    scale = np.abs(w).max(axis=0) / 127.0
    q8 = np.clip(np.round(w / scale), -127, 127).astype(np.int8)
    got = np.asarray(jnp.dot(x, jnp.asarray(q8).astype(bf16)) * jnp.asarray(scale, bf16), np.float32)
    want = np.asarray(x, np.float32) @ (q8.astype(np.float32) * scale)
    check(np.allclose(got, want, atol=2e-1, rtol=5e-2), "int8 dequant dot off its reference")
    from lumen_tpu.parallel.moe import _moe_exact_local, init_moe_params

    moe = _moe_exact_local(
        init_moe_params(jax.random.PRNGKey(seed), 64, 128, 8),
        jax.random.normal(jax.random.PRNGKey(seed + 1), (32, 64)),
        n_experts=8, k=2, norm_topk=True,
    )
    check(moe.shape == (32, 64) and bool(jnp.isfinite(moe).all()), "MoE grouped GEMM not finite")
    emit(
        "kernels", ok=True, interpret=interpret, tolerance=KERNEL_TOL,
        shapes={"batch": b, "heads": heads, "kv_heads": kv_heads, "head_dim": dh,
                "page": page, "pages_per_row": maxp, "kv_len": seq},
        kernels=results, xla_routes=["int8_dequant_dot", "moe_grouped_gemm"],
    )


# -- model directories and traffic ------------------------------------------


def jpeg(rng: np.random.Generator, edge: int) -> bytes:
    """A unique photo-like JPEG: smooth random colour field plus noise."""
    from PIL import Image

    coarse = rng.integers(0, 256, (8, 8, 3), np.uint8)
    img = np.asarray(Image.fromarray(coarse).resize((edge, edge * 3 // 4), Image.BICUBIC), np.int16)
    img = np.clip(img + rng.integers(-12, 13, img.shape), 0, 255).astype(np.uint8)
    buf = io.BytesIO()
    Image.fromarray(img).save(buf, format="JPEG", quality=88)
    return buf.getvalue()


CLIP_LABELS = ["cat", "photo", "a cat", "a photo", "photo of a cat"]


def write_model_dirs(root: str, sizes: Sizes, seed: int, with_vlm: bool) -> dict[str, str]:
    """Family -> the name of the model directory written for it."""
    from lumen_tpu.models.vlm.modeling import VLMConfig
    from lumen_tpu.testing.model_dirs import write_clip_dir, write_vlm_dir

    names = {"clip": "SmokeCLIP"}
    write_clip_dir(root, sizes.clip, name=names["clip"], labels=CLIP_LABELS, seed=seed)
    if with_vlm:
        names["vlm"] = "SmokeVLM"
        cfg = VLMConfig.tiny() if sizes.vlm_tiny else VLMConfig()
        write_vlm_dir(root, cfg, name=names["vlm"], seed=seed)
    return names


def hub_config(preset: str, root: str, names: dict[str, str]):
    """The preset's generated config with only the cache directory, the
    model names and the list of families changed."""
    from lumen_tpu.app.config_gen import generate_config
    from lumen_tpu.core.config import validate_config_dict

    raw = generate_config(preset, tier="full", cache_dir=root, mdns=False).model_dump(
        exclude_none=True
    )
    raw["deployment"]["services"] = list(names)
    raw["services"] = {f: raw["services"][f] for f in names}
    for family, name in names.items():
        (model,) = raw["services"][family]["models"].values()
        model["model"] = name
    raw["services"]["clip"]["models"]["clip"]["dataset"] = "labels"
    return validate_config_dict(raw)


def open_stub(port: int):
    import grpc

    from lumen_tpu.serving.proto import ml_service_pb2_grpc as pbg

    channel = grpc.insecure_channel(
        f"127.0.0.1:{port}",
        options=[("grpc.max_receive_message_length", 64 << 20),
                 ("grpc.max_send_message_length", 64 << 20)],
    )
    return channel, pbg.InferenceStub(channel)


def check_health(stub, handle, families) -> dict:
    from google.protobuf import empty_pb2

    from lumen_tpu.serving.resilience import DegradedService

    _, call = stub.Health.with_call(empty_pb2.Empty(), timeout=60)
    trailing = dict(call.trailing_metadata())
    statuses = json.loads(trailing["lumen-service-status"])
    check(sorted(statuses) == sorted(families), f"services {statuses} != configured {families}")
    for name in families:
        check(statuses[name] == "healthy", f"service {name!r} is {statuses[name]!r}")
        check(
            not isinstance(handle.services[name], DegradedService),
            f"service {name!r} booted degraded: {getattr(handle.services[name], 'error', '')}",
        )
    return statuses


def capabilities(stub) -> dict:
    from google.protobuf import empty_pb2

    return {
        cap.service_name: dict(cap.extra)
        for cap in stub.StreamCapabilities(empty_pb2.Empty(), timeout=60)
    }


def embed_images(stub, jpegs: list[bytes], streams: int) -> np.ndarray:
    from lumen_tpu import client

    def one(data: bytes) -> np.ndarray:
        out = client.infer(stub, "clip_image_embed", data, mime="image/jpeg")
        return np.asarray(out["vector"], np.float32)

    with ThreadPoolExecutor(streams) as pool:
        return np.stack(list(pool.map(one, jpegs)))


def check_unit_vectors(name: str, vecs: np.ndarray, dim: int) -> None:
    check(vecs.shape[-1] == dim, f"{name}: dim {vecs.shape[-1]} != {dim}")
    check(np.isfinite(vecs).all(), f"{name}: non-finite vector")
    norms = np.linalg.norm(vecs, axis=-1)
    check(np.allclose(norms, 1.0, atol=1e-3), f"{name}: norms {norms.min():.4f}..{norms.max():.4f}")


def decode_pixels(mgr, jpegs: list[bytes]) -> np.ndarray:
    """The uint8 tensors the server's own decode spec makes of these bytes."""
    from lumen_tpu.utils import host_decode

    spec = host_decode.resolve_decode_spec("clip_resize")
    return np.stack([spec(data, {"size": mgr.cfg.image_size}) for data in jpegs])


def clip_reference_f32(mgr, pixels: np.ndarray) -> np.ndarray:
    """Direct f32 ``model.apply`` of the same pixels on the device."""
    import jax
    import jax.numpy as jnp

    mean, std = (jnp.asarray(v, jnp.float32) for v in mgr.norm_stats)

    @jax.jit
    def encode(params, pixels_u8):
        x = (pixels_u8.astype(jnp.float32) / 255.0 - mean) / std
        return mgr.model.apply({"params": params}, x, method=lambda m, px: m.encode_image(px))

    params = jax.tree.map(lambda p: p.astype(jnp.float32), mgr.params)
    with jax.default_matmul_precision("highest"):
        return np.asarray(encode(params, jnp.asarray(pixels)), np.float32)


def row_cosines(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return np.sum(a * b, axis=-1) / (np.linalg.norm(a, axis=-1) * np.linalg.norm(b, axis=-1))


def occupancy(name: str) -> dict:
    from lumen_tpu.utils.metrics import metrics

    return metrics.snapshot()["gauges"].get(f"batch-occupancy:{name}", {})


def warm_clip(stub, mgr, rng, sizes: Sizes, streams: int) -> int:
    """Compile the batch buckets a window of ``streams`` concurrent callers
    can form: fire waves sized to each bucket the occupancy gauge has not
    seen yet, a few times over (batch formation is a matter of timing).
    Returns the warm-up's image count."""
    from lumen_tpu.serving.server import grpc_workers

    widest = min(streams, grpc_workers())  # handler threads bound a batch
    ladder = mgr._image_batcher.buckets
    targets = [b for b in ladder if b < widest] + [next(b for b in ladder if b >= widest)]
    sent = 0
    for _ in range(5):
        seen = {int(k[7:]) for k in occupancy("clip-image") if k.startswith("bucket_")}
        missing = [b for b in targets if b not in seen]
        if not missing:
            break
        for b in missing:
            n = min(b, streams)
            embed_images(stub, [jpeg(rng, sizes.jpeg_edge) for _ in range(n)], n)
            sent += n
    return sent


# -- serve ------------------------------------------------------------------


def vlm_request(stub, image: bytes, prompt: str, new_tokens: int) -> dict:
    from lumen_tpu import client

    meta = {
        "messages": json.dumps([{"role": "user", "content": prompt}]),
        "max_new_tokens": str(new_tokens),
    }
    return client.infer(stub, "vlm_generate", image, mime="image/jpeg", meta=meta, timeout=900)


def vlm_stream(stub, image: bytes, prompt: str, new_tokens: int) -> tuple[str, dict]:
    """``vlm_generate_stream``: the concatenated deltas and the final body."""
    from lumen_tpu import client

    meta = {
        "messages": json.dumps([{"role": "user", "content": prompt}]),
        "max_new_tokens": str(new_tokens),
    }
    deltas, final = [], None
    requests = client._requests("vlm_generate_stream", image, "image/jpeg", meta)
    for resp in stub.Infer(requests, timeout=900):
        check(not resp.error.message, f"stream error [{resp.error.code}]: {resp.error.message}")
        if resp.is_final:
            final = json.loads(resp.result)
        else:
            deltas.append(resp.result.decode("utf-8"))
    check(final is not None, "stream ended without a final message")
    return "".join(deltas), final


def vlm_wave(stub, mgr, rng, sizes: Sizes, first_word: int) -> dict:
    """The VLM traffic of one window: ``vlm_requests`` concurrent
    ``vlm_generate`` with distinct images, one ``vlm_generate_stream``, and
    one request through the manager's own API, which hands back token ids
    (the wire carries text) for the f32 reference to score."""
    from lumen_tpu.models.vlm.chat import ChatMessage

    n, new = sizes.vlm_requests, sizes.vlm_new_tokens
    images = [jpeg(rng, sizes.jpeg_edge * 2) for _ in range(n + 2)]
    prompts = [f"describe the image tok{first_word + i}" for i in range(n + 2)]
    t0 = time.perf_counter()
    with ThreadPoolExecutor(n) as pool:
        answers = list(pool.map(lambda i: vlm_request(stub, images[i], prompts[i], new), range(n)))
    seconds = time.perf_counter() - t0
    for out in answers:
        check(0 < out["generated_tokens"] <= new, f"generated_tokens: {out['generated_tokens']}")
        check(
            out["finish_reason"] == "eos_token" or out["generated_tokens"] == new,
            f"stopped early without EOS: {out['finish_reason']} at {out['generated_tokens']}",
        )
        check(out["text"].strip(), "empty generation")
    streamed, final = vlm_stream(stub, images[n], prompts[n], new)
    check(streamed.strip() == final["text"].strip(), "stream deltas do not concatenate to the final text")
    check(final["metadata"].get("ttft_ms") is not None, f"stream metadata: {final['metadata']}")
    direct = mgr.generate([ChatMessage("user", prompts[n + 1])], image_bytes=images[n + 1], max_new_tokens=new)
    return {"answers": answers, "seconds": seconds, "stream": final, "direct": direct,
            "direct_image": images[n + 1], "direct_prompt": prompts[n + 1]}


def vlm_teacher_forced_gap(mgr, image: bytes, prompt: str, served: list[int]) -> dict:
    """Score the engine's greedy tokens with an f32 cacheless forward of the
    whole sequence (plain XLA attention, no KV cache, no pages) on the
    device."""
    import jax
    import jax.numpy as jnp

    from lumen_tpu.models.vlm.chat import ChatMessage
    from lumen_tpu.utils import host_decode

    prompt_ids = mgr._encode_prompt([ChatMessage("user", prompt)], has_image=True)
    check(served, "the engine generated no tokens")
    canvas = host_decode.resolve_decode_spec("vlm_canvas")(image, {"size": mgr.cfg.vision.image_size})
    mean, std = jnp.asarray(mgr.cfg.vision.mean), jnp.asarray(mgr.cfg.vision.std)
    pixels = ((jnp.asarray(canvas[None], jnp.float32) / 255.0) - mean) / std
    params = jax.tree.map(lambda p: p.astype(jnp.float32), mgr.params)
    ids = jnp.asarray([prompt_ids + served], jnp.int32)
    flash = os.environ.get("LUMEN_FLASH")
    os.environ["LUMEN_FLASH"] = "0"  # the reference stays off the Pallas kernels
    try:
        with jax.default_matmul_precision("highest"):
            logits = jax.jit(lambda p, i, px: mgr.model.apply({"params": p}, i, px))(params, ids, pixels)
    finally:
        if flash is None:
            del os.environ["LUMEN_FLASH"]
        else:
            os.environ["LUMEN_FLASH"] = flash
    first = len(prompt_ids) - 1 + mgr.vision_tokens - 1  # position that predicts token 0
    rows = np.asarray(logits[0, first : first + len(served)], np.float32)
    picked = rows[np.arange(len(served)), served]
    std = rows.std(axis=-1)
    gaps = (rows.max(axis=-1) - picked) / std
    top2 = np.sort(rows, axis=-1)[:, -2:]
    return {
        "tokens": len(served),
        "distinct_tokens": len(set(served)),
        "argmax_agreement": round(float(np.mean(rows.argmax(axis=-1) == np.asarray(served))), 3),
        "max_gap_std": round(float(gaps.max()), 4),
        # how sharp the check is: a wide margin between the reference's two
        # best tokens means few faults could move the argmax
        "reference_top2_margin_std": round(float(((top2[:, 1] - top2[:, 0]) / std).min()), 4),
    }


def drained_engine_gauge() -> dict:
    """The ``vlm-continuous:*`` gauge once every row has retired: no page
    live, as many freed as were ever allocated."""
    from lumen_tpu.utils.metrics import metrics

    def read() -> dict:
        (engine,) = [v for k, v in metrics.snapshot()["gauges"].items() if k.startswith("vlm-continuous:")]
        return engine

    deadline = time.monotonic() + 30
    while (engine := read())["pages_live"] and time.monotonic() < deadline:
        time.sleep(0.1)
    check(engine["pages_live"] == 0, f"pages_live {engine['pages_live']} after drain")
    check(
        engine["pages_allocated_total"] == engine["pages_freed_total"] > 0,
        f"page accounting: {engine['pages_allocated_total']} allocated, {engine['pages_freed_total']} freed",
    )
    return engine


def phase_serve(sizes: Sizes, rehearse: bool, seed: int, events: JaxEvents) -> None:
    import importlib

    import jax
    import jax.numpy as jnp

    from lumen_tpu import client
    from lumen_tpu.serving.server import serve

    att = importlib.import_module("lumen_tpu.ops.attention")
    rng = np.random.default_rng(seed)
    root = tempfile.mkdtemp(prefix="chip_smoke_")
    t_setup = time.perf_counter()
    handle = channel = None
    try:
        names = write_model_dirs(root, sizes, seed, with_vlm=True)
        weights_s = time.perf_counter() - t_setup
        t_boot = time.perf_counter()
        handle = serve(
            hub_config("tpu_v5e_1", root, names),
            port_override=0, skip_download=True, metrics_port=0,
        )
        boot_s = time.perf_counter() - t_boot
        channel, stub = open_stub(handle.port)
        statuses = check_health(stub, handle, list(names))
        caps = capabilities(stub)
        check(caps["vlm"].get("scheduler") == "continuous", f"vlm scheduler: {caps['vlm'].get('scheduler')}")
        check(caps["vlm"].get("kv_layout", "").startswith("paged("), f"kv_layout: {caps['vlm'].get('kv_layout')}")
        clip_mgr = handle.services["clip"].managers["clip"]
        vlm_mgr = handle.services["vlm"].manager
        check(
            rehearse or vlm_mgr.pool_source == "device_memory",
            f"KV pool sized by {vlm_mgr.pool_source!r}, not from the device's bytes_limit",
        )

        # warm-up: every shape the window below will use
        warm_images = warm_clip(stub, clip_mgr, rng, sizes, sizes.clip_streams)
        client.infer(stub, "clip_text_embed", b"a photo", mime="text/plain")
        client.infer(stub, "clip_classify", jpeg(rng, sizes.jpeg_edge), mime="image/jpeg", meta={"top_k": "3"})
        vlm_wave(stub, vlm_mgr, rng, sizes, first_word=50)
        setup_s = time.perf_counter() - t_setup
        compiles_setup, programs_setup = xla_compiles(), len(events.compiled)

        # -- the request window --------------------------------------------
        t_req = time.perf_counter()
        jpegs = [jpeg(rng, sizes.jpeg_edge) for _ in range(sizes.clip_images)]
        t0 = time.perf_counter()
        vecs = embed_images(stub, jpegs, sizes.clip_streams)
        clip_s = time.perf_counter() - t0
        dim = clip_mgr.cfg.embed_dim
        check_unit_vectors("clip_image_embed", vecs, dim)
        text = client.infer(stub, "clip_text_embed", b"a photo of a cat", mime="text/plain")
        check_unit_vectors("clip_text_embed", np.asarray([text["vector"]], np.float32), dim)
        labels = client.infer(
            stub, "clip_classify", jpeg(rng, sizes.jpeg_edge), mime="image/jpeg", meta={"top_k": "3"}
        )["labels"]
        scores = [item["score"] for item in labels]
        check(len(labels) == 3 and all(item["label"] in CLIP_LABELS for item in labels), f"classify: {labels}")
        check(np.isfinite(scores).all() and scores == sorted(scores, reverse=True), f"classify scores: {scores}")

        wave = vlm_wave(stub, vlm_mgr, rng, sizes, first_word=100)
        answers, final = wave["answers"], wave["stream"]
        request_s = time.perf_counter() - t_req
        compiles_window = xla_compiles() - compiles_setup
        programs_window = sorted(set(events.compiled[programs_setup:]))

        # -- references, computed after the window (they compile) ----------
        cos = row_cosines(vecs, clip_reference_f32(clip_mgr, decode_pixels(clip_mgr, jpegs)))
        check(cos.min() >= CLIP_MIN_COSINE, f"clip vs f32 reference: min cosine {cos.min():.5f}")
        gap = vlm_teacher_forced_gap(
            vlm_mgr, wave["direct_image"], wave["direct_prompt"], wave["direct"].tokens
        )
        check(
            gap["max_gap_std"] <= VLM_MAX_LOGIT_GAP_STD,
            f"served tokens sit {gap['max_gap_std']} logit-std below the f32 reference's best",
        )

        engine = drained_engine_gauge()
        paged_fallbacks = sorted(r for r in att._FALLBACK_LOGGED if "paged kernel" in r)
        check(not paged_fallbacks, f"decode fell back from the paged kernel: {paged_fallbacks}")
        memory = jax.devices()[0].memory_stats() or {}
        fill = occupancy("clip-image")
        emit(
            "serve", ok=True, services=statuses,
            models={
                "clip": {"arch": sizes.clip, "embed_dim": dim, "image_size": clip_mgr.cfg.image_size},
                "vlm": {
                    "layers": vlm_mgr.cfg.decoder.layers, "hidden": vlm_mgr.cfg.decoder.hidden_size,
                    "heads": vlm_mgr.cfg.decoder.heads, "kv_heads": vlm_mgr.cfg.decoder.kv_heads,
                    "vocab": vlm_mgr.cfg.decoder.vocab_size, "vision_tokens": vlm_mgr.vision_tokens,
                    "dtype": jnp.dtype(vlm_mgr.policy.compute_dtype).name,
                },
            },
            vlm_capabilities={k: caps["vlm"][k] for k in ("scheduler", "kv_layout", "max_seq")},
            kv_pool={"source": vlm_mgr.pool_source, "pages": engine["pages_total"],
                     "pages_allocated_total": engine["pages_allocated_total"],
                     "pages_freed_total": engine["pages_freed_total"], "pages_live": engine["pages_live"]},
            paged_kernel_fallbacks=paged_fallbacks,
            clip={"images": len(jpegs), "streams": sizes.clip_streams, "seconds": round(clip_s, 3),
                  "min_cosine_vs_f32": round(float(cos.min()), 5), "batch_occupancy": fill},
            vlm={"requests": len(answers), "new_tokens": sizes.vlm_new_tokens, "seconds": round(wave["seconds"], 3),
                 "tokens_per_second": [a["metadata"].get("tokens_per_second") for a in answers],
                 "stream_ttft_ms": final["metadata"].get("ttft_ms"),
                 "stream_tokens_per_second": final["metadata"].get("tokens_per_second"),
                 "teacher_forced_f32": gap},
            face_ocr="not run on the chip",
            device_memory={k: memory.get(k) for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")},
            seconds={"weights": round(weights_s, 2), "boot": round(boot_s, 2),
                     "setup_total": round(setup_s, 2), "requests": round(request_s, 2)},
            warmup_images=warm_images,
            xla_compiles={"setup": compiles_setup, "request_window": compiles_window,
                          "request_window_programs": programs_window},
        )
    finally:
        if channel is not None:
            channel.close()
        if handle is not None:
            handle.drain_and_stop()
        shutil.rmtree(root, ignore_errors=True)


# -- four chips ---------------------------------------------------------------


def phase_dp4(sizes: Sizes, rehearse: bool, seed: int) -> None:
    """CLIP through the hub on the ``tpu_v5e_4`` preset's four-device
    ``data`` mesh, against the same images on a one-device mesh."""
    import jax

    from lumen_tpu.models.clip.manager import CLIPManager
    from lumen_tpu.runtime.batcher import mesh_sharded
    from lumen_tpu.serving.server import serve

    rng = np.random.default_rng(seed)
    root = tempfile.mkdtemp(prefix="chip_smoke_dp4_")
    t_setup = time.perf_counter()
    handle = channel = single = None
    try:
        names = write_model_dirs(root, sizes, seed, with_vlm=False)
        handle = serve(
            hub_config("tpu_v5e_4", root, names),
            port_override=0, skip_download=True, metrics_port=0,
        )
        channel, stub = open_stub(handle.port)
        statuses = check_health(stub, handle, ["clip"])
        mgr = handle.services["clip"].managers["clip"]
        check(dict(mgr.mesh.shape) == {"data": 4}, f"serving mesh {dict(mgr.mesh.shape)}")
        streams = 32
        warm_clip(stub, mgr, rng, sizes, streams)
        setup_s = time.perf_counter() - t_setup

        jpegs = [jpeg(rng, sizes.jpeg_edge) for _ in range(sizes.dp_images)]
        t0 = time.perf_counter()
        served = embed_images(stub, jpegs, streams)
        request_s = time.perf_counter() - t0
        check_unit_vectors("clip_image_embed", served, mgr.cfg.embed_dim)

        # one micro-batch the way the manager's batcher dispatches it
        pixels = decode_pixels(mgr, jpegs)
        batch = mesh_sharded(lambda px, n: mgr._encode_images(mgr.params, px), mgr.mesh)(pixels[:64], 64)
        shard_devices = sorted(str(s.device) for s in batch.addressable_shards)
        check(len(set(shard_devices)) == 4, f"micro-batch output on {shard_devices}")
        in_use = {str(d): (d.memory_stats() or {}).get("bytes_in_use") for d in jax.devices()}
        check(
            rehearse or all(v for v in in_use.values()),
            f"a device holds no bytes: {in_use}",
        )

        single = CLIPManager(
            os.path.join(root, "models", names["clip"]), dtype="bfloat16",
            batch_size=64, mesh_axes={"data": 1}, name_prefix="single",
        )
        single.initialize()
        check(single.mesh.devices.size == 1, f"comparison mesh {dict(single.mesh.shape)}")
        one_device = np.concatenate([
            np.asarray(single._encode_images(single.params, pixels[i : i + 64]), np.float32)
            for i in range(0, len(pixels), 64)
        ])
        cos = row_cosines(served, one_device)
        check(cos.min() >= DP_MIN_COSINE, f"dp4 vs one device: min cosine {cos.min():.6f}")
        emit(
            "dp4", ok=True, services=statuses, mesh=dict(mgr.mesh.shape), images=len(jpegs),
            min_cosine_vs_one_device=round(float(cos.min()), 6),
            micro_batch_shard_devices=shard_devices, bytes_in_use=in_use,
            batch_occupancy=occupancy("clip-image"),
            seconds={"setup": round(setup_s, 2), "requests": round(request_s, 3)},
        )
    finally:
        if single is not None:
            single.close()
        if channel is not None:
            channel.close()
        if handle is not None:
            handle.drain_and_stop()
        shutil.rmtree(root, ignore_errors=True)


# -- main -------------------------------------------------------------------


def exit_watchdog(seconds: float = 120.0) -> None:
    """``drain_and_stop()`` must leave nothing that keeps the process
    alive: if the interpreter has not exited by then, say who holds it and
    end with a failing code instead of hanging to the time limit."""

    def bark():
        time.sleep(seconds)
        alive = [t.name for t in threading.enumerate() if not t.daemon and t.is_alive()]
        print(f"chip_smoke: process still alive {seconds:.0f}s after the last line: {alive}", file=sys.stderr, flush=True)
        os._exit(70)

    threading.Thread(target=bark, name="exit-watchdog", daemon=True).start()


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 runs only the data-parallel CLIP phase and its one-device comparison")
    parser.add_argument("--seed", type=int, default=0, help="weights, images and kernel inputs")
    parser.add_argument("--rehearse", action="store_true",
                        help="sandbox rehearsal: tiny sizes, any backend, kernels in interpret mode")
    parser.add_argument("--log-file", default=None, help="INFO log of the program (default: warnings to stderr)")
    args = parser.parse_args(argv)
    if args.log_file:
        os.makedirs(os.path.dirname(os.path.abspath(args.log_file)), exist_ok=True)
        logging.basicConfig(filename=args.log_file, level=logging.INFO,
                            format="%(asctime)s %(levelname)s %(name)s: %(message)s")
    else:
        logging.basicConfig(level=logging.WARNING)
    sizes = REHEARSAL if args.rehearse else REAL
    if args.rehearse:
        # the explicit test switches: the served paged decode and prefill go
        # through the Pallas kernels in interpret mode
        os.environ["LUMEN_PAGED_KERNEL"] = "1"
        os.environ["LUMEN_FLASH"] = "1"

    phase = "device"
    t0 = time.perf_counter()
    try:
        device = phase_device(args)
        events = JaxEvents()
        if args.chips == 4:
            phase = "dp4"
            phase_dp4(sizes, args.rehearse, args.seed)
        else:
            phase = "kernels"
            phase_kernels(sizes, args.rehearse, args.seed)
            phase = "serve"
            phase_serve(sizes, args.rehearse, args.seed, events)
        phase = "teardown"
        from lumen_tpu.runtime.decode_pool import shutdown_decode_pool

        shutdown_decode_pool()  # the pool's worker processes are ours to stop
        emit(
            "summary", ok=True, seconds_total=round(time.perf_counter() - t0, 2),
            xla_compiles_total=xla_compiles(),
            compile_cache_events={"hits": events.hits, "misses": events.misses},
        )
    except (Exception, SystemExit) as e:  # noqa: BLE001 - every failure ends the run non-zero
        logging.getLogger("chip_smoke").exception("phase %s failed", phase)
        print(json.dumps({"ok": False, "phase": phase, "error": f"{type(e).__name__}: {e}"[:2000]}), flush=True)
        exit_watchdog()
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    exit_watchdog()
    return 0


if __name__ == "__main__":
    sys.exit(main())
