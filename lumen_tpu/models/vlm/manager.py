"""VLM manager: multimodal caption/chat generation on TPU.

Business-logic layer mirroring the reference ``FastVLMModelManager``
(``packages/lumen-vlm/src/lumen_vlm/fastvlm/fastvlm_model.py:51-400``) over
the TPU-native stack: host does image decode + letterbox + tokenize; device
runs ONE compiled prepare program (normalize -> vision encode -> token embed
-> image-token splice) and hands the row to the one serving engine, the
paged continuous scheduler (``continuous.py``: chunked prefill lane + block
decode over a page pool). Prompt lengths are padded to static buckets so
the number of distinct compiles is bounded.
"""

from __future__ import annotations

import hashlib
import io
import json
import logging
import os
import threading
import time
import weakref
from dataclasses import dataclass, field
from typing import Any, Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ...core.model_info import ModelInfo, load_model_info
from ...runtime.decode_pool import get_decode_pool
from ...runtime.policy import get_policy
from ...runtime.quarantine import guarded_key
from ...runtime.result_cache import get_result_cache, make_namespace
from ...runtime.weights import load_state_dict
from ...utils.metrics import metrics
from .chat import ChatMessage, VlmTokenizer
from .convert import convert_vlm_checkpoint
from .generate import Generator
from .modeling import VLMConfig, VLMModel, merge_image_embeddings

logger = logging.getLogger(__name__)

DEFAULT_PREFILL_BUCKETS = (64, 128, 256, 512, 1024)


@dataclass
class GenerationResult:
    text: str
    tokens: list[int]
    finish_reason: str  # stop | length | eos_token | stop_sequence | error
    input_tokens: int
    metadata: dict[str, Any] = field(default_factory=dict)


@dataclass
class GenerationChunk:
    text: str
    tokens: list[int]
    is_final: bool = False
    metadata: dict[str, Any] = field(default_factory=dict)


class VLMManager:
    def __init__(
        self,
        model_dir: str,
        dtype: str = "bfloat16",
        max_seq: int = 2048,
        max_new_cap: int = 512,
        prefill_buckets: Sequence[int] = DEFAULT_PREFILL_BUCKETS,
        warmup: bool = False,
        gen_slots: int = 8,
        gen_block: int = 8,
        quantize: str | None = None,  # None | "int8" (weight-only decoder quant)
        mesh_axes: dict[str, int] | None = None,
    ):
        if quantize not in (None, "int8"):
            raise ValueError(f"quantize must be None or 'int8', got {quantize!r}")
        self.quantize = quantize
        # Decode route actually in use ("bf16" | "int8"): finalized at
        # initialize() — a warmup A/B (LUMEN_VLM_Q8_ROUTE=auto) may fall an
        # int8 opt-in back to bf16 when q8 measures slower (q8 decode at
        # 0.03x bf16 on v5e; round-5 chip run, 2026-08-02, older than the
        # ledger).
        self.quant_route = "int8" if quantize else "bf16"
        self.quant_speedup: float | None = None  # measured q8/bf16 decode ratio
        self.model_dir = model_dir
        from ...runtime.fleet import plan_replicas

        # Serving mesh: a ``model`` axis tensor-parallelizes the decoder, an
        # ``expert`` axis shards MoE expert banks (SURVEY §2.8); without
        # either the mesh is the trivial data mesh and weights replicate.
        # The engine is built PER REPLICA through the fleet plan (one
        # engine + page pool per device slice, PR 7 semantics).
        self.fleet_plan = plan_replicas("vlm", mesh_axes)
        self.mesh = self.fleet_plan.meshes[0]
        from ...ops.quant_matmul import note_mesh_model_axis

        # TP x int8: pl.pallas_call has no GSPMD sharding rule, so a
        # model-axis mesh must keep decode on the XLA dequant fallback.
        note_mesh_model_axis(dict(self.mesh.shape).get("model", 1))
        self.policy = get_policy(dtype)
        self.warmup = warmup
        self.max_seq = max_seq
        self.max_new_cap = max_new_cap
        self.prefill_buckets = sorted(prefill_buckets)
        self.gen_slots = gen_slots
        self.gen_block = gen_block
        self.info: ModelInfo = load_model_info(model_dir)
        self.cfg = self._build_config(model_dir)
        if self.quantize:
            import dataclasses

            from ...ops.quant import resolve_q8_kernel

            # Kernel formulation for the int8 projections; "dynamic"
            # (W8A8, native MXU int8 dot) is the fallback for stacks where
            # the dequant convert doesn't fuse (see DecoderConfig).
            q8_kernel = resolve_q8_kernel("dequant")
            self.cfg = dataclasses.replace(
                self.cfg,
                decoder=dataclasses.replace(
                    self.cfg.decoder,
                    weight_quant=self.quantize,
                    weight_quant_kernel=q8_kernel,
                ),
            )
        self.model = VLMModel(self.cfg)
        self.model_id = self.info.name
        self._initialized = False
        # Overridden at initialize() when a vision.onnx graph is probed.
        self.vision_tokens = self.cfg.vision.num_tokens
        self._seed_lock = threading.Lock()
        self._seed = 0

    def _build_config(self, model_dir: str) -> VLMConfig:
        cfg_path = os.path.join(model_dir, "config.json")
        if os.path.exists(cfg_path):
            with open(cfg_path, "r", encoding="utf-8") as f:
                return VLMConfig.from_hf(json.load(f))
        # model_info extra_metadata fallback (the reference's only source,
        # ``backends/base.py:472-480``).
        meta = self.info.extra_metadata or {}
        if "generation_config" in meta:
            gen = dict(meta["generation_config"])
            kv = dict(meta.get("kv_cache_config", {}))
            vis = dict(meta.get("vision_config", {}))
            text_cfg = {
                "vocab_size": gen.get("vocab_size"),
                "bos_token_id": gen.get("bos_token_id"),
                "eos_token_id": gen.get("eos_token_id"),
                "pad_token_id": gen.get("pad_token_id"),
                "max_position_embeddings": gen.get("max_position_embeddings"),
                "hidden_size": kv.get("hidden_size"),
                "num_hidden_layers": kv.get("num_hidden_layers"),
                "num_attention_heads": kv.get("num_attention_heads"),
                "num_key_value_heads": kv.get("num_key_value_heads"),
                "head_dim": kv.get("head_dim"),
            }
            vision_cfg = {
                "image_size": vis.get("image_size"),
                "patch_size": vis.get("patch_size"),
                "image_mean": vis.get("mean"),
                "image_std": vis.get("std"),
            }
            raw = {
                # Absent manifest keys must fall through to from_hf's
                # defaults, so drop None-valued entries instead of passing
                # them (dict.get(k, default) would return the None).
                "text_config": {k: v for k, v in text_cfg.items() if v is not None},
                "vision_config": {k: v for k, v in vision_cfg.items() if v is not None},
            }
            if gen.get("image_token_index") is not None:
                raw["image_token_index"] = gen["image_token_index"]
            return VLMConfig.from_hf(raw)
        raise FileNotFoundError(f"no config.json or generation_config metadata in {model_dir}")

    # -- initialization ----------------------------------------------------

    def _place_params(self, params, quantized: bool | None = None, mesh=None):
        """Place loaded weights on the serving mesh: TP rules when the mesh
        carries a ``model`` axis, EP rules first when it carries ``expert``
        (first-match-wins keeps expert banks on the expert axis), replicated
        otherwise. int8-quantized trees ship (q, scale) leaves with their
        own rules (``INT8_TP_RULES``: scales shard along the same output
        axis as their q matrices) — TP x int8 is the advertised deployment
        shape for a quantized 2B on a multi-chip host. ``quantized``
        overrides the config-derived default (the warmup route A/B places
        one tree of EACH kind); ``mesh`` overrides the primary mesh (the
        replica fleet places one tree per slice)."""
        from ...parallel.sharding import (
            INT8_TP_RULES,
            MOE_EP_RULES,
            TRANSFORMER_TP_RULES,
            shard_params,
        )

        if quantized is None:
            quantized = bool(self.quantize)
        mesh = mesh if mesh is not None else self.mesh
        shape = dict(mesh.shape)
        rules = []
        if shape.get("expert", 1) > 1:
            rules += MOE_EP_RULES
        if shape.get("model", 1) > 1:
            if quantized:
                rules += INT8_TP_RULES
            rules += TRANSFORMER_TP_RULES
        if rules:
            logger.info(
                "sharding VLM params over mesh %s (%d rules)", shape, len(rules)
            )
        # shard_params with no rules degrades every leaf to replication,
        # and NamedSharding placement on a 1-device mesh is device_put —
        # one call covers all cases.
        return shard_params(params, mesh, rules)

    # -- quantization route -------------------------------------------------

    def _resolve_q8_route(self, converted: dict) -> dict:
        """Decide whether the int8 decode opt-in actually serves int8 —
        the VLM twin of the CLIP route gate (PR 2). q8 decode measured 135
        tok/s vs 4,498 bf16 (0.03x) on v5e (round-5 chip run, 2026-08-02,
        older than the ledger): an operator who
        opted into "int8" for memory almost certainly did not want a 30x
        decode regression. ``LUMEN_VLM_Q8_ROUTE``:

        - ``bf16``  — pin: skip quantization entirely (no per-boot
          quantize pass just to discard it);
        - ``int8``  — pin: quantize and serve int8, no timing;
        - ``auto``  (default) — with warmup on, run a one-shot timed
          decode A/B (synthetic prompt through the real Generator path,
          sequential placements so peak HBM stays at one decoder set) and
          serve the winner; without warmup there is nothing to time
          against, so the explicit opt-in wins.

        Returns the route-matching decoder tree (decoder subtree cast to
        the serving dtype on BOTH routes; vision subtree untouched) and
        sets ``self.cfg``/``self.model``/``self.quant_route``; the verdict
        is exported as the ``vlm-quant:<model>`` gauge provider
        (``int8_active``, ``q8_speedup_pct``)."""
        import dataclasses

        from .convert import quantize_decoder_int8

        route = os.environ.get("LUMEN_VLM_Q8_ROUTE", "auto").lower()
        if route not in ("auto", "int8", "bf16"):
            logger.warning("ignoring malformed LUMEN_VLM_Q8_ROUTE=%r", route)
            route = "auto"
        vision_sub = converted.pop("vision", None)
        # Cast first so the int8 grid is computed from the bf16 weights
        # serving would otherwise stream; scales stay fp32 (the later
        # blanket cast is skipped for quantized trees). The vision subtree
        # sits out: never quantized, and cast later only if kept.
        cast = self.policy.cast_params(converted)
        base_cfg = dataclasses.replace(
            self.cfg,
            decoder=dataclasses.replace(
                self.cfg.decoder, weight_quant=None, weight_quant_kernel=None
            ),
        )
        if route == "bf16":
            logger.info(
                "VLM quantize=int8 overridden to bf16 (LUMEN_VLM_Q8_ROUTE); "
                "skipping quantization"
            )
            chosen, params = "bf16", cast
        else:
            # Disk-tier verdict cache (next to the weights, keyed by
            # model@revision): the warmup A/B measured q8 decode at 0.03x
            # bf16 on v5e (round-5 chip run, 2026-08-02, older than the
            # ledger) — re-running the losing probe every
            # boot costs two timed decode passes for a known answer. An
            # explicit pin (route != auto) still bypasses the cache, and
            # a cache miss (new revision) re-measures and re-persists.
            cached = self._load_q8_verdict() if route == "auto" and self.warmup else None
            if cached is not None:
                chosen = cached["route"]
                self.quant_speedup = cached.get("q8_speedup")
                logger.info(
                    "VLM q8 decode verdict for %s loaded from disk: %s "
                    "(%.3fx bf16, measured %s); skipping warmup probe — "
                    "delete %s or pin LUMEN_VLM_Q8_ROUTE to re-measure",
                    self._q8_verdict_key(), chosen,
                    self.quant_speedup if self.quant_speedup is not None else float("nan"),
                    cached.get("measured_at", "?"), self._q8_verdict_path(),
                )
                params = quantize_decoder_int8(cast) if chosen == "int8" else cast
            elif route == "int8" or not self.warmup:
                chosen, params = "int8", quantize_decoder_int8(cast)
            else:
                chosen, params = self._q8_decode_ab(
                    base_cfg, cast, quantize_decoder_int8(cast)
                )
                self._save_q8_verdict(chosen)
        if chosen == "bf16":
            self.cfg = base_cfg
            self.model = VLMModel(self.cfg)
        self.quant_route = chosen
        ref = weakref.ref(self)

        def _route_gauges() -> dict:
            m = ref()
            if m is None:
                return {}
            out = {"int8_active": 1 if m.quant_route == "int8" else 0}
            if m.quant_speedup is not None:
                out["q8_speedup_pct"] = round(m.quant_speedup * 100, 1)
            return out

        self._route_gauge_fn = _route_gauges
        metrics.register_gauges(f"vlm-quant:{self.model_id}", _route_gauges)
        if vision_sub is not None:
            params["vision"] = vision_sub
        return params

    def _q8_verdict_key(self) -> str:
        return f"{self.info.name}@{self.info.version}"

    def _q8_verdict_path(self) -> str:
        return os.path.join(self.model_dir, ".lumen_q8_verdict.json")

    def _load_q8_verdict(self) -> dict | None:
        """Cached warmup A/B verdict for THIS model@revision, or None on
        miss/mismatch/corruption (all of which fall through to a fresh
        probe — a stale or mangled file must never pin a route)."""
        try:
            with open(self._q8_verdict_path(), "r", encoding="utf-8") as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError):
            return None
        if (
            not isinstance(data, dict)
            or data.get("model") != self._q8_verdict_key()
            or data.get("route") not in ("int8", "bf16")
        ):
            return None
        return data

    def _save_q8_verdict(self, route: str) -> None:
        """Best-effort persist (read-only model dirs lose the cache, not
        the boot)."""
        data = {
            "model": self._q8_verdict_key(),
            "route": route,
            "q8_speedup": self.quant_speedup,
            "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        }
        try:
            with open(self._q8_verdict_path(), "w", encoding="utf-8") as f:
                json.dump(data, f)
        except OSError as e:
            logger.debug("could not persist q8 verdict to %s: %s", self._q8_verdict_path(), e)

    def _q8_decode_ab(self, base_cfg, cast: dict, qtree: dict):
        """One-shot warmup decode A/B; returns ``(route, tree)``. Timed
        SEQUENTIALLY (place bf16, time, free; place q8, time, free) so the
        memory-tight deployments that quantize in the first place never
        hold two decoder placements at once."""
        tps_bf16 = self._time_decode_route(VLMModel(base_cfg), base_cfg, cast, quantized=False)
        tps_q8 = self._time_decode_route(self.model, self.cfg, qtree, quantized=True)
        self.quant_speedup = tps_q8 / max(tps_bf16, 1e-9)
        if self.quant_speedup >= 1.0:
            logger.info(
                "VLM int8 decode route confirmed: %.3fx bf16 tokens/s",
                self.quant_speedup,
            )
            return "int8", qtree
        logger.warning(
            "VLM int8 decode route DISABLED: warmup A/B measured q8 decode "
            "at %.3fx bf16 tokens/s (a regression); serving bf16 instead. "
            "Pin LUMEN_VLM_Q8_ROUTE=int8 to force.",
            self.quant_speedup,
        )
        metrics.count("vlm_q8_fallbacks")
        return "bf16", cast

    def _time_decode_route(self, model, cfg, params: dict, quantized: bool) -> float:
        """Decode tokens/sec for one route: a short synthetic prompt
        through a small dedicated :class:`Generator` (the REAL decode
        program — prefill + while_loop step — at a timing-sized KV), best
        of 2 after a compile pass. The placement is freed before return."""
        prompt_len, new_tokens = 16, 24
        batch = max(1, min(4, self.gen_slots))
        placed = self._place_params(params, quantized=quantized)
        gen = Generator(
            model, cfg,
            max_seq=prompt_len + new_tokens + 8,
            max_new_cap=new_tokens,
            cache_dtype=self.policy.compute_dtype,
        )
        hidden = cfg.decoder.hidden_size
        embeds = jnp.zeros((batch, prompt_len, hidden), self.policy.compute_dtype)
        positions = jnp.broadcast_to(jnp.arange(prompt_len)[None, :], (batch, prompt_len))
        lengths = jnp.full((batch,), prompt_len, jnp.int32)
        prompt_ids = jnp.ones((batch, prompt_len), jnp.int32)

        def run() -> int:
            out = gen.generate(
                placed, embeds, positions, lengths, prompt_ids,
                jax.random.PRNGKey(0), max_new_tokens=new_tokens,
            )
            return int(np.asarray(out.n_generated).sum())

        run()  # compile + settle off the clock
        best = 0.0
        for _ in range(2):
            t0 = time.perf_counter()
            n = run()
            best = max(best, max(n, 1) / (time.perf_counter() - t0))
        del placed
        return best

    def initialize(self) -> None:
        if self._initialized:
            return
        from .graph import VisionGraph, find_vision_onnx

        logger.info("loading VLM weights from %s", self.model_dir)
        state = load_state_dict(self.model_dir)
        from ...runtime.weights import assert_tree_shapes

        # Vision backend selection. ``auto`` (default): prefer converted
        # Flax vision weights when the checkpoint ships a complete tower —
        # an auxiliary vision*.onnx (e.g. an optimum export without the
        # projector) must not break a previously-working model dir — and
        # fall back to the ONNX graph otherwise (FastVLM-style repos whose
        # FastViTHD tower has no conversion rules). ``graph``/``native``
        # in model_info extra_metadata force one path.
        backend = str((self.info.extra_metadata or {}).get("vision_backend", "auto"))
        converted = convert_vlm_checkpoint(
            state, None, tie_word_embeddings=self.cfg.decoder.tie_word_embeddings
        )
        if self.quantize == "int8":
            # Route resolution may rebuild self.cfg/self.model (bf16 pin or
            # a warmup A/B fallback), so it runs BEFORE the eval_shape gate
            # below — the gate must describe the tree actually served.
            converted = self._resolve_q8_route(converted)
        init = jax.eval_shape(
            lambda: self.model.init(
                jax.random.PRNGKey(0),
                jnp.zeros((1, 4), jnp.int32),
                jnp.zeros(
                    (1, self.cfg.vision.image_size, self.cfg.vision.image_size, 3), jnp.float32
                ),
            )["params"]
        )
        has_native_vision = _subtree_matches(converted.get("vision"), init["vision"])
        vision_onnx = find_vision_onnx(self.model_dir) if backend != "native" else None
        vision_graph: VisionGraph | None = None
        if vision_onnx is not None and (backend == "graph" or not has_native_vision):
            vision_graph = VisionGraph.from_path(vision_onnx)
            params = converted
            # The Flax vision subtree is never executed on this path; keep
            # the shape gate on the decoder half only and don't burn HBM
            # on a dead tower.
            params.pop("vision", None)
            gate = {k: v for k, v in init.items() if k != "vision"}
            assert_tree_shapes(params, gate)
        else:
            if vision_onnx is None and backend == "graph":
                raise FileNotFoundError(
                    f"vision_backend=graph but no vision*.onnx in {self.model_dir}"
                )
            params = converted
            assert_tree_shapes(params, init)
        if not self.quantize:
            params = self.policy.cast_params(params)
        elif "vision" in params:
            # Quantized decoder was cast pre-quantization; the kept native
            # vision tower still needs its (ordinary) dtype cast.
            params["vision"] = self.policy.cast_params(params["vision"])
        self.params = self._place_params(params)
        self.tokenizer = VlmTokenizer.from_model_dir(self.model_dir)
        if vision_graph is not None:
            self.vision_tokens = vision_graph.probe(
                self.cfg.vision.image_size, self.cfg.decoder.hidden_size
            )
            from ...parallel.sharding import replicate

            # The graph-served vision tower has no TP rules; replicate so
            # it composes with a sharded decoder on the same mesh (on a
            # 1-device mesh this is plain device placement).
            self._vision_params = replicate(
                dict(vision_graph.module.params), self.mesh
            )
            logger.info(
                "vlm vision tower: graph %s (%d MB params, %d tokens)",
                vision_onnx,
                vision_graph.module.param_bytes() >> 20,
                self.vision_tokens,
            )
            # The host fp32 copy is duplicated on device now; the compiled
            # program receives weights via the vparams argument, so free
            # the originals instead of pinning them in the closure.
            vision_graph.module.release_weights()
        # A prompt bucket is usable only if prompt + vision tokens + the
        # decode budget fit in the KV buffer.
        v = self.vision_tokens
        if self.max_seq > 2048:
            # Ladders are written for the default max_seq of 2048 (the
            # presets' stop at 512, the default at 1024); a configured
            # longer max_seq continues them by doubling, up to the longest
            # prompt that still leaves room for the image and the decode cap.
            top = (self.max_seq - v - self.max_new_cap) // 128 * 128
            while self.prefill_buckets[-1] * 2 <= top:
                self.prefill_buckets.append(self.prefill_buckets[-1] * 2)
            if top > self.prefill_buckets[-1]:
                self.prefill_buckets.append(top)
        self.prefill_buckets = [
            b for b in self.prefill_buckets if b - 1 + v + self.max_new_cap + 1 <= self.max_seq
        ]
        if not self.prefill_buckets:
            raise ValueError(
                f"max_seq={self.max_seq} too small for any prompt bucket "
                f"(+{v} vision tokens, +{self.max_new_cap} decode budget)"
            )
        compute = self.policy.compute_dtype
        # One KV bucket per prompt bucket (merged length + decode budget,
        # rounded up to 64): a short caption request allocates a cache
        # sized for ITS prompt bucket, not worst-case max_seq — the KV
        # right-sizing half of the memory story (the continuous pool is
        # fixed-size by design; this covers the fused/coalescing path).
        seq_buckets = tuple(
            min(self.max_seq, -((b - 1 + v + self.max_new_cap + 1) // -64) * 64)
            for b in self.prefill_buckets
        )
        self.generator = Generator(
            self.model, self.cfg, self.max_seq, self.max_new_cap,
            cache_dtype=compute, seq_buckets=seq_buckets,
        )

        vis_cfg = self.cfg.vision
        mean = jnp.asarray(vis_cfg.mean)
        std = jnp.asarray(vis_cfg.std)

        if vision_graph is not None:

            @jax.jit
            def prepare_graph(params, vparams, pixels_u8, ids, length):
                x = pixels_u8.astype(jnp.float32) / 255.0
                x = (x - mean) / std
                vis = vision_graph(vparams, x.transpose(0, 3, 1, 2)).astype(compute)
                text = self.model.apply({"params": params}, ids, method=VLMModel.embed_tokens)
                return merge_image_embeddings(
                    text.astype(compute), vis, ids, self.cfg.image_token_id, length
                )

            def prepare(params, pixels_u8, ids, length):
                return prepare_graph(params, self._vision_params, pixels_u8, ids, length)

        else:

            @jax.jit
            def prepare(params, pixels_u8, ids, length):
                x = pixels_u8.astype(jnp.float32) / 255.0
                x = ((x - mean) / std).astype(compute)
                vis = self.model.apply({"params": params}, x, method=VLMModel.encode_vision)
                text = self.model.apply({"params": params}, ids, method=VLMModel.embed_tokens)
                return merge_image_embeddings(
                    text.astype(compute), vis, ids, self.cfg.image_token_id, length
                )

        @jax.jit
        def prepare_text(params, ids, length):
            text = self.model.apply({"params": params}, ids, method=VLMModel.embed_tokens)
            b, s = ids.shape
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))
            return text.astype(compute), positions, length

        self._prepare = prepare
        self._prepare_text = prepare_text
        self._engine_fleet = None
        from ...runtime.fleet import batcher_name
        from ...utils.env import env_int
        from .continuous import ContinuousScheduler
        from .paged_kv import DEFAULT_PAGE_SIZE, resolve_pool_pages

        self._page_size = env_int(
            "LUMEN_VLM_PAGE_SIZE", DEFAULT_PAGE_SIZE, minimum=8, maximum=256
        )
        self._pool_pages, self.pool_source = resolve_pool_pages(
            self.cfg, self._page_size, self.gen_slots, self.max_seq,
            dtype_bytes=jnp.dtype(compute).itemsize, block=self.gen_block,
        )
        plan = self.fleet_plan

        def build_engine(rid: int | None, mesh, placed) -> ContinuousScheduler:
            """Manager factory for one per-replica decode engine: its
            own page pool + block tables on the replica's mesh slice,
            per-replica gauge names (``vlm-continuous:<model>-rN``)."""
            return ContinuousScheduler(
                self.generator, placed, slots=self.gen_slots,
                block=self.gen_block,
                name=batcher_name(self.info.name, rid),
                page_size=self._page_size, pages=self._pool_pages,
                mesh=mesh if plan.replicas > 1 else None,
            )

        self._engine_factory = build_engine
        self._engines = [
            build_engine(None if plan.replicas == 1 else 0, plan.meshes[0], self.params)
        ]
        for rid in range(1, plan.replicas):
            placed = self._place_params(params, mesh=plan.meshes[rid])
            self._engines.append(build_engine(rid, plan.meshes[rid], placed))
        self._continuous = self._engines[0]
        if plan.replicas > 1:
            from ...runtime.fleet import EngineFleet

            def rebuild_engine(rid: int) -> ContinuousScheduler:
                """Unpark hook: re-place the (already device-resident)
                params on the replica's original mesh slice and build
                a fresh engine there. The migration dispatcher is
                wired at server boot only, so copy it over from a
                surviving sibling — a rebuilt engine in a role-tagged
                fleet must keep exporting rows."""
                placed = self._place_params(
                    self.params, mesh=plan.meshes[rid]
                )
                eng = build_engine(rid, plan.meshes[rid], placed)
                fleet = self._engine_fleet
                if fleet is not None:
                    for sib in fleet.serving_engines():
                        if sib.migrator is not None:
                            eng.migrator = sib.migrator
                            break
                return eng

            self._engine_fleet = EngineFleet(
                self.info.name, list(self._engines),
                build=rebuild_engine,
                devices_per_replica=plan.devices_per_replica,
            )
            logger.info(
                "VLM continuous engine fleet: %d replicas x %d slots "
                "(%d devices each)",
                plan.replicas, self.gen_slots, plan.devices_per_replica,
            )
        self._initialized = True
        # The host's copies of the weights (as read, converted, cast) are
        # placed on the device by now: let them go before the warm-up's
        # compiles take their own host memory.
        del state, converted, params
        if self.warmup:
            # Compile a photo library's dominant path up front: one caption
            # of an image with the shortest text bucket (the tower, the
            # prompt's prefill programs, the install, a decode block over
            # the table width such rows use). A text-only chat compiles its
            # own three programs on its first request (60 s on a v5e for a
            # 1.5B decoder, 0.2 s from the second on: PERF.md, PR 36):
            # warming those instead cost every caption deployment a second
            # set of programs it never ran (45-54 s of a compiling boot).
            t0 = time.perf_counter()
            messages, image = [ChatMessage(role="user", content=self._warmup_text())], self._warmup_image()
            self._compile_warmup(messages)
            self.generate(messages, image, max_new_tokens=1)
            logger.info("vlm warmup (image path) in %.1fs", time.perf_counter() - t0)
        logger.info(
            "VLM ready: %s layers=%d hidden=%d vision_tokens=%d",
            self.model_id,
            self.cfg.decoder.layers,
            self.cfg.decoder.hidden_size,
            self.vision_tokens,
        )

    def _compile_warmup(self, messages) -> None:
        """Compile what the warm-up caption is about to run side by side:
        the tower and splice, and the engine's lane programs up to its first
        decode block (``ContinuousScheduler.precompile``), each lowered for
        the arguments it will be called with. One after another these
        compile in about the sum of their times, side by side in about the
        longest's. Where something is left out, or a compile here fails, the
        warm-up compiles it as it runs."""
        from concurrent.futures import ThreadPoolExecutor

        if len(self._engines) != 1 or not hasattr(self._prepare, "trace"):
            return
        ids = self._encode_prompt(messages, True)
        width = self._bucket_len(len(ids))
        size = self.cfg.vision.image_size
        padded = np.full((1, width), self.cfg.pad_token_id, np.int32)
        prepare = self._prepare.trace(
            self.params, jnp.asarray(np.zeros((1, size, size, 3), np.uint8)), jnp.asarray(padded),
            jnp.asarray([len(ids)], jnp.int32),
        )
        span = prepare.out_info[0].shape[1]
        with ThreadPoolExecutor(8) as workers:
            futures = [workers.submit(lambda: prepare.lower().compile())]
            futures += self._continuous.precompile(
                span, width, len(ids) - 1 + self.vision_tokens, 1, workers
            )
            for f in futures:
                try:
                    f.result()
                except Exception:  # noqa: BLE001 - the warm-up compiles it instead
                    logger.warning("vlm warm-up: a program failed to compile ahead", exc_info=True)

    def _warmup_text(self) -> str:
        """What the warm-up asks beside its image. "hi" where rows have the
        default length. A ``max_seq`` configured past the default says the
        deployment's rows are long, so there the prompt fills the longest
        bucket: the boot then compiles the lane's chunk program, the
        install from the longest scratch and the decode block over the whole
        table, which every such row runs, in place of a short caption's
        one-shot prefill, small install and narrow block, which it may never
        run (a short request compiles those on its first use; 45 and 23 s
        less of a compiling run of the two long-context caption cells on a
        v5e: PERF.md, PR 39)."""
        if self.max_seq <= 2048 or len(self.prefill_buckets) < 2:
            return "hi"
        low, top = self.prefill_buckets[-2:]

        def tokens(words: int) -> int:
            text = " ".join(["hi"] * words)
            return len(self._encode_prompt([ChatMessage(role="user", content=text)], True, True))

        one, many = tokens(1), tokens(65)
        words = 1 + ((low + top) // 2 - one) * 64 // max(many - one, 1)
        return " ".join(["hi"] * words) if low < tokens(words) <= top else "hi"

    def _warmup_image(self) -> bytes:
        """A mid-gray JPEG at the tower's size: what the warm-up captions."""
        from PIL import Image

        size = self.cfg.vision.image_size
        buf = io.BytesIO()
        Image.new("RGB", (size, size), (128, 128, 128)).save(buf, "JPEG")
        return buf.getvalue()

    def close(self) -> None:
        if self._initialized:
            if self._engine_fleet is not None:
                # The fleet is authoritative after any unpark rebuilt an
                # engine the boot-time _engines list has no reference to.
                self._engine_fleet.close()
            else:
                for engine in self._engines:
                    engine.close()
        if fn := getattr(self, "_route_gauge_fn", None):
            metrics.unregister_gauges(f"vlm-quant:{self.model_id}", fn)
        self._initialized = False

    def _pick_engine(self):
        """Least-loaded dispatch across the per-replica continuous
        engines (queue depth + live rows + prefill lane). With a fleet
        attached, only SERVING engines are candidates — a parked engine
        stops receiving work the moment the autopilot parks it."""
        fleet = self._engine_fleet
        if fleet is not None:
            live = fleet.serving_engines()
            if live:
                return min(live, key=lambda e: e.load())
        if len(self._engines) == 1:
            return self._engines[0]
        return min(self._engines, key=lambda e: e.load())

    def kv_layout(self) -> str:
        """KV cache layout on the wire (capability ``extra``): operators
        and clients can see whether decode is paged without reading logs."""
        kv = self._continuous.kv
        return f"paged(page={kv.page_size},pages={kv.pages_total},slots={self.gen_slots})"

    def topology(self) -> dict[str, str]:
        """Device topology for the capability ``extra``: the engine fleet
        reports one replica per device slice (built through the manager
        factory)."""
        from ...runtime.fleet import topology_extra

        out = topology_extra(self.mesh)
        if len(getattr(self, "_engines", [])) > 1:
            out["replicas"] = str(len(self._engines))
        return out

    # -- prompt prep -------------------------------------------------------

    def _encode_prompt(
        self, messages: Sequence[ChatMessage], has_image: bool, add_generation_prompt: bool = True
    ) -> list[int]:
        prompt = self.tokenizer.render(messages, add_generation_prompt=add_generation_prompt)
        ids = self.tokenizer.encode(prompt)
        if has_image and self.cfg.image_token_id not in ids:
            # Template without an <image> slot: splice the placeholder up
            # front (reference requires the token to appear in the prompt,
            # ``onnxrt_backend.py:240-296``).
            ids = [self.cfg.image_token_id] + ids
        return ids

    def _bucket_len(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        raise ValueError(f"prompt of {n} tokens exceeds the largest bucket {self.prefill_buckets[-1]}")

    def _prepare_inputs(self, messages, image_bytes, add_generation_prompt: bool = True):
        has_image = bool(image_bytes)
        ids = self._encode_prompt(messages, has_image, add_generation_prompt)
        n = len(ids)
        bucket = self._bucket_len(n)
        padded = np.full((1, bucket), self.cfg.pad_token_id, np.int32)
        padded[0, :n] = ids
        length = jnp.asarray([n], jnp.int32)
        if has_image:
            decoded = get_decode_pool().run_decode(
                "vlm_canvas", image_bytes, {"size": self.cfg.vision.image_size}
            )
            try:
                # jnp.asarray copies host pixels onto the device before
                # returning, so the arena slot can recycle right after.
                embeds, positions, lengths = self._prepare(
                    self.params, jnp.asarray(decoded.array[None]),
                    jnp.asarray(padded), length,
                )
            finally:
                decoded.release()
        else:
            embeds, positions, lengths = self._prepare_text(
                self.params, jnp.asarray(padded), length
            )
        return embeds, positions, lengths, jnp.asarray(padded), n

    def _prefix_content(self, prompt_ids, n: int, image_bytes) -> "np.ndarray | None":
        """Content identity of the POST-SPLICE sequence for the prefix KV
        cache: text token ids, with the ``<image>`` placeholder expanded
        to ``V`` int64s derived from the image-bytes sha256 digest
        (``(1<<62) | digest<<14 | position`` — far above any vocab id, so
        a text prefix can never alias a vision prefix). Two requests get
        equal content exactly when their merged embedding sequences are
        byte-equal, which is what makes the cached KV pages reusable.
        None when the cache is unconfigured — no hashing on the hot path."""
        from .prefix_cache import prefix_cache_enabled

        if not prefix_cache_enabled():
            return None
        ids = np.asarray(prompt_ids)[0, :n].astype(np.int64)
        if not image_bytes:
            return ids
        pos = np.where(ids == self.cfg.image_token_id)[0]
        if pos.size == 0:
            return ids
        i = int(pos[0])
        v = self.cfg.vision.num_tokens
        digest = int.from_bytes(hashlib.sha256(image_bytes).digest()[:6], "big")
        vis = (1 << 62) + (digest << 14) + np.arange(v, dtype=np.int64)
        return np.concatenate([ids[:i], vis, ids[i + 1 :]])

    def _make_gen_request(
        self, embeds, positions, lengths, prompt_ids,
        max_new_tokens, temperature, top_p, do_sample, repetition_penalty,
        prefix_content=None,
    ):
        """One construction site for the engine's request object — adding a
        generation parameter means touching exactly here."""
        from ...utils import disagg
        from .continuous import _Request

        req = _Request(
            embeds=embeds,
            positions=positions,
            length=lengths,
            prompt_ids=prompt_ids,
            max_new=min(int(max_new_tokens), self.max_new_cap),
            temperature=float(temperature),
            top_p=float(top_p),
            do_sample=bool(do_sample),
            repetition_penalty=float(repetition_penalty),
            rng=self._next_rng(),
            prefix_content=prefix_content,
        )
        owner = disagg.current()
        if owner:
            # Disaggregated serving: the front tier pinned this
            # request's decode to a decode-lane peer; the scheduler
            # migrates the row there right after prefill.
            req.migrate_to = owner
        return req

    def _next_rng(self) -> jax.Array:
        with self._seed_lock:
            self._seed += 1
            seed = self._seed
        return jax.random.PRNGKey(seed)

    # -- generation --------------------------------------------------------

    def _cache_ns(self) -> str:
        """Result-cache namespace, qualified by compute dtype and the
        RESOLVED decode route (see
        :func:`~lumen_tpu.runtime.result_cache.make_namespace`): the
        warmup A/B can pick a different route across restarts, and an
        int8 generation must not answer for bf16 via the disk tier. A
        bf16-fallback route shares the unquantized namespace — it runs
        the identical program."""
        from ...ops.image import DECODE_POLICY

        return make_namespace(
            "vlm", "generate", self.model_id, self.info.version,
            jnp.dtype(self.policy.compute_dtype).name,
            "int8" if self.quant_route == "int8" else "",
            DECODE_POLICY,
        )

    def generate(
        self,
        messages: Sequence[ChatMessage],
        image_bytes: bytes | None = None,
        max_new_tokens: int = 256,
        temperature: float = 0.0,
        top_p: float = 1.0,
        do_sample: bool = False,
        repetition_penalty: float = 1.0,
        stop_sequences: Sequence[str] | None = None,
        add_generation_prompt: bool = True,
    ) -> GenerationResult:
        """Generate a caption/chat completion.

        Deterministic requests (greedy: ``do_sample=False`` and
        ``temperature <= 0`` — the caption-ingest default) route through
        the content-addressed result cache keyed on the raw image bytes +
        the full prompt/knob set, so a re-captioned photo skips vision
        encode, prefill and the whole decode loop; concurrent identical
        requests coalesce onto one flight. Sampled requests BYPASS the
        cache entirely — they are meant to differ run to run. Cached hits
        replay the original result verbatim, including its
        ``generation_time_ms`` metadata (the time the real computation
        took), plus a ``cached: True`` marker."""
        self._ensure_ready()
        if do_sample or temperature > 0.0:
            return self._generate_uncached(
                messages, image_bytes, max_new_tokens, temperature, top_p,
                do_sample, repetition_penalty, stop_sequences,
                add_generation_prompt,
            )
        options = {
            "messages": [(m.role, m.content) for m in messages],
            "max_new_tokens": int(max_new_tokens),
            "top_p": float(top_p),
            "repetition_penalty": float(repetition_penalty),
            "stop_sequences": list(stop_sequences) if stop_sequences else None,
            "add_generation_prompt": bool(add_generation_prompt),
        }

        def clone(result: GenerationResult) -> GenerationResult:
            import dataclasses

            return dataclasses.replace(
                result,
                tokens=list(result.tokens),
                metadata={**result.metadata, "cached": True},
            )

        # Quarantine gate on the request's content address (image bytes +
        # full prompt/knob set): a prompt+image pair that previously broke
        # the generation path is rejected before vision encode and
        # prefill. Sampled requests bypass the cache above and skip the
        # gate too — their options differ per call, so no stable
        # fingerprint exists to quarantine on.
        ns = self._cache_ns()
        payload = image_bytes or b""
        key = guarded_key(ns, options, payload)
        return get_result_cache().get_or_compute(
            ns,
            options,
            payload,
            lambda: self._generate_uncached(
                messages, image_bytes, max_new_tokens, temperature, top_p,
                do_sample, repetition_penalty, stop_sequences,
                add_generation_prompt,
            ),
            clone=clone,
            key=key,
        )

    def _generate_uncached(
        self,
        messages: Sequence[ChatMessage],
        image_bytes: bytes | None = None,
        max_new_tokens: int = 256,
        temperature: float = 0.0,
        top_p: float = 1.0,
        do_sample: bool = False,
        repetition_penalty: float = 1.0,
        stop_sequences: Sequence[str] | None = None,
        add_generation_prompt: bool = True,
    ) -> GenerationResult:
        t0 = time.perf_counter()
        embeds, positions, lengths, prompt_ids, n_input = self._prepare_inputs(
            messages, image_bytes, add_generation_prompt
        )
        req = self._make_gen_request(
            embeds, positions, lengths, prompt_ids,
            max_new_tokens, temperature, top_p, do_sample, repetition_penalty,
            prefix_content=self._prefix_content(prompt_ids, n_input, image_bytes),
        )
        row_tokens, n_gen, stopped_eos = self._pick_engine().submit(req).result()
        tokens = [int(t) for t in row_tokens[:n_gen]]
        text = self.tokenizer.decode(tokens)
        finish = "eos_token" if stopped_eos else "length"
        text, hit = _truncate_on_stop(text, stop_sequences)
        if hit:
            finish = "stop_sequence"
        dt_ms = (time.perf_counter() - t0) * 1e3
        meta = {
            "temperature": temperature,
            "top_p": top_p,
            "repetition_penalty": repetition_penalty,
            "do_sample": do_sample,
            "generation_time_ms": round(dt_ms, 2),
            "tokens_per_second": round(n_gen / max(dt_ms / 1e3, 1e-9), 2),
        }
        meta.update(_reuse_meta(req))
        return GenerationResult(
            text=text.strip(),
            tokens=tokens,
            finish_reason=finish,
            input_tokens=n_input,
            metadata=meta,
        )

    def generate_stream(
        self,
        messages: Sequence[ChatMessage],
        image_bytes: bytes | None = None,
        max_new_tokens: int = 256,
        temperature: float = 0.0,
        top_p: float = 1.0,
        do_sample: bool = False,
        repetition_penalty: float = 1.0,
        stop_sequences: Sequence[str] | None = None,
        add_generation_prompt: bool = True,
    ) -> Iterator[GenerationChunk]:
        """Incremental generation: yields text deltas as tokens arrive
        (true streaming — the reference collects all chunks into one
        response, ``fastvlm_service.py:492-506``)."""
        self._ensure_ready()
        t0 = time.perf_counter()
        # Hold back enough text that a stop sequence straddling a chunk
        # boundary can still be cut before emission.
        holdback = max((len(s) for s in stop_sequences), default=1) - 1 if stop_sequences else 0
        embeds, positions, lengths, prompt_ids, n_input = self._prepare_inputs(
            messages, image_bytes, add_generation_prompt
        )
        tokens: list[int] = []
        emitted = ""
        finish = "length"
        final_text: str | None = None
        # Time-to-first-emitted-chunk + per-stream decode rate, observed
        # at the source (this generator feeds both the gRPC stream path
        # and direct callers): cumulative histograms for /metrics,
        # rolling-window twins via the telemetry tee inside observe().
        first_emit_s: float | None = None

        def _note_first_emit() -> None:
            nonlocal first_emit_s
            if first_emit_s is None:
                first_emit_s = time.perf_counter()
                metrics.observe("vlm.ttft", (first_emit_s - t0) * 1e3)
        req = self._make_gen_request(
            embeds, positions, lengths, prompt_ids,
            max_new_tokens, temperature, top_p, do_sample, repetition_penalty,
            prefix_content=self._prefix_content(prompt_ids, n_input, image_bytes),
        )
        for tok in self._pick_engine().submit_stream(req):
            tokens.append(tok)
            if tok == self.cfg.eos_token_id:
                finish = "eos_token"
                break
            text = self.tokenizer.decode(tokens)
            # Byte-level BPE can split a multi-byte character across
            # tokens: the partial decode ends in U+FFFD and is not a
            # prefix of the next decode. Emit only stable prefixes.
            if text.endswith("�"):
                continue
            if stop_sequences:
                truncated, hit = _truncate_on_stop(text, stop_sequences)
                if hit:
                    finish = "stop_sequence"
                    final_text = truncated
                    break
            if not text.startswith(emitted):
                continue  # transient divergence; wait for re-extension
            delta = text[len(emitted) : max(len(text) - holdback, len(emitted))]
            if delta:
                emitted += delta
                _note_first_emit()
                yield GenerationChunk(text=delta, tokens=[tok])
        if final_text is None:
            final_text = self.tokenizer.decode(tokens)
        # Flush the held-back tail so the stream equals generate().
        if final_text.startswith(emitted) and len(final_text) > len(emitted):
            tail = final_text[len(emitted) :]
            emitted = final_text
            _note_first_emit()
            yield GenerationChunk(text=tail, tokens=[])
        dt_ms = (time.perf_counter() - t0) * 1e3
        meta = {
            "finish_reason": finish,
            "generated_tokens": len(tokens),
            "input_tokens": n_input,
            "generation_time_ms": round(dt_ms, 2),
        }
        if tokens:
            tps = len(tokens) / max(dt_ms / 1e3, 1e-9)
            # Histogram buckets are ms-labeled but dimensionless; this
            # series carries tokens/s (documented in OBSERVABILITY.md).
            metrics.observe("vlm.decode_tps", tps)
            meta["tokens_per_second"] = round(tps, 2)
        if first_emit_s is not None:
            meta["ttft_ms"] = round((first_emit_s - t0) * 1e3, 2)
        meta.update(_reuse_meta(req))
        yield GenerationChunk(text="", tokens=[], is_final=True, metadata=meta)

    # -- utils -------------------------------------------------------------

    def _ensure_ready(self) -> None:
        if not self._initialized:
            raise RuntimeError("VLMManager.initialize() not called")


def _flat_shapes(tree, prefix=""):
    out = {}
    for k, v in (tree or {}).items():
        if isinstance(v, dict):
            out.update(_flat_shapes(v, f"{prefix}{k}/"))
        else:
            out[f"{prefix}{k}"] = tuple(v.shape)
    return out


def _subtree_matches(sub, ref) -> bool:
    """True when ``sub`` carries exactly the leaves/shapes of ``ref`` — the
    checkpoint genuinely ships this subtree (not a partial or absent one)."""
    if not isinstance(sub, dict) or not sub:
        return False
    return _flat_shapes(sub) == _flat_shapes(ref)


def _reuse_meta(req) -> dict:
    """Per-request prefix-reuse / speculation outcomes for response
    metadata. Keys appear only when the engine actually recorded the
    feature for this request — an unconfigured engine's metadata is
    byte-identical to the pre-feature build."""
    out: dict[str, Any] = {}
    hit = getattr(req, "prefix_hit", None)
    if hit is not None:
        out["prefix_hit"] = round(hit, 3)
    proposed = getattr(req, "spec_proposed", 0)
    if proposed > 0:
        out["spec_accept_rate"] = round(req.spec_accepted / proposed, 3)
    drafts = getattr(req, "mtp_drafts", None)
    if drafts:
        # a drafting decoder's drafts, by the index of the emitted token each
        # stood for, as space-separated ids (metadata values are scalars):
        # -1 where none did (the first token; one after an accepted draft,
        # which the step sampled itself)
        out["mtp_drafts"] = " ".join(str(drafts.get(i, -1)) for i in range(max(drafts) + 1))
    return out


def _truncate_on_stop(text: str, stop_sequences: Sequence[str] | None) -> tuple[str, bool]:
    """Cut at the earliest stop sequence (reference ``stop_on_sequences``,
    ``backends/base.py:530-541``)."""
    if not stop_sequences:
        return text, False
    best = -1
    for stop in stop_sequences:
        idx = text.find(stop)
        if idx != -1 and (best == -1 or idx < best):
            best = idx
    if best == -1:
        return text, False
    return text[:best], True
