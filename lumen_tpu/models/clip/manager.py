"""CLIP model manager: embeddings + zero-shot classification on TPU.

Business-logic layer mirroring the reference's ``CLIPModelManager``
(``packages/lumen-clip/src/lumen_clip/general_clip/clip_model.py:48-403``)
and ``BioCLIPModelManager`` (``expert_bioclip/bioclip_model.py:45-375``),
rebuilt around jitted Flax towers behind micro-batchers:

- image/text encode are batched device calls (bucketed static shapes), not
  per-request session runs;
- classification is a device-side matmul against a resident label-embedding
  matrix (softmax mode for curated label sets; raw-cosine mode for huge
  taxonomies, the BioCLIP behavior at ``bioclip_model.py:310-316``);
- label embeddings load from the dataset's precomputed ``.npy`` or are
  computed on startup from labels via prompt templates
  (``clip_model.py:145-172``).
"""

from __future__ import annotations

import json
import logging
import os
import time
import weakref
from dataclasses import dataclass
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from ...core.model_info import ModelInfo, load_model_info
from ...ops.image import (
    IMAGENET_MEAN,
    IMAGENET_STD,
    OPENAI_CLIP_MEAN,
    OPENAI_CLIP_STD,
    decode_image_bytes,
)
from ...runtime.batcher import MicroBatcher, mesh_buckets, mesh_sharded, warmup_batcher
from ...runtime.decode_pool import get_decode_pool
from ...runtime.fleet import (
    batcher_name,
    build_fleet,
    each_batcher,
    plan_replicas,
    replicate_all,
    topology_extra,
)
from ...runtime.quarantine import guarded_key
from ...runtime.result_cache import get_result_cache, make_namespace
from ...runtime.policy import get_policy
from ...runtime.weights import load_state_dict
from ...utils.metrics import metrics
from .convert import convert_clip_checkpoint
from .modeling import CLIPConfig, CLIPModel
from .tokenizer import ClipTokenizer

logger = logging.getLogger(__name__)

# Scene-classify contract: the reference's 8 hardcoded prompts and its
# label derivation (prompt minus "a photo of " minus "an ") are part of the
# observable output (``clip_model.py:90-99`` builds them, ``:355-357``
# derives the label), so clients see identical scene buckets on both
# stacks. These short strings are wire-contract constants, like proto
# field names.
SCENE_PROMPTS = [
    "a photo of a person",
    "a photo of an animal",
    "a photo of a vehicle",
    "a photo of food",
    "a photo of a building",
    "a photo of nature",
    "a photo of an object",
    "a photo of a landscape",
]
SCENE_LABELS = [
    p.replace("a photo of ", "").replace("an ", "") for p in SCENE_PROMPTS
]
DEFAULT_PROMPT_TEMPLATE = "a photo of a {}"


@dataclass
class ClassifyResult:
    labels: list[tuple[str, float]]  # (label, score) best-first


class CLIPManager:
    """One loaded CLIP model + its datasets, ready to serve."""

    def __init__(
        self,
        model_dir: str,
        dataset: str | None = None,
        dtype: str = "bfloat16",
        batch_size: int = 8,
        max_batch_latency_ms: float = 5.0,
        mesh_axes: dict[str, int] | None = None,
        classify_mode: Literal["softmax", "cosine"] = "softmax",
        warmup: bool = False,
        quantize: str | None = None,  # None | "int8" (W8A8 tower blocks)
        name_prefix: str = "clip",
    ):
        if quantize not in (None, "int8"):
            raise ValueError(f"quantize must be None or 'int8', got {quantize!r}")
        self.quantize = quantize
        # Batcher/gauge name scope: "clip" for the default manager (the
        # historical names — dashboards don't move), the config alias for
        # siblings (a bioclip manager's batchers are "bioclip-image"/
        # "bioclip-text", so two managers in one service never collide on
        # gauges or replica-fleet state keys).
        self.name_prefix = name_prefix
        self.model_dir = model_dir
        self.dataset_name = dataset
        self.classify_mode = classify_mode
        self.policy = get_policy(dtype)
        self.batch_size = batch_size
        self.max_batch_latency_ms = max_batch_latency_ms
        # Replica fleet (LUMEN_REPLICAS / LUMEN_REPLICAS_CLIP): the host's
        # devices partition into N slices, one mesh per replica; the plan
        # is the single all-device mesh of every pre-fleet PR when N=1.
        # ``self.mesh`` stays the primary (replica-0) mesh — shape logic,
        # quant-route timing and label embedding all run there.
        self.fleet_plan = plan_replicas("clip", mesh_axes)
        self.mesh = self.fleet_plan.meshes[0]
        from ...ops.quant_matmul import note_mesh_model_axis

        # TP x int8: pl.pallas_call has no GSPMD sharding rule, so a
        # model-axis mesh must keep QDense on the XLA dequant fallback.
        note_mesh_model_axis(dict(self.mesh.shape).get("model", 1))
        self.warmup = warmup
        self.info: ModelInfo = load_model_info(model_dir)
        # (vision, text) ClipTowerGraph when graph-served; the probed flag
        # memoizes a negative probe so non-graph models scan the dir once.
        self._graphs = None
        self._graphs_probed = False
        self.cfg = self._build_config(model_dir)
        # Deployment override for the serving-side text pad length (e.g. a
        # BERT-text model whose queries are known-short).
        tsl = self.info.extra("text_serving_length")
        if tsl:
            import dataclasses

            self.cfg = dataclasses.replace(self.cfg, text_serving_length=int(tsl))
        if self.quantize:
            import dataclasses

            from ...ops.quant import resolve_q8_kernel

            # Unlike the VLM decoder (bandwidth-bound -> dequant default),
            # batch embedding is MXU-compute-bound: default to the W8A8
            # "dynamic" kernel, which runs a native int8 dot at ~2x the
            # bf16 MXU rate. Same env knob for on-chip A/Bs.
            self.cfg = dataclasses.replace(
                self.cfg,
                weight_quant=self.quantize,
                weight_quant_kernel=resolve_q8_kernel("dynamic"),
            )
        self.model = CLIPModel(self.cfg)
        self.model_id = self.info.name
        # Serving route actually in use ("bf16" | "int8"): int8 is opt-in
        # via `quantize` AND verified — q8 measured 0.923x bf16 on v5e
        # (round-5 chip run, 2026-08-02, older than the ledger), so a
        # warmup-timed A/B may fall the route back.
        self.quant_route = "bf16"
        self.quant_speedup: float | None = None  # measured q8/bf16, when timed
        self._initialized = False
        self._image_batcher: MicroBatcher | None = None  # or ReplicaSet (fleet)
        self._text_batcher: MicroBatcher | None = None
        self._fleet_params: list | None = None  # per-replica param placements
        self.label_names: list[str] = []
        self._label_matrix: jax.Array | None = None  # [L, D] unit-norm fp32

    # -- configuration ----------------------------------------------------

    def _build_config(self, model_dir: str) -> CLIPConfig:
        cfg_path = os.path.join(model_dir, "config.json")
        if os.path.exists(cfg_path):
            with open(cfg_path, "r", encoding="utf-8") as f:
                raw = json.load(f)
            if "vision_config" in raw:
                return CLIPConfig.from_hf(raw)
        # openclip-style config (open_clip_config.json) — reference loader
        # distinguishes the two the same way (resources/loader.py:186-204).
        oc_path = os.path.join(model_dir, "open_clip_config.json")
        if os.path.exists(oc_path):
            with open(oc_path, "r", encoding="utf-8") as f:
                raw = json.load(f).get("model_cfg", {})
            from .modeling import TowerConfig

            v, t = raw.get("vision_cfg", {}), raw.get("text_cfg", {})
            return CLIPConfig(
                embed_dim=raw.get("embed_dim", 512),
                image_size=v.get("image_size", 224),
                patch_size=v.get("patch_size", 32),
                vision=TowerConfig(v.get("width", 768), v.get("layers", 12), v.get("width", 768) // 64),
                text=TowerConfig(t.get("width", 512), t.get("layers", 12), t.get("heads", 8)),
                vocab_size=t.get("vocab_size", 49408),
                context_length=t.get("context_length", 77),
            )
        # No tower config at all: an exported-ONNX repo (e.g. MobileCLIP2
        # exports, the region=other default — reference serves these as its
        # primary dual-session path, ``onnxrt_backend.py:72-745``). Derive
        # the serving shapes from the graphs themselves.
        graphs = self._load_graphs(model_dir)
        if graphs is not None:
            vision_graph, text_graph = graphs
            vshape = next(iter(vision_graph.module.input_shapes().values()), ())
            size = vshape[-1] if len(vshape) == 4 and isinstance(vshape[-1], int) and vshape[-1] > 0 else 224
            return CLIPConfig(
                embed_dim=int(self.info.embedding_dim or 512),
                image_size=int(size),
                context_length=text_graph.context_length(77),
            )
        raise FileNotFoundError(
            f"no config.json / open_clip_config.json / onnx towers in {model_dir}"
        )

    def _load_graphs(self, model_dir: str):
        """Probe for exported vision+text towers; memoized on self (both
        outcomes, so a non-graph model scans the directory only once)."""
        if self._graphs_probed:
            return self._graphs
        self._graphs_probed = True
        from .graph import ClipTowerGraph, find_clip_onnx

        found = find_clip_onnx(model_dir, precision=self.info.extra("precision"))
        if "vision" in found and "text" in found:
            self._graphs = (
                ClipTowerGraph.from_path(found["vision"]),
                ClipTowerGraph.from_path(found["text"]),
            )
        return self._graphs

    @property
    def norm_stats(self) -> tuple[tuple[float, ...], tuple[float, ...]]:
        """Normalization stats; OpenAI-CLIP defaults unless the model name
        suggests ImageNet stats (reference heuristic, loader.py:101-139)."""
        name = self.info.name.lower()
        if "bioclip" in name or "imagenet" in (self.info.extra("norm", "") or ""):
            return IMAGENET_MEAN, IMAGENET_STD
        return OPENAI_CLIP_MEAN, OPENAI_CLIP_STD

    # -- initialization ---------------------------------------------------

    def initialize(self) -> None:
        if self._initialized:
            return
        from ...parallel.sharding import replicate

        mean, std = self.norm_stats
        compute_dtype = self.policy.compute_dtype
        backend = str(self.info.extra("clip_backend", "auto") or "auto")

        state = None
        if backend != "graph" and (self._graphs is None or backend == "native"):
            # clip_backend=native must reach for a real checkpoint even when
            # _build_config already derived a graph config (export-only dir).
            try:
                logger.info("loading CLIP weights from %s", self.model_dir)
                state = load_state_dict(self.model_dir)
            except FileNotFoundError:
                if backend == "native" or self._load_graphs(self.model_dir) is None:
                    raise
                logger.info("no native CLIP checkpoint; serving onnx towers")
        if backend == "graph" and self._load_graphs(self.model_dir) is None:
            raise FileNotFoundError(
                f"clip_backend=graph but no vision/text onnx in {self.model_dir}"
            )
        if state is None and self.quantize:
            # Covers EVERY graph-served route (export-only dirs probed at
            # config build, clip_backend=graph, and the no-checkpoint
            # fallback above): an operator who set int8 must not attribute
            # full-precision ONNX numbers to the quantized path.
            logger.warning(
                "quantize=%s ignored: the ONNX graph path runs the exported "
                "precision as-is", self.quantize,
            )

        if state is not None:
            # The shape gate runs against the UNQUANTIZED module tree
            # (checkpoints carry kernels); quantization rewrites matching
            # kernels to (q, scale) afterwards, on the cast weights.
            import dataclasses

            base_model = (
                CLIPModel(dataclasses.replace(self.cfg, weight_quant=None))
                if self.quantize else self.model
            )
            init = jax.eval_shape(
                lambda: base_model.init(
                    jax.random.PRNGKey(0),
                    jnp.zeros((1, self.cfg.image_size, self.cfg.image_size, 3), jnp.float32),
                    jnp.zeros((1, self.cfg.context_length), jnp.int32),
                )["params"]
            )
            params = convert_clip_checkpoint(state, init)
            params = self.policy.cast_params(params)
            qparams = None
            if self.quantize == "int8":
                # A bf16 route pin skips quantization entirely — an
                # operator who pinned away from the q8 regression must not
                # pay a full-checkpoint quantization at every boot just to
                # discard it.
                if os.environ.get("LUMEN_CLIP_Q8_ROUTE", "auto").lower() == "bf16":
                    logger.info(
                        "CLIP quantize=int8 overridden to bf16 "
                        "(LUMEN_CLIP_Q8_ROUTE); skipping quantization"
                    )
                else:
                    from .convert import quantize_clip_int8

                    qparams = quantize_clip_int8(
                        params, include_text=self.cfg.text_arch != "bert"
                    )

            def place(p, quantized: bool, mesh=None):
                # DP serving: params replicated over the mesh; micro-batches
                # are data-sharded so one batched call spreads across every
                # device (trivial placement on a 1-device mesh). A mesh with
                # a ``model`` axis additionally tensor-parallelizes the
                # towers (both towers are standard transformers, so the
                # shared TP rules apply — SURVEY §2.8). Replica fleets call
                # this once per replica mesh: every slice gets its own full
                # (or TP-sharded) copy of the winning params.
                mesh = self.mesh if mesh is None else mesh
                if dict(mesh.shape).get("model", 1) > 1:
                    from ...parallel.sharding import (
                        INT8_TP_RULES,
                        TRANSFORMER_TP_RULES,
                        shard_params,
                    )

                    rules = (INT8_TP_RULES if quantized else []) + TRANSFORMER_TP_RULES
                    return shard_params(p, mesh, rules)
                return replicate(p, mesh)

            def make_encoders(model):
                @jax.jit
                def encode_images(params, pixels_u8):
                    # pixels_u8: [B, S, S, 3] uint8 (resized on host or
                    # device-resized upstream); normalize + cast on device.
                    x = pixels_u8.astype(jnp.float32) / 255.0
                    x = (x - jnp.asarray(mean)) / jnp.asarray(std)
                    z = model.apply(
                        {"params": params},
                        x.astype(compute_dtype),
                        method=lambda m, px: m.encode_image(px),
                    )
                    return z  # fp32 unit-norm

                @jax.jit
                def encode_texts(params, ids):
                    return model.apply(
                        {"params": params}, ids, method=lambda m, i: m.encode_text(i)
                    )

                return encode_images, encode_texts

            if qparams is None:
                self.model = base_model
                self.params = place(params, quantized=False)
                self._fleet_params = [self.params] + [
                    place(params, quantized=False, mesh=m)
                    for m in self.fleet_plan.meshes[1:]
                ]
                encode_images, encode_texts = make_encoders(base_model)
            else:
                encode_images, encode_texts = self._pick_quant_route(
                    base_model, params, qparams, place, make_encoders
                )

        else:
            # Graph towers: the exporter's own weights as XLA programs; the
            # manager normalizes outputs host-of-device-side exactly like
            # the reference session path (``onnxrt_backend.py:486-489``).
            import dataclasses

            vision_graph, text_graph = self._graphs
            # Reconcile serving shapes with the exports' STATIC shapes even
            # when a config.json supplied the cfg (a text export built at
            # 52 tokens cannot run 77-padded ids; the vision export's input
            # side fixes the resize target).
            vshape = next(iter(vision_graph.module.input_shapes().values()), ())
            updates: dict = {}
            if len(vshape) == 4 and isinstance(vshape[-1], int) and vshape[-1] > 0:
                updates["image_size"] = int(vshape[-1])
            ctx = text_graph.context_length(self.cfg.context_length)
            updates["context_length"] = ctx
            # A static export runs at exactly its built length — any pad
            # cap (config- OR model_info-supplied) shorter than that would
            # feed shapes the graph's fixed ops can't take.
            updates["text_serving_length"] = None
            dim = vision_graph.probe_dim(
                np.zeros(
                    (1, 3, updates.get("image_size", self.cfg.image_size),
                     updates.get("image_size", self.cfg.image_size)), np.float32
                )
            )
            if dim != self.cfg.embed_dim:
                logger.info("graph towers emit %d-d embeddings (config said %d)", dim, self.cfg.embed_dim)
                updates["embed_dim"] = dim
            if updates:
                self.cfg = dataclasses.replace(self.cfg, **updates)
            host_tree = {
                "vision": dict(vision_graph.module.params),
                "text": dict(text_graph.module.params),
            }
            self.params = replicate(host_tree, self.mesh)
            # Every replica mesh gets its copy BEFORE the host weights are
            # released (there is nothing to re-place from afterwards).
            self._fleet_params = replicate_all(
                host_tree, self.fleet_plan, primary=self.params
            )
            # The jitted closures only need the graph TOPOLOGY; drop the
            # host-RAM weight copies (params AND the aliasing initializers)
            # now that the mesh holds them.
            vision_graph.module.release_weights()
            text_graph.module.release_weights()

            @jax.jit
            def encode_images(params, pixels_u8):
                x = pixels_u8.astype(jnp.float32) / 255.0
                x = (x - jnp.asarray(mean)) / jnp.asarray(std)
                z = vision_graph(params["vision"], x.transpose(0, 3, 1, 2))
                z = z.astype(jnp.float32)
                return z / jnp.maximum(jnp.linalg.norm(z, axis=-1, keepdims=True), 1e-12)

            @jax.jit
            def encode_texts(params, ids):
                z = text_graph(params["text"], ids).astype(jnp.float32)
                return z / jnp.maximum(jnp.linalg.norm(z, axis=-1, keepdims=True), 1e-12)

        self.tokenizer = ClipTokenizer.from_model_dir(self.model_dir, self.cfg.serving_text_length)
        self._encode_images = encode_images
        self._encode_texts = encode_texts

        dp = self.mesh.shape.get("data", 1)
        buckets = mesh_buckets(self.batch_size, dp)

        # Batcher fns DISPATCH and return the un-fetched device array: the
        # MicroBatcher's fetch worker does the one blocking device->host
        # transfer per batch, so the next batch stacks/transfers/dispatches
        # while this one computes (the pipelined serving data path). Each
        # replica closes over ITS mesh slice's param placement; build_fleet
        # hands back the plain single batcher (today's exact path) when the
        # fleet plan is one replica, a routed ReplicaSet otherwise. The
        # closures double as the fleet's revive hook: a wedged replica gets
        # a fresh batcher over the same placed params.
        def build_image(rid, mesh):
            params = self._fleet_params[rid or 0]
            return MicroBatcher(
                mesh_sharded(
                    lambda pixels, n, _p=params: self._encode_images(_p, pixels),
                    mesh,
                ),
                max_batch=buckets[-1],
                max_latency_ms=self.max_batch_latency_ms,
                buckets=buckets,
                name=batcher_name(f"{self.name_prefix}-image", rid),
                replica=None if rid is None else f"r{rid}",
            ).start()

        def build_text(rid, mesh):
            params = self._fleet_params[rid or 0]
            return MicroBatcher(
                mesh_sharded(
                    lambda ids, n, _p=params: self._encode_texts(_p, ids),
                    mesh,
                ),
                max_batch=buckets[-1],
                max_latency_ms=self.max_batch_latency_ms,
                buckets=buckets,
                name=batcher_name(f"{self.name_prefix}-text", rid),
                replica=None if rid is None else f"r{rid}",
            ).start()

        self._image_batcher = build_fleet(
            self.fleet_plan, f"{self.name_prefix}-image", build_image
        )
        self._text_batcher = build_fleet(
            self.fleet_plan, f"{self.name_prefix}-text", build_text
        )

        self._load_label_embeddings()
        if self.warmup:
            self._warmup(buckets)
        if self.quantize:
            # The chosen route is operator-visible state, not a log line:
            # "is this deployment actually serving int8?" must be
            # answerable from /metrics (gauge ``int8_active``, plus the
            # measured ``q8_speedup_pct`` when the warmup A/B ran).
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
            metrics.register_gauges(f"clip-quant:{self.model_id}", _route_gauges)
        self._initialized = True
        logger.info(
            "CLIP ready: %s embed_dim=%d labels=%d",
            self.model_id,
            self.cfg.embed_dim,
            len(self.label_names),
        )

    def _warmup(self, buckets: list[int]) -> None:
        """Compile every batch bucket at startup so first requests don't pay
        compile time (SURVEY.md §7 hard part 2: the reference's "load time"
        becomes our "compile time" — spend it before serving). Runs through
        the batchers' own callables so the cache is guaranteed to hit."""
        t0 = time.perf_counter()
        size = self.cfg.image_size
        for b in each_batcher(self._image_batcher):
            warmup_batcher(b, lambda n: np.zeros((n, size, size, 3), np.uint8))
        for b in each_batcher(self._text_batcher):
            warmup_batcher(
                b, lambda n: np.zeros((n, self.cfg.serving_text_length), np.int32)
            )
        logger.info("warmup: %d bucket(s) compiled in %.1fs", len(buckets), time.perf_counter() - t0)

    def close(self) -> None:
        if self._image_batcher:
            self._image_batcher.close()
        if self._text_batcher:
            self._text_batcher.close()
        if fn := getattr(self, "_route_gauge_fn", None):
            metrics.unregister_gauges(f"clip-quant:{self.model_id}", fn)
        self._initialized = False

    def topology(self) -> dict[str, str]:
        """Device topology + replica layout for the capability ``extra``
        (fleet-internal clients pick endpoints from this, not by probing)."""
        return topology_extra(self.mesh, self._image_batcher, self._text_batcher)

    # -- quantization route ------------------------------------------------

    def _pick_quant_route(self, base_model, params, qparams, place, make_encoders):
        """Decide whether the explicit int8 opt-in actually serves int8.

        The W8A8 dynamic kernel measured 0.923x bf16 on v5e (round-5 chip
        run, 2026-08-02, older than the ledger) —
        a *regression* the operator opting into "int8" almost certainly
        did not want. So when warmup is on, the two routes run a one-shot
        timed A/B at the top serving bucket and the loser's params are
        dropped; int8 only survives when it measures at least even. With
        warmup off there is nothing to time against, so the explicit
        config wins as-is. ``LUMEN_CLIP_Q8_ROUTE=int8|bf16`` pins the
        route (skips the A/B); ``auto`` (default) is the behavior above.
        Returns the chosen ``(encode_images, encode_texts)`` pair and sets
        ``self.model`` / ``self.params`` / ``self.quant_route``.
        """
        q_model = self.model  # built with weight_quant in __init__
        # A "bf16" pin never reaches here — initialize() skips the
        # quantization entirely in that case, so qparams is None and the
        # non-quantized path runs instead.
        route = os.environ.get("LUMEN_CLIP_Q8_ROUTE", "auto").lower()
        if route not in ("auto", "int8"):
            logger.warning("ignoring malformed LUMEN_CLIP_Q8_ROUTE=%r", route)
            route = "auto"
        if route == "auto" and not self.warmup:
            route = "int8"  # no warmup pass to time against: honor the opt-in
        if route == "int8":
            self.quant_route = "int8"
            self.params = place(qparams, quantized=True)
            self._fleet_params = [self.params] + [
                place(qparams, quantized=True, mesh=m)
                for m in self.fleet_plan.meshes[1:]
            ]
            return make_encoders(q_model)

        # One-shot warmup A/B, timed SEQUENTIALLY so peak HBM stays at one
        # tower set plus activations — memory-tight deployments quantize
        # precisely because bf16 barely fits, and a transient 2x at boot
        # would OOM exactly them. The loser's placement is freed before
        # the winner's (the q8 measurement's placement is reused when q8
        # wins; a bf16 win pays one extra host->device transfer).
        enc_bf16 = make_encoders(base_model)
        enc_q8 = make_encoders(q_model)
        params_bf16 = place(params, quantized=False)
        t_bf16 = self._time_image_encode(enc_bf16[0], params_bf16)
        del params_bf16  # free the bf16 placement before placing q8
        params_q8 = place(qparams, quantized=True)
        t_q8 = self._time_image_encode(enc_q8[0], params_q8)
        self.quant_speedup = t_bf16 / max(t_q8, 1e-9)
        if self.quant_speedup >= 1.0:
            logger.info(
                "CLIP int8 route confirmed: %.3fx bf16 at batch bucket",
                self.quant_speedup,
            )
            self.quant_route = "int8"
            self.params = params_q8
            self._fleet_params = [self.params] + [
                place(qparams, quantized=True, mesh=m)
                for m in self.fleet_plan.meshes[1:]
            ]
            return enc_q8
        logger.warning(
            "CLIP int8 route DISABLED: warmup A/B measured q8 at %.3fx bf16 "
            "(a regression); serving bf16 instead. Pin LUMEN_CLIP_Q8_ROUTE="
            "int8 to force.",
            self.quant_speedup,
        )
        metrics.count("clip_q8_fallbacks")
        self.quant_route = "bf16"
        self.model = base_model
        del params_q8
        self.params = place(params, quantized=False)
        self._fleet_params = [self.params] + [
            place(params, quantized=False, mesh=m)
            for m in self.fleet_plan.meshes[1:]
        ]
        return enc_bf16

    def _time_image_encode(self, encode, placed_params) -> float:
        """Best-of-3 wall time for one image-encode at the top serving
        bucket, inputs placed exactly like serving traffic (data-sharded)
        so the compiles land in the same cache the batcher warmup hits."""
        from ...runtime.mesh import data_sharding

        dp = self.mesh.shape.get("data", 1)
        bucket = mesh_buckets(self.batch_size, dp)[-1]
        size = self.cfg.image_size
        x = jax.device_put(
            np.zeros((bucket, size, size, 3), np.uint8), data_sharding(self.mesh)
        )
        jax.block_until_ready(encode(placed_params, x))  # compile off the clock
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(encode(placed_params, x))
            times.append(time.perf_counter() - t0)
        return min(times)

    # -- datasets ---------------------------------------------------------

    def _load_label_embeddings(self) -> None:
        if not self.dataset_name or not self.info.datasets:
            return
        ds = self.info.datasets.get(self.dataset_name)
        if ds is None:
            logger.warning("dataset %r not in model_info; classify disabled", self.dataset_name)
            return
        labels_path = os.path.join(self.model_dir, ds.labels)
        with open(labels_path, "r", encoding="utf-8") as f:
            raw_labels = json.load(f)
        self.label_names = [self._label_text(entry) for entry in raw_labels]
        emb_path = os.path.join(self.model_dir, ds.embeddings)
        if os.path.exists(emb_path):
            mat = np.load(emb_path, mmap_mode="r")
            mat = np.asarray(mat, np.float32)
            # Axis-order autodetect (reference: bioclip_model.py:287-309).
            if mat.shape[0] != len(self.label_names) and mat.shape[-1] == len(self.label_names):
                mat = mat.T
            if mat.shape[0] != len(self.label_names):
                raise ValueError(
                    f"label embedding shape {mat.shape} does not match "
                    f"{len(self.label_names)} labels"
                )
        else:
            logger.info("no precomputed label embeddings; encoding %d labels", len(self.label_names))
            mat = self._compute_label_embeddings(self.label_names)
        mat = mat / np.maximum(np.linalg.norm(mat, axis=-1, keepdims=True), 1e-12)
        self._label_matrix = jnp.asarray(mat)

    @staticmethod
    def _label_text(entry) -> str:
        """Dataset label entries are either plain strings or BioCLIP-style
        ``[[taxonomy...], common_name]`` pairs (reference name extraction,
        ``bioclip_model.py:192-217``)."""
        if isinstance(entry, str):
            return entry
        if isinstance(entry, (list, tuple)) and len(entry) == 2:
            taxonomy, common = entry
            if isinstance(common, str) and common:
                return common
            if isinstance(taxonomy, (list, tuple)) and taxonomy:
                return str(taxonomy[-1])
        return str(entry)

    def _compute_label_embeddings(self, labels: list[str], template: str = DEFAULT_PROMPT_TEMPLATE) -> np.ndarray:
        out = []
        bs = max(self.batch_size, 16)
        for i in range(0, len(labels), bs):
            chunk = [template.format(l) for l in labels[i : i + bs]]
            ids = self.tokenizer.encode_batch(chunk)
            out.append(np.asarray(self._encode_texts(self.params, jnp.asarray(ids))))
        return np.concatenate(out, axis=0)

    # -- inference API ----------------------------------------------------

    def _cache_ns(self, task: str, *qualifiers: str) -> str:
        """Result-cache namespace (see
        :func:`~lumen_tpu.runtime.result_cache.make_namespace`). Qualified
        by the compute dtype AND the resolved quant route — the warmup A/B
        can pick a different route across restarts, and disk-tier entries
        from one precision must not answer for another. Image tasks add
        the decode-policy qualifier (scaled decode changes resampling
        numerics across deploy generations)."""
        return make_namespace(
            "clip", task, self.model_id, self.info.version,
            jnp.dtype(self.policy.compute_dtype).name, self.quant_route,
            *qualifiers,
        )

    def encode_image(self, image_bytes: bytes) -> np.ndarray:
        """Single image bytes -> unit-norm fp32 embedding (batched under the
        hood with concurrent callers). Content-addressed cache first: the
        sha256 runs on the RAW bytes, so a hit (or a coalesced duplicate
        in flight) skips decode pool AND batcher entirely — identical
        re-index / duplicate-burst traffic costs one device call total.
        The same content address is the poison-quarantine gate: bytes that
        previously made a batch fail are rejected HERE — before the decode
        pool, admission queue and device — and it rides the batcher submit
        as the fingerprint bisection quarantines on.
        On a miss, decode+resize run on the shared decode pool — the
        calling (gRPC handler) thread only waits, so decode concurrency is
        bounded by ``LUMEN_DECODE_WORKERS``, not by however many handler
        threads pile in. Every hit returns a private copy: a caller
        mutating "its" embedding in place must not poison the store."""
        self._ensure_ready()
        from ...ops.image import DECODE_POLICY

        payload = bytes(image_bytes)
        ns = self._cache_ns("image_embed", DECODE_POLICY)
        key = guarded_key(ns, None, payload)
        return get_result_cache().get_or_compute(
            ns,
            None,
            payload,
            lambda: self._encode_image_uncached(image_bytes, fingerprint=key),
            clone=np.copy,
            key=key,
        )

    def _encode_image_uncached(
        self, image_bytes: bytes, fingerprint: str | None = None
    ) -> np.ndarray:
        # The "clip_resize" decode spec (scaled decode + square squash,
        # lumen_tpu.utils.host_decode) runs on the shared pool — in
        # process mode that is a worker process writing into a
        # shared-memory arena slot, and `decoded.array` is a zero-copy
        # view the batcher's collector stacks from directly; release()
        # recycles the slot once the batcher has settled (the collector
        # copied the row into its staging arena before dispatch).
        size = self.cfg.image_size
        decoded = get_decode_pool().run_decode(
            "clip_resize", image_bytes, {"size": size}
        )
        try:
            vec = self._image_batcher(decoded.array, fingerprint=fingerprint)
        finally:
            decoded.release()
        return self._check_vector(vec)

    def tensor_input_shape(self) -> tuple[int, int, int]:
        """The pre-decoded pixel tensor this manager accepts on the
        ``tensor/raw`` wire path: exactly what the ``clip_resize`` decode
        spec produces, so tensor- and JPEG-path results are identical."""
        size = self.cfg.image_size
        return (size, size, 3)

    def encode_image_tensor(self, pixels: np.ndarray, raw: bytes | None = None) -> np.ndarray:
        """Pre-decoded tensor -> unit-norm embedding: the zero-decode
        serving path. ``pixels`` must be the uint8 (size, size, 3) tensor
        the capability's input spec advertises; it goes STRAIGHT to the
        batcher — no decode pool, no resize. ``raw`` is the wire payload
        backing ``pixels`` (the same buffer, so passing it avoids a
        re-serialization); the result cache keys on sha256 of that raw
        buffer, hashed exactly once — the same single-hash guarantee the
        JPEG path has, under a ``tensor``-qualified namespace (raw pixels
        and JPEG bytes of one image are different byte strings and must
        never answer for each other)."""
        self._ensure_ready()
        size = self.cfg.image_size
        if pixels.dtype != np.uint8 or tuple(pixels.shape) != (size, size, 3):
            raise ValueError(
                f"tensor input must be uint8 of shape ({size}, {size}, 3); "
                f"got {pixels.dtype} {tuple(pixels.shape)}"
            )
        payload = raw if raw is not None else pixels.tobytes()
        ns = self._cache_ns("image_embed", "tensor")
        key = guarded_key(ns, None, payload)
        return get_result_cache().get_or_compute(
            ns,
            None,
            payload,
            lambda: self._check_vector(
                self._image_batcher(np.ascontiguousarray(pixels), fingerprint=key)
            ),
            clone=np.copy,
            key=key,
        )

    def encode_text(self, text: str) -> np.ndarray:
        self._ensure_ready()
        payload = text.encode("utf-8")
        ns = self._cache_ns("text_embed")
        key = guarded_key(ns, None, payload)
        return get_result_cache().get_or_compute(
            ns,
            None,
            payload,
            lambda: self._encode_text_uncached(text, fingerprint=key),
            clone=np.copy,
            key=key,
        )

    def _encode_text_uncached(self, text: str, fingerprint: str | None = None) -> np.ndarray:
        ids = self.tokenizer.encode_batch([text])[0]
        vec = self._text_batcher(ids, fingerprint=fingerprint)
        return self._check_vector(vec)

    def classify_image(self, image_bytes: bytes, top_k: int = 5) -> ClassifyResult:
        self._ensure_ready()
        if self._label_matrix is None:
            raise RuntimeError("no dataset loaded; classification unavailable")
        vec = self.encode_image(image_bytes)
        return self._classify_vector(vec, self.label_names, self._label_matrix, top_k)

    def classify_scene(self, image_bytes: bytes, top_k: int = 3) -> ClassifyResult:
        self._ensure_ready()
        if not hasattr(self, "_scene_matrix"):
            # The full prompts embed verbatim (template already baked in);
            # labels are their reference-derived short forms.
            mat = self._compute_label_embeddings(SCENE_PROMPTS, template="{}")
            mat = mat / np.maximum(np.linalg.norm(mat, axis=-1, keepdims=True), 1e-12)
            self._scene_matrix = jnp.asarray(mat)
        vec = self.encode_image(image_bytes)
        # Reference scene scoring is a plain softmax over raw cosine
        # similarities (``clip_model.py:344-350``) — no logit-scale
        # temperature, unlike classify_image.
        return self._classify_vector(
            vec, SCENE_LABELS, self._scene_matrix, top_k, temperature=1.0
        )

    def _classify_vector(
        self,
        vec: np.ndarray,
        names: list[str],
        matrix: jax.Array,
        top_k: int,
        temperature: float | None = None,
    ) -> ClassifyResult:
        sims = np.asarray(matrix @ jnp.asarray(vec))  # cosine: both unit-norm
        top_k = min(top_k, len(names))
        idx = np.argpartition(-sims, top_k - 1)[:top_k]
        idx = idx[np.argsort(-sims[idx])]
        if self.classify_mode == "cosine" and temperature is None:
            # Raw similarity scores (BioCLIP large-taxonomy behavior). An
            # explicitly pinned temperature (the scene path's 1.0) always
            # means softmax — even on a cosine-mode manager.
            scores = sims[idx]
        else:
            # Temperature-scaled stable softmax over ALL labels
            # (reference: clip_model.py:232-317; temperature = logit scale
            # unless the caller pins one, e.g. the scene path's 1.0).
            if temperature is None:
                temperature = self.temperature()
            logits = sims * temperature
            logits -= logits.max()
            probs = np.exp(logits)
            probs /= probs.sum()
            scores = probs[idx]
        return ClassifyResult(labels=[(names[i], float(s)) for i, s in zip(idx, scores)])

    # -- utils ------------------------------------------------------------

    def _ensure_ready(self) -> None:
        if not self._initialized:
            raise RuntimeError("CLIPManager.initialize() not called")

    @staticmethod
    def _check_vector(vec: np.ndarray) -> np.ndarray:
        vec = np.asarray(vec, np.float32)
        if not np.isfinite(vec).all():
            raise ValueError("model produced non-finite embedding")
        n = np.linalg.norm(vec)
        if n < 1e-6:
            raise ValueError("model produced zero-norm embedding")
        return vec / n

    def temperature(self) -> float:
        """Exported logit scale (exp'd). Graph-served towers carry no
        logit_scale param — ONNX exports don't ship the temperature, same
        as the reference's session path whose ``get_temperature`` is
        optional (``base.py:254-270``) — so the fallback chain is
        model_info ``extra.logit_scale`` then the CLIP-standard 100."""
        if "logit_scale" in self.params:
            return float(np.exp(np.asarray(self.params["logit_scale"], np.float32)))
        extra = self.info.extra("logit_scale")
        if extra is not None:
            return float(np.exp(float(extra)))
        return 100.0
