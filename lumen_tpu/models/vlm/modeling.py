"""VLM in Flax: ViT vision encoder + Qwen2-style causal decoder with a
preallocated static KV cache.

Replaces the reference's three opaque ONNX sessions (vision.onnx +
embed.onnx + decoder.onnx, ``packages/lumen-vlm/src/lumen_vlm/backends/
onnxrt_backend.py:107-140``) with explicit modules. The decisive TPU change
is the cache: the reference grows numpy KV tensors by concat every step
(``onnxrt_backend.py:731-755``, ``:319-320``); here the cache is a
statically-shaped ``[B, kv_heads, max_seq, head_dim]`` buffer updated in
place with ``lax.dynamic_update_slice`` so the whole decode loop compiles
into one XLA program (see ``generate.py``).

Architecture notes (TPU-first, not a translation):
- decoder: RoPE + GQA + RMSNorm + SwiGLU — the Qwen2 family layout that
  FastVLM's language model uses (image token id 151646 is in the Qwen2
  vocab, reference ``onnxrt_backend.py:240-296``);
- vision: a plain ViT over large patches + 2-layer MLP projector
  (LLaVA-style). The reference's hybrid-conv FastViTHD exists to make CPUs
  fast; on TPU a patchified transformer keeps everything on the MXU;
- the image-token splice (reference ``_merge_embeddings:240-296``) is a
  fully jittable gather — no host round-trip, static output length.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ...ops.attention import _count_route, attention, attention_cached, paged_attention, repeat_kv
from ...ops.quant import QDense
from ...utils.trace import SCOPE_KV_WRITE

FULL_ATTENTION = "full_attention"
WINDOW_ATTENTION = "sliding_attention"
#: the two kinds of a hybrid decoder (``granitemoehybrid``'s own words): a
#: Mamba-2 mixer, whose row state is recurrent and holds no page, and a
#: grouped-query attention layer over K/V pages
MAMBA = "mamba"
ATTENTION = "attention"
LATENT_KINDS = (FULL_ATTENTION, WINDOW_ATTENTION)


@dataclass(frozen=True)
class YarnScaling:
    """YaRN (``rope_scaling`` of ``type`` ``yarn``, the DeepSeek-V3 family's
    reading): each rotary frequency is a blend of ``theta^(-2i/d)`` and the
    same over ``factor``, by a linear ramp in ``i`` between the two
    correction dimensions (the pairs that turn ``beta_fast`` and
    ``beta_slow`` times over ``original_max`` positions); cos and sin are
    multiplied by ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``
    and the softmax scale by ``mscale(factor, mscale_all_dim) ** 2``, with
    ``mscale(s, m) = 0.1 m ln s + 1``."""

    factor: float
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    original_max: int = 4096
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @classmethod
    def from_hf(cls, r: dict | None) -> "YarnScaling | None":
        if not r:
            return None
        kind = r.get("type", r.get("rope_type"))
        if kind != "yarn":
            raise NotImplementedError(f"rope_scaling of type {kind!r}")
        return cls(
            factor=float(r["factor"]), beta_fast=float(r.get("beta_fast", 32)),
            beta_slow=float(r.get("beta_slow", 1)),
            original_max=int(r["original_max_position_embeddings"]),
            mscale=float(r.get("mscale", 1)), mscale_all_dim=float(r.get("mscale_all_dim", 0)),
        )

    @staticmethod
    def get_mscale(factor: float, m: float) -> float:
        return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0

    def inv_freq(self, dim: int, theta: float) -> np.ndarray:
        """The ``dim // 2`` blended frequencies, float64."""
        def correction(turns: float) -> float:
            return dim * math.log(self.original_max / (turns * 2 * math.pi)) / (2 * math.log(theta))

        low = max(math.floor(correction(self.beta_fast)), 0)
        high = min(math.ceil(correction(self.beta_slow)), dim - 1)
        plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
        ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0.0, 1.0)
        return plain / self.factor * ramp + plain * (1.0 - ramp)

    @property
    def rotation_scale(self) -> float:
        """What cos and sin are multiplied by."""
        return self.get_mscale(self.factor, self.mscale) / self.get_mscale(self.factor, self.mscale_all_dim)

    @property
    def softmax_scale(self) -> float:
        """What the score scale is multiplied by."""
        return self.get_mscale(self.factor, self.mscale_all_dim) ** 2 if self.mscale_all_dim else 1.0


@dataclass(frozen=True)
class LatentDims:
    """Sizes of one kind of latent attention layer: low-rank query and
    key/value projections, ``heads`` x (``nope`` no-position + ``rope``
    RoPE) query/key values a head, ``v_dim`` value values a head; the
    rotation's base and, where the model scales it, its YaRN settings."""

    heads: int
    q_lora: int
    kv_lora: int
    nope: int
    rope: int
    v_dim: int
    rope_theta: float
    rope_scaling: YarnScaling | None = None

    @property
    def scale(self) -> float:
        base = 1.0 / float(self.nope + self.rope) ** 0.5
        return base if self.rope_scaling is None else base * self.rope_scaling.softmax_scale


@dataclass(frozen=True)
class DecoderConfig:
    hidden_size: int = 896
    layers: int = 24
    heads: int = 14
    kv_heads: int = 2
    intermediate_size: int = 4864
    vocab_size: int = 151936
    head_dim: int | None = None  # None -> hidden_size // heads
    rope_theta: float = 1_000_000.0
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 32768
    tie_word_embeddings: bool = True
    # --- Mixture-of-experts decoder (Qwen2-MoE layout; 0 experts = dense).
    # MoE layers replace the SwiGLU MLP with a top-k routed expert bank;
    # layer i is sparse iff (i+1) % moe_every == 0 (HF decoder_sparse_step
    # semantics). Routing uses exact capacity (no token drops) so outputs
    # match dense-gather reference implementations token-for-token.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_intermediate_size: int | None = None  # None -> intermediate_size
    moe_shared_intermediate: int = 0  # >0 adds Qwen2-MoE's shared expert
    moe_every: int = 1
    moe_norm_topk: bool = True
    moe_dense_layers: tuple[int, ...] = ()  # HF mlp_only_layers: force-dense
    # --- Weight-only int8 quantization for the decoder's attention + MLP
    # projections (per-output-channel scales). Decode at small batch is
    # HBM-bandwidth-bound: the per-step cost is streaming the weights, so
    # int8 halves the dominant traffic vs bf16. Embeddings (gather +
    # tied lm_head), norms, and MoE expert banks stay full precision.
    # Set by the serving layer (backend_settings.quantize), not by
    # checkpoints — see ``quantize_decoder_int8`` in convert.py.
    weight_quant: str | None = None  # None | "int8"
    # How the int8 projections execute:
    #   "dequant"  — y = (x @ q.astype(bf16)) * scale; relies on XLA fusing
    #                the convert into the dot's operand read.
    #   "dynamic"  — W8A8-dynamic: per-token symmetric activation quant
    #                feeds the MXU a NATIVE int8 x int8 -> int32 dot (no
    #                weight convert at all; v5e runs int8 at 2x bf16 rate).
    # The first on-chip measurement found "dequant" pathologically slow
    # (20 tok/s vs 3896 bf16 — the convert lowered to non-vectorized
    # code), so both formulations ship and the bench A/Bs them.
    weight_quant_kernel: str = "dequant"  # "dequant" | "dynamic"
    # --- Latent attention decoder (``model_type`` ``dots3_note``, ``axk1``).
    # Empty ``layer_types`` = the Qwen2 layout above, untouched. Otherwise
    # one kind a layer: "full_attention" layers use ``latent_full`` and see
    # every causal key, or, where the decoder has an indexer
    # (``index_heads`` > 0), the ``index_topk`` causal keys of largest
    # learned index score; "sliding_attention" layers use ``latent_window``
    # and see the last ``sliding_window`` keys, the token itself included.
    # What a layer has beside the projections is said here, not by a model's
    # name: ``latent_gate`` passes every head's output through a sigmoid
    # gate computed from the layer's normed input; ``latent_rescale``
    # multiplies the normed latents by sqrt(hidden / rank); a
    # ``LatentDims.rope_scaling`` scales the rotation (YaRN). The cache
    # holds one latent row a token (``init_paged_kv_cache``), never K/V per
    # head.
    layer_types: tuple[str, ...] = ()
    latent_full: LatentDims | None = None
    latent_window: LatentDims | None = None
    sliding_window: int = 0
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    latent_rescale: bool = False
    latent_gate: bool = False
    # --- Routing rule and the held share of the bank (``parallel.moe``):
    # "sigmoid" scores with a learned selection bias, gates renormalised
    # over the selected and scaled; an ungated shared expert. ``moe_held``
    # = (lo, hi): this chip holds experts lo..hi-1 of ``moe_experts`` (the
    # router's width) and computes their part of the layer alone.
    # ``moe_n_group`` groups of consecutive experts, of which a token keeps
    # the ``moe_topk_group`` best before it selects (one group: no limit).
    moe_scoring: str = "softmax"
    moe_select_bias: bool = False
    moe_routed_scale: float = 1.0
    moe_shared_gated: bool = True
    moe_held: tuple[int, int] | None = None
    moe_n_group: int = 1
    moe_topk_group: int = 1
    # --- Hybrid decoder (``model_type`` ``granitemoehybrid``): ``layer_types``
    # of "mamba" and "attention". A mamba layer is a Mamba-2 mixer
    # (``Mamba2Mixer``: ``mamba_heads`` heads of ``mamba_head_dim``, a state
    # of ``mamba_state`` a head value, one B and C shared by every head
    # (``from_hf`` refuses more groups), a causal depthwise convolution of
    # ``mamba_conv`` taps,
    # ``mamba_chunk`` the block of the chunked scan); an attention layer is
    # ``DecoderAttention`` as the three switches below leave it. The four
    # multipliers are Granite's: token embeddings, each residual branch, the
    # attention scores (``attn_scale``) and the logits' divisor.
    mamba_heads: int = 0
    mamba_head_dim: int = 0
    mamba_state: int = 0
    mamba_conv: int = 4
    mamba_chunk: int = 256
    attn_rope: bool = True  # False: no rotation ("nope")
    attn_bias: bool = True  # q/k/v bias (Qwen2 has it)
    attn_scale: float | None = None  # None -> head_dim ** -0.5
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0

    @property
    def dim_per_head(self) -> int:
        return self.head_dim or self.hidden_size // self.heads

    @property
    def latent(self) -> bool:
        return any(k in LATENT_KINDS for k in self.layer_types)

    @property
    def indexer(self) -> bool:
        """Whether the full latent layers pick their keys with an indexer."""
        return self.index_heads > 0

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def mamba_conv_dim(self) -> int:
        """Channels the convolution runs over: x, B and C side by side."""
        return self.mamba_inner + 2 * self.mamba_state

    def layer_kind(self, i: int) -> str:
        """Layer ``i``'s kind; a decoder that names none is all grouped-query
        attention (the Qwen2 layout)."""
        return self.layer_types[i] if self.layer_types else ATTENTION

    def layers_of(self, kind: str) -> int:
        return sum(1 for k in self.layer_types if k == kind)

    def is_moe_layer(self, i: int) -> bool:
        return (
            self.moe_experts > 0
            and i not in self.moe_dense_layers
            and (i + 1) % self.moe_every == 0
        )


@dataclass(frozen=True)
class VisionTowerConfig:
    image_size: int = 1024
    patch_size: int = 64
    width: int = 768
    layers: int = 12
    heads: int = 12
    mean: tuple[float, float, float] = (0.0, 0.0, 0.0)
    std: tuple[float, float, float] = (1.0, 1.0, 1.0)

    @property
    def num_tokens(self) -> int:
        return (self.image_size // self.patch_size) ** 2


@dataclass(frozen=True)
class VLMConfig:
    decoder: DecoderConfig = field(default_factory=DecoderConfig)
    vision: VisionTowerConfig = field(default_factory=VisionTowerConfig)
    #: Qwen2 `<image>` placeholder id (reference IMAGE_TOKEN_ID,
    #: ``onnxrt_backend.py:240-296``).
    image_token_id: int = 151646
    bos_token_id: int = 151643
    eos_token_id: int = 151645
    pad_token_id: int = 151643

    @classmethod
    def tiny(cls) -> "VLMConfig":
        """Small config for CPU tests."""
        return cls(
            decoder=DecoderConfig(
                hidden_size=32,
                layers=2,
                heads=4,
                kv_heads=2,
                intermediate_size=64,
                vocab_size=256,
                rope_theta=10_000.0,
                max_position_embeddings=128,
            ),
            vision=VisionTowerConfig(image_size=32, patch_size=16, width=48, layers=2, heads=4),
            image_token_id=250,
            bos_token_id=1,
            eos_token_id=2,
            pad_token_id=0,
        )

    @classmethod
    def from_hf(cls, cfg: dict[str, Any]) -> "VLMConfig":
        """Build from an HF LLaVA-style ``config.json`` (``text_config`` +
        ``vision_config``) or a flat Qwen2-style decoder config."""
        text = cfg.get("text_config", cfg)
        vis = cfg.get("vision_config", {})
        if text.get("model_type") == "dots3_note":
            decoder = _dots3_decoder(text)
        elif text.get("model_type") == "axk1":
            decoder = _axk1_decoder(text)
        elif text.get("model_type") == "granitemoehybrid":
            decoder = _granite_decoder(text)
        else:
            decoder = cls._qwen2_decoder(cfg, text)
        return cls._with_tower(cfg, text, vis, decoder)

    @staticmethod
    def _qwen2_decoder(cfg: dict[str, Any], text: dict[str, Any]) -> DecoderConfig:
        return DecoderConfig(
            hidden_size=text.get("hidden_size", 896),
            layers=text.get("num_hidden_layers", 24),
            heads=text.get("num_attention_heads", 14),
            kv_heads=text.get("num_key_value_heads", text.get("num_attention_heads", 14)),
            intermediate_size=text.get("intermediate_size", 4864),
            vocab_size=text.get("vocab_size", 151936),
            head_dim=text.get("head_dim"),
            rope_theta=text.get("rope_theta", 1_000_000.0),
            rms_norm_eps=text.get("rms_norm_eps", 1e-6),
            max_position_embeddings=text.get("max_position_embeddings", 32768),
            tie_word_embeddings=text.get("tie_word_embeddings", cfg.get("tie_word_embeddings", True)),
            # Qwen2-MoE config keys (absent on dense checkpoints).
            moe_experts=text.get("num_experts", 0),
            # HF Qwen2MoeConfig defaults num_experts_per_tok to 4.
            moe_top_k=text.get("num_experts_per_tok", 4 if text.get("num_experts", 0) else 2),
            moe_intermediate_size=text.get("moe_intermediate_size"),
            moe_shared_intermediate=text.get("shared_expert_intermediate_size", 0),
            moe_every=text.get("decoder_sparse_step", 1),
            # HF Qwen2MoeConfig defaults norm_topk_prob to False.
            moe_norm_topk=text.get("norm_topk_prob", not text.get("num_experts", 0)),
            moe_dense_layers=tuple(text.get("mlp_only_layers", ())),
        )

    @classmethod
    def _with_tower(cls, cfg, text, vis, decoder: DecoderConfig) -> "VLMConfig":
        vision = VisionTowerConfig(
            image_size=vis.get("image_size", 1024),
            patch_size=vis.get("patch_size", 64),
            width=vis.get("hidden_size", 768),
            layers=vis.get("num_hidden_layers", 12),
            heads=vis.get("num_attention_heads", 12),
            mean=tuple(vis.get("image_mean", (0.0, 0.0, 0.0))),
            std=tuple(vis.get("image_std", (1.0, 1.0, 1.0))),
        )
        return cls(
            decoder=decoder,
            vision=vision,
            image_token_id=cfg.get("image_token_index", cfg.get("image_token_id", 151646)),
            bos_token_id=text.get("bos_token_id", 151643),
            eos_token_id=text.get("eos_token_id", 151645),
            pad_token_id=text.get("pad_token_id", text.get("bos_token_id", 151643)),
        )


def _latent_moe_decoder(t: dict[str, Any], **fields) -> DecoderConfig:
    """What the latent decoders' configurations share (the DeepSeek-V3
    family's keys): the full layers' ``LatentDims``, leading dense layers,
    routed experts with one ungated shared expert. ``n_routed_experts``
    counts the experts HELD here; ``ep_size`` chips share each layer
    (default 1: the whole bank), so the router is ``n_routed_experts *
    ep_size`` wide and this chip, ``ep_rank``, holds the range ``[rank * n,
    (rank + 1) * n)``. ``fields`` are the caller's own."""
    held, ep, rank = t["n_routed_experts"], t.get("ep_size", 1), t.get("ep_rank", 0)
    return DecoderConfig(
        hidden_size=t["hidden_size"],
        layers=t["num_hidden_layers"],
        heads=t["num_attention_heads"],
        kv_heads=t.get("num_key_value_heads", t["num_attention_heads"]),
        intermediate_size=t["intermediate_size"],
        vocab_size=t["vocab_size"],
        rope_theta=float(t["rope_theta"]),
        tie_word_embeddings=t.get("tie_word_embeddings", False),
        moe_experts=held * ep,
        moe_top_k=t["num_experts_per_tok"],
        moe_intermediate_size=t["moe_intermediate_size"],
        moe_shared_intermediate=t.get("n_shared_experts", 0) * t["moe_intermediate_size"],
        moe_every=t.get("moe_layer_freq", 1),
        moe_norm_topk=t.get("norm_topk_prob", True),
        moe_dense_layers=tuple(range(t.get("first_k_dense_replace", 0))),
        latent_full=LatentDims(
            heads=t["num_attention_heads"], q_lora=t["q_lora_rank"], kv_lora=t["kv_lora_rank"],
            nope=t["qk_nope_head_dim"], rope=t["qk_rope_head_dim"], v_dim=t["v_head_dim"],
            rope_theta=float(t["rope_theta"]), rope_scaling=YarnScaling.from_hf(t.get("rope_scaling")),
        ),
        moe_routed_scale=float(t.get("routed_scaling_factor", 1.0)),
        moe_shared_gated=False,
        moe_held=(rank * held, (rank + 1) * held),
        **fields,
    )


def _dots3_decoder(t: dict[str, Any]) -> DecoderConfig:
    """``model_type`` ``dots3_note``: latent attention of two kinds by
    ``layer_types``, an indexer in the full layers, a gate a head, leading
    dense layers, sigmoid-routed experts with one ungated shared expert
    (:func:`_latent_moe_decoder`)."""
    n = t["num_hidden_layers"]
    kinds = tuple(t["layer_types"][:n])
    if len(kinds) != n or set(kinds) - {FULL_ATTENTION, WINDOW_ATTENTION}:
        raise ValueError(f"layer_types must name {n} full_attention/sliding_attention layers, got {kinds}")
    return _latent_moe_decoder(
        t,
        rms_norm_eps=t.get("rms_norm_eps", 1e-5),
        max_position_embeddings=t.get("max_position_embeddings", 32768),
        layer_types=kinds,
        latent_window=LatentDims(
            heads=t["swa_num_attention_heads"], q_lora=t["swa_q_lora_rank"],
            kv_lora=t["swa_kv_lora_rank"], nope=t["swa_qk_nope_head_dim"],
            rope=t["swa_qk_rope_head_dim"], v_dim=t["swa_v_head_dim"],
            rope_theta=float(t["swa_rope_theta"]),
        ),
        sliding_window=t["sliding_window_size"],
        index_heads=t["index_n_heads"],
        index_head_dim=t["index_head_dim"],
        index_topk=t["index_topk"],
        latent_rescale=bool(t.get("apply_mla_qkv_lora_rescale", False)),
        latent_gate=True,
        moe_scoring=t.get("scoring_func", "softmax"),
        moe_select_bias=t.get("topk_method") == "noaux_tc",
    )


def _axk1_decoder(t: dict[str, Any]) -> DecoderConfig:
    """``model_type`` ``axk1`` (the DeepSeek-V3 layer): every layer latent
    attention over every causal key (no indexer, no window, no gate) under a
    YaRN-scaled rotation, leading dense layers, sigmoid-routed experts
    selected within the ``topk_group`` best of ``n_group`` groups, one
    ungated shared expert (:func:`_latent_moe_decoder`). The router adds
    ``e_score_correction_bias`` for selection whatever ``topk_method`` says
    (``transformers``' ``DeepseekV3TopkRouter`` reads no such key; a zero
    bias is the rule without one)."""
    groups = t.get("n_group", 1)
    width = t["n_routed_experts"] * t.get("ep_size", 1)
    if width % groups:
        raise ValueError(f"{width} routed experts do not divide into n_group {groups}")
    return _latent_moe_decoder(
        t,
        rms_norm_eps=t.get("rms_norm_eps", 1e-6),
        max_position_embeddings=t.get("max_position_embeddings", 131072),
        layer_types=(FULL_ATTENTION,) * t["num_hidden_layers"],
        moe_scoring=t.get("scoring_func", "sigmoid"),
        moe_select_bias=True,
        moe_n_group=groups,
        moe_topk_group=t.get("topk_group", groups),
    )


def _granite_decoder(t: dict[str, Any]) -> DecoderConfig:
    """``model_type`` ``granitemoehybrid``: Mamba-2 and grouped-query layers
    by ``layer_types``, no rotation, softmax over the selected experts'
    logits, an ungated shared expert, the four multipliers.
    ``num_local_experts`` counts the experts HELD here; ``ep_size`` chips
    share each layer (default 1: the whole bank), as for ``dots3_note``."""
    n = t["num_hidden_layers"]
    kinds = tuple(t.get("layer_types", (ATTENTION,) * n)[:n])
    if len(kinds) != n or set(kinds) - {MAMBA, ATTENTION}:
        raise ValueError(f"layer_types must name {n} mamba/attention layers, got {kinds}")
    if t.get("mamba_n_groups", 1) != 1:
        raise NotImplementedError("a Mamba-2 mixer with more than one B/C group")
    if t.get("position_embedding_type", "nope") != "nope":
        raise NotImplementedError(f"position_embedding_type {t['position_embedding_type']!r} in a hybrid decoder")
    held, ep, rank = t.get("num_local_experts", 0), t.get("ep_size", 1), t.get("ep_rank", 0)
    return DecoderConfig(
        hidden_size=t["hidden_size"],
        layers=n,
        heads=t["num_attention_heads"],
        kv_heads=t.get("num_key_value_heads", t["num_attention_heads"]),
        intermediate_size=t["intermediate_size"],
        vocab_size=t["vocab_size"],
        rope_theta=float(t.get("rope_theta", 10000.0)),
        rms_norm_eps=t.get("rms_norm_eps", 1e-5),
        max_position_embeddings=t.get("max_position_embeddings", 131072),
        tie_word_embeddings=t.get("tie_word_embeddings", True),
        moe_experts=held * ep,
        moe_top_k=t.get("num_experts_per_tok", 0),
        moe_intermediate_size=t["intermediate_size"],
        moe_shared_intermediate=t.get("shared_intermediate_size", 0),
        moe_norm_topk=True,  # softmax over the selected logits
        moe_shared_gated=False,
        moe_held=(rank * held, (rank + 1) * held) if held else None,
        layer_types=kinds,
        mamba_heads=t["mamba_n_heads"],
        mamba_head_dim=t["mamba_d_head"],
        mamba_state=t["mamba_d_state"],
        mamba_conv=t.get("mamba_d_conv", 4),
        mamba_chunk=t.get("mamba_chunk_size", 256),
        attn_rope=False,
        attn_bias=bool(t.get("attention_bias", False)),
        attn_scale=float(t["attention_multiplier"]) if "attention_multiplier" in t else None,
        embedding_multiplier=float(t.get("embedding_multiplier", 1.0)),
        residual_multiplier=float(t.get("residual_multiplier", 1.0)),
        logits_scaling=float(t.get("logits_scaling", 1.0)),
    )


# -- KV cache ---------------------------------------------------------------


def _recurrent_cache(d: DecoderConfig, rows: int, dtype) -> dict:
    """One Mamba layer's row state, led by row (a batch row of a scratch, a
    slot of the pool), never by page: ``conv`` the last ``mamba_conv - 1``
    inputs of the convolution, token-major ``[rows, K-1, channels]`` (HF
    keeps ``[rows, channels, K]``: channels on the lanes tile whole), and
    ``ssm`` the scan's state ``[rows, d_state, heads * head_dim]`` in
    float32 (``ops.ssm``)."""
    return {
        "conv": jnp.zeros((rows, d.mamba_conv - 1, d.mamba_conv_dim), dtype),
        "ssm": jnp.zeros((rows, d.mamba_state, d.mamba_inner), jnp.float32),
    }


def _layer_cache(d: DecoderConfig, i: int, lead: tuple[int, int], rows: int, dtype) -> dict:
    """Layer ``i``'s cache over ``lead`` = (batch, max_seq) or (pages, page)
    for the kinds that keep a row a token, over ``rows`` (batch rows or
    slots) for a Mamba layer, which keeps a state a row."""
    kind = d.layer_kind(i)
    if kind in LATENT_KINDS:
        return _latent_cache(d, i, lead, dtype)
    if kind == MAMBA:
        return _recurrent_cache(d, rows, dtype)
    shape = (lead[0], d.kv_heads, lead[1], d.dim_per_head)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_kv_cache(cfg: VLMConfig, batch: int, max_seq: int, dtype=jnp.bfloat16) -> list[dict]:
    """Preallocated per-layer cache: the reference's zero-length grow-by-
    concat cache (``onnxrt_backend.py:731-755``) becomes a fixed buffer.
    A Mamba layer's entry is its row state (:func:`_recurrent_cache`)."""
    d = cfg.decoder
    return [_layer_cache(d, i, (batch, max_seq), batch, dtype) for i in range(d.layers)]


def _latent_cache(d: DecoderConfig, i: int, lead: tuple[int, int], dtype) -> dict:
    """One latent layer's cache over ``lead`` = (pages, page) or (batch,
    max_seq): the normed latent row ``c`` and the rotated position key ``r``
    a token; a full layer with an indexer also keeps its index key ``ik``."""
    full = d.layer_kind(i) == FULL_ATTENTION
    dims = d.latent_full if full else d.latent_window
    cache = {
        "c": jnp.zeros((*lead, dims.kv_lora), dtype),
        "r": jnp.zeros((*lead, dims.rope), dtype),
    }
    if full and d.indexer:
        cache["ik"] = jnp.zeros((*lead, d.index_head_dim), dtype)
    return cache


def init_paged_kv_cache(
    cfg: VLMConfig, pages: int, page_size: int, dtype=jnp.bfloat16,
    window_pages: int | None = None, slots: int = 0,
) -> list[dict]:
    """Per-layer PAGED cache: a pool of ``pages`` fixed-size pages shared
    by every decode row, addressed through per-row block tables
    (``models/vlm/paged_kv.py``) instead of one contiguous ``max_seq``
    region per slot. Page 0 is the reserved dump page.

    A latent decoder's pages hold latent rows (``[pages, page, width]``), and
    its window layers draw theirs from an id space of their own,
    ``window_pages`` large (``paged_kv.WindowPages``): they free pages behind
    the window while the full layers keep theirs. A Mamba layer holds no
    page: its entry is one row of state a SLOT (``slots`` of them,
    :func:`_recurrent_cache`), beside the pages of the attention layers."""
    d = cfg.decoder
    return [
        _layer_cache(
            d, i, (window_pages if d.layer_kind(i) == WINDOW_ATTENTION else pages, page_size), slots, dtype
        )
        for i in range(d.layers)
    ]


# -- modules ----------------------------------------------------------------


def _dense(cfg: DecoderConfig, features: int, name: str, use_bias: bool, dtype):
    """Dense factory for decoder projections: honors ``weight_quant``."""
    if cfg.weight_quant == "int8":
        return QDense(
            features, use_bias=use_bias, kernel_mode=cfg.weight_quant_kernel, name=name
        )
    return nn.Dense(features, use_bias=use_bias, name=name, dtype=dtype)


class RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (x32 * scale.astype(jnp.float32)).astype(x.dtype)


def rope_rotate(
    x: jax.Array, positions: jax.Array, theta: float, scaling: YarnScaling | None = None
) -> jax.Array:
    """Rotary embedding, HF half-split convention. ``x``: [B, H, S, D],
    ``positions``: [B, S] absolute token positions. ``scaling``: the YaRN
    frequencies and cos/sin factor in place of the plain ones."""
    d = x.shape[-1]
    if scaling is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    else:
        inv_freq = jnp.asarray(scaling.inv_freq(d, theta), jnp.float32)
    angles = positions[:, None, :, None].astype(jnp.float32) * inv_freq  # [B,1,S,D/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scaling is not None and scaling.rotation_scale != 1.0:
        cos, sin = cos * scaling.rotation_scale, sin * scaling.rotation_scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return rotated.astype(x.dtype)


class DecoderAttention(nn.Module):
    cfg: DecoderConfig

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        positions: jax.Array,
        cache: dict | None,
        cache_offset: jax.Array | None,
        kv_valid_len: jax.Array,
        block_tables: jax.Array | None = None,
    ) -> tuple[jax.Array, dict | None]:
        """``x``: [B, S, hidden]. With a cache, new K/V are written at
        ``cache_offset`` (scalar slot index; prefill uses 0, decode uses the
        current length) and attention runs against the full cache buffer
        masked to ``kv_valid_len`` [B] live slots.

        With ``block_tables`` [B, max_pages], the cache is PAGED
        (``{"k"/"v": [num_pages, kv_heads, page, dh]}``): the single new
        token's K/V lands in the page+slot its row's table maps
        ``cache_offset`` to, and attention runs the ragged paged kernel
        (exact XLA gather reference off-TPU) over the row's pages only."""
        c = self.cfg
        b, s, _ = x.shape
        dh = c.dim_per_head
        q = _dense(c, c.heads * dh, "q_proj", c.attn_bias, x.dtype)(x)
        k = _dense(c, c.kv_heads * dh, "k_proj", c.attn_bias, x.dtype)(x)
        v = _dense(c, c.kv_heads * dh, "v_proj", c.attn_bias, x.dtype)(x)
        q = q.reshape(b, s, c.heads, dh).transpose(0, 2, 1, 3)
        k = k.reshape(b, s, c.kv_heads, dh).transpose(0, 2, 1, 3)
        v = v.reshape(b, s, c.kv_heads, dh).transpose(0, 2, 1, 3)
        if c.attn_rope:
            q = rope_rotate(q, positions, c.rope_theta)
            k = rope_rotate(k, positions, c.rope_theta)
        scale = c.attn_scale  # None: the ops' own head_dim ** -0.5

        if block_tables is not None:
            page = cache["k"].shape[2]
            off = jnp.asarray(cache_offset, jnp.int32)  # [B] write position
            bidx = jnp.arange(b)
            new_k, new_v = cache["k"], cache["v"]
            # Rows own their decode-frontier pages exclusively, so the
            # scatter indices are unique across live rows; free/done rows
            # dump into page 0. s > 1 is the speculative verify window:
            # token t of every row lands at its row's position off+t
            # (static unroll — W is small and fixed per program).
            with jax.named_scope(SCOPE_KV_WRITE):
                for t in range(s):
                    off_t = off + t
                    page_idx = block_tables[bidx, off_t // page]  # [B] page ids
                    slot = off_t % page
                    new_k = new_k.at[page_idx, :, slot].set(k[:, :, t].astype(new_k.dtype))
                    new_v = new_v.at[page_idx, :, slot].set(v[:, :, t].astype(new_v.dtype))
            cache = {"k": new_k, "v": new_v}
            if s == 1:
                out = paged_attention(
                    q[:, :, 0],
                    new_k.astype(x.dtype),
                    new_v.astype(x.dtype),
                    block_tables,
                    kv_valid_len,
                    scale=scale,
                )[:, :, None, :]
            else:
                # [B, W, H, dh] query selects the variable-query-length
                # verify path; kv_valid_len stays the t=0 visibility and
                # window slot t sees kv_valid_len + t keys in-kernel.
                out = paged_attention(
                    q.transpose(0, 2, 1, 3),
                    new_k.astype(x.dtype),
                    new_v.astype(x.dtype),
                    block_tables,
                    kv_valid_len,
                    scale=scale,
                ).transpose(0, 2, 1, 3)
            out = out.transpose(0, 2, 1, 3).reshape(b, s, c.heads * dh)
            return _dense(c, c.hidden_size, "o_proj", False, x.dtype)(out), cache

        if cache is not None:
            off = jnp.asarray(cache_offset, jnp.int32)
            with jax.named_scope(SCOPE_KV_WRITE):
                if off.ndim == 0:
                    # Prefill: one contiguous segment at a shared offset.
                    zero = jnp.zeros((), jnp.int32)
                    new_k = jax.lax.dynamic_update_slice(
                        cache["k"], k.astype(cache["k"].dtype), (zero, zero, off, zero)
                    )
                    new_v = jax.lax.dynamic_update_slice(
                        cache["v"], v.astype(cache["v"].dtype), (zero, zero, off, zero)
                    )
                else:
                    # Decode: one token per sample at a per-sample slot (prompts
                    # in a batch have different lengths).
                    assert s == 1, "per-sample cache offsets require a single-token segment"
                    bidx = jnp.arange(b)
                    new_k = cache["k"].at[bidx, :, off].set(k[:, :, 0].astype(cache["k"].dtype))
                    new_v = cache["v"].at[bidx, :, off].set(v[:, :, 0].astype(cache["v"].dtype))
            cache = {"k": new_k, "v": new_v}
            keys, values = new_k.astype(x.dtype), new_v.astype(x.dtype)
            n_rep = c.heads // c.kv_heads
            # Key slot j is visible iff filled AND causally reachable:
            # j < kv_valid_len[b] (prefill garbage beyond the true prompt
            # length is excluded; decode overwrites slots in order) and
            # j <= absolute query position. ``positions`` rows are
            # contiguous (arange-offset), so the whole mask is carried by
            # two [B] scalars — on TPU this dispatches to the Pallas flash
            # kernel for prefill-size queries (mask computed in-kernel,
            # dead key blocks skipped) and plain XLA for 1-token decode.
            out = attention_cached(
                q,
                repeat_kv(keys, n_rep),
                repeat_kv(values, n_rep),
                q_offsets=positions[:, 0],
                kv_valid=kv_valid_len,
                scale=scale,
            )
        else:
            keys, values = k, v
            n_rep = c.heads // c.kv_heads
            # Cacheless forward: positions are arange rows (see
            # ``VLMModel.__call__`` / ``merge_image_embeddings``), so the
            # positions-pairwise mask is exactly the causal triangle.
            out = attention(
                q, repeat_kv(keys, n_rep), repeat_kv(values, n_rep), causal=True, scale=scale
            )

        out = out.transpose(0, 2, 1, 3).reshape(b, s, c.heads * dh)
        return _dense(c, c.hidden_size, "o_proj", False, x.dtype)(out), cache


def prefix_ladder(length: int, step: int = 1024) -> list[int]:
    """Static key-prefix lengths a prefill chunk may attend over: a chunk
    ending at ``e`` uses the smallest one >= ``e``, so early chunks of a
    long prompt do not pay for the whole scratch."""
    ladder = list(range(step, length, step))
    return ladder + [length]


class LatentAttention(nn.Module):
    """Latent attention in absorbed form (``ops.latent_attention``), of the
    kind ``kind`` names: low-rank query (``q_a_proj`` -> norm -> ``q_b_proj``)
    and key/value (``kv_a_proj`` -> norm; ``kv_b_proj`` folded into the query
    and applied to the weighted latents), one RoPE key shared by all heads,
    rotated as the kind's ``LatentDims`` says (YaRN where it is scaled, the
    score scale with it). What else the layer has, the configuration says:
    a sigmoid gate a head from the layer's normed input (``latent_gate``);
    in a full layer an indexer (``index_q`` from the query latent,
    ``index_k`` with a LayerNorm, ``index_w``) that keeps the ``index_topk``
    causal keys of largest score (``index_heads`` > 0). A full layer without
    one attends every causal key. The cache is this layer's
    ``_latent_cache``. In paged decode, a row ``token_valid`` leaves out (a
    done or free slot, which keeps its last row's length) reads no key."""

    cfg: DecoderConfig
    kind: str

    @nn.compact
    def __call__(
        self, x, positions, cache, cache_offset, kv_valid_len, block_tables=None, token_valid=None
    ):
        from ...ops import latent_attention as la

        c = self.cfg
        windowed = self.kind == WINDOW_ATTENTION
        d = c.latent_window if windowed else c.latent_full
        b, s, _ = x.shape
        h = d.heads

        def dense(features, name):
            return nn.Dense(features, use_bias=False, name=name, dtype=x.dtype)

        cq = RMSNorm(c.rms_norm_eps, name="q_a_norm")(dense(d.q_lora, "q_a_proj")(x))
        kv = dense(d.kv_lora + d.rope, "kv_a_proj")(x)
        ckv = RMSNorm(c.rms_norm_eps, name="kv_a_norm")(kv[..., : d.kv_lora])
        if c.latent_rescale:
            cq = cq * (c.hidden_size / d.q_lora) ** 0.5
            ckv = ckv * (c.hidden_size / d.kv_lora) ** 0.5
        rotate = lambda t: rope_rotate(t, positions, d.rope_theta, d.rope_scaling)
        k_r = rotate(kv[:, None, :, d.kv_lora :])[:, 0]  # [B, S, R]
        q = dense(h * (d.nope + d.rope), "q_b_proj")(cq)
        q = q.reshape(b, s, h, d.nope + d.rope).transpose(0, 2, 1, 3)
        q_r = rotate(q[..., d.nope :])  # [B, H, S, R]
        w_kvb = self.param(
            "kv_b_proj", nn.initializers.normal(0.02), (d.kv_lora, h * (d.nope + d.v_dim))
        ).astype(x.dtype).reshape(d.kv_lora, h, d.nope + d.v_dim)
        q_c = jnp.einsum("bhsn,chn->bhsc", q[..., : d.nope], w_kvb[..., : d.nope])  # absorbed
        if c.latent_gate:
            gate = jax.nn.sigmoid(dense(h, "attn_gate")(x).astype(jnp.float32))  # [B, S, H]
        new = {"c": ckv, "r": k_r}
        indexed = not windowed and c.indexer
        if indexed:
            j, di = c.index_heads, c.index_head_dim
            q_i = dense(j * di, "index_q")(cq).reshape(b, s, j, di).transpose(0, 2, 1, 3)
            q_i = jnp.concatenate(
                [rope_rotate(q_i[..., : d.rope], positions, d.rope_theta), q_i[..., d.rope :]], -1
            ).transpose(0, 2, 1, 3)  # [B, S, J, Di]
            k_i = nn.LayerNorm(epsilon=c.rms_norm_eps, name="index_k_norm", dtype=x.dtype)(
                dense(di, "index_k")(x)
            )
            k_i = jnp.concatenate(
                [rope_rotate(k_i[:, None, :, : d.rope], positions, d.rope_theta)[:, 0], k_i[..., d.rope :]],
                -1,
            )  # [B, S, Di]
            w_i = dense(j, "index_w")(x).astype(jnp.float32) * (j**-0.5 * di**-0.5)  # [B, S, J]
            new["ik"] = k_i

        if block_tables is not None:
            if s != 1:
                raise NotImplementedError("latent paged decode takes one token a row")
            # [B, MAXP] of this kind's id space: one table a row, or, beside
            # window layers, the full layers' and theirs
            table = block_tables if block_tables.ndim == 2 else block_tables[:, 1 if windowed else 0]
            page = cache["c"].shape[1]
            off = jnp.asarray(cache_offset, jnp.int32)
            with jax.named_scope(SCOPE_KV_WRITE):
                page_idx = table[jnp.arange(b), off // page]
                slot = off % page
                cache = {
                    name: cache[name].at[page_idx, slot].set(new[name][:, 0].astype(cache[name].dtype))
                    for name in cache
                }
            if token_valid is not None:  # its output is thrown away
                kv_valid_len = jnp.where(token_valid.reshape(b), kv_valid_len, 0)
            sel, span = None, None
            if windowed:
                kv_start = jnp.maximum(kv_valid_len - c.sliding_window, 0)
                span = la.window_span_pages(c.sliding_window, page)
            else:
                kv_start = jnp.zeros_like(kv_valid_len)
                slots = table.shape[1] * page
                if not indexed:
                    _count_route("latent-all", slots)
                elif slots > c.index_topk:
                    scores = la.indexer_scores(q_i[:, 0], w_i[:, 0], cache["ik"], table)
                    ok = jnp.arange(slots, dtype=jnp.int32)[None, :] < kv_valid_len[:, None]
                    sel = la.topk_select(scores, ok, c.index_topk)
            out = la.latent_paged_attention(
                q_c[:, :, 0], q_r[:, :, 0], cache["c"], cache["r"], table,
                kv_valid_len, kv_start, sel, scale=d.scale, span=span,
            )[:, :, None]  # [B, H, 1, C]
        else:
            if cache is not None:
                off = jnp.asarray(cache_offset, jnp.int32)
                if off.ndim != 0:
                    raise NotImplementedError(
                        "a latent decoder decodes through the paged pool (continuous scheduler) only"
                    )
                zero = jnp.zeros((), jnp.int32)
                with jax.named_scope(SCOPE_KV_WRITE):
                    cache = {
                        name: jax.lax.dynamic_update_slice(
                            cache[name], new[name].astype(cache[name].dtype), (zero, off, zero)
                        )
                        for name in cache
                    }
                length = cache["c"].shape[1]
            else:
                off, length = jnp.zeros((), jnp.int32), s

            def attend(keys: dict, k_pos):
                """Queries of this segment against ``keys`` at ``k_pos`` [S']."""
                see = (k_pos[None, None, :] <= positions[:, :, None]) & (
                    k_pos[None, None, :] < kv_valid_len[:, None, None]
                )
                if windowed:
                    see &= positions[:, :, None] - k_pos[None, None, :] < c.sliding_window
                elif indexed and k_pos.shape[0] > c.index_topk:
                    scores = la.indexer_scores_dense(q_i, w_i, keys["ik"].astype(x.dtype))
                    see = la.topk_select(scores, see, c.index_topk)
                return la.latent_prefill_attention(
                    q_c, q_r, keys["c"].astype(x.dtype), keys["r"].astype(x.dtype), see,
                    scale=d.scale,
                )

            keys = cache if cache is not None else new  # cacheless: the segment's own rows
            if windowed:
                # The chunk's windows lie inside one static-length slice.
                pad = -(-(c.sliding_window - 1) // 128) * 128
                klen = min(length, s + pad)
                start = jnp.clip(off - pad, 0, length - klen)
                sliced = {
                    n: jax.lax.dynamic_slice_in_dim(keys[n], start, klen, axis=1) for n in keys
                }
                out = attend(sliced, start + jnp.arange(klen, dtype=jnp.int32))
            else:
                ladder = prefix_ladder(length)

                def branch(n):
                    return lambda ks: attend(
                        {name: v[:, :n] for name, v in ks.items()}, jnp.arange(n, dtype=jnp.int32)
                    )

                if len(ladder) == 1:
                    out = branch(length)(keys)
                else:
                    idx = jnp.searchsorted(jnp.asarray(ladder), off + s, side="left")
                    out = jax.lax.switch(idx, [branch(n) for n in ladder], keys)
        out = jnp.einsum("bhsc,chv->bshv", out, w_kvb[..., d.nope :])  # [B, S, H, V]
        if c.latent_gate:
            out = out * gate[..., None].astype(out.dtype)
        return dense(c.hidden_size, "o_proj")(out.reshape(b, s, h * d.v_dim)), cache


class Mamba2Mixer(nn.Module):
    """Mamba-2 mixer of a hybrid decoder's "mamba" layer (``ops.ssm`` has
    the mathematics): ``[z | xBC | dt] = u W_in``; ``xBC`` through a causal
    depthwise convolution and SiLU, split into ``x`` (heads x head_dim), ``B``
    and ``C`` (``d_state`` each, shared by every head); ``dt = softplus(dt +
    dt_bias)``, ``A = -exp(A_log)``; the scan; ``y = RMSNorm(y * silu(z))``
    over the whole inner width; ``W_out``. The cache is the layer's row
    state (``_recurrent_cache``): a prefill segment starts from it and
    leaves it at the segment's last LIVE token (positions at or past
    ``kv_valid_len`` are right padding: their ``dt`` is 0 and the
    convolution's tail is taken in front of them); a decode step
    (``block_tables`` given, or a per-row ``cache_offset``) moves the rows
    ``token_valid`` marks and leaves a done or free slot's state alone."""

    cfg: DecoderConfig

    @nn.compact
    def __call__(
        self, x, positions, cache, cache_offset, kv_valid_len, block_tables=None, token_valid=None
    ):
        from ...ops import ssm

        c = self.cfg
        b, s, _ = x.shape
        h, n, inner, conv_dim = c.mamba_heads, c.mamba_state, c.mamba_inner, c.mamba_conv_dim
        proj = nn.Dense(inner + conv_dim + h, use_bias=False, name="in_proj", dtype=x.dtype)(x)
        z, xbc, dt = proj[..., :inner], proj[..., inner : inner + conv_dim], proj[..., inner + conv_dim :]
        init = nn.initializers.normal(0.02)
        conv_w = self.param("conv_kernel", init, (c.mamba_conv, conv_dim), jnp.float32)
        conv_b = self.param("conv_bias", nn.initializers.zeros, (conv_dim,), jnp.float32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (h,), jnp.float32)
        a = -jnp.exp(self.param("A_log", nn.initializers.zeros, (h,), jnp.float32))
        d_skip = self.param("D", nn.initializers.ones, (h,), jnp.float32)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
        stepping = cache is not None and (
            block_tables is not None or jnp.ndim(cache_offset) == 1
        )
        if stepping:
            if s != 1:
                raise NotImplementedError("a recurrent layer decodes one token a row")
            active = jnp.ones((b,), bool) if token_valid is None else token_valid.reshape(b)
            # The slot's tail and state are rewritten in place by the
            # one-token update itself, so the write's scope holds the update.
            with jax.named_scope(SCOPE_KV_WRITE):
                act, tail = ssm.conv1d_update(xbc[:, 0], cache["conv"], conv_w, conv_b, active)
                act = act.astype(x.dtype)
                y, state = ssm.ssm_state_update(
                    act[:, :inner], dt[:, 0], a, act[:, inner : inner + n], act[:, inner + n :],
                    d_skip, cache["ssm"], active,
                )
            y, cache = y[:, None], {"conv": tail, "ssm": state}
        else:
            live = positions < kv_valid_len[:, None]  # [B, S]
            if cache is None:
                tail = jnp.zeros((b, c.mamba_conv - 1, conv_dim), x.dtype)
                state = jnp.zeros((b, n, inner), jnp.float32)
            else:
                tail, state = cache["conv"], cache["ssm"]
            act, tail = ssm.causal_conv1d(xbc, tail, conv_w, conv_b, live.sum(axis=1))
            act = act.astype(x.dtype)
            y, state = ssm.ssd_chunk_scan(
                act[..., :inner], jnp.where(live[..., None], dt, 0.0), a,
                act[..., inner : inner + n], act[..., inner + n :], d_skip, state,
                chunk=c.mamba_chunk,
            )
            if cache is not None:
                cache = {"conv": tail, "ssm": state}
        gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        y = RMSNorm(c.rms_norm_eps, name="norm")(gated).astype(x.dtype)
        return nn.Dense(c.hidden_size, use_bias=False, name="out_proj", dtype=x.dtype)(y), cache


class SwiGLU(nn.Module):
    cfg: DecoderConfig
    intermediate: int | None = None  # override cfg.intermediate_size

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        c = self.cfg
        inter = self.intermediate or c.intermediate_size
        gate = _dense(c, inter, "gate_proj", False, x.dtype)(x)
        up = _dense(c, inter, "up_proj", False, x.dtype)(x)
        return _dense(c, c.hidden_size, "down_proj", False, x.dtype)(nn.silu(gate) * up)


class MoEFFN(nn.Module):
    """Qwen2-MoE sparse MLP: softmax router -> top-k routed SwiGLU expert
    bank (+ optional sigmoid-gated shared expert). The routed compute is
    :func:`lumen_tpu.parallel.moe.moe_ffn` with EXACT capacity, so outputs
    match HF's dense-gather reference (``Qwen2MoeSparseMoeBlock``)
    token-for-token; at pod scale the stacked ``w_*`` banks shard their
    leading dim over the ``expert`` mesh axis (``parallel.sharding``
    MOE_EP_RULES) or run through ``moe_ffn(mesh=...)`` explicitly."""

    cfg: DecoderConfig

    @nn.compact
    def __call__(self, x: jax.Array, token_valid: jax.Array | None = None) -> jax.Array:
        from ...parallel.moe import MoEParams, moe_ffn

        c = self.cfg
        e = c.moe_experts
        d = c.hidden_size
        f = c.moe_intermediate_size or c.intermediate_size
        lo, hi = c.moe_held or (0, e)
        init = nn.initializers.normal(0.02)
        router = self.param("router", init, (d, e), jnp.float32)
        w_gate = self.param("w_gate", init, (hi - lo, d, f), jnp.float32)
        w_up = self.param("w_up", init, (hi - lo, d, f), jnp.float32)
        w_down = self.param("w_down", init, (hi - lo, f, d), jnp.float32)
        b, s, _ = x.shape
        tokens = x.reshape(b * s, d)
        params = MoEParams(
            router=router,
            w_gate=w_gate.astype(x.dtype),
            w_up=w_up.astype(x.dtype),
            w_down=w_down.astype(x.dtype),
        )
        if c.moe_held is None and c.moe_scoring == "softmax":
            y = moe_ffn(
                params, tokens, mesh=None, k=c.moe_top_k,
                capacity_factor=None,  # exact: no token drops at inference
                norm_topk=c.moe_norm_topk,
            )
        else:
            # One chip's share: route over all ``e``, compute the held
            # experts' part, and count what the layer saw (``moe_stats``:
            # assignments routed, of them held here, held experts that got
            # a token, calls) for whoever applies with it mutable.
            bias = (
                self.param("select_bias", nn.initializers.zeros, (e,), jnp.float32)
                if c.moe_select_bias else None
            )
            y, stats = moe_ffn(
                params, tokens, mesh=None, k=c.moe_top_k, capacity_factor=None,
                norm_topk=c.moe_norm_topk, scoring=c.moe_scoring, select_bias=bias,
                routed_scale=c.moe_routed_scale, held=(lo, hi), n_experts=e,
                token_valid=token_valid, with_stats=True,
                n_group=c.moe_n_group, topk_group=c.moe_topk_group,
            )
            self.sow(
                "moe_stats", "counts", stats,
                reduce_fn=lambda a, b: a + b, init_fn=lambda: jnp.zeros((4,), jnp.int32),
            )
        y = y.reshape(b, s, d)
        if c.moe_shared_intermediate:
            shared = SwiGLU(c, intermediate=c.moe_shared_intermediate, name="shared")(x)
            if c.moe_shared_gated:
                gate = nn.Dense(1, use_bias=False, name="shared_gate", dtype=x.dtype)(x)
                shared = jax.nn.sigmoid(gate) * shared
            y = y + shared
        return y


class DecoderLayer(nn.Module):
    cfg: DecoderConfig
    layer_idx: int = 0

    @nn.compact
    def __call__(
        self, x, positions, cache, cache_offset, kv_valid_len, block_tables=None, token_valid=None
    ):
        c = self.cfg
        kind = c.layer_kind(self.layer_idx)
        normed = RMSNorm(c.rms_norm_eps, name="input_norm")(x)
        if kind == MAMBA:
            h, cache = Mamba2Mixer(c, name="mamba")(
                normed, positions, cache, cache_offset, kv_valid_len, block_tables, token_valid
            )
        else:
            if kind in LATENT_KINDS:
                h, cache = LatentAttention(c, kind, name="attn")(
                    normed, positions, cache, cache_offset, kv_valid_len, block_tables, token_valid
                )
            else:
                h, cache = DecoderAttention(c, name="attn")(
                    normed, positions, cache, cache_offset, kv_valid_len, block_tables
                )
        r = c.residual_multiplier
        x = x + (h if r == 1.0 else h * jnp.asarray(r, h.dtype))
        y = RMSNorm(c.rms_norm_eps, name="post_attn_norm")(x)
        f = MoEFFN(c, name="mlp")(y, token_valid) if c.is_moe_layer(self.layer_idx) else SwiGLU(c, name="mlp")(y)
        x = x + (f if r == 1.0 else f * jnp.asarray(r, f.dtype))
        return x, cache


class Decoder(nn.Module):
    """Causal LM over input *embeddings* (not ids) so vision embeddings can
    be spliced upstream, mirroring the reference's embed/decoder session
    split (``onnxrt_backend.py:494-506``)."""

    cfg: DecoderConfig

    def setup(self):
        c = self.cfg
        self.embed_tokens = nn.Embed(c.vocab_size, c.hidden_size, name="embed_tokens")
        self.blocks = [
            DecoderLayer(c, layer_idx=i, name=f"layers_{i}") for i in range(c.layers)
        ]
        self.final_norm = RMSNorm(c.rms_norm_eps, name="final_norm")
        if not c.tie_word_embeddings:
            # _dense so weight_quant="int8" applies to the untied lm_head
            # (convert.quantize_decoder_int8 rewrites its kernel to q+scale).
            self.lm_head = _dense(c, c.vocab_size, "lm_head", False, None)

    def embed(self, input_ids: jax.Array) -> jax.Array:
        """Token rows only carry ``embedding_multiplier``: image rows are
        spliced over them afterwards as the tower gives them."""
        e = self.embed_tokens(input_ids)
        m = self.cfg.embedding_multiplier
        return e if m == 1.0 else e * jnp.asarray(m, e.dtype)

    def __call__(
        self,
        embeds: jax.Array,
        positions: jax.Array,
        caches: list[dict] | None,
        cache_offset: jax.Array | None,
        kv_valid_len: jax.Array,
        block_tables: jax.Array | None = None,
        token_valid: jax.Array | None = None,
    ) -> tuple[jax.Array, list[dict] | None]:
        x = embeds
        new_caches: list[dict] = []
        for i, block in enumerate(self.blocks):
            layer_cache = caches[i] if caches is not None else None
            x, layer_cache = block(
                x, positions, layer_cache, cache_offset, kv_valid_len, block_tables, token_valid
            )
            new_caches.append(layer_cache)
        x = self.final_norm(x)
        if self.cfg.tie_word_embeddings:
            logits = x @ self.embed_tokens.embedding.T.astype(x.dtype)
        else:
            logits = self.lm_head(x)
        if self.cfg.logits_scaling != 1.0:
            logits = logits / jnp.asarray(self.cfg.logits_scaling, logits.dtype)
        return logits, (new_caches if caches is not None else None)


class VisionEncoder(nn.Module):
    """ViT over large patches -> [B, num_tokens, width] patch features, then
    a 2-layer GELU MLP projector into decoder hidden space (LLaVA layout)."""

    cfg: VLMConfig

    @nn.compact
    def __call__(self, pixel_values: jax.Array) -> jax.Array:
        v = self.cfg.vision
        from ..clip.modeling import PatchEmbed  # reshape+matmul, MXU-shaped

        x = PatchEmbed(v.width, v.patch_size, use_bias=True, name="patch_embed")(
            pixel_values
        )
        b = x.shape[0]
        pos = self.param("position_embedding", nn.initializers.normal(0.02), (v.num_tokens, v.width))
        x = x + pos.astype(x.dtype)
        from ..clip.modeling import Block  # same pre-LN transformer block

        for i in range(v.layers):
            x = Block(v.width, v.heads, "gelu", 1e-6, name=f"blocks_{i}")(x)
        x = nn.LayerNorm(epsilon=1e-6, name="post_ln", dtype=x.dtype)(x)
        h = nn.Dense(self.cfg.decoder.hidden_size, name="proj_fc1", dtype=x.dtype)(x)
        h = jax.nn.gelu(h, approximate=True)
        return nn.Dense(self.cfg.decoder.hidden_size, name="proj_fc2", dtype=x.dtype)(h)


class VLMModel(nn.Module):
    cfg: VLMConfig

    def setup(self):
        self.vision = VisionEncoder(self.cfg, name="vision")
        self.decoder = Decoder(self.cfg.decoder, name="decoder")

    def encode_vision(self, pixel_values: jax.Array) -> jax.Array:
        return self.vision(pixel_values)

    def embed_tokens(self, input_ids: jax.Array) -> jax.Array:
        return self.decoder.embed(input_ids)

    def decode(self, embeds, positions, caches, cache_offset, kv_valid_len, token_valid=None):
        return self.decoder(
            embeds, positions, caches, cache_offset, kv_valid_len, None, token_valid
        )

    def decode_paged(
        self, embeds, positions, caches, block_tables, cache_offset, kv_valid_len, token_valid=None
    ):
        """Single-token decode against the paged KV pool (continuous
        engine): ``caches`` from :func:`init_paged_kv_cache`,
        ``block_tables`` [B, max_pages] per-row page maps ([B, 2, max_pages]
        for a latent decoder: the full layers' table and the window
        layers')."""
        return self.decoder(
            embeds, positions, caches, cache_offset, kv_valid_len, block_tables, token_valid
        )

    def __call__(self, input_ids: jax.Array, pixel_values: jax.Array | None = None):
        """Cacheless forward (tests / loss): embeds ids, optionally splices
        one image per sample at the image-token position, returns logits."""
        embeds = self.decoder.embed(input_ids)
        if pixel_values is not None:
            vis = self.vision(pixel_values)
            embeds, positions, _ = merge_image_embeddings(
                embeds, vis, input_ids, self.cfg.image_token_id
            )
        else:
            b, s = input_ids.shape
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        logits, _ = self.decoder(
            embeds, positions, None, None, jnp.full((embeds.shape[0],), embeds.shape[1])
        )
        return logits


def merge_image_embeddings(
    text_embeds: jax.Array,
    vision_embeds: jax.Array,
    input_ids: jax.Array,
    image_token_id: int,
    input_lengths: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """LLaVA-style splice with static shapes: replace the single ``<image>``
    placeholder token with the ``V`` vision tokens.

    The reference does this on host with a python list split + concat
    (``onnxrt_backend.py:240-296``); here it is a gather so it lives inside
    jit. Output length is static: ``S - 1 + V``.

    Returns ``(merged [B, L, H], positions [B, L], lengths [B])``.
    ``input_lengths`` [B] is the unpadded token count of each sample
    (defaults to S); ``lengths`` is the post-splice live token count —
    positions beyond it are right-padding the caller masks via kv_valid_len.
    """
    b, s = input_ids.shape
    v = vision_embeds.shape[1]
    l = s - 1 + v
    if input_lengths is None:
        input_lengths = jnp.full((b,), s)
    has_image = jnp.any(input_ids == image_token_id, axis=1)  # [B]
    # Index of the placeholder (first occurrence); samples without an image
    # get idx = s so every output position maps to a text token.
    idx = jnp.where(
        has_image, jnp.argmax((input_ids == image_token_id).astype(jnp.int32), axis=1), s
    )  # [B]
    pos = jnp.arange(l)[None, :]  # [1, L]
    idx_b = idx[:, None]
    in_image = (pos >= idx_b) & (pos < idx_b + v) & has_image[:, None]
    # text source index: before splice -> pos; after -> pos - (V - 1)
    text_src = jnp.where(pos < idx_b, pos, pos - (v - 1))
    text_src = jnp.clip(text_src, 0, s - 1)
    vis_src = jnp.clip(pos - idx_b, 0, v - 1)
    gathered_text = jnp.take_along_axis(text_embeds, text_src[:, :, None], axis=1)
    gathered_vis = jnp.take_along_axis(
        vision_embeds.astype(text_embeds.dtype), vis_src[:, :, None], axis=1
    )
    merged = jnp.where(in_image[:, :, None], gathered_vis, gathered_text)
    positions = jnp.broadcast_to(pos, (b, l))
    lengths = jnp.where(has_image, input_lengths - 1 + v, input_lengths)
    return merged, positions, lengths
