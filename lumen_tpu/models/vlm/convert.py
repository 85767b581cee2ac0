"""Checkpoint conversion: HF LLaVA/Qwen2-style VLM -> Flax params.

The reference consumes pre-exported ONNX graphs and never touches raw
checkpoints; we load the source safetensors directly (FastVLM-style repos
ship a Qwen2 language model + vision tower + 2-layer projector). Converted
trees are shape-gated against the module's init tree before serving, same
as the other families (``lumen_tpu/models/clip/convert.py``).
"""

from __future__ import annotations

import logging
import re

import numpy as np

from ...runtime.weights import (
    apply_rules,
    assert_tree_shapes,
    conv_kernel,
    is_native_checkpoint,
    linear_kernel,
    split_collections,
    unflatten,
)

logger = logging.getLogger(__name__)

_QKV = r"(q_proj|k_proj|v_proj)"

DECODER_RULES = [
    (r"model\.embed_tokens\.weight", r"decoder/embed_tokens/embedding", None),
    (rf"model\.layers\.(\d+)\.self_attn\.{_QKV}\.weight", r"decoder/layers_\1/attn/\2/kernel", linear_kernel),
    (rf"model\.layers\.(\d+)\.self_attn\.{_QKV}\.bias", r"decoder/layers_\1/attn/\2/bias", None),
    (r"model\.layers\.(\d+)\.self_attn\.o_proj\.weight", r"decoder/layers_\1/attn/o_proj/kernel", linear_kernel),
    (r"model\.layers\.(\d+)\.mlp\.gate_proj\.weight", r"decoder/layers_\1/mlp/gate_proj/kernel", linear_kernel),
    (r"model\.layers\.(\d+)\.mlp\.up_proj\.weight", r"decoder/layers_\1/mlp/up_proj/kernel", linear_kernel),
    (r"model\.layers\.(\d+)\.mlp\.down_proj\.weight", r"decoder/layers_\1/mlp/down_proj/kernel", linear_kernel),
    # Qwen2-MoE sparse layers: router + per-expert SwiGLU (stacked into
    # [E, ...] banks by _stack_experts below) + sigmoid-gated shared expert.
    (r"model\.layers\.(\d+)\.mlp\.gate\.weight", r"decoder/layers_\1/mlp/router", linear_kernel),
    (r"model\.layers\.(\d+)\.mlp\.experts\.(\d+)\.gate_proj\.weight", r"decoder/layers_\1/mlp/__expert_gate__/\2", linear_kernel),
    (r"model\.layers\.(\d+)\.mlp\.experts\.(\d+)\.up_proj\.weight", r"decoder/layers_\1/mlp/__expert_up__/\2", linear_kernel),
    (r"model\.layers\.(\d+)\.mlp\.experts\.(\d+)\.down_proj\.weight", r"decoder/layers_\1/mlp/__expert_down__/\2", linear_kernel),
    (r"model\.layers\.(\d+)\.mlp\.shared_expert\.gate_proj\.weight", r"decoder/layers_\1/mlp/shared/gate_proj/kernel", linear_kernel),
    (r"model\.layers\.(\d+)\.mlp\.shared_expert\.up_proj\.weight", r"decoder/layers_\1/mlp/shared/up_proj/kernel", linear_kernel),
    (r"model\.layers\.(\d+)\.mlp\.shared_expert\.down_proj\.weight", r"decoder/layers_\1/mlp/shared/down_proj/kernel", linear_kernel),
    (r"model\.layers\.(\d+)\.mlp\.shared_expert_gate\.weight", r"decoder/layers_\1/mlp/shared_gate/kernel", linear_kernel),
    # Latent attention (``DeepseekV3``-style names; ``dots3_note``, ``axk1``):
    # low-rank q and kv projections, one up-projection folded into the query
    # at run time (a bare kernel, not a Dense), the router's selection bias,
    # and, where the checkpoint has them, a gate a head and the indexer.
    (r"model\.layers\.(\d+)\.self_attn\.(q_a_proj|q_b_proj)\.weight", r"decoder/layers_\1/attn/\2/kernel", linear_kernel),
    (r"model\.layers\.(\d+)\.self_attn\.kv_a_proj_with_mqa\.weight", r"decoder/layers_\1/attn/kv_a_proj/kernel", linear_kernel),
    (r"model\.layers\.(\d+)\.self_attn\.kv_b_proj\.weight", r"decoder/layers_\1/attn/kv_b_proj", linear_kernel),
    (r"model\.layers\.(\d+)\.self_attn\.q_a_layernorm\.weight", r"decoder/layers_\1/attn/q_a_norm/scale", None),
    (r"model\.layers\.(\d+)\.self_attn\.kv_a_layernorm\.weight", r"decoder/layers_\1/attn/kv_a_norm/scale", None),
    (r"model\.layers\.(\d+)\.self_attn\.attn_gate\.weight", r"decoder/layers_\1/attn/attn_gate/kernel", linear_kernel),
    (r"model\.layers\.(\d+)\.self_attn\.indexer\.wq_b\.weight", r"decoder/layers_\1/attn/index_q/kernel", linear_kernel),
    (r"model\.layers\.(\d+)\.self_attn\.indexer\.wk\.weight", r"decoder/layers_\1/attn/index_k/kernel", linear_kernel),
    (r"model\.layers\.(\d+)\.self_attn\.indexer\.weights_proj\.weight", r"decoder/layers_\1/attn/index_w/kernel", linear_kernel),
    (r"model\.layers\.(\d+)\.self_attn\.indexer\.k_norm\.weight", r"decoder/layers_\1/attn/index_k_norm/scale", None),
    (r"model\.layers\.(\d+)\.self_attn\.indexer\.k_norm\.bias", r"decoder/layers_\1/attn/index_k_norm/bias", None),
    (r"model\.layers\.(\d+)\.mlp\.gate\.e_score_correction_bias", r"decoder/layers_\1/mlp/select_bias", None),
    (r"model\.layers\.(\d+)\.mlp\.shared_experts\.(gate_proj|up_proj|down_proj)\.weight", r"decoder/layers_\1/mlp/shared/\2/kernel", linear_kernel),
    # Hybrid decoder (``granitemoehybrid``): the Mamba-2 mixer; the experts as
    # two stacked tensors a layer (``input_linear`` holds the gate and up
    # halves of every expert side by side: split by _split_fused below); the
    # shared expert likewise.
    (r"model\.layers\.(\d+)\.mamba\.(in_proj|out_proj)\.weight", r"decoder/layers_\1/mamba/\2/kernel", linear_kernel),
    (r"model\.layers\.(\d+)\.mamba\.conv1d\.weight", r"decoder/layers_\1/mamba/conv_kernel", lambda w: np.ascontiguousarray(w[:, 0, :].T)),
    (r"model\.layers\.(\d+)\.mamba\.conv1d\.bias", r"decoder/layers_\1/mamba/conv_bias", None),
    (r"model\.layers\.(\d+)\.mamba\.(dt_bias|A_log|D)", r"decoder/layers_\1/mamba/\2", None),
    (r"model\.layers\.(\d+)\.mamba\.norm\.weight", r"decoder/layers_\1/mamba/norm/scale", None),
    (r"model\.layers\.(\d+)\.block_sparse_moe\.router\.layer\.weight", r"decoder/layers_\1/mlp/router", linear_kernel),
    (r"model\.layers\.(\d+)\.block_sparse_moe\.input_linear\.weight", r"decoder/layers_\1/mlp/__fused_in__", None),
    (r"model\.layers\.(\d+)\.block_sparse_moe\.output_linear\.weight", r"decoder/layers_\1/mlp/w_down", lambda w: np.ascontiguousarray(w.transpose(0, 2, 1))),
    (r"model\.layers\.(\d+)\.shared_mlp\.input_linear\.weight", r"decoder/layers_\1/mlp/shared/__fused_in__", None),
    (r"model\.layers\.(\d+)\.shared_mlp\.output_linear\.weight", r"decoder/layers_\1/mlp/shared/down_proj/kernel", linear_kernel),
    # A multi-token prediction layer (``num_nextn_predict_layers``; index
    # ``num_hidden_layers``, one past the decoder's last): its two input
    # norms, the projection of their concatenation, the norm before the
    # head; its decoder block takes the rules above.
    (r"model\.layers\.(\d+)\.(enorm|hnorm)\.weight", r"decoder/layers_\1/\2/scale", None),
    (r"model\.layers\.(\d+)\.eh_proj\.weight", r"decoder/layers_\1/eh_proj/kernel", linear_kernel),
    (r"model\.layers\.(\d+)\.shared_head\.norm\.weight", r"decoder/layers_\1/head_norm/scale", None),
    (r"model\.layers\.(\d+)\.input_layernorm\.weight", r"decoder/layers_\1/input_norm/scale", None),
    (r"model\.layers\.(\d+)\.post_attention_layernorm\.weight", r"decoder/layers_\1/post_attn_norm/scale", None),
    (r"model\.norm\.weight", r"decoder/final_norm/scale", None),
    (r"lm_head\.weight", r"decoder/lm_head/kernel", linear_kernel),
]

VISION_RULES = [
    (r"vision_tower\.patch_embed\.weight", r"vision/patch_embed/kernel", conv_kernel),
    (r"vision_tower\.patch_embed\.bias", r"vision/patch_embed/bias", None),
    (r"vision_tower\.position_embedding", r"vision/position_embedding", None),
    (rf"vision_tower\.blocks\.(\d+)\.attn\.{_QKV}\.weight", r"vision/blocks_\1/attn/\2/kernel", linear_kernel),
    (rf"vision_tower\.blocks\.(\d+)\.attn\.{_QKV}\.bias", r"vision/blocks_\1/attn/\2/bias", None),
    (r"vision_tower\.blocks\.(\d+)\.attn\.out_proj\.weight", r"vision/blocks_\1/attn/out_proj/kernel", linear_kernel),
    (r"vision_tower\.blocks\.(\d+)\.attn\.out_proj\.bias", r"vision/blocks_\1/attn/out_proj/bias", None),
    (r"vision_tower\.blocks\.(\d+)\.norm1\.weight", r"vision/blocks_\1/ln1/scale", None),
    (r"vision_tower\.blocks\.(\d+)\.norm1\.bias", r"vision/blocks_\1/ln1/bias", None),
    (r"vision_tower\.blocks\.(\d+)\.norm2\.weight", r"vision/blocks_\1/ln2/scale", None),
    (r"vision_tower\.blocks\.(\d+)\.norm2\.bias", r"vision/blocks_\1/ln2/bias", None),
    (r"vision_tower\.blocks\.(\d+)\.mlp\.fc1\.weight", r"vision/blocks_\1/mlp/fc1/kernel", linear_kernel),
    (r"vision_tower\.blocks\.(\d+)\.mlp\.fc1\.bias", r"vision/blocks_\1/mlp/fc1/bias", None),
    (r"vision_tower\.blocks\.(\d+)\.mlp\.fc2\.weight", r"vision/blocks_\1/mlp/fc2/kernel", linear_kernel),
    (r"vision_tower\.blocks\.(\d+)\.mlp\.fc2\.bias", r"vision/blocks_\1/mlp/fc2/bias", None),
    (r"vision_tower\.post_norm\.weight", r"vision/post_ln/scale", None),
    (r"vision_tower\.post_norm\.bias", r"vision/post_ln/bias", None),
    (r"multi_modal_projector\.linear_1\.weight", r"vision/proj_fc1/kernel", linear_kernel),
    (r"multi_modal_projector\.linear_1\.bias", r"vision/proj_fc1/bias", None),
    (r"multi_modal_projector\.linear_2\.weight", r"vision/proj_fc2/kernel", linear_kernel),
    (r"multi_modal_projector\.linear_2\.bias", r"vision/proj_fc2/bias", None),
    # HF-CLIP-style vision tower naming (llava checkpoints that embed a
    # CLIPVisionModel): map encoder layers onto the same block tree.
    (r"vision_tower\.vision_model\.embeddings\.patch_embedding\.weight", r"vision/patch_embed/kernel", conv_kernel),
    (r"vision_tower\.vision_model\.embeddings\.patch_embedding\.bias", r"vision/patch_embed/bias", None),
    (r"vision_tower\.vision_model\.embeddings\.position_embedding\.weight", r"vision/position_embedding", None),
    (rf"vision_tower\.vision_model\.encoder\.layers\.(\d+)\.self_attn\.{_QKV}\.weight", r"vision/blocks_\1/attn/\2/kernel", linear_kernel),
    (rf"vision_tower\.vision_model\.encoder\.layers\.(\d+)\.self_attn\.{_QKV}\.bias", r"vision/blocks_\1/attn/\2/bias", None),
    (r"vision_tower\.vision_model\.encoder\.layers\.(\d+)\.self_attn\.out_proj\.weight", r"vision/blocks_\1/attn/out_proj/kernel", linear_kernel),
    (r"vision_tower\.vision_model\.encoder\.layers\.(\d+)\.self_attn\.out_proj\.bias", r"vision/blocks_\1/attn/out_proj/bias", None),
    (r"vision_tower\.vision_model\.encoder\.layers\.(\d+)\.layer_norm1\.weight", r"vision/blocks_\1/ln1/scale", None),
    (r"vision_tower\.vision_model\.encoder\.layers\.(\d+)\.layer_norm1\.bias", r"vision/blocks_\1/ln1/bias", None),
    (r"vision_tower\.vision_model\.encoder\.layers\.(\d+)\.layer_norm2\.weight", r"vision/blocks_\1/ln2/scale", None),
    (r"vision_tower\.vision_model\.encoder\.layers\.(\d+)\.layer_norm2\.bias", r"vision/blocks_\1/ln2/bias", None),
    (r"vision_tower\.vision_model\.encoder\.layers\.(\d+)\.mlp\.fc1\.weight", r"vision/blocks_\1/mlp/fc1/kernel", linear_kernel),
    (r"vision_tower\.vision_model\.encoder\.layers\.(\d+)\.mlp\.fc1\.bias", r"vision/blocks_\1/mlp/fc1/bias", None),
    (r"vision_tower\.vision_model\.encoder\.layers\.(\d+)\.mlp\.fc2\.weight", r"vision/blocks_\1/mlp/fc2/kernel", linear_kernel),
    (r"vision_tower\.vision_model\.encoder\.layers\.(\d+)\.mlp\.fc2\.bias", r"vision/blocks_\1/mlp/fc2/bias", None),
    (r"vision_tower\.vision_model\.post_layernorm\.weight", r"vision/post_ln/scale", None),
    (r"vision_tower\.vision_model\.post_layernorm\.bias", r"vision/post_ln/bias", None),
]

DROP = [
    r"rotary_emb\.inv_freq$",
    r"position_ids$",
    r"vision_tower\.vision_model\.embeddings\.class_embedding",
    r"vision_tower\.vision_model\.pre_layrnorm\.",
    # a multi-token prediction layer's copies of the embedding and the head:
    # it shares the decoder's own
    r"model\.layers\.\d+\.embed_tokens\.weight$",
    r"model\.layers\.\d+\.shared_head\.head\.weight$",
]


_EXPERT_BANKS = {
    "__expert_gate__": "w_gate",
    "__expert_up__": "w_up",
    "__expert_down__": "w_down",
}


def _stack_experts(flat: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
    """Collapse ``.../mlp/__expert_gate__/<i>`` leaves into one stacked
    ``.../mlp/w_gate`` bank per layer (``[E, ...]``, in expert order on the
    leading dim — the layout ``MoEFFN`` and the ``expert``-axis sharding
    rules expect). A chip's share of an expert-parallel checkpoint names its
    experts by their ids in the whole bank (``lo..hi-1``): any contiguous
    range stacks, and the configuration's held range says which it is."""
    groups: dict[tuple[str, str], dict[int, np.ndarray]] = {}
    out: dict[str, np.ndarray] = {}
    for key, val in flat.items():
        parts = key.split("/")
        if len(parts) >= 2 and parts[-2] in _EXPERT_BANKS:
            prefix = "/".join(parts[:-2])
            groups.setdefault((prefix, parts[-2]), {})[int(parts[-1])] = val
        else:
            out[key] = val
    for (prefix, marker), members in groups.items():
        ids = sorted(members)
        if ids != list(range(ids[0], ids[0] + len(ids))):
            raise ValueError(f"{prefix}/{marker}: non-contiguous expert indices {ids}")
        out[f"{prefix}/{_EXPERT_BANKS[marker]}"] = np.stack([members[i] for i in ids], axis=0)
    return out


def _split_fused(flat: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
    """``.../__fused_in__`` leaves (HF ``input_linear.weight``: the gate half
    of the outputs, then the up half) into the two the modules hold: a bank
    ``[E, 2f, d]`` into ``w_gate`` / ``w_up`` ``[E, d, f]``, a single
    ``[2f, d]`` into ``gate_proj`` / ``up_proj`` kernels ``[d, f]``."""
    out: dict[str, np.ndarray] = {}
    for key, val in flat.items():
        if not key.endswith("/__fused_in__"):
            out[key] = val
            continue
        prefix = key.removesuffix("/__fused_in__")
        gate, up = np.split(np.swapaxes(val, -1, -2), 2, axis=-1)
        names = ("w_gate", "w_up") if val.ndim == 3 else ("gate_proj/kernel", "up_proj/kernel")
        out[f"{prefix}/{names[0]}"] = np.ascontiguousarray(gate)
        out[f"{prefix}/{names[1]}"] = np.ascontiguousarray(up)
    return out


#: decoder projections QDense replaces when ``weight_quant="int8"`` — must
#: stay in lockstep with modeling._dense call sites (attn q/k/v/o, SwiGLU
#: gate/up/down incl. the MoE shared expert, untied lm_head). MoE expert
#: banks (w_*), router, embeddings, and norms stay full precision.
_QUANT_KERNEL = re.compile(
    r"^decoder/.*(q_proj|k_proj|v_proj|o_proj|gate_proj|up_proj|down_proj|lm_head)/kernel$"
)


def quantize_decoder_int8(params: dict) -> dict:
    """Weight-only int8 for the decoder projections (see
    ``ops.quant.quantize_tree_int8`` for the mechanics; apply AFTER the
    dtype-policy cast so the grid is computed from serving weights)."""
    from ...ops.quant import quantize_tree_int8

    return quantize_tree_int8(params, _QUANT_KERNEL, "decoder")


def convert_vlm_checkpoint(
    state: dict[str, np.ndarray],
    init_params: dict | None = None,
    tie_word_embeddings: bool = True,
) -> dict:
    """Normalize prefixes (``language_model.`` wrappers), convert, and gate
    against the init tree. Native (``/``-pathed) checkpoints pass through."""
    if is_native_checkpoint(state):
        params = split_collections(state)["params"]
        if init_params is not None:
            assert_tree_shapes(params, init_params)
        return params
    normalized: dict[str, np.ndarray] = {}
    for key, val in state.items():
        key = key.removeprefix("language_model.")
        if key.startswith("model.vision_tower."):
            key = key.removeprefix("model.")
        normalized[key] = val
    drop = list(DROP)
    if tie_word_embeddings:
        drop.append(r"^lm_head\.weight$")
    flat = apply_rules(normalized, DECODER_RULES + VISION_RULES, drop=drop)
    params = unflatten(_stack_experts(_split_fused(flat)))
    if init_params is not None:
        assert_tree_shapes(params, init_params)
    return params
