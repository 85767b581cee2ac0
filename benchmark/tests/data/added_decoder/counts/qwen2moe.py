"""Fixture: the work of a Qwen2-MoE decoder behind the repo's tower. A token
is multiplied with its attention weights, and in a sparse layer with the
router, its ``num_experts_per_tok`` routed experts, the shared expert and
its gate; a decode step must read every expert that some row of it routes
to (routing taken as uniform: ``E x (1 - (1 - k/E)^rows)`` of ``E``)."""

from __future__ import annotations

from benchmark.counts import vlm as qwen2

image_flops = qwen2.image_flops  # the same tower and projector


def _layers(t: dict) -> tuple[int, int]:
    sparse = sum(1 for i in range(t["num_hidden_layers"])
                 if i not in t.get("mlp_only_layers", ()) and (i + 1) % t.get("decoder_sparse_step", 1) == 0)
    return t["num_hidden_layers"] - sparse, sparse


def _attention_params(t: dict) -> int:
    h, nh = t["hidden_size"], t["num_attention_heads"]
    dh = t.get("head_dim") or h // nh
    return 2 * h * nh * dh + 2 * h * t["num_key_value_heads"] * dh


def _sparse_fixed_params(t: dict) -> int:
    """Router, shared expert and its gate: read and multiplied whatever is routed."""
    h = t["hidden_size"]
    return h * t["num_experts"] + 3 * h * t["shared_expert_intermediate_size"] + h


def matmul_params(cfg: dict, experts_a_layer: float) -> float:
    """Weights of the decoder's layers with ``experts_a_layer`` routed experts
    counted in each sparse layer."""
    t = cfg["text_config"]
    h = t["hidden_size"]
    dense, sparse = _layers(t)
    expert = 3 * h * t["moe_intermediate_size"]
    return (t["num_hidden_layers"] * _attention_params(t) + dense * 3 * h * t["intermediate_size"]
            + sparse * (_sparse_fixed_params(t) + experts_a_layer * expert))


def _token_flops(cfg: dict, context: float, with_head: bool) -> float:
    t = cfg["text_config"]
    dh = t.get("head_dim") or t["hidden_size"] // t["num_attention_heads"]
    attn = t["num_hidden_layers"] * 2 * 2 * context * t["num_attention_heads"] * dh
    head = 2 * t["hidden_size"] * t["vocab_size"] if with_head else 0
    return 2 * matmul_params(cfg, t["num_experts_per_tok"]) + attn + head


def prefill_flops(cfg: dict, prompt_tokens: int) -> float:
    head = 2 * cfg["text_config"]["hidden_size"] * cfg["text_config"]["vocab_size"]
    return prompt_tokens * _token_flops(cfg, prompt_tokens / 2, False) + head


def decode_token_flops(cfg: dict, context: float) -> float:
    return _token_flops(cfg, context, True)


def decode_step_bytes(cfg: dict, rows: float, context: float, weight_bytes: float, kv_bytes: int = 2) -> float:
    t = cfg["text_config"]
    e, k = t["num_experts"], t["num_experts_per_tok"]
    touched = e * (1.0 - (1.0 - k / e) ** rows)
    dh = t.get("head_dim") or t["hidden_size"] // t["num_attention_heads"]
    weights = matmul_params(cfg, touched) * weight_bytes + t["hidden_size"] * t["vocab_size"] * 2
    kv = rows * context * t["num_hidden_layers"] * 2 * t["num_key_value_heads"] * dh * kv_bytes
    return weights + kv
