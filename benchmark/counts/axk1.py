"""An ``axk1`` decoder behind the repo's ViT tower: the counts of a
``models.vlm`` entry that names ``"counts": "axk1"``. What the algorithm
needs, whatever implements it: latent attention un-absorbed over EVERY causal
key in every layer (the decoder has no indexer and no window: nothing cuts a
row's keys), and of the experts only those held HERE that a token reaches
(``num_experts_per_tok`` x held / router width of them on average, plus the
shared one), not the published whole. The group limit moves which experts a
token selects, not how many."""

from __future__ import annotations

from benchmark.counts.dots3 import experts_touched, feed_forward_params  # the same held experts, the same dense layers
from benchmark.counts.vlm import image_flops  # noqa: F401 - the shared tower


def dims(cfg: dict) -> dict:
    t = cfg["text_config"]
    held, ep = t["n_routed_experts"], t.get("ep_size", 1)
    return {
        "t": t, "h": t["hidden_size"], "layers": t["num_hidden_layers"], "dense": t.get("first_k_dense_replace", 0),
        "held": held, "width": held * ep, "k": t["num_experts_per_tok"], "f": t["moe_intermediate_size"],
        "shared": t.get("n_shared_experts", 0), "vocab": t["vocab_size"],
    }


def attention_params(t: dict) -> int:
    """Weights of one attention layer every token is multiplied with."""
    h, heads = t["hidden_size"], t["num_attention_heads"]
    nope, rope, v = t["qk_nope_head_dim"], t["qk_rope_head_dim"], t["v_head_dim"]
    return (h * t["q_lora_rank"] + t["q_lora_rank"] * heads * (nope + rope) + h * (t["kv_lora_rank"] + rope)
            + t["kv_lora_rank"] * heads * (nope + v) + heads * v * h)


def matmul_params(cfg: dict, experts_reached: float | None = None) -> float:
    """Weights a token is multiplied with in the layers: by default with the
    held experts it reaches on average, ``k * held / width``."""
    d = dims(cfg)
    reached = d["k"] * d["held"] / d["width"] if experts_reached is None else experts_reached
    return sum(attention_params(d["t"]) + feed_forward_params(d, i, reached) for i in range(d["layers"]))


def attention_flops(t: dict, context: float) -> float:
    """Scores and weighted values of one token in one layer with ``context``
    causal keys (itself included): every one of them, for every head."""
    return 2 * context * t["num_attention_heads"] * (t["qk_nope_head_dim"] + t["qk_rope_head_dim"] + t["v_head_dim"])


def prefill_flops(cfg: dict, prompt_tokens: int) -> float:
    """``prompt_tokens`` merged tokens through the decoder, token ``i``
    attending to all ``i + 1`` causal keys in every layer, the head at the
    last one."""
    d, n = dims(cfg), int(prompt_tokens)
    attn = d["layers"] * attention_flops(d["t"], n * (n + 1) / 2)  # linear in the context: the sum of 1..n
    return n * 2 * matmul_params(cfg) + attn + 2 * d["h"] * d["vocab"]


def decode_token_flops(cfg: dict, context: float) -> float:
    d = dims(cfg)
    return 2 * matmul_params(cfg) + d["layers"] * attention_flops(d["t"], context) + 2 * d["h"] * d["vocab"]


def cache_bytes_read(t: dict, context: float, kv_bytes: int = 2) -> float:
    """Cache bytes one decode row must read in one layer: the latent row and
    the position key of every causal key."""
    return context * (t["kv_lora_rank"] + t["qk_rope_head_dim"]) * kv_bytes


def decode_step_bytes(cfg: dict, rows: float, context: float, weight_bytes: float, kv_bytes: int = 2) -> float:
    """Bytes one decode step must read: the attention, dense, router and
    shared weights and the head once, the held experts its ``rows`` tokens
    touch, and each row's whole cache in every layer."""
    d = dims(cfg)
    weights = sum(
        attention_params(d["t"])
        + (feed_forward_params(d, i, 0.0) if i < d["dense"] else feed_forward_params(d, i, experts_touched(d, rows)))
        for i in range(d["layers"])
    ) * weight_bytes + d["h"] * d["vocab"] * 2
    return weights + rows * d["layers"] * cache_bytes_read(d["t"], context, kv_bytes)
