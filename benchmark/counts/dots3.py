"""A ``dots3_note`` decoder behind the repo's ViT tower: the counts of a
``models.vlm`` entry that names ``"counts": "dots3"``. What the algorithm
needs, whatever implements it: attention un-absorbed over the keys a layer
really attends to (the indexer's ``index_topk`` in a full layer, the window
in a window layer), the indexer's own pass over every causal key once the
context is over its top-k, and of the experts only those held HERE that a
token reaches (``num_experts_per_tok`` x held / router width of them on
average, plus the shared one), not the published whole."""

from __future__ import annotations

from benchmark.counts.vlm import image_flops  # noqa: F401 - the shared tower

FULL, WINDOW = "full_attention", "sliding_attention"


def dims(cfg: dict) -> dict:
    t = cfg["text_config"]
    kinds = t["layer_types"][: t["num_hidden_layers"]]
    held, ep = t["n_routed_experts"], t.get("ep_size", 1)
    return {
        "t": t, "h": t["hidden_size"], "kinds": kinds, "dense": t.get("first_k_dense_replace", 0),
        "held": held, "width": held * ep, "k": t["num_experts_per_tok"], "f": t["moe_intermediate_size"],
        "shared": t.get("n_shared_experts", 0), "vocab": t["vocab_size"],
    }


def kind_dims(t: dict, kind: str) -> dict:
    s = "swa_" if kind == WINDOW else ""
    return {"heads": t[s + "num_attention_heads"], "q_lora": t[s + "q_lora_rank"], "kv_lora": t[s + "kv_lora_rank"],
            "nope": t[s + "qk_nope_head_dim"], "rope": t[s + "qk_rope_head_dim"], "v": t[s + "v_head_dim"]}


def attention_params(t: dict, kind: str) -> int:
    """Weights of one attention layer every token is multiplied with (the
    indexer's projections in a full layer among them)."""
    h, a = t["hidden_size"], kind_dims(t, kind)
    n = (h * a["q_lora"] + a["q_lora"] * a["heads"] * (a["nope"] + a["rope"]) + h * (a["kv_lora"] + a["rope"])
         + a["kv_lora"] * a["heads"] * (a["nope"] + a["v"]) + a["heads"] * a["v"] * h + h * a["heads"])
    if kind == FULL:
        n += a["q_lora"] * t["index_n_heads"] * t["index_head_dim"] + h * t["index_head_dim"] + h * t["index_n_heads"]
    return n


def expert_params(d: dict) -> int:
    return 3 * d["h"] * d["f"]


def feed_forward_params(d: dict, layer: int, experts_reached: float) -> float:
    """Weights of layer ``layer``'s feed-forward a token is multiplied with,
    ``experts_reached`` of the held experts among them."""
    if layer < d["dense"]:
        return 3 * d["h"] * d["t"]["intermediate_size"]
    return d["h"] * d["width"] + (experts_reached + d["shared"]) * expert_params(d)


def matmul_params(cfg: dict, experts_reached: float | None = None) -> float:
    """Weights a token is multiplied with in the layers: by default with the
    held experts it reaches on average, ``k * held / width``."""
    d = dims(cfg)
    reached = d["k"] * d["held"] / d["width"] if experts_reached is None else experts_reached
    return sum(attention_params(d["t"], kind) + feed_forward_params(d, i, reached)
               for i, kind in enumerate(d["kinds"]))


def attention_flops(t: dict, kind: str, context: float) -> float:
    """Scores and weighted values of one token in one layer of ``kind`` with
    ``context`` causal keys (itself included), and the indexer's pass."""
    a = kind_dims(t, kind)
    if kind == WINDOW:
        keys, index = min(context, t["sliding_window_size"]), 0.0
    else:
        keys = min(context, t["index_topk"])
        index = 2 * context * t["index_n_heads"] * (t["index_head_dim"] + 1) if context > t["index_topk"] else 0.0
    return 2 * keys * a["heads"] * (a["nope"] + a["rope"] + a["v"]) + index


def decoder_token_flops(cfg: dict, context: float, with_head: bool) -> float:
    d = dims(cfg)
    attn = sum(attention_flops(d["t"], kind, context) for kind in d["kinds"])
    return 2 * matmul_params(cfg) + attn + (2 * d["h"] * d["vocab"] if with_head else 0)


def prefill_flops(cfg: dict, prompt_tokens: int) -> float:
    """``prompt_tokens`` merged tokens through the decoder, token ``i``
    attending to ``i + 1`` causal keys under its layer's rule, the head at
    the last one."""
    d = dims(cfg)
    attn = sum(attention_flops(d["t"], kind, i + 1) for kind in d["kinds"] for i in range(int(prompt_tokens)))
    return prompt_tokens * 2 * matmul_params(cfg) + attn + 2 * d["h"] * d["vocab"]


def decode_token_flops(cfg: dict, context: float) -> float:
    return decoder_token_flops(cfg, context, True)


def experts_touched(d: dict, rows: float) -> float:
    """Held experts that at least one of ``rows`` tokens reaches, on average."""
    return d["held"] * (1.0 - (1.0 - d["k"] / d["width"]) ** rows)


def cache_bytes_read(t: dict, kind: str, context: float, kv_bytes: int = 2) -> float:
    """Cache bytes one decode row must read in one layer: the latent rows of
    the keys it attends to and, past the top-k, every index key."""
    a = kind_dims(t, kind)
    if kind == WINDOW:
        return min(context, t["sliding_window_size"]) * (a["kv_lora"] + a["rope"]) * kv_bytes
    index = context * t["index_head_dim"] * kv_bytes if context > t["index_topk"] else 0.0
    return min(context, t["index_topk"]) * (a["kv_lora"] + a["rope"]) * kv_bytes + index


def decode_step_bytes(cfg: dict, rows: float, context: float, weight_bytes: float, kv_bytes: int = 2) -> float:
    """Bytes one decode step must read: the attention, dense, router and
    shared weights and the head once, the held experts its ``rows`` tokens
    touch, and each row's cache as :func:`cache_bytes_read` counts it."""
    d = dims(cfg)
    weights = sum(
        attention_params(d["t"], kind)
        + (feed_forward_params(d, i, 0.0) if i < d["dense"]
           else feed_forward_params(d, i, experts_touched(d, rows)))
        for i, kind in enumerate(d["kinds"])
    ) * weight_bytes + d["h"] * d["vocab"] * 2
    cache = rows * sum(cache_bytes_read(d["t"], kind, context, kv_bytes) for kind in d["kinds"])
    return weights + cache
