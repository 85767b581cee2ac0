"""A Qwen2 decoder behind the repo's ViT tower: the counts of every
``models.vlm`` entry that names no other. Another decoder's counts take
``image_flops`` from here where they share the tower."""

from __future__ import annotations

from benchmark.counts.common import block_flops


def _decoder_dims(cfg: dict) -> dict:
    t = cfg["text_config"]
    h, nh, nkv = t["hidden_size"], t["num_attention_heads"], t["num_key_value_heads"]
    dh = t.get("head_dim") or h // nh
    return {"h": h, "q": nh * dh, "kv": nkv * dh, "dh": dh, "nh": nh, "nkv": nkv,
            "inter": t["intermediate_size"], "layers": t["num_hidden_layers"], "vocab": t["vocab_size"]}


def decoder_matmul_params(cfg: dict) -> int:
    """Weights every token is multiplied with in the decoder's layers."""
    d = _decoder_dims(cfg)
    per_layer = d["h"] * d["q"] + 2 * d["h"] * d["kv"] + d["q"] * d["h"] + 3 * d["h"] * d["inter"]
    return d["layers"] * per_layer


def decoder_token_flops(cfg: dict, context: float, with_head: bool) -> float:
    """One token through the decoder with ``context`` keys to attend to."""
    d = _decoder_dims(cfg)
    attn = d["layers"] * 2 * 2 * context * d["q"]
    head = 2 * d["h"] * d["vocab"] if with_head else 0
    return 2 * decoder_matmul_params(cfg) + attn + head


def image_flops(cfg: dict) -> int:
    """One image through the captioner's tower and projector."""
    v, h = cfg["vision_config"], cfg["text_config"]["hidden_size"]
    w, p = v["hidden_size"], v["patch_size"]
    n = (v["image_size"] // p) ** 2
    return (2 * n * 3 * p * p * w + v["num_hidden_layers"] * block_flops(n, w, 4 * w)
            + 2 * n * w * h + 2 * n * h * h)


def prefill_flops(cfg: dict, prompt_tokens: int) -> float:
    """``prompt_tokens`` merged tokens through the decoder, each attending to
    those before it (half the prompt on average), the head at the last one."""
    head = 2 * cfg["text_config"]["hidden_size"] * cfg["text_config"]["vocab_size"]
    return prompt_tokens * decoder_token_flops(cfg, prompt_tokens / 2, False) + head


def decode_token_flops(cfg: dict, context: float) -> float:
    """One decoded token, the head included."""
    return decoder_token_flops(cfg, context, True)


def decode_step_bytes(cfg: dict, rows: float, context: float, weight_bytes: float, kv_bytes: int = 2) -> float:
    """Bytes one decode step must read: every layer's weights and the head
    once (``weight_bytes`` a parameter of the layers; the tied head stays in
    its 2-byte type), and the live keys and values of ``rows`` rows."""
    d = _decoder_dims(cfg)
    weights = decoder_matmul_params(cfg) * weight_bytes + d["h"] * d["vocab"] * 2
    kv = rows * context * d["layers"] * 2 * d["kv"] * kv_bytes
    return weights + kv
