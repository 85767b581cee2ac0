"""A ``granitemoehybrid`` decoder behind the repo's ViT tower: the counts of
a ``models.vlm`` entry that names ``"counts": "granite"``. What the
algorithm needs, whatever implements it: a Mamba-2 layer's projections and
its recurrence token by token (the state decayed, added to and read out
once a token, never the chunked form's block products), attention over the
causal keys in the attention layers alone, and of the experts only those held
HERE that a token reaches (``num_experts_per_tok`` x held / router width of
them on average, plus the shared one), not the published whole."""

from __future__ import annotations

from benchmark.counts.vlm import image_flops  # noqa: F401 - the shared tower

MAMBA, ATTENTION = "mamba", "attention"


def dims(cfg: dict) -> dict:
    t = cfg["text_config"]
    h, nh = t["hidden_size"], t["num_attention_heads"]
    dh = t.get("head_dim") or h // nh
    held, ep = t["num_local_experts"], t.get("ep_size", 1)
    heads, n = t["mamba_n_heads"], t["mamba_d_state"]
    inner = heads * t["mamba_d_head"]
    return {
        "t": t, "h": h, "kinds": t["layer_types"][: t["num_hidden_layers"]], "vocab": t["vocab_size"],
        "q": nh * dh, "kv": t["num_key_value_heads"] * dh,
        "held": held, "width": held * ep, "k": t["num_experts_per_tok"], "f": t["intermediate_size"],
        "fs": t.get("shared_intermediate_size", 0),
        "heads": heads, "inner": inner, "n": n, "conv": t["mamba_d_conv"],
        "conv_dim": inner + 2 * t.get("mamba_n_groups", 1) * n,
    }


def mixer_params(d: dict, kind: str) -> int:
    """Weights of one mixer every token is multiplied with."""
    if kind == MAMBA:
        return d["h"] * (d["inner"] + d["conv_dim"] + d["heads"]) + d["inner"] * d["h"]
    return 2 * d["h"] * d["q"] + 2 * d["h"] * d["kv"]


def expert_params(d: dict) -> int:
    return 3 * d["h"] * d["f"]


def feed_forward_params(d: dict, experts_reached: float) -> float:
    """Weights of a layer's feed-forward a token is multiplied with,
    ``experts_reached`` of the held experts among them."""
    return d["h"] * d["width"] + experts_reached * expert_params(d) + 3 * d["h"] * d["fs"]


def matmul_params(cfg: dict, experts_reached: float | None = None) -> float:
    """Weights a token is multiplied with in the layers: by default with the
    held experts it reaches on average, ``k * held / width``."""
    d = dims(cfg)
    reached = d["k"] * d["held"] / d["width"] if experts_reached is None else experts_reached
    return sum(mixer_params(d, kind) + feed_forward_params(d, reached) for kind in d["kinds"])


def state_values(d: dict) -> int:
    """Values of one row's scan state in one Mamba layer."""
    return d["inner"] * d["n"]


def recurrence_flops(d: dict) -> float:
    """One token through one Mamba layer's convolution and recurrence: every
    state value decayed and added to (three operations) and read out (two),
    ``mamba_d_conv`` taps a channel."""
    return 5 * state_values(d) + 2 * d["conv"] * d["conv_dim"]


def mixing_flops(d: dict, context: float) -> float:
    """What one token costs in the mixers beside their projections, with
    ``context`` causal keys (itself included) in an attention layer."""
    return sum(recurrence_flops(d) if kind == MAMBA else 2 * 2 * context * d["q"] for kind in d["kinds"])


def prefill_flops(cfg: dict, prompt_tokens: int) -> float:
    """``prompt_tokens`` merged tokens through the decoder, each attending to
    those before it (half the prompt on average), the head at the last one."""
    d = dims(cfg)
    return (prompt_tokens * (2 * matmul_params(cfg) + mixing_flops(d, prompt_tokens / 2))
            + 2 * d["h"] * d["vocab"])


def decode_token_flops(cfg: dict, context: float) -> float:
    d = dims(cfg)
    return 2 * matmul_params(cfg) + mixing_flops(d, context) + 2 * d["h"] * d["vocab"]


def experts_touched(d: dict, rows: float) -> float:
    """Held experts that at least one of ``rows`` tokens reaches, on average."""
    return d["held"] * (1.0 - (1.0 - d["k"] / d["width"]) ** rows)


def state_bytes(d: dict, kv_bytes: int = 2) -> int:
    """One row's state in one Mamba layer: the scan state in float32 and the
    convolution's tail in the cache's type."""
    return state_values(d) * 4 + (d["conv"] - 1) * d["conv_dim"] * kv_bytes


def decode_step_bytes(cfg: dict, rows: float, context: float, weight_bytes: float, kv_bytes: int = 2) -> float:
    """Bytes one decode step must move: the mixers', routers' and shared
    experts' weights and the head once, the held experts its ``rows`` tokens
    touch, each row's state read AND written in every Mamba layer, and each
    row's keys and values in the attention layers."""
    d = dims(cfg)
    touched = experts_touched(d, rows)
    weights = sum(mixer_params(d, kind) + feed_forward_params(d, touched) for kind in d["kinds"]) * weight_bytes
    mamba_layers = d["kinds"].count(MAMBA)
    state = rows * mamba_layers * 2 * state_bytes(d, kv_bytes)
    kv = rows * context * (len(d["kinds"]) - mamba_layers) * 2 * d["kv"] * kv_bytes
    return weights + d["h"] * d["vocab"] * 2 + state + kv
