"""A ``glm_moe_dsa`` decoder (GLM-5) behind the repo's ViT tower: the counts
of a ``models.vlm`` entry that names ``"counts": "glm5"``. What the algorithm
needs, whatever implements it: attention un-absorbed over the keys a layer
attends to (the indexer's ``index_topk`` once the context is past it, in
every layer), the indexer's own pass over every causal key then, of the
experts only those held HERE that a token reaches (``num_experts_per_tok`` x
held / router width of them on average, plus the shared one), and the
multi-token prediction layer (a layer of the same kind behind its input
projection, and the head again).

A decode step verifies a draft: the decoder and the multi-token prediction
layer each run over two positions a row (the pending token and its draft),
and the head over both. With random weights a draft is taken at about
chance, so a step emits one token a row: ``decode_token_flops`` counts that
step's work for one emitted token. Its bytes are read once for both
positions: the weights, and a row's cache, whose two selections are counted
as one (the keys two consecutive positions keep nearly coincide; the union
is at least one of them, so the count is a floor)."""

from __future__ import annotations

from benchmark.counts.dots3 import attention_flops as _kind_attention_flops
from benchmark.counts.dots3 import experts_touched, feed_forward_params  # the same held experts, the same dense layers
from benchmark.counts.vlm import image_flops  # noqa: F401 - the shared tower

FULL = "full_attention"
#: positions a decode step runs a row through: the pending token and its draft
VERIFY = 2


def dims(cfg: dict) -> dict:
    t = cfg["text_config"]
    held, ep = t["n_routed_experts"], t.get("ep_size", 1)
    return {
        "t": t, "h": t["hidden_size"], "layers": t["num_hidden_layers"], "dense": t.get("first_k_dense_replace", 0),
        "mtp": t.get("num_nextn_predict_layers", 0),
        "held": held, "width": held * ep, "k": t["num_experts_per_tok"], "f": t["moe_intermediate_size"],
        "shared": t.get("n_shared_experts", 0), "vocab": t["vocab_size"],
    }


def attention_params(t: dict) -> int:
    """Weights of one attention layer every token is multiplied with, the
    indexer's projections among them (no gate)."""
    h, heads, q_lora, kv_lora = t["hidden_size"], t["num_attention_heads"], t["q_lora_rank"], t["kv_lora_rank"]
    nope, rope, v = t["qk_nope_head_dim"], t["qk_rope_head_dim"], t["v_head_dim"]
    j, di = t["index_n_heads"], t["index_head_dim"]
    return (h * q_lora + q_lora * heads * (nope + rope) + h * (kv_lora + rope) + kv_lora * heads * (nope + v)
            + heads * v * h + q_lora * j * di + h * di + h * j)


def matmul_params(cfg: dict, experts_reached: float | None = None) -> float:
    """Weights a token is multiplied with in the decoder's layers: by
    default with the held experts it reaches on average, ``k * held / width``."""
    d = dims(cfg)
    reached = d["k"] * d["held"] / d["width"] if experts_reached is None else experts_reached
    return sum(attention_params(d["t"]) + feed_forward_params(d, i, reached) for i in range(d["layers"]))


def mtp_params(cfg: dict, experts_reached: float | None = None) -> float:
    """Weights a token is multiplied with in the multi-token prediction
    layers: the input projection of two widths into one, and a layer with
    experts."""
    d = dims(cfg)
    reached = d["k"] * d["held"] / d["width"] if experts_reached is None else experts_reached
    one = 2 * d["h"] * d["h"] + attention_params(d["t"]) + feed_forward_params(d, d["layers"], reached)
    return d["mtp"] * one


def attention_flops(t: dict, context: float) -> float:
    """Scores and weighted values of one token in one layer with ``context``
    causal keys (itself included): the indexer's top-k of them once the
    context is past it, and the indexer's pass over all of them then."""
    return _kind_attention_flops(t, FULL, context)


def token_flops(cfg: dict, context: float, with_head: bool) -> float:
    """One position through the decoder and the multi-token prediction
    layers, each with the head where ``with_head``."""
    d = dims(cfg)
    attn = (d["layers"] + d["mtp"]) * attention_flops(d["t"], context)
    head = (1 + d["mtp"]) * 2 * d["h"] * d["vocab"] if with_head else 0
    return 2 * (matmul_params(cfg) + mtp_params(cfg)) + attn + head


def prefill_flops(cfg: dict, prompt_tokens: int) -> float:
    """``prompt_tokens`` merged tokens through the decoder and the multi-token
    prediction layer, token ``i`` attending to its ``i + 1`` causal keys
    under the indexer's rule, the head and the draft head at the last."""
    d = dims(cfg)
    attn = sum(attention_flops(d["t"], i + 1) for i in range(int(prompt_tokens)))
    return (prompt_tokens * 2 * (matmul_params(cfg) + mtp_params(cfg)) + (d["layers"] + d["mtp"]) * attn
            + (1 + d["mtp"]) * 2 * d["h"] * d["vocab"])


def decode_token_flops(cfg: dict, context: float) -> float:
    """A verify step's work for the one token it emits at random weights:
    two positions through everything, the head on both."""
    return sum(token_flops(cfg, context + i, True) for i in range(VERIFY))


def cache_bytes_read(t: dict, context: float, kv_bytes: int = 2) -> float:
    """Cache bytes one decode row must read in one layer for its verify
    window: the latent rows of the keys it attends to (the two positions'
    selections counted as one) and, past the top-k, every index key once."""
    index = context * t["index_head_dim"] * kv_bytes if context > t["index_topk"] else 0.0
    return min(context, t["index_topk"]) * (t["kv_lora_rank"] + t["qk_rope_head_dim"]) * kv_bytes + index


def decode_step_bytes(cfg: dict, rows: float, context: float, weight_bytes: float, kv_bytes: int = 2) -> float:
    """Bytes one decode step must read: every layer's attention, dense,
    router and shared weights, the multi-token prediction layer's and its
    input projection, the head once, the held experts the step's ``2 *
    rows`` positions touch in each expert layer, and each row's cache in
    every layer as :func:`cache_bytes_read` counts it."""
    d = dims(cfg)
    touched = experts_touched(d, VERIFY * rows)
    layers = sum(
        attention_params(d["t"]) + feed_forward_params(d, i, 0.0 if i < d["dense"] else touched)
        for i in range(d["layers"])
    )
    weights = (layers + mtp_params(cfg, touched)) * weight_bytes + d["h"] * d["vocab"] * 2
    return weights + rows * (d["layers"] + d["mtp"]) * cache_bytes_read(d["t"], context + 1, kv_bytes)
