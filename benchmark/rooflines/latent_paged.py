"""Work of absorbed latent decode attention, from shapes: one query token a
row against the latent rows of the keys its layer attends to (the
indexer's top-k in a full layer, the window in a window layer). Absorbed
form: scores against ``latent + rope`` values a key and weighted values
against ``latent`` values a key, for every head; each key's row is read
once, shared by all heads. Counts what the algorithm needs, whatever
implements it (the kernel also visits the pages of keys it then masks)."""

from __future__ import annotations

from benchmark.rooflines.paged_attn import least_seconds  # noqa: F401 - the same two peaks bound every kernel

FULL, WINDOW = "full_attention", "sliding_attention"


def work(rows: float, keys: float, heads: int, latent: int, rope: int, layers: float = 1,
         kv_bytes: int = 2, io_bytes: int = 2) -> dict:
    flops = layers * rows * heads * keys * 2 * ((latent + rope) + latent)
    cache = layers * rows * keys * (latent + rope) * kv_bytes
    io = layers * rows * heads * ((latent + rope) + latent) * io_bytes
    return {"flops": float(flops), "bytes": float(cache + io)}


def cell_work(t: dict, rows: float, context: float, calls: int) -> dict:
    """``calls`` kernel calls of a decoder whose layers are ``t``'s kinds, in
    their ratio: each call one layer of a decode step."""
    kinds = t["layer_types"][: t["num_hidden_layers"]]
    total = {"flops": 0.0, "bytes": 0.0}
    for kind in (FULL, WINDOW):
        share = calls * kinds.count(kind) / len(kinds)
        s = "swa_" if kind == WINDOW else ""
        keys = min(context, t["sliding_window_size"] if kind == WINDOW else t["index_topk"])
        w = work(rows, keys, t[s + "num_attention_heads"], t[s + "kv_lora_rank"], t[s + "qk_rope_head_dim"], share)
        total = {k: total[k] + w[k] for k in total}
    return total
