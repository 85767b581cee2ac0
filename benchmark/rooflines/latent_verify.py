"""Work of absorbed latent decode attention over a verify window of two
queries a row (the pending token and its draft), from shapes: a decoder whose
every layer has an indexer, so each query attends the ``index_topk`` causal
keys its own indexer picked once the context is past it. Absorbed form
(``rooflines/latent_paged.py`` ``work``): for each query, scores against
``latent + rope`` values a key and weighted values against ``latent``
values a key, for every head; each page of the two selections' union is
read once, shared by both queries and every head: the union counted as one
selection (two consecutive positions keep nearly the same keys; the union
is at least one of them, so the bytes are a floor and the share a floor).
Counts what the algorithm needs, whatever implements it."""

from __future__ import annotations

from benchmark.rooflines.paged_attn import least_seconds  # noqa: F401 - the same two peaks bound every kernel

#: queries a row in a verify window
WINDOW = 2


def work(rows: float, keys: float, heads: int, latent: int, rope: int, calls: float = 1,
         kv_bytes: int = 2, io_bytes: int = 2) -> dict:
    queries = calls * rows * WINDOW
    flops = queries * heads * keys * 2 * ((latent + rope) + latent)
    cache = calls * rows * keys * (latent + rope) * kv_bytes
    io = queries * heads * ((latent + rope) + latent) * io_bytes
    return {"flops": float(flops), "bytes": float(cache + io)}


def cell_work(t: dict, rows: float, context: float, calls: int) -> dict:
    """``calls`` kernel calls, each one layer of a decode step of ``rows``
    rows at ``context`` keys a row."""
    keys = min(context, t["index_topk"])
    return work(rows, keys, t["num_attention_heads"], t["kv_lora_rank"], t["qk_rope_head_dim"], calls)
