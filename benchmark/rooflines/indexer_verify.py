"""Work of the indexer's scoring pass for a verify window of two queries a
row (the pending token and its draft): every index head of each query
against the index key of every causal key, a ReLU and a weighted sum over
the heads; each index key is read once for both queries, and each query's
scores written once."""

from __future__ import annotations

from benchmark.rooflines.paged_attn import least_seconds  # noqa: F401

#: queries a row in a verify window
WINDOW = 2


def work(rows: float, context: float, heads: int, dim: int, calls: float = 1, kv_bytes: int = 2) -> dict:
    flops = calls * rows * WINDOW * context * heads * (2 * dim + 2)
    read = calls * rows * (context * dim * kv_bytes + WINDOW * (heads * dim * kv_bytes + context * 4))
    return {"flops": float(flops), "bytes": float(read)}


def cell_work(t: dict, rows: float, context: float, calls: int) -> dict:
    """``calls`` calls, each one layer of a decode step (the pass runs for
    rows whose context is past the top-k: in the cell whose prompts are,
    every decode row)."""
    return work(rows, context, t["index_n_heads"], t["index_head_dim"], calls)
