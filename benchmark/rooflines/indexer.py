"""Work of the indexer's scoring pass for a decode row: every index head's
query against the index key of every causal key, a ReLU and a weighted sum
over the heads; each index key is read once."""

from __future__ import annotations

from benchmark.rooflines.paged_attn import least_seconds  # noqa: F401


def work(rows: float, context: float, heads: int, dim: int, layers: float = 1, kv_bytes: int = 2) -> dict:
    flops = layers * rows * context * heads * (2 * dim + 2)
    read = layers * rows * (context * dim * kv_bytes + heads * dim * kv_bytes + context * 4)
    return {"flops": float(flops), "bytes": float(read)}


def cell_work(t: dict, rows: float, context: float, calls: int) -> dict:
    """``calls`` calls, each one full layer of a decode step (the pass runs
    only for rows whose context is over the top-k: in a cell whose prompts
    are, every decode row)."""
    return work(rows, context, t["index_n_heads"], t["index_head_dim"], calls)
