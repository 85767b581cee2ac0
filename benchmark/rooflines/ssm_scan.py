"""Work of a prefill segment's Mamba-2 scan, from shapes: the recurrence
token by token (every state value decayed and added to, three operations, and
read out, two), the segment's x, B, C and dt read and its y written, the
row's state read and written once a call. Counts what the algorithm needs,
whatever implements it (the chunked form multiplies whole blocks instead)."""

from __future__ import annotations

from benchmark.rooflines.paged_attn import least_seconds  # noqa: F401 - the same two peaks bound every kernel


def work(tokens: float, heads: int, head_dim: int, state: int, calls: float = 1, io_bytes: int = 2) -> dict:
    """``calls`` calls of ``tokens`` live tokens each (one row a call)."""
    values = heads * head_dim * state
    flops = calls * tokens * 5 * values
    moved = calls * (tokens * ((2 * heads * head_dim + 2 * state) * io_bytes + heads * 4) + 2 * values * 4)
    return {"flops": float(flops), "bytes": float(moved)}


def cell_work(t: dict, tokens: float, calls: int) -> dict:
    return work(tokens, t["mamba_n_heads"], t["mamba_d_head"], t["mamba_d_state"], calls)
