"""Work of ragged paged decode attention, from shapes: one query token a
row against its own ``context`` cached keys and values, grouped-query
heads. Counts what the algorithm needs, whatever implements it."""

from __future__ import annotations


def work(rows: float, context: float, heads: int, kv_heads: int, head_dim: int, layers: int = 1,
         kv_bytes: int = 2, io_bytes: int = 2) -> dict:
    """Operations and bytes of ``layers`` calls with ``rows`` rows of mean
    live length ``context``: scores and weighted values are 2 x 2 x context x
    heads x head_dim operations a row; each row's keys and values are read
    once, its query read and its output written."""
    flops = layers * rows * 2 * 2 * context * heads * head_dim
    kv = layers * rows * context * 2 * kv_heads * head_dim * kv_bytes
    io = layers * rows * 2 * heads * head_dim * io_bytes
    return {"flops": float(flops), "bytes": float(kv + io)}


def least_seconds(w: dict, peak_flops: float, peak_bytes_per_s: float) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_f, t_b = w["flops"] / peak_flops, w["bytes"] / peak_bytes_per_s
    return (t_f, "compute") if t_f >= t_b else (t_b, "bandwidth")
