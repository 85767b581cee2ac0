"""Work of the held experts' grouped matrix multiplications: each
assignment that lands on a held expert goes through that expert's gate, up
and down matrices; each expert that got a token has its three matrices read
once in a call. One layer call is three grouped multiplications."""

from __future__ import annotations

from benchmark.rooflines.paged_attn import least_seconds  # noqa: F401

MATMULS_PER_LAYER_CALL = 3


def work(assignments: float, experts_touched: float, hidden: int, inter: int, layer_calls: float = 1,
         weight_bytes: int = 2, io_bytes: int = 2) -> dict:
    flops = layer_calls * assignments * 2 * 3 * hidden * inter
    weights = layer_calls * experts_touched * 3 * hidden * inter * weight_bytes
    io = layer_calls * assignments * (2 * hidden + 3 * inter) * io_bytes
    return {"flops": float(flops), "bytes": float(weights + io)}
