"""Work of a decode step's Mamba-2 state update, from shapes: one token a
row; every value of the row's state ``[heads * head_dim, d_state]`` decayed
and added to (three operations), read out against C (two), read from memory
and written back once, in float32. Counts what the algorithm needs,
whatever implements it."""

from __future__ import annotations

from benchmark.rooflines.paged_attn import least_seconds  # noqa: F401 - the same two peaks bound every kernel

MAMBA = "mamba"


def work(rows: float, heads: int, head_dim: int, state: int, calls: float = 1, io_bytes: int = 2) -> dict:
    values = heads * head_dim * state
    flops = calls * rows * 5 * values
    moved = calls * rows * (2 * values * 4 + (2 * heads * head_dim + 2 * state) * io_bytes + heads * 4)
    return {"flops": float(flops), "bytes": float(moved)}


def cell_work(t: dict, rows: float, context: float, calls: int) -> dict:
    """``calls`` kernel calls, each one Mamba layer of a decode step of
    ``rows`` live rows (``context`` does not enter: the state's size does
    not grow with a row's length)."""
    return work(rows, t["mamba_n_heads"], t["mamba_d_head"], t["mamba_d_state"], calls)
