"""Work of absorbed latent decode attention over EVERY causal key of a row,
from shapes: a decoder whose latent layers have no indexer and no window, so
a query token's keys are the row's whole length, in every layer alike.
Absorbed form (``rooflines/latent_paged.py`` ``work``): scores against
``latent + rope`` values a key and weighted values against ``latent`` values
a key, for every head; each key's row is read once, shared by all heads.
Counts what the algorithm needs, whatever implements it."""

from __future__ import annotations

from benchmark.rooflines.latent_paged import work
from benchmark.rooflines.paged_attn import least_seconds  # noqa: F401 - the same two peaks bound every kernel


def cell_work(t: dict, rows: float, context: float, calls: int) -> dict:
    """``calls`` kernel calls, each one layer of a decode step of ``rows``
    rows at ``context`` keys a row."""
    return work(rows, context, t["num_attention_heads"], t["kv_lora_rank"], t["qk_rope_head_dim"], calls)
