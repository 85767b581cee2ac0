"""What the counts modules share. A counts module is
``benchmark/counts/<name>.py``: operations and bytes the algorithm needs,
counted from one model entry's ``config`` alone, whatever implements it. A
multiply-add is two operations. The whole-step shares of the chip's peak
(``*_step_mfu``, ``vlm_step_hbm_pct``) ask it for:

- ``image_flops(cfg)``: one image through the tower (and projection);
- ``prefill_flops(cfg, prompt_tokens)``: a prefill of that many merged tokens;
- ``decode_token_flops(cfg, context)``: one decoded token, head and all,
  with ``context`` keys to attend to;
- ``decode_step_bytes(cfg, rows, context, weight_bytes)``: the bytes one
  decode step must read.

A tower-only family (``clip``) gives the first alone. A kernel's own count
lives in ``benchmark/rooflines/<kernel>.py``."""

from __future__ import annotations


def block_flops(tokens: int, width: int, inter: int) -> int:
    """One pre-LN transformer block over ``tokens`` tokens attending to each
    other: q/k/v/out projections, scores and weighted values, two-matrix MLP."""
    proj = 4 * 2 * tokens * width * width
    attn = 2 * 2 * tokens * tokens * width
    mlp = 2 * 2 * tokens * width * inter
    return proj + attn + mlp
