"""Helpers the readers share. A reader is ``read(ctx, spec) -> float | None``:
``ctx`` holds the cell, the window's ``result`` (client view, the program's
counters ``before`` and ``after``), the ``trace`` in neutral form with its
``reduced`` numbers, and the chip's ``peaks``; ``spec`` is the metric's own
file. A reader that finds nothing to read returns None."""

from __future__ import annotations

from benchmark import cells


def gauge(snapshot: dict, prefix: str) -> dict:
    """The first gauge whose name is ``prefix`` or starts with it."""
    gauges = snapshot.get("gauges", {})
    if prefix in gauges:
        return gauges[prefix]
    for name in sorted(gauges):
        if name.startswith(prefix):
            return gauges[name]
    return {}


def delta(ctx: dict, prefix: str, field: str):
    before, after = gauge(ctx["result"]["before"], prefix), gauge(ctx["result"]["after"], prefix)
    if field not in after:
        return None
    return after[field] - before.get(field, 0)


def model_config(ctx: dict, family: str) -> dict:
    return ctx["cell"].config["models"][family]["config"]


def counts(ctx: dict, family: str):
    """The module that counts the work of the cell's ``family`` model:
    ``benchmark/counts/<name>.py``, where ``<name>`` is the model entry's
    ``counts`` key or, for an entry without one, the family's own name."""
    model = ctx["cell"].config["models"][family]
    return cells.load_module("counts", model.get("counts", family), ctx["cell"].here)


def vlm_prompt_tokens(ctx: dict) -> int:
    """Merged prompt length: role, image tokens, instruction, role."""
    v = model_config(ctx, "vlm")["vision_config"]
    return int(ctx["cell"].traffic["instruction_tokens"]) + 2 + (v["image_size"] // v["patch_size"]) ** 2


def decode_rows(ctx: dict) -> float | None:
    """Mean rows in a decode step over the window: streamed tokens less each
    request's first (prefill makes that one), over blocks run x block length."""
    client = ctx["result"]["client"]
    blocks = delta(ctx, "vlm-continuous:", "blocks_run")
    if not blocks:
        return None
    block = int(ctx["result"]["settings"]["vlm"]["decode_block"])
    return max(0, client["tokens_total"] - client["attempted"]) / (blocks * block)


def mean_context(ctx: dict) -> float:
    """Mean live length of a decoding row: the prompt and half its new tokens."""
    client = ctx["result"]["client"]
    return vlm_prompt_tokens(ctx) + client["tokens_total"] / max(client["attempted"], 1) / 2
