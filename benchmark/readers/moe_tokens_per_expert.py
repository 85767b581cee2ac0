"""Tokens a held expert got in an expert layer call, on average over the
window: ``moe_tokens_held`` over held experts x ``moe_layer_calls`` (deltas
of the scheduler's gauge). Prefill chunks and decode steps both count."""

from benchmark.readers.common import delta, model_config


def read(ctx, spec):
    held, calls = delta(ctx, spec["gauge"], "moe_tokens_held"), delta(ctx, spec["gauge"], "moe_layer_calls")
    if held is None or not calls:
        return None
    return held / (calls * model_config(ctx, "vlm")["text_config"]["n_routed_experts"])
