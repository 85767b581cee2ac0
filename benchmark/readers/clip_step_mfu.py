"""Photos embedded in the window times the operations a photo needs, over
the window times the chip's bf16 peak."""

from benchmark.readers.common import counts, model_config


def read(ctx, spec):
    client = ctx["result"]["client"]
    if not ctx["peaks"] or not client.get("completed_in_window"):
        return None
    flops = client["completed_in_window"] * counts(ctx, "clip").image_flops(model_config(ctx, "clip"))
    return 100.0 * flops / (client["window_s"] * ctx["peaks"]["bf16_flops"])
