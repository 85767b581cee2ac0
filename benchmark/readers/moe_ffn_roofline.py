"""The expert layers' grouped multiplications against their roofline: the
work of a layer call is what the program's own counters say a call held on
average over the window (``moe_tokens_held``, ``moe_experts_touched`` over
``moe_layer_calls``), and the calls in the trace are its events over three
(gate, up, down)."""

from benchmark import cells, trace_reduce
from benchmark.readers.common import delta, model_config


def read(ctx, spec):
    seconds, events = trace_reduce.seconds_matching(ctx["trace"], spec["op_pattern"])
    calls = delta(ctx, spec["gauge"], "moe_layer_calls")
    held, touched = delta(ctx, spec["gauge"], "moe_tokens_held"), delta(ctx, spec["gauge"], "moe_experts_touched")
    if not events or not calls or held is None or touched is None or not ctx["peaks"]:
        return None
    roof = cells.load_module("rooflines", spec["roofline"], ctx["cell"].here)
    t = model_config(ctx, "vlm")["text_config"]
    w = roof.work(held / calls, touched / calls, t["hidden_size"], t["moe_intermediate_size"],
                  events / roof.MATMULS_PER_LAYER_CALL)
    least, _ = roof.least_seconds(w, ctx["peaks"]["bf16_flops"], ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
