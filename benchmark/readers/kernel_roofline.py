"""A kernel's share of its roofline: the least time the chip could take for
the work its calls in the trace had to do (``benchmark/rooflines/<kernel>.py``,
from shapes) over the summed device time of its events."""

from benchmark import cells, trace_reduce
from benchmark.readers.common import decode_rows, mean_context, model_config


def read(ctx, spec):
    seconds, calls = trace_reduce.seconds_matching(ctx["trace"], spec["op_pattern"])
    rows = decode_rows(ctx)
    if not calls or not rows or not ctx["peaks"]:
        return None
    roof = cells.load_module("rooflines", spec["roofline"], ctx["cell"].here)
    t = model_config(ctx, "vlm")["text_config"]
    head_dim = t.get("head_dim") or t["hidden_size"] // t["num_attention_heads"]
    w = roof.work(rows, mean_context(ctx), t["num_attention_heads"],
                  t["num_key_value_heads"], head_dim, layers=calls)
    least, _ = roof.least_seconds(w, ctx["peaks"]["bf16_flops"], ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
