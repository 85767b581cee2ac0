"""A kernel's share of its roofline where the work follows from the cell's
own decoder configuration: ``benchmark/rooflines/<kernel>.py`` gives
``cell_work(text_config, rows, context, calls)`` for the calls found in the
trace (``kernel_roofline`` asks for grouped-query heads instead)."""

from benchmark import cells, trace_reduce
from benchmark.readers.common import decode_rows, mean_context, model_config


def read(ctx, spec):
    seconds, calls = trace_reduce.seconds_matching(ctx["trace"], spec["op_pattern"])
    rows = decode_rows(ctx)
    if not calls or not rows or not ctx["peaks"]:
        return None
    roof = cells.load_module("rooflines", spec["roofline"], ctx["cell"].here)
    w = roof.cell_work(model_config(ctx, "vlm")["text_config"], rows, mean_context(ctx), calls)
    least, _ = roof.least_seconds(w, ctx["peaks"]["bf16_flops"], ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
