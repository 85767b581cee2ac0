"""The prefill scan kernel's share of its roofline. A call is one Mamba layer
of one prefill program of one request: its live tokens are the merged prompt
over the programs a prompt ran through (``prefill_chunks_run`` over
``admitted`` in the window; a prompt admitted whole is one program)."""

from benchmark import cells, trace_reduce
from benchmark.readers.common import delta, model_config, vlm_prompt_tokens


def read(ctx, spec):
    seconds, calls = trace_reduce.seconds_matching(ctx["trace"], spec["op_pattern"])
    admitted = delta(ctx, "vlm-continuous:", "admitted")
    if not calls or not admitted or not ctx["peaks"]:
        return None
    chunks = delta(ctx, "vlm-continuous:", "prefill_chunks_run") or 0
    tokens = vlm_prompt_tokens(ctx) / max(chunks / admitted, 1.0)
    roof = cells.load_module("rooflines", spec["roofline"], ctx["cell"].here)
    w = roof.cell_work(model_config(ctx, "vlm")["text_config"], tokens, calls)
    least, _ = roof.least_seconds(w, ctx["peaks"]["bf16_flops"], ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
