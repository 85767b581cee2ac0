"""Bytes one decode step must read (the decoder's weights and the head once,
the live keys and values of the rows in the step) over the traced time of a
step times the chip's memory bandwidth. The step's time is the summed device
time of the decode program's runs in the trace over the steps they ran."""

import re

from benchmark.readers.common import counts, decode_rows, mean_context, model_config


def read(ctx, spec):
    planes = [p for p in ctx["trace"]["planes"] if p["name"].startswith("/device:")]
    if not planes or not ctx["peaks"]:
        return None
    rx = re.compile(spec["module_pattern"])
    runs = [ev for line in planes[0]["lines"] if line["name"] == spec.get("line", "XLA Modules")
            for ev in line["events"] if rx.search(ev[0])]
    if not runs:
        return None
    block = int(ctx["result"]["settings"]["vlm"]["decode_block"])
    step_s = sum(ev[2] for ev in runs) / 1e9 / (len(runs) * block)
    weight_bytes = 1 if ctx["cell"].config["precision"]["vlm"] == "int8" else 2
    need = counts(ctx, "vlm").decode_step_bytes(
        model_config(ctx, "vlm"), decode_rows(ctx) or 1.0, mean_context(ctx), weight_bytes)
    return 100.0 * need / (step_s * ctx["peaks"]["hbm_bytes_per_s"])
