"""A kernel's summed device time as a share of device busy time."""

from benchmark import trace_reduce


def read(ctx, spec):
    seconds, calls = trace_reduce.seconds_matching(ctx["trace"], spec["op_pattern"])
    busy = ctx["reduced"]["busy_s"]
    if not calls or not busy:
        return None
    return 100.0 * seconds / busy
