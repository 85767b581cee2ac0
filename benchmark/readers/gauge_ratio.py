"""Delta of one gauge field over the delta of another, over the window."""

from benchmark.readers.common import delta


def read(ctx, spec):
    num, den = delta(ctx, spec["gauge"], spec["numerator"]), delta(ctx, spec["gauge"], spec["denominator"])
    if not num or not den:
        return None
    return num / den
