"""Delta of one gauge field over the delta of another, over the window,
where the first may stay where it was: a share that reads 0 when none of
what the second counts became what the first counts (``gauge_ratio`` reads
nothing then). Nothing where the second did not move or the gauge lacks
either field."""

from benchmark.readers.common import delta


def read(ctx, spec):
    num, den = delta(ctx, spec["gauge"], spec["numerator"]), delta(ctx, spec["gauge"], spec["denominator"])
    if num is None or not den:
        return None
    return num / den
