"""Delta of one gauge field over the window."""

from benchmark.readers.common import delta


def read(ctx, spec):
    return delta(ctx, spec["gauge"], spec["field"])
