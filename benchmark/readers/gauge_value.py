"""A gauge field as it stands when the window has closed (for gauges that
are rolling statistics already, such as the decode pool's median wait)."""

from benchmark.readers.common import gauge


def read(ctx, spec):
    return gauge(ctx["result"]["after"], spec["gauge"]).get(spec["field"])
