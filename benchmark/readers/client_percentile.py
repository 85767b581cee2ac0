"""A percentile of one of the client's lists (all requests of the run)."""


def read(ctx, spec):
    return ctx["percentile"](ctx["result"]["client"].get(spec["list"], []), float(spec["q"]))
