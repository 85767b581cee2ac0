"""Client median latency of a task minus the server's own mean for it over
the window (sum and count deltas of its per-task series): what the wire,
the handler hop and the queue before the handler add."""


def read(ctx, spec):
    before = ctx["result"]["before"].get("tasks", {}).get(spec["task"], {})
    after = ctx["result"]["after"].get("tasks", {}).get(spec["task"], {})
    n = after.get("count", 0) - before.get("count", 0)
    client = ctx["median"](ctx["result"]["client"].get(spec["client"], []))
    if n <= 0 or client is None:
        return None
    return client - (after["sum_ms"] - before.get("sum_ms", 0.0)) / n
