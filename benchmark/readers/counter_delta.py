"""Delta of one of the program's counters over the window."""


def read(ctx, spec):
    before = ctx["result"]["before"].get("counters", {}).get(spec["counter"], 0)
    after = ctx["result"]["after"].get("counters", {})
    if spec["counter"] not in after and not spec.get("zero_if_absent"):
        return None
    return after.get(spec["counter"], 0) - before
