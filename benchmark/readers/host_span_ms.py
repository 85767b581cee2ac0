"""Summed duration of the host events whose name matches ``span_pattern``
(less those that also match ``except_pattern``), over the number of events
matching ``per_pattern``, in ms: the host's time in those spans for each
unit of work. Reads the program's ``lumen:`` phases, which exist in a trace
only where the program writes them; without them there is nothing to read."""

import re


def read(ctx, spec):
    span, per = re.compile(spec["span_pattern"]), re.compile(spec["per_pattern"])
    skip = re.compile(spec["except_pattern"]) if spec.get("except_pattern") else None
    total = units = 0
    for plane in ctx["trace"]["planes"]:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            for name, _, dur in line["events"]:
                if span.search(name) and not (skip and skip.search(name)):
                    total += dur
                if per.search(name):
                    units += 1
    if not units:
        return None
    return total / 1e6 / units
