"""Summed device time of the compiled programs whose name matches
``module_pattern`` (the device's ``XLA Modules`` line) as a share of device
busy time."""

import re


def read(ctx, spec):
    planes = [p for p in ctx["trace"]["planes"] if p["name"].startswith("/device:")]
    busy = ctx["reduced"]["busy_s"]
    if not planes or not busy:
        return None
    rx = re.compile(spec["module_pattern"])
    runs = [ev[2] for line in planes[0]["lines"] if line["name"] == spec.get("line", "XLA Modules")
            for ev in line["events"] if rx.search(ev[0])]
    if not runs:
        return None
    return 100.0 * sum(runs) / 1e9 / busy
