"""Share of the first device's idle time (the gaps between the merged
intervals of its operations, as ``trace_reduce.union_intervals`` gives them)
that lies inside host events whose name matches ``span_pattern``, in %: how
much of the idle time the program's own spans can name. Events on several
threads are merged first, so time under two spans counts once. Only the idle
time between the first matching event's start and the last one's end counts:
the profiler stops its tracers one after another, so the device's trace
outlasts the recorder of these spans by up to 1.7 s, and nothing can name a
gap that recorder never saw."""

import re

from benchmark import trace_reduce


def read(ctx, spec):
    trace = ctx["trace"]
    planes = trace_reduce.device_planes(trace)
    rx = re.compile(spec["span_pattern"])
    named = sorted(
        (ev for plane in trace["planes"] if not plane["name"].startswith("/device:")
         for line in plane["lines"] for ev in line["events"] if rx.search(ev[0])),
        key=lambda ev: ev[1],
    )
    if not planes or not named:
        return None
    seen_lo, seen_hi = named[0][1], max(ev[1] + ev[2] for ev in named)
    busy = trace_reduce.union_intervals(trace_reduce.op_events(planes[0]))
    gaps = [(max(a[1], seen_lo), min(b[0], seen_hi)) for a, b in zip(busy, busy[1:])]
    gaps = [(lo, hi) for lo, hi in gaps if hi > lo]
    idle = sum(hi - lo for lo, hi in gaps)
    if not idle:
        return None
    spans = trace_reduce.union_intervals(named)
    under, i = 0, 0
    for lo, hi in gaps:  # both lists are sorted and disjoint within themselves
        while i < len(spans) and spans[i][1] <= lo:
            i += 1
        j = i
        while j < len(spans) and spans[j][0] < hi:
            under += min(hi, spans[j][1]) - max(lo, spans[j][0])
            j += 1
    return 100.0 * under / idle
