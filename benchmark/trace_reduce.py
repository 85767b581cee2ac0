"""From a profiler trace to numbers: device busy time as the union of the
intervals in which an operation ran, idle share, time by operation name,
and the longest idle gaps named by what the host was doing in them.

The reduction works on a neutral form, so it can be checked on a small
recorded trace: ``{"planes": [{"name": str, "lines": [{"name": str,
"events": [[name, start_ns, duration_ns], ...]}]}]}``. ``from_xplane`` makes
that form from the ``.xplane.pb`` the JAX profiler writes.
"""

from __future__ import annotations

import glob
import os
import re

#: lines of a device plane whose events are single operations on the device
OP_LINES = ("XLA Ops",)
#: host events that say nothing about what the host was doing
_HOST_NOISE = re.compile(r"^(\$|Thread|ThreadpoolListener|EventMgr)", re.I)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def from_xplane(path: str, keep_host_min_ns: int = 20_000) -> dict:
    """The neutral form of one trace file. Host events shorter than
    ``keep_host_min_ns`` are dropped: they cannot name a gap worth naming."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            events = [
                [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events
                if device or ev.duration_ns >= keep_host_min_ns
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: dict) -> list[dict]:
    return [p for p in trace["planes"] if re.match(r"^/device:(TPU|GPU):\d+$", p["name"])]


def op_events(plane: dict) -> list[list]:
    events = [ev for line in plane["lines"] if line["name"] in OP_LINES for ev in line["events"]]
    return sorted(events, key=lambda ev: ev[1])


def union_intervals(events: list[list]) -> list[tuple[int, int]]:
    """Merged [start, end) intervals of events sorted by start."""
    merged: list[tuple[int, int]] = []
    for _, start, dur in events:
        end = start + dur
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def busy_seconds(trace: dict) -> tuple[float, float]:
    """(busy, window) seconds: busy averaged over the device planes, the
    window from the first operation's start to the last one's end on any."""
    planes = device_planes(trace)
    if not planes:
        return 0.0, 0.0
    busy, lo, hi = [], None, None
    for plane in planes:
        merged = union_intervals(op_events(plane))
        busy.append(sum(e - s for s, e in merged))
        if merged:
            lo = merged[0][0] if lo is None else min(lo, merged[0][0])
            hi = merged[-1][1] if hi is None else max(hi, merged[-1][1])
    window = (hi - lo) if lo is not None else 0
    return sum(busy) / len(busy) / 1e9, window / 1e9


_CONTAINERS = ("while", "conditional", "call")


def stem(name: str) -> str:
    """``%paged_attention_kernel.260 = bf16[...] custom-call(...)`` ->
    ``paged_attention_kernel``: the operation's name without the HLO text
    and the instance number."""
    return re.sub(r"\.\d+$", "", name.split(" = ", 1)[0].strip().lstrip("%"))


def time_by_name(trace: dict, top: int | None = None) -> list[tuple[str, float]]:
    """Summed device seconds of each operation (first device plane), by
    :func:`stem`. Loops and branches are left out: the operations inside
    them are on the same line and would be counted twice."""
    planes = device_planes(trace)
    if not planes:
        return []
    totals: dict[str, int] = {}
    for name, _, dur in op_events(planes[0]):
        name = stem(name)
        if name in _CONTAINERS:
            continue
        totals[name] = totals.get(name, 0) + dur
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])
    return [(n, d / 1e9) for n, d in (ranked[:top] if top else ranked)]


def seconds_matching(trace: dict, pattern: str) -> tuple[float, int]:
    """Summed device seconds, and count, of operations whose name matches."""
    planes = device_planes(trace)
    if not planes:
        return 0.0, 0
    rx = re.compile(pattern)
    hits = [dur for name, _, dur in op_events(planes[0]) if rx.search(stem(name))]
    return sum(hits) / 1e9, len(hits)


def host_events(trace: dict) -> list[tuple[str, int, int]]:
    out = []
    for plane in trace["planes"]:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if not _HOST_NOISE.match(name):
                    out.append((f"{line['name'].split('/')[0]}: {name}", start, dur))
    return out


def idle_gaps(trace: dict, top: int = 10) -> list[tuple[str, float]]:
    """The longest idle gaps of the first device, summed by the host event
    that covered most of each (``"no host event"`` where none did)."""
    planes = device_planes(trace)
    if not planes:
        return []
    merged = union_intervals(op_events(planes[0]))
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(merged, merged[1:]) if b[0] > a[1]),
                  reverse=True)[:200]
    hosts = host_events(trace)
    totals: dict[str, int] = {}
    for length, lo, hi in gaps:
        best, best_cover = "no host event", 0
        for name, start, dur in hosts:
            cover = min(hi, start + dur) - max(lo, start)
            if cover > best_cover:
                best, best_cover = name, cover
        totals[best] = totals.get(best, 0) + length
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return [(n[:120], d / 1e9) for n, d in ranked]


def reduce(trace: dict) -> dict:
    busy, window = busy_seconds(trace)
    return {
        "busy_s": busy, "window_s": window,
        "device_ops": [[n, s] for n, s in time_by_name(trace, top=10)],
        "idle_gaps": [[n, s] for n, s in idle_gaps(trace)],
    }
