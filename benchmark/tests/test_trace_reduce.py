"""Trace reduction on a hand-made trace and on an excerpt recorded on the chip."""

import json
import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def hand_trace():
    # device ops (ns): a [0,100) b [50,150) overlap -> busy [0,150); gap [150,400); c [400,500)
    # kernel "paged_k" twice; a second gap [500,900) then d [900,1000)
    ops = [["a", 0, 100], ["paged_k.1", 50, 100], ["c", 400, 100], ["paged_k.2", 900, 100]]
    host = [["wait_for_request", 140, 200], ["stack_batch", 520, 390], ["tiny", 150, 5]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops},
                                            {"name": "XLA Modules", "events": [["jit_step", 0, 1000]]}]},
        {"name": "/host:CPU", "lines": [{"name": "sched/123", "events": host}]},
    ]}


def test_busy_is_the_union_of_intervals():
    busy, window = tr.busy_seconds(hand_trace())
    assert busy == pytest.approx(350e-9)
    assert window == pytest.approx(1000e-9)


def test_idle_share_and_kernel_time_by_name():
    trace = hand_trace()
    reduced = tr.reduce(trace)
    assert 1 - reduced["busy_s"] / reduced["window_s"] == pytest.approx(0.65)
    seconds, calls = tr.seconds_matching(trace, "paged")
    assert (seconds, calls) == (pytest.approx(200e-9), 2)
    assert dict(tr.time_by_name(trace))["a"] == pytest.approx(100e-9)


def test_gaps_are_named_by_the_host_event_that_covers_them():
    gaps = dict(tr.idle_gaps(hand_trace()))
    assert gaps["sched: wait_for_request"] == pytest.approx(250e-9)
    assert gaps["sched: stack_batch"] == pytest.approx(400e-9)


def test_modules_do_not_count_as_operations():
    trace = hand_trace()
    trace["planes"][0]["lines"][0]["events"] = []
    assert tr.busy_seconds(trace) == (0.0, 0.0)


@pytest.mark.parametrize("name", sorted(f for f in os.listdir(os.path.join(HERE, "data")) if f.endswith(".excerpt.json"))
                         if os.path.isdir(os.path.join(HERE, "data")) else [])
def test_recorded_excerpt_reduces(name):
    with open(os.path.join(HERE, "data", name)) as f:
        trace = json.load(f)
    reduced = tr.reduce(trace)
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    assert reduced["device_ops"] and all(s > 0 for _, s in reduced["device_ops"])
    total = sum(s for _, s in tr.time_by_name(trace))
    assert total >= reduced["busy_s"] * 0.999  # a union never exceeds the sum
