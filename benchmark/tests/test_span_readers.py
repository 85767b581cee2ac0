"""The two readers of the program's ``lumen:`` phases, on a small hand-made
trace in neutral form (``data/phases.hand.json`` says what it holds), and
the layer-metric files that name them."""

import json
import os

import pytest

from benchmark import cells

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture()
def ctx():
    with open(os.path.join(HERE, "data", "phases.hand.json")) as f:
        return {"trace": json.load(f)}


def reader(name):
    return cells.load_module("readers", name)


def without_phases(trace):
    return {"planes": [
        {**p, "lines": [{**ln, "events": [ev for ev in ln["events"] if not ev[0].startswith("lumen:")]}
                        for ln in p["lines"]]} for p in trace["planes"]]}


def test_idle_under_spans_counts_each_idle_ns_once(ctx):
    # gap 1 wholly inside (200), gap 2 under the end of one span and the
    # start of the next (90 + 100 of 200), gap 3 under XLA's event only (0),
    # gap 4 under two overlapping spans on two threads (200, not 250); of
    # gap 5 nothing counts, idle or named: the last lumen: span ended at 1290
    got = reader("idle_under_spans").read(ctx, {"span_pattern": "^lumen:"})
    assert got == pytest.approx(100.0 * (200 + 190 + 0 + 200) / 800)
    only_dispatch = reader("idle_under_spans").read(ctx, {"span_pattern": r"^lumen:vlm\.block\.dispatch"})
    # the two dispatch spans alone were recorded over [120,1150): 180 + 200 + 200 + 150 of idle time
    assert only_dispatch == pytest.approx(100.0 * (180 + 150) / 730)


def test_idle_the_span_recorder_never_saw_is_left_out(ctx):
    late = {"planes": [ctx["trace"]["planes"][0], {"name": "/host:CPU", "lines": [
        {"name": "t/1", "events": [["lumen:batch.window", 150, 100], ["XlaLinearize", 380, 900],
                                   ["lumen:batch.settle", 420, 20]]}]}]}
    # lumen: spans were recorded over [150,440): gap 1 counts from 150 (150 idle, 100 named), gap 2 up to
    # 440 (40 idle, 20 named); XLA's own event runs on to 1280 and says nothing about that recorder
    got = reader("idle_under_spans").read({"trace": late}, {"span_pattern": "^lumen:"})
    assert got == pytest.approx(100.0 * (100 + 20) / (150 + 40))


def test_host_span_ms_sums_spans_over_units(ctx):
    spec = {"span_pattern": r"lumen:vlm\.", "except_pattern": r"vlm\.(wait_work|block\.fetch)",
            "per_pattern": r"lumen:vlm\.block\.dispatch"}
    # prepare 30 + dispatch 190 + emit 140 + dispatch 150, over two dispatches
    assert reader("host_span_ms").read(ctx, spec) == pytest.approx((30 + 190 + 140 + 150) / 1e6 / 2)
    stage = {"span_pattern": r"lumen:batch\.(stack|put)", "per_pattern": r"lumen:batch\.dispatch"}
    assert reader("host_span_ms").read(ctx, stage) == pytest.approx((60 + 40) / 1e6)


@pytest.mark.parametrize("name,spec", [
    ("idle_under_spans", {"span_pattern": "^lumen:"}),
    ("host_span_ms", {"span_pattern": r"lumen:vlm\.", "per_pattern": r"lumen:vlm\.block\.dispatch"}),
])
def test_a_program_without_phases_reads_nothing(ctx, name, spec):
    # the parent commit writes no lumen: events: no value, and no error
    assert reader(name).read({"trace": without_phases(ctx["trace"])}, spec) is None
    assert reader(name).read({"trace": {"planes": []}}, spec) is None


NEW = ("bulk_queue_ms", "decode_wait_mean_ms", "batch_collect_wait_ms", "batch_stage_ms", "idle_named_pct.embed",
       "admit_wait_ms", "prefill_lane_ms", "server_ttft_ms", "sched_host_ms_per_block", "idle_named_pct.caption")


@pytest.mark.parametrize("name", NEW)
def test_each_new_metric_has_its_file_reader_and_one_cell(name):
    bench = cells._read_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    (cell_name,) = entry["workloads"]
    cell = cells.Cell(cell_name)
    spec, module = cell.layer_metric(name)
    assert callable(module.read) and name in [m["name"] for m in cell.per_layer()]
    moved = {m["name"] for m in cell.end_to_end()}
    assert entry["moves"] in moved
    if spec["reader"] in ("host_span_ms", "idle_under_spans"):
        assert entry["source"] == "device_trace" and "lumen:" in spec["span_pattern"]
    else:
        assert entry["source"] == "program_counter" and spec["reader"] == "gauge_ratio"
