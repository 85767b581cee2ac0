"""A whole run at tiny sizes on the CPU: a cell added in a temporary copy of
the tree (one configuration file, one traffic file, one layer-metric file,
one ``workloads`` entry, nothing edited) is found and runs; the last line
has the contract's keys; the control and each planted fault come out as not
correct."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import cells

ROOT = cells.ROOT


def _copy_tree(tmp) -> str:
    root = str(tmp / "tree")
    os.makedirs(root)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(ROOT, "lumen_tpu"), os.path.join(root, "lumen_tpu"))
    os.symlink(os.path.join(ROOT, "native"), os.path.join(root, "native"))
    return root


@pytest.fixture(scope="module")
def added_cell_line(tmp_path_factory):
    """Add files and entries only, then run the new cell as the driver would
    (but for ``--rehearse``: there is no chip here)."""
    root = _copy_tree(tmp_path_factory.mktemp("copy"))
    here = os.path.join(root, "benchmark")
    before = {os.path.relpath(os.path.join(d, f), here): os.path.getmtime(os.path.join(d, f))
              for d, _, fs in os.walk(here) for f in fs}
    config = cells._read_json(os.path.join(here, "configs", "rehearsal-tiny.json"))
    config["name"] = "added-tiny"
    with open(os.path.join(here, "configs", "added-tiny.json"), "w") as f:
        json.dump(config, f)
    traffic = cells._read_json(os.path.join(here, "traffic", "import_bulk.json"))
    traffic.update(traffic.pop("rehearse"))
    traffic.update(name="import_small", bulk_streams=1, outstanding_per_stream=3)
    with open(os.path.join(here, "traffic", "import_small.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(here, "layer_metrics", "decode_tasks.json"), "w") as f:
        json.dump({"reader": "gauge_delta", "gauge": "decode_pool", "field": "tasks"}, f)
    bench = cells._read_json(os.path.join(root, "BENCHMARK.json"))
    bench["configs"].append({"name": "added-tiny", "source": "none", "file": "benchmark/configs/added-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "added-tiny.import_small", "config": "added-tiny",
                               "traffic": "import_small", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("photos_per_s", "embed_p95_ms"):
            m["workloads"].append("added-tiny.import_small")
    bench["per_layer"].append({"name": "decode_tasks", "unit": "count", "better": "higher",
                               "source": "program_counter", "layer": "host decode", "moves": "photos_per_s",
                               "workloads": ["added-tiny.import_small"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    lines = {}
    for trace in (0, 1):
        out = subprocess.run(
            [sys.executable, os.path.join(here, "run.py"), "--workload", "added-tiny.import_small", "--seed",
             str(2**31 + 11), "--seconds", "2", "--trace", str(trace), "--rehearse"],
            cwd=root, capture_output=True, text=True, timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        assert out.returncode == 0, out.stderr[-3000:]
        lines[trace] = (json.loads(out.stdout.strip().splitlines()[-1]), out.stderr)
    after = {k: os.path.getmtime(os.path.join(here, k)) for k in before}
    assert after == before, "an existing file of the benchmark was edited"
    return lines


def test_an_added_cell_is_found_and_runs(added_cell_line):
    line, _ = added_cell_line[0]
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"photos_per_s", "embed_p95_ms", "setup_s"}
    traced, _ = added_cell_line[1]
    assert traced["metrics"]["decode_tasks"]["value"] > 0  # the added metric, read by an existing reader


def test_the_last_line_has_the_contracts_keys(added_cell_line):
    for trace in (0, 1):
        line, err = added_cell_line[trace]
        keys = list(line)
        assert set(cells.RESULT_KEYS) <= set(keys) and keys[-1] == "compared"
        assert set(keys) - set(cells.RESULT_KEYS) <= {"breakdown", "compared", "rehearsal"}
        assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
        for m in line["metrics"].values():
            assert set(m) == {"value", "unit"}
        assert all(set(c) == {"value", "limit"} for c in line["compared"].values())
        assert "compared " in err.splitlines()[-1]  # each number beside its limit ends standard error
    assert {"busy_s", "window_s"} <= set(added_cell_line[1][0]["device"])


def test_no_chip_no_result(tmp_path):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         "hub-vitl14-qwen2-1p5b.import_bulk", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_alone_without_the_program_it_fails(tmp_path):
    root = str(tmp_path / "bare")
    os.makedirs(root)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), "--workload",
         "rehearsal-tiny.import_bulk", "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=root, capture_output=True, text=True, timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode != 0 and out.stdout.strip() == ""
