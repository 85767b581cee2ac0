"""A whole run at tiny sizes on the CPU: a cell added in a temporary copy of
the tree (one configuration file, one traffic file, one layer-metric file,
one ``workloads`` entry, nothing edited) is found and runs; so is a
captioner whose decoder the harness's own listing cannot draw (its tensor
listing, counts, reference, configuration and traffic files and its
entries, nothing edited); the last line has the contract's keys."""

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


def _mtimes(here: str) -> dict:
    return {os.path.relpath(os.path.join(d, f), here): os.path.getmtime(os.path.join(d, f))
            for d, _, fs in os.walk(here) for f in fs}


def _assert_untouched(here: str, before: dict) -> None:
    assert {k: v for k, v in _mtimes(here).items() if k in before} == before, "an existing file was edited"


def _run_untraced_and_traced(root: str, workload: str, seed: int, seconds: float) -> dict:
    """The new cell as the driver would run it (but for ``--rehearse``: there
    is no chip here): ``{trace: (last line, standard error)}``."""
    lines = {}
    for trace in (0, 1):
        out = subprocess.run(
            [sys.executable, os.path.join(root, "benchmark", "run.py"), "--workload", workload, "--seed", str(seed),
             "--seconds", str(seconds), "--trace", str(trace), "--rehearse"],
            cwd=root, capture_output=True, text=True, timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        assert out.returncode == 0, out.stderr[-3000:]
        lines[trace] = (json.loads(out.stdout.strip().splitlines()[-1]), out.stderr)
    return lines


@pytest.fixture(scope="module")
def added_cell_line(tmp_path_factory):
    """Add files and entries only, then run the new cell."""
    root = _copy_tree(tmp_path_factory.mktemp("copy"))
    here = os.path.join(root, "benchmark")
    before = _mtimes(here)
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
    lines = _run_untraced_and_traced(root, "added-tiny.import_small", 2**31 + 11, 2)
    _assert_untouched(here, before)
    return lines


def add_decoder(root: str) -> str:
    """All that a configuration of a new decoder architecture touches: the
    fixture's five files into their directories, one ``configs`` and one
    ``workloads`` entry, and its cell's name on the ``workloads`` list of
    every metric of the captioning cell (``caption_tokens_per_s``,
    ``ttft_p50_ms``, ``vlm_step_mfu``, ``vlm_step_hbm_pct`` among them)."""
    here = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(here, "tests", "data", "added_decoder"), here, dirs_exist_ok=True)
    bench = cells._read_json(os.path.join(root, "BENCHMARK.json"))
    bench["configs"].append({"name": "added-moe-tiny", "source": "none",
                             "file": "benchmark/configs/added-moe-tiny.json", "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "added-moe-tiny.caption_moe", "config": "added-moe-tiny",
                               "traffic": "caption_moe", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "hub-vitl14-qwen2-1p5b.caption_storm" in m.get("workloads", ()):
            m["workloads"].append("added-moe-tiny.caption_moe")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return "added-moe-tiny.caption_moe"


@pytest.fixture(scope="module")
def added_decoder(tmp_path_factory):
    root = _copy_tree(tmp_path_factory.mktemp("decoder"))
    here = os.path.join(root, "benchmark")
    before = _mtimes(here)
    lines = _run_untraced_and_traced(root, add_decoder(root), 2**31 + 29, 3)
    _assert_untouched(here, before)
    return root, lines, sorted(k for k in _mtimes(here) if k not in before and ".cache" not in k and "__pycache__" not in k)


def test_an_added_cell_is_found_and_runs(added_cell_line):
    line, _ = added_cell_line[0]
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"photos_per_s", "embed_p95_ms", "setup_s"}
    traced, _ = added_cell_line[1]
    assert traced["metrics"]["decode_tasks"]["value"] > 0  # the added metric, read by an existing reader


def test_an_added_decoder_is_files_and_entries_only(added_decoder):
    """A captioner the harness's own listing cannot draw (router, experts,
    gated shared expert) is served and judged with nothing edited."""
    root, lines, added = added_decoder
    assert added == ["configs/added-moe-tiny.json", "counts/qwen2moe.py", "references/vlm_qwen2moe.py",
                     "tensors/qwen2moe.py", "traffic/caption_moe.json"]
    for trace in (0, 1):
        line, _ = lines[trace]
        assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0, line
        assert line["compared"]["logit_gap_std"]["limit"] == 5e-05  # the added configuration's own limits
    assert set(lines[0][0]["metrics"]) == {"caption_tokens_per_s", "ttft_p50_ms", "setup_s"}
    assert lines[1][0]["metrics"]["rows_per_step"]["value"] > 0  # the mix's counters, read from this decoder's run
    assert lines[1][0]["metrics"]["ttft_p95_ms.storm"]["value"] > 0  # the tail, per layer in a saturated loop
    config = cells._read_json(os.path.join(root, "benchmark", "configs", "added-moe-tiny.json"))["models"]["vlm"]
    names = {n for n, _ in cells.load_module("tensors", config["tensors"], os.path.join(root, "benchmark"))
             .tensors(config["config"])}
    qwen2 = {n for n, _ in cells.load_module("tensors", "vlm").tensors(config["config"])}
    for name in ("model.layers.1.mlp.gate.weight", "model.layers.3.mlp.experts.3.down_proj.weight",
                 "model.layers.2.mlp.shared_expert.up_proj.weight", "model.layers.1.mlp.shared_expert_gate.weight"):
        assert name in names and name not in qwen2


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
