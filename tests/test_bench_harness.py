"""Unit tests for bench.py's parent-harness logic: result merging (good
results vs diagnostic markers vs CPU fallbacks), per-phase line parsing,
and the in-session artifact backfill. These guard the claim-retention
protocol the on-chip collection depends on — a phase crash or a flaky
backend start must never erase real TPU numbers."""

import json
import sys
import threading
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import bench  # noqa: E402


class TestMergeRules:
    def test_marker_never_clobbers_good_result(self):
        r = {"clip": {"images_per_sec": 500, "platform": "tpu"}}
        bench._merge_results(r, {"clip": {"error": "late crash"}})
        assert r["clip"]["images_per_sec"] == 500
        assert r["clip"]["tail_error"] == "late crash"

    def test_cpu_fallback_never_clobbers_on_chip(self):
        r = {"clip": {"images_per_sec": 500, "platform": "tpu"}}
        bench._merge_results(r, {"clip": {"images_per_sec": 9, "platform": "cpu"}})
        assert r["clip"]["platform"] == "tpu"

    def test_good_result_replaces_marker_and_cpu(self):
        r = {"vlm": {"error": "x"}, "clip": {"images_per_sec": 9, "platform": "cpu"}}
        bench._merge_results(
            r,
            {
                "vlm": {"tokens_per_sec": 5, "platform": "tpu"},
                "clip": {"images_per_sec": 500, "platform": "tpu"},
            },
        )
        assert bench._is_ok(r["vlm"])
        assert r["clip"]["platform"] == "tpu"

    def test_is_ok(self):
        assert not bench._is_ok(None)
        assert not bench._is_ok({"error": "x"})
        assert not bench._is_ok({"skipped": "budget"})
        assert bench._is_ok({"images_per_sec": 1})


class TestChildLineParsing:
    def _child(self, lines: list[str]) -> "bench._ChildAttempt":
        child = object.__new__(bench._ChildAttempt)
        child._out_lines = [line + "\n" for line in lines]
        child._lock = threading.Lock()
        return child

    def test_partial_then_error_keeps_partial_and_tail(self):
        child = self._child(
            [
                json.dumps({"phase": "bench_grpc", "partial": True, "rps": 10}),
                json.dumps({"phase": "bench_grpc", "error": "vlm half died"}),
            ]
        )
        res = child.results()["bench_grpc"]
        assert res["rps"] == 10
        assert res["tail_error"] == "vlm half died"

    def test_retry_success_overwrites_error(self):
        child = self._child(
            [
                json.dumps({"phase": "face", "error": "transient"}),
                json.dumps({"phase": "face", "images_per_sec": 42}),
            ]
        )
        assert child.results()["face"] == {"images_per_sec": 42}

    def test_garbage_lines_ignored(self):
        child = self._child(["not json", "[1,2]", "42", json.dumps({"phase": "p", "x": 1})])
        assert child.results() == {"p": {"x": 1}}


class TestGroupRunnerProtocol:
    """End-to-end subprocess runs of ``bench.py --phase-group`` with stub
    phases (BENCH_TEST_PHASES=1): a phase crash must flush an error marker
    and continue under the same process (the claim), with one retry at the
    end of the group."""

    def _run_group(self, names: str) -> tuple[int, dict[str, list[dict]]]:
        import subprocess

        env = dict(__import__("os").environ)
        env["BENCH_TEST_PHASES"] = "1"
        env.pop("BENCH_GROUP_DEADLINE", None)
        proc = subprocess.run(
            [sys.executable, str(Path(bench.__file__)), "--phase-group", names],
            capture_output=True, text=True, timeout=60, env=env,
            cwd=str(Path(bench.__file__).parent),
        )
        by_phase: dict[str, list[dict]] = {}
        for line in proc.stdout.splitlines():
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            by_phase.setdefault(rec.pop("phase", "?"), []).append(rec)
        return proc.returncode, by_phase

    def test_crash_continues_and_retries(self):
        rc, lines = self._run_group("probe,stub_flaky,stub_ok,stub_broken")
        assert rc == 0
        assert lines["probe"] == [{"platform": "stub", "device_kind": "stub"}]
        # stub_ok ran even though stub_flaky crashed before it
        assert lines["stub_ok"] == [{"platform": "stub", "x": 1}]
        # flaky: error marker first, then the end-of-group retry succeeds
        assert "error" in lines["stub_flaky"][0]
        assert lines["stub_flaky"][1] == {"platform": "stub", "recovered": True}
        # broken: initial error + retry error, nothing else
        assert all("error" in rec for rec in lines["stub_broken"])
        assert len(lines["stub_broken"]) == 2

    def test_all_green_group(self):
        rc, lines = self._run_group("probe,stub_ok")
        assert rc == 0
        assert "error" not in lines["stub_ok"][0]


class TestPeaksTable:
    """A utilization is computed against the peak of the device JAX
    reports; a device the table does not know is an error, never "v5e"."""

    @pytest.mark.parametrize(
        "kind,flops,gbps",
        [("TPU v5 lite", 197e12, 819), ("TPU v5e", 197e12, 819), ("TPU v6 lite", 918e12, 1640), ("TPU v4", 275e12, 1228)],
    )
    def test_known_device_kinds(self, kind, flops, gbps):
        assert bench._peak(bench.PEAK_FLOPS, kind) == flops
        assert bench._peak(bench.PEAK_HBM_GBPS, kind) == gbps

    @pytest.mark.parametrize("kind", ["", "cpu", "TPU7x", "TPU v5p", "NVIDIA H100"])
    def test_unknown_device_kind_raises(self, kind):
        with pytest.raises(ValueError, match="no published peak"):
            bench._peak(bench.PEAK_FLOPS, kind)


class TestTpuTestsOutcome:
    def test_outcome_mapping(self):
        # real runs
        assert bench._tests_outcome(0, 5, 0) == "passed"
        assert bench._tests_outcome(1, 3, 2) == "failed"
        # fixture/teardown errors: rc 1 with call-failures possibly 0 but
        # tally counts setup errors as failed, so they still read failed
        assert bench._tests_outcome(1, 0, 1) == "failed"
        # selection problems are not failures
        assert bench._tests_outcome(5, 0, 0) == "no-tests"
        assert bench._tests_outcome(0, 0, 0) == "no-tests"  # all-skipped


class TestSessionArtifactBackfill:
    @pytest.fixture()
    def repo(self, tmp_path, monkeypatch):
        monkeypatch.setattr(bench, "REPO", str(tmp_path))
        return tmp_path

    def test_loads_on_chip_results_only(self, repo):
        (repo / "TPU_SESSION_r03.jsonl").write_text(
            json.dumps({"event": "segment",
                        "results": {"clip": {"images_per_sec": 900, "platform": "tpu"},
                                    "ocr": {"det_images_per_sec": 5, "platform": "cpu"}}})
            + "\n"
        )
        out = bench._load_session_artifact()
        assert out["clip"]["images_per_sec"] == 900
        assert out["clip"]["source"] == "TPU_SESSION_r03.jsonl"
        assert "ocr" not in out  # cpu records are not hardware evidence

    def test_json_summary_wins_over_jsonl(self, repo):
        (repo / "TPU_SESSION_r03.jsonl").write_text(
            json.dumps({"results": {"clip": {"images_per_sec": 1, "platform": "tpu"}}}) + "\n"
        )
        (repo / "TPU_SESSION_r03.json").write_text(
            json.dumps({"results": {"clip": {"images_per_sec": 2, "platform": "tpu"}}})
        )
        assert bench._load_session_artifact()["clip"]["images_per_sec"] == 2

    def test_per_phase_newest_round_wins(self, repo):
        """A phase measured in the newest round wins; a phase the newest
        round hasn't (re-)measured keeps the older round's on-chip number,
        stamped with its source file so the round it came from stays
        visible (the current round's collector log exists from session
        start but may hold only some phases under a saturated pool)."""
        (repo / "TPU_SESSION_r02.json").write_text(
            json.dumps({"results": {"clip": {"images_per_sec": 1, "platform": "tpu"},
                                    "vlm": {"tokens_per_sec": 9, "platform": "tpu"}}})
        )
        (repo / "TPU_SESSION_r03.json").write_text(
            json.dumps({"results": {"clip": {"images_per_sec": 2, "platform": "tpu"}}})
        )
        out = bench._load_session_artifact()
        assert out["clip"]["images_per_sec"] == 2
        assert out["clip"]["source"] == "TPU_SESSION_r03.json"
        assert out["vlm"]["tokens_per_sec"] == 9
        assert out["vlm"]["source"] == "TPU_SESSION_r02.json"

    def test_empty_or_missing_files(self, repo):
        assert bench._load_session_artifact() == {}
        (repo / "TPU_SESSION_r03.jsonl").write_text("garbage\n")
        assert bench._load_session_artifact() == {}


class TestPublishedLines:
    """The driver parses the process's LAST valid JSON line, so every exit
    path must leave real numbers (not a zeroed line) as that last line."""

    @pytest.fixture
    def repo(self, tmp_path, monkeypatch):
        monkeypatch.setattr(bench, "REPO", str(tmp_path))
        (tmp_path / "TPU_SESSION_r03.json").write_text(
            json.dumps({"results": {"clip": {
                "images_per_sec": 4000.0, "batch": 256, "platform": "tpu",
                "device_kind": "TPU v5 lite"}}})
        )
        (tmp_path / "BASELINE_CACHE.json").write_text(
            json.dumps({"clip": {"images_per_sec": 8.0}})
        )
        return tmp_path

    def test_startup_backfill_assembles_artifact_numbers(self, repo):
        results, sources = bench._session_backfill(["probe", "clip", "vlm"])
        line = bench._assemble(results, bench._load_baseline_cache(), [])
        assert line["value"] == 4000.0
        assert line["vs_baseline"] == 500.0
        assert line["platform"] == "tpu"
        assert sources == ["TPU_SESSION_r03.json"]

    def test_crash_handler_reprints_last_good_line(self, repo, monkeypatch, capsys):
        """A mid-run exception must re-print the startup-backfill line
        (plus the crash note), never a value-0.0 line that would supersede
        real numbers as the driver-visible LAST line."""
        import bench as b

        monkeypatch.setattr(
            b, "_run_tpu_attempts",
            lambda *a, **k: (_ for _ in ()).throw(RuntimeError("mid-run crash")),
        )
        monkeypatch.setenv("BENCH_BUDGET", "30")

        class Args:
            phase = None
            phase_group = None
            light = True

        with pytest.raises(RuntimeError):
            b.main(Args())
        printed = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]
        assert printed[0]["stage"] == "startup-backfill"
        assert printed[0]["value"] == 4000.0
        # the crash handler in __main__ re-prints _LAST_GOOD_LINE:
        assert b._LAST_GOOD_LINE["value"] == 4000.0
