"""Tier-1 gate: the aggregate doc-gate runner (scripts/check_all.py) runs
all six surface checks and fails when ANY of them does — one command is
the whole pre-push story."""

import importlib.util
import os

_SPEC = importlib.util.spec_from_file_location(
    "check_all",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "scripts", "check_all.py"),
)
check_all = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(check_all)


def test_every_gate_passes():
    worst, results = check_all.run_all()
    failing = [(name, out) for name, rc, out in results if rc != 0]
    assert worst == 0 and not failing, (
        "doc gates failing:\n"
        + "\n".join(f"--- {name} ---\n{out}" for name, out in failing)
    )


def test_covers_all_known_gates():
    # The aggregate must not silently drop a gate: the registry names all
    # six known scanners, and each produced SOME output when run.
    assert set(check_all.GATES) == {
        "check_knobs", "check_metrics", "check_meta_keys", "check_endpoints",
        "check_events", "check_tasks",
    }
    _, results = check_all.run_all()
    assert len(results) == 6
    for name, _rc, out in results:
        assert out.strip(), f"gate {name} produced no output"


def test_failure_detection(monkeypatch):
    # A gate whose main() fails (or crashes) must fail the aggregate —
    # simulated by pointing the loader at a stub, not by undocumenting a
    # real knob.
    class FailingGate:
        @staticmethod
        def main() -> int:
            print("synthetic gap")
            return 1

    real_load = check_all.load_gate
    monkeypatch.setattr(
        check_all, "load_gate",
        lambda name: FailingGate if name == "check_knobs" else real_load(name),
    )
    worst, results = check_all.run_all()
    assert worst == 1
    by_name = {name: rc for name, rc, _ in results}
    assert by_name["check_knobs"] == 1
    assert by_name["check_endpoints"] == 0


# -- a document names only files the tree has ---------------------------------

import re  # noqa: E402

import pytest  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DOCS = ["README.md", os.path.join(".claude", "skills", "verify", "SKILL.md")] + sorted(
    os.path.join("docs", f) for f in os.listdir(os.path.join(_ROOT, "docs")) if f.endswith(".md")
)
# `name.py`, `name.md`, and `Name.json` (a record at the root), with or without a path or `:line`
_CITED = re.compile(r"`([A-Za-z0-9_./-]+\.(?:py|md)|(?:[A-Za-z0-9_./-]+/)?[A-Z][A-Za-z0-9_]*\.json)(?::[0-9,:-]+)?`")
# the reference project's own files, which docs/MIGRATION.md maps from
_UPSTREAM = {"fastvlm_service.py", "onnxrt_backend.py", "env_checker.py", "compute_bioclip_npy_embeddings.py"}


def _tree_basenames() -> set[str]:
    names = set()
    for top, dirs, files in os.walk(_ROOT):
        dirs[:] = [d for d in dirs if not d.startswith(".") and d not in ("chiprun_out", "__pycache__")]
        names.update(files)
    return names


@pytest.mark.parametrize("doc", _DOCS)
def test_a_document_names_only_files_the_tree_has(doc):
    """PR 33 removed a harness and 22 records that 41 places in the documents
    still cited: a backticked file name in a document is a file of the tree."""
    have = _tree_basenames() | _UPSTREAM
    with open(os.path.join(_ROOT, doc), encoding="utf-8") as f:
        cited = {m.group(1) for m in _CITED.finditer(f.read())}
    missing = sorted(c for c in cited if os.path.basename(c) not in have)
    assert not missing, f"{doc} names files the tree does not have: {missing}"
