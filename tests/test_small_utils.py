"""Coverage for the small shared utilities: ONNX export discovery
(precision-preference chain), logging setup idempotence, and the
persistent compile cache switch."""

import logging
import os

from lumen_tpu.onnx_bridge.discovery import find_onnx_exports
from lumen_tpu.runtime.compile_cache import enable_persistent_cache
from lumen_tpu.utils.logger import setup_logging


class TestExportDiscovery:
    def _mkfiles(self, root, names):
        for n in names:
            path = root / n
            path.parent.mkdir(parents=True, exist_ok=True)
            path.write_bytes(b"onnx")

    def test_prefers_requested_precision_then_fp32_then_fp16(self, tmp_path):
        self._mkfiles(tmp_path, ["vision.fp16.onnx", "vision.fp32.onnx"])
        out = find_onnx_exports(str(tmp_path), {"vision": "vision"}, precision="fp16")
        assert out["vision"].endswith("vision.fp16.onnx")
        out = find_onnx_exports(str(tmp_path), {"vision": "vision"})
        assert out["vision"].endswith("vision.fp32.onnx")

    def test_bare_name_is_last_resort(self, tmp_path):
        self._mkfiles(tmp_path, ["text.onnx"])
        out = find_onnx_exports(str(tmp_path), {"text": "text"})
        assert out["text"].endswith("text.onnx")

    def test_scans_onnx_runtime_subdir(self, tmp_path):
        self._mkfiles(tmp_path, [os.path.join("onnx", "det.fp32.onnx")])
        out = find_onnx_exports(str(tmp_path), {"det": "det"})
        assert out["det"].endswith(os.path.join("onnx", "det.fp32.onnx"))

    def test_missing_component_and_missing_dir(self, tmp_path):
        self._mkfiles(tmp_path, ["vision.fp32.onnx"])
        out = find_onnx_exports(str(tmp_path), {"vision": "vision", "text": "text"})
        assert "text" not in out
        assert find_onnx_exports(str(tmp_path / "nope"), {"x": "x"}) == {}


class TestLoggerSetup:
    def test_idempotent_single_handler(self):
        setup_logging("INFO")
        setup_logging("DEBUG")  # re-run must replace, not stack
        ours = [
            h for h in logging.getLogger().handlers
            if getattr(h, "_lumen_tpu", False)
        ]
        assert len(ours) == 1
        assert logging.getLogger().level == logging.DEBUG

    def test_non_tty_output_has_no_ansi(self, capsys):
        setup_logging("INFO")
        logging.getLogger("t").info("plain message")
        err = capsys.readouterr().err
        assert "plain message" in err
        assert "\x1b[" not in err  # capsys pipe is not a tty


class TestCompileCache:
    def test_disabled_by_env(self, monkeypatch):
        monkeypatch.setenv("LUMEN_COMPILE_CACHE", "0")
        assert enable_persistent_cache() is None

    def test_takes_no_directory_argument(self):
        """The cache is placed by JAX_COMPILATION_CACHE_DIR or the fixed
        in-checkout default — no caller picks its own."""
        import inspect

        assert not inspect.signature(enable_persistent_cache).parameters


class TestRandomVariablesGuards:
    """tests/clip_fixtures.random_variables: normalizer stats are matched by
    explicit leaf name, and unknown stat leaves fail loudly instead of
    receiving random (possibly <= 0) fills that would NaN the normalizer."""

    def _tree(self, leaves):
        import jax.numpy as jnp

        return lambda: {
            "params": {"proj": {"kernel": jnp.zeros((4, 4))}},
            "batch_stats": {"norm": {k: jnp.ones((4,)) for k in leaves}},
        }

    def test_var_scale_filled_with_ones(self):
        from tests.clip_fixtures import random_variables

        tree = random_variables(self._tree(["var", "mean"]))
        import numpy as np

        assert np.all(np.asarray(tree["batch_stats"]["norm"]["var"]) == 1.0)
        assert np.any(np.asarray(tree["params"]["proj"]["kernel"]) != 0.0)

    def test_unknown_stat_leaf_raises(self):
        import pytest

        from tests.clip_fixtures import random_variables

        with pytest.raises(ValueError, match="unknown normalizer stat leaf"):
            random_variables(self._tree(["var", "running_median"]))
