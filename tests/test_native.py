"""Native host-ops library: build/load, and numerical parity between the C
core and the numpy/cv2 reference implementations."""

import numpy as np
import pytest

from lumen_tpu import native
from lumen_tpu.ops.ctc import ctc_collapse, ctc_collapse_rows
from lumen_tpu.ops.image import letterbox_numpy, letterbox_params
from lumen_tpu.ops.nms import nms_numpy

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native host-ops unavailable (no toolchain)"
)


class TestResize:
    def test_matches_cv2_within_rounding(self):
        import cv2

        rng = np.random.default_rng(0)
        img = rng.integers(0, 255, (37, 53, 3), np.uint8)
        ours = native.resize_bilinear_u8(img, 64, 96)
        ref = cv2.resize(img, (96, 64), interpolation=cv2.INTER_LINEAR)
        assert ours.shape == ref.shape
        # cv2 uses fixed-point interpolation; allow 1 LSB of drift.
        diff = np.abs(ours.astype(int) - ref.astype(int))
        assert diff.max() <= 1, f"max diff {diff.max()}"

    def test_identity_resize(self):
        img = np.random.default_rng(1).integers(0, 255, (16, 16, 3), np.uint8)
        out = native.resize_bilinear_u8(img, 16, 16)
        np.testing.assert_array_equal(out, img)

    def test_upscale_shape_and_range(self):
        img = np.random.default_rng(2).integers(0, 255, (8, 8, 1), np.uint8)
        out = native.resize_bilinear_u8(img, 32, 24)
        assert out.shape == (32, 24, 1)


class TestLetterbox:
    def test_geometry_matches_letterbox_params(self):
        img = np.random.default_rng(3).integers(0, 255, (30, 50, 3), np.uint8)
        out, scale, pad_top, pad_left = native.letterbox_u8(img, 64, fill=7)
        exp_scale, new_h, new_w, exp_top, exp_left = letterbox_params(30, 50, 64)
        assert out.shape == (64, 64, 3)
        assert scale == pytest.approx(exp_scale)
        assert (pad_top, pad_left) == (exp_top, exp_left)
        # Padding rows carry the fill value.
        assert (out[:pad_top] == 7).all()
        assert (out[pad_top + new_h :] == 7).all()
        assert (out[:, :pad_left] == 7).all()

    def test_half_integer_scale_matches_python_round(self):
        # 3x4 -> target 6: scale 1.5, h*scale = 4.5 — banker's rounding
        # (Python round) gives new_h=4/pad_top=1; half-away-from-zero would
        # give 5/0 and shift the content by a row.
        img = np.random.default_rng(9).integers(0, 255, (3, 4, 3), np.uint8)
        _, scale, pad_top, pad_left = native.letterbox_u8(img, 6)
        exp_scale, _, _, exp_top, exp_left = letterbox_params(3, 4, 6)
        assert (scale, pad_top, pad_left) == (pytest.approx(exp_scale), exp_top, exp_left)

    def test_close_to_cv2_letterbox(self):
        img = np.random.default_rng(4).integers(0, 255, (45, 23, 3), np.uint8)
        ref, scale_ref, top_ref, left_ref = letterbox_numpy(img, 96)
        ours, scale, top, left = native.letterbox_u8(img, 96)
        assert (scale, top, left) == (pytest.approx(scale_ref), top_ref, left_ref)
        diff = np.abs(ours.astype(int) - ref.astype(int))
        assert diff.max() <= 1


class TestNms:
    def test_matches_numpy_reference(self):
        rng = np.random.default_rng(5)
        for trial in range(5):
            xy = rng.uniform(0, 100, (40, 2)).astype(np.float32)
            wh = rng.uniform(5, 40, (40, 2)).astype(np.float32)
            boxes = np.concatenate([xy, xy + wh], axis=1)
            scores = rng.uniform(0, 1, (40,)).astype(np.float32)
            ours = native.nms_f32(boxes, scores, 0.4)
            # reference path with native disabled
            x1, y1, x2, y2 = boxes[:, 0], boxes[:, 1], boxes[:, 2], boxes[:, 3]
            areas = np.maximum(x2 - x1, 0) * np.maximum(y2 - y1, 0)
            order = scores.argsort()[::-1]
            keep = []
            while order.size:
                i = order[0]
                keep.append(i)
                xx1 = np.maximum(x1[i], x1[order[1:]])
                yy1 = np.maximum(y1[i], y1[order[1:]])
                xx2 = np.minimum(x2[i], x2[order[1:]])
                yy2 = np.minimum(y2[i], y2[order[1:]])
                inter = np.maximum(xx2 - xx1, 0) * np.maximum(yy2 - yy1, 0)
                iou = inter / np.maximum(areas[i] + areas[order[1:]] - inter, 1e-9)
                order = order[1:][iou <= 0.4]
            np.testing.assert_array_equal(ours, np.asarray(keep, np.int64))

    def test_nms_numpy_uses_native(self):
        boxes = np.array([[0, 0, 10, 10], [1, 1, 11, 11], [50, 50, 60, 60]], np.float32)
        scores = np.array([0.9, 0.8, 0.7], np.float32)
        keep = nms_numpy(boxes, scores, 0.4)
        np.testing.assert_array_equal(keep, [0, 2])

    def test_tie_break_matches_numpy_fallback(self):
        # Equal scores: argsort()[::-1] visits the HIGHER index first, so
        # index 1 suppresses index 0 — native must agree.
        boxes = np.array([[0, 0, 10, 10], [0, 0, 10, 10]], np.float32)
        scores = np.array([0.5, 0.5], np.float32)
        np.testing.assert_array_equal(native.nms_f32(boxes, scores, 0.4), [1])

    def test_empty(self):
        assert len(nms_numpy(np.empty((0, 4), np.float32), np.empty((0,), np.float32))) == 0


class TestCtc:
    def test_batch_matches_per_row(self):
        rng = np.random.default_rng(6)
        vocab = ["<blank>"] + list("abcdefg")
        ids = rng.integers(0, len(vocab), (5, 20)).astype(np.int32)
        confs = rng.uniform(0, 1, (5, 20)).astype(np.float32)
        batch = ctc_collapse_rows(ids, confs, vocab)
        for b in range(5):
            text, score = ctc_collapse(ids[b], confs[b], vocab)
            assert batch[b][0] == text
            assert batch[b][1] == pytest.approx(score, rel=1e-6)

    def test_repeat_and_blank_collapse(self):
        vocab = ["<blank>", "a", "b"]
        ids = np.array([[1, 1, 0, 1, 2, 2, 0, 0, 2]], np.int32)
        confs = np.ones((1, 9), np.float32)
        (text, score), = ctc_collapse_rows(ids, confs, vocab)
        # collapse: a (t0), repeat dropped, a (after blank), b, repeat
        # dropped, b (after blanks)
        assert text == "aabb"
        assert score == 1.0

    def test_out_of_vocab_ids_skipped(self):
        vocab = ["<blank>", "a"]
        ids = np.array([[1, 5, 1]], np.int32)  # 5 has no vocab entry
        confs = np.full((1, 3), 0.5, np.float32)
        (text, score), = ctc_collapse_rows(ids, confs, vocab)
        assert text == "aa"
        assert score == pytest.approx(0.5)


class TestLoader:
    def test_available_and_abi(self):
        lib = native.load()
        assert lib is not None
        assert lib.lumen_host_ops_abi_version() == native.ABI_VERSION

    @pytest.fixture()
    def fresh_loader(self, tmp_path, monkeypatch):
        """The loader with nothing bound yet, pointed at an empty build
        directory that holds one un-keyed prebuilt copy of the real
        library (what ``make -C native`` leaves)."""
        import shutil

        keyed = tmp_path / "liblumen_host_ops-keyed.so"
        prebuilt = tmp_path / "liblumen_host_ops.so"
        shutil.copy(native.provenance()["path"], prebuilt)
        monkeypatch.setattr(native, "_LIB_PATH", str(keyed))
        monkeypatch.setattr(native, "_PREBUILT_PATH", str(prebuilt))
        for name, value in (
            ("_lib", None), ("_tried", False), ("_loaded_path", None), ("_built_here", False)
        ):
            monkeypatch.setattr(native, name, value)
        return keyed, prebuilt

    def test_builds_from_source_rather_than_take_unkeyed_prebuilt(self, fresh_loader):
        """With a compiler, the library is the one keyed by the committed
        source's digest — an un-keyed prebuilt one is not taken on trust."""
        keyed, _ = fresh_loader
        got = native.provenance()
        assert got["path"] == str(keyed) and got["digest_keyed"] and got["built_this_run"]

    def test_unkeyed_prebuilt_only_without_compiler(self, fresh_loader, monkeypatch):
        _, prebuilt = fresh_loader
        monkeypatch.setattr(native, "_CXX", "no-such-compiler-xyz")
        got = native.provenance()
        assert got["path"] == str(prebuilt) and not got["digest_keyed"]
        assert got["compiler"] is None and not got["built_this_run"]
