"""W8A8 int8 CLIP towers (round 5): quantized embeddings stay close to
full precision, the manager serves the quantized model end-to-end, and
the int8 TP sharding rules cover the tower tree.

Motivation (docstring'd on ``CLIPConfig.weight_quant``): batch image
embedding is MXU-compute-bound, and TPU int8 peak is ~2x bf16 — unlike
the VLM decoder's bandwidth-motivated weight-only int8. The reference
has no quantized execution at all.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.clip_fixtures import make_clip_model_dir, png_bytes


def _cos_rows(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    num = (a * b).sum(-1)
    den = np.linalg.norm(a, axis=-1) * np.linalg.norm(b, axis=-1) + 1e-30
    return num / den


class TestQuantizedTowers:
    @pytest.mark.parametrize("kernel", ["dynamic", "dequant"])
    def test_image_embeds_close_to_fp(self, kernel):
        from lumen_tpu.models.clip.convert import quantize_clip_int8
        from lumen_tpu.models.clip.modeling import CLIPConfig, CLIPModel

        cfg = CLIPConfig.tiny()
        model = CLIPModel(cfg)
        params = model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, cfg.image_size, cfg.image_size, 3)),
            jnp.zeros((1, cfg.context_length), jnp.int32),
        )["params"]
        qcfg = dataclasses.replace(cfg, weight_quant="int8", weight_quant_kernel=kernel)
        qmodel = CLIPModel(qcfg)
        qparams = quantize_clip_int8(params)

        px = jnp.asarray(np.random.RandomState(0).rand(2, 32, 32, 3), jnp.float32)
        want = np.asarray(model.apply(
            {"params": params}, px, method=lambda m, x: m.encode_image(x)))
        got = np.asarray(qmodel.apply(
            {"params": qparams}, px, method=lambda m, x: m.encode_image(x)))
        cos = _cos_rows(got, want)
        assert cos.min() > 0.98, cos

    def test_text_embeds_close_to_fp(self):
        from lumen_tpu.models.clip.convert import quantize_clip_int8
        from lumen_tpu.models.clip.modeling import CLIPConfig, CLIPModel

        cfg = CLIPConfig.tiny()
        model = CLIPModel(cfg)
        params = model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, cfg.image_size, cfg.image_size, 3)),
            jnp.zeros((1, cfg.context_length), jnp.int32),
        )["params"]
        qcfg = dataclasses.replace(cfg, weight_quant="int8")
        qparams = quantize_clip_int8(params)
        ids = jnp.asarray([[1, 5, 9, 127] + [0] * 12], jnp.int32)
        want = np.asarray(model.apply(
            {"params": params}, ids, method=lambda m, x: m.encode_text(x)))
        got = np.asarray(CLIPModel(qcfg).apply(
            {"params": qparams}, ids, method=lambda m, x: m.encode_text(x)))
        assert _cos_rows(got, want).min() > 0.98

    def test_vision_only_pattern_skips_text(self):
        from lumen_tpu.models.clip.convert import quantize_clip_int8
        from lumen_tpu.models.clip.modeling import CLIPConfig, CLIPModel

        cfg = CLIPConfig.tiny()
        params = CLIPModel(cfg).init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, cfg.image_size, cfg.image_size, 3)),
            jnp.zeros((1, cfg.context_length), jnp.int32),
        )["params"]
        q = quantize_clip_int8(params, include_text=False)
        assert "q" in q["vision"]["blocks_0"]["attn"]["q_proj"]
        assert "kernel" in q["text"]["blocks_0"]["attn"]["q_proj"]


class TestQuantizedManager:
    def test_manager_serves_quantized(self, tmp_path):
        from lumen_tpu.models.clip import CLIPManager

        model_dir = make_clip_model_dir(tmp_path)
        fp = CLIPManager(model_dir, dtype="float32")
        fp.initialize()
        q = CLIPManager(model_dir, dtype="float32", quantize="int8")
        q.initialize()
        try:
            img = png_bytes(0)
            a = fp.encode_image(img)
            b = q.encode_image(img)
            # both unit-norm [D]; the int8 grid shifts them only slightly
            assert _cos_rows(a[None], b[None]).min() > 0.98
            t_a = fp.encode_text("a photo")
            t_b = q.encode_text("a photo")
            assert _cos_rows(t_a[None], t_b[None]).min() > 0.98
        finally:
            fp.close()
            q.close()

    def test_bad_quantize_rejected(self, tmp_path):
        from lumen_tpu.models.clip import CLIPManager

        with pytest.raises(ValueError, match="quantize"):
            CLIPManager(make_clip_model_dir(tmp_path), quantize="int4")


class TestQuantRouteSelection:
    """int8 is opt-in AND verified: without a warmup pass the explicit
    config wins; with warmup, a one-shot A/B may fall the route back to
    bf16 (q8 at 0.923x bf16 on v5e was a regression: round-5 chip run,
    2026-08-02, older than the ledger); the
    chosen route lands in a metrics gauge either way."""

    def test_explicit_optin_without_warmup_serves_int8(self, tmp_path):
        from lumen_tpu.models.clip import CLIPManager

        q = CLIPManager(make_clip_model_dir(tmp_path), dtype="float32", quantize="int8")
        q.initialize()
        try:
            assert q.quant_route == "int8"
            assert q.quant_speedup is None  # nothing was timed
        finally:
            q.close()

    def test_env_pin_bf16_overrides_optin(self, tmp_path, monkeypatch):
        from lumen_tpu.models.clip import CLIPManager

        monkeypatch.setenv("LUMEN_CLIP_Q8_ROUTE", "bf16")
        q = CLIPManager(make_clip_model_dir(tmp_path), dtype="float32", quantize="int8")
        q.initialize()
        try:
            assert q.quant_route == "bf16"
            vec = q.encode_image(png_bytes(0))  # bf16 route actually serves
            assert np.isfinite(vec).all()
        finally:
            q.close()

    def test_warmup_ab_times_routes_and_registers_gauge(self, tmp_path):
        from lumen_tpu.models.clip import CLIPManager
        from lumen_tpu.utils.metrics import metrics

        q = CLIPManager(
            make_clip_model_dir(tmp_path), dtype="float32", quantize="int8",
            batch_size=2, warmup=True,
        )
        q.initialize()
        try:
            # Which side wins on CPU is irrelevant — the contract is that
            # the A/B RAN, picked a route, and exported it observably.
            assert q.quant_route in ("int8", "bf16")
            assert q.quant_speedup is not None and q.quant_speedup > 0
            gauges = metrics.snapshot()["gauges"][f"clip-quant:{q.model_id}"]
            assert gauges["int8_active"] == (1 if q.quant_route == "int8" else 0)
            assert gauges["q8_speedup_pct"] == round(q.quant_speedup * 100, 1)
            vec = q.encode_image(png_bytes(0))  # chosen route serves
            assert np.isfinite(vec).all()
        finally:
            q.close()
        assert f"clip-quant:{q.model_id}" not in metrics.snapshot().get("gauges", {})


class TestInt8TpRulesCoverClip:
    def test_rules_match_tower_q_leaves(self):
        import re

        from lumen_tpu.models.clip.convert import quantize_clip_int8
        from lumen_tpu.models.clip.modeling import CLIPConfig, CLIPModel
        from lumen_tpu.parallel.sharding import INT8_TP_RULES
        from lumen_tpu.runtime.weights import flatten

        from tests.clip_fixtures import random_variables

        cfg = CLIPConfig.tiny()
        # Shape-only init: the test only checks the quantized tree's *paths*
        # against the TP rules, so concrete weight values are irrelevant.
        params = random_variables(
            lambda: CLIPModel(cfg).init(
                jax.random.PRNGKey(0),
                jnp.zeros((1, cfg.image_size, cfg.image_size, 3)),
                jnp.zeros((1, cfg.context_length), jnp.int32),
            )["params"]
        )
        flat = flatten(quantize_clip_int8(params))
        q_paths = [p for p in flat if p.endswith("/q")]
        assert q_paths
        pats = [re.compile(p) for p, _ in INT8_TP_RULES]
        for path in q_paths:
            assert any(p.match(path) for p in pats), path
