"""Ops tests: attention (Pallas kernel vs XLA reference), NMS parity,
CTC decode, sampling distributions, image preprocessing."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lumen_tpu.ops import (
    attention_cached,
    attention_reference,
    clip_preprocess,
    ctc_collapse,
    ctc_greedy_device,
    flash_attention,
    flash_attention_cache,
    letterbox_numpy,
    nms_jax,
    nms_numpy,
    repeat_kv,
    sample,
    top_p_filter,
)


def cache_mask_reference(q, k, v, q_offsets, kv_valid):
    """Ground truth: the VLM cache mask built as an explicit bool tensor
    (pre-flash semantics of ``models/vlm/modeling.py``)."""
    sq, sk = q.shape[2], k.shape[2]
    slots = jnp.arange(sk)
    q_abs = q_offsets[:, None] + jnp.arange(sq)[None, :]
    live = slots[None, :] < kv_valid[:, None]
    causal = slots[None, None, :] <= q_abs[:, :, None]
    mask = (live[:, None, :] & causal)[:, None]
    return attention_reference(q, k, v, mask=mask)


def rand_qkv(rng, b=2, h=4, sq=64, sk=64, d=32, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(rng, 3)
    return (
        jax.random.normal(kq, (b, h, sq, d), dtype),
        jax.random.normal(kk, (b, h, sk, d), dtype),
        jax.random.normal(kv, (b, h, sk, d), dtype),
    )


# the package re-exports a *function* named ``attention`` that shadows the
# submodule attribute, so import_module it is
attn_mod = importlib.import_module("lumen_tpu.ops.attention")
CROSSOVER = attn_mod._FLASH_CROSSOVER_SEQ


class TestAttention:
    def test_reference_softmax_rows_sum(self):
        q, k, v = rand_qkv(jax.random.PRNGKey(0))
        out = attention_reference(q, k, v)
        assert out.shape == q.shape
        assert np.isfinite(np.asarray(out)).all()

    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_matches_reference(self, causal):
        q, k, v = rand_qkv(jax.random.PRNGKey(1), sq=128, sk=128, d=64)
        ref = attention_reference(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_flash_unpadded_sequences(self):
        # seq not a multiple of block: causal path pads and still matches.
        q, k, v = rand_qkv(jax.random.PRNGKey(2), sq=80, sk=80, d=32)
        ref = attention_reference(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_causal_first_token_attends_self_only(self):
        q, k, v = rand_qkv(jax.random.PRNGKey(3), sq=16, sk=16, d=16)
        out = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out[:, :, 0]), np.asarray(v[:, :, 0]), atol=1e-5)

    @pytest.mark.parametrize(
        "length,mask,head_dim,force,route",
        [
            # by shape: what fits a tile or a few goes to the fused XLA attention
            (77, None, 64, None, "xla"),  # CLIP text tower
            (256, None, 64, None, "xla"),  # the captioner's image tower
            (257, None, 64, None, "xla"),  # ViT-L/14 at 224 px
            (CROSSOVER - 1, None, 64, None, "xla"),
            (CROSSOVER, None, 64, None, "flash"),
            (8192, None, 128, None, "flash"),
            # the kernel's other refusals hold at any length
            (4096, "mask", 64, None, "xla"),
            (4096, None, 512, None, "xla"),
            # LUMEN_FLASH forces either way; a mask still refuses the kernel
            (77, None, 64, "1", "flash"),
            (4096, None, 64, "0", "xla"),
            (4096, "mask", 64, "1", "xla"),
        ],
    )
    def test_flash_dispatch_by_shape(self, monkeypatch, length, mask, head_dim, force, route):
        """One route a shape on a TPU: (length, mask, head width,
        ``LUMEN_FLASH``) -> the program :func:`attention` picks."""
        if force is None:
            monkeypatch.delenv("LUMEN_FLASH", raising=False)
        else:
            monkeypatch.setenv("LUMEN_FLASH", force)
        monkeypatch.setattr(attn_mod, "_on_tpu", lambda: True)
        usable = attn_mod._flash_usable(head_dim, object() if mask else None, length)
        assert ("flash" if usable else "xla") == route

    @pytest.mark.parametrize("keys,route", [(255, "xla"), (256, "flash"), (2048, "flash")])
    def test_cache_path_keeps_its_own_gate(self, monkeypatch, keys, route):
        # attention_cached gates on the key length, at the value it had
        # before the crossover of attention() was measured.
        monkeypatch.delenv("LUMEN_FLASH", raising=False)
        monkeypatch.setattr(attn_mod, "_on_tpu", lambda: True)
        usable = attn_mod._flash_usable(64, None, keys, attn_mod._FLASH_CACHE_MIN_KEYS)
        assert ("flash" if usable else "xla") == route

    @pytest.mark.parametrize("causal", [False, True])
    def test_attention_at_the_tower_shape_against_float32(self, monkeypatch, causal):
        """``attention()`` at ViT-L/14's shape in the stated precision
        (bfloat16 operands, float32 scores and softmax) against the same
        values computed in float32. Tolerance from the dtype: outputs are
        weighted means of unit-scale values, rounded to bfloat16 (8 bits:
        2**-9 relative), as are the softmax weights before the second
        product; 2**-7, absolute plus relative, is twice the widest reading."""
        from lumen_tpu.ops import attention

        monkeypatch.delenv("LUMEN_FLASH", raising=False)
        q, k, v = rand_qkv(jax.random.PRNGKey(7), b=2, h=16, sq=257, sk=257, d=64, dtype=jnp.bfloat16)
        out = attention(q, k, v, causal=causal)
        assert out.dtype == jnp.bfloat16 and out.shape == q.shape
        ref = attention_reference(*(x.astype(jnp.float32) for x in (q, k, v)), causal=causal)
        np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref), atol=2**-7, rtol=2**-7)

    def test_attention_route_gauge_counts_traced_calls(self, monkeypatch):
        """The route is chosen when a program is traced; the
        ``attention-route`` gauge provider counts the choices by route and
        query length."""
        from lumen_tpu.ops import attention
        from lumen_tpu.utils.metrics import metrics

        monkeypatch.delenv("LUMEN_FLASH", raising=False)
        monkeypatch.setattr(attn_mod, "_on_tpu", lambda: True)  # trace only: nothing runs
        long_seq = 2 * CROSSOVER
        before = dict(metrics.snapshot()["gauges"].get("attention-route", {}))
        for seq in (257, long_seq):
            spec = jax.ShapeDtypeStruct((1, 2, seq, 64), jnp.bfloat16)
            jax.eval_shape(lambda q, k, v: attention(q, k, v), spec, spec, spec)
        after = metrics.snapshot()["gauges"]["attention-route"]
        moved = {key: after[key] - before.get(key, 0) for key in after if after[key] != before.get(key, 0)}
        assert moved == {"xla:257": 1, f"flash:{long_seq}": 1}

    def test_repeat_kv(self):
        x = jnp.arange(2 * 2 * 3 * 4).reshape(2, 2, 3, 4)
        y = repeat_kv(x, 3)
        assert y.shape == (2, 6, 3, 4)
        np.testing.assert_array_equal(np.asarray(y[:, 0]), np.asarray(y[:, 2]))


class TestNms:
    def test_numpy_suppresses_overlaps(self):
        boxes = np.array([[0, 0, 10, 10], [1, 1, 11, 11], [50, 50, 60, 60]], np.float32)
        scores = np.array([0.9, 0.8, 0.7], np.float32)
        keep = nms_numpy(boxes, scores, 0.4)
        assert list(keep) == [0, 2]

    def test_jax_matches_numpy(self):
        rng = np.random.default_rng(0)
        n = 64
        xy = rng.uniform(0, 100, (n, 2)).astype(np.float32)
        wh = rng.uniform(5, 30, (n, 2)).astype(np.float32)
        boxes = np.concatenate([xy, xy + wh], axis=1)
        scores = rng.uniform(0, 1, n).astype(np.float32)
        ref = set(nms_numpy(boxes, scores, 0.5).tolist())
        keep_mask = np.asarray(nms_jax(jnp.asarray(boxes), jnp.asarray(scores), 0.5))
        assert set(np.nonzero(keep_mask)[0].tolist()) == ref

    def test_jax_static_shape_with_padding(self):
        boxes = jnp.zeros((8, 4))
        scores = jnp.full((8,), -jnp.inf).at[0].set(1.0)
        boxes = boxes.at[0].set(jnp.array([0, 0, 10, 10]))
        keep = np.asarray(nms_jax(boxes, scores, 0.4))
        assert keep[0] and keep.sum() == 1  # -inf rows never kept


class TestCtc:
    def test_collapse_semantics(self):
        vocab = ["<blank>", "a", "b", "c"]
        ids = np.array([1, 1, 0, 1, 2, 0, 0, 3])
        confs = np.ones(8) * 0.5
        text, conf = ctc_collapse(ids, confs, vocab)
        assert text == "aabc"
        assert conf == pytest.approx(0.5)

    def test_empty_sequence(self):
        text, conf = ctc_collapse(np.zeros(4, int), np.ones(4), ["<blank>", "x"])
        assert text == "" and conf == 1.0

    def test_device_argmax(self):
        logits = jnp.zeros((1, 3, 4)).at[0, 0, 2].set(5.0).at[0, 1, 0].set(5.0).at[0, 2, 1].set(5.0)
        ids, conf = ctc_greedy_device(logits)
        assert ids.tolist() == [[2, 0, 1]]
        assert float(conf[0, 0]) > 0.9


class TestSampling:
    def test_greedy_when_do_sample_false(self):
        logits = jnp.array([[0.1, 5.0, 0.2]])
        tok = sample(jax.random.PRNGKey(0), logits, temperature=1.0, do_sample=False)
        assert tok.tolist() == [1]

    def test_temperature_zero_is_greedy(self):
        logits = jnp.array([[0.1, 5.0, 0.2]])
        tok = sample(jax.random.PRNGKey(0), logits, temperature=0.0, do_sample=True)
        assert tok.tolist() == [1]

    def test_top_p_filters_tail(self):
        logits = jnp.log(jnp.array([[0.5, 0.3, 0.15, 0.05]]))
        filtered = top_p_filter(logits, 0.7)
        # 0.5 + 0.3 >= 0.7 -> only the first two survive
        assert np.isfinite(np.asarray(filtered[0, :2])).all()
        assert np.isneginf(np.asarray(filtered[0, 2:])).all()

    def test_sampling_respects_distribution(self):
        logits = jnp.log(jnp.array([0.8, 0.2]))
        keys = jax.random.split(jax.random.PRNGKey(0), 500)
        toks = jax.vmap(lambda k: sample(k, logits, temperature=1.0, top_p=1.0))(keys)
        frac = float(np.mean(np.asarray(toks) == 0))
        assert 0.7 < frac < 0.9


class TestImage:
    def test_clip_preprocess_shape_and_range(self):
        imgs = jnp.ones((2, 100, 160, 3), jnp.uint8) * 128
        out = clip_preprocess(imgs, size=224)
        assert out.shape == (2, 224, 224, 3)
        # 128/255 normalized by CLIP stats is near zero.
        assert abs(float(out.mean())) < 1.0

    def test_letterbox_preserves_aspect(self):
        img = np.zeros((100, 200, 3), np.uint8)
        out, scale, pad_top, pad_left = letterbox_numpy(img, 64)
        assert out.shape == (64, 64, 3)
        assert scale == pytest.approx(64 / 200)
        assert pad_top == (64 - 32) // 2 and pad_left == 0


class TestFlashCacheKernel:
    """The (q_offsets, kv_valid) kernel that carries the VLM prefill/decode
    mask as two [B] scalars instead of a [B,1,S,K] bool tensor."""

    def test_prefill_matches_mask_reference(self):
        # Prompt lengths differ per sample; queries right-padded.
        q, k, v = rand_qkv(jax.random.PRNGKey(10), b=3, sq=48, sk=96, d=32)
        q_off = jnp.zeros((3,), jnp.int32)
        kv_valid = jnp.asarray([48, 17, 33], jnp.int32)
        ref = cache_mask_reference(q, k, v, q_off, kv_valid)
        out = flash_attention_cache(
            q, k, v, q_off, kv_valid, block_q=16, block_k=16, interpret=True
        )
        # Compare only live query rows (padded rows are discarded downstream).
        for b, n in enumerate([48, 17, 33]):
            np.testing.assert_allclose(
                np.asarray(out[b, :, :n]), np.asarray(ref[b, :, :n]), atol=2e-5, rtol=2e-5
            )

    def test_decode_single_token_per_sample_offsets(self):
        # One query per sample at different cache fill levels.
        q, k, v = rand_qkv(jax.random.PRNGKey(11), b=3, sq=1, sk=64, d=32)
        q_off = jnp.asarray([5, 20, 63], jnp.int32)
        kv_valid = q_off + 1
        ref = cache_mask_reference(q, k, v, q_off, kv_valid)
        out = flash_attention_cache(
            q, k, v, q_off, kv_valid, block_q=16, block_k=16, interpret=True
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_chunked_prefill_nonzero_offset(self):
        # Second prefill chunk: queries start at absolute position 32 and
        # must see the 32 earlier cache slots plus their own prefix.
        q, k, v = rand_qkv(jax.random.PRNGKey(12), b=2, sq=32, sk=64, d=32)
        q_off = jnp.asarray([32, 32], jnp.int32)
        kv_valid = jnp.asarray([64, 50], jnp.int32)
        ref = cache_mask_reference(q, k, v, q_off, kv_valid)
        out = flash_attention_cache(
            q, k, v, q_off, kv_valid, block_q=16, block_k=16, interpret=True
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_dispatcher_reference_path_matches(self):
        # attention_cached off-TPU routes to XLA with the equivalent mask.
        q, k, v = rand_qkv(jax.random.PRNGKey(13), b=2, sq=40, sk=64, d=32)
        q_off = jnp.zeros((2,), jnp.int32)
        kv_valid = jnp.asarray([40, 25], jnp.int32)
        ref = cache_mask_reference(q, k, v, q_off, kv_valid)
        out = attention_cached(q, k, v, q_off, kv_valid)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)

    def test_dispatcher_forced_flash_matches(self, monkeypatch):
        monkeypatch.setenv("LUMEN_FLASH", "1")
        q, k, v = rand_qkv(jax.random.PRNGKey(14), b=2, sq=40, sk=64, d=32)
        q_off = jnp.zeros((2,), jnp.int32)
        kv_valid = jnp.asarray([40, 25], jnp.int32)
        ref = cache_mask_reference(q, k, v, q_off, kv_valid)
        out = attention_cached(q, k, v, q_off, kv_valid)
        for b, n in enumerate([40, 25]):
            np.testing.assert_allclose(
                np.asarray(out[b, :, :n]), np.asarray(ref[b, :, :n]), atol=2e-5, rtol=2e-5
            )


class TestAttentionEdgeCases:
    def test_flash_kv_cache_decode_offset(self):
        # sq != sk causal: query i attends keys <= i + sk - sq.
        q, k, v = rand_qkv(jax.random.PRNGKey(9), sq=16, sk=64, d=32)
        ref = attention_reference(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_flash_noncausal_padded_k(self):
        # sk not a block multiple: padded K positions must get zero weight.
        q, k, v = rand_qkv(jax.random.PRNGKey(10), sq=32, sk=40, d=32)
        ref = attention_reference(q, k, v, causal=False)
        out = flash_attention(q, k, v, causal=False, block_q=32, block_k=32, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_top_p_zero_is_greedy(self):
        logits = jnp.array([[0.1, 5.0, 0.2]])
        for seed in range(5):
            tok = sample(jax.random.PRNGKey(seed), logits, temperature=1.0, top_p=0.0)
            assert tok.tolist() == [1]


class TestShardingNamedtuplePytree:
    def test_keypath_str_handles_attr_keys(self):
        from typing import NamedTuple
        from lumen_tpu.parallel import shard_params, TRANSFORMER_TP_RULES
        from lumen_tpu.runtime import build_mesh

        class Params(NamedTuple):
            kernel: jnp.ndarray

        mesh = build_mesh({"data": -1})
        sharded = shard_params({"layer": Params(kernel=jnp.ones((4, 4)))}, mesh, TRANSFORMER_TP_RULES)
        assert sharded["layer"].kernel.shape == (4, 4)


class TestRaggedDecodeBuckets:
    """Decode-path KV bucketing must be invisible in outputs: only the
    bytes read change."""

    def _run(self, sk, valids):
        from lumen_tpu.ops.attention import attention_cached

        b, h, d = len(valids), 4, 32
        q, k, v = rand_qkv(jax.random.PRNGKey(0), b=b, h=h, sq=1, sk=sk, d=d)
        q_off = jnp.asarray([v - 1 for v in valids], jnp.int32)
        kv_valid = jnp.asarray(valids, jnp.int32)
        return attention_cached(q, k, v, q_off, kv_valid)

    @pytest.mark.parametrize(
        "valids", [[1, 2], [255, 256], [257, 100], [512, 513], [1024, 7], [2048, 2048]]
    )
    def test_matches_unbucketed_across_boundaries(self, valids, monkeypatch):
        sk = 2048
        monkeypatch.setenv("LUMEN_RAGGED_DECODE", "1")  # pin: env may carry the kill switch
        bucketed = self._run(sk, valids)
        monkeypatch.setenv("LUMEN_RAGGED_DECODE", "0")
        plain = self._run(sk, valids)
        np.testing.assert_allclose(
            np.asarray(bucketed), np.asarray(plain), atol=2e-6, rtol=2e-6
        )

    def test_jit_and_scan_compatible(self):
        """The switch must compile inside a scan (the decode-loop shape)."""
        from lumen_tpu.ops.attention import attention_cached

        b, h, sk, d = 2, 2, 512, 16
        q, k, v = rand_qkv(jax.random.PRNGKey(1), b=b, h=h, sq=1, sk=sk, d=d)

        def step(carry, t):
            out = attention_cached(
                q, k, v, jnp.full((b,), t, jnp.int32), jnp.full((b,), t + 1, jnp.int32)
            )
            return carry + out.sum(), None

        total, _ = jax.jit(
            lambda: jax.lax.scan(step, jnp.zeros(()), jnp.arange(8, dtype=jnp.int32))
        )()
        assert bool(jnp.isfinite(total))
