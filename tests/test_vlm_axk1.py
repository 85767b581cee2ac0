"""The ``axk1`` decoder (the DeepSeek-V3 layer: latent attention over every
causal key in every layer, with no indexer, no window and no gate; a
YaRN-scaled rotation; sigmoid routing limited to the best groups; a share of
the bank held) against its plain reference, at a small size on the CPU:
prefill in lane chunks, then paged decode through ``ContinuousScheduler``,
agree in LOGITS with the reference's one full forward, and the same program
in bfloat16 does not; YaRN's frequencies and score scale against the closed
form; group-limited selection; the shares of an expert-parallel deployment
add up to the uncut layer; what a row keeps and what is refused; the names
the benchmark's readers match."""

from __future__ import annotations

import json
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.references import vlm_axk1 as ref
from benchmark.tensors import axk1 as listing
from lumen_tpu.models.vlm.continuous import ContinuousScheduler
from lumen_tpu.models.vlm.convert import convert_vlm_checkpoint
from lumen_tpu.models.vlm.generate import Generator
from lumen_tpu.models.vlm.modeling import (
    FULL_ATTENTION, VLMConfig, VLMModel, YarnScaling, init_paged_kv_cache, rope_rotate,
)
from lumen_tpu.models.vlm.paged_kv import RowState
from tests.test_vlm_hybrid import BLOCK, CHUNK, PAGE, SLOTS, DictCheckpoint, Tap, request

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 16, "type": "yarn"}


def tiny_config(**over) -> dict:
    """Sixteen experts in four groups of four, two groups kept, top-4; the
    prompts below lie past YaRN's 16 original positions."""
    t = {
        "model_type": "axk1", "hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "intermediate_size": 96, "vocab_size": 96, "num_attention_heads": 4, "num_key_value_heads": 4,
        "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "rope_theta": 10000, "rope_scaling": dict(YARN),
        "n_routed_experts": 8, "ep_size": 2, "ep_rank": 1, "n_group": 4, "topk_group": 2,
        "n_shared_experts": 1, "num_experts_per_tok": 4, "moe_intermediate_size": 32, "moe_layer_freq": 1,
        "norm_topk_prob": True, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "topk_method": "none",
        "rms_norm_eps": 1e-6, "tie_word_embeddings": False, "max_position_embeddings": 4096,
        "bos_token_id": 4, "eos_token_id": 5, "pad_token_id": 4,
    }
    t.update(over)
    return {
        "text_config": t, "image_token_index": 6,
        "vision_config": {"image_size": 32, "patch_size": 16, "hidden_size": 32,
                          "num_hidden_layers": 1, "num_attention_heads": 2},
    }


def random_state(cfg: dict, seed: int = 0) -> dict[str, np.ndarray]:
    """A checkpoint under the listing's names: N(0, 0.3) so that attention
    and the router have something to tell apart."""
    rng = np.random.default_rng(seed)
    state = {}
    for name, shape in listing.tensors(cfg):
        draw = rng.standard_normal(shape).astype(np.float32)
        norm = name.endswith(("norm.weight", "norm1.weight", "norm2.weight"))
        state[name] = 1.0 + 0.1 * draw if norm else 0.3 * draw
    return state


def reference_logits(cfg: dict, state: dict, ids) -> np.ndarray:
    """The plain reference's logits at every position of ``ids`` [S]."""
    t, ck = cfg["text_config"], DictCheckpoint(state)
    with jax.default_matmul_precision("highest"):
        x = ck.get("model.embed_tokens.weight")[jnp.asarray(ids)][None]
        for i in range(t["num_hidden_layers"]):
            x = ref.decoder_layer(x, ref.layer_params(ck, t, i, None), t, i)
        x = ref.rms_norm(x, ck.get("model.norm.weight"), t["rms_norm_eps"])
        return np.asarray(x[0] @ ck.get("lm_head.weight").T)


@pytest.fixture(scope="module")
def built():
    cfg = tiny_config()
    state = random_state(cfg)
    vcfg = VLMConfig.from_hf(cfg)
    model = VLMModel(vcfg)
    init = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32), jnp.zeros((1, 32, 32, 3)))
    )["params"]
    params = jax.tree.map(jnp.asarray, convert_vlm_checkpoint(state, init, tie_word_embeddings=False))
    return cfg, state, vcfg, model, params


def serve(vcfg, model, params, ids, max_new: int, span: int, dtype=jnp.float32, name="axk1-alone"):
    """One request through a scheduler of its own (lane chunks of 16, pages
    of 4, blocks of 2): its tokens, the logits each was drawn from, the
    scheduler's gauges."""
    if dtype != jnp.float32:
        params = jax.tree.map(lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
    gen = Tap(model, vcfg, max_seq=64, max_new_cap=16, cache_dtype=dtype)
    sched = ContinuousScheduler(
        gen, params, slots=SLOTS, block=BLOCK, name=name, page_size=PAGE, prefill_chunk=CHUNK
    )
    try:
        req = request(model, params, ids, max_new, span)
        toks, n_gen, _ = sched.submit(req).result(timeout=300)
        gauges = sched._gauge_fn()
    finally:
        sched.close()
    (row,) = gen.row_logits()
    return [int(t) for t in np.asarray(toks)[:n_gen]], row, gauges, sched


PROMPT = np.random.default_rng(1).integers(7, 96, 37)
#: logits agree to this share of their own spread: float32 against float32
#: differs by summation order alone (7e-6 read); the same program and weights in
#: bfloat16 read 1.5 (weights at N(0, 0.3) make these logits sensitive: its fourth
#: token already differs), so a tenth of a percent tells the two apart with room
TOLERANCE = 1e-3


def _worst(cfg, state, ids, toks, row) -> float:
    """Largest gap between the row's logits and the reference's, over every
    token the row emitted, in units of the reference's spread."""
    want = reference_logits(cfg, state, list(ids) + toks[:-1])
    n = len(ids)
    assert set(row) >= set(range(len(toks)))
    return max(float(np.abs(np.asarray(row[k], np.float32) - want[n - 1 + k]).max()) for k in range(len(toks))) / want.std()


def test_full_forward_matches_the_plain_reference(built):
    cfg, state, vcfg, model, params = built
    got = model.apply({"params": params}, jnp.asarray(PROMPT)[None], mutable=["moe_stats"])[0][0]
    want = reference_logits(cfg, state, PROMPT)
    assert np.abs(np.asarray(got) - want).max() < TOLERANCE * want.std()


def test_chunked_prefill_then_paged_decode_through_the_scheduler_agree_in_logits(built):
    """37 live tokens in a 48-token span (two full lane chunks and a padded
    third, each attending every earlier key of the scratch under the causal
    mask alone), then ten decode steps over the row's whole table: the
    logits of every emitted token against the reference's one full forward;
    and the control: the same program and weights in bfloat16 fail it."""
    cfg, state, vcfg, model, params = built
    toks, row, gauges, sched = serve(vcfg, model, params, PROMPT, 10, 48)
    assert sched.chunks_run == 3 and len(toks) == 10
    assert all(toks[k] == int(np.argmax(row[k])) for k in range(10))
    assert _worst(cfg, state, PROMPT, toks, row) < TOLERANCE
    low_toks, low_row, _, _ = serve(vcfg, model, params, PROMPT, 10, 48, dtype=jnp.bfloat16, name="axk1-bf16")
    # judged at its own tokens where they part from the float32 run's: the reference follows either
    assert _worst(cfg, state, PROMPT, low_toks, low_row) > 5 * TOLERANCE


def test_the_gauge_counts_the_keys_a_full_layers_query_attends(built):
    """``latent_keys_sum`` beside ``rows_stepped``: a block's live rows at
    their lengths when it starts (no indexer: nothing cuts them); the fields
    of window layers and of an indexer are absent, the experts' are there."""
    _, _, vcfg, model, params = built
    toks, _, gauges, sched = serve(vcfg, model, params, PROMPT, 10, 48, name="axk1-gauges")
    # the first token comes from the prefill; blocks of two steps emit the other nine
    starts = [37 + 1 + BLOCK * b for b in range(gauges["blocks_run"])]
    assert gauges["rows_stepped"] == gauges["blocks_run"] == 5 and gauges["latent_keys_sum"] == sum(starts)
    assert not {"window_pages_freed", "indexer_rows", "indexer_keys_scored", "state_bytes"} & set(gauges)
    assert {"moe_tokens_routed", "moe_tokens_held", "moe_experts_touched", "moe_layer_calls"} <= set(gauges)
    assert 0 < gauges["moe_tokens_held"] < gauges["moe_tokens_routed"]
    assert sched.kv.window is None and sched.kv.stats().pages_live == 0


def test_the_two_latent_decoders_are_two_settings_of_the_same_fields():
    """A gate or none, an indexer or none, a scaled rotation or none: one
    attention module, told by the configuration what the layer has."""
    from tests.test_vlm_latent import tiny_config as dots3_config

    d = VLMConfig.from_hf(dots3_config()).decoder
    assert d.indexer and d.index_topk == 8 and d.latent_gate and d.latent_full.rope_scaling is None
    a = VLMConfig.from_hf(tiny_config()).decoder
    assert not a.indexer and not a.latent_gate and a.latent_window is None and a.latent_full.rope_scaling is not None
    assert (d.moe_n_group, a.moe_n_group, a.moe_topk_group) == (1, 4, 2)


# -- YaRN -----------------------------------------------------------------------


def test_yarn_frequencies_and_scale_at_the_published_keys():
    """A.X-K1's ``rope_scaling`` (factor 32, beta 32 / 1, 4,096 original
    positions) on 64 rotary values at base 10,000, by hand: correction
    dimensions 64 ln(4096 / (2 pi n)) / (2 ln 10000) = 10.47 and 22.53, so
    pairs 0..10 keep their frequency, pairs 23..31 turn 32 times slower, and
    those between follow the ramp (i - 10) / 13."""
    y = YarnScaling.from_hf({**YARN, "original_max_position_embeddings": 4096})
    f = y.inv_freq(64, 10000.0)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(f[23:], plain[23:] / 32, rtol=1e-12)
    for i in (11, 16, 22):
        r = (i - 10) / 13
        np.testing.assert_allclose(f[i], plain[i] * (1 - r) + plain[i] / 32 * r, rtol=1e-12)
    np.testing.assert_allclose(f, ref.rope_frequencies({"qk_rope_head_dim": 64, "rope_theta": 10000,
                                                        "rope_scaling": {**YARN, "original_max_position_embeddings": 4096}}), rtol=1e-12)
    m = 0.1 * math.log(32) + 1
    assert y.rotation_scale == 1.0 and y.softmax_scale == pytest.approx(m * m) and m == pytest.approx(1.34657, abs=1e-5)
    pub = _published()
    if pub is not None:
        d = VLMConfig.from_hf(pub).decoder.latent_full
        assert d.scale == pytest.approx(0.13086, abs=1e-5) and d.rope_scaling == y


def test_rotation_with_and_without_scaling():
    """``rope_rotate`` under YaRN turns each pair by position x its blended
    frequency; with ``rope_scaling`` null it is the function it was."""
    x = jnp.asarray(np.random.default_rng(2).standard_normal((1, 2, 5, 8)), jnp.float32)
    pos = jnp.asarray([[0, 3, 17, 40, 900]])
    y = YarnScaling.from_hf(YARN)
    got = np.asarray(rope_rotate(x, pos, 10000.0, y))
    ang = np.asarray(pos)[0][:, None] * y.inv_freq(8, 10000.0)
    x1, x2 = np.asarray(x)[..., :4], np.asarray(x)[..., 4:]
    want = np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang), x2 * np.cos(ang) + x1 * np.sin(ang)], -1)
    np.testing.assert_allclose(got, want, atol=1e-4)  # float32 angles of up to 900 radians
    np.testing.assert_array_equal(np.asarray(rope_rotate(x, pos, 10000.0)), np.asarray(rope_rotate(x, pos, 10000.0, None)))
    assert np.abs(got - np.asarray(rope_rotate(x, pos, 10000.0))).max() > 0.1
    half = YarnScaling(factor=32.0, original_max=16, mscale=1.0, mscale_all_dim=0.0)
    assert half.softmax_scale == 1.0 and half.rotation_scale == pytest.approx(0.1 * math.log(32) + 1)
    with pytest.raises(NotImplementedError, match="linear"):
        YarnScaling.from_hf({"type": "linear", "factor": 2})


# -- group-limited selection ------------------------------------------------------


def _scores(rng, tokens=64, hidden=16, experts=16):
    x = jnp.asarray(rng.standard_normal((tokens, hidden)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((hidden, experts)), jnp.float32)
    return x, router, np.asarray(jax.nn.sigmoid(x @ router))


def test_selection_stays_inside_the_kept_groups_and_takes_their_best():
    from lumen_tpu.parallel.moe import _topk_gates

    rng = np.random.default_rng(3)
    x, router, scores = _scores(rng)
    bias = jnp.asarray(rng.standard_normal(16) * 0.3, jnp.float32)
    vals, idx = (np.asarray(a) for a in _topk_gates(x, router, 4, True, "sigmoid", bias, 2.5, n_group=4, topk_group=2))
    ranked = scores + np.asarray(bias)
    group_score = np.sort(ranked.reshape(-1, 4, 4), axis=-1)[..., -2:].sum(-1)  # the two largest of each group
    kept = np.argsort(-group_score, axis=-1)[:, :2]
    for t in range(len(idx)):
        assert set(idx[t] // 4) <= set(kept[t])  # never an expert outside the kept groups
        allowed = np.where(np.isin(np.arange(16) // 4, kept[t]), ranked[t], -np.inf)
        assert set(idx[t]) == set(np.argsort(-allowed)[:4])  # and of those the four largest score + bias
    # gates: the selected experts' own scores over their sum, times the scale
    picked = np.take_along_axis(scores, idx, axis=1)
    np.testing.assert_allclose(vals, 2.5 * picked / picked.sum(1, keepdims=True), rtol=1e-5)
    free = np.asarray(_topk_gates(x, router, 4, True, "sigmoid", bias, 2.5)[1])
    assert (np.sort(free, 1) != np.sort(idx, 1)).any()  # the limit changes who is selected
    want = np.asarray(ref.selected(jnp.asarray(scores), bias, {"n_group": 4, "topk_group": 2, "num_experts_per_tok": 4}))
    np.testing.assert_array_equal(np.sort(idx, 1), np.sort(want, 1))


@pytest.mark.parametrize("groups, keep", [(1, 1), (4, 4)], ids=["one-group", "every-group-kept"])
def test_one_group_is_plain_top_k_bit_for_bit(groups, keep):
    from lumen_tpu.parallel.moe import _topk_gates

    rng = np.random.default_rng(4)
    x, router, _ = _scores(rng)
    bias = jnp.asarray(rng.standard_normal(16) * 0.3, jnp.float32)
    plain = _topk_gates(x, router, 4, True, "sigmoid", bias, 2.5)
    limited = _topk_gates(x, router, 4, True, "sigmoid", bias, 2.5, n_group=groups, topk_group=keep)
    for a, b in zip(plain, limited):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if groups == 1:  # and the program is the one it was: the same operations
        text = lambda **kw: jax.jit(lambda x, r, b: _topk_gates(x, r, 4, True, "sigmoid", b, 2.5, **kw)).lower(x, router, bias).as_text()
        assert text() == text(n_group=1, topk_group=1)
    with pytest.raises(NotImplementedError, match="sigmoid"):
        _topk_gates(x, router, 4, True, "softmax", None, 1.0, n_group=4, topk_group=2)


# -- the share test -----------------------------------------------------------------


def test_the_shares_of_every_chip_add_up_to_the_uncut_layer():
    """Four chips of four experts each (a whole routing group a chip; with
    eight chips of two, half a group, as the benchmark's cut): each chip
    routes over all sixteen under the group limit and computes its own
    experts' part; the parts, with the shared expert counted once, add up to
    the plain reference's whole layer, and each is the reference's for that
    range."""
    from lumen_tpu.parallel.moe import MoEParams, moe_ffn

    rng = np.random.default_rng(5)
    g = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    e, d, f = 16, 16, 12
    bank = MoEParams(router=g(d, e), w_gate=g(e, d, f) * 0.3, w_up=g(e, d, f) * 0.3, w_down=g(e, f, d) * 0.3)
    x, bias = g(24, d), g(e) * 0.3
    t = {"num_experts_per_tok": 4, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
         "n_routed_experts": e, "n_group": 4, "topk_group": 2}
    hf = lambda w: jnp.swapaxes(w, -1, -2)  # the reference takes HF [out, in] weights
    shared = tuple(g(*s) * 0.3 for s in ((f, d), (f, d), (d, f)))
    p = {"router_w": hf(bank.router), "select_bias": bias, "shared": shared,
         "experts": {i: (hf(bank.w_gate[i]), hf(bank.w_up[i]), hf(bank.w_down[i])) for i in range(e)}}
    with jax.default_matmul_precision("highest"):
        whole = ref.expert_layer(x, p, t, held=(0, e))
        for chips in (4, 8):
            n = e // chips
            total, stats = ref.swiglu(x, *shared), np.zeros(4, np.int64)
            for lo in range(0, e, n):
                share = MoEParams(bank.router, *(w[lo:lo + n] for w in (bank.w_gate, bank.w_up, bank.w_down)))
                y, s = moe_ffn(share, x, k=4, capacity_factor=None, scoring="sigmoid", select_bias=bias,
                               routed_scale=2.5, held=(lo, lo + n), n_experts=e, with_stats=True,
                               n_group=4, topk_group=2)
                np.testing.assert_allclose(
                    np.asarray(y), np.asarray(ref.expert_layer(x, p, t, held=(lo, lo + n), shared=False)),
                    atol=2e-5, rtol=2e-5)
                total, stats = total + y, stats + np.asarray(s)
            np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=5e-5, rtol=5e-5)
            # every chip routed all 96 assignments; together they held each once
            assert stats.tolist()[:2] == [chips * 96, 96] and stats[3] == chips


# -- the configuration, the names, what a row keeps ------------------------------------


def _published():
    try:
        rows = [json.loads(line) for line in open("/opt/skills/guides/model-configs/architectures.jsonl")]
    except OSError:
        return None
    (pub,) = [r["config"] for r in rows if r["name"] == "A.X-K1"]
    return pub


def test_from_hf_reads_the_catalog_config_of_axk1_and_the_benchmarks_cut():
    pub = _published()
    if pub is None:
        pytest.skip("the catalog is not on this machine")
    d = VLMConfig.from_hf(pub).decoder
    assert d.latent and d.layers == 61 and d.layer_types == (FULL_ATTENTION,) * 61
    f = d.latent_full
    assert (f.heads, f.q_lora, f.kv_lora, f.nope, f.rope, f.v_dim, f.rope_theta) == (64, 1536, 512, 128, 64, 128, 1e4)
    assert d.latent_window is None and not d.indexer and not d.latent_gate and not d.latent_rescale
    assert (d.moe_experts, d.moe_top_k, d.moe_held, d.moe_scoring, d.moe_select_bias) == (192, 8, (0, 192), "sigmoid", True)
    assert (d.moe_n_group, d.moe_topk_group, d.moe_routed_scale, d.moe_norm_topk) == (8, 4, 2.5, True)
    assert d.moe_dense_layers == (0,) and not d.is_moe_layer(0) and d.is_moe_layer(1)
    assert d.moe_shared_intermediate == 2048 and not d.moe_shared_gated and not d.tie_word_embeddings
    cut = dict(pub, num_hidden_layers=5, n_routed_experts=12, ep_size=16, ep_rank=0, vocab_size=20480)
    c = VLMConfig.from_hf({"text_config": cut, "vision_config": {}, "image_token_index": 20000}).decoder
    assert c.layers == 5 and c.moe_experts == 192 and c.moe_held == (0, 12) and c.vocab_size == 20480
    assert VLMConfig.from_hf({"text_config": dict(cut, ep_rank=3)}).decoder.moe_held == (36, 48)
    with pytest.raises(ValueError, match="n_group"):
        VLMConfig.from_hf({"text_config": dict(cut, n_routed_experts=11, ep_size=1)})


def test_a_row_keeps_latent_and_rope_values_alone(built):
    """576 values a token a layer at the published widths; here 16 + 8: no
    index key, no window id space, every layer in the pool's own."""
    vcfg = built[2]
    rows = RowState(vcfg)
    assert rows.kinds == (RowState.LATENT,) * 3 and rows.full_latent_layers == 3
    assert rows.indexer_layers == rows.window_layers == rows.state_layers == 0 and not rows.shareable
    assert rows.page_bytes(PAGE, 2) == 3 * PAGE * (16 + 8) * 2 and rows.window_page_bytes(PAGE, 2) == 0
    caches = init_paged_kv_cache(vcfg, pages=9, page_size=PAGE)
    assert [sorted(layer) for layer in caches] == [["c", "r"]] * 3
    assert caches[0]["c"].shape == (9, PAGE, 16) and caches[0]["r"].shape == (9, PAGE, 8)
    pub = _published()
    if pub is not None:
        cut = VLMConfig.from_hf({"text_config": dict(pub, num_hidden_layers=5, n_routed_experts=12, ep_size=16)})
        assert RowState(cut).page_bytes(64, 2) == 64 * 5 * 576 * 2  # 5,760 B a token over five layers


def test_the_pool_is_sized_from_latent_pages_alone(built, monkeypatch):
    from lumen_tpu.models.vlm.paged_kv import resolve_pool_pages

    class Device:
        platform = "tpu"

        def __init__(self, free):
            self.free = free

        def memory_stats(self):
            return {"bytes_limit": self.free, "bytes_in_use": 0}

    vcfg = built[2]
    for name in ("LUMEN_VLM_KV_PAGES", "LUMEN_VLM_KV_HEADROOM"):
        monkeypatch.delenv(name, raising=False)
    free = 40 * RowState(vcfg).page_bytes(PAGE, 2) * 10 // 6 + 1  # 0.6 of it buys 40 pages
    monkeypatch.setattr(jax, "local_devices", lambda: [Device(free)])
    assert resolve_pool_pages(vcfg, PAGE, 4, max_seq=64) == (40, "device_memory")


def test_hf_names_convert_and_a_later_ranks_experts_stack_under_their_own_ids(built):
    """The DeepSeek-V3-style names land on the module's parameters; chip 1 of
    2 lists experts 8..15 under those ids and they stack in that order; no
    gate and no indexer tensor is asked for."""
    cfg, state, vcfg, model, params = built
    assert any(n.endswith("mlp.experts.8.gate_proj.weight") for n in state) and not any("experts.0." in n for n in state)
    attn = params["decoder"]["layers_1"]["attn"]
    assert sorted(attn) == ["kv_a_norm", "kv_a_proj", "kv_b_proj", "o_proj", "q_a_norm", "q_a_proj", "q_b_proj"]
    mlp = params["decoder"]["layers_1"]["mlp"]
    assert mlp["w_gate"].shape == (8, 64, 32) and mlp["router"].shape == (64, 16) and mlp["select_bias"].shape == (16,)
    np.testing.assert_array_equal(np.asarray(mlp["w_gate"][3]), state["model.layers.1.mlp.experts.11.gate_proj.weight"].T)
    np.testing.assert_array_equal(np.asarray(attn["kv_a_proj"]["kernel"]), state["model.layers.1.self_attn.kv_a_proj_with_mqa.weight"].T)
    broken = {k: v for k, v in state.items() if "experts.9." not in k}
    with pytest.raises(ValueError, match="non-contiguous"):
        convert_vlm_checkpoint(broken, None, tie_word_embeddings=False)


@pytest.mark.parametrize("env", ["LUMEN_VLM_PREFIX_BYTES", "LUMEN_VLM_SPEC_K"])
def test_sharing_a_latent_row_is_refused_at_construction_for_a_true_reason(built, monkeypatch, env):
    _, _, vcfg, model, params = built
    monkeypatch.setenv(env, "4096" if "PREFIX" in env else "2")
    gen = Generator(model, vcfg, max_seq=64, max_new_cap=16, cache_dtype=jnp.float32)
    with pytest.raises(NotImplementedError, match="latent decoder .*do not read or write latent leaves yet"):
        ContinuousScheduler(gen, params, slots=SLOTS, block=BLOCK, name="axk1-refused", page_size=PAGE)


def test_spill_is_off_and_export_resume_verify_and_migration_are_refused(built):
    _, _, vcfg, model, params = built
    gen = Generator(model, vcfg, max_seq=64, max_new_cap=16, cache_dtype=jnp.float32)
    sched = ContinuousScheduler(gen, params, slots=SLOTS, block=BLOCK, name="axk1-spill", page_size=PAGE)
    one = jnp.zeros((1,), jnp.int32)
    try:
        assert sched._spill_budget == 0 and sched.prefix is None and sched.spec_k == 0
        attempts = [
            lambda: sched.submit_migrated(request(model, params, PROMPT[:8], 2), None, [], 0),
            lambda: gen._export_row(sched.pool, 0, jnp.zeros((2,), jnp.int32)),
            lambda: gen._resume({"caches": one}, 0, one, one, *([one] * 9)),
            lambda: gen._seed_prefix([{"c": one + 0}], [{"c": one}], one),
            lambda: gen._verify(params, {"cur_tok": one}, one[None], jax.random.PRNGKey(0), one[None], one, width=2),
            lambda: gen.generate(params, jnp.zeros((1, 4, 64)), jnp.arange(4)[None], one + 4,
                                 jnp.zeros((1, 4), jnp.int32), jax.random.PRNGKey(0), max_new_tokens=2),
        ]
        for attempt in attempts:
            with pytest.raises(NotImplementedError, match="latent decoder"):
                attempt()
        assert sched.kv.stats().pages_live == 0
    finally:
        sched.close()


def test_the_route_gauge_names_the_decode_over_every_key(built):
    """``attention-route`` counts a ``latent-all:{key slots}`` program for a
    full latent layer that attends its row's whole table."""
    from lumen_tpu.utils.metrics import metrics

    _, _, vcfg, model, params = built
    serve(vcfg, model, params, PROMPT[:9], 3, 12, name="axk1-route")
    routes = metrics.snapshot()["gauges"]["attention-route"]
    assert any(name.startswith("latent-all:") for name in routes), routes
