"""The latent decoder (``model_type`` ``dots3_note``) against its plain
reference, at a small size on the CPU: prefill chunks then decode through
the paged latent cache agree in logits with the reference's one full
forward, for a full layer past its top-k and a window layer past its
window; pages behind the window are freed and never read; the routing rule;
the shares of an expert-parallel deployment add up to the uncut layer."""

from __future__ import annotations

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.references import vlm_dots3 as ref
from benchmark.tensors import dots3 as listing
from lumen_tpu.models.vlm.convert import convert_vlm_checkpoint
from lumen_tpu.models.vlm.generate import Generator
from lumen_tpu.models.vlm.modeling import FULL_ATTENTION, VLMConfig, VLMModel
from lumen_tpu.models.vlm.paged_kv import PagedKVPool, WindowPages, window_pool_pages

PAGE = 4
POISON = 1e4
PROMPT, NEW = 40, 12


def tiny_config(**over) -> dict:
    t = {
        "model_type": "dots3_note", "hidden_size": 64, "num_hidden_layers": 4,
        "layer_types": ["full_attention", "full_attention", "sliding_attention", "sliding_attention"],
        "first_k_dense_replace": 1, "intermediate_size": 96, "vocab_size": 96,
        "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 80000000,
        "swa_num_attention_heads": 2, "swa_num_key_value_heads": 2, "swa_q_lora_rank": 32,
        "swa_kv_lora_rank": 32, "swa_qk_nope_head_dim": 24, "swa_qk_rope_head_dim": 8,
        "swa_v_head_dim": 16, "swa_rope_theta": 50000, "sliding_window_size": 5,
        "index_n_heads": 4, "index_head_dim": 16, "index_topk": 8,
        "apply_mla_qkv_lora_rescale": True, "attention_gate_type": "headwise",
        "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 2,
        "moe_intermediate_size": 32, "moe_layer_freq": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "rms_norm_eps": 1e-5, "tie_word_embeddings": False, "max_position_embeddings": 4096,
        "bos_token_id": 4, "eos_token_id": 5, "pad_token_id": 4,
    }
    t.update(over)
    return {
        "text_config": t, "image_token_index": 6,
        "vision_config": {"image_size": 32, "patch_size": 16, "hidden_size": 32,
                          "num_hidden_layers": 1, "num_attention_heads": 2},
    }


def random_state(cfg: dict, seed: int = 0) -> dict[str, np.ndarray]:
    """A checkpoint under the listing's names: N(0, 0.3) so that attention,
    the indexer and the router all have something to tell apart."""
    rng = np.random.default_rng(seed)
    state = {}
    for name, shape in listing.tensors(cfg):
        if name.endswith("norm.weight") or name.endswith("norm1.weight") or name.endswith("norm2.weight"):
            state[name] = 1.0 + 0.1 * rng.standard_normal(shape).astype(np.float32)
        else:
            state[name] = (0.3 * rng.standard_normal(shape)).astype(np.float32)
    return state


class DictCheckpoint:
    def __init__(self, state):
        self.state = state

    def get(self, name):
        return jnp.asarray(self.state[name], jnp.float32)


def reference_logits(cfg: dict, state: dict, ids: np.ndarray) -> np.ndarray:
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
    params = convert_vlm_checkpoint(state, init, tie_word_embeddings=False)
    params = jax.tree.map(jnp.asarray, params)
    return cfg, state, vcfg, model, params


def serve(vcfg, model, params, ids: np.ndarray, new: int, chunk: int = 16, poison: bool = False):
    """Prefill ``ids`` in chunks into a scratch, install into pages, decode
    ``new`` teacher-forced steps: the program's logits at every position that
    the steps cover, and the pool. ``poison`` fills every page that no table
    names before each step (finite, so that a masked slot of a reused page
    still weighs nothing, and huge, so that a read would show)."""
    gen = Generator(model, vcfg, max_seq=64, max_new_cap=16, cache_dtype=jnp.float32)
    slots, block = 2, 1
    maxp = 64 // PAGE
    wpages = window_pool_pages(vcfg, PAGE, slots, block)
    window = WindowPages(wpages, PAGE, slots, maxp, vcfg.decoder.sliding_window)
    kv = PagedKVPool(slots * maxp + 1, PAGE, slots, maxp, window=window)
    pool = gen.init_pool(slots, pages=kv.pages_total, page_size=PAGE, window_pages=wpages)
    n = len(ids) - new
    embeds = model.apply({"params": params}, jnp.asarray(ids)[None], method=VLMModel.embed_tokens)
    span = -(-n // PAGE) * PAGE
    caches = gen.new_prefill_cache(span)
    logits = []
    for off in range(0, n, chunk):
        c = min(chunk, n - off)
        out, caches = gen._prefill_chunk(
            params, caches, embeds[:, off:off + c],
            jnp.arange(off, off + c)[None], jnp.asarray(off, jnp.int32), jnp.asarray([n], jnp.int32),
        )
        logits.append(np.asarray(out[0]))
    slot = 1
    row = kv.admit(slot, n)
    pool = gen._admit(
        pool, slot, caches, jnp.asarray([ids[n]]), jnp.zeros((1, vcfg.decoder.vocab_size), bool),
        jnp.asarray([n]), jnp.asarray(np.stack([row, window.tables[slot]])), new, 0.0, 1.0, False, 1.0,
    )
    caches_p = pool["caches"]
    for step in range(new):
        pos = n + step
        window.trim(slot, pos)
        assert kv.grow(slot, pos + 1)
        if poison:
            live_full = set(kv.block_tables.ravel()) - {0}
            live_win = set(window.tables.ravel()) - {0}
            caches_p = [
                {
                    name: arr.at[jnp.asarray(sorted(
                        set(range(1, arr.shape[0]))
                        - (live_full if vcfg.decoder.layer_kind(i) == FULL_ATTENTION else live_win)
                    ), jnp.int32)].set(POISON)
                    for name, arr in layer.items()
                }
                for i, layer in enumerate(caches_p)
            ]
        tok = jnp.zeros((slots,), jnp.int32).at[slot].set(int(ids[pos]))
        emb = model.apply({"params": params}, tok[:, None], method=VLMModel.embed_tokens)
        p = jnp.zeros((slots,), jnp.int32).at[slot].set(pos)
        out, caches_p, stats = gen._decode_paged(
            params, emb, p[:, None], caches_p, jnp.asarray(kv.device_tables(maxp)), p, p + 1,
            jnp.arange(slots) == slot,
        )
        logits.append(np.asarray(out[slot]))
    return np.concatenate(logits, axis=0), kv, stats


def test_prefill_then_paged_decode_agree_with_the_reference_in_logits(built):
    """40 prompt tokens in chunks of 16, then 12 decode steps: every full
    layer row is past the indexer's top-k (8) and every window layer row past
    its window (5) from early in the prompt on."""
    cfg, state, vcfg, model, params = built
    ids = np.random.default_rng(1).integers(7, 96, PROMPT + NEW)
    want = reference_logits(cfg, state, ids)
    got, kv, stats = serve(vcfg, model, params, ids, NEW)
    scale = want.std()
    assert np.abs(got - want).max() < 2e-3 * scale, np.abs(got - want).max() / scale
    # the decode steps alone, so that a prefill that is right cannot hide them
    assert np.abs(got[PROMPT:] - want[PROMPT:]).max() < 2e-3 * scale
    assert int(stats[3]) == 3  # three expert layers counted one call each


def test_pages_behind_the_window_are_freed_and_never_read(built):
    cfg, state, vcfg, model, params = built
    ids = np.random.default_rng(2).integers(7, 96, PROMPT + NEW)
    clean, kv, _ = serve(vcfg, model, params, ids, NEW)
    dirty, kv2, _ = serve(vcfg, model, params, ids, NEW, poison=True)
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(clean, dirty)
    w = kv2.window
    # 52 tokens: the last query (position 51) sees keys 47..51, page 11 on
    assert w.first_live(PROMPT + NEW - 1) == 11
    assert w.freed_behind == 11 and w.pages_live == 13 - 11
    assert (w.tables[1, :11] == 0).all() and (w.tables[1, 11:13] > 0).all()
    # the full layers keep every page of the row
    assert len(kv2.owned_pages(1)) == 13
    kv2.release(1)
    assert w.pages_live == 0 and kv2.pages_live == 0


# -- the kernels against their XLA references (interpret mode) -----------------


def _paged_case(rng, rows=3, heads=4, c_dim=32, r_dim=8, maxp=6, lens=(22, 9, 17), page=PAGE):
    from lumen_tpu.ops import latent_attention as la

    pages = rows * maxp + 1
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    bt = np.zeros((rows, maxp), np.int32)
    ids = iter(rng.permutation(np.arange(1, pages)))
    for b, n in enumerate(lens):
        for j in range(-(-n // page)):
            bt[b, j] = next(ids)
    return la, dict(
        qc=f(rows, heads, c_dim), qr=f(rows, heads, r_dim), c_pages=f(pages, page, c_dim),
        r_pages=f(pages, page, r_dim), block_tables=jnp.asarray(bt), kv_lens=jnp.asarray(lens, jnp.int32),
    )


#: name -> (case, _paged_case's shapes, the window or the pages row 0 loses)
PAGED_CASES = {
    "full": ("full", {}, None),
    "selected": ("selected", {}, None),
    "window": ("window", {}, 5),
    # 64-token pages, 16 a grid step: rows of 20, 18 and 5 pages whose last
    # group is partial, an idle row (kv_len 1) that copies one page, and done
    # or free rows (kv_len 0: LatentAttention's for a row token_valid leaves
    # out) that copy none
    "full_partial_groups": ("full", dict(rows=6, maxp=20, lens=(1250, 1, 0, 300, 1100, 0), page=64), None),
    # a window of 1,100 keys over 19 pages, two steps: a row's groups begin
    # at the page of its window's start, which falls mid-page (900 in row 2)
    "window_start_mid_page": ("window", dict(rows=4, maxp=32, lens=(100, 1300, 2000, 1), page=64), 1100),
    # the indexer picks nothing in row 0's second group (pages 16-31)
    "selected_empty_group": ("selected", dict(rows=3, maxp=34, lens=(2100, 300, 1), page=64), (16, 32)),
}


@pytest.mark.parametrize("name", list(PAGED_CASES))
def test_latent_paged_kernel_matches_its_reference(name):
    case, shapes, extra = PAGED_CASES[name]
    rng = np.random.default_rng(3)
    la, a = _paged_case(rng, **shapes)
    rows, maxp = a["block_tables"].shape
    page = a["c_pages"].shape[1]
    starts = jnp.zeros((rows,), jnp.int32)
    sel, span = None, None
    if case == "selected":
        # row 0 loses its whole first page and every third key after it
        keep = np.ones((rows, maxp * page), bool)
        keep[0, :page] = False
        keep[:, 5::3] = False
        if extra:
            keep[0, extra[0] * page : extra[1] * page] = False
        sel = jnp.asarray(keep)
    if case == "window":
        starts = jnp.maximum(a["kv_lens"] - extra, 0)
        span = la.window_span_pages(extra, page)
    args = (a["qc"], a["qr"], a["c_pages"], a["r_pages"], a["block_tables"], a["kv_lens"], starts, sel)
    want = la.latent_paged_attention_reference(*args, scale=0.3, span=span)
    got = la.latent_paged_attention_kernel(*args, scale=0.3, span=span, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
    if case == "window":  # and both are what a full pass under the same bounds gives
        whole = la.latent_paged_attention_reference(*args, scale=0.3, span=None)
        np.testing.assert_allclose(np.asarray(want), np.asarray(whole), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("case", ["full", "selected", "window"])
def test_the_route_gauge_counts_the_keys_of_a_latent_grid_step(case):
    """``attention-route`` counts ``latent-grid:{keys a grid step}`` once for
    each latent decode layer traced, whichever kind (the grid follows the
    shapes: a whole table of 6 pages of 4 is one step of 24 keys, a window
    of 5 keys two pages of 4), on the CPU's XLA path as on the chip's."""
    from lumen_tpu.utils.metrics import metrics

    la, a = _paged_case(np.random.default_rng(5))
    starts, sel, span = jnp.zeros((3,), jnp.int32), None, None
    if case == "selected":
        sel = jnp.ones((3, 6 * PAGE), bool)
    if case == "window":
        starts, span = jnp.maximum(a["kv_lens"] - 5, 0), la.window_span_pages(5, PAGE)
    name = f"latent-grid:{8 if case == 'window' else 24}"
    before = metrics.snapshot().get("gauges", {}).get("attention-route", {}).get(name, 0)
    la.latent_paged_attention(
        a["qc"], a["qr"], a["c_pages"], a["r_pages"], a["block_tables"], a["kv_lens"], starts, sel,
        scale=0.3, span=span,
    )
    assert metrics.snapshot()["gauges"]["attention-route"][name] == before + 1


def test_a_latent_grid_step_covers_1024_keys_of_the_pool_page():
    """A.X-K1's whole table (72 pages of 64) takes 5 grid steps a row, and
    dots3's window layer (span 9) one; a short table is one step."""
    from lumen_tpu.ops import latent_attention as la

    assert la.grid_pages(64, 72) * 64 == la.GRID_KEYS == 1024
    assert -(-72 // la.grid_pages(64, 72)) == 5
    assert la.grid_pages(64, la.window_span_pages(513, 64)) == 9
    assert la.grid_pages(4, 6) == 6 and la.grid_pages(2048, 6) == 1


def test_indexer_kernel_matches_its_reference_and_the_selection_keeps_k():
    rng = np.random.default_rng(4)
    la, a = _paged_case(rng)
    qi = jnp.asarray(rng.standard_normal((3, 4, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 4)), jnp.float32)
    ik = jnp.asarray(rng.standard_normal((a["c_pages"].shape[0], PAGE, 16)), jnp.float32)
    want = la.indexer_scores_reference(qi, w, ik, a["block_tables"])
    got = la.indexer_scores_kernel(qi, w, ik, a["block_tables"], interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
    ok = jnp.arange(want.shape[1])[None, :] < a["kv_lens"][:, None]
    sel = np.asarray(la.topk_select(want, ok, 8))
    assert sel.sum(axis=1).tolist() == [8, 8, 8] and not (sel & ~np.asarray(ok)).any()
    best = np.sort(np.where(np.asarray(ok), np.asarray(want), -np.inf), axis=1)[:, -8]
    assert (np.asarray(want)[sel] >= np.repeat(best, 8)).all()
    # fewer allowed keys than k: all of them
    few = np.asarray(la.topk_select(want, ok & (jnp.arange(want.shape[1]) < 5)[None, :], 8))
    assert few.sum(axis=1).tolist() == [5, 5, 5]


# -- the expert layer ------------------------------------------------------------


def _bank(rng, e=8, d=16, f=12):
    from lumen_tpu.parallel.moe import MoEParams

    g = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return MoEParams(router=g(d, e), w_gate=g(e, d, f) * 0.3, w_up=g(e, d, f) * 0.3, w_down=g(e, f, d) * 0.3)


def test_shares_of_an_expert_parallel_layer_add_up_to_the_uncut_reference():
    """Four chips of two experts each: their routed parts, with the shared
    expert counted once, add up to the plain reference's whole layer; each
    share is also what the reference gives for that range alone."""
    from lumen_tpu.parallel.moe import MoEParams, moe_ffn

    rng = np.random.default_rng(5)
    bank = _bank(rng)
    x = jnp.asarray(rng.standard_normal((24, 16)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(8) * 0.5, jnp.float32)
    t = {"num_experts_per_tok": 2, "norm_topk_prob": True, "routed_scaling_factor": 1,
         "n_routed_experts": 8}
    hf = lambda w: jnp.swapaxes(w, -1, -2)  # the reference takes HF [out, in] weights
    shared = tuple(jnp.asarray(rng.standard_normal(s), jnp.float32) * 0.3 for s in ((12, 16), (12, 16), (16, 12)))
    p = {"router_w": hf(bank.router), "select_bias": bias, "shared": shared,
         "experts": {e: (hf(bank.w_gate[e]), hf(bank.w_up[e]), hf(bank.w_down[e])) for e in range(8)}}
    with jax.default_matmul_precision("highest"):
        whole = ref.expert_layer(x, p, t, held=(0, 8))
        total = ref.swiglu(x, *shared)
        stats = np.zeros(4, np.int64)
        for lo in range(0, 8, 2):
            share = MoEParams(bank.router, *(w[lo:lo + 2] for w in (bank.w_gate, bank.w_up, bank.w_down)))
            y, s = moe_ffn(share, x, k=2, capacity_factor=None, scoring="sigmoid", select_bias=bias,
                           held=(lo, lo + 2), n_experts=8, with_stats=True)
            np.testing.assert_allclose(
                np.asarray(y), np.asarray(ref.expert_layer(x, p, t, held=(lo, lo + 2), shared=False)),
                atol=1e-5, rtol=1e-5)
            total = total + y
            stats += np.asarray(s)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=2e-5, rtol=2e-5)
    # every chip routed all 48 assignments; together they held each once
    assert stats.tolist()[:2] == [4 * 48, 48] and stats[3] == 4 and 1 <= stats[2] <= 8


def test_the_selection_bias_changes_who_is_selected_and_not_the_weights():
    from lumen_tpu.parallel.moe import _topk_gates

    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    scores = np.asarray(jax.nn.sigmoid(x @ router))
    bias = jnp.asarray([5.0, 0, 0, 0, 0, 0, 0, -5.0])  # expert 0 always in, expert 7 never
    v0, i0 = (np.asarray(a) for a in _topk_gates(x, router, 2, False, "sigmoid", None))
    v1, i1 = (np.asarray(a) for a in _topk_gates(x, router, 2, False, "sigmoid", bias))
    assert (i1 == 0).any(axis=1).all() and not (i1 == 7).any() and (i0 != i1).any()
    # the gate of a selected expert is its own score, bias or no bias
    np.testing.assert_allclose(v1, np.take_along_axis(scores, i1, axis=1), rtol=1e-6)
    np.testing.assert_allclose(v0, np.take_along_axis(scores, i0, axis=1), rtol=1e-6)
    vn, _ = _topk_gates(x, router, 2, True, "sigmoid", bias, routed_scale=2.5)
    np.testing.assert_allclose(np.asarray(vn).sum(axis=1), 2.5, rtol=1e-5)


# -- the configuration --------------------------------------------------------------


def test_from_hf_reads_the_catalog_config_of_dots3_note_prev():
    """The published ``config`` (flat, as the catalog holds it), and the cut
    of the benchmark's configuration: 5 layers, 32 experts held of 256."""
    import json

    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(line) for line in open(path)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    (pub,) = [r["config"] for r in rows if r["name"] == "dots3-note-prev"]
    d = VLMConfig.from_hf(pub).decoder
    assert d.latent and d.layers == 46 and d.layers_of(FULL_ATTENTION) == 13
    assert (d.latent_full.heads, d.latent_full.kv_lora, d.latent_full.nope, d.latent_full.rope) == (128, 512, 128, 64)
    assert (d.latent_window.heads, d.latent_window.kv_lora, d.latent_window.nope, d.latent_window.v_dim) == (64, 1024, 192, 128)
    assert d.latent_full.rope_theta == 8e7 and d.latent_window.rope_theta == 5e4
    assert (d.sliding_window, d.index_heads, d.index_head_dim, d.index_topk) == (513, 64, 128, 2048)
    assert (d.moe_experts, d.moe_top_k, d.moe_held, d.moe_scoring, d.moe_select_bias) == (256, 8, (0, 256), "sigmoid", True)
    assert d.moe_dense_layers == (0,) and not d.is_moe_layer(0) and d.is_moe_layer(1)
    assert d.moe_shared_intermediate == 1536 and not d.moe_shared_gated and d.latent_rescale
    cut = dict(pub, num_hidden_layers=5, layer_types=pub["layer_types"][:5], n_routed_experts=32,
               ep_size=8, ep_rank=0, vocab_size=19008)
    c = VLMConfig.from_hf({"text_config": cut, "vision_config": {}, "image_token_index": 19000}).decoder
    assert c.layers == 5 and c.layer_types == ("full_attention",) * 2 + ("sliding_attention",) * 3
    assert c.moe_experts == 256 and c.moe_held == (0, 32) and c.vocab_size == 19008


def test_page_bytes_count_what_each_kind_of_layer_holds():
    from lumen_tpu.models.vlm.paged_kv import RowState

    cfg = VLMConfig.from_hf(tiny_config())
    # two full layers: (16 + 8 latent and rope, 16 index key) values a token
    assert RowState(cfg).page_bytes(PAGE, 2) == 2 * PAGE * (16 + 8 + 16) * 2
    # two window layers: 32 + 8 values a token, in their own id space
    assert RowState(cfg).window_page_bytes(PAGE, 2) == 2 * PAGE * (32 + 8) * 2
    # a row holds its window (5) grown by a block (8), at the worst alignment: 4 pages
    assert WindowPages.row_pages(5, PAGE, 8) == 4
    assert window_pool_pages(cfg, PAGE, 4, 8) == 4 * 4 + 1


# -- through the manager: hub settings, lane, scheduler, pool, counters ------------


@pytest.fixture(scope="module")
def latent_mgr(tmp_path_factory):
    """The rehearsal-size ``dots3_note`` captioner (window 33, top-k 64, 4 of
    16 experts held), written by the benchmark's own writer from HF-named
    tensors, behind the manager at ``max_seq`` 2,304."""
    import json
    import os

    from benchmark import cells, weights
    from lumen_tpu.models.vlm import VLMManager

    entry = cells._read_json(os.path.join(cells.HERE, "configs", "rehearsal-tiny-dots3.json"))["models"]["vlm"]
    root = str(tmp_path_factory.mktemp("latent"))
    name = weights.ensure_model_dir(root, "rehearsal-tiny-dots3", "vlm", entry)
    mgr = VLMManager(
        os.path.join(root, "models", name), dtype="float32", max_seq=2304, max_new_cap=512,
        gen_slots=2, gen_block=4,
    )
    mgr.initialize()
    yield mgr, entry, os.path.join(root, "models", name)
    mgr.close()


def _gauges(sched) -> dict:
    from lumen_tpu.utils.metrics import metrics

    return metrics.snapshot()["gauges"][f"vlm-continuous:{sched.name}"]


def test_a_max_seq_over_2048_continues_the_prompt_ladder(latent_mgr):
    mgr, _, _ = latent_mgr
    # 2304 - 16 image tokens - 512 decode cap = 1776 -> 1664 after the 1024 of the default ladder
    assert mgr.prefill_buckets == [64, 128, 256, 512, 1024, 1664]
    assert mgr.generator.max_seq == 2304 and mgr._continuous.kv.max_pages == 2304 // 64
    assert mgr._continuous.page_size == 64 and mgr._continuous.kv.window is not None


def test_a_long_max_seq_warms_the_longest_bucket_and_the_default_a_short_caption(latent_mgr, monkeypatch):
    """``max_seq`` 2,304 was configured past the default: the warm-up's
    prompt lands in the last prefill bucket (1,024 < n <= 1,664), so the boot
    compiles what the longest rows run; at the default length it says "hi"."""
    from lumen_tpu.models.vlm import ChatMessage

    mgr, _, _ = latent_mgr
    text = mgr._warmup_text()
    n = len(mgr._encode_prompt([ChatMessage(role="user", content=text)], True, True))
    assert 1024 < n <= 1664 and mgr._bucket_len(n) == 1664
    monkeypatch.setattr(mgr, "max_seq", 2048)
    assert mgr._warmup_text() == "hi"


def test_the_served_tokens_are_the_references_and_the_counters_count(latent_mgr):
    """A 300-word prompt through the chunked-prefill lane (the lane's chunk
    is 320, an eighth of ``max_seq`` 2,304 in whole pages; the 512-token
    bucket goes as two even chunks of 256), then
    24 greedy tokens in blocks of 4: every token is the plain reference's
    first choice at its position, window pages were freed, the indexer ran,
    and the expert layers counted the held share of what they routed."""
    from lumen_tpu.models.vlm import ChatMessage

    mgr, entry, model_dir = latent_mgr
    sched = mgr._continuous
    before = _gauges(sched)
    words = np.random.default_rng(8).integers(4, 2000, 300)
    out = mgr.generate(
        [ChatMessage(role="user", content=" ".join(f"w{w}" for w in words))], max_new_tokens=24
    )
    after = _gauges(sched)
    assert len(out.tokens) == 24 or out.finish_reason == "eos_token"
    t = entry["config"]["text_config"]
    ck = ref.plain.Checkpoint(model_dir)
    ids = np.asarray([1, *words, 2, *out.tokens[:-1]])  # role_user w.. role_assistant, then what was served
    with jax.default_matmul_precision("highest"):
        x = ck.get("model.embed_tokens.weight")[jnp.asarray(ids)][None]
        for i in range(t["num_hidden_layers"]):
            x = ref.decoder_layer(x, ref.layer_params(ck, t, i, None), t, i)
        logits = ref.rms_norm(x[0], ck.get("model.norm.weight"), t["rms_norm_eps"]) @ ck.get("lm_head.weight").T
    at = np.arange(len(out.tokens)) + len(words) + 1
    picked = np.asarray(logits)[at, np.asarray(out.tokens)]
    gap = (np.asarray(logits)[at].max(axis=1) - picked) / np.asarray(logits)[at].std(axis=1)
    assert gap.max() < 1e-3, gap
    d = {k: after[k] - before.get(k, 0) for k in after if isinstance(after[k], (int, float))}
    # the names the benchmark's readers match, and what they say
    assert sched.prefill_chunk == 320 and d["prefill_chunks_run"] == 2 and d["admitted"] == 1
    # 302 prompt tokens + 24: window layers hold at most (33 + 4 + 64 - 2) // 64 + 1 = 2 pages of 64
    assert d["window_pages_freed"] == (302 + 23 - 33 + 1) // 64 and after["window_pages_live"] == 0
    # what was dispatched through the indexer, in both full layers: two 256-token chunks against
    # the 640-slot scratch, six blocks of 2 slots x 4 steps against 8 pages of 64 (all over top-k 64)
    assert d["indexer_rows"] == 2 * (2 * 256 + 6 * 2 * 4)
    assert d["indexer_keys_scored"] == 2 * (2 * 256 * 640 + 6 * 2 * 4 * 512)
    assert d["moe_layer_calls"] == 4 * (2 + 24)  # four expert layers: two chunks, six blocks of four steps
    assert 0 < d["moe_tokens_held"] < d["moe_tokens_routed"]
    assert d["moe_experts_touched"] <= 4 * d["moe_layer_calls"]


def test_the_device_counts_one_block_of_expert_calls_and_the_host_keeps_the_totals(latent_mgr):
    """The expert layers' sums on the device are int32 and would wrap after
    hours of serving: a block hands its sum over (``moe_block``) and zeroes
    ``moe_stats``; the gauges read the scheduler's int64 totals."""
    from lumen_tpu.models.vlm import ChatMessage

    mgr, _, _ = latent_mgr
    sched = mgr._continuous
    before = _gauges(sched)["moe_layer_calls"]
    out = mgr.generate([ChatMessage(role="user", content="w7 w8 w9")], max_new_tokens=8)
    assert len(out.tokens) == 8 or out.finish_reason == "eos_token"
    assert sched.moe_stats.dtype == np.int64
    # one-shot prefill, then two blocks of four steps, four expert layers each
    assert _gauges(sched)["moe_layer_calls"] - before == 4 * (1 + 8)
    assert np.asarray(sched.pool["moe_stats"]).tolist() == [0, 0, 0, 0]
    assert int(sched.moe_block[3]) == 4 * 4  # the last block's calls alone


def test_backend_settings_carry_max_seq_to_the_manager(monkeypatch):
    """``backend_settings.max_seq`` is a field of the configuration, and the
    service hands it to the manager (none given: the manager's default)."""
    from lumen_tpu.core.config import BackendSettings
    from lumen_tpu.serving.services import vlm_service

    assert BackendSettings().max_seq is None and BackendSettings(max_seq=4608).max_seq == 4608
    with pytest.raises(ValueError):
        BackendSettings(max_seq=64)
    seen = {}

    class Manager:
        def __init__(self, model_dir, **kw):
            seen.update(kw)

        def initialize(self):
            pass

    monkeypatch.setattr(vlm_service, "VLMManager", Manager)
    monkeypatch.setattr(vlm_service, "require_executable_runtime", lambda mc: None)

    class Cfg:
        models = {"vlm": type("M", (), {"model": "x/y"})()}

    for bs, want in ((BackendSettings(max_seq=4608), 4608), (BackendSettings(), None)):
        seen.clear()
        Cfg.backend_settings = bs
        vlm_service.VlmService.from_config(Cfg, "/nowhere")
        assert seen.get("max_seq") == want


def test_the_table_ladders_last_rung_is_the_whole_table():
    """72 pages a row (4,608 tokens of 64): rows that need 33..72 pages all
    step under the one 72-page program; a power-of-two table keeps its
    ladder as it was."""
    from lumen_tpu.models.vlm.continuous import ContinuousScheduler

    class Kv:
        def __init__(self, max_pages):
            self.max_pages, self.page_size, self.window = max_pages, 64, None

        def pages_for(self, tokens):
            return max(1, -(-tokens // self.page_size))

    def bucket(max_pages, need_tokens):
        s = ContinuousScheduler.__new__(ContinuousScheduler)
        s.kv, s.block, s._slots = Kv(max_pages), 8, {0: object()}
        s._spec_active = lambda: False
        s._ensure_growth = lambda horizon=None: None
        s._row_need = lambda slot, horizon=None: need_tokens
        return s._plan_block()[2]

    assert [bucket(72, n * 64) for n in (1, 2, 3, 16, 32, 33, 64, 72)] == [1, 2, 4, 16, 32, 72, 72, 72]
    assert [bucket(128, n * 64) for n in (1, 3, 33, 64, 65, 128)] == [1, 4, 64, 64, 128, 128]


def test_a_prompts_chunks_are_even_and_its_tail_is_padded_to_one_program(latent_mgr):
    """1,100 words go in the 1,664-token bucket: six chunks of 320 whose last
    holds 64 tokens and is padded to 320, so one chunk program serves them
    all; the tokens are still the reference's."""
    from lumen_tpu.models.vlm import ChatMessage

    mgr, entry, model_dir = latent_mgr
    sched = mgr._continuous
    shapes = []
    inner = sched.gen._prefill_chunk

    def spy(params, caches, embeds, *rest):
        shapes.append(int(embeds.shape[1]))
        return inner(params, caches, embeds, *rest)

    sched.gen._prefill_chunk = spy
    try:
        words = np.random.default_rng(9).integers(4, 2000, 1100)
        out = mgr.generate(
            [ChatMessage(role="user", content=" ".join(f"w{w}" for w in words))], max_new_tokens=6
        )
    finally:
        sched.gen._prefill_chunk = inner
    # the live prompt (1,102 tokens) ends in the fourth chunk: the lane stops there
    assert shapes == [320] * 4 and len(out.tokens) == 6
    t = entry["config"]["text_config"]
    ck = ref.plain.Checkpoint(model_dir)
    ids = np.asarray([1, *words, 2, *out.tokens[:-1]])
    with jax.default_matmul_precision("highest"):
        x = ck.get("model.embed_tokens.weight")[jnp.asarray(ids)][None]
        for i in range(t["num_hidden_layers"]):
            x = ref.decoder_layer(x, ref.layer_params(ck, t, i, None), t, i)
        logits = np.asarray(
            ref.rms_norm(x[0], ck.get("model.norm.weight"), t["rms_norm_eps"]) @ ck.get("lm_head.weight").T
        )
    at = np.arange(len(out.tokens)) + len(words) + 1
    gap = (logits[at].max(axis=1) - logits[at, np.asarray(out.tokens)]) / logits[at].std(axis=1)
    assert gap.max() < 1e-3, gap


def test_a_short_tail_is_padded_and_serves_what_the_unpadded_tail_serves(latent_mgr):
    """An image and 450 words: 527 merged positions are two chunks of 320,
    the second holding 207 and padded; the tokens are those of the same
    request with its tail left short (two programs)."""
    from lumen_tpu.models.vlm import ChatMessage
    from tests.test_vlm import png_bytes

    mgr, _, _ = latent_mgr
    sched = mgr._continuous
    shapes = []
    inner = sched.gen._prefill_chunk

    def spy(params, caches, embeds, *rest):
        shapes.append(int(embeds.shape[1]))
        return inner(params, caches, embeds, *rest)

    words = " ".join(f"w{w}" for w in np.random.default_rng(10).integers(4, 2000, 450))
    msgs = [ChatMessage(role="user", content="<image> " + words)]
    sched.gen._prefill_chunk = spy
    try:
        padded = mgr.generate(msgs, image_bytes=png_bytes(64, seed=3), max_new_tokens=8)
        sched._even_chunks = False
        short = mgr.generate(msgs, image_bytes=png_bytes(64, seed=3), max_new_tokens=8)
    finally:
        sched._even_chunks = True
        sched.gen._prefill_chunk = inner
    assert shapes == [320, 320, 320, 207]
    assert padded.tokens == short.tokens and len(padded.tokens) == 8


# -- what a latent decoder refuses, so that it is never silently wrong ---------------


def _new_scheduler(mgr):
    from lumen_tpu.models.vlm.continuous import ContinuousScheduler

    return ContinuousScheduler(mgr.generator, mgr.params, slots=2, block=4, name="latent-refusals")


def _refuse_prefix_cache(mgr, monkeypatch):
    monkeypatch.setenv("LUMEN_VLM_PREFIX_BYTES", str(1 << 20))
    _new_scheduler(mgr)


def _refuse_speculation(mgr, monkeypatch):
    monkeypatch.setenv("LUMEN_VLM_SPEC_K", "2")
    _new_scheduler(mgr)


def _spill_tier_is_off(mgr, monkeypatch):
    monkeypatch.setenv("LUMEN_VLM_SPILL_BYTES", str(1 << 20))
    sched = _new_scheduler(mgr)
    try:
        assert sched._spill_budget == 0 and mgr._continuous._spill_budget == 0
    finally:
        sched.close()


_ONE = jnp.zeros((1,), jnp.int32)


def _refuse_reference_loop(mgr, monkeypatch):
    hidden = mgr.cfg.decoder.hidden_size
    mgr.generator.generate(
        mgr.params, jnp.zeros((1, 4, hidden)), jnp.arange(4)[None], _ONE + 4, jnp.zeros((1, 4), jnp.int32),
        jax.random.PRNGKey(0), max_new_tokens=2,
    )


def _refuse_export_row(mgr, monkeypatch):
    mgr.generator._export_row({"caches": _ONE}, 0, _ONE)


def _refuse_resume(mgr, monkeypatch):
    mgr.generator._resume({"caches": _ONE}, 0, _ONE, _ONE, *([_ONE] * 9))


def _refuse_seed_prefix(mgr, monkeypatch):
    mgr.generator._seed_prefix([{"k": _ONE + 0}], [{"k": _ONE}], _ONE)


def _refuse_verify(mgr, monkeypatch):
    mgr.generator._verify(mgr.params, {"cur_tok": _ONE}, _ONE[None], jax.random.PRNGKey(0), _ONE[None], _ONE, width=2)


def _refuse_admit_shared(mgr, monkeypatch):
    mgr._continuous.kv.admit_shared(0, [1], 100)


def _refuse_admit_exact(mgr, monkeypatch):
    mgr._continuous.kv.admit_exact(0, 1)


@pytest.mark.parametrize(
    "attempt, says",
    [
        (_refuse_prefix_cache, "LUMEN_VLM_PREFIX_BYTES"),
        (_refuse_speculation, "LUMEN_VLM_SPEC_K"),
        (_spill_tier_is_off, None),  # not raised: switched off at boot, a preempted row restarts from its prompt
        (_refuse_reference_loop, "the fused generate program"),
        (_refuse_export_row, "the spill tier's export"),
        (_refuse_resume, "the spill tier's resume"),
        (_refuse_seed_prefix, "seeding a scratch from a cached prefix"),
        (_refuse_verify, "speculative verify"),
        (_refuse_admit_shared, "shared prefix cannot be attached to window layers"),
        (_refuse_admit_exact, "does not export window layers' pages"),
    ],
    ids=["prefix-cache", "spec-k", "spill-off", "reference-loop", "export-row", "resume", "seed-prefix", "verify",
         "admit-shared", "admit-exact"],
)
def test_what_shares_or_exports_a_latent_row_is_refused(latent_mgr, monkeypatch, attempt, says):
    """Window layers free the pages a shared prefix or an exported row would
    need, so each of these raises (or, for the spill tier, is switched off at
    boot) rather than serve a row with holes in it."""
    mgr, _, _ = latent_mgr
    if says is None:
        attempt(mgr, monkeypatch)
    else:
        with pytest.raises(NotImplementedError, match=says):
            attempt(mgr, monkeypatch)
    assert mgr._continuous.kv.stats().pages_live == 0  # nothing was granted on the way


def _no_window_config() -> dict:
    """A latent decoder whose every layer is full and has no indexer: it
    keeps every page of a row."""
    from tests.test_vlm_axk1 import tiny_config as no_window

    return no_window()


def _drafting_config() -> dict:
    """A latent decoder with an indexer in every layer and a multi-token
    prediction layer (GLM-5's kind): it keeps every page of a row and
    verifies its own drafts inside the decode step."""
    from tests.test_vlm_glm5 import tiny_config as drafting

    return drafting()


@pytest.mark.parametrize(
    "make, indexer_layers, reason",
    [
        (tiny_config, 2, "window layers free the pages a shared prefix would need"),
        (_no_window_config, 0, "do not read or write latent leaves yet"),
        (_drafting_config, 4, "do not read or write latent leaves yet"),
    ],
    ids=["window-and-indexer", "every-key-every-layer", "indexer-every-layer-drafting"],
)
def test_each_latent_decoder_is_refused_for_a_reason_that_is_true_of_it(make, indexer_layers, reason):
    """``RowState.refuse`` names window layers only where the decoder has
    them; a latent decoder without any keeps all its pages and is refused
    because nothing that shares or exports rows handles latent leaves yet:
    the prefix cache, the spill tier, the host's n-gram verify turns and
    migration, each named; what such a decoder does verify, its own
    multi-token prediction drafts inside the decode step, is said beside
    them. ``indexer_layers`` counts only layers that have an indexer (a
    multi-token prediction layer among them)."""
    from lumen_tpu.models.vlm.paged_kv import RowState

    rows = RowState(VLMConfig.from_hf(make()))
    assert rows.indexer_layers == indexer_layers and rows.full_latent_layers >= 2 and not rows.shareable
    assert (rows.window_layers > 0) == ("window" in reason)
    for what in ("LUMEN_VLM_PREFIX_BYTES", "the spill tier's export", "admitting a migrated row", "speculative verify"):
        with pytest.raises(NotImplementedError, match=reason) as err:
            rows.refuse(what)
        assert what in str(err.value) and "latent decoder" in str(err.value)
        if not rows.window_layers:
            said = str(err.value)
            for refused in ("prefix cache", "spill tier", "n-gram verify turns", "migration wire format"):
                assert refused in said, said
            assert "multi-token prediction drafts are verified inside the decode step" in said


# -- the manager's behaviour with the second kind of row state ------------------------


def _ask(mgr, words: str, budget: int):
    from lumen_tpu.models.vlm import ChatMessage

    return mgr.generate([ChatMessage(role="user", content=words)], max_new_tokens=budget)


def _together(*calls):
    """Run the calls at once, one thread each; their results in order."""
    barrier, out = threading.Barrier(len(calls)), {}

    def run(i, call):
        barrier.wait()
        out[i] = call()

    threads = [threading.Thread(target=run, args=(i, c)) for i, c in enumerate(calls)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(out) == len(calls)
    return [out[i] for i in range(len(calls))]


class TestLatentRowsThroughTheManager:
    """``test_vlm_batched.TestBatchedGeneration``'s five behaviours, for rows
    of latent and window pages: the one engine serves both kinds."""

    PROMPTS = ("w11 w12 w13", "w21 w22 w23 w24 w25 w26", "w31", "w41 w42 w43 w44")

    def test_concurrent_greedy_matches_serial(self, latent_mgr):
        mgr, _, _ = latent_mgr
        serial = [_ask(mgr, p, 8) for p in self.PROMPTS]
        before = _gauges(mgr._continuous)
        got = _together(*[lambda p=p: _ask(mgr, p, 8) for p in self.PROMPTS])
        after = _gauges(mgr._continuous)
        assert [g.tokens for g in got] == [s.tokens for s in serial]
        assert after["admitted"] - before["admitted"] == len(self.PROMPTS)
        assert after["blocks_run"] - before["blocks_run"] < after["rows_stepped"] - before["rows_stepped"]
        assert after["pages_live"] == 0 and after["window_pages_live"] == 0

    def test_a_row_stops_at_its_own_budget(self, latent_mgr):
        mgr, _, _ = latent_mgr
        long = _ask(mgr, "w11 w12 w13", 8)
        short, again = _together(lambda: _ask(mgr, "w11 w12 w13", 2), lambda: _ask(mgr, "w11 w12 w13", 8))
        assert again.tokens == long.tokens
        assert short.tokens == long.tokens[: len(short.tokens)]
        assert len(short.tokens) == 2 or short.finish_reason == "eos_token"

    def test_a_zero_budget_row_emits_nothing(self, latent_mgr):
        mgr, _, _ = latent_mgr
        none, some = _together(lambda: _ask(mgr, "w11 w12 w13", 0), lambda: _ask(mgr, "w11 w12 w13", 8))
        assert none.tokens == [] and len(some.tokens) > 0

    def test_two_prompt_buckets_are_both_served(self, latent_mgr):
        mgr, _, _ = latent_mgr
        long_prompt = " ".join(f"w{100 + i}" for i in range(90))  # past the 64-token bucket
        a, b = _together(lambda: _ask(mgr, "w11", 4), lambda: _ask(mgr, long_prompt, 4))
        assert a.input_tokens <= 64 < b.input_tokens <= 128
        assert len(a.tokens) > 0 and len(b.tokens) > 0

    def test_a_streams_text_is_generates(self, latent_mgr):
        from lumen_tpu.models.vlm import ChatMessage

        mgr, _, _ = latent_mgr
        msgs = [ChatMessage(role="user", content="w11 w12 w13")]
        chunks, whole = _together(
            lambda: list(mgr.generate_stream(msgs, max_new_tokens=6)), lambda: _ask(mgr, "w11 w12 w13", 6)
        )
        assert chunks[-1].is_final
        assert "".join(c.text for c in chunks if not c.is_final).strip() == whole.text
