"""The ``glm_moe_dsa`` decoder (GLM-5: latent attention with an indexer in
every layer, rotation in interleaved pairs, sigmoid routing over a held
share of the bank, and a multi-token prediction layer that drafts inside the
decode step) against its plain reference, at a small size on the CPU:
prefill in lane chunks, then paged decode through ``ContinuousScheduler``
with a verify window of two at every step, agree in LOGITS with the
reference's one full forward, and its drafts with the reference's
multi-token prediction layer; the two-query kernels against their dense
forms; interleaved rotation against the reference's pairs; the shares of an
expert-parallel deployment against the uncut layer; what a drawing row, an
end of sequence and a budget in the middle of a pair do; what a row keeps."""

from __future__ import annotations

import json
import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.references import vlm_glm5 as ref
from benchmark.tensors import glm5 as listing
from lumen_tpu.models.vlm.continuous import ContinuousScheduler
from lumen_tpu.models.vlm.convert import convert_vlm_checkpoint
from lumen_tpu.models.vlm.generate import Generator
from lumen_tpu.models.vlm.modeling import FULL_ATTENTION, VLMConfig, VLMModel, rope_rotate
from lumen_tpu.models.vlm.paged_kv import RowState
from lumen_tpu.ops import latent_attention as la
from tests.test_vlm_hybrid import BLOCK, CHUNK, PAGE, SLOTS, DictCheckpoint, request


def tiny_config(**over) -> dict:
    """A dense layer and two expert layers, then the multi-token prediction
    layer; four of eight experts held, top-3; an indexer keeping 12 keys, so
    the 37-token prompts below are cut in every layer."""
    t = {
        "model_type": "glm_moe_dsa", "hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "intermediate_size": 96, "vocab_size": 96, "num_attention_heads": 4, "num_key_value_heads": 4,
        "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "rope_parameters": {"rope_theta": 10000, "rope_type": "default"}, "rope_interleave": True,
        "index_n_heads": 2, "index_head_dim": 16, "index_topk": 12, "indexer_rope_interleave": True,
        "n_routed_experts": 4, "ep_size": 2, "ep_rank": 1, "n_group": 1, "topk_group": 1, "n_shared_experts": 1,
        "num_experts_per_tok": 3, "moe_intermediate_size": 32, "moe_layer_freq": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "rms_norm_eps": 1e-5, "tie_word_embeddings": False, "max_position_embeddings": 4096,
        "num_nextn_predict_layers": 1, "bos_token_id": 4, "eos_token_id": 5, "pad_token_id": 4,
    }
    t.update(over)
    return {
        "text_config": t, "image_token_index": 6,
        "vision_config": {"image_size": 32, "patch_size": 16, "hidden_size": 32,
                          "num_hidden_layers": 1, "num_attention_heads": 2},
    }


def random_state(cfg: dict, seed: int = 0) -> dict[str, np.ndarray]:
    """A checkpoint under the listing's names: N(0, 0.3) so that attention,
    the indexer and the router have something to tell apart."""
    rng = np.random.default_rng(seed)
    state = {}
    for name, shape in listing.tensors(cfg):
        draw = rng.standard_normal(shape).astype(np.float32)
        norm = name.endswith(("norm.weight", "norm1.weight", "norm2.weight"))
        state[name] = 1.0 + 0.1 * draw if norm else 0.3 * draw
    return state


def reference(cfg: dict, state: dict, ids) -> tuple[np.ndarray, np.ndarray]:
    """The plain reference's logits at every position of ``ids`` [S], and its
    multi-token prediction layer's (position ``i`` over the hidden state
    there and the embedding at ``i + 1``; the last position's is the first's,
    rolled round, as the program's cacheless forward has it)."""
    t, ck = cfg["text_config"], DictCheckpoint(state)
    n = t["num_hidden_layers"]
    with jax.default_matmul_precision("highest"):
        x0 = ck.get("model.embed_tokens.weight")[jnp.asarray(ids)][None]
        x = x0
        for i in range(n):
            x = ref.decoder_layer(x, ref.layer_params(ck, t, i, None), t, i)
        head = ck.get("lm_head.weight")
        logits = ref.rms_norm(x, ck.get("model.norm.weight"), t["rms_norm_eps"]) @ head.T
        p = ref.layer_params(ck, t, n, None)
        y = ref.mtp_layer(x, jnp.roll(x0, -1, axis=1), p, t)
        drafts = ref.rms_norm(y, p["head_norm"], t["rms_norm_eps"]) @ head.T
        return np.asarray(logits[0]), np.asarray(drafts[0])


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


PROMPT = np.random.default_rng(1).integers(7, 96, 37)
#: logits agree to this share of their own spread: float32 against float32
#: differs by summation order alone (~1e-5 read); the same program and weights
#: in bfloat16 read 0.1 and more (weights at N(0, 0.3) make these logits
#: sensitive), so a tenth of a percent tells the two apart with room
TOLERANCE = 1e-3


class Tap(Generator):
    """``Generator`` whose programs report, in order: the logits each token
    was drawn or taken from (a step's two positions), and the draft logits
    of each decode step's pass of the multi-token prediction layer (a
    prefill chunk's pass is not reported). With ``oracle`` (the tokens a
    greedy run serves, and the indices of the ones to get wrong) a step's
    drafts are those tokens, so the verify step accepts them: the layer
    still runs and writes its cache."""

    def __init__(self, *a, oracle=None, prompt: int = 0, **kw):
        self.events: list[tuple] = []
        self.oracle, self.prompt = oracle, prompt
        super().__init__(*a, **kw)

    def _sample_next(self, rng, logits, *rest):
        jax.debug.callback(lambda l: self.events.append(("logits", np.asarray(l))), logits, ordered=True)
        return super()._sample_next(rng, logits, *rest)

    def _greedy_next(self, logits, *rest):
        jax.debug.callback(lambda l: self.events.append(("logits", np.asarray(l))), logits, ordered=True)
        return super()._greedy_next(logits, *rest)

    def _mtp_pass(self, params, hidden, next_embeds, positions, cache, offset, kv_len, block_tables=None, valid=None):
        logits, cache, stats = super()._mtp_pass(
            params, hidden, next_embeds, positions, cache, offset, kv_len, block_tables, valid
        )
        if block_tables is None:
            return logits, cache, stats
        if self.oracle is not None:
            served, wrong = self.oracle
            at = positions + 2 - self.prompt  # position i drafts the token at i + 2
            want = jnp.asarray(list(served) + [0] * 64)[jnp.clip(at, 0, len(served) + 63)]
            want = jnp.where(jnp.isin(at, jnp.asarray(list(wrong) + [-1])), (want + 1) % logits.shape[-1], want)
            logits = jax.nn.one_hot(want, logits.shape[-1], dtype=logits.dtype) * 100.0
        jax.debug.callback(lambda d: self.events.append(("step", np.asarray(d))), logits, ordered=True)
        return logits, cache, stats

    def row(self, served: int) -> tuple[dict, dict]:
        """One request's ``{token index: the logits it was drawn from}`` and
        ``{token index: the draft logits that drafted it}``, walked from the
        events as the programs run: the install's pass of the layer drafts
        token 1; a step emits the pending token, and the draft too where the
        first position's argmax is the draft, and drafts again from the
        position of its last emitted token."""
        logits: dict[int, np.ndarray] = {}
        drafts: dict[int, np.ndarray] = {}
        e, draft = 0, None
        evs = iter(self.events)
        for ev in evs:
            if ev[0] == "logits" and not logits:
                logits[0] = ev[1][0]
            elif ev[0] == "step" and draft is None:  # the install's pass
                drafts[1] = ev[1][0, 0]
                draft = int(np.argmax(drafts[1]))
            elif ev[0] == "logits":  # a step: L0, L1, then the drafts
                l0, l1, d = ev[1][0], next(evs)[1][0], next(evs)
                assert d[0] == "step"
                if e >= served:
                    continue
                if e + 1 < served:
                    logits.setdefault(e + 1, l0)
                taken = e + 1 < served and int(np.argmax(l0)) == draft
                if taken and e + 2 < served:
                    logits.setdefault(e + 2, l1)
                behind = int(taken)
                e += 2 if taken else 1
                drafts.setdefault(e + 1, d[1][0, behind])
                draft = int(np.argmax(d[1][0, behind]))
        return logits, drafts


def serve(vcfg, model, params, ids, max_new: int, span: int, dtype=jnp.float32, name="glm5-alone",
          oracle=None, sample: bool = False):
    """One request through a scheduler of its own (lane chunks of 16, pages
    of 4, blocks of 2): its tokens, the logits and draft logits behind them,
    the scheduler's gauges, the request."""
    if dtype != jnp.float32:
        params = jax.tree.map(lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
    gen = Tap(model, vcfg, max_seq=64, max_new_cap=16, cache_dtype=dtype, oracle=oracle, prompt=len(ids))
    sched = ContinuousScheduler(
        gen, params, slots=SLOTS, block=BLOCK, name=name, page_size=PAGE, prefill_chunk=CHUNK
    )
    sched._rng = jax.random.PRNGKey(11)  # entropy-seeded otherwise: a drawing row's tokens would vary by run
    try:
        req = request(model, params, ids, max_new, span)
        if sample:
            req.do_sample, req.temperature = True, 1.0
        toks, n_gen, _ = sched.submit(req).result(timeout=300)
        gauges = sched._gauge_fn()
    finally:
        sched.close()
    toks = [int(t) for t in np.asarray(toks)[:n_gen]]
    rows, drafts = gen.row(len(toks))
    return toks, rows, drafts, gauges, req, sched


def _worst(want: np.ndarray, rows: dict, at) -> float:
    """Largest gap between the program's rows and the reference's, in units
    of the reference's spread; row ``k`` against the reference at ``at(k)``."""
    return max(float(np.abs(np.asarray(r, np.float32) - want[at(k)]).max()) for k, r in rows.items()) / want.std()


def test_full_forward_and_its_drafts_match_the_plain_reference(built):
    cfg, state, vcfg, model, params = built
    (got, drafts), _ = model.apply(
        {"params": params}, jnp.asarray(PROMPT)[None], with_drafts=True, mutable=["moe_stats"]
    )
    want, want_drafts = reference(cfg, state, PROMPT)
    assert np.abs(np.asarray(got[0]) - want).max() < TOLERANCE * want.std()
    assert np.abs(np.asarray(drafts[0]) - want_drafts).max() < TOLERANCE * want_drafts.std()


def test_chunked_prefill_then_decode_verifying_a_draft_every_step_agrees_in_logits(built):
    """37 live tokens in a 48-token span (two full lane chunks and a padded
    third; every layer's indexer keeps 12 of the keys), then blocks of two
    verify steps of two positions: the logits of every emitted token against
    the reference's full forward, and the draft logits behind every draft
    against the reference's multi-token prediction layer at the position
    that drafted it; and the control: the same program and weights in
    bfloat16 fail both."""
    cfg, state, vcfg, model, params = built
    n = len(PROMPT)
    toks, rows, drafts, gauges, req, sched = serve(vcfg, model, params, PROMPT, 10, 48)
    assert sched.chunks_run == 3 and len(toks) == 10 and sorted(rows) == list(range(10))
    assert all(toks[k] == int(np.argmax(rows[k])) for k in range(10))
    want, want_drafts = reference(cfg, state, list(PROMPT) + toks)
    assert _worst(want, rows, lambda k: n - 1 + k) < TOLERANCE
    drafts = {k: d for k, d in drafts.items() if k < len(toks)}
    assert sorted(drafts) == sorted(req.mtp_drafts) and len(drafts) >= 5
    assert all(req.mtp_drafts[k] == int(np.argmax(d)) for k, d in drafts.items())
    assert _worst(want_drafts, drafts, lambda k: n + k - 2) < TOLERANCE
    # every step checked a draft while the row went on; at chance, none was taken
    assert gauges["mtp_drafted"] == len(drafts)
    low = serve(vcfg, model, params, PROMPT, 10, 48, dtype=jnp.bfloat16, name="glm5-bf16")
    low_want, low_drafts = reference(cfg, state, list(PROMPT) + low[0])
    assert _worst(low_want, low[1], lambda k: n - 1 + k) > 5 * TOLERANCE
    assert _worst(low_drafts, {k: d for k, d in low[2].items() if k < len(low[0])}, lambda k: n + k - 2) > 5 * TOLERANCE


def test_accepted_drafts_emit_two_tokens_a_step_and_the_same_tokens(built):
    """With drafts that are the greedy tokens (two of them wrong), a verify
    step emits the pending token and the draft where the draft is right, one
    token where it is wrong: the tokens are those of the run that accepted
    nothing, the second position's logits agree with the reference, and the
    tallies count what was checked and taken. The install's draft of token 1
    is checked by the first step; with drafts right the steps emit pairs
    (0-1, 2-...), and the wrong drafts of tokens 3 and 6 are checked ones."""
    cfg, state, vcfg, model, params = built
    n = len(PROMPT)
    plain_toks = serve(vcfg, model, params, PROMPT, 12, 48, name="glm5-plain")[0]
    toks, rows, _, gauges, req, _ = serve(
        vcfg, model, params, PROMPT, 12, 48, name="glm5-oracle", oracle=(plain_toks, (3, 6))
    )
    assert toks == plain_toks and sorted(rows) == list(range(12))
    want, _ = reference(cfg, state, list(PROMPT) + toks)
    assert _worst(want, rows, lambda k: n - 1 + k) < TOLERANCE
    taken = gauges["mtp_accepted"]
    assert gauges["mtp_drafted"] - taken == 2 and taken >= 4
    # fewer steps than tokens: 12 tokens, the first from prefill, the rest 1 or 2 a step
    assert gauges["blocks_run"] * BLOCK < 12
    assert req.mtp_drafts[3] == (plain_toks[3] + 1) % 96 and req.mtp_drafts[4] == plain_toks[4]
    assert req.mtp_drafts[1] == plain_toks[1]  # the install's draft, checked by the first step


def test_a_drawing_row_accepts_no_draft(built):
    """A row that draws its tokens takes none of the drafts, even drafts
    that are right: each step emits one token, as plain decoding does."""
    _, _, vcfg, model, params = built
    plain_toks = serve(vcfg, model, params, PROMPT, 10, 48, name="glm5-plain2")[0]
    toks, _, _, gauges, _, _ = serve(
        vcfg, model, params, PROMPT, 10, 48, name="glm5-draws", oracle=(plain_toks, ()), sample=True
    )
    assert len(toks) == 10 and gauges["mtp_drafted"] >= 8 and gauges["mtp_accepted"] == 0
    assert gauges["blocks_run"] * BLOCK >= 9


@pytest.mark.parametrize("stop", ["eos", "budget"])
def test_a_row_stopping_on_the_first_of_an_accepted_pair_emits_nothing_after_it(built, stop):
    """Every draft right (the install's too), so the steps emit pairs
    (tokens 0-1, 2-3, ...) and the first of a pair sits at an even index: an
    end-of-sequence token there, or the budget running out there, ends the
    row without the draft that the step would have taken."""
    _, _, vcfg, model, params = built
    plain_toks = serve(vcfg, model, params, PROMPT, 14, 48, name="glm5-plain3")[0]
    if stop == "eos":
        k = next(k for k in range(2, 14, 2) if plain_toks[k] not in plain_toks[:k])
        cfg, max_new = replace(vcfg, eos_token_id=plain_toks[k]), 14
    else:
        k, cfg, max_new = 4, vcfg, 5
    toks, _, _, gauges, req, _ = serve(
        cfg, model, params, PROMPT, max_new, 48, name=f"glm5-{stop}", oracle=(plain_toks, ())
    )
    assert toks == plain_toks[: k + 1]
    assert gauges["mtp_accepted"] == gauges["mtp_drafted"] == k // 2 and max(req.mtp_drafts) < len(toks)


def test_interleaved_rotation_equals_the_references_pairs():
    """``rope_rotate`` with ``interleave`` turns channels (2i, 2i + 1)
    together: its output is the reference's pairs laid out half-split (even
    channels, then odd), so every product of a query and a key is the
    reference's; without it the same values are paired i with i + d/2."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((1, 2, 11, 8)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((1, 2, 11, 8)), jnp.float32)
    pos = jnp.arange(11)[None]
    got = rope_rotate(x, pos, 1e4, interleave=True)
    want = ref.rope_pairs(x, 1e4, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(jnp.concatenate([want[..., 0::2], want[..., 1::2]], -1)), atol=1e-6)
    prod = lambda a, b: jnp.einsum("bhsd,bhtd->bhst", a, b)
    np.testing.assert_allclose(
        np.asarray(prod(got, rope_rotate(y, pos, 1e4, interleave=True))),
        np.asarray(prod(want, ref.rope_pairs(y, 1e4, True))), atol=1e-5,
    )
    np.testing.assert_allclose(np.asarray(rope_rotate(x, pos, 1e4)), np.asarray(ref.rope_pairs(x, 1e4, False)), atol=1e-6)
    assert not np.allclose(np.asarray(prod(got, got)), np.asarray(prod(rope_rotate(x, pos, 1e4), rope_rotate(x, pos, 1e4))))


def test_the_two_query_kernels_agree_with_their_dense_forms():
    """A verify window of two queries a row through the Pallas kernels
    (interpret mode): the indexer's scores against the dense sum over index
    heads, and the latent attention against the dense softmax over each
    query's own causal keys and own top-k, with a row whose two queries keep
    different keys; a done row (length 0) reads none."""
    rng = np.random.default_rng(4)
    b, h, c, r, page, maxp, j, di, topk = 3, 4, 32, 8, 16, 6, 2, 16, 20
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    pool_c, pool_r, pool_k = f(20, page, c), f(20, page, r), f(20, page, di)
    bt = jnp.asarray(rng.permutation(np.arange(1, 20))[: b * maxp].reshape(b, maxp), jnp.int32)
    lens = jnp.asarray([41, 70, 0], jnp.int32)  # the window's first query sees these
    qi, w = f(b, 2, j, di), f(b, 2, j)
    scores = la.indexer_scores_kernel(qi, w, pool_k, bt, interpret=True)
    keys = pool_k[bt].reshape(b, -1, di)
    dense = jnp.einsum("bwj,bwjs->bws", w, jax.nn.relu(jnp.einsum("bwjd,bsd->bwjs", qi, keys)))
    np.testing.assert_allclose(np.asarray(scores), np.asarray(dense), rtol=1e-5, atol=1e-4)
    slots = jnp.arange(maxp * page)
    ok = slots[None, None, :] < (lens[:, None] + jnp.arange(2)[None, :])[..., None]
    sel = la.topk_select(scores, ok, topk)
    assert not bool(jnp.all(sel[1, 0] == sel[1, 1]))  # the two queries keep different keys
    qc, qr = f(b, 2, h, c), f(b, 2, h, r)
    got = la.latent_paged_attention_kernel(qc, qr, pool_c, pool_r, bt, lens, jnp.zeros_like(lens), sel,
                                           scale=0.2, interpret=True)
    cs, rs = pool_c[bt].reshape(b, -1, c), pool_r[bt].reshape(b, -1, r)
    s = (jnp.einsum("bwhc,bsc->bwhs", qc, cs) + jnp.einsum("bwhr,bsr->bwhs", qr, rs)) * 0.2
    want = jnp.einsum("bwhs,bsc->bwhc", jax.nn.softmax(jnp.where(sel[:, :, None], s, -jnp.inf), -1), cs)
    np.testing.assert_allclose(np.asarray(got[:2]), np.asarray(want[:2]), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(  # the XLA path the CPU serves from is the same function
        np.asarray(la.latent_paged_attention_reference(qc, qr, pool_c, pool_r, bt, lens, jnp.zeros_like(lens), sel,
                                                       scale=0.2)[:2]),
        np.asarray(want[:2]), rtol=1e-4, atol=1e-4)


def test_the_shares_of_every_chip_add_up_to_the_uncut_layer():
    """Four chips of four experts (and eight of two): each routes over all
    sixteen by score + bias and computes its own experts' part; the parts,
    with the shared expert counted once, add up to the plain reference's
    whole layer."""
    from lumen_tpu.parallel.moe import MoEParams, moe_ffn

    rng = np.random.default_rng(5)
    g = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    e, d, f = 16, 16, 12
    bank = MoEParams(router=g(d, e), w_gate=g(e, d, f) * 0.3, w_up=g(e, d, f) * 0.3, w_down=g(e, f, d) * 0.3)
    x, bias = g(24, d), g(e) * 0.3
    t = {"num_experts_per_tok": 4, "norm_topk_prob": True, "routed_scaling_factor": 2.5, "n_routed_experts": e,
         "n_group": 1, "topk_group": 1, "rms_norm_eps": 1e-5}
    hf = lambda w: jnp.swapaxes(w, -1, -2)
    shared = tuple(g(*s) * 0.3 for s in ((f, d), (f, d), (d, f)))
    p = {"router_w": hf(bank.router), "select_bias": bias, "shared": shared,
         "experts": {i: (hf(bank.w_gate[i]), hf(bank.w_up[i]), hf(bank.w_down[i])) for i in range(e)}}
    with jax.default_matmul_precision("highest"):
        whole = ref.expert_layer(x, p, t, held=(0, e))
        for chips in (4, 8):
            n = e // chips
            total = ref.swiglu(x, *shared)
            for lo in range(0, e, n):
                share = MoEParams(bank.router, *(wt[lo:lo + n] for wt in (bank.w_gate, bank.w_up, bank.w_down)))
                y, _ = moe_ffn(share, x, k=4, capacity_factor=None, scoring="sigmoid", select_bias=bias,
                               routed_scale=2.5, held=(lo, lo + n), n_experts=e, with_stats=True)
                total = total + y
            np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=5e-5, rtol=5e-5)


# -- the configuration, the names, what a row keeps ------------------------------------


def _published() -> dict:
    """GLM-5's published config.json, as the benchmark's tests keep it."""
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark", "tests", "data", "glm5_published.json")) as f:
        return json.load(f)["config"]


def test_from_hf_reads_the_catalog_config_of_glm5():
    pub = _published()
    d = VLMConfig.from_hf(pub).decoder
    assert d.latent and d.layers == 78 and d.layer_types == (FULL_ATTENTION,) * 78 and d.mtp_layers == 1
    f = d.latent_full
    assert (f.heads, f.q_lora, f.kv_lora, f.nope, f.rope, f.v_dim, f.rope_theta) == (64, 2048, 512, 192, 64, 256, 1e6)
    assert f.rope_interleave and d.index_rope_interleave and f.rope_scaling is None and f.scale == 256 ** -0.5
    assert (d.index_heads, d.index_head_dim, d.index_topk) == (32, 128, 2048) and d.indexer
    assert d.latent_window is None and not d.latent_gate and not d.latent_rescale
    assert (d.moe_experts, d.moe_top_k, d.moe_scoring, d.moe_select_bias, d.moe_routed_scale) == (256, 8, "sigmoid", True, 2.5)
    assert (d.moe_n_group, d.moe_topk_group, d.moe_shared_intermediate) == (1, 1, 2048)
    assert d.moe_dense_layers == (0, 1, 2) and d.is_moe_layer(3) and d.is_moe_layer(78)
    assert d.cache_layers == 79 and d.layer_kind(78) == FULL_ATTENTION
    cut = dict(pub, num_hidden_layers=5, first_k_dense_replace=1, n_routed_experts=8, ep_size=32, vocab_size=19360)
    c = VLMConfig.from_hf({"text_config": cut, "vision_config": {}, "image_token_index": 19000}).decoder
    assert (c.layers, c.moe_experts, c.moe_held, c.vocab_size, c.moe_dense_layers) == (5, 256, (0, 8), 19360, (0,))
    with pytest.raises(NotImplementedError, match="multi-token prediction"):
        VLMConfig.from_hf({"text_config": dict(cut, num_nextn_predict_layers=2)})


def test_a_row_keeps_the_multi_token_prediction_layers_pages(built):
    """The multi-token prediction layer is one more full latent layer of the
    row: its latent, rope and index key values under the row's block table,
    counted in a page's bytes and in the pool's caches."""
    vcfg = built[2]
    rows = RowState(vcfg)
    assert rows.kinds == (RowState.LATENT,) * 4 and rows.full_latent_layers == rows.indexer_layers == 4
    assert rows.page_bytes(PAGE, 2) == 4 * PAGE * (16 + 8 + 16) * 2 and not rows.shareable
    cut = VLMConfig.from_hf({"text_config": dict(_published(), num_hidden_layers=5, n_routed_experts=8, ep_size=32)})
    assert RowState(cut).page_bytes(64, 2) == 64 * 6 * (512 + 64 + 128) * 2  # 8,448 B a token over six layers


def test_hf_names_convert_and_the_draft_layer_shares_the_embedding_and_head(built):
    cfg, state, vcfg, model, params = built
    mtp = params["decoder"]["layers_3"]
    assert {"enorm", "hnorm", "eh_proj", "head_norm", "attn", "mlp", "input_norm", "post_attn_norm"} <= set(mtp)
    np.testing.assert_array_equal(np.asarray(mtp["eh_proj"]["kernel"]), state["model.layers.3.eh_proj.weight"].T)
    assert sorted(mtp["attn"]) == sorted(params["decoder"]["layers_1"]["attn"])
    assert "index_k_norm" in mtp["attn"] and mtp["mlp"]["w_gate"].shape == (4, 64, 32)
    # a checkpoint's own copies of the embedding and the head under the layer's name are left aside
    extra = dict(state, **{"model.layers.3.embed_tokens.weight": state["model.embed_tokens.weight"],
                           "model.layers.3.shared_head.head.weight": state["lm_head.weight"]})
    again = convert_vlm_checkpoint(extra, None, tie_word_embeddings=False)
    assert set(again["decoder"]["layers_3"]) == set(mtp)


def test_the_route_gauge_names_the_verify_width(built):
    from lumen_tpu.utils.metrics import metrics

    _, _, vcfg, model, params = built
    serve(vcfg, model, params, PROMPT[:9], 3, 12, name="glm5-route")
    routes = metrics.snapshot()["gauges"]["attention-route"]
    assert routes.get("verify_width:2", 0) > 0, routes


@pytest.mark.parametrize("mtp", [1, 0], ids=["drafting", "plain"])
def test_the_lane_programs_compiled_ahead_are_the_ones_a_prompt_runs(mtp):
    """``ContinuousScheduler.precompile`` lowers a lane prompt's programs for
    what their call sites pass, weights placed on a mesh as the manager
    places them: a prompt of that span then runs its chunks, finish, install
    (the drafting decoder's with its first draft) and first decode block
    without compiling any of them again."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    cfg = tiny_config(num_nextn_predict_layers=mtp)
    vcfg = VLMConfig.from_hf(cfg)
    model = VLMModel(vcfg)
    init = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32), jnp.zeros((1, 32, 32, 3)))
    )["params"]
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    params = jax.device_put(
        convert_vlm_checkpoint(random_state(cfg), init, tie_word_embeddings=False),
        NamedSharding(mesh, PartitionSpec()),
    )
    gen = Generator(model, vcfg, max_seq=64, max_new_cap=16, cache_dtype=jnp.float32)
    sched = ContinuousScheduler(
        gen, params, slots=SLOTS, block=BLOCK, name=f"glm5-ahead-{mtp}", page_size=PAGE, prefill_chunk=CHUNK
    )
    programs = {f"jit({name})" for name in ("_prefill_chunk_impl", "_chunk_finish_impl", "_admit_impl",
                                            "_step_block_impl")}
    compiled: list[str] = []

    def listen(event: str, secs: float, fun_name: str = "", **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration" and fun_name in programs:
            compiled.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        req = request(model, params, PROMPT, 4, 48)
        req.embeds, req.length = jax.device_put((req.embeds, req.length), NamedSharding(mesh, PartitionSpec()))
        with ThreadPoolExecutor(4) as workers:
            for f in sched.precompile(48, PROMPT.size, len(PROMPT), req.max_new, workers):
                f.result()
        assert sorted(compiled) == sorted(programs)
        toks, n_gen, _ = sched.submit(req).result(timeout=300)
        assert sched.chunks_run == 3 and int(n_gen) == 4
        assert sorted(compiled) == sorted(programs)  # the prompt compiled none of them again
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
        sched.close()
