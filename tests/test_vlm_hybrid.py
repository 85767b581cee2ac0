"""The hybrid decoder (``model_type`` ``granitemoehybrid``: Mamba-2 layers
whose recurrent state lives beside the page pool, a grouped-query layer
without rotation, softmax-routed experts of which a share is held) against
its plain reference, at a small size on the CPU: the full forward; chunked
prefill with a padded last chunk and decode through ``ContinuousScheduler``
agree in LOGITS with the reference's one full forward; rows admitted at
different turns and a reused slot give the logits each gives alone; a done
row's state does not move; the shares of an expert-parallel deployment add up
to the uncut layer; the kernels against their twins; the HF names; the
refusals; and the Qwen2 and ``dots3_note`` programs as they were."""

from __future__ import annotations

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.references import vlm_granite as ref
from benchmark.tensors import granite as listing
from lumen_tpu.models.vlm.continuous import ContinuousScheduler, _Request
from lumen_tpu.models.vlm.convert import convert_vlm_checkpoint
from lumen_tpu.models.vlm.generate import Generator
from lumen_tpu.models.vlm.modeling import ATTENTION, MAMBA, MoEFFN, SwiGLU, VLMConfig, VLMModel
from lumen_tpu.models.vlm.paged_kv import PagedKVPool, RowState, WindowPages, window_pool_pages
from lumen_tpu.ops import ssm

PAGE, CHUNK, SLOTS, BLOCK = 4, 16, 2, 2


def tiny_config(**over) -> dict:
    t = {
        "model_type": "granitemoehybrid", "hidden_size": 32, "num_hidden_layers": 4,
        "layer_types": [MAMBA, ATTENTION, MAMBA, MAMBA],
        "num_attention_heads": 4, "num_key_value_heads": 2, "intermediate_size": 16,
        "shared_intermediate_size": 24, "num_local_experts": 4, "ep_size": 2, "ep_rank": 0,
        "num_experts_per_tok": 3, "vocab_size": 96,
        "mamba_n_heads": 8, "mamba_d_head": 8, "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_d_conv": 4,
        "mamba_expand": 2, "mamba_chunk_size": 8, "mamba_conv_bias": True, "mamba_proj_bias": False,
        "attention_bias": False, "attention_multiplier": 0.3, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "logits_scaling": 16, "position_embedding_type": "nope",
        "rms_norm_eps": 1e-5, "tie_word_embeddings": True, "max_position_embeddings": 4096,
        "bos_token_id": 4, "eos_token_id": 5, "pad_token_id": 4,
    }
    t.update(over)
    return {
        "text_config": t, "image_token_index": 6,
        "vision_config": {"image_size": 32, "patch_size": 16, "hidden_size": 32,
                          "num_hidden_layers": 1, "num_attention_heads": 2},
    }


def random_state(cfg: dict, seed: int = 0) -> dict[str, np.ndarray]:
    """A checkpoint under the listing's names, wide enough that the mixers,
    the router and the state's memory all have something to tell apart."""
    rng = np.random.default_rng(seed)
    state = {}
    for name, shape in listing.tensors(cfg):
        draw = rng.standard_normal(shape).astype(np.float32)
        if name.endswith(("norm.weight", "norm1.weight", "norm2.weight", "layernorm.weight")):
            state[name] = 1.0 + 0.1 * draw
        elif name.endswith("mamba.dt_bias"):
            state[name] = -1.5 + 0.5 * draw  # dt about 0.1-0.4: a state that remembers
        elif name.endswith("mamba.D"):
            state[name] = 1.0 + 0.2 * draw
        elif name.endswith("mamba.A_log"):
            state[name] = 0.5 * draw
        elif name.endswith("embed_tokens.weight"):
            state[name] = 0.05 * draw
        else:
            state[name] = 0.3 * draw
    return state


class DictCheckpoint:
    def __init__(self, state):
        self.state = state

    def get(self, name):
        return jnp.asarray(self.state[name], jnp.float32)


def reference_logits(cfg: dict, state: dict, ids) -> np.ndarray:
    """The plain reference's logits at every position of ``ids`` [S]."""
    t, ck = cfg["text_config"], DictCheckpoint(state)
    with jax.default_matmul_precision("highest"):
        embed = ck.get("model.embed_tokens.weight")
        x = (embed[jnp.asarray(ids)] * t["embedding_multiplier"])[None]
        for i in range(t["num_hidden_layers"]):
            x = ref.decoder_layer(x, ref.layer_params(ck, t, i, None), t, i)
        x = ref.rms_norm(x, ck.get("model.norm.weight"), t["rms_norm_eps"])
        return np.asarray(x[0] @ embed.T / t["logits_scaling"])


def build(cfg: dict, state: dict):
    vcfg = VLMConfig.from_hf(cfg)
    model = VLMModel(vcfg)
    init = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32), jnp.zeros((1, 32, 32, 3)))
    )["params"]
    params = convert_vlm_checkpoint(state, init, tie_word_embeddings=True)
    return vcfg, model, jax.tree.map(jnp.asarray, params)


@pytest.fixture(scope="module")
def built():
    cfg = tiny_config()
    state = random_state(cfg)
    return (cfg, state, *build(cfg, state))


# -- a generator that tells what its programs computed -------------------------


class Tap(Generator):
    """``Generator`` whose programs report, in order: the logits every
    sample was drawn from, the slot of every install, and the ``done`` mask
    and token counts a decode block started from."""

    def __init__(self, *a, **kw):
        self.events: list[tuple] = []
        super().__init__(*a, **kw)

    def _sample_next(self, rng, logits, *rest):
        jax.debug.callback(lambda l: self.events.append(("logits", np.asarray(l))), logits, ordered=True)
        return super()._sample_next(rng, logits, *rest)

    def _admit_impl(self, pool, slot, *rest):
        jax.debug.callback(lambda s: self.events.append(("admit", int(s))), slot, ordered=True)
        return super()._admit_impl(pool, slot, *rest)

    def _step_block_impl(self, params, pool, block_tables, rng, *, block):
        jax.debug.callback(
            lambda d, n: self.events.append(("block", np.asarray(d), np.asarray(n))),
            pool["done"], pool["n_gen"], ordered=True,
        )
        return super()._step_block_impl(params, pool, block_tables, rng, block=block)

    def row_logits(self) -> list[dict[int, np.ndarray]]:
        """Per installed row, in install order: ``{token index: the logits it
        was drawn from}``."""
        rows: list[dict] = []
        by_slot: dict[int, dict] = {}
        first: list[np.ndarray] = []  # first-token logits waiting for their install
        steps_left, started = 0, None
        for ev in self.events:
            if ev[0] == "block":
                steps_left, started, step = BLOCK, ev, 0
            elif ev[0] == "admit":
                by_slot[ev[1]] = {0: first.pop(0)}
                rows.append(by_slot[ev[1]])
            elif steps_left:
                done, n_gen = started[1], started[2]
                for slot, row in by_slot.items():
                    if not done[slot]:  # step j emits token n_gen + j and draws the next
                        row.setdefault(int(n_gen[slot]) + step + 1, ev[1][slot])
                steps_left, step = steps_left - 1, step + 1
            else:
                first.extend(ev[1])
        return rows


def request(model, params, ids, max_new: int, span: int | None = None) -> _Request:
    """A text-only request: the ids' embeddings right-padded to ``span``."""
    n = len(ids)
    span = span or -(-n // PAGE) * PAGE
    embeds = model.apply({"params": params}, jnp.asarray(ids)[None], method=VLMModel.embed_tokens)
    return _Request(
        embeds=jnp.pad(embeds, ((0, 0), (0, span - n), (0, 0))),
        positions=jnp.arange(span)[None], length=jnp.asarray([n], jnp.int32),
        prompt_ids=jnp.asarray(ids, jnp.int32)[None], max_new=max_new, temperature=0.0, top_p=1.0,
        do_sample=False, repetition_penalty=1.0, rng=jax.random.PRNGKey(0),
    )


def scheduler(vcfg, model, params, name: str):
    gen = Tap(model, vcfg, max_seq=64, max_new_cap=16, cache_dtype=jnp.float32)
    return gen, ContinuousScheduler(
        gen, params, slots=SLOTS, block=BLOCK, name=name, page_size=PAGE, prefill_chunk=CHUNK
    )


def served_alone(vcfg, model, params, ids, max_new: int, span: int | None = None):
    """One request through a scheduler of its own: (tokens, its row's logits)."""
    gen, sched = scheduler(vcfg, model, params, "hybrid-alone")
    try:
        toks, n_gen, _ = sched.submit(request(model, params, ids, max_new, span)).result(timeout=300)
    finally:
        sched.close()
    (row,) = gen.row_logits()
    return [int(t) for t in np.asarray(toks)[:n_gen]], row, sched


def check_row(cfg, state, ids, toks, row, atol=2e-4):
    """The row's logits at every token it emitted against the reference's one
    full forward over the prompt and those tokens."""
    n = len(ids)
    want = reference_logits(cfg, state, list(ids) + toks[:-1])
    assert set(row) >= set(range(len(toks)))
    for k in range(len(toks)):
        np.testing.assert_allclose(row[k], want[n - 1 + k], atol=atol, rtol=1e-4, err_msg=f"token {k}")
        assert toks[k] == int(np.argmax(row[k]))


PROMPT = np.random.default_rng(1).integers(7, 96, 37)


def test_full_forward_matches_the_plain_reference(built):
    cfg, state, vcfg, model, params = built
    got = model.apply({"params": params}, jnp.asarray(PROMPT)[None], mutable=["moe_stats"])[0][0]
    np.testing.assert_allclose(np.asarray(got), reference_logits(cfg, state, PROMPT), atol=2e-4, rtol=1e-4)


def test_chunked_prefill_with_a_padded_tail_then_decode_through_the_scheduler(built):
    """37 live tokens in a 48-token span: two full lane chunks and a third of
    5 live and 11 padded positions (its padding must not advance the state),
    then ten decode steps through state and pages."""
    cfg, state, vcfg, model, params = built
    toks, row, sched = served_alone(vcfg, model, params, PROMPT, max_new=10, span=48)
    assert sched.chunks_run == 3 and len(toks) == 10
    check_row(cfg, state, PROMPT, toks, row)
    assert sched.state_installs == sched.admitted == 1 and sched.state_resets == 0


def test_rows_admitted_at_different_turns_and_a_reused_slot_decode_as_alone(built):
    """A (long, through the lane) decodes alone for a while, B joins a turn
    later in the other slot, C (short, admitted whole) takes A's slot after
    it: each row's logits are the ones it gives alone, and the reference's."""
    cfg, state, vcfg, model, params = built
    rng = np.random.default_rng(2)
    a, b, c = PROMPT, rng.integers(7, 96, 21), rng.integers(7, 96, 9)
    plan = [(a, 12, 48), (b, 6, None), (c, 8, None)]
    alone = [served_alone(vcfg, model, params, ids, new, span)[:2] for ids, new, span in plan]
    gen, sched = scheduler(vcfg, model, params, "hybrid-shared")
    try:
        fa = sched.submit(request(model, params, a, 12, 48))
        while sched.blocks_run < 1:
            time.sleep(0.01)
        fb = sched.submit(request(model, params, b, 6))
        ra = fa.result(timeout=300)
        fc = sched.submit(request(model, params, c, 8))
        results = [ra, fb.result(timeout=300), fc.result(timeout=300)]
        gauges = sched._gauge_fn()
    finally:
        sched.close()
    rows = gen.row_logits()
    assert len(rows) == 3 and gauges["state_installs"] == 3 and gauges["state_resets"] == 1
    for (ids, _, _), (toks_alone, row_alone), (toks, n_gen, _), row in zip(plan, alone, results, rows):
        toks = [int(t) for t in np.asarray(toks)[:n_gen]]
        assert toks == toks_alone
        for k in range(len(toks)):
            np.testing.assert_allclose(row[k], row_alone[k], atol=1e-5, rtol=1e-5)
        check_row(cfg, state, ids, toks, row)


def test_a_done_rows_state_does_not_move(built):
    """A block over a pool whose slot 0 finished and whose slot 1 is live:
    slot 0's convolution tail and scan state come back bit for bit, slot 1's
    moved."""
    cfg, state, vcfg, model, params = built
    gen = Generator(model, vcfg, max_seq=64, max_new_cap=16, cache_dtype=jnp.float32)
    maxp = 64 // PAGE
    kv = PagedKVPool(SLOTS * maxp + 1, PAGE, SLOTS, maxp)
    pool = gen.init_pool(SLOTS, pages=kv.pages_total, page_size=PAGE)
    n = 12
    for slot, max_new in ((0, 0), (1, 8)):  # a budget of 0: installed and done at once
        req = request(model, params, PROMPT[:n], max_new)
        caches, tok0, seen = gen._prefill(
            params, req.embeds, req.positions, req.length, req.prompt_ids, req.rng,
            jnp.zeros((1,)), jnp.ones((1,)), jnp.zeros((1,), bool), jnp.ones((1,)), kv_len=n,
        )
        pool = gen._admit(pool, slot, caches, tok0, seen, req.length, jnp.asarray(kv.admit(slot, n)),
                          max_new, 0.0, 1.0, False, 1.0)
    before = jax.tree.map(np.asarray, pool["caches"])
    assert kv.grow(1, n + BLOCK + 1)
    pool, _, _ = gen._step_block(params, pool, jnp.asarray(kv.device_tables(maxp)), jax.random.PRNGKey(0), block=BLOCK)
    moved = 0
    for kind, was, now in zip(gen.rows.kinds, before, pool["caches"]):
        if kind != RowState.RECURRENT:
            continue
        for name in ("conv", "ssm"):
            np.testing.assert_array_equal(was[name][0], np.asarray(now[name][0]))
            moved += not np.array_equal(was[name][1], np.asarray(now[name][1]))
    assert moved == 2 * gen.rows.state_layers == 6


def test_the_two_shares_of_a_layer_add_up_to_the_uncut_reference_layer():
    """Experts 0-3 on one chip, 4-7 on the other, the shared expert counted
    once: what the two programs compute adds up to the plain reference's
    layer over the whole 8-expert bank."""
    whole = tiny_config(num_local_experts=8, ep_size=1)
    state = random_state(whole, seed=4)
    t = whole["text_config"]
    y = jnp.asarray(np.random.default_rng(5).standard_normal((1, 19, 32)), jnp.float32)
    pre = "model.layers.0."
    with jax.default_matmul_precision("highest"):
        p = ref.layer_params(DictCheckpoint(state), t, 0, None)["moe"]
        want = np.asarray(ref.expert_layer(y[0], p, t, held=(0, 8), shared=True))
    total = 0.0
    for rank in (0, 1):
        cut = tiny_config(ep_rank=rank)
        part = dict(state)
        for name in ("input_linear", "output_linear"):
            key = f"{pre}block_sparse_moe.{name}.weight"
            part[key] = state[key][4 * rank: 4 * rank + 4]
        for i in range(1, 4):
            for name in ("input_linear", "output_linear"):
                key = f"model.layers.{i}.block_sparse_moe.{name}.weight"
                part[key] = state[key][:4]
        vcfg, _, params = build(cut, part)
        assert vcfg.decoder.moe_held == (4 * rank, 4 * rank + 4) and vcfg.decoder.moe_experts == 8
        mlp = params["decoder"]["layers_0"]["mlp"]
        out, _ = MoEFFN(vcfg.decoder).apply({"params": mlp}, y, mutable=["moe_stats"])
        total = total + np.asarray(out[0])
    shared = SwiGLU(vcfg.decoder, intermediate=24).apply({"params": mlp["shared"]}, y)
    np.testing.assert_allclose(total - np.asarray(shared[0]), want, atol=2e-4, rtol=1e-4)


# -- the kernels against their twins --------------------------------------------


def scan_operands(b, s, h, p, n, dtype, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(k[0], (b, s, h * p)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, s, h)) - 1)
    a = -jnp.exp(0.5 * jax.random.normal(k[2], (h,)))
    bm, cm = (jax.random.normal(k[i], (b, s, n)).astype(dtype) for i in (3, 4))
    return x, dt, a, bm, cm, jax.random.normal(k[5], (h,)), jax.random.normal(k[6], (b, n, h * p))


def token_by_token(x, dt, a, bm, cm, d, state):
    """The recurrence as written, one token at a time, in float32."""
    b, s, hp = x.shape
    h, n = a.shape[0], bm.shape[-1]
    st, ys = state.reshape(b, n, h, hp // h), []
    for t in range(s):
        xt = x[:, t].astype(jnp.float32).reshape(b, h, -1)
        st = st * jnp.exp(dt[:, t] * a)[:, None, :, None] + (
            bm[:, t].astype(jnp.float32)[:, :, None, None] * (dt[:, t][:, :, None] * xt)[:, None]
        )
        ys.append((jnp.einsum("bn,bnhp->bhp", cm[:, t].astype(jnp.float32), st) + d[:, None] * xt).reshape(b, hp))
    return jnp.stack(ys, 1), st.reshape(b, n, hp)


@pytest.mark.parametrize(
    "b,s,h,p,n,chunk", [(2, 40, 8, 16, 32, 16), (1, 37, 16, 8, 16, 256), (1, 64, 8, 64, 128, 32)],
    ids=["three_blocks", "one_padded_block", "published_head"],
)
def test_scan_kernel_in_interpret_mode_against_its_twin_and_the_recurrence(b, s, h, p, n, chunk):
    x, dt, a, bm, cm, d, st = scan_operands(b, s, h, p, n, jnp.float32)
    dt = dt.at[0, -5:].set(0.0)  # a padded tail: it must leave the state where the live tokens put it
    y0, s0 = token_by_token(x, dt, a, bm, cm, d, st)
    y1, s1 = ssm.ssd_chunk_scan_reference(x, dt, a, bm, cm, d, st, chunk=chunk)
    y2, s2 = ssm.ssd_chunk_scan_kernel(x, dt, a, bm, cm, d, st, chunk=chunk, interpret=True)
    scale = float(jnp.abs(y0).max())
    for y, state in ((y1, s1), (y2, s2)):
        np.testing.assert_allclose(np.asarray(y), np.asarray(y0), atol=2e-5 * scale)
        np.testing.assert_allclose(np.asarray(state), np.asarray(s0), atol=2e-5 * scale)
    _, live = token_by_token(x[:1, :-5], dt[:1, :-5], a, bm[:1, :-5], cm[:1, :-5], d, st[:1])
    np.testing.assert_allclose(np.asarray(s2[0]), np.asarray(live[0]), atol=2e-5 * scale)


@pytest.mark.parametrize("active", [(True, False, True), (False, False, False), (True, True, True)],
                         ids=["some", "none", "all"])
def test_update_kernel_in_interpret_mode_against_its_twin(active):
    x, dt, a, bm, cm, d, st = scan_operands(3, 1, 8, 16, 32, jnp.float32, seed=1)
    args = (x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], d, st, jnp.asarray(active))
    y0, s0 = ssm.ssm_state_update_reference(*args)
    y1, s1 = ssm.ssm_state_update_kernel(*args, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), atol=1e-6)
    yt, stt = token_by_token(x, dt, a, bm, cm, d, st)
    for row, on in enumerate(active):  # an active row as the recurrence has it, the others untouched
        np.testing.assert_allclose(np.asarray(s1[row]), np.asarray((stt if on else st)[row]), atol=1e-5)
        np.testing.assert_allclose(np.asarray(y1[row]), np.asarray(yt[row, 0]) * on, atol=1e-5)


def test_conv_tail_is_taken_at_the_last_live_token():
    rng = np.random.default_rng(0)
    xbc, tail = jnp.asarray(rng.standard_normal((2, 8, 6))), jnp.asarray(rng.standard_normal((2, 3, 6)))
    w, bias = jnp.asarray(rng.standard_normal((4, 6))), jnp.asarray(rng.standard_normal((6,)))
    out, new = ssm.causal_conv1d(xbc, tail, w, bias, jnp.asarray([8, 0]))
    np.testing.assert_allclose(np.asarray(new[0]), np.asarray(xbc[0, -3:]), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(new[1]), np.asarray(tail[1]).astype(new.dtype))  # wholly padded
    for t in range(8):  # the same activations a token at a time
        one, tail = ssm.conv1d_update(xbc[:, t], tail, w, bias, jnp.asarray([True, True]))
        np.testing.assert_allclose(np.asarray(one), np.asarray(out[:, t]), atol=1e-5)


# -- names, descriptions, refusals ----------------------------------------------


def test_hf_names_convert_to_the_modules_parameters(built):
    cfg, state, vcfg, model, params = built
    d, t = vcfg.decoder, cfg["text_config"]
    assert d.layer_types == (MAMBA, ATTENTION, MAMBA, MAMBA) and not d.latent
    assert (d.moe_experts, d.moe_held, d.moe_top_k, d.moe_shared_gated) == (8, (0, 4), 3, False)
    assert (d.attn_rope, d.attn_bias, d.attn_scale) == (False, False, 0.3)
    assert (d.embedding_multiplier, d.residual_multiplier, d.logits_scaling) == (12.0, 0.22, 16.0)
    layer = params["decoder"]["layers_0"]
    pre, f = "model.layers.0.", t["intermediate_size"]
    np.testing.assert_array_equal(layer["mamba"]["in_proj"]["kernel"], state[pre + "mamba.in_proj.weight"].T)
    np.testing.assert_array_equal(layer["mamba"]["conv_kernel"], state[pre + "mamba.conv1d.weight"][:, 0, :].T)
    for name in ("dt_bias", "A_log", "D"):
        np.testing.assert_array_equal(layer["mamba"][name], state[pre + "mamba." + name])
    bank = state[pre + "block_sparse_moe.input_linear.weight"]
    np.testing.assert_array_equal(layer["mlp"]["w_gate"][2], bank[2, :f].T)
    np.testing.assert_array_equal(layer["mlp"]["w_up"][2], bank[2, f:].T)
    np.testing.assert_array_equal(layer["mlp"]["w_down"][1], state[pre + "block_sparse_moe.output_linear.weight"][1].T)
    fused = state[pre + "shared_mlp.input_linear.weight"]
    np.testing.assert_array_equal(layer["mlp"]["shared"]["up_proj"]["kernel"], fused[24:].T)
    attn = params["decoder"]["layers_1"]["attn"]
    assert set(attn["q_proj"]) == {"kernel"}  # no bias
    np.testing.assert_array_equal(attn["k_proj"]["kernel"], state["model.layers.1.self_attn.k_proj.weight"].T)


@pytest.mark.parametrize(
    "over", [{"mamba_n_groups": 2}, {"position_embedding_type": "rope"}, {"layer_types": [MAMBA, "full", MAMBA, MAMBA]}],
    ids=["two-groups", "rope", "unknown-kind"],
)
def test_a_hybrid_listing_the_program_has_no_path_for_is_refused(over):
    with pytest.raises((NotImplementedError, ValueError)):
        VLMConfig.from_hf(tiny_config(**over))


def test_a_decoder_with_no_paged_layer_has_no_page_size():
    vcfg = VLMConfig.from_hf(tiny_config(layer_types=[MAMBA] * 4))
    with pytest.raises(ValueError, match="keeps pages"):
        RowState(vcfg).page_size_of([{"conv": None, "ssm": None}] * 4)


def test_row_state_describes_each_kind_of_decoder(built):
    vcfg = built[2]
    rows = RowState(vcfg)
    assert rows.kinds == (RowState.RECURRENT, RowState.PAGED, RowState.RECURRENT, RowState.RECURRENT)
    assert not rows.shareable and rows.state_layers == 3 and rows.indexer_layers == rows.window_layers == 0
    assert rows.page_bytes(PAGE, 2) == PAGE * 2 * 2 * 8 * 2  # K and V of two heads of 8 in the one attention layer
    assert rows.slot_bytes(2) == 3 * (16 * 64 * 4 + 3 * 96 * 2)  # scan state in float32, tail in the cache's type
    plain_rows = RowState(VLMConfig.tiny())
    assert plain_rows.shareable and plain_rows.slot_bytes(2) == 0 and set(plain_rows.kinds) == {RowState.PAGED}
    plain_rows.refuse("anything")  # no refusal for rows of K/V pages


def test_the_pool_is_sized_from_what_the_slots_state_leaves(built, monkeypatch):
    """``resolve_pool_pages`` takes every slot's recurrent state off the
    headroom before it buys pages with the rest."""
    from lumen_tpu.models.vlm.paged_kv import resolve_pool_pages

    class Device:
        platform = "tpu"

        def __init__(self, free):
            self.free = free

        def memory_stats(self):
            return {"bytes_limit": self.free, "bytes_in_use": 0}

    vcfg = built[2]
    rows = RowState(vcfg)
    for name in ("LUMEN_VLM_KV_PAGES", "LUMEN_VLM_KV_HEADROOM"):
        monkeypatch.delenv(name, raising=False)
    slots, page, state = 4, 4, 4 * rows.slot_bytes(2)
    free = (state + 40 * rows.page_bytes(page, 2)) * 10 // 6 + 1  # 0.6 of it: the state and 40 pages
    monkeypatch.setattr(jax, "local_devices", lambda: [Device(free)])
    assert resolve_pool_pages(vcfg, page, slots, max_seq=64) == (40, "device_memory")
    monkeypatch.setattr(jax, "local_devices", lambda: [Device(state // 2)])  # not even the state fits
    assert resolve_pool_pages(vcfg, page, slots, max_seq=64) == (slots * 4 + 1, "device_memory")  # the floor


@pytest.mark.parametrize("env", ["LUMEN_VLM_PREFIX_BYTES", "LUMEN_VLM_SPEC_K"])
def test_sharing_a_recurrent_row_is_refused_at_construction(built, monkeypatch, env):
    _, _, vcfg, model, params = built
    monkeypatch.setenv(env, "4096" if "PREFIX" in env else "2")
    gen = Generator(model, vcfg, max_seq=64, max_new_cap=16, cache_dtype=jnp.float32)
    with pytest.raises(NotImplementedError, match="recurrent decoder"):
        ContinuousScheduler(gen, params, slots=SLOTS, block=BLOCK, name="hybrid-refused", page_size=PAGE)


def test_spill_is_off_and_a_migrated_row_is_refused(built):
    """A preempted row restarts from its prompt (no spill record); what
    ``handle_kv_put`` calls to admit a peer's row raises before it queues."""
    _, _, vcfg, model, params = built
    gen, sched = scheduler(vcfg, model, params, "hybrid-spill")
    try:
        assert sched._spill_budget == 0 and sched.prefix is None and sched.spec_k == 0
        with pytest.raises(NotImplementedError, match="recurrent decoder"):
            sched.submit_migrated(request(model, params, PROMPT[:8], 2), None, [], 0)
        with pytest.raises(NotImplementedError, match="recurrent decoder"):
            gen._export_row(sched.pool, 0, jnp.zeros((2,), jnp.int32))
        gauges = sched._gauge_fn()
    finally:
        sched.close()
    assert gauges["state_layers"] == 3 and gauges["state_bytes"] == SLOTS * RowState(vcfg).slot_bytes(4)
    assert {"state_installs", "state_resets", "moe_tokens_held", "moe_layer_calls"} <= set(gauges)


def test_a_preempted_recurrent_row_restarts_from_its_prompt(built):
    """Pages for both prompts and no more: the newest row is preempted when the
    older one grows, its state goes with its slot, and it decodes again from
    its prompt to the same tokens."""
    cfg, state, vcfg, model, params = built
    ids = PROMPT[:14]
    want = served_alone(vcfg, model, params, ids, 12)[0]
    gen = Tap(model, vcfg, max_seq=64, max_new_cap=16, cache_dtype=jnp.float32)
    sched = ContinuousScheduler(gen, params, slots=SLOTS, block=BLOCK, name="hybrid-tight",
                                page_size=PAGE, pages=9, prefill_chunk=CHUNK)
    try:
        futures = [sched.submit(request(model, params, ids, 12)) for _ in range(2)]
        results = [f.result(timeout=300) for f in futures]
        assert sched.preemptions >= 1 and sched.preempt_redone >= 1 and sched.spills == 0
    finally:
        sched.close()
    for toks, n_gen, _ in results:
        assert [int(t) for t in np.asarray(toks)[:n_gen]] == want


# -- the decoders that were there ------------------------------------------------

#: logits of the Qwen2 and dots3_note tiny programs as the tree before this
#: file's PR computed them (float32, CPU): the last position's first six of the
#: cacheless forward and of a chunked prefill, the sums of their magnitudes,
#: the tokens of two paged decode blocks
BEFORE = {
    "qwen2": ([-0.878581, 0.518706, -1.553877, -1.427442, 0.953712, -0.242282], 2684.7068,
              [-0.87858, 0.518705, -1.553876, -1.427442, 0.953712, -0.242282], 2684.707, [21, 77, 28, 60]),
    "dots3": ([1.495378, -1.376381, -0.993982, 1.300362, -2.740742, -0.789337], 3627.6179,
              [1.495378, -1.376382, -0.993984, 1.300359, -2.740744, -0.789337], 3627.6177, [35, 37, 11, 24]),
}


def run_programs(vcfg, model, params, ids, latent: bool):
    """(cacheless logits, chunked-prefill logits, tokens of two paged blocks)"""
    n, page = len(ids), 4
    full = np.asarray(model.apply({"params": params}, jnp.asarray(ids)[None], mutable=["moe_stats"])[0][0])
    gen = Generator(model, vcfg, max_seq=64, max_new_cap=16, cache_dtype=jnp.float32)
    slots, block, maxp = 2, 2, 64 // page
    window = wpages = None
    if latent:
        wpages = window_pool_pages(vcfg, page, slots, block)
        window = WindowPages(wpages, page, slots, maxp, vcfg.decoder.sliding_window)
    kv = PagedKVPool(slots * maxp + 1, page, slots, maxp, window=window)
    pool = gen.init_pool(slots, pages=kv.pages_total, page_size=page, window_pages=wpages)
    embeds = model.apply({"params": params}, jnp.asarray(ids)[None], method=VLMModel.embed_tokens)
    span = -(-n // page) * page
    embeds = jnp.pad(embeds, ((0, 0), (0, span - n), (0, 0)))
    caches, outs = gen.new_prefill_cache(span), []
    for off in range(0, span, 8):
        c = min(8, span - off)
        out, caches = gen._prefill_chunk(params, caches, embeds[:, off:off + c], jnp.arange(off, off + c)[None],
                                         jnp.asarray(off, jnp.int32), jnp.asarray([n], jnp.int32))
        outs.append(np.asarray(out[0]))
    chunked = np.concatenate(outs)[:n]
    row = kv.admit(1, n)
    table = np.stack([row, window.tables[1]]) if latent else row
    pool = gen._admit(pool, 1, caches, jnp.asarray([int(np.argmax(chunked[n - 1]))]),
                      jnp.zeros((1, vcfg.decoder.vocab_size), bool), jnp.asarray([n]), jnp.asarray(table),
                      6, 0.0, 1.0, False, 1.0)
    toks, rng = [], jax.random.PRNGKey(0)
    for _ in range(2):
        assert kv.grow(1, n + len(toks) + block + 1)
        pool, rng, t = gen._step_block(params, pool, jnp.asarray(kv.device_tables(maxp)), rng, block=block)
        toks += [int(x) for x in np.asarray(t)[1]]
    return full, chunked, toks


@pytest.mark.parametrize("name", ["qwen2", "dots3"])
def test_the_decoders_that_were_there_compute_what_they_did(name):
    if name == "qwen2":
        vcfg = VLMConfig.tiny()
        model = VLMModel(vcfg)
        ids = (np.arange(13) * 7 + 3) % 200 + 3
        params = model.init(jax.random.PRNGKey(0), jnp.asarray(ids)[None], jnp.zeros((1, 32, 32, 3)))["params"]
    else:
        import test_vlm_latent as latent

        cfg = latent.tiny_config()
        vcfg = VLMConfig.from_hf(cfg)
        model = VLMModel(vcfg)
        init = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32), jnp.zeros((1, 32, 32, 3)))
        )["params"]
        params = jax.tree.map(
            jnp.asarray, convert_vlm_checkpoint(latent.random_state(cfg), init, tie_word_embeddings=False)
        )
        ids = np.random.default_rng(3).integers(7, 96, 21)
    full, chunked, toks = run_programs(vcfg, model, params, ids, latent=name == "dots3")
    full_tail, full_sum, chunk_tail, chunk_sum, want_toks = BEFORE[name]
    np.testing.assert_allclose(full[-1, :6], full_tail, atol=2e-5)
    np.testing.assert_allclose(chunked[-1, :6], chunk_tail, atol=2e-5)
    assert abs(np.abs(full).sum() - full_sum) < 0.05 and abs(np.abs(chunked).sum() - chunk_sum) < 0.05
    assert toks == want_toks
