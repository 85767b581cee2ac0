"""The sampler's nucleus runs only when a live row draws (``ops/sampling.py``
``sample()``: a ``lax.cond`` on ``draws``), and the scheduler counts the blocks
in which none did (``blocks_greedy``): ``sample()`` against the straight line
it replaced, kept here; where the vocabulary's sort sits in the programs; the
three tiny decoders streaming the same tokens with either sampler, in blocks
of greedy rows, of greedy and drawing rows mixed, and with a drawing request's
slot left free; the gauge and the dispatch phase."""

from __future__ import annotations

import contextlib
import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import test_vlm_hybrid as hybrid
import test_vlm_latent as latent
from lumen_tpu.models.vlm import continuous, generate
from lumen_tpu.models.vlm.continuous import ContinuousScheduler, _Request
from lumen_tpu.models.vlm.convert import convert_vlm_checkpoint
from lumen_tpu.models.vlm.generate import Generator
from lumen_tpu.models.vlm.modeling import VLMConfig, VLMModel
from lumen_tpu.ops import sampling
from lumen_tpu.ops.sampling import _per_sample, draws, greedy, sample, top_p_filter
from lumen_tpu.utils.metrics import metrics


def straight_line(rng, logits, temperature=1.0, top_p=1.0, do_sample=True):
    """``sample()`` as it stood before the conditional: the nucleus for every
    row in every call, thrown away where the row is greedy."""
    greedy_ids = greedy(logits)
    scaled = logits.astype(jnp.float32) / jnp.maximum(_per_sample(temperature, logits), 1e-6)
    filtered = top_p_filter(scaled, top_p)
    sampled_ids = jax.random.categorical(rng, filtered, axis=-1)
    hot = jnp.asarray(temperature, jnp.float32) > 1e-6
    use_sample = jnp.asarray(do_sample) & hot
    return jnp.where(use_sample, sampled_ids, greedy_ids)


# -- (a) sample() against the straight line --------------------------------------

ROWS, VOCAB = 4, 64
CASES = {
    "all_greedy": dict(temperature=np.float32([0.7, 1.0, 0.0, 1.3]), top_p=np.float32([0.9, 1.0, 0.5, 0.8]),
                       do_sample=np.zeros(ROWS, bool)),
    "all_sampled": dict(temperature=np.float32([0.7, 1.0, 2.0, 1.3]), top_p=np.float32([0.9, 1.0, 0.5, 0.8]),
                        do_sample=np.ones(ROWS, bool)),
    "mixed_rows": dict(temperature=np.float32([0.7, 1.0, 2.0, 0.0]), top_p=np.float32([0.9, 1.0, 0.5, 0.8]),
                       do_sample=np.array([True, False, True, True])),
    "scalar_sampled": dict(temperature=0.8, top_p=0.9, do_sample=True),
    "scalar_greedy": dict(temperature=0.8, top_p=0.9, do_sample=False),
    "temperature_zero": dict(temperature=np.zeros(ROWS, np.float32), top_p=np.float32([0.9, 1.0, 0.5, 0.8]),
                             do_sample=np.ones(ROWS, bool)),
}


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("seed", range(4))
def test_sample_gives_the_ids_of_the_straight_line(seed, case):
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(100 + seed), (ROWS, VOCAB))
    key = jax.random.PRNGKey(seed)
    got = jax.jit(sample)(key, logits, **CASES[case])
    want = jax.jit(straight_line)(key, logits, **CASES[case])
    assert got.shape == want.shape == (ROWS,)
    assert got.tolist() == want.tolist()
    if case in ("all_greedy", "scalar_greedy", "temperature_zero"):
        assert got.tolist() == jnp.argmax(logits, -1).tolist()


def test_the_drawing_cases_do_draw():
    """The comparison above is of draws, not of one argmax with itself."""
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(100), (ROWS, VOCAB))
    ids = {tuple(sample(jax.random.PRNGKey(s), logits, **CASES["all_sampled"]).tolist()) for s in range(8)}
    assert len(ids) > 1


# -- the one predicate, on the host and on the device ----------------------------

TABLE = [  # do_sample, temperature, draws
    (False, 0.0, False), (False, 0.7, False), (True, 0.0, False), (True, 0.7, True),
    (True, 1e-6, False), (True, 1.00000001e-6, False), (True, 1.1e-6, True), (True, 9e-7, False),
    (True, -1.0, False), (True, 100.0, True), (False, 1e-6, False),
]


@pytest.mark.parametrize("do_sample,temperature,want", TABLE)
def test_host_and_device_agree_on_which_row_draws(do_sample, temperature, want):
    host = draws(do_sample, temperature, np)
    device = jax.jit(draws)(jnp.asarray(do_sample), jnp.asarray(temperature, jnp.float32))
    assert bool(host) == bool(device) == want
    # and that is the row sample() draws for: a two-token row whose draw would show
    if want and temperature < 0.1:
        return  # it draws, from a distribution that cold sharpens to one token
    logits = jnp.log(jnp.asarray([0.6, 0.4]))
    keys = jax.random.split(jax.random.PRNGKey(0), 32)
    ids = jax.vmap(lambda k: sample(k, logits, temperature, 1.0, do_sample))(keys)
    assert (len(set(ids.tolist())) > 1) == want


def test_the_predicate_takes_rows_and_an_empty_pool():
    got = draws([True, False, True], [0.7, 0.7, 0.0], np)
    assert got.tolist() == [True, False, False] and got.dtype == bool
    assert not draws([], [], np).any()


# -- (b) where the sort sits -----------------------------------------------------


def sorts(jaxpr, under_cond=None, found=None):
    """``[(operand shape, (cond branch index or None))]`` of every ``sort`` in
    ``jaxpr`` and what it calls."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            found.append((tuple(eqn.invars[0].aval.shape), under_cond))
        for name, value in eqn.params.items():
            subs = value if isinstance(value, (tuple, list)) else (value,)
            for k, sub in enumerate(subs):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    branch = k if eqn.primitive.name == "cond" and name == "branches" else under_cond
                    sorts(inner, branch, found)
    return found


def test_sample_sorts_only_in_the_branch_that_draws():
    logits = jnp.zeros((ROWS, VOCAB))
    jaxpr = jax.make_jaxpr(sample)(jax.random.PRNGKey(0), logits, **CASES["mixed_rows"]).jaxpr
    assert sorts(jaxpr) == [((ROWS, VOCAB), 1)]  # branches[1] is the true branch
    assert sorts(jax.make_jaxpr(straight_line)(jax.random.PRNGKey(0), logits, **CASES["mixed_rows"]).jaxpr) == [
        ((ROWS, VOCAB), None)
    ]


# -- the three tiny decoders -----------------------------------------------------

PAGE, CHUNK, SLOTS, BLOCK = hybrid.PAGE, 16, 3, 2  # requests are padded to whole pages by ``hybrid.request``
ARCHS = ("qwen2", "dots3", "granite")


@functools.lru_cache(maxsize=None)
def decoder(arch: str):
    """``(config, model, parameters)`` of a tiny decoder of each kind, as the
    other suites build them."""
    if arch == "qwen2":
        vcfg = VLMConfig.tiny()
        model = VLMModel(vcfg)
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32), jnp.zeros((1, 32, 32, 3)))["params"]
        return vcfg, model, params
    if arch == "granite":
        cfg = hybrid.tiny_config()
        return hybrid.build(cfg, hybrid.random_state(cfg))
    cfg = latent.tiny_config()
    vcfg = VLMConfig.from_hf(cfg)
    model = VLMModel(vcfg)
    init = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32), jnp.zeros((1, 32, 32, 3)))
    )["params"]
    params = convert_vlm_checkpoint(latent.random_state(cfg), init, tie_word_embeddings=False)
    return vcfg, model, jax.tree.map(jnp.asarray, params)


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("program", ["step_block", "chunk_finish"])
def test_a_caption_program_sorts_the_vocabulary_only_under_the_cond(arch, program):
    vcfg, model, params = decoder(arch)
    vocab = vcfg.decoder.vocab_size
    gen = Generator(model, vcfg, max_seq=64, max_new_cap=16, cache_dtype=jnp.float32)
    if program == "step_block":
        sched = ContinuousScheduler(gen, params, slots=SLOTS, block=BLOCK, name=f"jaxpr-{arch}", page_size=PAGE)
        try:
            tables = jnp.asarray(sched.kv.device_tables(4))
            jaxpr = jax.make_jaxpr(functools.partial(gen._step_block_impl, block=BLOCK))(
                params, sched.pool, tables, jax.random.PRNGKey(0)
            ).jaxpr
        finally:
            sched.close()
    else:
        jaxpr = jax.make_jaxpr(gen._chunk_finish_impl)(
            jnp.zeros((1, CHUNK, vocab)), jnp.zeros((1,), jnp.int32), jnp.zeros((1, 8), jnp.int32),
            jnp.asarray([8]), jax.random.PRNGKey(0), jnp.zeros((1,)), jnp.ones((1,)), jnp.zeros((1,), bool),
            jnp.ones((1,)),
        ).jaxpr
    found = sorts(jaxpr)
    over_vocab = [s for s in found if s[0][-1] == vocab]
    assert over_vocab and all(branch == 1 for _, branch in over_vocab), found
    if arch == "qwen2":  # no experts: nothing else sorts either
        assert found == over_vocab


def make_request(arch, ids, max_new, do_sample=False, temperature=0.0, top_p=1.0, seed=0) -> _Request:
    _, model, params = decoder(arch)
    return dataclasses.replace(
        hybrid.request(model, params, ids, max_new), do_sample=do_sample, temperature=temperature, top_p=top_p,
        repetition_penalty=1.1, rng=jax.random.PRNGKey(seed),
    )


def prompt(seed: int, n: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(7, 96, n)


DRAW = dict(do_sample=True, temperature=1.5, top_p=0.95)
#: a mix is a list of (prompt seed, prompt tokens, max_new, sampling); a prompt
#: over CHUNK tokens goes through the lane and samples in ``_chunk_finish``
TRAFFIC = {
    "greedy": [(1, 12, 8, {}), (2, 12, 6, {}), (3, 21, 8, {})],
    "mixed": [(1, 12, 8, {}), (2, 12, 8, DRAW), (3, 21, 8, DRAW), (4, 12, 6, {})],
    # the drawing request is done after one block; the others run on beside its free slot
    "stale": [(2, 12, BLOCK, DRAW), (1, 12, 12, {}), (4, 12, 10, {})],
}


def serve(arch, gen, mix, phases=None):
    """The mix through a scheduler of its own, all of it queued before the loop
    takes any (so admissions, blocks and the key stream repeat): each request's
    tokens, and the gauge's two counts."""
    vcfg, model, params = decoder(arch)
    name = f"greedy-{arch}"
    sched = ContinuousScheduler(
        gen, params, slots=SLOTS, block=BLOCK, name=name, page_size=PAGE, prefill_chunk=CHUNK
    )
    sched._rng = jax.random.PRNGKey(11)  # entropy-seeded otherwise
    try:
        reqs = [make_request(arch, prompt(s, n), new, seed=s, **kw) for s, n, new, kw in mix]
        with sched._cond:
            futures = [sched.submit(r) for r in reqs]
        tokens = []
        for f in futures:
            toks, n_gen, _ = f.result(timeout=300)
            tokens.append([int(t) for t in np.asarray(toks)[:n_gen]])
        gauge = metrics.snapshot()["gauges"][f"vlm-continuous:{name}"]
    finally:
        sched.close()
    return dict(tokens=tokens, blocks_run=gauge["blocks_run"], blocks_greedy=gauge["blocks_greedy"])


@pytest.fixture(scope="module", params=ARCHS)
def served(request):
    """Every mix under the sampler as it is and under the straight line
    patched into ``generate.py``; one ``Generator`` a sampler, so a program
    compiles once. Under the sampler as it is, ``nucleus`` counts the steps in
    which the device ran the drawing branch, and ``phases`` holds what every
    ``vlm.block.dispatch`` carried."""
    arch = request.param
    vcfg, model, _ = decoder(arch)
    out = {"arch": arch}
    runs = {"n": 0}
    real_filter = top_p_filter

    def counting_filter(logits, top_p):
        jax.debug.callback(lambda: runs.__setitem__("n", runs["n"] + 1))
        return real_filter(logits, top_p)

    phases: list[tuple[str, dict]] = []

    def recording_phase(name, **args):
        phases.append((name, args))
        return contextlib.nullcontext()

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sampling, "top_p_filter", counting_filter)
        mp.setattr(continuous, "phase", recording_phase)
        gen = Generator(model, vcfg, max_seq=64, max_new_cap=16, cache_dtype=jnp.float32)
        for mix in TRAFFIC:
            runs["n"], before = 0, len(phases)
            out["cond", mix] = serve(arch, gen, TRAFFIC[mix])
            jax.effects_barrier()
            out["cond", mix]["nucleus"] = runs["n"]
            out["cond", mix]["dispatch"] = [a for n, a in phases[before:] if n == "vlm.block.dispatch"]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(generate, "sample", straight_line)
        gen = Generator(model, vcfg, max_seq=64, max_new_cap=16, cache_dtype=jnp.float32)
        for mix in TRAFFIC:
            out["line", mix] = serve(arch, gen, TRAFFIC[mix])
    return out


@pytest.mark.parametrize("mix", list(TRAFFIC))
def test_the_streams_are_those_of_the_straight_line(served, mix):
    """(c), (d): greedy rows only, greedy and drawing rows mixed, and greedy
    rows beside the slot a drawing request left."""
    got, want = served["cond", mix], served["line", mix]
    assert got["tokens"] == want["tokens"]
    assert [len(t) for t in got["tokens"]] == [new for _, _, new, _ in TRAFFIC[mix]]
    assert got["blocks_run"] == want["blocks_run"]


def test_a_drawing_row_draws_and_a_greedy_row_beside_it_does_not(served):
    """The mixes share prompts: a greedy request streams the same tokens
    whoever sits beside it, a drawing one does not stream its argmax."""
    greedy_run, mixed, stale = (served["cond", m]["tokens"] for m in ("greedy", "mixed", "stale"))
    assert mixed[0] == greedy_run[0]  # prompt 1, greedy in both
    assert stale[1][:8] == greedy_run[0]  # prompt 1 again, beside the freed slot
    assert mixed[1][:6] != greedy_run[1]  # prompt 2 drawn
    assert mixed[2] != greedy_run[2]  # prompt 3 drawn, first token in _chunk_finish


@pytest.mark.parametrize("mix", list(TRAFFIC))
def test_the_gauge_counts_the_blocks_in_which_no_live_row_drew(served, mix):
    """(d), (e)."""
    r = served["cond", mix]
    assert 0 <= r["blocks_greedy"] <= r["blocks_run"]
    flags = [d["sampling"] for d in r["dispatch"]]
    assert len(flags) == r["blocks_run"] and flags.count(0) == r["blocks_greedy"]
    assert all(set(d) >= {"step", "rows", "bucket", "sampling"} for d in r["dispatch"])
    if mix == "greedy":
        assert r["blocks_greedy"] == r["blocks_run"] > 0
    elif mix == "stale":
        # the drawing request lives through one block; its slot then stays
        # free (three requests, three slots) and every later block is greedy
        assert flags == [1] + [0] * (r["blocks_run"] - 1) and r["blocks_run"] >= 5
    else:
        assert 0 < r["blocks_greedy"] < r["blocks_run"]
        assert flags == sorted(flags, reverse=True)  # the drawing rows finish first
    assert served["line", mix]["blocks_greedy"] == r["blocks_greedy"]  # the host's count, whatever the device runs


@pytest.mark.parametrize("mix", list(TRAFFIC))
def test_the_device_runs_the_nucleus_in_no_block_the_host_counted_greedy(served, mix):
    """The device tests the predicate in every step over the rows still live
    there, and a freed slot's stale ``do_sample`` is masked out: the drawing
    branch runs at most once a first token and once a step of a block that
    held a drawing row."""
    r = served["cond", mix]
    drawing = sum(1 for *_, kw in TRAFFIC[mix] if kw)
    most = drawing + BLOCK * (r["blocks_run"] - r["blocks_greedy"])
    if mix == "greedy":
        assert r["nucleus"] == 0
    else:
        assert drawing <= r["nucleus"] <= most
