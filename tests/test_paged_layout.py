"""Round trips through the pool's token-major pages.

A grouped-query layer's pages are ``[pages, page, kv_heads * dh]`` while a
prefill scratch stays head-major ``[1, kv_heads, Lb, dh]``. Every program
that moves a row between the two forms, or between pools, must give back
what it was handed: admission (``RowState.install``) and a prefix-cache
hit's seeding (``_seed_prefix_impl``), and the spill tier's export and
resume, after which the row decodes as it would have in place.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from lumen_tpu.models.vlm.generate import Generator
from lumen_tpu.models.vlm.modeling import VLMConfig, VLMModel

PAGE, SLOTS, PROMPT = 8, 2, 30
GRANT = [3, 7, 1, 5]  # four pages, out of order, cover 32 positions


def _generator():
    vcfg = VLMConfig.tiny()
    model = VLMModel(vcfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32), jnp.zeros((1, 32, 32, 3)))["params"]
    return vcfg, Generator(model, vcfg, max_seq=64, max_new_cap=16, cache_dtype=jnp.float32), params


def _scratch(gen, seed=0):
    """A batch-1 scratch over four pages' positions, every leaf random."""
    rng = np.random.default_rng(seed)
    return [
        {name: jnp.asarray(rng.standard_normal(x.shape), x.dtype) for name, x in layer.items()}
        for layer in gen.new_prefill_cache(len(GRANT) * PAGE)
    ]


def _table(row_pages, slot):
    tables = np.zeros((SLOTS, 64 // PAGE), np.int32)
    tables[slot, : len(row_pages)] = row_pages
    return jnp.asarray(tables)


def test_an_installed_prompt_seeds_back_to_its_scratch():
    vcfg, gen, _ = _generator()
    pool = gen.init_pool(SLOTS, pages=9, page_size=PAGE)
    scratch = _scratch(gen)
    bt_row = _table(GRANT, 0)[0]
    caches = [gen.rows.install(i, dst, pre, 0, bt_row, PAGE) for i, (dst, pre) in enumerate(zip(pool["caches"], scratch))]
    d = vcfg.decoder
    assert caches[0]["k"].shape == (9, PAGE, d.kv_heads * d.dim_per_head)
    # token 10 lies in the grant's second page, slot 2: its heads side by side
    np.testing.assert_array_equal(
        np.asarray(caches[0]["k"][GRANT[1], 2]), np.asarray(scratch[0]["k"][0, :, 10]).reshape(-1)
    )
    empty = gen.new_prefill_cache(len(GRANT) * PAGE)
    back = gen._seed_prefix_impl(empty, caches, jnp.asarray(GRANT, jnp.int32))
    for got, want in zip(back, scratch):
        for name in want:
            np.testing.assert_array_equal(np.asarray(got[name]), np.asarray(want[name]))


def test_an_exported_row_resumed_elsewhere_decodes_the_same_tokens():
    vcfg, gen, params = _generator()
    vocab = vcfg.decoder.vocab_size
    scalars = dict(max_new=16, temperature=0.0, top_p=1.0, do_sample=False, rep=1.0)
    pool = gen.init_pool(SLOTS, pages=9, page_size=PAGE)
    pool = gen._admit_impl(
        pool, 0, _scratch(gen, seed=1), jnp.asarray([5], jnp.int32), jnp.zeros((1, vocab), bool),
        jnp.asarray([PROMPT], jnp.int32), _table(GRANT, 0)[0], **scalars,
    )
    row = gen._export_row_impl(pool, 0, jnp.asarray(GRANT, jnp.int32))
    moved = [2, 4, 6, 8]
    other = gen._resume_impl(
        gen.init_pool(SLOTS, pages=9, page_size=PAGE), 1, row["pages"], jnp.asarray(moved, jnp.int32),
        row["seen"], row["cur_tok"], row["cur_len"], row["n_gen"], **scalars,
    )
    for a, b in zip(pool["caches"], other["caches"]):
        for name in a:
            np.testing.assert_array_equal(np.asarray(a[name][jnp.asarray(GRANT)]), np.asarray(b[name][jnp.asarray(moved)]))
    rng = jax.random.PRNGKey(3)
    _, _, here = gen._step_block_impl(params, pool, _table(GRANT, 0), rng, block=2)
    _, _, there = gen._step_block_impl(params, other, _table(moved, 1), rng, block=2)
    assert int(here[0, 0]) == 5  # the row was live: its pending token came first
    np.testing.assert_array_equal(np.asarray(here[0]), np.asarray(there[1]))
