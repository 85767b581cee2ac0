"""Latent (low-rank key/value) attention in absorbed form, and the learned
indexer that picks a full layer's keys.

A latent layer caches ONE row a token, shared by every head: the normed
key/value latent ``c`` (``kv_lora`` values) and the rotated position key
``r`` (``rope`` values). Absorbed means the query is folded through the
key half of the up-projection before it meets the cache (``q_c = q_nope .
W_uk``), scores and weighted values are taken against the latent row
itself, and the value half (``W_uv``) is applied to the ``[heads, kv_lora]``
result afterwards; nothing per head is ever expanded from the cache.

Three callers, one mathematics:

- paged decode (:func:`latent_paged_attention`): one query token a row
  against its pages ``{"c": [P, page, C], "r": [P, page, R]}``. A window
  layer passes ``kv_starts`` and visits only the ``span`` pages its window
  can touch; a full layer may pass ``sel``, the keys its indexer picked.
  Pallas kernel ``latent_paged_attention_kernel`` on TPU, the exact XLA
  gather elsewhere (the rule of ``ops.attention.paged_attention``:
  ``LUMEN_PAGED_KERNEL=0`` disables, ``=1`` forces interpret mode);
- the indexer's scoring pass for such a row (:func:`indexer_scores`,
  kernel ``indexer_scores_kernel``) and the selection (:func:`topk_select`);
- prefill chunks and the cacheless forward (:func:`latent_prefill_attention`,
  :func:`indexer_scores_dense`), dense XLA under the same masks.

The kernels' names are what the benchmark's ``latent_attn_*`` and
``indexer_roofline`` readers match on the device's ``XLA Ops`` line
(``^latent_paged_attention``, ``^indexer_scores``); ``tests/test_tpu_compile.py``
pins them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import (
    _LANES,
    NEG_INF,
    _interpret_mode,
    _paged_kernel_usable,
    _q_group_pad,
)


def window_span_pages(window: int, page: int) -> int:
    """Pages that ``window`` consecutive keys can touch, whatever their
    alignment."""
    return (window + page - 2) // page + 1


def topk_select(scores: jax.Array, ok: jax.Array, k: int) -> jax.Array:
    """Of the keys ``ok`` allows, the ``k`` of largest score (all of them
    while fewer are allowed): a bool mask shaped like ``scores``. Keys that
    tie with the k-th score are all kept."""
    if scores.shape[-1] <= k:
        return ok
    masked = jnp.where(ok, scores, -jnp.inf)
    kth = jax.lax.top_k(masked, k)[0][..., -1:]
    return ok & (masked >= kth)


# -- paged decode ------------------------------------------------------------


def _latent_paged_kernel(
    bt_ref,  # [B, MAXP] int32 block table (SMEM, prefetched)
    len_ref,  # [B] int32 keys visible to the row (its own token included)
    start_ref,  # [B] int32 first visible key (0 in a full layer)
    qc_ref,  # [1, Hp, C] query folded through W_uk
    qr_ref,  # [1, Hp, R] rotated position query
    c_ref,  # [1, page, C] one page of latent rows
    r_ref,  # [1, page, R] one page of position keys
    *rest,  # [sel_ref [1, 1, 1, page] f32], o_ref, acc, m, l
    sm_scale: float,
    page: int,
    steps: int,
    maxp: int,
    has_sel: bool,
):
    del bt_ref  # consumed by the index maps
    if has_sel:
        sel_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    kv_len = len_ref[b]
    start = start_ref[b]
    slot = start // page + j  # logical page of the row this step visits

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when((slot < maxp) & (slot * page < kv_len))
    def _update():
        c = c_ref[0]
        s = jax.lax.dot_general(
            qc_ref[0], c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) + jax.lax.dot_general(
            qr_ref[0], r_ref[0], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * sm_scale  # [Hp, page]
        pos = slot * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = (pos >= start) & (pos < kv_len)
        if has_sel:
            ok = ok & (sel_ref[0, 0] > 0.0)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        # A page may hold no key this row may see (all behind the window's
        # start or none picked by the indexer): masked weights are set to
        # zero outright, since exp(NEG_INF - NEG_INF) is one.
        p = jnp.where(ok, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p, c.astype(jnp.float32), preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(j == steps - 1)
    def _finalize():
        l = l_ref[:, 0]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-20)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "span", "interpret"))
def latent_paged_attention_kernel(
    qc: jax.Array,  # [B, H, C]
    qr: jax.Array,  # [B, H, R]
    c_pages: jax.Array,  # [P, page, C]
    r_pages: jax.Array,  # [P, page, R]
    block_tables: jax.Array,  # [B, MAXP] int32 (dead entries: 0)
    kv_lens: jax.Array,  # [B] int32
    kv_starts: jax.Array,  # [B] int32
    sel: jax.Array | None = None,  # [B, MAXP*page] bool: keys the indexer picked
    *,
    scale: float,
    span: int | None = None,  # window layer: pages visited from the start's page
    interpret: bool = False,
) -> jax.Array:
    """Pallas absorbed latent decode attention: ``[B, H, C]`` weighted
    latent rows. Grid ``(B, steps)``; each step DMAs one page picked by the
    prefetched block table, ``steps`` being the whole table for a full layer
    and ``span`` pages from the window's first for a window layer."""
    b, h, c_dim = qc.shape
    page = c_pages.shape[1]
    maxp = block_tables.shape[1]
    steps = maxp if span is None else min(span, maxp)
    hp = _q_group_pad(h)
    if hp != h:
        qc = jnp.pad(qc, ((0, 0), (0, hp - h), (0, 0)))
        qr = jnp.pad(qr, ((0, 0), (0, hp - h), (0, 0)))
    r_dim = qr.shape[-1]

    def slot_of(i, j, ks):
        return jnp.minimum(ks[i] // page + j, maxp - 1)

    def q_map(i, j, bt, kl, ks):
        return (i, 0, 0)

    def page_map(i, j, bt, kl, ks):
        return (bt[i, slot_of(i, j, ks)], 0, 0)

    def sel_map(i, j, bt, kl, ks):
        return (i, slot_of(i, j, ks), 0, 0)

    in_specs = [
        pl.BlockSpec((1, hp, c_dim), q_map),
        pl.BlockSpec((1, hp, r_dim), q_map),
        pl.BlockSpec((1, page, c_dim), page_map),
        pl.BlockSpec((1, page, r_dim), page_map),
    ]
    operands = [qc, qr, c_pages, r_pages]
    if sel is not None:
        in_specs.append(pl.BlockSpec((1, 1, 1, page), sel_map))
        operands.append(sel.astype(jnp.float32).reshape(b, maxp, 1, page))
    kernel = functools.partial(
        _latent_paged_kernel, sm_scale=scale, page=page, steps=steps, maxp=maxp,
        has_sel=sel is not None,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, steps),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, hp, c_dim), q_map),
            scratch_shapes=[
                pltpu.VMEM((hp, c_dim), jnp.float32),
                pltpu.VMEM((hp, _LANES), jnp.float32),
                pltpu.VMEM((hp, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hp, c_dim), qc.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(
        block_tables.astype(jnp.int32), kv_lens.astype(jnp.int32),
        kv_starts.astype(jnp.int32), *operands,
    )
    return out[:, :h]


def latent_paged_attention_reference(
    qc, qr, c_pages, r_pages, block_tables, kv_lens, kv_starts, sel=None,
    *, scale: float, span: int | None = None,
) -> jax.Array:
    """Exact XLA reference of the kernel above (the CPU / tier-1 serving
    path): gather the pages the kernel would visit, mask, one-pass softmax
    in float32."""
    b = qc.shape[0]
    page = c_pages.shape[1]
    maxp = block_tables.shape[1]
    steps = maxp if span is None else min(span, maxp)
    first = kv_starts.astype(jnp.int32) // page if span is not None else jnp.zeros((b,), jnp.int32)
    slots = first[:, None] + jnp.arange(steps, dtype=jnp.int32)[None, :]  # [B, steps]
    ids = jnp.take_along_axis(block_tables, jnp.minimum(slots, maxp - 1), axis=1)
    c = c_pages[ids].reshape(b, steps * page, -1).astype(jnp.float32)
    r = r_pages[ids].reshape(b, steps * page, -1).astype(jnp.float32)
    pos = (slots[:, :, None] * page + jnp.arange(page, dtype=jnp.int32)).reshape(b, steps * page)
    ok = (pos >= kv_starts[:, None]) & (pos < kv_lens[:, None])
    if sel is not None:
        ok = ok & jnp.take_along_axis(sel, jnp.minimum(pos, sel.shape[1] - 1), axis=1)
    s = (
        jnp.einsum("bhc,bsc->bhs", qc.astype(jnp.float32), c)
        + jnp.einsum("bhr,bsr->bhs", qr.astype(jnp.float32), r)
    ) * scale
    s = jnp.where(ok[:, None, :], s, NEG_INF)
    p = jnp.where(ok[:, None, :], jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    out = jnp.einsum("bhs,bsc->bhc", p, c) / jnp.maximum(p.sum(-1, keepdims=True), 1e-20)
    return out.astype(qc.dtype)


def latent_paged_attention(
    qc, qr, c_pages, r_pages, block_tables, kv_lens, kv_starts, sel=None,
    *, scale: float, span: int | None = None,
) -> jax.Array:
    """Dispatch: the Pallas kernel on TPU, the XLA reference elsewhere."""
    if _paged_kernel_usable(0):
        return latent_paged_attention_kernel(
            qc, qr, c_pages, r_pages, block_tables, kv_lens, kv_starts, sel,
            scale=scale, span=span, interpret=_interpret_mode(),
        )
    return latent_paged_attention_reference(
        qc, qr, c_pages, r_pages, block_tables, kv_lens, kv_starts, sel,
        scale=scale, span=span,
    )


# -- the indexer's scores for a decode row ------------------------------------


def _indexer_kernel(bt_ref, q_ref, w_ref, k_ref, o_ref):
    del bt_ref
    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [J, page]
    o_ref[0, 0] = jnp.sum(jnp.maximum(s, 0.0) * w_ref[0], axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def indexer_scores_kernel(
    qi: jax.Array,  # [B, J, Di] index queries of the row's token
    w: jax.Array,  # [B, J] float32 head weights, scaled
    ik_pages: jax.Array,  # [P, page, Di] index keys
    block_tables: jax.Array,  # [B, MAXP]
    interpret: bool = False,
) -> jax.Array:
    """``I(s) = sum_j w_j relu(q_j . k_s)`` for every slot of the row's block
    table: ``[B, MAXP*page]`` float32 (slots past the row's length hold what
    the dump page holds: the caller masks by length)."""
    b, j, di = qi.shape
    page = ik_pages.shape[1]
    maxp = block_tables.shape[1]
    jp = _q_group_pad(j)
    if jp != j:
        qi = jnp.pad(qi, ((0, 0), (0, jp - j), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, jp - j)))
    out = pl.pallas_call(
        _indexer_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, maxp),
            in_specs=[
                pl.BlockSpec((1, jp, di), lambda i, p, bt: (i, 0, 0)),
                pl.BlockSpec((1, jp, 1), lambda i, p, bt: (i, 0, 0)),
                pl.BlockSpec((1, page, di), lambda i, p, bt: (bt[i, p], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, 1, page), lambda i, p, bt: (i, p, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, maxp, 1, page), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), qi, w.astype(jnp.float32)[:, :, None], ik_pages)
    return out.reshape(b, maxp * page)


def indexer_scores_reference(qi, w, ik_pages, block_tables) -> jax.Array:
    b = qi.shape[0]
    k = ik_pages[block_tables].reshape(b, -1, ik_pages.shape[-1]).astype(jnp.float32)
    s = jnp.einsum("bjd,bsd->bjs", qi.astype(jnp.float32), k)
    return jnp.einsum("bj,bjs->bs", w.astype(jnp.float32), jnp.maximum(s, 0.0))


def indexer_scores(qi, w, ik_pages, block_tables) -> jax.Array:
    if _paged_kernel_usable(0):
        return indexer_scores_kernel(qi, w, ik_pages, block_tables, interpret=_interpret_mode())
    return indexer_scores_reference(qi, w, ik_pages, block_tables)


# -- prefill chunks and the cacheless forward -----------------------------------


def indexer_scores_dense(qi: jax.Array, w: jax.Array, ik: jax.Array) -> jax.Array:
    """``qi`` [B, T, J, Di], ``w`` [B, T, J] float32, ``ik`` [B, S, Di] ->
    ``I`` [B, T, S] float32."""
    s = jnp.einsum("btjd,bsd->btjs", qi, ik, preferred_element_type=jnp.float32)
    return jnp.einsum("btj,btjs->bts", w.astype(jnp.float32), jnp.maximum(s, 0.0))


def latent_prefill_attention(
    qc: jax.Array,  # [B, H, T, C]
    qr: jax.Array,  # [B, H, T, R]
    c: jax.Array,  # [B, S, C]
    r: jax.Array,  # [B, S, R]
    mask: jax.Array,  # [B, T, S] bool: keys each query may see
    *,
    scale: float,
    head_block: int = 32,
) -> jax.Array:
    """Dense absorbed attention of ``T`` queries a row against ``S`` latent
    rows under ``mask``: ``[B, H, T, C]``. Heads go ``head_block`` at a time
    so that the ``[heads, T, S]`` float32 scores of a 4,608-key scratch stay
    a few hundred MB."""
    b, h, t, _ = qc.shape

    def block(args):
        qc_b, qr_b = args  # [B, hb, T, *]
        s = (
            jnp.einsum("bhtc,bsc->bhts", qc_b, c, preferred_element_type=jnp.float32)
            + jnp.einsum("bhtr,bsr->bhts", qr_b, r, preferred_element_type=jnp.float32)
        ) * scale
        s = jnp.where(mask[:, None], s, NEG_INF)
        p = jnp.where(mask[:, None], jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
        out = jnp.einsum("bhts,bsc->bhtc", p.astype(c.dtype), c, preferred_element_type=jnp.float32)
        return (out / jnp.maximum(p.sum(-1, keepdims=True), 1e-20)).astype(qc.dtype)

    if h <= head_block or h % head_block:
        return block((qc, qr))
    n = h // head_block
    split = lambda x: x.reshape(b, n, head_block, t, x.shape[-1]).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(block, (split(qc), split(qr)))  # [n, B, hb, T, C]
    return out.transpose(1, 0, 2, 3, 4).reshape(b, h, t, -1)
