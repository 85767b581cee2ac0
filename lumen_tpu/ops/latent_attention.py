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
  against its pages ``{"c": [P, page, C], "r": [P, page, R]}``, or a verify
  window of ``W`` consecutive tokens a row (query ``w`` sees ``w`` keys more
  than the first: a drafted token checked in the same step). A window
  layer passes ``kv_starts`` and visits only the ``span`` pages its window
  can touch; a full layer may pass ``sel``, the keys its indexer picked
  (one set a query).
  A grid step covers ``GRID_KEYS`` keys of the row's pages and copies only
  those that hold a key the row may see.
  Pallas kernel ``latent_paged_attention_kernel`` on TPU, the exact XLA
  gather elsewhere (the rule of ``ops.attention.paged_attention``:
  ``LUMEN_PAGED_KERNEL=0`` disables, ``=1`` forces interpret mode);
- the indexer's scoring pass for such a row, every query of its window
  against one read of its index keys (:func:`indexer_scores`, kernel
  ``indexer_scores_kernel``), and the selection (:func:`topk_select`);
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
    _count_route,
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


#: keys one grid step of the latent decode kernel covers: its pages are
#: copied from the pool that many keys at a time (timed on the chip at 256,
#: 512 and 1,024, the last fastest at every shape the cells run: ``PERF.md``
#: section 6, PR 40)
GRID_KEYS = 1024


def grid_pages(page: int, steps: int) -> int:
    """Pages one grid step of :func:`latent_paged_attention_kernel` covers:
    ``GRID_KEYS`` keys' worth, and no more than the ``steps`` pages a row may
    visit (a window layer's span caps it)."""
    return max(1, min(GRID_KEYS // page, steps))


def _latent_paged_kernel(
    bt_ref,  # [B, MAXP] int32 block table (SMEM, prefetched)
    len_ref,  # [B] int32 keys visible to the row's last query (its own token included)
    start_ref,  # [B] int32 first visible key (0 in a full layer)
    qc_ref,  # [1, nq*Hp, C] query folded through W_uk, the window's queries one after another
    qr_ref,  # [1, nq*Hp, R] rotated position query
    c_hbm,  # [P, page, C] the pool's latent rows, left in HBM
    *rest,  # k x r_ref [1, page, R], [sel_ref [1, 1, nq, k*page] f32], o_ref, c_buf, sems, flags, acc, m, l
    sm_scale: float,
    page: int,
    steps: int,
    maxp: int,
    k: int,
    has_sel: bool,
    nq: int,
    hp: int,
):
    r_refs, rest = rest[:k], rest[k:]
    if has_sel:
        sel_ref, o_ref, c_buf, sems, flags, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, c_buf, sems, flags, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    g = pl.program_id(1)
    rows = pl.num_programs(0)
    groups = pl.num_programs(1)
    kv_len = len_ref[b]
    start = start_ref[b]
    first, last = _row_slots(len_ref, start_ref, b, page, steps, maxp)

    def copy(row, grp, buf, what):
        """Start or wait for the copies of ``row``'s group ``grp`` into
        buffer ``buf``: one a page of the row's table that holds a key it
        may see."""
        lo, hi = _row_slots(len_ref, start_ref, row, page, steps, maxp)
        for i in range(k):
            slot = lo + grp * k + i
            cp = pltpu.make_async_copy(
                c_hbm.at[bt_ref[row, jnp.minimum(slot, maxp - 1)]],
                c_buf.at[buf, pl.ds(i * page, page)],
                sems.at[buf],
            )
            pl.when(slot <= hi)(getattr(cp, what))

    @pl.when((b == 0) & (g == 0))
    def _first():
        # Slots of a group past the row's last page are never copied: give
        # them zeros once, so that a masked weight (zero) never meets a NaN.
        c_buf[...] = jnp.zeros_like(c_buf)
        flags[0] = 0  # the buffer this step's pages are in
        flags[1] = -1  # the step (b * groups + g) whose copies are in flight

    @pl.when(g == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(g < _row_groups(first, last, k))
    def _update():
        buf = flags[0]

        @pl.when(flags[1] != b * groups + g)
        def _own():  # nothing was prefetched for it: the call's first, or after a row with no key
            copy(b, g, buf, "start")

        # the next step with pages: this row's next group, else the next row's first
        more = g + 1 < _row_groups(first, last, k)
        nb = jnp.where(more, b, b + 1)
        ng = jnp.where(more, g + 1, 0)
        nxt = jnp.minimum(nb, rows - 1)
        n_first, n_last = _row_slots(len_ref, start_ref, nxt, page, steps, maxp)
        prefetch = (nb < rows) & (ng < _row_groups(n_first, n_last, k))

        @pl.when(prefetch)
        def _next():
            copy(nxt, ng, 1 - buf, "start")
            flags[0] = 1 - buf
            flags[1] = nb * groups + ng

        @pl.when(~prefetch)
        def _none():
            flags[1] = -1

        copy(b, g, buf, "wait")
        c = c_buf[buf]  # [k*page, C]
        r = jnp.concatenate([r_ref[0] for r_ref in r_refs], axis=0)  # [k*page, R]
        s = jax.lax.dot_general(
            qc_ref[0], c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) + jax.lax.dot_general(
            qr_ref[0], r, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * sm_scale  # [nq*Hp, k*page]
        pos = (first + g * k) * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # keys of slots past the last page copied are not the row's: masked too
        ok = (pos >= start) & (pos < jnp.minimum(kv_len, (last + 1) * page))
        if nq > 1:
            # query w of the window sees its own token and those before it
            query = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // hp
            ok = ok & (pos < kv_len - (nq - 1) + query)
        if has_sel:
            if nq > 1:
                picked = jnp.zeros(s.shape, jnp.float32)
                for w in range(nq):
                    picked = jnp.where(query == w, sel_ref[0, 0, w : w + 1], picked)
                ok = ok & (picked > 0.0)
            else:
                ok = ok & (sel_ref[0, 0] > 0.0)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        # A group may hold no key this row may see (none picked by the
        # indexer): masked weights are set to zero outright, since
        # exp(NEG_INF - NEG_INF) is one.
        p = jnp.where(ok, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p, c.astype(jnp.float32), preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(g == groups - 1)
    def _finalize():
        l = l_ref[:, 0]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-20)[:, None]).astype(o_ref.dtype)


def _row_slots(len_ref, start_ref, row, page: int, steps: int, maxp: int):
    """First and last slot of ``row``'s table that hold a key it may see,
    within the ``steps`` slots visited from its start's page (last < first:
    none)."""
    first = start_ref[row] // page
    end = jnp.minimum((len_ref[row] + page - 1) // page, jnp.minimum(first + steps, maxp))
    return first, end - 1


def _row_groups(first, last, k: int):
    """Grid steps of ``k`` pages that hold the slots ``first..last``."""
    return jnp.where(last >= first, (last - first) // k + 1, 0)


@functools.partial(jax.jit, static_argnames=("scale", "span", "interpret"))
def latent_paged_attention_kernel(
    qc: jax.Array,  # [B, H, C], or [B, W, H, C]: a verify window of W queries
    qr: jax.Array,  # [B, H, R] / [B, W, H, R]
    c_pages: jax.Array,  # [P, page, C]
    r_pages: jax.Array,  # [P, page, R]
    block_tables: jax.Array,  # [B, MAXP] int32 (dead entries: 0)
    kv_lens: jax.Array,  # [B] int32 keys the window's FIRST query sees (0: the row reads none)
    kv_starts: jax.Array,  # [B] int32
    sel: jax.Array | None = None,  # [B, MAXP*page] / [B, W, MAXP*page] bool: keys the indexer picked (kv_starts 0)
    *,
    scale: float,
    span: int | None = None,  # window layer: pages visited from the start's page
    interpret: bool = False,
) -> jax.Array:
    """Pallas absorbed latent decode attention: ``[B, H, C]`` (``[B, W, H,
    C]``) weighted latent rows. A window's queries are rows of one grid step
    (``W * Hp`` of them, each query's own causal length and own selection
    masked in the step), so a page the window reads is copied once for all
    of them. Grid ``(B, ceil(steps / k))``: a step covers ``k`` pages of
    the row (:func:`grid_pages`), ``steps`` being the whole table for a full
    layer and ``span`` pages from the window's first for a window layer. A
    step reads only the pages that hold a key the row may see (from
    ``kv_start // page`` to the page of its last key), and the next step's
    are copied while it computes: ``c`` by copies the kernel starts from the
    pool left in HBM, by the prefetched block table; ``r`` by ``k`` block
    specs, one a page, whose index stays where it was for a slot past the
    row's last page, so nothing is copied for it (Mosaic refuses a copy of
    one page of a 64-wide pool)."""
    nq = qc.shape[1] if qc.ndim == 4 else 1
    b, h, c_dim = qc.shape[0], qc.shape[-2], qc.shape[-1]
    page = c_pages.shape[1]
    maxp = block_tables.shape[1]
    steps = maxp if span is None else min(span, maxp)
    k = grid_pages(page, steps)
    groups = -(-steps // k)
    hp = _q_group_pad(h)
    if hp != h:
        pad = ((0, 0),) * (qc.ndim - 2) + ((0, hp - h), (0, 0))
        qc, qr = jnp.pad(qc, pad), jnp.pad(qr, pad)
    r_dim = qr.shape[-1]
    if nq > 1:  # a window's queries are rows of one step; the pages to read are the last query's
        qc, qr = qc.reshape(b, nq * hp, c_dim), qr.reshape(b, nq * hp, r_dim)
        kv_lens = jnp.where(kv_lens > 0, kv_lens + nq - 1, 0)

    def at(kl, ks, i, j):
        """The row's group for grid step ``j``: past its last, its last."""
        first, last = _row_slots(kl, ks, i, page, steps, maxp)
        return first, last, jnp.clip(j, 0, jnp.maximum(_row_groups(first, last, k) - 1, 0))

    def q_map(i, j, bt, kl, ks):
        return (i, 0, 0)

    def r_map(n):
        def index(i, j, bt, kl, ks):
            first, last, grp = at(kl, ks, i, j)
            slot = first + grp * k + n
            # a slot past the row's last page takes the block this input
            # held a group before (no copy), or in a row's first group its
            # last page (a copy already made by another input); a row with
            # no key to see takes the dump page, as the row before it did
            slot = jnp.where(slot <= last, slot, jnp.where(grp > 0, slot - k, last))
            return (jnp.where(last >= first, bt[i, jnp.clip(slot, 0, maxp - 1)], 0), 0, 0)

        return index

    def sel_map(i, j, bt, kl, ks):
        return (i, at(kl, ks, i, j)[2], 0, 0)

    in_specs = [
        pl.BlockSpec((1, nq * hp, c_dim), q_map),
        pl.BlockSpec((1, nq * hp, r_dim), q_map),
        pl.BlockSpec(memory_space=pl.ANY),
        *(pl.BlockSpec((1, page, r_dim), r_map(n)) for n in range(k)),
    ]
    operands = [qc, qr, c_pages, *([r_pages] * k)]
    if sel is not None:
        keep = sel.astype(jnp.float32)
        keep = jnp.pad(keep, ((0, 0),) * (keep.ndim - 1) + ((0, groups * k * page - keep.shape[-1]),))
        in_specs.append(pl.BlockSpec((1, 1, nq, k * page), sel_map))
        if nq > 1:
            operands.append(keep.reshape(b, nq, groups, k * page).transpose(0, 2, 1, 3))
        else:
            operands.append(keep.reshape(b, groups, 1, k * page))
    kernel = functools.partial(
        _latent_paged_kernel, sm_scale=scale, page=page, steps=steps, maxp=maxp, k=k,
        has_sel=sel is not None, nq=nq, hp=hp,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, groups),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, nq * hp, c_dim), q_map),
            scratch_shapes=[
                pltpu.VMEM((2, k * page, c_dim), c_pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.VMEM((nq * hp, c_dim), jnp.float32),
                pltpu.VMEM((nq * hp, _LANES), jnp.float32),
                pltpu.VMEM((nq * hp, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, nq * hp, c_dim), qc.dtype),
        # one sequence over rows too: a step copies the next row's first group
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(
        block_tables.astype(jnp.int32), kv_lens.astype(jnp.int32),
        kv_starts.astype(jnp.int32), *operands,
    )
    if nq > 1:
        return out.reshape(b, nq, hp, c_dim)[:, :, :h]
    return out[:, :h]


def latent_paged_attention_reference(
    qc, qr, c_pages, r_pages, block_tables, kv_lens, kv_starts, sel=None,
    *, scale: float, span: int | None = None,
) -> jax.Array:
    """Exact XLA reference of the kernel above (the CPU / tier-1 serving
    path): gather the pages the kernel would visit, mask, one-pass softmax
    in float32. A window (``qc`` [B, W, H, C]) is its queries one by one."""
    if qc.ndim == 4:
        return jnp.stack([
            latent_paged_attention_reference(
                qc[:, w], qr[:, w], c_pages, r_pages, block_tables,
                jnp.where(kv_lens > 0, kv_lens + w, 0), kv_starts, None if sel is None else sel[:, w],
                scale=scale, span=span,
            )
            for w in range(qc.shape[1])
        ], axis=1)
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
    """Dispatch: the Pallas kernel on TPU, the XLA reference elsewhere. The
    ``attention-route`` gauge counts ``latent-grid:{keys a grid step}`` for
    each layer traced, on either path (the grid is decided by the shapes)."""
    page = c_pages.shape[1]
    maxp = block_tables.shape[1]
    _count_route("latent-grid", grid_pages(page, maxp if span is None else min(span, maxp)) * page)
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


def _indexer_kernel(bt_ref, q_ref, w_ref, k_ref, o_ref, *, nq: int, jp: int):
    del bt_ref
    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [nq*J, page]
    weighted = jnp.maximum(s, 0.0) * w_ref[0]
    if nq == 1:
        o_ref[0, 0] = jnp.sum(weighted, axis=0, keepdims=True)
    else:
        for w in range(nq):
            o_ref[0, 0, w : w + 1] = jnp.sum(weighted[w * jp : (w + 1) * jp], axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def indexer_scores_kernel(
    qi: jax.Array,  # [B, J, Di] index queries of the row's token, or [B, W, J, Di] of a window's
    w: jax.Array,  # [B, J] / [B, W, J] float32 head weights, scaled
    ik_pages: jax.Array,  # [P, page, Di] index keys
    block_tables: jax.Array,  # [B, MAXP]
    interpret: bool = False,
) -> jax.Array:
    """``I(s) = sum_j w_j relu(q_j . k_s)`` for every slot of the row's block
    table: ``[B, MAXP*page]`` (``[B, W, MAXP*page]``) float32 (slots past the
    row's length hold what the dump page holds: the caller masks by length).
    A window's queries are rows of one grid step: each index key is read
    once for all of them."""
    nq = qi.shape[1] if qi.ndim == 4 else 1
    b, j, di = qi.shape[0], qi.shape[-2], qi.shape[-1]
    page = ik_pages.shape[1]
    maxp = block_tables.shape[1]
    jp = _q_group_pad(j)
    if jp != j:
        qi = jnp.pad(qi, ((0, 0),) * (qi.ndim - 2) + ((0, jp - j), (0, 0)))
        w = jnp.pad(w, ((0, 0),) * (w.ndim - 1) + ((0, jp - j),))
    if nq > 1:
        qi, w = qi.reshape(b, nq * jp, di), w.reshape(b, nq * jp)
    out = pl.pallas_call(
        functools.partial(_indexer_kernel, nq=nq, jp=jp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, maxp),
            in_specs=[
                pl.BlockSpec((1, nq * jp, di), lambda i, p, bt: (i, 0, 0)),
                pl.BlockSpec((1, nq * jp, 1), lambda i, p, bt: (i, 0, 0)),
                pl.BlockSpec((1, page, di), lambda i, p, bt: (bt[i, p], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, nq, page), lambda i, p, bt: (i, p, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, maxp, nq, page), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), qi, w.astype(jnp.float32)[:, :, None], ik_pages)
    if nq > 1:
        return out.transpose(0, 2, 1, 3).reshape(b, nq, maxp * page)
    return out.reshape(b, maxp * page)


def indexer_scores_reference(qi, w, ik_pages, block_tables) -> jax.Array:
    b = qi.shape[0]
    k = ik_pages[block_tables].reshape(b, -1, ik_pages.shape[-1]).astype(jnp.float32)
    if qi.ndim == 4:  # a window's queries: [B, W, J, Di]
        s = jnp.einsum("bwjd,bsd->bwjs", qi.astype(jnp.float32), k)
        return jnp.einsum("bwj,bwjs->bws", w.astype(jnp.float32), jnp.maximum(s, 0.0))
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
