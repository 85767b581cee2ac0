"""Attention ops: XLA reference implementation + Pallas flash-attention.

Used by the CLIP towers (bidirectional), the VLM prefill (causal, long
sequences — this is where flash attention pays, SURVEY.md §7 step 7) and
ring attention (``lumen_tpu.parallel.ring_attention`` wraps the blockwise
math over a ``seq`` mesh axis).

Layouts: ``q/k/v`` are ``[batch, heads, seq, head_dim]``. GQA callers repeat
KV heads before calling (XLA fuses the broadcast).
"""

from __future__ import annotations

import functools
import math
import os
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128  # VMEM lane width; scratch stats are padded to this

#: batch*heads and q-block axes carry no state between steps, so megacore
#: chips (v4/v5p: two TensorCores per chip) may split them; the k axis is
#: the online-softmax accumulation and must stay sequential.
_DIM_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)


def _on_tpu() -> bool:
    """True when the default backend is a TPU. A backend that fails to
    initialise raises here: serving the XLA reference in its place would
    hide a missing chip."""
    return jax.default_backend() == "tpu"


def attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: jax.Array | None = None,
    causal: bool = False,
    scale: float | None = None,
) -> jax.Array:
    """Plain XLA attention. ``mask``: broadcastable to [B,H,Sq,Sk]; True=keep.

    Causal semantics for sq != sk match a KV-cache decode: query i may
    attend keys ``<= i + sk - sq`` (``tril`` offset by ``sk - sq``).
    """
    *_, sq, d = q.shape
    sk = k.shape[-2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if causal:
        causal_mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(causal_mask, logits, NEG_INF)
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", weights.astype(v.dtype), v)


# -- pallas flash attention -------------------------------------------------
#
# Grid: (batch*heads, q_blocks, k_blocks). The TPU grid runs sequentially
# with the last axis fastest, so the online-softmax running stats for one
# (head, q_block) live in VMEM scratch across the k_block steps: only one
# (block_q, d) Q tile and one (block_k, d) K/V tile are VMEM-resident at a
# time — O(block) memory however long the sequence is.


def _flash_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    causal: bool,
    sm_scale: float,
    offset: int,
    kv_len: int,
    block_q: int,
    block_k: int,
    num_k_blocks: int,
):
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Causal: skip blocks entirely above the (offset) diagonal.
    if causal:
        block_live = j * block_k <= (qi + 1) * block_q - 1 + offset
    else:
        block_live = j * block_k < kv_len

    @pl.when(block_live)
    def _update():
        q = q_ref[0].astype(jnp.float32) * sm_scale  # [block_q, d]
        k = k_ref[0].astype(jnp.float32)  # [block_k, d]
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # [block_q, block_k]
        k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        live = k_pos < kv_len  # mask K padding
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            live = live & (q_pos + offset >= k_pos)
        s = jnp.where(live, s, NEG_INF)

        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(j == num_k_blocks - 1)
    def _finalize():
        l = l_ref[:, 0]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-20)[:, None]).astype(o_ref.dtype)


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Fused attention via Pallas on TPU (online softmax, O(block) VMEM).

    Handles ``sq != sk`` (KV-cache decode offset) and sequences that are
    not block multiples (padded K positions are masked inside the kernel).
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    sm_scale = scale if scale is not None else 1.0 / math.sqrt(d)

    block_q_eff = min(block_q, max(sq, 16))
    block_k_eff = min(block_k, max(sk, 16))
    qp = _pad_to(q, 2, block_q_eff)
    kp = _pad_to(k, 2, block_k_eff)
    vp = _pad_to(v, 2, block_k_eff)
    sq_p, sk_p = qp.shape[2], kp.shape[2]
    num_k_blocks = sk_p // block_k_eff

    qkv = (qp.reshape(b * h, sq_p, d), kp.reshape(b * h, sk_p, d), vp.reshape(b * h, sk_p, d))
    grid = (b * h, sq_p // block_q_eff, num_k_blocks)
    kernel = functools.partial(
        _flash_kernel,
        causal=causal,
        sm_scale=sm_scale,
        offset=sk - sq,
        kv_len=sk,
        block_q=block_q_eff,
        block_k=block_k_eff,
        num_k_blocks=num_k_blocks,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q_eff, d), lambda i, qi, j: (i, qi, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k_eff, d), lambda i, qi, j: (i, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k_eff, d), lambda i, qi, j: (i, j, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (1, block_q_eff, d), lambda i, qi, j: (i, qi, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q_eff, d), jnp.float32),
            pltpu.VMEM((block_q_eff, _LANES), jnp.float32),
            pltpu.VMEM((block_q_eff, _LANES), jnp.float32),
        ],
        compiler_params=_DIM_SEMANTICS,
        interpret=interpret,
    )(*qkv)
    return out.reshape(b, h, sq_p, d)[:, :, :sq]


# -- cache-aware flash attention (VLM prefill/decode path) ------------------
#
# Same online-softmax scheme, but masking is driven by two [B] scalar-
# prefetch arrays instead of a static causal triangle:
#   q_offsets[b]  absolute position of sample b's FIRST query token
#                 (query i is at q_offsets[b] + i; positions are contiguous)
#   kv_valid[b]   number of live key slots (prefill: prompt length;
#                 decode: cache fill level + 1)
# key j is visible to query i iff  j <= q_offsets[b] + i  AND  j < kv_valid[b]
# — exactly the (live & causal) mask of the VLM cache path
# (models/vlm/modeling.py:228-240), computed in-kernel instead of as a
# [B, 1, S, K] bool tensor in HBM.


def _flash_cache_kernel(
    q_off_ref,  # [B] int32 (SMEM, prefetched)
    kv_valid_ref,  # [B] int32 (SMEM, prefetched)
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    heads: int,
    sm_scale: float,
    block_q: int,
    block_k: int,
    num_k_blocks: int,
):
    i = pl.program_id(0)  # fused batch*heads index
    qi = pl.program_id(1)
    j = pl.program_id(2)
    b = i // heads
    q_off = q_off_ref[b]
    kv_valid = kv_valid_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Skip blocks fully above the causal diagonal or past the live slots.
    max_k_this_q = q_off + (qi + 1) * block_q - 1  # largest visible key pos
    block_live = (j * block_k <= max_k_this_q) & (j * block_k < kv_valid)

    @pl.when(block_live)
    def _update():
        q = q_ref[0].astype(jnp.float32) * sm_scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        q_abs = q_off + qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        live = (k_pos < kv_valid) & (k_pos <= q_abs)
        s = jnp.where(live, s, NEG_INF)

        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(j == num_k_blocks - 1)
    def _finalize():
        l = l_ref[:, 0]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-20)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_q", "block_k", "interpret")
)
def flash_attention_cache(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_offsets: jax.Array,
    kv_valid: jax.Array,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention against a KV buffer with per-sample causal offsets
    and live-slot counts (see block comment above)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    sm_scale = scale if scale is not None else 1.0 / math.sqrt(d)

    block_q_eff = min(block_q, max(sq, 16))
    block_k_eff = min(block_k, max(sk, 16))
    qp = _pad_to(q, 2, block_q_eff)
    kp = _pad_to(k, 2, block_k_eff)
    vp = _pad_to(v, 2, block_k_eff)
    sq_p, sk_p = qp.shape[2], kp.shape[2]
    num_k_blocks = sk_p // block_k_eff
    # Padded key slots beyond sk must never win: kv_valid <= sk by contract.

    kernel = functools.partial(
        _flash_cache_kernel,
        heads=h,
        sm_scale=sm_scale,
        block_q=block_q_eff,
        block_k=block_k_eff,
        num_k_blocks=num_k_blocks,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * h, sq_p // block_q_eff, num_k_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q_eff, d), lambda i, qi, j, *_: (i, qi, 0)),
            pl.BlockSpec((1, block_k_eff, d), lambda i, qi, j, *_: (i, j, 0)),
            pl.BlockSpec((1, block_k_eff, d), lambda i, qi, j, *_: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q_eff, d), lambda i, qi, j, *_: (i, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q_eff, d), jnp.float32),
            pltpu.VMEM((block_q_eff, _LANES), jnp.float32),
            pltpu.VMEM((block_q_eff, _LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
        compiler_params=_DIM_SEMANTICS,
        interpret=interpret,
    )(
        q_offsets.astype(jnp.int32),
        kv_valid.astype(jnp.int32),
        qp.reshape(b * h, sq_p, d),
        kp.reshape(b * h, sk_p, d),
        vp.reshape(b * h, sk_p, d),
    )
    return out.reshape(b, h, sq_p, d)[:, :, :sq]


#: Shortest sequence :func:`attention` hands to the tiled kernel: where the
#: two programs cross on a TPU v5e (``PERF.md`` section 6, PR 32; bf16, 16
#: heads of 64, time of the kernel over time of ``attention_reference``):
#:
#:   tokens x rows   257x8  512x8  1024x8  2048x2  2048x8  3072x1  4096x1  4096x2  8192x1
#:   unmasked         3.57   2.79    2.84    2.66    2.94    2.77    1.11    2.75    0.08
#:   causal           2.53   1.92    2.15    1.53    2.20    1.58    0.62    0.81    0.05
#:
#: Under it the float32 scores fit the device's memory and one batched XLA
#: attention is the faster program at every length and row count measured:
#: the kernel pays for each 128 x 128 grid step (nine a head at 257 tokens,
#: 0.58 us each, float32 operands). From it up the scores of one row are
#: 1 GiB, the kernel wins under a causal mask, and by 8,192 tokens XLA's
#: program takes 449 ms against the kernel's 35.
_FLASH_CROSSOVER_SEQ = 4096

#: Key length from which :func:`attention_cached` hands a prefill-size
#: query block to the cache kernel. Not the crossover above: that kernel
#: reads its mask from two scalars a row where the XLA path builds a
#: [B, 1, Sq, Sk] mask, and no A/B of it is on record (``ROADMAP.md`` D3).
_FLASH_CACHE_MIN_KEYS = 256


#: fallback reasons already logged this process (log ONCE per distinct
#: reason — the dispatch sits inside jitted-model call paths that run per
#: request; a silent fallback is undebuggable but a log-per-call is worse)
_FALLBACK_LOGGED: set[str] = set()


def _log_fallback_once(reason: str) -> None:
    if reason in _FALLBACK_LOGGED:
        return
    _FALLBACK_LOGGED.add(reason)
    import logging

    logging.getLogger(__name__).info(
        "flash attention NOT selected: %s (XLA reference path serves this "
        "shape; set LUMEN_FLASH=1 to force the kernel)", reason
    )


def _flash_usable(head_dim: int, mask, sq: int, min_seq: int = _FLASH_CROSSOVER_SEQ) -> bool:
    force = os.environ.get("LUMEN_FLASH")
    if force == "0":
        _log_fallback_once("disabled by LUMEN_FLASH=0")
        return False
    if mask is not None:
        _log_fallback_once("explicit attention mask (kernel supports none/causal only)")
        return False
    if head_dim > 256:
        _log_fallback_once(f"head_dim {head_dim} > 256 exceeds the kernel's VMEM tile")
        return False
    if force == "1":  # tests force the kernel on small CPU shapes
        return True
    if not _on_tpu():
        _log_fallback_once("backend is not TPU (Pallas kernel is TPU-only)")
        return False
    if sq < min_seq:
        _log_fallback_once(
            f"seq {sq} < {min_seq}: one fused XLA attention beats a kernel "
            "grid of a few tiles a head"
        )
        return False
    return True


#: calls of :func:`attention` traced so far, by route and query length
#: (``xla:257``, ``flash:2048``). The route is chosen while a program is
#: traced, so this counts programs built, not requests served.
_ROUTES_TRACED: dict[str, int] = {}
_ROUTES_LOCK = threading.Lock()


def _route_gauges() -> dict:
    with _ROUTES_LOCK:
        return dict(_ROUTES_TRACED)


def _count_route(route: str, sq: int) -> None:
    """Publish the choice as the ``attention-route`` gauge provider: a
    hub's /metrics after warm-up says which program its towers run."""
    from ..utils.metrics import metrics

    key = f"{route}:{sq}"
    with _ROUTES_LOCK:
        _ROUTES_TRACED[key] = _ROUTES_TRACED.get(key, 0) + 1
    metrics.register_gauges("attention-route", _route_gauges)


def _interpret_mode() -> bool:
    """Pallas ``interpret=True`` when flash is forced on a non-TPU backend
    (tests exercise the kernel path on CPU)."""
    return not _on_tpu()


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: jax.Array | None = None,
    causal: bool = False,
    scale: float | None = None,
) -> jax.Array:
    """Dispatch by shape: the Pallas flash kernel on TPU for unmasked/causal
    attention on sequences of ``_FLASH_CROSSOVER_SEQ`` tokens or more, where
    online softmax saves the memory traffic of the scores; the XLA reference
    for everything shorter (the CLIP and captioner image towers at 50-257
    tokens, the text tower at 77), off the TPU and under an explicit mask.
    ``LUMEN_FLASH=0`` disables the kernel; ``LUMEN_FLASH=1`` forces it
    (interpret mode off TPU, for tests). The ``attention-route`` gauge
    counts the choices."""
    if _flash_usable(q.shape[-1], mask, q.shape[2]):
        _count_route("flash", q.shape[2])
        return flash_attention(
            q, k, v, causal=causal, scale=scale, interpret=_interpret_mode()
        )
    _count_route("xla", q.shape[2])
    return attention_reference(q, k, v, mask=mask, causal=causal, scale=scale)


#: decode KV-bucket ladder starts here; caches at or below this length are
#: read whole (the switch overhead wouldn't pay).
_RAGGED_DECODE_MIN = 256


def _ragged_decode_enabled() -> bool:
    return os.environ.get("LUMEN_RAGGED_DECODE", "1") != "0"


def _decode_masked(q, k, v, q_offsets, kv_valid, scale):
    """Masked reference attention for the [Sq small] cache path."""
    sq, sk = q.shape[2], k.shape[2]
    key_slots = jnp.arange(sk)
    q_abs = q_offsets[:, None] + jnp.arange(sq)[None, :]  # [B, Sq]
    live = key_slots[None, :] < kv_valid[:, None]  # [B, Sk]
    causal = key_slots[None, None, :] <= q_abs[:, :, None]  # [B, Sq, Sk]
    mask = (live[:, None, :] & causal)[:, None]  # [B, 1, Sq, Sk]
    return attention_reference(q, k, v, mask=mask, scale=scale)


def attention_cached(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_offsets: jax.Array,
    kv_valid: jax.Array,
    scale: float | None = None,
    min_flash_q: int = 32,
) -> jax.Array:
    """Cache-path dispatch: the Pallas cache kernel when profitable (prefill-
    size query blocks on TPU), else the XLA reference with the equivalent
    [B, 1, Sq, Sk] mask.

    Single-token decode additionally applies RAGGED KV BUCKETING: the
    cache buffer is allocated at ``max_seq`` but a step only needs the
    live prefix, and a decode step's cost IS streaming the KV bytes. A
    ``lax.switch`` over a doubling ladder of static prefix lengths makes
    each step read ~``max(kv_valid)`` worth of cache instead of the whole
    buffer (the XLA-native slice of TPU paged attention's dead-block
    skip; disable with ``LUMEN_RAGGED_DECODE=0``). All branches share
    output shapes, so the switch compiles once inside the decode loop.
    """
    sq, sk = q.shape[2], k.shape[2]
    # Gate on the KEY length, not the query length: prefill chunks are
    # short (sq 64) against a long cache buffer (sk >> sq), and the
    # kernel's win is streaming those keys without a [B,1,Sq,Sk] HBM
    # mask. min_flash_q still keeps near-decode query blocks on the
    # cheaper masked path.
    if _flash_usable(q.shape[-1], None, sk, _FLASH_CACHE_MIN_KEYS) and sq >= min_flash_q:
        return flash_attention_cache(
            q, k, v, q_offsets, kv_valid, scale=scale, interpret=_interpret_mode()
        )
    if sq == 1 and sk > _RAGGED_DECODE_MIN and _ragged_decode_enabled():
        ladder = []
        length = _RAGGED_DECODE_MIN
        while length < sk:
            ladder.append(length)
            length *= 2
        ladder.append(sk)
        # Keys a decode step may attend: the live prefix (decode writes in
        # order, so slot indices >= kv_valid are dead for every row).
        bound = jnp.max(kv_valid)
        idx = jnp.searchsorted(jnp.asarray(ladder), bound, side="left")

        def branch(prefix_len):
            def run(q, k, v, q_offsets, kv_valid):
                return _decode_masked(
                    q, k[:, :, :prefix_len], v[:, :, :prefix_len],
                    q_offsets, kv_valid, scale,
                )

            return run

        return jax.lax.switch(
            idx, [branch(n) for n in ladder], q, k, v, q_offsets, kv_valid
        )
    return _decode_masked(q, k, v, q_offsets, kv_valid, scale)


def repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """[B, kv_heads, S, D] -> [B, kv_heads*n_rep, S, D] for GQA."""
    if n_rep == 1:
        return x
    b, h, s, d = x.shape
    return jnp.broadcast_to(x[:, :, None], (b, h, n_rep, s, d)).reshape(b, h * n_rep, s, d)


# -- ragged paged-attention decode (paged KV pool path) ---------------------
#
# The continuous VLM engine keeps KV in a pool of fixed-size pages
# ([num_pages, kv_heads, page_size, head_dim] per layer) with a per-row
# block table instead of one contiguous max_seq region per slot, so a
# decode step streams only the pages a row actually owns. The kernel grid
# is (batch*kv_heads, max_pages): the page axis runs sequentially and each
# step DMAs ONE page picked by the scalar-prefetched block table — the
# "ragged" part: row lengths differ, and dead pages (j beyond the row's
# live count) skip their matmuls entirely. The page is the kernel's tile:
# the keys a grid step covers are the pool's page size, and small pages
# make the kernel's time its step count (paged_kv.DEFAULT_PAGE_SIZE says
# what was measured). Every live page folds into
# flash-style running statistics (row max, row sum, weighted V) in VMEM
# scratch whose shapes do not depend on the row capacity. Nothing is
# stored at a per-page offset: a page shorter than the 128-lane tile can
# never land on the tile boundary Mosaic requires of a dynamic lane
# offset, which is what an assembled [Gp, MAXP*page] logits row needed. Online
# rescaling sums in a different order than the one-pass XLA reference
# below, so the two agree to f32 rounding, not bitwise
# (tests/test_paged_attention.py states the bound).
#
# Speculative decoding verifies K drafted tokens in ONE target step: each
# row carries a WINDOW of W = K+1 query tokens written at consecutive
# positions. Same kernel — the window folds into the query-row axis
# ([W*Gp, dh] per (b, kv_head) instead of [Gp, dh]) and the mask becomes
# per-window-position causal: window slot t (row r -> t = r // Gp) sees
# key j iff j < kv_lens[b] + t, where kv_lens is the t=0 visibility
# (cur_len + 1, the just-written token included). Single-token decode is
# the W == 1 case.


def _q_group_pad(g: int) -> int:
    """Query-head group size padded to the f32 sublane (8) so the
    [Gp, ...] VMEM tiles are well-formed on real TPUs. The REFERENCE pads
    too: at g=1, XLA's matvec special-case rounds differently than a gemm,
    and keeping both paths on the same contraction keeps their gap at
    summation-order size."""
    return max(8, -(-g // 8) * 8)


def _paged_kernel(
    bt_ref,  # [B, MAXP] int32 block table (SMEM, prefetched)
    kv_len_ref,  # [B] int32 t=0 visibility per row (SMEM, prefetched)
    q_ref,  # [1, 1, W*Gp, dh] window-folded query heads for this (b, kv_head)
    k_ref,  # [1, 1, page, dh] one K page
    v_ref,  # [1, 1, page, dh] one V page
    o_ref,  # [1, 1, W*Gp, dh]
    acc_ref,  # VMEM [W*Gp, dh] f32 running weighted V
    m_ref,  # VMEM [W*Gp, 128] f32 running row max (lane-broadcast)
    l_ref,  # VMEM [W*Gp, 128] f32 running row sum (lane-broadcast)
    *,
    kv_heads: int,
    sm_scale: float,
    page: int,
    num_pages: int,
    window: int,
    gp: int,
):
    del bt_ref  # consumed by the index maps
    j = pl.program_id(1)  # page slot within the row's block table
    kv_len = kv_len_ref[pl.program_id(0) // kv_heads]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # A page is live iff its first slot is visible to the WIDEST window
    # position (slot W-1 sees kv_len + W - 1 keys); stale tail slots of a
    # partially live page are masked below.
    @pl.when(j * page < kv_len + (window - 1))
    def _update():
        q = q_ref[0, 0].astype(jnp.float32)  # [W*Gp, dh]
        k = k_ref[0, 0].astype(jnp.float32)  # [page, dh]
        v = v_ref[0, 0].astype(jnp.float32)
        # Same (rd, sd -> rs) contraction and scale-after-dot as the
        # reference einsum, so the logits themselves round alike.
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [W*Gp, page]
        visible = kv_len - j * page  # keys of this page slot 0 may see
        if window > 1:
            # row r belongs to window slot t = r // Gp and sees t more keys
            visible = visible + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // gp
        s = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < visible, s, NEG_INF
        )

        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        # NEG_INF is finite: a row with no visible key in this page keeps
        # m_prev and adds exp(NEG_INF - m_prev) == 0. Page 0 always holds a
        # visible key (kv_lens >= 1), so m_prev is real from then on.
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(j == num_pages - 1)
    def _finalize():
        l = l_ref[:, 0]
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-20)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_attention_varq_kernel(
    q: jax.Array,  # [B, W, H, dh] verify window, position-ordered
    k_pages: jax.Array,  # [P, kv_heads, page, dh]
    v_pages: jax.Array,  # [P, kv_heads, page, dh]
    block_tables: jax.Array,  # [B, MAXP] int32 page ids (dead entries: 0)
    kv_lens: jax.Array,  # [B] int32 t=0 visibility (cur token included)
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Pallas ragged paged-attention over a W-token window per row (see
    block comment above)."""
    b, w, h, d = q.shape
    _, kv_heads, page, _ = k_pages.shape
    maxp = block_tables.shape[1]
    g = h // kv_heads
    sm_scale = scale if scale is not None else 1.0 / math.sqrt(d)
    gp = _q_group_pad(g)
    qg = q.reshape(b, w, kv_heads, g, d)
    if gp != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, gp - g), (0, 0)))
    # Fold the window into the query-row axis: [B, kv_heads, W*Gp, dh].
    rows = w * gp
    qg = qg.transpose(0, 2, 1, 3, 4).reshape(b, kv_heads, rows, d)

    kernel = functools.partial(
        _paged_kernel,
        kv_heads=kv_heads,
        sm_scale=sm_scale,
        page=page,
        num_pages=maxp,
        window=w,
        gp=gp,
    )

    def q_map(i, j, bt, kl):
        return (i // kv_heads, i % kv_heads, 0, 0)

    def page_map(i, j, bt, kl):
        # The block table picks which page the DMA fetches — the ragged
        # indirection lives in the index map, not the kernel body.
        return (bt[i // kv_heads, j], i % kv_heads, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * kv_heads, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, rows, d), q_map),
            pl.BlockSpec((1, 1, page, d), page_map),
            pl.BlockSpec((1, 1, page, d), page_map),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((rows, d), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv_heads, rows, d), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(
        block_tables.astype(jnp.int32),
        kv_lens.astype(jnp.int32),
        qg,
        k_pages,
        v_pages,
    )
    out = out.reshape(b, kv_heads, w, gp, d)[:, :, :, :g]
    return out.transpose(0, 2, 1, 3, 4).reshape(b, w, h, d)


def paged_attention_kernel(
    q: jax.Array,  # [B, H, dh] one decode token per row
    k_pages: jax.Array,  # [P, kv_heads, page, dh]
    v_pages: jax.Array,  # [P, kv_heads, page, dh]
    block_tables: jax.Array,  # [B, MAXP] int32 page ids (dead entries: 0)
    kv_lens: jax.Array,  # [B] int32 live tokens (current token included)
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Pallas ragged paged-attention (decode): the one-token window."""
    return paged_attention_varq_kernel(
        q[:, None], k_pages, v_pages, block_tables, kv_lens,
        scale=scale, interpret=interpret,
    )[:, 0]


def paged_attention_reference(
    q: jax.Array,  # [B, H, dh]
    k_pages: jax.Array,  # [P, kv_heads, page, dh]
    v_pages: jax.Array,  # [P, kv_heads, page, dh]
    block_tables: jax.Array,  # [B, MAXP] int32
    kv_lens: jax.Array,  # [B] int32
    scale: float | None = None,
) -> jax.Array:
    """Exact XLA reference for ragged paged decode attention: gather each
    row's pages via its block table, mask slots past the row's live
    length, one-pass softmax. This is the CPU/tier-1 serving path and what
    the Pallas kernel above is held to (f32 rounding apart, see its block
    comment); the query-head group is padded like the kernel's (see
    :func:`_q_group_pad`). Contract: ``kv_lens >= 1`` per row (the engine
    always counts the just-written token; an all-dead row's output is
    unspecified on both paths)."""
    b, h, d = q.shape
    _, kv_heads, page, _ = k_pages.shape
    maxp = block_tables.shape[1]
    g = h // kv_heads
    gp = _q_group_pad(g)
    sm_scale = scale if scale is not None else 1.0 / math.sqrt(d)
    # [B, MAXP, kv_heads, page, dh] -> [B, kv_heads, MAXP*page, dh]
    k = k_pages[block_tables].transpose(0, 2, 1, 3, 4).reshape(b, kv_heads, maxp * page, d)
    v = v_pages[block_tables].transpose(0, 2, 1, 3, 4).reshape(b, kv_heads, maxp * page, d)
    qg = q.reshape(b, kv_heads, g, d).astype(jnp.float32)
    if gp != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    s = jnp.einsum(
        "bkgd,bksd->bkgs", qg, k.astype(jnp.float32), preferred_element_type=jnp.float32
    ) * sm_scale
    live = jnp.arange(maxp * page)[None, :] < kv_lens[:, None]  # [B, S]
    s = jnp.where(live[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    w = p / jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum(
        "bkgs,bksd->bkgd", w, v.astype(jnp.float32), preferred_element_type=jnp.float32
    )
    return out[:, :, :g].reshape(b, h, d).astype(q.dtype)


def paged_attention_varq_reference(
    q: jax.Array,  # [B, W, H, dh]
    k_pages: jax.Array,  # [P, kv_heads, page, dh]
    v_pages: jax.Array,  # [P, kv_heads, page, dh]
    block_tables: jax.Array,  # [B, MAXP] int32
    kv_lens: jax.Array,  # [B] int32 t=0 visibility
    scale: float | None = None,
) -> jax.Array:
    """Exact XLA reference for the verify window: same gather, same
    window-folded [W*Gp, S] logits matrix and per-slot causal mask as the
    kernel, one-pass softmax. Slot t equals :func:`paged_attention_reference`
    at ``kv_lens + t`` bitwise (tests pin it), which is what keeps verified
    drafts token-identical to sequential decode on the reference path."""
    b, w, h, d = q.shape
    _, kv_heads, page, _ = k_pages.shape
    maxp = block_tables.shape[1]
    g = h // kv_heads
    gp = _q_group_pad(g)
    sm_scale = scale if scale is not None else 1.0 / math.sqrt(d)
    k = k_pages[block_tables].transpose(0, 2, 1, 3, 4).reshape(b, kv_heads, maxp * page, d)
    v = v_pages[block_tables].transpose(0, 2, 1, 3, 4).reshape(b, kv_heads, maxp * page, d)
    qg = q.reshape(b, w, kv_heads, g, d).astype(jnp.float32)
    if gp != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, gp - g), (0, 0)))
    qg = qg.transpose(0, 2, 1, 3, 4).reshape(b, kv_heads, w * gp, d)
    s = jnp.einsum(
        "bkrd,bksd->bkrs", qg, k.astype(jnp.float32), preferred_element_type=jnp.float32
    ) * sm_scale
    t = jnp.arange(w * gp, dtype=jnp.int32) // gp  # window slot per folded row
    live = (
        jnp.arange(maxp * page, dtype=jnp.int32)[None, None, :]
        < kv_lens.astype(jnp.int32)[:, None, None] + t[None, :, None]
    )  # [B, R, S]
    s = jnp.where(live[:, None, :, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    wgt = p / jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum(
        "bkrs,bksd->bkrd", wgt, v.astype(jnp.float32), preferred_element_type=jnp.float32
    )
    out = out.reshape(b, kv_heads, w, gp, d)[:, :, :, :g]
    return out.transpose(0, 2, 1, 3, 4).reshape(b, w, h, d).astype(q.dtype)


def _paged_kernel_usable(head_dim: int) -> bool:
    force = os.environ.get("LUMEN_PAGED_KERNEL")
    if force == "0":
        _log_fallback_once("paged kernel disabled by LUMEN_PAGED_KERNEL=0")
        return False
    if head_dim > 256:
        _log_fallback_once(
            f"paged kernel: head_dim {head_dim} > 256 exceeds the VMEM tile"
        )
        return False
    if force == "1":  # tests force interpret mode on CPU
        return True
    if not _on_tpu():
        _log_fallback_once("paged kernel: backend is not TPU")
        return False
    return True


def paged_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    kv_lens: jax.Array,
    scale: float | None = None,
) -> jax.Array:
    """Dispatch: Pallas ragged paged-attention on TPU, exact XLA reference
    elsewhere (CPU tier-1 serves the reference so both paths are covered).
    ``LUMEN_PAGED_KERNEL=0`` disables the kernel; ``=1`` forces it
    (interpret mode off TPU, for tests). A 4-D ``q`` ([B, W, H, dh])
    selects the variable-query-length verify-window path (speculative
    decoding); ``kv_lens`` is then the t=0 visibility and slot t sees
    ``kv_lens + t`` keys."""
    if _paged_kernel_usable(q.shape[-1]):
        kernel = paged_attention_varq_kernel if q.ndim == 4 else paged_attention_kernel
        return kernel(
            q, k_pages, v_pages, block_tables, kv_lens,
            scale=scale, interpret=_interpret_mode(),
        )
    reference = (
        paged_attention_varq_reference if q.ndim == 4 else paged_attention_reference
    )
    return reference(q, k_pages, v_pages, block_tables, kv_lens, scale=scale)
