"""Mamba-2 (state-space duality) mixer mathematics: the chunked scan of a
prefill segment and the one-token state update of a decode step.

A head ``h`` carries a state ``S`` of ``[P, N]`` values (``P`` the head's
width, ``N`` ``d_state``); with ``a = dt * A`` (``A < 0`` a head, ``dt > 0``
a token and head):

    S_t = exp(a_t) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t + D x_t

``B`` and ``C`` (``[N]`` a token) are shared by every head (one group). The
state is kept as ``[rows, N, H*P]`` float32: ``d_state`` on the sublanes and
every head's values side by side on the lanes, so a row's state is whole
(8, 128) tiles, a block of heads is a block of lanes, and both programs
below read it with plain matrix products. (HF keeps ``[rows, H, P, N]``;
the bytes are the same.)

Two callers, one mathematics:

- a prefill segment (:func:`ssd_chunk_scan`): within a block of ``chunk``
  tokens the masked ``C B^T`` product against the decays, between blocks
  the carried state. A padded position has ``dt = 0``: it neither decays
  the state nor adds to it. Pallas kernel ``ssd_chunk_scan_kernel`` on TPU
  (grid: row, block of heads, chunk; the state stays in VMEM across a
  row's chunks), the same chunked form in XLA elsewhere
  (:func:`ssd_chunk_scan_reference`);
- a decode step (:func:`ssm_state_update`): one token a row, the state read
  and written once, ``active`` rows only: a done or free slot's state is
  not touched, nor moved over the memory bus. Pallas kernel
  ``ssm_state_update_kernel`` on TPU, :func:`ssm_state_update_reference`
  elsewhere.

The depthwise causal convolution in front of the scan is plain XLA in both
(:func:`causal_conv1d`, :func:`conv1d_update`): four taps a channel.

Dispatch follows ``ops.attention.paged_attention``
(``LUMEN_PAGED_KERNEL=0`` disables the kernels, ``=1`` forces interpret
mode off TPU). The kernels' names are what the benchmark's ``ssm_scan_*``
and ``ssm_update_*`` readers match on the device's ``XLA Ops`` line
(``^ssd_chunk_scan``, ``^ssm_state_update``); ``tests/test_tpu_compile.py``
pins them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _interpret_mode, _paged_kernel_usable

#: heads a grid step of the scan kernel covers (a block of ``8 * P`` lanes)
_SCAN_HEADS = 8
#: lanes of a row's state a grid step of the update kernel covers
_UPDATE_LANES = 2048


# -- the convolution in front ---------------------------------------------------


def causal_conv1d(xbc, tail, weight, bias, n_live):
    """Depthwise causal convolution of a segment, then SiLU.

    ``xbc`` [b, S, C]: the segment's inputs; ``tail`` [b, K-1, C]: the K-1
    inputs before it (zeros at a prompt's start); ``weight`` [K, C] (tap
    K-1 meets the token itself), ``bias`` [C]; ``n_live`` [b]: the
    segment's live tokens (the rest is right padding). Returns the
    activations [b, S, C] (float32) and the new tail, taken at the last
    live token: a wholly padded segment leaves it as it was."""
    k = weight.shape[0]
    full = jnp.concatenate([tail.astype(jnp.float32), xbc.astype(jnp.float32)], axis=1)
    s = xbc.shape[1]
    w = weight.astype(jnp.float32)
    out = bias.astype(jnp.float32) + sum(full[:, j : j + s] * w[j] for j in range(k))
    new_tail = jax.vmap(
        lambda rows, n: jax.lax.dynamic_slice_in_dim(rows, n, k - 1, axis=0)
    )(full, jnp.clip(n_live.astype(jnp.int32), 0, s))
    return jax.nn.silu(out), new_tail.astype(tail.dtype)


def conv1d_update(xbc, tail, weight, bias, active):
    """One token a row through the same convolution: ``xbc`` [b, C],
    ``tail`` [b, K-1, C]. Rows not ``active`` keep their tail."""
    full = jnp.concatenate([tail.astype(jnp.float32), xbc.astype(jnp.float32)[:, None]], axis=1)
    out = bias.astype(jnp.float32) + jnp.einsum("bkc,kc->bc", full, weight.astype(jnp.float32))
    new_tail = jnp.where(active[:, None, None], full[:, 1:].astype(tail.dtype), tail)
    return jax.nn.silu(out), new_tail


# -- the chunked scan -----------------------------------------------------------


def _block_length(s: int, chunk: int) -> int:
    """Tokens a block of the scan holds: a segment shorter than ``chunk`` is
    one block of its own length rounded up to the bf16 sublane tile."""
    return min(chunk, -(-s // 16) * 16)


def _scan_operands(x, dt, a, bm, cm, block: int):
    """Pad to whole blocks and work out what depends on (token, head) alone:
    ``cs`` the running sum of ``dt * A`` within a block and ``cs_last`` its
    value at the block's end."""
    b, s, _ = x.shape
    pad = -s % block
    if pad:
        x, bm, cm = (jnp.pad(v, ((0, 0), (0, pad), (0, 0))) for v in (x, bm, cm))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))  # dt 0: a padded token moves nothing
    nc = (s + pad) // block
    dt = dt.astype(jnp.float32)
    cs = jnp.cumsum((dt * a.astype(jnp.float32)).reshape(b, nc, block, -1), axis=2)
    return x, dt, bm, cm, cs, cs[:, :, -1], nc


def ssd_chunk_scan_reference(x, dt, a, bm, cm, d, state, *, chunk: int = 256):
    """The chunked scan in XLA, operand for operand what the kernel does
    (factors rounded to the serving type, products and sums in float32). ``x`` [b, S, H*P],
    ``dt`` [b, S, H] (0 at padded positions), ``a`` [H] negative, ``bm`` /
    ``cm`` [b, S, N], ``d`` [H], ``state`` [b, N, H*P] float32. Returns
    ``y`` [b, S, H*P] in ``x``'s type and the state after the last token."""
    b, s, hp = x.shape
    h, n = a.shape[0], bm.shape[-1]
    p = hp // h
    block = _block_length(s, chunk)
    xp, dtp, bmp, cmp_, cs, cs_last, nc = _scan_operands(x, dt, a, bm, cm, block)
    f32, lo = jnp.float32, x.dtype
    rounded = lambda v: v.astype(lo).astype(f32)  # what the kernel hands the matrix unit
    xc = xp.reshape(b, nc, block, h, p).astype(f32)
    bc, cc = rounded(bmp.reshape(b, nc, block, n)), rounded(cmp_.reshape(b, nc, block, n))
    dtc = dtp.reshape(b, nc, block, h)
    g = jnp.einsum("bctn,bcsn->bcts", cc, bc)
    tri = jnp.arange(block)[:, None] >= jnp.arange(block)[None, :]
    gap = jnp.where(tri[None, None, :, :, None], cs[:, :, :, None, :] - cs[:, :, None, :, :], -jnp.inf)
    m = rounded(g[..., None] * jnp.exp(gap) * dtc[:, :, None, :, :])  # [b, nc, t, s, h]
    y = jnp.einsum("bctsh,bcshp->bcthp", m, xc)
    # what each block adds to the state, and how far it decays what came in
    w = jnp.exp(cs_last[:, :, None, :] - cs) * dtc  # [b, nc, s, h]
    add = jnp.einsum("bcsn,bcshp->bcnhp", bc, rounded(xc * w[..., None]))
    keep = jnp.exp(cs_last)  # [b, nc, h]

    def carry(st, blk):
        add_c, keep_c = blk
        return st * keep_c[:, None, :, None] + add_c, st

    last, came_in = jax.lax.scan(
        carry, state.astype(f32).reshape(b, n, h, p),
        (add.transpose(1, 0, 2, 3, 4), keep.transpose(1, 0, 2)),
    )
    came_in = came_in.transpose(1, 0, 2, 3, 4)  # [b, nc, n, h, p]: the state a block starts from
    y = y + jnp.exp(cs)[..., None] * jnp.einsum("bctn,bcnhp->bcthp", cc, rounded(came_in))
    y = y + d.astype(f32)[:, None] * xc
    return y.reshape(b, nc * block, hp)[:, :s].astype(lo), last.reshape(b, n, hp)


def _scan_kernel(
    x_ref,  # [1, L, hb*P] the block's tokens, a block of heads on the lanes
    cst_ref,  # [1, 1, L, hb] cs by token (a head's column)
    wt_ref,  # [1, 1, L, hb] exp(cs_last - cs) * dt by token
    csh_ref,  # [1, hb, L] cs by head (a head's row)
    dth_ref,  # [1, hb, L] dt by head
    b_ref,  # [1, L, N]
    c_ref,  # [1, L, N]
    bt_ref,  # [1, N, L] B transposed
    keep_ref,  # [1, 1, 1, hb*P] exp(cs_last), a head's value on each of its lanes
    d_ref,  # [1, hb*P] D likewise
    s0_ref,  # [1, N, hb*P] the state the segment starts from
    y_ref,  # [1, L, hb*P]
    s_ref,  # [1, N, hb*P] the carried state: resident over a row's chunks
    xw_ref,  # scratch [L, hb*P]: the tokens weighted for the state's update
    *,
    heads: int,
    p: int,
):
    @pl.when(pl.program_id(2) == 0)
    def _start():
        s_ref[0] = s0_ref[0]

    lo = x_ref.dtype
    length = x_ref.shape[1]
    state = s_ref[0]
    cm = c_ref[0]
    g = jax.lax.dot_general(
        cm, b_ref[0], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [L, L]: C_t . B_s
    from_state = jnp.dot(cm, state.astype(lo), preferred_element_type=jnp.float32)  # [L, hb*P]
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (length, length), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (length, length), 1)
    tri = t_idx >= s_idx
    cst, wt = cst_ref[0, 0], wt_ref[0, 0]
    csh, dth = csh_ref[0], dth_ref[0]
    for h in range(heads):
        lanes = pl.ds(h * p, p)
        col = cst[:, h : h + 1]  # [L, 1]
        gap = jnp.where(tri, col - csh[h : h + 1, :], -1e30)
        m = (g * jnp.exp(gap) * dth[h : h + 1, :]).astype(lo)
        xh = x_ref[0, :, lanes]
        xf = xh.astype(jnp.float32)
        yh = (
            jnp.dot(m, xh, preferred_element_type=jnp.float32)
            + jnp.exp(col) * from_state[:, h * p : (h + 1) * p]
            + d_ref[:, lanes] * xf
        )
        y_ref[0, :, lanes] = yh.astype(y_ref.dtype)
        xw_ref[:, lanes] = (xf * wt[:, h : h + 1]).astype(lo)
    s_ref[0] = state * keep_ref[0, 0] + jnp.dot(
        bt_ref[0], xw_ref[...], preferred_element_type=jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk_scan_kernel(x, dt, a, bm, cm, d, state, *, chunk: int = 256, interpret: bool = False):
    """Pallas chunked scan; arguments and results as
    :func:`ssd_chunk_scan_reference`."""
    b, s, hp = x.shape
    h, n = a.shape[0], bm.shape[-1]
    p = hp // h
    hb = _SCAN_HEADS if h % _SCAN_HEADS == 0 else h
    block = _block_length(s, chunk)
    xp, dtp, bmp, cmp_, cs, cs_last, nc = _scan_operands(x, dt, a, bm, cm, block)
    sp = nc * block
    f32, lo = jnp.float32, x.dtype
    cs = cs.reshape(b, sp, h)
    w = (jnp.exp(cs_last[:, :, None, :] - cs.reshape(b, nc, block, h))).reshape(b, sp, h) * dtp
    by_token = lambda v: v.reshape(b, sp, h // hb, hb).transpose(0, 2, 1, 3)  # [b, H/hb, S, hb]
    by_head = lambda v: v.transpose(0, 2, 1)  # [b, H, S]
    lanes = lambda v: jnp.repeat(v, p, axis=-1)  # a head's value on each of its lanes
    bmp, cmp_ = bmp.astype(lo), cmp_.astype(lo)
    grid = (b, h // hb, nc)
    y, last = pl.pallas_call(
        functools.partial(_scan_kernel, heads=hb, p=p),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block, hb * p), lambda i, j, c: (i, c, j)),
            pl.BlockSpec((1, 1, block, hb), lambda i, j, c: (i, j, c, 0)),
            pl.BlockSpec((1, 1, block, hb), lambda i, j, c: (i, j, c, 0)),
            pl.BlockSpec((1, hb, block), lambda i, j, c: (i, j, c)),
            pl.BlockSpec((1, hb, block), lambda i, j, c: (i, j, c)),
            pl.BlockSpec((1, block, n), lambda i, j, c: (i, c, 0)),
            pl.BlockSpec((1, block, n), lambda i, j, c: (i, c, 0)),
            pl.BlockSpec((1, n, block), lambda i, j, c: (i, 0, c)),
            pl.BlockSpec((1, 1, 1, hb * p), lambda i, j, c: (i, c, 0, j)),
            pl.BlockSpec((1, hb * p), lambda i, j, c: (0, j)),
            pl.BlockSpec((1, n, hb * p), lambda i, j, c: (i, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block, hb * p), lambda i, j, c: (i, c, j)),
            pl.BlockSpec((1, n, hb * p), lambda i, j, c: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sp, hp), lo),
            jax.ShapeDtypeStruct((b, n, hp), f32),
        ],
        scratch_shapes=[pltpu.VMEM((block, hb * p), lo)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(
        xp, by_token(cs), by_token(w), by_head(cs), by_head(dtp), bmp, cmp_,
        bmp.transpose(0, 2, 1), lanes(jnp.exp(cs_last))[:, :, None, :],
        lanes(d.astype(f32))[None, :], state.astype(f32),
    )
    return y[:, :s], last


def ssd_chunk_scan(x, dt, a, bm, cm, d, state, *, chunk: int = 256):
    """A prefill segment through the scan: the kernel on TPU, its XLA twin
    elsewhere."""
    p = x.shape[-1] // a.shape[0]
    if _paged_kernel_usable(p):
        return ssd_chunk_scan_kernel(
            x, dt, a, bm, cm, d, state, chunk=chunk, interpret=_interpret_mode()
        )
    return ssd_chunk_scan_reference(x, dt, a, bm, cm, d, state, chunk=chunk)


# -- the one-token update ---------------------------------------------------------


def _update_operands(x, dt, a, d, p: int):
    """``exp(dt * A)`` and ``dt * x`` a lane, ``D * x``: float32."""
    f32 = jnp.float32
    dt = dt.astype(f32)
    xf = x.astype(f32)
    keep = jnp.repeat(jnp.exp(dt * a.astype(f32)), p, axis=-1)  # [b, H*P]
    return keep, jnp.repeat(dt, p, axis=-1) * xf, jnp.repeat(d.astype(f32), p) * xf


def ssm_state_update_reference(x, dt, a, bm, cm, d, state, active):
    """One token a row: ``x`` [b, H*P], ``dt`` [b, H], ``bm`` / ``cm``
    [b, N], ``state`` [b, N, H*P] float32, ``active`` [b] bool. Returns
    ``y`` [b, H*P] in ``x``'s type (zero for rows not active) and the state,
    moved for the active rows only."""
    p = x.shape[-1] // a.shape[0]
    keep, dtx, skip = _update_operands(x, dt, a, d, p)
    new = state * keep[:, None, :] + bm.astype(jnp.float32)[:, :, None] * dtx[:, None, :]
    y = jnp.einsum("bn,bnl->bl", cm.astype(jnp.float32), new) + skip
    on = active[:, None]
    return jnp.where(on, y, 0.0).astype(x.dtype), jnp.where(on[:, :, None], new, state)


def _update_kernel(rows_ref, n_ref, s_ref, keep_ref, dtx_ref, b_ref, c_ref, o_ref, y_ref):
    del rows_ref  # consumed by the index maps
    i, j = pl.program_id(0), pl.program_id(1)
    n = n_ref[0]

    @pl.when(i < n)
    def _move():
        new = s_ref[0] * keep_ref[0] + b_ref[0] * dtx_ref[0]  # [N, lanes]
        o_ref[0] = new
        y_ref[0] = jnp.sum(new * c_ref[0], axis=0, keepdims=True)

    @pl.when((n == 0) & (i == 0) & (j == 0))
    def _untouched():  # no row is active: the one block the grid holds goes back as it came
        o_ref[0] = s_ref[0]
        y_ref[0] = jnp.zeros_like(y_ref[0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_state_update_kernel(x, dt, a, bm, cm, d, state, active, *, interpret: bool = False):
    """Pallas one-token update, in place on ``state`` (aliased to the
    result); arguments and results as :func:`ssm_state_update_reference`.
    The grid walks the ACTIVE rows, listed first in a prefetched order; the
    steps past them stay on the last block visited and do nothing, so a done
    or free slot's state is neither computed nor moved."""
    b, hp = x.shape
    h, n = a.shape[0], bm.shape[-1]
    p = hp // h
    lanes = _UPDATE_LANES if hp % _UPDATE_LANES == 0 else hp
    steps = hp // lanes
    keep, dtx, skip = _update_operands(x, dt, a, d, p)
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)  # active rows first
    count = active.sum().astype(jnp.int32)[None]

    def row(i, rows, cnt):
        return rows[jnp.minimum(i, jnp.maximum(cnt[0] - 1, 0))]

    def lane_block(i, j, cnt):
        return jnp.where(i < cnt[0], j, steps - 1)

    state_map = lambda i, j, rows, cnt: (row(i, rows, cnt), 0, lane_block(i, j, cnt))
    col_map = lambda i, j, rows, cnt: (row(i, rows, cnt), 0, 0)
    f32 = jnp.float32
    new, y = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, steps),
            in_specs=[
                pl.BlockSpec((1, n, lanes), state_map),
                pl.BlockSpec((1, 1, lanes), state_map),
                pl.BlockSpec((1, 1, lanes), state_map),
                pl.BlockSpec((1, n, 1), col_map),
                pl.BlockSpec((1, n, 1), col_map),
            ],
            out_specs=[
                pl.BlockSpec((1, n, lanes), state_map),
                pl.BlockSpec((1, 1, lanes), state_map),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32), jax.ShapeDtypeStruct((b, 1, hp), f32)],
        input_output_aliases={2: 0},  # the state, after the two prefetched scalars
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(
        order, count, state.astype(f32), keep[:, None, :], dtx[:, None, :],
        bm.astype(f32)[:, :, None], cm.astype(f32)[:, :, None],
    )
    y = jnp.where(active[:, None], y[:, 0] + skip, 0.0)  # rows not visited hold nothing
    return y.astype(x.dtype), new


def ssm_state_update(x, dt, a, bm, cm, d, state, active):
    """A decode step's token a row: the kernel on TPU, its XLA twin
    elsewhere."""
    p = x.shape[-1] // a.shape[0]
    if _paged_kernel_usable(p):
        return ssm_state_update_kernel(
            x, dt, a, bm, cm, d, state, active, interpret=_interpret_mode()
        )
    return ssm_state_update_reference(x, dt, a, bm, cm, d, state, active)
