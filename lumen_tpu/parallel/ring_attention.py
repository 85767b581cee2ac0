"""Ring attention: exact attention over sequences sharded across a mesh axis.

Long-context support (absent from the reference — SURVEY.md §5 notes no
SP/CP anywhere; here it is first-class). Each device holds a sequence shard
of Q/K/V; K/V blocks rotate around the ring via ``ppermute`` over ICI while
a blockwise online-softmax accumulator keeps the math exact — memory per
device is O(seq/n_devices), communication overlaps with compute.

Layout: ``[batch, heads, seq_shard, head_dim]`` inside ``shard_map``; the
public wrapper takes globally-sharded ``[B, H, S, D]`` arrays.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from ..runtime.mesh import SEQ_AXIS

NEG_INF = -1e30


def _ring_attention_local(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    causal: bool,
    scale: float | None,
) -> jnp.ndarray:
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    sm_scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qf = q.astype(jnp.float32) * sm_scale

    q_pos = my_idx * sq + jnp.arange(sq)  # global positions of local queries

    acc = jnp.zeros((b, h, sq, d), jnp.float32)
    m = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, sq), jnp.float32)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(i, carry):
        k_blk, v_blk, acc, m, l = carry
        # After i rotations we hold the block originally on device (my-i) mod n.
        src = (my_idx - i) % n
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_blk.astype(jnp.float32))
        if causal:
            k_pos = src * sk + jnp.arange(sk)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32)
        )
        # Last step's rotation would be discarded; skip the collective.
        k_next, v_next = jax.lax.cond(
            i < n - 1,
            lambda kv: (
                jax.lax.ppermute(kv[0], axis_name, perm),
                jax.lax.ppermute(kv[1], axis_name, perm),
            ),
            lambda kv: kv,
            (k_blk, v_blk),
        )
        return k_next, v_next, acc_new, m_new, l_new

    _, _, acc, m, l = jax.lax.fori_loop(0, n, step, (k, v, acc, m, l))
    # Fully-masked rows (causal with padding) have l=0; emit zeros.
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    causal: bool = False,
    scale: float | None = None,
    axis_name: str = SEQ_AXIS,
) -> jax.Array:
    """Exact attention with Q/K/V sequence-sharded over ``axis_name``.

    Requires ``S % mesh.shape[axis_name] == 0`` (pad upstream). Batch and
    head dims stay unsharded here; combine with data/tensor parallelism by
    nesting this inside an outer ``shard_map``/``pjit``.
    """
    if axis_name not in mesh.axis_names:
        raise ValueError(f"mesh has no axis {axis_name!r}; axes: {mesh.axis_names}")
    spec = P(None, None, axis_name, None)
    inner = functools.partial(
        _ring_attention_local, axis_name=axis_name, causal=causal, scale=scale
    )
    return shard_map(
        inner, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )(q, k, v)
