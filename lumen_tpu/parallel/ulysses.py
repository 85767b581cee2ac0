"""Ulysses-style all-to-all sequence parallelism.

The second long-context strategy next to :mod:`.ring_attention` (the
reference has neither — SURVEY.md §5 notes no SP/CP anywhere). Where ring
attention rotates K/V blocks around the mesh and keeps an online-softmax
accumulator, the all-to-all layout swap re-shards the *heads* instead:

1. Q/K/V arrive sequence-sharded ``[B, H, S/n, D]`` per device;
2. one ``all_to_all`` per tensor swaps the sharded dim — each device now
   holds ``[B, H/n, S, D]``: the FULL sequence for a subset of heads;
3. plain (flash-eligible) attention runs locally per head group — no
   per-step collectives, no online-softmax bookkeeping;
4. one ``all_to_all`` back returns the sequence-sharded layout.

Trade-off vs ring: 2 collectives total (vs n-1 ppermutes) and the local
compute is a dense attention XLA already knows how to fuse — but heads
must be divisible by the axis size, and each device needs O(S) K/V memory
for its head group (ring keeps O(S/n)). Pick per workload: many-head
models with moderate S -> all-to-all; extreme S -> ring.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from ..ops.attention import attention
from ..runtime.mesh import SEQ_AXIS


def _ulysses_local(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    causal: bool,
    scale: float | None,
) -> jnp.ndarray:
    # [B, H, S/n, D] -> [B, H/n, S, D]: split heads, gather sequence.
    gather = functools.partial(
        jax.lax.all_to_all, axis_name=axis_name, split_axis=1, concat_axis=2, tiled=True
    )
    qh, kh, vh = gather(q), gather(k), gather(v)
    out = attention(qh, kh, vh, causal=causal, scale=scale)
    # [B, H/n, S, D] -> [B, H, S/n, D]: split sequence, regather heads.
    return jax.lax.all_to_all(
        out, axis_name=axis_name, split_axis=2, concat_axis=1, tiled=True
    )


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    causal: bool = False,
    scale: float | None = None,
    axis_name: str = SEQ_AXIS,
) -> jax.Array:
    """Exact attention with Q/K/V sequence-sharded over ``axis_name``,
    computed via the all-to-all head/sequence layout swap.

    Requires ``S % n == 0`` and ``H % n == 0`` for ``n =
    mesh.shape[axis_name]`` (pad sequence / replicate-repeat KV heads
    upstream; GQA callers should ``repeat_kv`` first so K/V carry the same
    head count as Q). Batch stays unsharded here; nest inside an outer
    ``shard_map``/``pjit`` to combine with data/tensor parallelism.
    """
    if axis_name not in mesh.axis_names:
        raise ValueError(f"mesh has no axis {axis_name!r}; axes: {mesh.axis_names}")
    n = mesh.shape[axis_name]
    if q.shape[1] % n:
        raise ValueError(
            f"heads ({q.shape[1]}) must divide by mesh axis {axis_name!r} size {n} "
            "for all-to-all sequence parallelism; use ring_attention otherwise"
        )
    spec = P(None, None, axis_name, None)
    inner = functools.partial(
        _ulysses_local, axis_name=axis_name, causal=causal, scale=scale
    )
    return shard_map(
        inner, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )(q, k, v)
