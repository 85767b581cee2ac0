"""Pipeline parallelism: GPipe-style microbatch schedule over a ``stage``
mesh axis.

The reference has no pipeline parallelism (SURVEY.md §2.8 — its models are
small enough to replicate), but the driver contract for this framework
treats PP as first-class alongside DP/TP/SP/EP. The TPU-native shape of PP
is NOT a process-per-stage runtime with send/recv threads (the GPU
pattern): it is ONE ``shard_map``-traced program in which

1. the layer stack's parameters carry a leading ``[n_stages, ...]`` dim
   sharded over ``stage`` — each device holds only its stage's weights;
2. a ``lax.scan`` runs ``n_micro + n_stages - 1`` ticks; every tick each
   stage applies its layers to its current activation and hands the result
   to the next stage with a single ring ``ppermute`` (riding ICI);
3. stage 0 injects a fresh microbatch each tick, the last stage's outputs
   are masked/psum'd back to every device.

Because the whole schedule is traced, ``jax.grad`` through this function
yields the reverse pipeline (ppermutes transpose to the opposite ring
direction) with no extra code — PP training falls out of autodiff.

Bubble fraction is the usual ``(n_stages-1)/(n_micro+n_stages-1)``; pick
``n_micro >= 4*n_stages`` to amortize.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..runtime.mesh import STAGE_AXIS


def stack_stage_params(per_stage_params: list):
    """Stack one pytree per stage into a single pytree whose leaves carry a
    leading ``[n_stages, ...]`` dim (shard it with :func:`stage_sharding`)."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *per_stage_params)


def stage_sharding(mesh: Mesh, axis_name: str = STAGE_AXIS) -> NamedSharding:
    """Sharding for stacked stage params: leading dim over ``stage``."""
    return NamedSharding(mesh, P(axis_name))


def _pipeline_local(
    stage_params,
    microbatches: jnp.ndarray,
    *,
    stage_fn: Callable,
    n_stages: int,
    axis_name: str,
) -> jnp.ndarray:
    """Per-device body. ``stage_params`` leaves are ``[1, ...]`` (this
    stage's slice); ``microbatches`` is the full ``[n_micro, mb, ...]``
    (replicated — activations are small relative to weights, and this keeps
    the schedule free of gather logic)."""
    params = jax.tree.map(lambda l: l[0], stage_params)
    stage = lax.axis_index(axis_name)
    n_micro = microbatches.shape[0]
    ticks = n_micro + n_stages - 1

    # Pad the injection stream with zeros for the drain ticks.
    pad = jnp.zeros((n_stages - 1,) + microbatches.shape[1:], microbatches.dtype)
    inject = jnp.concatenate([microbatches, pad], axis=0)

    fwd_ring = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, mb_in):
        # Stage 0 consumes the injected microbatch; later stages consume
        # whatever the previous stage handed them last tick.
        x = jnp.where(stage == 0, mb_in, carry)
        y = stage_fn(params, x)
        handoff = lax.ppermute(y, axis_name, fwd_ring)
        return handoff, y

    carry0 = jnp.zeros_like(microbatches[0])
    _, ys = lax.scan(tick, carry0, inject)

    # Microbatch m leaves the last stage at tick m + n_stages - 1.
    outs = lax.slice_in_dim(ys, n_stages - 1, n_stages - 1 + n_micro, axis=0)
    outs = jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs))
    # Replicate the last stage's outputs to every device so callers see a
    # plain (unsharded) result.
    return lax.psum(outs, axis_name)


def pipeline_apply(
    stage_fn: Callable,
    stacked_params,
    x: jax.Array,
    mesh: Mesh,
    n_microbatches: int,
    axis_name: str = STAGE_AXIS,
) -> jax.Array:
    """Run ``x`` through ``n_stages`` pipelined applications of ``stage_fn``.

    - ``stage_fn(params, mb) -> mb_out`` applies ONE stage's layers to one
      microbatch; input and output must have identical shape/dtype (the
      activation format that flows between stages).
    - ``stacked_params``: pytree with leading ``[n_stages, ...]`` leaves
      (see :func:`stack_stage_params`), sharded over ``axis_name``.
    - ``x``: global batch ``[B, ...]`` with ``B % n_microbatches == 0``.

    Differentiable end-to-end; compose with DP/TP by nesting inside an
    outer pjit whose mesh carries the extra axes.
    """
    if axis_name not in mesh.axis_names:
        raise ValueError(f"mesh has no axis {axis_name!r}; axes: {mesh.axis_names}")
    n_stages = mesh.shape[axis_name]
    b = x.shape[0]
    if b % n_microbatches:
        raise ValueError(f"batch {b} not divisible by n_microbatches {n_microbatches}")
    n_leading = {l.shape[0] for l in jax.tree.leaves(stacked_params)}
    if n_leading != {n_stages}:
        raise ValueError(
            f"stacked params leading dims {n_leading} != n_stages {n_stages}; "
            "build them with stack_stage_params (one entry per stage)"
        )
    mbs = x.reshape((n_microbatches, b // n_microbatches) + x.shape[1:])
    inner = functools.partial(
        _pipeline_local, stage_fn=stage_fn, n_stages=n_stages, axis_name=axis_name
    )
    out = shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(),
        check_vma=False,
    )(stacked_params, mbs)
    return out.reshape((b,) + out.shape[2:])
