"""Expert parallelism: a top-k routed mixture-of-experts FFN with GShard
all-to-all dispatch over an ``expert`` mesh axis.

Absent from the reference (SURVEY.md §2.8 lists EP as N/A there); built
here because the driver contract treats EP as a first-class sharding and
because the obvious growth path for the VLM family is an MoE decoder
(Qwen/Mixtral-style). TPU-native shape:

- tokens arrive sharded over the ``expert`` axis (the axis doubles as the
  data axis for the MoE block — the standard TPU layout, so the dispatch
  rides the same ICI ring in both directions);
- routing is capacity-based: each expert processes at most ``C`` tokens
  per shard, overflow drops (GShard semantics) — this keeps every shape
  static for XLA, no data-dependent gather sizes;
- dispatch/combine are einsums against a one-hot dispatch mask plus ONE
  ``all_to_all`` each way; expert FFNs run as a batched einsum over the
  device's local expert slice (dense, MXU-friendly).

Everything is differentiable; ``jax.grad`` transposes the all-to-alls
automatically.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..runtime.mesh import EXPERT_AXIS


class MoEParams(NamedTuple):
    """Weights for a routed SwiGLU expert bank.

    ``router``: [D, E] — token -> expert logits (kept fp32 for stable
    softmax, as every production MoE does).
    ``w_gate``/``w_up``: [E, D, F]; ``w_down``: [E, F, D].
    """

    router: jax.Array
    w_gate: jax.Array
    w_up: jax.Array
    w_down: jax.Array


def init_moe_params(
    key: jax.Array, d_model: int, d_ff: int, n_experts: int, dtype=jnp.float32
) -> MoEParams:
    kr, kg, ku, kd = jax.random.split(key, 4)
    scale_in = 1.0 / math.sqrt(d_model)
    scale_out = 1.0 / math.sqrt(d_ff)
    return MoEParams(
        router=(jax.random.normal(kr, (d_model, n_experts)) * scale_in).astype(
            jnp.float32
        ),
        w_gate=(jax.random.normal(kg, (n_experts, d_model, d_ff)) * scale_in).astype(dtype),
        w_up=(jax.random.normal(ku, (n_experts, d_model, d_ff)) * scale_in).astype(dtype),
        w_down=(jax.random.normal(kd, (n_experts, d_ff, d_model)) * scale_out).astype(dtype),
    )


def moe_sharding(mesh: Mesh, axis_name: str = EXPERT_AXIS) -> MoEParams:
    """Shardings matching :func:`moe_ffn`: expert banks split their leading
    (expert) dim over the axis; the router is replicated."""
    ex = NamedSharding(mesh, P(axis_name))
    return MoEParams(
        router=NamedSharding(mesh, P()), w_gate=ex, w_up=ex, w_down=ex
    )


def _topk_gates(
    x: jnp.ndarray, router: jnp.ndarray, k: int, norm_topk: bool,
    scoring: str = "softmax", select_bias: jnp.ndarray | None = None,
    routed_scale: float = 1.0, n_group: int = 1, topk_group: int = 1,
):
    """Top-k routing: ``[T, k]`` gate values + expert ids, in float32.

    ``scoring="softmax"``: softmax over the experts, then the k largest.
    ``scoring="sigmoid"``: a sigmoid score an expert; the k largest of
    ``score + select_bias`` are selected (the bias steers selection only)
    and the gate is the selected expert's own score. With ``n_group`` > 1
    the experts form that many groups of consecutive ids, a group's score is
    the sum of its two largest ``score + select_bias``, and selection is
    among the experts of the ``topk_group`` best groups alone. ``norm_topk``
    renormalises the gates over the selected; ``routed_scale`` multiplies
    them."""
    logits = x.astype(jnp.float32) @ router.astype(jnp.float32)  # [T, E]
    if n_group > 1 and scoring != "sigmoid":
        raise NotImplementedError("group-limited selection is defined for sigmoid scores")
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        ranked = scores if select_bias is None else scores + select_bias.astype(jnp.float32)
        if n_group > 1:
            t, e = ranked.shape
            group_scores = lax.top_k(ranked.reshape(t, n_group, e // n_group), 2)[0].sum(-1)
            _, kept = lax.top_k(group_scores, topk_group)  # [T, topk_group]
            keep = jax.nn.one_hot(kept, n_group, dtype=jnp.bool_).any(axis=1)  # [T, G]
            ranked = jnp.where(jnp.repeat(keep, e // n_group, axis=1), ranked, -jnp.inf)
        _, gate_idx = lax.top_k(ranked, k)
        gate_vals = jnp.take_along_axis(scores, gate_idx, axis=-1)
    elif scoring == "softmax":
        gate_vals, gate_idx = lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    else:
        raise ValueError(f"scoring must be 'softmax' or 'sigmoid', got {scoring!r}")
    if norm_topk:
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    if routed_scale != 1.0:
        gate_vals = gate_vals * routed_scale
    return gate_vals, gate_idx


def _moe_exact_local(
    params: MoEParams, x: jnp.ndarray, *, n_experts: int, k: int, norm_topk: bool,
    scoring: str = "softmax", select_bias: jnp.ndarray | None = None,
    routed_scale: float = 1.0, held: tuple[int, int] | None = None,
    token_valid: jnp.ndarray | None = None, with_stats: bool = False,
    n_group: int = 1, topk_group: int = 1,
):
    """Exact (zero-drop) single-device MoE via grouped GEMM.

    Sorts the ``T*k`` (token, choice) assignments by expert and runs the
    expert bank as three ``lax.ragged_dot`` calls — O(T*k) dispatch work
    and O(T*k*D*F) FLOPs, vs the capacity formulation whose exact variant
    needs an ``[E, T, D]`` buffer and O(T^2*E*D) one-hot einsums. This is
    the inference path that reproduces dense-gather references (HF MoE)
    token-for-token.

    ``held=(lo, hi)``: the bank in ``params`` holds experts ``lo..hi-1`` of
    ``n_experts`` (one chip's share of an expert-parallel deployment). The
    router still scores all ``n_experts``; assignments to absent experts
    sort behind the held groups, are left out of the grouped GEMMs and add
    nothing — what the chips that hold them would add. ``with_stats`` also
    returns int32 ``[routed, held, experts touched, 1]`` over the tokens
    ``token_valid`` [T] allows (all, when None).
    """
    t, d = x.shape
    gate_vals, gate_idx = _topk_gates(
        x, params.router, k, norm_topk, scoring, select_bias, routed_scale, n_group, topk_group
    )
    lo, hi = held if held is not None else (0, n_experts)
    n_held = hi - lo
    if params.w_gate.shape[0] != n_held:
        raise ValueError(f"bank holds {params.w_gate.shape[0]} experts, held range {lo}:{hi} asks {n_held}")
    e_flat = gate_idx.reshape(-1)  # [N], N = T*k; index t*k+j = (token t, choice j)
    here = (e_flat >= lo) & (e_flat < hi)
    local = jnp.where(here, e_flat - lo, n_held)  # absent experts: one group past the bank
    order = jnp.argsort(local, stable=True)
    inv = jnp.argsort(order)
    xs = jnp.repeat(x, k, axis=0)[order].astype(params.w_gate.dtype)  # [N, D]
    group_sizes = jnp.bincount(local, length=n_held + 1)[:n_held].astype(jnp.int32)
    hg = lax.ragged_dot(xs, params.w_gate, group_sizes)
    hu = lax.ragged_dot(xs, params.w_up, group_sizes)
    ys = lax.ragged_dot(jax.nn.silu(hg) * hu, params.w_down, group_sizes)  # [N, D]
    ys = jnp.where(here[:, None], ys[inv], 0).reshape(t, k, d).astype(jnp.float32)
    y = (ys * gate_vals[..., None]).sum(axis=1).astype(x.dtype)
    if not with_stats:
        return y
    valid = jnp.ones((t,), bool) if token_valid is None else token_valid.reshape(t)
    counted = here & jnp.repeat(valid, k)
    touched = jnp.bincount(jnp.where(counted, local, n_held), length=n_held + 1)[:n_held] > 0
    stats = jnp.stack([
        valid.sum() * k, counted.sum(), touched.sum(), jnp.ones((), jnp.int32)
    ]).astype(jnp.int32)
    return y, stats


def _route(
    x: jnp.ndarray,
    router: jnp.ndarray,
    n_experts: int,
    k: int,
    capacity: int,
    norm_topk: bool = True,
):
    """Top-k capacity-limited routing for ``x: [T, D]``.

    Returns ``dispatch: [T, E, C]`` one-hot (token t occupies slot c of
    expert e) and ``combine: [T, E, C]`` (same support, scaled by the
    router probability — renormalized over the top-k iff ``norm_topk``,
    matching HF's ``norm_topk_prob``).
    """
    gate_vals, gate_idx = _topk_gates(x, router, k, norm_topk)

    # Slot assignment: all rank-0 choices across tokens claim slots before
    # any rank-1 choice (primary routes never lose capacity to secondaries).
    sel = jax.nn.one_hot(gate_idx, n_experts, dtype=jnp.float32)  # [T, k, E]
    flat = sel.transpose(1, 0, 2).reshape(k * x.shape[0], n_experts)
    pos = jnp.cumsum(flat, axis=0) - 1.0  # slot index per (choice, expert)
    pos = pos.reshape(k, x.shape[0], n_experts).transpose(1, 0, 2)  # [T, k, E]
    slot = (pos * sel).sum(-1)  # [T, k] slot within the chosen expert
    fits = (slot < capacity) & (sel.sum(-1) > 0)

    slot_oh = jax.nn.one_hot(slot.astype(jnp.int32), capacity, dtype=jnp.float32)  # [T, k, C]
    choice = sel * fits[..., None]  # [T, k, E]
    dispatch = jnp.einsum("tke,tkc->tec", choice, slot_oh)
    combine = jnp.einsum("tke,tkc,tk->tec", choice, slot_oh, gate_vals)
    return dispatch, combine


def _expert_ffn(params: MoEParams, xs: jnp.ndarray) -> jnp.ndarray:
    """SwiGLU over a local expert bank: ``xs: [E_local, N, D]``."""
    gate = jnp.einsum("end,edf->enf", xs, params.w_gate)
    up = jnp.einsum("end,edf->enf", xs, params.w_up)
    act = jax.nn.silu(gate) * up
    return jnp.einsum("enf,efd->end", act, params.w_down)


def _moe_local(
    params: MoEParams,
    x: jnp.ndarray,
    *,
    n_experts: int,
    k: int,
    capacity: int,
    n_shards: int,
    axis_name: str | None,
    norm_topk: bool = True,
) -> jnp.ndarray:
    t = x.shape[0]
    dispatch, combine = _route(x, params.router, n_experts, k, capacity, norm_topk)
    buf = jnp.einsum("td,tec->ecd", x.astype(jnp.float32), dispatch)  # [E, C, D]
    buf = buf.astype(params.w_gate.dtype)

    if axis_name is not None:
        # [E, C, D] -> every device holds its E/n local experts with the
        # slots from ALL n shards: [E/n, n*C, D].
        e_local = n_experts // n_shards
        buf = lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=0, tiled=True)
        buf = buf.reshape(n_shards, e_local, capacity, buf.shape[-1])
        buf = buf.transpose(1, 0, 2, 3).reshape(e_local, n_shards * capacity, -1)
        out = _expert_ffn(params, buf)
        out = out.reshape(e_local, n_shards, capacity, -1).transpose(1, 0, 2, 3)
        out = out.reshape(n_experts, capacity, -1)
        out = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0, tiled=True)
    else:
        out = _expert_ffn(params, buf)

    y = jnp.einsum("ecd,tec->td", out.astype(jnp.float32), combine)
    return y.astype(x.dtype).reshape(t, -1)


def moe_ffn(
    params: MoEParams,
    x: jax.Array,
    mesh: Mesh | None = None,
    *,
    k: int = 2,
    capacity_factor: float | None = 1.25,
    axis_name: str = EXPERT_AXIS,
    norm_topk: bool = True,
    scoring: str = "softmax",
    select_bias: jax.Array | None = None,
    routed_scale: float = 1.0,
    held: tuple[int, int] | None = None,
    n_experts: int | None = None,
    token_valid: jax.Array | None = None,
    with_stats: bool = False,
    n_group: int = 1,
    topk_group: int = 1,
) -> jax.Array:
    """Apply the routed expert FFN to ``x: [T, D]`` (flatten [B, S, D]
    upstream).

    With a mesh, tokens and expert banks are sharded over ``axis_name``
    (``T`` and ``E`` must divide by its size) and dispatch runs via
    all-to-all; without one, the same math runs single-device (the unit
    test oracle and the 1-chip serving path).

    ``capacity_factor=None`` means EXACT routing (nothing drops) for
    parity with dense-gather implementations (HF). Single-device this
    runs the grouped-GEMM path (``lax.ragged_dot`` over expert-sorted
    assignments, O(T*k) dispatch); sharded it sets per-shard capacity to
    the local token count — the worst per-expert load, since a token's
    top-k choices are distinct experts — at an ``[E, T_local, D]`` buffer
    memory cost, so prefer a finite factor at scale.

    ``scoring`` / ``select_bias`` / ``routed_scale`` / ``n_group`` /
    ``topk_group``: the routing rule (see :func:`_topk_gates`). ``held=(lo, hi)`` with ``n_experts`` (the router's
    width): ``params`` is one chip's share of the bank, experts ``lo..hi-1``;
    the layer routes over all ``n_experts`` and computes its own experts'
    part of the result, exactly and without an exchange (single device,
    ``capacity_factor=None`` only: it is what each chip of an expert-
    parallel deployment computes before the combine). ``with_stats``
    returns ``(y, [routed, held, experts touched, 1])``.
    """
    n_experts = n_experts or params.w_gate.shape[0]
    plain = (
        scoring == "softmax" and select_bias is None and held is None and not with_stats
        and n_group == 1
    )
    if not plain:
        if capacity_factor is not None or not (
            mesh is None or axis_name not in mesh.axis_names or mesh.shape[axis_name] == 1
        ):
            raise NotImplementedError(
                "sigmoid scoring, a selection bias, groups, a held range and routing stats run the "
                "exact single-device path only (capacity_factor=None, no expert mesh axis)"
            )
        return _moe_exact_local(
            params, x, n_experts=n_experts, k=k, norm_topk=norm_topk, scoring=scoring,
            select_bias=select_bias, routed_scale=routed_scale, held=held,
            token_valid=token_valid, with_stats=with_stats, n_group=n_group, topk_group=topk_group,
        )
    if mesh is None or axis_name not in mesh.axis_names or mesh.shape[axis_name] == 1:
        if capacity_factor is None:
            return _moe_exact_local(
                params, x, n_experts=n_experts, k=k, norm_topk=norm_topk
            )
        t = x.shape[0]
        capacity = max(1, int(capacity_factor * k * t / n_experts))
        return _moe_local(
            params, x, n_experts=n_experts, k=k, capacity=capacity,
            n_shards=1, axis_name=None, norm_topk=norm_topk,
        )
    n = mesh.shape[axis_name]
    if x.shape[0] % n or n_experts % n:
        raise ValueError(
            f"tokens ({x.shape[0]}) and experts ({n_experts}) must divide by "
            f"mesh axis {axis_name!r} size {n}"
        )
    t_local = x.shape[0] // n
    capacity = t_local if capacity_factor is None else max(
        1, int(capacity_factor * k * t_local / n_experts)
    )
    inner = functools.partial(
        _moe_local, n_experts=n_experts, k=k, capacity=capacity,
        n_shards=n, axis_name=axis_name, norm_topk=norm_topk,
    )
    param_specs = MoEParams(
        router=P(), w_gate=P(axis_name), w_up=P(axis_name), w_down=P(axis_name)
    )
    return shard_map(
        inner,
        mesh=mesh,
        in_specs=(param_specs, P(axis_name)),
        out_specs=P(axis_name),
        check_vma=False,
    )(params, x)
