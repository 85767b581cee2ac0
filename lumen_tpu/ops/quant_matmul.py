"""Pallas w8a16 dequant-matmul for the weight-streaming decode path.

Why this kernel exists (measured on chip, round 5): XLA:TPU lowers the
decode-shape dequant projection ``dot(x[B,1,K], convert(s8 W[K,N]))`` to a
broadcast-multiply-REDUCE on the VPU instead of an MXU matmul — the
optimized while-body HLO for the int8 decoder carries 85 ``reduce`` ops
where the bf16 body has none, and the measured decode is ~34x slower than
bf16 (119 vs 4065 tok/s, HBM util 0.43%: the chip spends the step grinding
29M weights/step through the vector unit). The same program at batch-256
CLIP shapes lowers fine (int8 MXU), so the pathology is specific to tiny
row counts.

This kernel restores the intended cost model — stream one byte per weight
element, convert s8->bf16 in-register, feed the MXU:

    y[B, N] = (x[B, K] @ convert(W[K, N])) * scale[N]

Grid: one step per N block; the weight tile [K, block_n] streams HBM->VMEM
while the MXU consumes the previous block (pallas double-buffers block
inputs automatically). ``x`` is tiny (B<=32 rows) and stays resident.

The reference has no quantized execution at all (its ONNX sessions run
exported precision as-is, ``packages/lumen-vlm/src/lumen_vlm/backends/
onnxrt_backend.py:107-140``); this is TPU-native capability on top.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Max rows routed to this kernel: decode/serving matvec-ish shapes. Larger
#: row counts (batch embedding) already lower to the MXU via XLA.
MAX_PALLAS_ROWS = 64

#: Largest ``model``-axis size any serving mesh in this process has
#: reported (see :func:`note_mesh_model_axis`). ``pl.pallas_call`` inside a
#: GSPMD-jitted program has no sharding rule: under tensor parallelism
#: (INT8_TP_RULES shard ``q`` along ``model``) the kernel would fail to
#: partition or silently all-gather/replicate the weights it exists to
#: stream — so TP disables this route entirely and decode falls back to
#: the XLA dequant dot, which shards fine.
_MESH_MODEL_AXIS = 1


def note_mesh_model_axis(size: int) -> None:
    """Serving managers report their mesh's ``model``-axis size here at
    construction. Sticky maximum: one TP manager anywhere in the process
    disables the Pallas route for everyone — conservative, because a
    replicated sibling sharing the process cannot be told apart at trace
    time, and the fallback is merely slower, not wrong."""
    global _MESH_MODEL_AXIS
    _MESH_MODEL_AXIS = max(_MESH_MODEL_AXIS, int(size))

_SUBLANE_S8 = 32  # s8 VMEM tile is (32, 128): K must divide into sublanes
_LANES = 128


def _kernel(x_ref, q_ref, s_ref, o_ref):
    # q tile [K, block_n] s8 -> bf16 in-register; integers |w|<=127 are
    # exact in bf16 (8 mantissa bits cover 0..256).
    w = q_ref[...].astype(jnp.bfloat16)
    acc = jnp.dot(
        x_ref[...].astype(jnp.bfloat16), w, preferred_element_type=jnp.float32
    )
    o_ref[...] = (acc * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def _w8a16_2d(x, q, scale, *, block_n: int, interpret: bool):
    b, k = x.shape
    _, n = q.shape
    # scale rides as [1, N]: Mosaic rejects 1D operand blocks whose lane
    # tile disagrees with XLA's padded 1D layout (T(1024) vs T(128)).
    return pl.pallas_call(
        _kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((b, k), lambda j: (0, 0)),
            pl.BlockSpec((k, block_n), lambda j: (0, j)),
            pl.BlockSpec((1, block_n), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((b, block_n), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), x.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, q, scale.reshape(1, n))


def pallas_usable(rows: int, k: int, n: int, dtype=None) -> bool:
    """Route through the Pallas kernel? TPU backend (or forced interpret),
    decode-sized row count, tile-aligned dims, bf16 activations, and no
    tensor-parallel serving mesh in the process.

    The dtype gate is a precision contract: the kernel computes the dot in
    bf16 (weights convert s8->bf16 in-register) and applies scale in f32 —
    correct for the bf16 serving policy, but an f32 caller routed here
    would silently lose activation mantissa vs. the XLA dequant fallback,
    which computes in the caller's dtype. Both correctness gates sit BEFORE
    the ``LUMEN_Q8_PALLAS=1`` force knob: the knob forces interpret-mode
    execution off-TPU, never an unsound routing."""
    if os.environ.get("LUMEN_Q8_PALLAS") == "0":
        return False
    if _MESH_MODEL_AXIS > 1:
        return False
    if rows > MAX_PALLAS_ROWS or k % _SUBLANE_S8 or n % _LANES:
        return False
    if dtype is not None and jnp.dtype(dtype) != jnp.bfloat16:
        return False
    if os.environ.get("LUMEN_Q8_PALLAS") == "1":
        return True
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """Interpret mode only where the kernel was forced onto a non-TPU
    backend (``LUMEN_Q8_PALLAS=1``, tests)."""
    return jax.default_backend() != "tpu"


def w8a16_matmul(x: jax.Array, q: jax.Array, scale: jax.Array) -> jax.Array:
    """``(x @ convert(q)) * scale`` via the Pallas MXU kernel.

    ``x``: [..., K] activations (leading dims flattened to rows),
    ``q``: [K, N] int8 weights, ``scale``: [N] f32 per-output-channel.
    Caller gates on :func:`pallas_usable`.
    """
    k, n = q.shape
    lead = x.shape[:-1]
    rows = 1
    for d in lead:
        rows *= d
    x2 = x.reshape(rows, k)
    # Pad rows to the f32/bf16 sublane (8): pallas wants aligned blocks and
    # decode rows are small, so the pad cost is noise.
    pad = (-rows) % 8
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    block_n = 256 if n % 256 == 0 else _LANES
    y = _w8a16_2d(x2, q, scale, block_n=block_n, interpret=_interpret())
    if pad:
        y = y[:rows]
    return y.reshape(*lead, n)
