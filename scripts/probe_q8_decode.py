"""On-chip micro-probe: why is int8 VLM decode ~34x slower than bf16?

A chip session (round 5, git history) measured the fused int8 decode at
119 tok/s vs 4065 bf16 (hbm_util 0.43% — the device is idle, so some op inside the compiled
step lowers catastrophically). This probe times the isolated projection
formulations at decode shapes (batch rows x [896 -> 4864]) to attribute
the pathology:

  bf16        y = x @ w_bf16                        (control)
  dequant     y = (x @ q.astype(bf16)) * scale      (QDense mode today)
  dynamic     y = (q8(x) @ q) * sx * scale          (QDense W8A8 mode)
  predeq      q dequantized ONCE outside the loop   (isolates the convert)
  deq_f32     convert via float32 then bf16         (alt convert path)

Each variant runs a lax.scan of STEPS chained matmuls (output feeds a
reduction back into x) so the weight stream cannot be hoisted; reported
as us/step. Run under any claimed chip: python scripts/probe_q8_decode.py
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

B, DIN, DOUT, STEPS = 8, 896, 4864, 50


def bench(fn, *args):
    fn(*args)  # compile
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    reps = 5
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / (reps * STEPS) * 1e6  # us/step


def chain(proj):
    """scan STEPS steps; each step's output perturbs the next input so the
    weight read can't be CSE'd/hoisted out of the loop."""

    def step(x, _):
        y = proj(x)
        return x + jnp.tanh(y.mean(axis=-1, keepdims=True)), ()

    @jax.jit
    def run(x):
        out, _ = jax.lax.scan(step, x, None, length=STEPS)
        return out

    return run


def main() -> None:
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(B, DIN)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(DIN, DOUT)) * 0.02, jnp.bfloat16)
    scale = jnp.asarray(np.abs(rng.normal(size=(DOUT,))) * 0.01 + 1e-3, jnp.float32)
    q = jnp.asarray(rng.integers(-127, 128, size=(DIN, DOUT)), jnp.int8)
    qT = jnp.asarray(np.asarray(q).T.copy(), jnp.int8)  # [out, in]

    results: dict[str, float] = {}

    results["bf16"] = bench(chain(lambda xx: jnp.dot(xx, w)), x)

    results["dequant"] = bench(
        chain(lambda xx: jnp.dot(xx, q.astype(jnp.bfloat16)) * scale.astype(jnp.bfloat16)),
        x,
    )

    def dyn(xx):
        sx = jnp.maximum(
            jnp.max(jnp.abs(xx), axis=-1, keepdims=True).astype(jnp.float32) / 127.0, 1e-8
        )
        qx = jnp.clip(jnp.round(xx.astype(jnp.float32) / sx), -127, 127).astype(jnp.int8)
        acc = jax.lax.dot_general(
            qx, q, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        return (acc.astype(jnp.float32) * sx * scale).astype(jnp.bfloat16)

    results["dynamic"] = bench(chain(dyn), x)

    # control: dequantized once OUTSIDE the jit — pure-bf16 inner loop
    w_pre = (q.astype(jnp.bfloat16) * scale.astype(jnp.bfloat16)).block_until_ready()
    results["predeq"] = bench(chain(lambda xx: jnp.dot(xx, w_pre)), x)

    results["deq_f32"] = bench(
        chain(
            lambda xx: (
                jnp.dot(xx.astype(jnp.float32), q.astype(jnp.float32)) * scale
            ).astype(jnp.bfloat16)
        ),
        x,
    )

    # transposed weight layout: stream [out, in] int8, contract on dim 1
    results["dequant_T"] = bench(
        chain(
            lambda xx: jax.lax.dot_general(
                xx, qT.astype(jnp.bfloat16),
                dimension_numbers=(((1,), (1,)), ((), ())),
            )
            * scale.astype(jnp.bfloat16)
        ),
        x,
    )

    # int8 weights bitcast to int32 lanes, unpacked in-program via shifts:
    # tests whether the convert (not the load) is the slow part.
    qi32 = jax.lax.bitcast_convert_type(
        np.asarray(q).reshape(DIN, DOUT // 4, 4), jnp.int32
    )

    def unpack(xx):
        r = qi32[..., None] >> jnp.array([0, 8, 16, 24], jnp.int32)
        bytes_ = (r & 0xFF).astype(jnp.uint8).astype(jnp.int8)  # sign via cast below
        wlocal = bytes_.astype(jnp.int8).astype(jnp.bfloat16).reshape(DIN, DOUT)
        return jnp.dot(xx, wlocal) * scale.astype(jnp.bfloat16)

    try:
        results["unpack_i32"] = bench(chain(unpack), x)
    except Exception as e:  # noqa: BLE001
        results["unpack_i32"] = f"failed: {type(e).__name__}"

    info = {
        "platform": jax.devices()[0].platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
        "shape": f"b{B} {DIN}->{DOUT} x{STEPS} steps",
        "us_per_step": results,
    }
    print(json.dumps(info))


if __name__ == "__main__":
    main()
