"""Fourth int8-decode probe: the REAL VLMModel decode step, bisected.

probe_q8_steps showed hand-rolled QDense math is FASTER than bf16 at every
real decoder shape — so the 34x slowdown (vlm_q8; round 5, git history) must
come from the actual model/generate structure. This times the real
bench-model decode step (same configs as bench.phase_vlm) three ways:

  step1   one jitted decode step (embed -> decoder -> logits)
  scan    the same step scanned 50x in one program (fused-decode analog)
  gen     Generator.generate end-to-end (the measured pathology)

for bf16 vs int8-dequant vs int8-dynamic params. Wherever the factor-30
appears, that's the layer to blame.
"""

from __future__ import annotations

import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from lumen_tpu.models.vlm.generate import Generator
from lumen_tpu.models.vlm.modeling import (
    DecoderConfig,
    VisionTowerConfig,
    VLMConfig,
    VLMModel,
    init_kv_cache,
)

BATCH, PROMPT, NEW = 8, 64, 32


def build(quantize: str | None, kernel: str):
    dec = DecoderConfig(
        vocab_size=32768, hidden_size=896, intermediate_size=4864,
        layers=12, heads=14, kv_heads=2,
    )
    cfg = VLMConfig(
        decoder=dec,
        vision=VisionTowerConfig(image_size=224, patch_size=32, width=256, layers=2, heads=4),
        image_token_id=dec.vocab_size - 1,
        bos_token_id=1, eos_token_id=2, pad_token_id=0,
    )
    model = VLMModel(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x, params
    )
    if quantize:
        from lumen_tpu.models.vlm.convert import quantize_decoder_int8

        cfg = dataclasses.replace(
            cfg, decoder=dataclasses.replace(
                cfg.decoder, weight_quant="int8", weight_quant_kernel=kernel
            )
        )
        model = VLMModel(cfg)
        params = quantize_decoder_int8(jax.tree.map(np.asarray, params))
        params = jax.tree.map(jnp.asarray, params)
    return model, cfg, params


def timeit(fn, reps=3):
    out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def main() -> None:
    res = {}
    for name, (qz, kern) in {
        "bf16": (None, "dequant"),
        "q8_dequant": ("int8", "dequant"),
        "q8_dynamic": ("int8", "dynamic"),
    }.items():
        model, cfg, params = build(qz, kern)
        kv_len = 128
        caches = init_kv_cache(cfg, BATCH, kv_len, jnp.bfloat16)
        cur_tok = jnp.ones((BATCH,), jnp.int32)
        cur_len = jnp.full((BATCH,), PROMPT, jnp.int32)

        @jax.jit
        def step1(params, caches, cur_tok, cur_len):
            emb = model.apply({"params": params}, cur_tok[:, None], method=VLMModel.embed_tokens)
            logits, caches = model.apply(
                {"params": params}, emb.astype(jnp.bfloat16), cur_len[:, None],
                caches, cur_len, cur_len + 1, method=VLMModel.decode,
            )
            return logits.argmax(-1)[:, 0], caches

        t_step = timeit(lambda: step1(params, caches, cur_tok, cur_len))

        @jax.jit
        def scan50(params, caches, cur_tok, cur_len):
            def body(c, _):
                caches, tok, ln = c
                emb = model.apply({"params": params}, tok[:, None], method=VLMModel.embed_tokens)
                logits, caches = model.apply(
                    {"params": params}, emb.astype(jnp.bfloat16), ln[:, None],
                    caches, ln, ln + 1, method=VLMModel.decode,
                )
                return (caches, logits.argmax(-1)[:, 0].astype(jnp.int32), ln + 1), ()

            (caches, tok, ln), _ = jax.lax.scan(
                body, (caches, cur_tok, cur_len), None, length=50
            )
            return tok

        t_scan = timeit(lambda: scan50(params, caches, cur_tok, cur_len)) / 50

        gen = Generator(model, cfg, max_seq=PROMPT + NEW, max_new_cap=NEW)
        rng0 = np.random.default_rng(0)
        embeds = jnp.asarray(
            rng0.normal(size=(BATCH, PROMPT, cfg.decoder.hidden_size)), jnp.bfloat16
        )
        positions = jnp.broadcast_to(jnp.arange(PROMPT)[None, :], (BATCH, PROMPT))
        lengths = jnp.full((BATCH,), PROMPT, jnp.int32)
        prompt_ids = jnp.ones((BATCH, PROMPT), jnp.int32)

        def run_gen():
            return gen.generate(
                params, embeds, positions, lengths, prompt_ids,
                jax.random.PRNGKey(1), max_new_tokens=NEW,
            ).tokens

        t_gen = timeit(run_gen, reps=2) / NEW

        res[name] = {
            "step1_ms": round(t_step * 1e3, 2),
            "scan_step_ms": round(t_scan * 1e3, 3),
            "gen_step_ms": round(t_gen * 1e3, 3),
        }
        print(json.dumps({name: res[name]}), flush=True)

    print(json.dumps({
        "platform": jax.devices()[0].platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
        "results": res,
    }))


if __name__ == "__main__":
    main()
