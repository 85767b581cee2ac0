"""Every Pallas kernel of the serving path, compiled for a TPU v5e that is
described and not attached (``/opt/skills/guides/on-chip-measurement`` §2).

Interpret mode checks a kernel's arithmetic; only the chip's compiler
checks that Mosaic accepts its layouts (tile-aligned slices, VMEM budget).
Both paged-attention kernels passed every interpret-mode test while the
compiler refused them outright — these cases are the ones that would
have caught that, at no chip time: shapes, not arrays, at Qwen2-0.5B
widths (14 query / 2 KV heads, head_dim 64, hidden 896, MLP 4864,
16-token pages) and, for the paged decode kernels, at the serving
default's page and Qwen2-1.5B's heads as well, ``interpret=False``
passed explicitly because ``jax.default_backend()`` is the CPU here.
"""

from __future__ import annotations

import importlib
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

# ``lumen_tpu.ops`` re-exports the ``attention`` FUNCTION over the submodule.
att = importlib.import_module("lumen_tpu.ops.attention")
from lumen_tpu.models.vlm.paged_kv import DEFAULT_PAGE_SIZE
from lumen_tpu.ops import latent_attention as lat
from lumen_tpu.ops import quant_matmul, ssm

B, HEADS, KV_HEADS, HEAD_DIM, PAGE = 8, 14, 2, 64, 16
HIDDEN, MLP = 896, 4864
BF16, I32 = jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def v5e():
    """Sharding on one chip of a described ``v5e:2x2`` host; skips where
    the installed runtime cannot describe it."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    # An AOT executable for an absent chip is written to the persistent
    # cache but cannot be read back: the next run would warn and recompile.
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _paged(fn, q_shape, maxp, page=PAGE):
    rows, head_dim = q_shape[0], q_shape[-1]
    pages = (rows * maxp + 1, KV_HEADS, page, head_dim)
    return (
        lambda q, k, v, bt, kl: fn(q, k, v, bt, kl, interpret=False),
        [(q_shape, BF16), (pages, BF16), (pages, BF16), ((rows, maxp), I32), ((rows,), I32)],
    )


def _flash_cache(sq, sk):
    return (
        lambda q, k, v, off, valid: att.flash_attention_cache(
            q, k, v, off, valid, interpret=False
        ),
        [
            ((B, HEADS, sq, HEAD_DIM), BF16),
            ((B, HEADS, sk, HEAD_DIM), BF16),
            ((B, HEADS, sk, HEAD_DIM), BF16),
            ((B,), I32),
            ((B,), I32),
        ],
    )


def _latent(heads, c_dim, span, sel):
    """The latent decode kernel at the published ``dots3_note`` widths: 16
    rows, 64-token pages, 72 pages a row (max_seq 4,608)."""
    rows, maxp, page, rope = 16, 72, 64, 64
    shapes = [
        ((rows, heads, c_dim), BF16), ((rows, heads, rope), BF16),
        ((rows * maxp + 1, page, c_dim), BF16), ((rows * maxp + 1, page, rope), BF16),
        ((rows, maxp), I32), ((rows,), I32), ((rows,), I32),
    ]
    if sel:
        shapes.append(((rows, maxp * page), jnp.bool_))
    return (
        lambda *a: lat.latent_paged_attention_kernel(
            *a, scale=0.07, span=span, interpret=False
        ),
        shapes,
    )


def _ssm_scan(rows, tokens):
    """The prefill scan at granite-4.0-h-small's dimensions (128 heads of 64,
    d_state 128): a lane chunk, its 64-token tail, a whole prompt admitted
    in a group."""
    heads, head_dim, state = 128, 64, 128
    f32 = jnp.float32
    return (
        lambda *a: ssm.ssd_chunk_scan_kernel(*a, interpret=False),
        [((rows, tokens, heads * head_dim), BF16), ((rows, tokens, heads), f32), ((heads,), f32),
         ((rows, tokens, state), BF16), ((rows, tokens, state), BF16), ((heads,), f32),
         ((rows, state, heads * head_dim), f32)],
    )


#: the decode step as the caption cells serve it: sixteen slots of
#: Qwen2-1.5B (12 query / 2 KV heads of 128), the default page, and the
#: whole table of a max_seq of 2,048
DEFAULT_MAXP = 2048 // DEFAULT_PAGE_SIZE

CASES = {
    # 16-token pages, stated: 128 pages a row is a max_seq of 2,048; 512
    # pages is the 8,192-token row the old kernel capped at.
    "paged_decode": _paged(att.paged_attention_kernel, (B, HEADS, HEAD_DIM), 128),
    "paged_decode_8k_row": _paged(att.paged_attention_kernel, (B, HEADS, HEAD_DIM), 512),
    "paged_varq_w5": _paged(att.paged_attention_varq_kernel, (B, 5, HEADS, HEAD_DIM), 128),
    "paged_decode_default_page": _paged(
        att.paged_attention_kernel, (16, 12, 128), DEFAULT_MAXP, DEFAULT_PAGE_SIZE
    ),
    "paged_varq_w5_default_page": _paged(
        att.paged_attention_varq_kernel, (16, 5, 12, 128), DEFAULT_MAXP, DEFAULT_PAGE_SIZE
    ),
    # attention() hands the kernel nothing shorter than the crossover
    "flash_prefill_crossover": (
        lambda q, k, v: att.flash_attention(q, k, v, causal=True, interpret=False),
        [((B, HEADS, att._FLASH_CROSSOVER_SEQ, HEAD_DIM), BF16)] * 3,
    ),
    "flash_prefill_8k": (
        lambda q, k, v: att.flash_attention(q, k, v, causal=True, interpret=False),
        [((1, HEADS, 8192, HEAD_DIM), BF16)] * 3,
    ),
    "flash_unmasked_crossover": (
        lambda q, k, v: att.flash_attention(q, k, v, interpret=False),
        [((1, 16, att._FLASH_CROSSOVER_SEQ, HEAD_DIM), BF16)] * 3,
    ),
    "flash_cache_sq1": _flash_cache(1, 2048),
    "flash_cache_sq256": _flash_cache(256, 2048),
    "latent_full": _latent(128, 512, None, sel=False),
    "latent_full_selected": _latent(128, 512, None, sel=True),
    "latent_window": _latent(64, 1024, 9, sel=False),
    # a full layer without an indexer (A.X-K1: 64 heads over a 512-value latent):
    # every page of the row's table, no selection
    "latent_all_keys": _latent(64, 512, None, sel=False),
    "indexer_scores": (
        lambda q, w, k, bt: lat.indexer_scores_kernel(q, w, k, bt, interpret=False),
        [((16, 64, 128), BF16), ((16, 64), jnp.float32), ((16 * 72 + 1, 64, 128), BF16), ((16, 72), I32)],
    ),
    "ssm_scan_chunk": _ssm_scan(1, 256),
    "ssm_scan_tail": _ssm_scan(1, 64),
    "ssm_scan_group": _ssm_scan(4, 320),
    # sixteen slots' states updated in place, the active rows alone
    "ssm_update": (
        lambda *a: ssm.ssm_state_update_kernel(*a, interpret=False),
        [((16, 8192), BF16), ((16, 128), jnp.float32), ((128,), jnp.float32), ((16, 128), BF16),
         ((16, 128), BF16), ((128,), jnp.float32), ((16, 128, 8192), jnp.float32), ((16,), jnp.bool_)],
    ),
    "w8a16": (
        lambda x, q, s: quant_matmul._w8a16_2d(x, q, s, block_n=256, interpret=False),
        [((B, HIDDEN), BF16), ((HIDDEN, MLP), jnp.int8), ((MLP,), jnp.float32)],
    ),
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(v5e, name):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e) for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()  # raises what the chip's compiler would
    assert "tpu_custom_call" in compiled.as_text()


def _kernel_names(hlo: str) -> list[str]:
    """The names of a compiled program's Pallas kernels, as the benchmark's
    ``trace_reduce.stem`` reads them: number and HLO text stripped."""
    import re

    return [
        re.sub(r"\.\d+$", "", line.split(" = ", 1)[0].strip().removeprefix("ROOT ").lstrip("%"))
        for line in hlo.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line and " = " in line
    ]


@pytest.mark.parametrize(
    "name",
    ["paged_decode", "paged_varq_w5", "paged_decode_default_page", "paged_varq_w5_default_page"],
)
def test_paged_kernels_keep_the_name_the_benchmark_reads(v5e, name):
    """The benchmark's ``paged_attn_*`` metrics find the kernel on the
    device's ``XLA Ops`` line by ``^paged_attention`` on the instruction's
    name (number and HLO text stripped). A rename would null them without
    failing anything, so pin it here: the decode step's paged kernels
    compile to a ``tpu_custom_call`` instruction of that name."""
    import re

    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e) for shape, dtype in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    kernels = _kernel_names(text)
    assert kernels and all(re.search("^paged_attention", k) for k in kernels), kernels


@pytest.mark.parametrize(
    "name,pattern",
    [("latent_full_selected", "^latent_paged_attention"), ("latent_window", "^latent_paged_attention"),
     ("latent_all_keys", "^latent_paged_attention"), ("indexer_scores", "^indexer_scores")],
)
def test_latent_kernels_keep_the_names_the_benchmark_reads(v5e, name, pattern):
    """``latent_attn_roofline`` / ``latent_attn_time_pct`` (and their
    ``.full`` twins, for a decoder that attends every key) match
    ``^latent_paged_attention`` and ``indexer_roofline`` matches
    ``^indexer_scores`` on the device's ``XLA Ops`` line, as above."""
    import re

    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e) for shape, dtype in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    kernels = _kernel_names(text)
    assert kernels and all(re.search(pattern, k) for k in kernels), kernels


@pytest.mark.parametrize(
    "name,pattern",
    [("ssm_scan_chunk", "^ssd_chunk_scan"), ("ssm_scan_tail", "^ssd_chunk_scan"), ("ssm_update", "^ssm_state_update")],
)
def test_ssm_kernels_keep_the_names_the_benchmark_reads(v5e, name, pattern):
    """``ssm_scan_roofline`` / ``ssm_scan_time_pct`` match ``^ssd_chunk_scan``
    and ``ssm_update_roofline`` / ``ssm_update_time_pct`` match
    ``^ssm_state_update`` on the device's ``XLA Ops`` line, as above."""
    import re

    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e) for shape, dtype in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    kernels = _kernel_names(text)
    assert kernels and all(re.search(pattern, k) for k in kernels), kernels


@pytest.mark.parametrize("at_crossover", [False, True], ids=["257_tokens", "crossover"])
def test_clip_image_tower_route_by_sequence_length(v5e, monkeypatch, at_crossover):
    """The CLIP ``encode_images`` program at ViT-L/14 widths (1,024 wide, 16
    heads of 64, 14-px patches, two of the 24 layers): at 224 px, 257
    tokens, it holds no Mosaic call, the compiler's own attention serves
    it; a tower whose sequence reaches the crossover holds the kernel."""
    from lumen_tpu.models.clip.modeling import CLIPConfig, CLIPModel, TowerConfig

    monkeypatch.delenv("LUMEN_FLASH", raising=False)
    monkeypatch.setattr(att, "_on_tpu", lambda: True)  # default_backend() is the CPU here
    # patches a side: 16 at 224 px, else the smallest square grid that reaches the crossover
    side = next(n for n in range(16, 200) if n * n + 1 >= att._FLASH_CROSSOVER_SEQ) if at_crossover else 16
    cfg = CLIPConfig(
        embed_dim=768, image_size=14 * side, patch_size=14,
        vision=TowerConfig(1024, 2, 16), text=TowerConfig(768, 1, 12),
    )
    model = CLIPModel(cfg)
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, cfg.image_size, cfg.image_size, 3)), jnp.zeros((1, cfg.context_length), I32),
    )["params"]
    params = jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, BF16, sharding=v5e), shapes)
    rows = 1 if at_crossover else 8
    pixels = jax.ShapeDtypeStruct((rows, cfg.image_size, cfg.image_size, 3), jnp.uint8, sharding=v5e)

    def encode_images(p, pixels_u8):  # as models/clip/manager.py builds it
        x = pixels_u8.astype(jnp.float32) / 255.0
        return model.apply({"params": p}, x.astype(BF16), method=lambda m, px: m.encode_image(px))

    text = jax.jit(encode_images).lower(params, pixels).compile().as_text()
    assert ("tpu_custom_call" in text) == at_crossover


def test_a_latent_decoder_without_indexer_compiles_its_step_and_chunk_programs(v5e, monkeypatch):
    """The decode block and the 512-token lane chunk of the benchmark's
    A.X-K1 configuration at its published widths (hidden 7,168, 64 heads over
    a 512-value latent, 12 held experts of 2,048, 16 slots, ``max_seq`` 4,608),
    cut to the dense layer and one expert layer: the step holds the latent
    paged kernel under the name the ``latent_attn_*.full`` metrics match and
    nothing of an indexer; the chunk holds no kernel of the repo's (the prefix
    ladder's branches over the causal mask are XLA's)."""
    import json

    from lumen_tpu.models.vlm.generate import Generator
    from lumen_tpu.models.vlm.modeling import VLMConfig, VLMModel

    monkeypatch.setattr(att, "_on_tpu", lambda: True)  # default_backend() is the CPU here
    monkeypatch.delenv("LUMEN_PAGED_KERNEL", raising=False)
    path = os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs", "hub-vitl14-axk1-ep16.json")
    with open(path) as f:
        entry = json.load(f)
    hf = entry["models"]["vlm"]["config"]
    hf = {**hf, "text_config": {**hf["text_config"], "num_hidden_layers": 2}}
    vcfg = VLMConfig.from_hf(hf)
    model = VLMModel(vcfg)
    slots, max_seq, page = 16, entry["backend_settings"]["vlm"]["max_seq"], DEFAULT_PAGE_SIZE
    gen = Generator(model, vcfg, max_seq=max_seq, max_new_cap=512, cache_dtype=BF16)

    def on_chip(tree, floats=None):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, floats if floats and jnp.issubdtype(a.dtype, jnp.floating) else a.dtype, sharding=v5e
            ), tree,
        )

    size = vcfg.vision.image_size
    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), I32), jnp.zeros((1, size, size, 3)))
    )["params"], BF16)
    maxp = max_seq // page
    pool = on_chip(jax.eval_shape(lambda: gen.init_pool(slots, pages=slots * maxp + 1, page_size=page)))
    shape = lambda *s, dtype=I32: jax.ShapeDtypeStruct(s, dtype, sharding=v5e)
    step = jax.jit(gen._step_block_impl, static_argnames=("block",)).lower(
        params, pool, shape(slots, maxp), shape(2, dtype=jnp.uint32), block=8
    ).compile().as_text()
    kernels = _kernel_names(step)
    # the compiler's own grouped multiplications are custom calls too (``moe_ffn_roofline`` matches ``^ragged-dot``)
    assert {k for k in kernels if not k.startswith("ragged-dot")} == {"latent_paged_attention_kernel"}, kernels
    assert any(k.startswith("ragged-dot") for k in kernels)
    scratch = on_chip(jax.eval_shape(lambda: gen.new_prefill_cache(max_seq)))
    assert [sorted(layer) for layer in scratch[:2]] == [["c", "r"]] * 2  # no index key kept
    chunk = jax.jit(gen._prefill_chunk_impl).lower(
        params, scratch, shape(1, 512, vcfg.decoder.hidden_size, dtype=BF16), shape(1, 512), shape(), shape(1)
    ).compile().as_text()
    assert all(k.startswith("ragged-dot") for k in _kernel_names(chunk)), _kernel_names(chunk)
