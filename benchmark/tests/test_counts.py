"""FLOP and byte counts against hand-worked values at tiny shapes, and the
lookup that hands a configuration's counts to the whole-step readers."""

import os

import pytest

from benchmark import cells

paged = cells.load_module("rooflines", "paged_attn")
clip = cells.load_module("counts", "clip")
vlm = cells.load_module("counts", "vlm")

CLIP = {"projection_dim": 4, "vision_config": {"hidden_size": 8, "intermediate_size": 32, "num_hidden_layers": 2,
                                               "image_size": 4, "patch_size": 2}}
VLM = {"text_config": {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1, "intermediate_size": 16,
                       "num_hidden_layers": 3, "vocab_size": 10},
       "vision_config": {"hidden_size": 4, "patch_size": 2, "image_size": 4, "num_hidden_layers": 1}}


def test_clip_image_flops():
    # 4 patches of 12 values -> width 8: 2*4*12*8 = 768
    # a block over 5 tokens: proj 4*2*5*8*8 = 2560, attn 2*2*5*5*8 = 800, mlp 2*2*5*8*32 = 5120 -> 8480; two blocks
    # projection 2*8*4 = 64
    assert clip.image_flops(CLIP) == 768 + 2 * 8480 + 64


def test_decoder_counts():
    # head_dim 4: q 8x8, k 8x4, v 8x4, o 8x8, three 8x16 -> 64+32+32+64+384 = 576 a layer, 3 layers
    assert vlm.decoder_matmul_params(VLM) == 3 * 576
    # a token at context 10: 2*1728 + layers*2*2*10*q(8) = 3456 + 960; head 2*8*10 = 160
    assert vlm.decoder_token_flops(VLM, 10, True) == 3456 + 960 + 160
    assert vlm.decoder_token_flops(VLM, 10, False) == 3456 + 960
    assert vlm.decode_token_flops(VLM, 10) == vlm.decoder_token_flops(VLM, 10, True)


def test_decode_step_bytes():
    # weights 1728 params x 2 B + head 8*10*2 B = 3616; kv: 4 rows x 10 ctx x 3 layers x 2 x kv(4) x 2 B = 1920
    assert vlm.decode_step_bytes(VLM, 4, 10, 2) == 3616 + 1920
    assert vlm.decode_step_bytes(VLM, 4, 10, 1) == 1728 + 160 + 1920


def test_paged_attention_work_and_roofline():
    w = paged.work(rows=4, context=10, heads=2, kv_heads=1, head_dim=4, layers=3)
    assert w["flops"] == 3 * 4 * 2 * 2 * 10 * 2 * 4  # 3840
    assert w["bytes"] == 3 * (4 * 10 * 2 * 1 * 4 * 2 + 4 * 2 * 2 * 4 * 2)  # kv 640 + io 128 a layer
    seconds, bound = paged.least_seconds(w, peak_flops=1e3, peak_bytes_per_s=1e6)
    assert (seconds, bound) == (pytest.approx(3.84), "compute")
    seconds, bound = paged.least_seconds(w, peak_flops=1e12, peak_bytes_per_s=1e3)
    assert bound == "bandwidth" and seconds == pytest.approx(w["bytes"] / 1e3)


def test_peaks_table_refuses_an_unknown_device():
    assert cells.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(cells.CellError):
        cells.peaks("TPU v9 imaginary")


class _Cell:
    """As much of a ``cells.Cell`` as a whole-step reader asks for."""

    def __init__(self, config: dict, here: str = cells.HERE):
        self.config, self.here, self.traffic = config, here, {"instruction_tokens": 60}


# a window as the generator reports it (PERF.md section 5, PR 28: 126 requests, ~12,600 tokens in 40 s)
WINDOW = {"attempted": 126, "tokens_in_window": 12600, "tokens_total": 13100, "window_s": 40.0}


def _ctx(config: dict, here: str = cells.HERE) -> dict:
    return {"cell": _Cell(config, here), "result": {"client": WINDOW}, "peaks": cells.peaks("TPU v5 lite")}


def test_the_shipped_configurations_counts_are_what_the_parent_counted():
    """Recorded from ``benchmark/work.py`` at the parent commit (3dfd6be), through the lookup the readers use."""
    config = cells._read_json(os.path.join(cells.HERE, "configs", "hub-vitl14-qwen2-1p5b.json"))
    ctx = _ctx(config)
    common = cells.load_module("readers", "common")
    cc, vc = config["models"]["clip"]["config"], config["models"]["vlm"]["config"]
    assert common.counts(ctx, "clip").image_flops(cc) == 162025537536
    work = common.counts(ctx, "vlm")
    assert (work.image_flops(vc), work.prefill_flops(vc, 318), work.decode_token_flops(vc, 400.0)) == (
        52546240512, 842449502208.0, 3155951616.0)
    assert (work.decode_step_bytes(vc, 8.0, 400.0, 2), work.decode_step_bytes(vc, 8.0, 400.0, 1)) == (
        3178889216.0, 1868693504.0)
    # tower + prefill of 126 requests + 12,600 tokens at context 318 + 13100/126/2, over 40 s x 197 TFLOP/s
    flops = 126 * (52546240512 + 842449502208.0) + 12600 * work.decode_token_flops(vc, 318 + 13100 / 126 / 2)
    got = cells.load_module("readers", "vlm_step_mfu").read(ctx, {})
    assert got == pytest.approx(100.0 * flops / (40.0 * 197e12)) and 1.8 < got < 2.0


def test_a_configuration_that_names_its_counts_is_read_through_them():
    """``vlm_step_mfu`` on the same window, for the added decoder of
    ``data/added_decoder``: its own counts module, found by the name in its
    configuration file, not Qwen2's."""
    data = os.path.join(cells.HERE, "tests", "data", "added_decoder")
    config = cells._read_json(os.path.join(data, "configs", "added-moe-tiny.json"))
    vc = config["models"]["vlm"]["config"]
    ctx = _ctx(config, here=data)
    moe = cells.load_module("counts", "qwen2moe", data)
    # h 128, 4 heads of 32, 2 kv heads: attention 2*128*128 + 2*128*64 = 49152 a layer; layer 0 dense 3*128*256;
    # layers 1-3: router 128*4, shared 3*128*128 + gate 128, 2 of 4 experts of 3*128*64 each
    active = 4 * 49152 + 98304 + 3 * (512 + 49152 + 128 + 2 * 24576)
    assert moe.matmul_params(vc, 2) == active
    assert moe.decode_token_flops(vc, 10) == 2 * active + 4 * 2 * 2 * 10 * 128 + 2 * 128 * 4096
    # one row touches 2 of 4 experts, many rows all 4; 4 layers x 2 x 64 kv values x 2 B a cached token
    assert moe.decode_step_bytes(vc, 1, 10, 2) == 2 * active + 2 * 128 * 4096 + 10 * 4 * 2 * 64 * 2
    assert moe.decode_step_bytes(vc, 64, 0, 2) == pytest.approx(2 * moe.matmul_params(vc, 4) + 2 * 128 * 4096)
    prompt = 60 + 2 + 16  # instruction, two role words, a 4x4 grid of image tokens
    flops = (126 * (vlm.image_flops(vc) + moe.prefill_flops(vc, prompt))
             + 12600 * moe.decode_token_flops(vc, prompt + 13100 / 126 / 2))
    got = cells.load_module("readers", "vlm_step_mfu").read(ctx, {})
    assert got == pytest.approx(100.0 * flops / (40.0 * 197e12))
    qwen2 = 126 * (vlm.image_flops(vc) + vlm.prefill_flops(vc, prompt)) + 12600 * vlm.decode_token_flops(vc, prompt + 13100 / 126 / 2)
    assert flops != qwen2
