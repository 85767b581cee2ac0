"""FLOP and byte counts against hand-worked values at tiny shapes."""

import pytest

from benchmark import cells, work

paged = cells.load_module("rooflines", "paged_attn")

CLIP = {"projection_dim": 4, "vision_config": {"hidden_size": 8, "intermediate_size": 32, "num_hidden_layers": 2,
                                               "image_size": 4, "patch_size": 2}}
VLM = {"text_config": {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1, "intermediate_size": 16,
                       "num_hidden_layers": 3, "vocab_size": 10},
       "vision_config": {"hidden_size": 4, "patch_size": 2, "image_size": 4, "num_hidden_layers": 1}}


def test_clip_image_flops():
    # 4 patches of 12 values -> width 8: 2*4*12*8 = 768
    # a block over 5 tokens: proj 4*2*5*8*8 = 2560, attn 2*2*5*5*8 = 800, mlp 2*2*5*8*32 = 5120 -> 8480; two blocks
    # projection 2*8*4 = 64
    assert work.clip_image_flops(CLIP) == 768 + 2 * 8480 + 64


def test_decoder_counts():
    # head_dim 4: q 8x8, k 8x4, v 8x4, o 8x8, three 8x16 -> 64+32+32+64+384 = 576 a layer, 3 layers
    assert work.decoder_matmul_params(VLM) == 3 * 576
    # a token at context 10: 2*1728 + layers*2*2*10*q(8) = 3456 + 960; head 2*8*10 = 160
    assert work.decoder_token_flops(VLM, 10, True) == 3456 + 960 + 160
    assert work.decoder_token_flops(VLM, 10, False) == 3456 + 960


def test_decode_step_bytes():
    # weights 1728 params x 2 B + head 8*10*2 B = 3616; kv: 4 rows x 10 ctx x 3 layers x 2 x kv(4) x 2 B = 1920
    assert work.decode_step_bytes(VLM, 4, 10, 2) == 3616 + 1920
    assert work.decode_step_bytes(VLM, 4, 10, 1) == 1728 + 160 + 1920


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
