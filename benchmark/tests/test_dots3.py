"""The ``dots3_note`` configuration (PR 30) in the benchmark's own tests: its
reference's control and fault at rehearsal size on the CPU, its counts and
its kernels' work against hand counts of one layer of each kind, and its
configuration file against what it says of itself."""

import os

import pytest

from benchmark import cells, run
from benchmark.tests.test_control_and_faults import alter_tokens, verdict


def drive(mix: str, break_it=None, seconds: float = 2.0, config: str = "rehearsal-tiny-dots3"):
    cell = cells.Cell(f"{config}.{mix}", rehearse=True)
    bench = run.Bench(cell, rehearse=True)
    try:
        bench.setup(2**31 + 21)
        if break_it:
            break_it(bench.handle)
        result = bench.window(seconds)
        sample = bench.sample(result)
    finally:
        bench.teardown()
    return cell, bench, result, sample


# -- the dots3_note decoder and its own reference (PR 30) ------------------------
# rehearsal-tiny-dots3: 318-token prompts, past the window (33) and the
# indexer's top-k (64), a quarter of a 16-expert bank held, float32.


@pytest.fixture(scope="module")
def dots3_run():
    return drive("caption_context", seconds=3.0)


def test_dots3_sound_run_is_correct_and_the_control_is_not(dots3_run):
    ok, compared = verdict(*dots3_run)
    assert ok, compared
    ok, compared = verdict(*dots3_run, control=True)  # the reference in bfloat16, in the program's place
    assert not ok, compared


def test_dots3_an_altered_token_is_not_correct():
    ok, compared = verdict(*drive("caption_context", alter_tokens, seconds=3.0))
    assert not ok and compared["logit_gap_std"]["value"] > compared["logit_gap_std"]["limit"]


def test_dots3_the_run_passed_the_window_and_the_top_k_and_counted_its_experts(dots3_run):
    """The traffic does what the cell is for: window pages were freed behind
    the rows, the indexer ran over contexts past its top-k, and the expert
    layers counted a held share of what they routed."""
    cell, bench, result, sample = dots3_run
    (gauge,) = [g for name, g in result["after"]["gauges"].items() if name.startswith("vlm-continuous:")]
    assert gauge["window_pages_freed"] > 0 and gauge["indexer_rows"] > 0
    assert gauge["indexer_keys_scored"] > 64 * gauge["indexer_rows"]
    routed, held = gauge["moe_tokens_routed"], gauge["moe_tokens_held"]
    assert gauge["moe_layer_calls"] > 0 and 0 < held < routed  # 4 of 16 experts held
    assert 0.1 < held / routed < 0.45 and gauge["moe_experts_touched"] <= 4 * gauge["moe_layer_calls"]


# -- the dots3_note decoder (PR 30): one layer of each kind by hand ---------------

dots3 = cells.load_module("counts", "dots3")
latent = cells.load_module("rooflines", "latent_paged")
indexer = cells.load_module("rooflines", "indexer")
moe_ffn = cells.load_module("rooflines", "moe_ffn")

D3 = {"text_config": {
    "hidden_size": 8, "num_hidden_layers": 3, "layer_types": ["full_attention", "full_attention", "sliding_attention", "full_attention"],
    "first_k_dense_replace": 1, "intermediate_size": 16, "vocab_size": 10,
    "num_attention_heads": 2, "q_lora_rank": 4, "kv_lora_rank": 4, "qk_nope_head_dim": 2, "qk_rope_head_dim": 2, "v_head_dim": 2,
    "swa_num_attention_heads": 1, "swa_q_lora_rank": 4, "swa_kv_lora_rank": 6, "swa_qk_nope_head_dim": 4,
    "swa_qk_rope_head_dim": 2, "swa_v_head_dim": 2, "sliding_window_size": 3,
    "index_n_heads": 2, "index_head_dim": 4, "index_topk": 5,
    "n_routed_experts": 2, "ep_size": 4, "n_shared_experts": 1, "num_experts_per_tok": 2, "moe_intermediate_size": 6},
    "vision_config": {"hidden_size": 4, "patch_size": 2, "image_size": 4, "num_hidden_layers": 1}}


def test_dots3_layer_weights_by_hand():
    t = D3["text_config"]
    # full: q_a 8x4, q_b 4x2x4, kv_a 8x6, kv_b 4x2x4, o 2x2x8, gate 8x2 = 32+32+48+32+32+16 = 192;
    # indexer: wq_b 4x2x4, wk 8x4, weights 8x2 = 32+32+16 = 80
    assert dots3.attention_params(t, "full_attention") == 192 + 80
    # window: q_a 8x4, q_b 4x1x6, kv_a 8x8, kv_b 6x1x6, o 1x2x8, gate 8x1 = 32+24+64+36+16+8
    assert dots3.attention_params(t, "sliding_attention") == 180
    # layer 0 dense 3x8x16 = 384; layers 1, 2: router 8x8 = 64, an expert 3x8x6 = 144:
    # 2 of 8 experts held, top-2 -> 0.5 held expert a token, plus the shared one
    per_moe = 64 + 1.5 * 144
    assert dots3.matmul_params(D3) == (272 + 384) + (272 + per_moe) + (180 + per_moe)
    assert dots3.matmul_params(D3, experts_reached=2) == (272 + 384) + (272 + 64 + 3 * 144) + (180 + 64 + 3 * 144)


def test_dots3_attention_counts_the_keys_a_layer_attends_to():
    t = D3["text_config"]
    # full, 4 keys (under the top-k of 5): 2 x 4 keys x 2 heads x (2 + 2 + 2), no indexer pass
    assert dots3.attention_flops(t, "full_attention", 4) == 96
    # full, 9 keys: 5 selected -> 2 x 5 x 2 x 6 = 120, and the indexer over all 9: 2 x 9 x 2 heads x (4 + 1) = 180
    assert dots3.attention_flops(t, "full_attention", 9) == 120 + 180
    # window, 9 keys: 3 in the window x 1 head x (4 + 2 + 2) x 2
    assert dots3.attention_flops(t, "sliding_attention", 9) == 48
    assert dots3.decode_token_flops(D3, 9) == 2 * dots3.matmul_params(D3) + 2 * 300 + 48 + 2 * 8 * 10
    # a prompt of 2 tokens: contexts 1 and 2 in every layer, the head once
    two = sum(dots3.attention_flops(t, k, c) for k in t["layer_types"][:3] for c in (1, 2))
    assert dots3.prefill_flops(D3, 2) == 2 * 2 * dots3.matmul_params(D3) + two + 160


def test_dots3_decode_step_bytes():
    t = D3["text_config"]
    # cache a row reads at context 9: full = 5 selected x (4 + 2) x 2 B + 9 index keys x 4 x 2 B = 60 + 72;
    # window = 3 x (6 + 2) x 2 B = 48
    assert dots3.cache_bytes_read(t, "full_attention", 9) == 132 and dots3.cache_bytes_read(t, "sliding_attention", 9) == 48
    # one row touches 2 x (1 - (1 - 2/8)^1) = 0.5 held experts a layer
    weights = (272 + 384) + (272 + 64 + 1.5 * 144) + (180 + 64 + 1.5 * 144)
    assert dots3.decode_step_bytes(D3, 1, 9, 2) == pytest.approx(weights * 2 + 8 * 10 * 2 + 2 * 132 + 48)
    # many rows touch both held experts
    assert dots3.experts_touched(dots3.dims(D3), 64) == pytest.approx(2.0, abs=1e-6)


def test_the_new_kernels_work_and_rooflines():
    # absorbed: 3 rows x 2 heads x 5 keys x 2 x ((4 + 2) + 4) = 600; cache 3 x 5 x 6 x 2 B = 180, io 3 x 2 x 10 x 2 B = 120
    assert latent.work(3, 5, 2, 4, 2) == {"flops": 600.0, "bytes": 300.0}
    t = D3["text_config"]
    # 6 calls of a step over (full, full, window): 4 full at min(9, 5) keys, 2 window at 3 keys
    w = latent.cell_work(t, 3, 9, 6)
    assert w["flops"] == 4 * 600 + 2 * (3 * 1 * 3 * 2 * ((6 + 2) + 6))
    # indexer: 3 rows x 9 keys x 2 heads x (2 x 4 + 2) = 540 a call
    assert indexer.cell_work(t, 3, 9, 2)["flops"] == 2 * 540
    # experts: 10 assignments through three 8x6 matrices, 2 experts read
    m = moe_ffn.work(10, 2, 8, 6)
    assert m["flops"] == 10 * 2 * 3 * 48 and m["bytes"] == 2 * 3 * 48 * 2 + 10 * (16 + 18) * 2
    assert latent.least_seconds(w, 1e3, 1e12)[1] == "compute"


def test_the_dots3_configuration_file_is_the_catalogs_config_cut_as_it_says():
    cfg = cells._read_json(os.path.join(cells.HERE, "configs", "hub-vitl14-dots3-note-ep8.json"))
    text = cfg["models"]["vlm"]["config"]["text_config"]
    extra = {"ep_size", "ep_rank", "bos_token_id", "eos_token_id", "pad_token_id"}
    # the top-level copy (what the driver compares with the catalog) and what the harness reads agree
    assert {k: v for k, v in text.items() if k not in extra} == {k: cfg[k] for k in text if k not in extra}
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert {k: cfg[k] for k in cfg["reduced"]} == {"num_hidden_layers": 5, "n_routed_experts": 32, "vocab_size": 19008}
    assert cfg["published"] == {"num_hidden_layers": 46, "n_routed_experts": 256, "vocab_size": 152064}
    assert text["n_routed_experts"] * text["ep_size"] == 256 and text["num_experts_per_tok"] == 8
    assert cfg["backend_settings"]["vlm"] == {"batch_size": 16, "max_seq": 4608} and cfg["env"] == {}
    for word in (cfg["models"]["vlm"]["config"]["image_token_index"], text["bos_token_id"], text["eos_token_id"]):
        assert 0 <= word < text["vocab_size"]
    # five layers = 3.89 B parameters, the decoder 4.09 B: 8.2 GB in bf16 (ISSUE 30's arithmetic)
    from benchmark import weights

    specs = weights.listing("vlm", cfg["models"]["vlm"]).tensors(cfg["models"]["vlm"]["config"])
    import numpy as np

    decoder = sum(int(np.prod(s, dtype=np.int64)) for n, s in specs if n.startswith(("model.", "lm_head")))
    assert 4.05e9 < decoder < 4.13e9
