"""The ``axk1`` configuration (PR 39) in the benchmark's own tests: its
reference's control and fault at rehearsal size on the CPU, what the run's
gauge says of the keys a query attends, its counts and its kernel's work
against hand counts of one layer, its configuration file against the
catalog and against what it says of itself (the parameter count among it),
and what a checkout without the decoder is told."""

import json
import os

import numpy as np
import pytest

from benchmark import cells, weights
from benchmark.tests.test_control_and_faults import alter_tokens, verdict
from benchmark.tests.test_dots3 import drive

TINY = "rehearsal-tiny-axk1"
MIX = "caption_context_mla"


# rehearsal-tiny-axk1: 318-token prompts, far past YaRN's 64 original
# positions, a quarter of a 16-expert bank held (one routing group of four,
# two groups kept, top-4), float32.


@pytest.fixture(scope="module")
def mla_run():
    return drive(MIX, seconds=3.0, config=TINY)


def test_axk1_sound_run_is_correct_and_the_control_is_not(mla_run):
    ok, compared = verdict(*mla_run)
    assert ok, compared
    ok, compared = verdict(*mla_run, control=True)  # the reference in bfloat16, in the program's place
    assert not ok and compared["logit_gap_mean_std"]["value"] > compared["logit_gap_mean_std"]["limit"], compared


def test_axk1_an_altered_token_is_not_correct():
    ok, compared = verdict(*drive(MIX, alter_tokens, seconds=3.0, config=TINY))
    assert not ok and compared["logit_gap_std"]["value"] > compared["logit_gap_std"]["limit"]


def test_axk1_the_gauge_counts_every_key_of_a_row_and_the_held_experts(mla_run):
    """The traffic does what the cell is for: a decode row's query attends
    its whole length (nothing cuts it: over the 318-token prompt, under it
    plus the 40 new tokens), no window page is freed and no indexer runs, and
    the expert layers counted the held quarter of what they routed."""
    cell, bench, result, sample = mla_run
    (gauge,) = [g for name, g in result["after"]["gauges"].items() if name.startswith("vlm-continuous:")]
    assert gauge["rows_stepped"] > 0 and 318 < gauge["latent_keys_sum"] / gauge["rows_stepped"] < 318 + 41
    assert not {"window_pages_freed", "indexer_rows", "indexer_keys_scored"} & set(gauge)
    routed, held = gauge["moe_tokens_routed"], gauge["moe_tokens_held"]
    assert gauge["moe_layer_calls"] > 0 and 0 < held < routed  # 4 of 16 experts held
    assert 0.1 < held / routed < 0.45 and gauge["moe_experts_touched"] <= 4 * gauge["moe_layer_calls"]
    # the reader the cell's metric names gives that ratio over the window
    spec, reader = cell.layer_metric("latent_keys_per_row")
    value = reader.read({"result": result}, spec)
    assert 318 < value < 318 + 41


# -- counts and the kernel's work: one layer by hand ----------------------------

axk1 = cells.load_module("counts", "axk1")
latent_full = cells.load_module("rooflines", "latent_full")

A = {"text_config": {
    "hidden_size": 8, "num_hidden_layers": 3, "first_k_dense_replace": 1, "intermediate_size": 16, "vocab_size": 10,
    "num_attention_heads": 2, "q_lora_rank": 4, "kv_lora_rank": 4, "qk_nope_head_dim": 2, "qk_rope_head_dim": 2,
    "v_head_dim": 2, "n_routed_experts": 2, "ep_size": 4, "n_group": 4, "topk_group": 2, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "moe_intermediate_size": 6},
    "vision_config": {"hidden_size": 4, "patch_size": 2, "image_size": 4, "num_hidden_layers": 1}}


def test_axk1_layer_weights_by_hand():
    t = A["text_config"]
    # q_a 8x4, q_b 4x2x4, kv_a 8x6, kv_b 4x2x4, o 2x2x8 = 32+32+48+32+32: no gate, no indexer
    assert axk1.attention_params(t) == 176
    # layer 0 dense 3x8x16 = 384; layers 1, 2: router 8x8 = 64, an expert 3x8x6 = 144:
    # 2 of 8 experts held, top-2 -> 0.5 held expert a token, plus the shared one
    per_moe = 64 + 1.5 * 144
    assert axk1.matmul_params(A) == (176 + 384) + 2 * (176 + per_moe)
    assert axk1.matmul_params(A, experts_reached=2) == (176 + 384) + 2 * (176 + 64 + 3 * 144)


def test_axk1_attention_counts_every_causal_key_in_every_layer():
    t = A["text_config"]
    # 9 keys: 2 x 9 x 2 heads x (2 + 2 + 2), whatever the context: nothing cuts it
    assert axk1.attention_flops(t, 9) == 216 and axk1.attention_flops(t, 9000) == 216000
    assert axk1.decode_token_flops(A, 9) == 2 * axk1.matmul_params(A) + 3 * 216 + 2 * 8 * 10
    # a prompt of 3 tokens: contexts 1, 2, 3 in each of three layers, the head once
    assert axk1.prefill_flops(A, 3) == 3 * 2 * axk1.matmul_params(A) + 3 * 24 * (1 + 2 + 3) + 160


def test_axk1_decode_step_bytes():
    t = A["text_config"]
    assert axk1.cache_bytes_read(t, 9) == 9 * (4 + 2) * 2
    # one row touches 2 x (1 - (1 - 2/8)^1) = 0.5 held experts a layer
    weights_ = (176 + 384) + 2 * (176 + 64 + 1.5 * 144)
    assert axk1.decode_step_bytes(A, 1, 9, 2) == pytest.approx(weights_ * 2 + 8 * 10 * 2 + 3 * 108)
    assert axk1.experts_touched(axk1.dims(A), 64) == pytest.approx(2.0, abs=1e-6)


def test_the_full_latent_kernels_work_is_every_key_of_every_row():
    t = A["text_config"]
    # absorbed: 3 rows x 2 heads x 9 keys x 2 x ((4 + 2) + 4) = 1,080 a call; cache 3 x 9 x 6 x 2 B, io 3 x 2 x 10 x 2 B
    assert latent_full.cell_work(t, 3, 9, 1) == {"flops": 1080.0, "bytes": 324.0 + 120.0}
    assert latent_full.cell_work(t, 3, 9, 6) == {"flops": 6 * 1080.0, "bytes": 6 * 444.0}
    # at the published widths and the cell's shapes (8 rows of 4,100 keys) memory bounds it
    pub = {"num_attention_heads": 64, "kv_lora_rank": 512, "qk_rope_head_dim": 64}
    w = latent_full.cell_work(pub, 8, 4100, 1)
    assert w["bytes"] == 8 * 4100 * 576 * 2 + 8 * 64 * 1088 * 2 and w["flops"] == 8 * 64 * 4100 * 2 * 1088
    assert latent_full.least_seconds(w, 197e12, 819e9)[1] == "bandwidth"


# -- the configuration file ------------------------------------------------------


def _file() -> dict:
    return cells._read_json(os.path.join(cells.HERE, "configs", "hub-vitl14-axk1-ep16.json"))


def test_the_axk1_configuration_file_is_the_catalogs_config_cut_as_it_says():
    cfg = _file()
    text = cfg["models"]["vlm"]["config"]["text_config"]
    extra = {"ep_size", "ep_rank", "bos_token_id", "eos_token_id", "pad_token_id"}
    # the top-level copy (what the driver compares with the catalog) and what the harness reads agree,
    # but for ep_size: the source's own 1 at the top, the deployment's 16 where the program reads it
    assert {k: v for k, v in text.items() if k not in extra} == {k: cfg[k] for k in text if k not in extra}
    assert (cfg["ep_size"], text["ep_size"], text["ep_rank"]) == (1, 16, 0)
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert {k: cfg[k] for k in cfg["reduced"]} == {"num_hidden_layers": 5, "n_routed_experts": 12, "vocab_size": 20480}
    assert cfg["published"] == {"num_hidden_layers": 61, "n_routed_experts": 192, "vocab_size": 163840}
    assert text["n_routed_experts"] * text["ep_size"] == 192 and text["num_experts_per_tok"] == 8
    assert (text["n_group"], text["topk_group"], text["first_k_dense_replace"]) == (8, 4, 1)
    assert cfg["backend_settings"]["vlm"] == {"batch_size": 16, "max_seq": 4608} and cfg["env"] == {}
    for word in (cfg["models"]["vlm"]["config"]["image_token_index"], text["bos_token_id"], text["eos_token_id"]):
        assert 0 <= word < text["vocab_size"]
    for key in ("deployment", "assumed", "limits_read"):
        assert cfg[key]
    try:
        rows = [json.loads(line) for line in open("/opt/skills/guides/model-configs/architectures.jsonl")]
    except OSError:
        return
    (pub,) = [r for r in rows if r["name"] == "A.X-K1"]
    assert cfg["source"] == cfg["models"]["vlm"]["source"] == pub["source_url"]
    differs = {k for k, v in pub["config"].items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"])  # every other key of the source, nested groups whole, as published


def test_the_listing_counts_the_parameters_the_issue_reckoned():
    """One dense and four expert layers at the published widths: attention
    101.12 M a layer; layer 0 with its dense feed-forward 497.50 M; an expert
    layer 675.04 M (router 1.38 M, shared expert 44.04 M, twelve held experts
    528.48 M); embedding and untied head at 20,480 rows 293.60 M: 3,491.3 M,
    6.98 GB in bf16 (ISSUE 39's arithmetic, to the parameter)."""
    model = _file()["models"]["vlm"]
    specs = weights.listing("vlm", model).tensors(model["config"])
    count = lambda pre: sum(int(np.prod(s, dtype=np.int64)) for n, s in specs if n.startswith(pre))
    assert count("model.layers.1.self_attn.") == 101_124_096
    assert count("model.layers.0.") == 497_500_160 and count("model.layers.4.") == 675_037_376
    assert count("model.layers.1.mlp.experts.") == 12 * 3 * 7168 * 2048
    assert count("model.embed_tokens") + count("lm_head") == 293_601_280
    assert count("model.") + count("lm_head") == 3_491_258_112
    assert not any("experts.12." in n or "attn_gate" in n or "indexer" in n for n, _ in specs)


def test_a_checkout_without_the_decoder_is_told_so_before_anything_is_written(monkeypatch):
    """``run.py`` asks for the vocabulary before it writes a checkpoint or
    touches a chip; a program whose ``from_hf`` makes a plain decoder of this
    configuration (the parent of the PR that added it) ends there."""
    from lumen_tpu.models.vlm import modeling

    model = _file()["models"]["vlm"]
    assert len(weights.vlm_vocab(model)) == 20480
    monkeypatch.setattr(modeling.VLMConfig, "from_hf",
                        classmethod(lambda cls, cfg: cls._with_tower(cfg, cfg["text_config"], {}, modeling.DecoderConfig())))
    with pytest.raises(cells.CellError, match="reads no model_type 'axk1'"):
        weights.vlm_vocab(model)
