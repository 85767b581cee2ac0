"""The ``glm_moe_dsa`` configuration (GLM-5) in the benchmark's own
tests: its reference's control and faults at rehearsal size on the CPU, for
the served tokens and for the drafts; what the run's gauges count of the
drafts; its counts and its kernels' work at a verify window of two against
hand counts of one layer; its configuration file against the published config and
against what it says of itself (the parameter count among it); and what a
checkout without the decoder is told."""

import json
import os

import numpy as np
import pytest

from benchmark import cells, weights
from benchmark.references import vlm_glm5 as ref
from benchmark.tests.test_control_and_faults import alter_tokens, verdict
from benchmark.tests.test_dots3 import drive

TINY = "rehearsal-tiny-glm5"
MIX = "caption_context_mtp"


# rehearsal-tiny-glm5: 318-token prompts, past the indexer's top-k of 64 in
# every layer, a quarter of a 16-expert bank held, one multi-token
# prediction layer, float32.


@pytest.fixture(scope="module")
def mtp_run():
    return drive(MIX, seconds=3.0, config=TINY)


def test_glm5_sound_run_is_correct_and_the_control_is_not_on_tokens_or_drafts(mtp_run):
    ok, compared = verdict(*mtp_run)
    assert ok, compared
    assert compared["draft_gap_mean_std"]["value"] <= compared["draft_gap_mean_std"]["limit"]
    ok, compared = verdict(*mtp_run, control=True)  # the reference in bfloat16, in the program's place
    assert not ok
    for name in ("logit_gap_mean_std", "draft_gap_mean_std"):
        assert compared[name]["value"] > compared[name]["limit"], compared


def test_glm5_an_altered_token_is_not_correct():
    ok, compared = verdict(*drive(MIX, alter_tokens, seconds=3.0, config=TINY))
    assert not ok and compared["logit_gap_std"]["value"] > compared["logit_gap_std"]["limit"]


def test_glm5_an_altered_draft_is_not_correct(mtp_run):
    """Every fifth draft the program checked replaced by its neighbour: the
    served tokens stand, the drafts do not."""
    cell, bench, result, sample = mtp_run
    ok, compared = verdict(cell, bench, result, ref.fault_drafts(sample))
    assert not ok and compared["draft_gap_std"]["value"] > compared["draft_gap_std"]["limit"]
    assert compared["logit_gap_std"]["value"] <= compared["logit_gap_std"]["limit"]


def test_glm5_the_sample_carries_a_draft_for_every_token_after_the_first_it_checked(mtp_run):
    """A finished record's tokens are the served ids, then one draft a
    served id (-1 where none was checked: the first, and one after a taken
    draft); the gauges count the drafts checked and taken over the run, and
    the cell's reader gives their share."""
    cell, bench, result, sample = mtp_run
    for r in sample["requests"]:
        toks, drafts = ref.split_drafts(r)
        assert len(toks) == len(drafts) and drafts[0] == -1
        assert sum(d >= 0 for d in drafts) >= len(toks) // 2
    (gauge,) = [g for name, g in result["after"]["gauges"].items() if name.startswith("vlm-continuous:")]
    assert gauge["mtp_drafted"] > 0 and 0 <= gauge["mtp_accepted"] <= gauge["mtp_drafted"]
    spec, reader = cell.layer_metric("mtp_accept_rate")
    value = reader.read({"result": result}, spec)
    before = {n: g for n, g in result["before"]["gauges"].items() if n.startswith("vlm-continuous:")}
    assert value is not None and 0.0 <= value <= 1.0, (value, before)


# -- counts and the kernels' work: one layer by hand ----------------------------

glm5 = cells.load_module("counts", "glm5")
latent_verify = cells.load_module("rooflines", "latent_verify")
indexer_verify = cells.load_module("rooflines", "indexer_verify")

G5 = {"text_config": {
    "hidden_size": 8, "num_hidden_layers": 3, "first_k_dense_replace": 1, "intermediate_size": 16, "vocab_size": 10,
    "num_attention_heads": 2, "q_lora_rank": 4, "kv_lora_rank": 4, "qk_nope_head_dim": 2, "qk_rope_head_dim": 2,
    "v_head_dim": 2, "index_n_heads": 2, "index_head_dim": 4, "index_topk": 5, "n_routed_experts": 2, "ep_size": 4,
    "n_shared_experts": 1, "num_experts_per_tok": 2, "moe_intermediate_size": 6, "num_nextn_predict_layers": 1},
    "vision_config": {"hidden_size": 4, "patch_size": 2, "image_size": 4, "num_hidden_layers": 1}}


def test_glm5_layer_weights_by_hand():
    t = G5["text_config"]
    # q_a 8x4, q_b 4x2x4, kv_a 8x6, kv_b 4x2x4, o 2x2x8 = 176; indexer wq_b 4x2x4, wk 8x4, weights 8x2 = 80; no gate
    assert glm5.attention_params(t) == 256
    # layer 0 dense 3x8x16 = 384; layers 1, 2: router 8x8 = 64, an expert 3x8x6 = 144,
    # 0.5 held expert a token plus the shared one
    assert glm5.matmul_params(G5) == (256 + 384) + 2 * (256 + 64 + 1.5 * 144)
    # the multi-token prediction layer: eh_proj 16x8, then a layer with experts
    assert glm5.mtp_params(G5) == 128 + 256 + 64 + 1.5 * 144


def test_glm5_a_decode_token_is_a_verify_step_of_two_positions():
    t = G5["text_config"]
    # 9 keys: 5 selected -> 2 x 5 x 2 x 6 = 120, the indexer over all 9: 2 x 9 x 2 x (4 + 1) = 180
    assert glm5.attention_flops(t, 9) == 300 and glm5.attention_flops(t, 10) == 320
    per_position = lambda attn: 2 * (glm5.matmul_params(G5) + glm5.mtp_params(G5)) + 4 * attn + 2 * (2 * 8 * 10)
    # the pending token at 9 keys and its draft at 10, each through three layers, the draft layer and both heads
    assert glm5.decode_token_flops(G5, 9) == per_position(300) + per_position(320) == 12624
    # a prompt of 2 tokens: contexts 1 and 2 in four layers, the two heads once
    two = 4 * (glm5.attention_flops(t, 1) + glm5.attention_flops(t, 2))
    assert glm5.prefill_flops(G5, 2) == 2 * 2 * (glm5.matmul_params(G5) + glm5.mtp_params(G5)) + two + 2 * 160


def test_glm5_decode_step_bytes_read_once_for_both_positions():
    t = G5["text_config"]
    # at 10 keys (the draft's): 5 selected x (4 + 2) x 2 B + 10 index keys x 4 x 2 B
    assert glm5.cache_bytes_read(t, 10) == 60 + 80
    # one row is two positions: 2 x (1 - (1 - 2/8)^2) = 0.875 held experts touched a layer
    touched = 0.875 + 1
    layers = (256 + 384) + 2 * (256 + 64 + touched * 144)
    mtp = 128 + 256 + 64 + touched * 144
    assert glm5.decode_step_bytes(G5, 1, 9, 2) == pytest.approx((layers + mtp) * 2 + 8 * 10 * 2 + 4 * 140)


def test_the_verify_kernels_work_counts_two_queries_over_one_read():
    # 3 rows x 2 queries x 2 heads x 5 keys x 2 x ((4 + 2) + 4); the keys read once a row: 3 x 5 x 6 x 2 B;
    # the queries in and out: 6 x 2 heads x 10 x 2 B
    assert latent_verify.work(3, 5, 2, 4, 2) == {"flops": 1200.0, "bytes": 180.0 + 240.0}
    assert latent_verify.cell_work(G5["text_config"], 3, 9, 4)["flops"] == 4 * 1200.0  # the top-k of 5 of 9 keys
    # indexer: 3 rows x 2 queries x 9 keys x 2 heads x (2 x 4 + 2); index keys once, each query's weights and scores
    assert indexer_verify.work(3, 9, 2, 4) == {"flops": 1080.0, "bytes": 3 * (9 * 4 * 2 + 2 * (2 * 4 * 2 + 9 * 4))}
    # at the published widths and the cell's shapes (6 rows of 4,100 keys) memory bounds both
    pub = {"num_attention_heads": 64, "kv_lora_rank": 512, "qk_rope_head_dim": 64, "index_topk": 2048,
           "index_n_heads": 32, "index_head_dim": 128}
    for roof in (latent_verify, indexer_verify):
        assert roof.least_seconds(roof.cell_work(pub, 6, 4100, 1), 197e12, 819e9)[1] == "bandwidth"


# -- the configuration file ------------------------------------------------------


def _file() -> dict:
    return cells._read_json(os.path.join(cells.HERE, "configs", "hub-vitl14-glm5-ep32.json"))


def test_the_glm5_configuration_file_is_the_catalogs_config_cut_as_it_says():
    cfg = _file()
    text = cfg["models"]["vlm"]["config"]["text_config"]
    extra = {"ep_size", "ep_rank", "bos_token_id", "eos_token_id", "pad_token_id"}
    assert {k: v for k, v in text.items() if k not in extra} == {k: cfg[k] for k in text if k not in extra}
    assert (cfg["ep_size"], text["ep_size"], text["ep_rank"]) == (1, 32, 0)
    assert cfg["reduced"] == ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size"]
    assert {k: cfg[k] for k in cfg["reduced"]} == {
        "num_hidden_layers": 5, "first_k_dense_replace": 1, "n_routed_experts": 8, "vocab_size": 19360}
    assert cfg["published"] == {
        "num_hidden_layers": 78, "first_k_dense_replace": 3, "n_routed_experts": 256, "vocab_size": 154880}
    assert text["n_routed_experts"] * text["ep_size"] == 256 and text["num_nextn_predict_layers"] == 1
    assert cfg["backend_settings"]["vlm"] == {"batch_size": 16, "max_seq": 4608} and cfg["env"] == {}
    for word in (cfg["models"]["vlm"]["config"]["image_token_index"], text["bos_token_id"], text["eos_token_id"]):
        assert 0 <= word < text["vocab_size"]
    assert set(cfg["models"]["vlm"]["config"]["text_config"]) >= {"rope_interleave", "indexer_rope_interleave"}
    assert set(cfg["limits"]["vlm"]) == {"logit_gap_mean_std", "logit_gap_std", "draft_gap_mean_std", "draft_gap_std"}
    for key in ("deployment", "assumed", "limits_read"):
        assert cfg[key]
    pub = cells._read_json(os.path.join(cells.HERE, "tests", "data", "glm5_published.json"))
    assert cfg["source"] == cfg["models"]["vlm"]["source"] == pub["source_url"]
    differs = {k for k, v in pub["config"].items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"])  # every other key of the source, nested groups whole, as published


def test_the_listing_counts_the_parameters_of_the_cut():
    """One dense layer, four expert layers and the multi-token prediction
    layer at the published widths: attention 174.39 M a layer (its indexer
    9.37 M); the dense layer 400.90 M; an expert layer 515.72 M (eight held
    experts 301.99 M, the shared expert 37.75 M, the router 1.57 M); the draft
    layer 591.23 M (eh_proj 75.50 M); embedding and untied head at 19,360 rows
    237.90 M: 3,292,907,264 = 6.59 GB in bf16."""
    model = _file()["models"]["vlm"]
    specs = weights.listing("vlm", model).tensors(model["config"])
    count = lambda pre: sum(int(np.prod(s, dtype=np.int64)) for n, s in specs if n.startswith(pre))
    assert count("model.layers.1.self_attn.") == 174_394_112 and count("model.layers.1.self_attn.indexer") == 9_371_904
    assert count("model.layers.0.") == 400_898_816 and count("model.layers.4.") == 515_718_144
    assert count("model.layers.1.mlp.experts.") == 8 * 3 * 6144 * 2048
    assert count("model.layers.5.") == 591_234_048 and count("model.layers.5.eh_proj") == 75_497_472
    assert count("model.embed_tokens") + count("lm_head") == 237_895_680
    assert count("model.") + count("lm_head") == 3_292_907_264
    assert not any("experts.8." in n or "attn_gate" in n or "layers.6." in n for n, _ in specs)


def test_a_checkout_without_the_decoder_is_told_so_before_anything_is_written(monkeypatch):
    """``run.py`` asks for the vocabulary before it writes a checkpoint or
    touches a chip; a program whose ``from_hf`` makes a plain decoder of this
    configuration (the parent of the PR that added it) ends there."""
    from lumen_tpu.models.vlm import modeling

    model = _file()["models"]["vlm"]
    assert len(weights.vlm_vocab(model)) == 19360
    monkeypatch.setattr(modeling.VLMConfig, "from_hf",
                        classmethod(lambda cls, cfg: cls._with_tower(cfg, cfg["text_config"], {}, modeling.DecoderConfig())))
    with pytest.raises(cells.CellError, match="reads no model_type 'glm_moe_dsa'"):
        weights.vlm_vocab(model)
