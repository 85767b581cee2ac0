"""The ``granitemoehybrid`` configuration (PR 36) and the open-loop caption
mix in the benchmark's own tests: the reference's control and fault at
rehearsal size on the CPU, what the run's gauge says of the recurrent state,
the counts and the kernels' work against hand counts, the configuration file
against what it says of itself (its parameter count among it), and the
open-loop schedule."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import cells, run, weights
from benchmark.tests.test_control_and_faults import alter_tokens, verdict
from benchmark.tests.test_dots3 import drive

TINY = "rehearsal-tiny-granite"


@pytest.fixture(scope="module")
def hybrid_run():
    return drive("caption_storm_hybrid", seconds=3.0, config=TINY)


def test_granite_sound_run_is_correct_and_the_control_is_not(hybrid_run):
    ok, compared = verdict(*hybrid_run)
    assert ok, compared
    ok, compared = verdict(*hybrid_run, control=True)  # the reference in bfloat16, in the program's place
    assert not ok and compared["logit_gap_mean_std"]["value"] > compared["logit_gap_mean_std"]["limit"], compared


def test_granite_an_altered_token_is_not_correct():
    ok, compared = verdict(*drive("caption_storm_hybrid", alter_tokens, seconds=3.0, config=TINY))
    assert not ok and compared["logit_gap_std"]["value"] > compared["logit_gap_std"]["limit"]


def test_granite_the_gauge_tells_the_state_it_holds_and_counts_its_experts(hybrid_run):
    """Every admitted row installed a state, the state's bytes are the
    slots' whatever the rows' lengths, and the expert layers counted a held
    half of what they routed."""
    cell, bench, result, sample = hybrid_run
    (gauge,) = [g for name, g in result["after"]["gauges"].items() if name.startswith("vlm-continuous:")]
    t = cell.config["models"]["vlm"]["config"]["text_config"]
    inner = t["mamba_n_heads"] * t["mamba_d_head"]
    state = inner * t["mamba_d_state"] * 4 + 3 * (inner + 2 * t["mamba_d_state"]) * 4  # float32 rehearsal
    assert gauge["state_layers"] == 3 and gauge["state_bytes"] == gauge["slots_total"] * 3 * state
    assert gauge["state_installs"] == gauge["admitted"] > 0 and gauge["state_resets"] > 0
    routed, held = gauge["moe_tokens_routed"], gauge["moe_tokens_held"]
    assert gauge["moe_layer_calls"] > 0 and 0.25 < held / routed < 0.75  # 4 of 8 experts held
    assert "window_pages_freed" not in gauge and gauge["preempted"] == 0


# -- counts and kernels' work: one layer of each kind by hand ----------------------

granite = cells.load_module("counts", "granite")
ssm_scan = cells.load_module("rooflines", "ssm_scan")
ssm_update = cells.load_module("rooflines", "ssm_update")

G = {"text_config": {
    "hidden_size": 8, "num_hidden_layers": 3, "layer_types": ["mamba", "attention", "mamba", "mamba"],
    "num_attention_heads": 2, "num_key_value_heads": 1, "intermediate_size": 6, "shared_intermediate_size": 4,
    "num_local_experts": 2, "ep_size": 2, "num_experts_per_tok": 2, "vocab_size": 10,
    "mamba_n_heads": 4, "mamba_d_head": 4, "mamba_d_state": 8, "mamba_n_groups": 1, "mamba_d_conv": 4},
    "vision_config": {"hidden_size": 4, "patch_size": 2, "image_size": 4, "num_hidden_layers": 1}}


def test_granite_layer_weights_by_hand():
    d = granite.dims(G)
    # mamba: in_proj 8 x (16 + (16 + 16) + 4) = 416, out_proj 16 x 8 = 128
    assert granite.mixer_params(d, "mamba") == 544
    # attention: q and o 8 x 8 each, k and v 8 x 4 each
    assert granite.mixer_params(d, "attention") == 192
    # router 8 x 4, an expert 3 x 8 x 6 = 144 (2 of 4 held, top-2: one held expert a token), shared 3 x 8 x 4
    per_ffn = 32 + 144 + 96
    assert granite.matmul_params(G) == (544 + per_ffn) + (192 + per_ffn) + (544 + per_ffn)
    assert granite.matmul_params(G, experts_reached=2) == granite.matmul_params(G) + 3 * 144


def test_granite_token_flops_count_the_recurrence_and_the_one_attention_layer():
    d = granite.dims(G)
    # a state of 16 x 8 values: 5 operations each; the convolution 2 x 4 taps x 32 channels
    assert granite.recurrence_flops(d) == 5 * 128 + 256
    # context 9: two mamba layers and one attention layer of 2 x 2 x 9 x 8
    assert granite.mixing_flops(d, 9) == 2 * 896 + 288
    assert granite.decode_token_flops(G, 9) == 2 * granite.matmul_params(G) + 2 * 896 + 288 + 2 * 8 * 10
    assert granite.prefill_flops(G, 4) == 4 * (2 * granite.matmul_params(G) + 2 * 896 + 2 * 2 * 2 * 8) + 160


def test_granite_decode_step_bytes_count_state_both_ways_experts_touched_and_one_layers_keys():
    d = granite.dims(G)
    # a row's state in a mamba layer: 128 values in float32 and a tail of 3 x 32 in bf16
    assert granite.state_bytes(d) == 512 + 192
    # one row touches 2 x (1 - (1 - 2/4)) = 1 held expert a layer
    weights_ = (544 + 192 + 544) + 3 * (32 + 144 + 96)
    assert granite.decode_step_bytes(G, 1, 9, 2) == pytest.approx(
        weights_ * 2 + 8 * 10 * 2 + 2 * 2 * 704 + 9 * 1 * 2 * 4 * 2)
    assert granite.experts_touched(d, 64) == pytest.approx(2.0, abs=1e-6)


def test_the_ssm_kernels_work_and_rooflines():
    t = G["text_config"]
    # update: 3 rows x 128 values x 5; bytes 3 x (2 x 128 x 4 + (2 x 16 + 2 x 8) x 2 + 4 x 4)
    assert ssm_update.cell_work(t, 3, 400, 2) == {"flops": 2 * 3 * 640.0, "bytes": 2 * 3 * (1024 + 96 + 16.0)}
    # scan: 10 tokens x 128 x 5; bytes 10 x (96 + 16) + the state in and out
    assert ssm_scan.cell_work(t, 10, 3) == {"flops": 3 * 6400.0, "bytes": 3 * (1120 + 1024.0)}
    assert ssm_update.least_seconds(ssm_update.cell_work(t, 3, 400, 2), 197e12, 819e9)[1] == "bandwidth"


def test_the_granite_configuration_file_is_the_catalogs_config_cut_as_it_says():
    cfg = cells._read_json(os.path.join(cells.HERE, "configs", "hub-vitl14-granite4-h-small-ep2.json"))
    text = cfg["models"]["vlm"]["config"]["text_config"]
    extra = {"ep_size", "ep_rank", "bos_token_id", "eos_token_id", "pad_token_id", "moe_intermediate_size",
             "n_routed_experts"}
    # the top-level copy (what the driver compares with the catalog) and what the harness reads agree
    assert {k: v for k, v in text.items() if k not in extra} == {k: cfg[k] for k in text if k not in extra}
    assert (text["moe_intermediate_size"], text["n_routed_experts"]) == (text["intermediate_size"], text["num_local_experts"])
    assert cfg["reduced"] == ["num_hidden_layers", "num_local_experts", "vocab_size"]
    assert {k: cfg[k] for k in cfg["reduced"]} == {"num_hidden_layers": 10, "num_local_experts": 36, "vocab_size": 50176}
    assert cfg["published"] == {"num_hidden_layers": 40, "num_local_experts": 72, "vocab_size": 100352}
    assert text["num_local_experts"] * text["ep_size"] == 72 and text["num_experts_per_tok"] == 10
    assert text["layer_types"][:10] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4 and len(text["layer_types"]) == 40
    assert cfg["backend_settings"]["vlm"] == {"batch_size": 16, "max_seq": 2048} and cfg["env"] == {}
    for word in (cfg["models"]["vlm"]["config"]["image_token_index"], text["bos_token_id"], text["eos_token_id"]):
        assert 0 <= word < text["vocab_size"]
    for key in ("deployment", "assumed", "limits_read"):
        assert cfg[key]
    # the listing decides the count: nine Mamba layers of 121.5 M + 339.7 M held experts, one attention layer
    # of 61.1 M + 339.7 M, the tied head of 205.5 M: 4.757 B, 9.51 GB in bf16 (ISSUE 36's arithmetic)
    specs = weights.listing("vlm", cfg["models"]["vlm"]).tensors(cfg["models"]["vlm"]["config"])
    count = lambda pre: sum(int(np.prod(s, dtype=np.int64)) for n, s in specs if n.startswith(pre))
    assert count("model.") == 4_757_211_776
    assert count("model.layers.0.") == 461_203_072 and count("model.layers.5.") == 400_859_136
    assert count("model.layers.0.block_sparse_moe.input_linear") + count(
        "model.layers.0.block_sparse_moe.output_linear") == 36 * 3 * 768 * 4096


# -- the open-loop caption mix -----------------------------------------------------


def test_the_caption_schedule_is_a_poisson_process_drawn_from_the_seed():
    caption_open = cells.load_module("generators", "caption_open")
    traffic = cells._read_json(os.path.join(cells.HERE, "traffic", "caption_interactive.json"))
    big = 2**31 + 99
    a = caption_open.schedule(big, 0, 40.0, traffic, 64)
    assert a == caption_open.schedule(big, 0, 40.0, traffic, 64)
    assert a != caption_open.schedule(big + 1, 0, 40.0, traffic, 64) and a != caption_open.schedule(big, 500, 40.0, traffic, 64)
    due = [x["due"] for x in a]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 40.0
    lo, hi = traffic["new_tokens"]["min"], traffic["new_tokens"]["max"]
    assert all(lo <= x["max_new"] <= hi and 0 <= x["image"] < 64 for x in a)
    # exponential gaps at the mix's rate, lengths one by one: over many seeds the count has a Poisson's
    # mean and variance (rate x seconds both), and a window's offered tokens move by a tenth
    rate = float(traffic["rate_rps"])
    plans = [caption_open.schedule(big + i, 0, 40.0, traffic, 64) for i in range(400)]
    counts = np.array([len(p) for p in plans], float)
    assert abs(counts.mean() - rate * 40) < 2.0 and 0.7 < counts.var() / (rate * 40) < 1.3
    gaps = np.diff(np.concatenate([[x["due"] for x in p] for p in plans[:40] if len(p) > 1]))
    gaps = gaps[gaps > 0]
    assert abs(gaps.mean() * rate - 1) < 0.05 and abs(gaps.std() * rate - 1) < 0.1
    tokens = np.array([sum(x["max_new"] for x in p) for p in plans], float)
    assert 0.07 < tokens.std() / tokens.mean() < 0.13
    lengths = np.array([x["max_new"] for p in plans for x in p])
    assert lengths.min() == lo and lengths.max() == hi and abs(lengths.mean() - (lo + hi) / 2) < 1.0


def test_the_open_caption_generator_never_imports_jax():
    traffic = cells._read_json(os.path.join(cells.HERE, "traffic", "caption_interactive.json"))
    traffic.update(traffic["rehearse"])
    context = {"vocab_size": 2048, "special": {"<image>": 2000, "role_user": 1, "role_assistant": 2}}
    msgs = [{"op": "init", "generator": "caption_open", "traffic": traffic, "port": 1, "context": context},
            {"op": "prepare", "seed": 2**31 + 3}, {"op": "quit"}]
    out = subprocess.run([sys.executable, os.path.join(cells.HERE, "loadgen.py")], cwd=cells.ROOT, text=True,
                         input="".join(json.dumps(m) + "\n" for m in msgs), capture_output=True, timeout=120)
    replies = [json.loads(l) for l in out.stdout.splitlines()]
    assert [r["ok"] for r in replies] == [True, True, True], out.stderr
    assert replies[1]["images"] == 4 and not any(r.get("jax_imported") for r in replies)


def test_the_open_caption_mix_runs_and_is_correct_at_rehearsal_size():
    cell, bench, result, sample = drive("caption_interactive", seconds=4.0, config="rehearsal-tiny")
    ok, compared = verdict(cell, bench, result, sample)
    client = result["client"]
    sent = client["offered"]
    assert ok and sent == client["attempted"] > 0 and client["failed"] == 0, (compared, client["errors"])
    assert len(client["ttft_ms"]) == sent and min(client["ttft_ms"]) > 0 and len(client["lateness_ms"]) == sent
    line = run.end_to_end(cell, result, 1.0)
    assert set(line) >= {"caption_tokens_per_s", "setup_s"}
