"""Generators reproduce from a seed and never import JAX."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import cells, weights
from benchmark.photos import photo_jpeg, pool_sizes, seeded_order, tagged

ROOT = cells.ROOT
caption = cells.load_module("generators", "caption_stream")


def test_photos_and_orders_are_functions_of_the_seed():
    big = 2**31 + 12345
    assert photo_jpeg(big, 3, 96) == photo_jpeg(big, 3, 96)
    assert photo_jpeg(big, 3, 96) != photo_jpeg(big + 1, 3, 96)
    assert seeded_order(big, 1, 48) == seeded_order(big, 1, 48)
    assert sorted(seeded_order(big, 1, 48)) == list(range(48))  # the same set, another order
    assert seeded_order(big, 1, 48) != seeded_order(big + 1, 1, 48)


def test_a_tag_changes_the_bytes_and_not_the_pixels():
    import io

    import numpy as np
    from PIL import Image

    jpeg = photo_jpeg(5, 0, 64)
    a, b = tagged(jpeg, 1), tagged(jpeg, 2)
    assert a != b != jpeg
    px = lambda d: np.asarray(Image.open(io.BytesIO(d)).convert("RGB"))
    assert (px(a) == px(jpeg)).all() and (px(b) == px(jpeg)).all()


def test_every_seed_sends_the_same_sizes():
    traffic = cells._read_json(os.path.join(cells.HERE, "traffic", "import_bulk.json"))
    assert len(pool_sizes(traffic["photo_pool"])) == 48
    assert min(pool_sizes(traffic["photo_pool"])) == 640 and max(pool_sizes(traffic["photo_pool"])) == 4032


def test_instruction_and_word_ids_round_trip():
    ids = caption.instruction_ids(2**31 + 7, 60, 151936, [151646, 151645])
    assert ids == caption.instruction_ids(2**31 + 7, 60, 151936, [151646, 151645]) and len(ids) == 60
    special = {"<image>": 151646, "role_user": 1}
    text = "role_user <image> " + " ".join(f"w{i}" for i in ids)
    assert caption.words_to_ids(text, special) == [1, 151646, *ids]
    assert caption.words_to_ids("w12 garbage", special) is None


def test_vocabulary_covers_every_id_once():
    model = cells._read_json(os.path.join(cells.HERE, "configs", "rehearsal-tiny.json"))["models"]["vlm"]
    cfg, vocab = model["config"], weights.vlm_vocab(model)
    assert sorted(vocab.values()) == list(range(cfg["text_config"]["vocab_size"]))
    assert vocab["<image>"] == cfg["image_token_index"]


def test_a_special_word_outside_the_vocabulary_is_an_error():
    model = cells._read_json(os.path.join(cells.HERE, "configs", "rehearsal-tiny.json"))["models"]["vlm"]
    model["config"]["text_config"]["vocab_size"] = 4002  # a sliced vocabulary: <image> (4002) falls outside
    with pytest.raises(cells.CellError, match=r"'<image>' has id 4002 \(image_token_index\)"):
        weights.vlm_vocab(model)


def test_the_load_generator_never_imports_jax():
    traffic = cells._read_json(os.path.join(cells.HERE, "traffic", "import_bulk.json"))
    traffic.update(traffic["rehearse"])
    msgs = [{"op": "init", "generator": "bulk_embed", "traffic": traffic, "port": 1, "context": {}},
            {"op": "prepare", "seed": 2**31 + 3}, {"op": "quit"}]
    out = subprocess.run([sys.executable, os.path.join(cells.HERE, "loadgen.py")], cwd=ROOT, text=True,
                         input="".join(json.dumps(m) + "\n" for m in msgs), capture_output=True, timeout=120)
    replies = [json.loads(l) for l in out.stdout.splitlines()]
    assert [r["ok"] for r in replies] == [True, True, True], out.stderr
    assert replies[1]["photos"] == 8 and not any(r.get("jax_imported") for r in replies)


def test_tensors_are_functions_of_seed_and_name():
    import numpy as np

    a = weights.draw_tensor(7, "x.weight", (40, 8), 0.02)
    assert (a == weights.draw_tensor(7, "x.weight", (40, 8), 0.02)).all()
    assert not (a == weights.draw_tensor(8, "x.weight", (40, 8), 0.02)).all()
    assert abs(float(np.std(a.astype(np.float32))) - 0.02) < 0.004
    assert weights.init_rule("model.layers.0.input_layernorm.weight", [[".*", 0.02]]) == "ones"
    assert weights.init_rule("vision_model.pre_layrnorm.bias", [[".*", 0.02]]) == "zeros"
    assert weights.init_rule("a.q_proj.weight", [["(q_proj|k_proj)\\.weight$", 0.05], [".*", 0.02]]) == 0.05
