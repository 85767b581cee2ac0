"""``lumen_tpu.testing.model_dirs``: the seeded model directories that
``chip_smoke.py`` and the tests serve from must be what the managers'
load paths accept, at any size — checked here at the tiny cuts without a
compile (shape gates, manifests, tokenizers)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from lumen_tpu.core.model_info import load_model_info
from lumen_tpu.runtime.weights import assert_tree_shapes, load_state_dict
from lumen_tpu.testing.model_dirs import write_clip_dir, write_vlm_dir


def test_vlm_dir_round_trips_config_weights_and_vocabulary(tmp_path):
    from tokenizers import Tokenizer

    from lumen_tpu.models.vlm.convert import convert_vlm_checkpoint
    from lumen_tpu.models.vlm.modeling import VLMConfig, VLMModel

    cfg = VLMConfig.tiny()
    model_dir = write_vlm_dir(str(tmp_path), cfg, name="TinyVLM", seed=3)
    assert model_dir == str(tmp_path / "models" / "TinyVLM")
    assert load_model_info(model_dir).name == "TinyVLM"
    with open(os.path.join(model_dir, "config.json")) as f:
        assert VLMConfig.from_hf(json.load(f)) == cfg
    state = load_state_dict(model_dir)
    params = convert_vlm_checkpoint(state, None, tie_word_embeddings=True)
    init = jax.eval_shape(
        lambda: VLMModel(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32),
            jnp.zeros((1, cfg.vision.image_size, cfg.vision.image_size, 3), jnp.float32),
        )["params"]
    )
    assert_tree_shapes(params, init)
    scales = [v for k, v in state.items() if k.endswith("/scale")]
    assert scales and all(np.all(v == 1.0) for v in scales)  # norms pass signal through
    # every id decodes to a word, so generated tokens always stream as text
    tok = Tokenizer.from_file(os.path.join(model_dir, "tokenizer.json"))
    assert tok.get_vocab_size() == cfg.decoder.vocab_size
    assert tok.decode([100, 200]) == "tok100 tok200" and tok.encode("tok100 tok200").ids == [100, 200]
    # same seed, same weights; another seed, other weights
    again = load_state_dict(write_vlm_dir(str(tmp_path / "b"), cfg, seed=3))
    other = load_state_dict(write_vlm_dir(str(tmp_path / "c"), cfg, seed=4))
    key = next(k for k in state if k.endswith("/kernel"))
    assert np.array_equal(state[key], again[key]) and not np.array_equal(state[key], other[key])


def test_clip_dir_carries_dataset_and_gapless_tokenizer(tmp_path):
    from tokenizers import Tokenizer

    model_dir = write_clip_dir(str(tmp_path), "tiny", name="TinyCLIP", labels=["cat", "photo"])
    info = load_model_info(model_dir)
    assert info.name == "TinyCLIP" and info.datasets["labels"].labels == "labels.json"
    with open(os.path.join(model_dir, "labels.json")) as f:
        assert json.load(f) == ["cat", "photo"]
    assert any("visual_projection" in k for k in load_state_dict(model_dir))
    tok = Tokenizer.from_file(os.path.join(model_dir, "tokenizer.json"))
    vocab = tok.get_vocab()
    assert sorted(vocab.values()) == list(range(128))  # no holes for the library to print
    assert tok.encode("a photo of cat").ids == [1, 2, 3, 4, 127]
    # without labels there is no dataset, so no clip_classify task
    assert load_model_info(write_clip_dir(str(tmp_path / "b"), "tiny")).datasets is None
