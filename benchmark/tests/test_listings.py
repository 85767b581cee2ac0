"""What must not move when a listing moves: the tensors (names, shapes,
order) and the directory name of every shipped model entry, recorded from
``benchmark/weights.py`` at the parent commit (3dfd6be, PR 28), and the
bytes of the checkpoint at rehearsal size."""

import hashlib
import json
import os

import numpy as np
import pytest

from benchmark import cells, weights

# configuration, family -> directory name, tensors, sha256 of [[name, shape], ...] in order, parameters
RECORDED = {
    ("hub-vitl14-qwen2-1p5b", "clip"): ("hub-vitl14-qwen2-1p5b.clip.b50575fdb9", 590,
                                        "9c688689a112c4019c964663039138841df670241ab1e6cc09a2ffab543db3b0", 427616513),
    ("hub-vitl14-qwen2-1p5b", "vlm"): ("hub-vitl14-qwen2-1p5b.vlm.1e260f56bc", 539,
                                       "e9647abc3430c96553d82715009ea060e9ccc8e3e924254006d6d0640f8543c0", 1641946880),
    ("hub-vith14-qwen2-1p5b-int8", "clip"): ("hub-vith14-qwen2-1p5b-int8.clip.02da2b1013", 910,
                                             "eecdd46b422b65cd990fec6a10aef440af99c678acc1023f361fc1c3a971e6b8", 986109441),
    ("hub-vith14-qwen2-1p5b-int8", "vlm"): ("hub-vith14-qwen2-1p5b-int8.vlm.1e260f56bc", 539,
                                            "e9647abc3430c96553d82715009ea060e9ccc8e3e924254006d6d0640f8543c0", 1641946880),
    ("rehearsal-tiny", "clip"): ("rehearsal-tiny.clip.0a40862da3", 142,
                                 "4bb0fe82e140f84d5d1d090a0955165f2ba651a3676240e01c73b57a6dd73cda", 1365569),
    ("rehearsal-tiny", "vlm"): ("rehearsal-tiny.vlm.5ef44fedf7", 115,
                                "3ffb6e1fd3f97c129ceb5c840effeb5e1846de02f71325f59e41ff5ce8c67233", 1587392),
}
# sha256 of the model.safetensors the parent wrote for rehearsal-tiny
RECORDED_BYTES = {"clip": "9620262166ca8ff10a7ff753aa6d8be60ba4359b55fa161fae7289c85a467c7a",
                  "vlm": "a6caa4cf48fac460139d7637d39452477d19ebc2fb97884772e53e768d05fde2"}


def _entry(config: str, family: str) -> dict:
    return cells._read_json(os.path.join(cells.HERE, "configs", f"{config}.json"))["models"][family]


@pytest.mark.parametrize("config,family", sorted(RECORDED))
def test_a_shipped_entrys_listing_and_directory_are_the_parents(config, family):
    model = _entry(config, family)
    assert "tensors" not in model and "counts" not in model  # no shipped file was edited to name them
    specs = weights.listing(family, model).tensors(model["config"])
    digest = hashlib.sha256(json.dumps([[n, list(s)] for n, s in specs]).encode()).hexdigest()
    params = sum(int(np.prod(s, dtype=np.int64)) if s else 1 for _, s in specs)
    assert (weights.model_dir_name(config, family, model), len(specs), digest, params) == RECORDED[config, family]


@pytest.mark.parametrize("family", sorted(RECORDED_BYTES))
def test_the_checkpoint_written_is_the_parents_byte_for_byte(tmp_path, family):
    name = weights.ensure_model_dir(str(tmp_path), "rehearsal-tiny", family, _entry("rehearsal-tiny", family))
    with open(tmp_path / "models" / name / "model.safetensors", "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest() == RECORDED_BYTES[family]


def test_an_entry_that_names_a_listing_gets_a_directory_of_its_own():
    model = _entry("rehearsal-tiny", "vlm")
    named = {**model, "tensors": "vlm"}
    assert weights.listing("vlm", named).tensors(model["config"]) == weights.listing("vlm", model).tensors(model["config"])
    assert weights.model_dir_name("rehearsal-tiny", "vlm", named) != weights.model_dir_name("rehearsal-tiny", "vlm", model)
    with pytest.raises(cells.CellError, match="benchmark/tensors/nowhere.py does not exist"):
        weights.listing("vlm", {**model, "tensors": "nowhere"})
