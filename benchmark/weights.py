"""Seeded model directories, written from a configuration file alone.

A configuration's ``models`` section holds the published HF-style
``config.json`` of each family. The entry's tensor listing (found by name
in ``benchmark/tensors/``, see :func:`listing`) gives every tensor under its
HF checkpoint name from those shapes; this module draws each from
``(weights_seed, name)`` and writes one ``model.safetensors`` beside the
small files the program's normal load path asks for (``config.json``,
``model_info.json``, a word-level ``tokenizer.json``). Every value is
rounded to bf16, the served type, and stored as float16: a two-byte type
numpy knows, which the load path transposes and casts three times faster
than bf16 itself (my sandbox timing, PR 26). Values under float16's normal
range (|w| < 6e-5, 0.2% of a N(0, 0.02) draw) keep float16's absolute
spacing of 6e-8.

Nothing here imports the program or JAX: the plain references read the
same files back by the same names.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import zlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import cells

#: elements drawn by one generator; larger tensors are drawn in row blocks
_BLOCK_ELEMS = 1 << 24

CHAT_TEMPLATE = (
    "{% for m in messages %}role_{{ m.role }} {{ m.content }} {% endfor %}"
    "{% if add_generation_prompt %}role_assistant{% endif %}"
)


def listing(family: str, model: dict):
    """The module that lists a model entry's tensors:
    ``benchmark/tensors/<name>.py``, where ``<name>`` is the entry's
    ``tensors`` key or, for an entry without one, the family's own name."""
    return cells.load_module("tensors", model.get("tensors", family))


_NORM_WEIGHT = re.compile(r"(layer_?norm\d?|layrnorm|norm\d?)\.weight$")
_NORM_BIAS = re.compile(r"(layer_?norm\d?|layrnorm|norm\d?)\.bias$")


def init_rule(name: str, init: list) -> float | str:
    """How ``name`` is drawn: ``"ones"``, ``"zeros"``, a constant
    (``["const", x]``) or the standard deviation of a normal draw. ``init``
    is the configuration's ordered ``[pattern, rule]`` list, first match
    wins; norm scales are ones and norm biases zeros whatever it says."""
    if _NORM_WEIGHT.search(name):
        return "ones"
    if _NORM_BIAS.search(name):
        return "zeros"
    for pattern, rule in init:
        if re.search(pattern, name):
            return rule
    raise KeyError(f"no init rule matches tensor {name!r}")


def _draw_block(seed: int, name: str, block: int, shape: tuple, std: float) -> np.ndarray:
    import ml_dtypes

    key = [((seed & 0xFFFFFFFF) << 32) | zlib.crc32(name.encode()), block]
    rng = np.random.Generator(np.random.Philox(key=np.array(key, np.uint64)))
    x = rng.standard_normal(shape, dtype=np.float32)
    x *= np.float32(std)
    return x.astype(ml_dtypes.bfloat16).astype(np.float16)


def draw_tensor(seed: int, name: str, shape: tuple, rule, pool=None) -> np.ndarray:
    """The tensor ``name`` (bf16 values held in float16): a pure function of
    (seed, name, shape, rule)."""
    import ml_dtypes

    if rule == "ones":
        return np.ones(shape, np.float16)
    if rule == "zeros":
        return np.zeros(shape, np.float16)
    if isinstance(rule, list) and rule[0] == "const":
        return np.full(shape, rule[1], np.float32).astype(ml_dtypes.bfloat16).astype(np.float16)
    std = float(rule)
    n = int(np.prod(shape, dtype=np.int64)) if shape else 1
    if n <= _BLOCK_ELEMS or len(shape) < 2:
        return _draw_block(seed, name, 0, shape, std)
    rows = max(1, _BLOCK_ELEMS // int(np.prod(shape[1:])))
    spans = [(i, min(i + rows, shape[0])) for i in range(0, shape[0], rows)]
    draw = lambda b: _draw_block(seed, name, b, (spans[b][1] - spans[b][0],) + tuple(shape[1:]), std)
    parts = list(pool.map(draw, range(len(spans)))) if pool else [draw(b) for b in range(len(spans))]
    return np.concatenate(parts, axis=0)


def _special_words(cfg: dict) -> dict[int, str]:
    """The ids the chat template and a LLaVA-style configuration name."""
    t = cfg["text_config"]
    return {
        cfg["image_token_index"]: "<image>",
        t["eos_token_id"]: "<eos>",
        t["bos_token_id"]: "<bos>",
        0: "<unk>", 1: "role_user", 2: "role_assistant", 3: "role_system",
    }


#: where the configuration states the id of a special word, for the error below
_SPECIAL_KEYS = {"<image>": "image_token_index", "<eos>": "text_config.eos_token_id",
                 "<bos>": "text_config.bos_token_id"}


def vlm_vocab(model: dict) -> dict[str, int]:
    """Word-level vocabulary of a ``models.vlm`` entry covering every id, so
    that any generated id decodes to one word and the text maps back to ids:
    ``w<id>``, but for the special words: ``{id: word}`` from the entry's
    listing (``special_words(cfg)``) or, where it gives none, the ids the
    chat template and the configuration name."""
    cfg = model["config"]
    size = cfg["text_config"]["vocab_size"]
    special = getattr(listing("vlm", model), "special_words", _special_words)(cfg)
    for i, word in special.items():
        if not 0 <= i < size:
            key = _SPECIAL_KEYS.get(word, "the listing's special_words")
            raise cells.CellError(f"special word {word!r} has id {i} ({key}), outside text_config.vocab_size "
                                  f"{size}: the tokenizer would lose it")
    return {special.get(i, f"w{i}"): i for i in range(size)}


def _write_tokenizer(model_dir: str, vocab: dict[str, int], unk: str, template: str | None) -> None:
    from tokenizers import Tokenizer, models, pre_tokenizers

    tok = Tokenizer(models.WordLevel(vocab, unk_token=unk))
    tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    tok.save(os.path.join(model_dir, "tokenizer.json"))
    if template:
        with open(os.path.join(model_dir, "tokenizer_config.json"), "w") as f:
            json.dump({"chat_template": template}, f)


def model_dir_name(config_name: str, family: str, model: dict) -> str:
    digest = hashlib.sha256(("f16:" + json.dumps(model, sort_keys=True)).encode()).hexdigest()[:10]
    return f"{config_name}.{family}.{digest}"


def ensure_model_dir(root: str, config_name: str, family: str, model: dict) -> str:
    """Write ``root/models/<name>`` for one family of a configuration if it
    is not there whole already; returns the directory's name."""
    name = model_dir_name(config_name, family, model)
    model_dir = os.path.join(root, "models", name)
    done = os.path.join(model_dir, ".complete")
    if os.path.exists(done):
        return name
    from safetensors.numpy import save_file

    os.makedirs(model_dir, exist_ok=True)
    cfg, seed, init = model["config"], int(model["weights_seed"]), model["init"]
    specs = listing(family, model).tensors(cfg)
    with ThreadPoolExecutor(min(12, os.cpu_count() or 4)) as blocks, ThreadPoolExecutor(4) as outer:
        tensors = dict(zip(
            (n for n, _ in specs),
            outer.map(lambda s: draw_tensor(seed, s[0], s[1], init_rule(s[0], init), blocks), specs),
        ))
    tmp = os.path.join(model_dir, "model.safetensors.tmp")
    save_file(tensors, tmp)
    os.replace(tmp, os.path.join(model_dir, "model.safetensors"))
    with open(os.path.join(model_dir, "config.json"), "w") as f:
        json.dump(cfg, f)
    if family == "vlm":
        _write_tokenizer(model_dir, vlm_vocab(model), "<unk>", CHAT_TEMPLATE)
        extra = {}
    else:
        t = cfg["text_config"]
        vocab = {f"w{i}": i for i in range(t["vocab_size"])}
        _write_tokenizer(model_dir, vocab, "w0", None)
        extra = {"embedding_dim": cfg["projection_dim"]}
    with open(os.path.join(model_dir, "model_info.json"), "w") as f:
        json.dump({
            "name": name, "version": "1.0.0", "description": "seeded random weights (benchmark)",
            "model_type": family,
            "source": {"format": "custom", "repo_id": f"benchmark/{family}"},
            "runtimes": {"jax": {"available": True, "files": ["model.safetensors"]}},
            **extra,
        }, f)
    with open(done, "w") as f:
        f.write("ok\n")
    return name
