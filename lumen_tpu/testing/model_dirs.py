"""Seeded random-weight model directories that the managers' normal load
paths accept — the real weight-load + serve stack without a download.

``chip_smoke.py`` writes the published architectures with these
(CLIP ViT-B/32, Qwen2-0.5B with the 1024-px tower); tests write
tiny cuts. Nothing written here is ever committed: a
0.5B-parameter safetensors file in the tree would sink the copy to the
chip.
"""

from __future__ import annotations

import json
import os

import numpy as np

#: CLIP sizes below the published one, for CPU runs.
_CLIP_CUTS = {
    # heavy enough that per-batch device time dominates the GIL-bound host
    # path on CPU, light enough to compile every replica's buckets in seconds
    "mid": dict(
        projection_dim=64,
        text_config={"hidden_size": 64, "num_hidden_layers": 2,
                     "num_attention_heads": 4, "vocab_size": 128,
                     "max_position_embeddings": 16, "intermediate_size": 256,
                     "hidden_act": "quick_gelu", "eos_token_id": 127},
        vision_config={"hidden_size": 256, "num_hidden_layers": 4,
                       "num_attention_heads": 8, "image_size": 64,
                       "patch_size": 8, "intermediate_size": 1024,
                       "hidden_act": "quick_gelu"},
    ),
    "tiny": dict(
        projection_dim=32,
        text_config={"hidden_size": 48, "num_hidden_layers": 2,
                     "num_attention_heads": 4, "vocab_size": 128,
                     "max_position_embeddings": 16, "intermediate_size": 192,
                     "hidden_act": "quick_gelu", "eos_token_id": 127},
        vision_config={"hidden_size": 64, "num_hidden_layers": 2,
                       "num_attention_heads": 4, "image_size": 32,
                       "patch_size": 16, "intermediate_size": 256,
                       "hidden_act": "quick_gelu"},
    ),
}


def _write_model_info(model_dir: str, name: str, model_type: str, **extra) -> None:
    with open(os.path.join(model_dir, "model_info.json"), "w") as f:
        json.dump({
            "name": name, "version": "1.0.0", "description": "seeded random weights",
            "model_type": model_type,
            "source": {"format": "custom", "repo_id": f"bench/{model_type}"},
            "runtimes": {"jax": {"available": True, "files": ["model.safetensors"]}},
            **extra,
        }, f)


def write_clip_dir(
    root: str,
    size: str = "vitb32",
    name: str = "BenchCLIP",
    labels: list[str] | None = None,
    seed: int = 0,
) -> str:
    """Random-weight HF-format CLIP checkpoint under ``root/models/name``
    that the manager's normal convert path loads. ``size``: ``"vitb32"``
    (the published ViT-B/32, HF's defaults) or a ``_CLIP_CUTS`` key.
    ``labels`` adds a zero-shot dataset named ``"labels"`` (the manager
    embeds them at boot), which is what registers ``clip_classify``."""
    import torch
    from safetensors.torch import save_file
    from tokenizers import Tokenizer, models, pre_tokenizers
    from tokenizers.processors import TemplateProcessing
    from transformers import CLIPConfig as HFCLIPConfig, CLIPModel as HFCLIPModel

    cfg = HFCLIPConfig() if size == "vitb32" else HFCLIPConfig(**_CLIP_CUTS[size])
    eot = 49407 if size == "vitb32" else 127
    torch.manual_seed(seed)
    model = HFCLIPModel(cfg).eval()
    model_dir = os.path.join(root, "models", name)
    os.makedirs(model_dir, exist_ok=True)
    state = {k: v for k, v in model.state_dict().items() if "position_ids" not in k}
    save_file(state, os.path.join(model_dir, "model.safetensors"))
    with open(os.path.join(model_dir, "config.json"), "w") as f:
        json.dump(cfg.to_dict(), f)
    # Every id below <eot> gets a word: the tokenizers library prints each
    # hole of a sparse vocabulary to stderr (49,402 of them at ViT-B/32).
    vocab = {"<unk>": 0, "a": 1, "photo": 2, "of": 3, "cat": 4, "<eot>": eot}
    vocab.update((f"w{i}", i) for i in range(len(vocab) - 1, eot))
    tok = Tokenizer(models.WordLevel(vocab, unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    tok.post_processor = TemplateProcessing(
        single="$A <eot>", special_tokens=[("<eot>", eot)]
    )
    tok.save(os.path.join(model_dir, "tokenizer.json"))
    extra: dict = {"embedding_dim": cfg.projection_dim}
    if labels:
        with open(os.path.join(model_dir, "labels.json"), "w") as f:
            json.dump(labels, f)
        extra["datasets"] = {
            "labels": {"labels": "labels.json", "embeddings": "labels.npy"}
        }
    _write_model_info(model_dir, name, "clip", **extra)
    return model_dir


def write_vlm_dir(root: str, cfg, name: str = "BenchVLM", seed: int = 0) -> str:
    """Random-weight flax-native VLM checkpoint for ``cfg`` (a
    ``VLMConfig``; ``VLMConfig()`` is Qwen2-0.5B as published with the
    1024-px tower) under ``root/models/name``, with a word-level tokenizer
    that covers the whole vocabulary so GENERATED ids decode to real text —
    a stream whose tokens all decode to empty strings never emits a chunk."""
    import jax
    import jax.numpy as jnp
    from safetensors.numpy import save_file
    from tokenizers import Tokenizer, models, pre_tokenizers

    from ..models.vlm.modeling import VLMModel
    from ..runtime.weights import flatten_variables

    model = VLMModel(cfg)
    shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, 4), jnp.int32),
            jnp.zeros((1, cfg.vision.image_size, cfg.vision.image_size, 3), jnp.float32),
        )
    )
    rng = np.random.default_rng(seed)
    # Norm scales are ones, everything else N(0, 0.02): with random scales
    # near zero every block's output vanishes against the residual stream,
    # and a forward in which attention does not matter checks nothing.
    flat = {
        k: np.ones(v.shape, np.float32) if k.endswith("/scale")
        else 0.02 * rng.standard_normal(v.shape, dtype=np.float32)
        for k, v in flatten_variables(
            jax.tree.map(lambda s: np.broadcast_to(np.float32(0), s.shape), dict(shapes))
        ).items()
    }
    model_dir = os.path.join(root, "models", name)
    os.makedirs(model_dir, exist_ok=True)
    save_file(flat, os.path.join(model_dir, "model.safetensors"))
    d, v = cfg.decoder, cfg.vision
    with open(os.path.join(model_dir, "config.json"), "w") as f:
        json.dump({
            "text_config": {
                "hidden_size": d.hidden_size, "num_hidden_layers": d.layers,
                "num_attention_heads": d.heads, "num_key_value_heads": d.kv_heads,
                "intermediate_size": d.intermediate_size, "vocab_size": d.vocab_size,
                "rope_theta": d.rope_theta,
                "max_position_embeddings": d.max_position_embeddings,
                "bos_token_id": cfg.bos_token_id, "eos_token_id": cfg.eos_token_id,
                "pad_token_id": cfg.pad_token_id, "tie_word_embeddings": True,
            },
            "vision_config": {
                "image_size": v.image_size, "patch_size": v.patch_size,
                "hidden_size": v.width, "num_hidden_layers": v.layers,
                "num_attention_heads": v.heads,
            },
            "image_token_index": cfg.image_token_id,
        }, f)
    words = {"<pad>": 0, "<bos>": 1, "<eos>": 2, "<unk>": 3,
             "describe": 10, "the": 11, "image": 12}
    taken = set(words.values())
    words.update((f"tok{i}", i) for i in range(d.vocab_size) if i not in taken)
    tok = Tokenizer(models.WordLevel(words, unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    tok.save(os.path.join(model_dir, "tokenizer.json"))
    with open(os.path.join(model_dir, "tokenizer_config.json"), "w") as f:
        json.dump({"chat_template": (
            "{% for m in messages %}<|{{ m.role }}|> {{ m.content }} {% endfor %}"
            "{% if add_generation_prompt %}<|assistant|>{% endif %}"
        )}, f)
    _write_model_info(model_dir, name, "vlm")
    return model_dir
