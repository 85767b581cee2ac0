"""HF ``CLIPModel`` state-dict names and shapes: the listing of every
``models.clip`` entry that names no other."""

from __future__ import annotations

from benchmark.tensors.common import HF_CLIP, tower_tensors


def tensors(cfg: dict) -> list[tuple[str, tuple]]:
    v, t, proj = cfg["vision_config"], cfg["text_config"], cfg["projection_dim"]
    vw, tw = v["hidden_size"], t["hidden_size"]
    n_pos = (v["image_size"] // v["patch_size"]) ** 2 + 1
    out = [
        ("logit_scale", ()),
        ("text_model.embeddings.token_embedding.weight", (t["vocab_size"], tw)),
        ("text_model.embeddings.position_embedding.weight", (t["max_position_embeddings"], tw)),
    ]
    out += tower_tensors("text_model.encoder.layers", tw, t["intermediate_size"],
                         t["num_hidden_layers"], HF_CLIP)
    out += [
        ("text_model.final_layer_norm.weight", (tw,)),
        ("text_model.final_layer_norm.bias", (tw,)),
        ("text_projection.weight", (proj, tw)),
        ("vision_model.embeddings.class_embedding", (vw,)),
        ("vision_model.embeddings.patch_embedding.weight", (vw, 3, v["patch_size"], v["patch_size"])),
        ("vision_model.embeddings.position_embedding.weight", (n_pos, vw)),
        ("vision_model.pre_layrnorm.weight", (vw,)),
        ("vision_model.pre_layrnorm.bias", (vw,)),
    ]
    out += tower_tensors("vision_model.encoder.layers", vw, v["intermediate_size"],
                         v["num_hidden_layers"], HF_CLIP)
    out += [
        ("vision_model.post_layernorm.weight", (vw,)),
        ("vision_model.post_layernorm.bias", (vw,)),
        ("visual_projection.weight", (proj, vw)),
    ]
    return out
