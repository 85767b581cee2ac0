"""Qwen2 decoder (HF names) + the repo's ViT tower and 2-layer projector:
the listing of every ``models.vlm`` entry that names no other. Another
decoder's listing takes from here the parts it shares (``attention``,
``vision``) and lists its own layers between them."""

from __future__ import annotations

from benchmark.tensors.common import VLM_TOWER, tower_tensors


def attention(p: str, t: dict) -> list[tuple[str, tuple]]:
    """Grouped-query attention of layer prefix ``p``: biased q/k/v, plain o."""
    h = t["hidden_size"]
    dh = t.get("head_dim") or h // t["num_attention_heads"]
    q, kv = t["num_attention_heads"] * dh, t["num_key_value_heads"] * dh
    return [
        (p + "self_attn.q_proj.weight", (q, h)), (p + "self_attn.q_proj.bias", (q,)),
        (p + "self_attn.k_proj.weight", (kv, h)), (p + "self_attn.k_proj.bias", (kv,)),
        (p + "self_attn.v_proj.weight", (kv, h)), (p + "self_attn.v_proj.bias", (kv,)),
        (p + "self_attn.o_proj.weight", (h, q)),
    ]


def norms(p: str, h: int) -> list[tuple[str, tuple]]:
    return [(p + "input_layernorm.weight", (h,)), (p + "post_attention_layernorm.weight", (h,))]


def vision(cfg: dict) -> list[tuple[str, tuple]]:
    """The captioner's tower and projector into the decoder's width."""
    v, h = cfg["vision_config"], cfg["text_config"]["hidden_size"]
    vw, patch = v["hidden_size"], v["patch_size"]
    out = [
        ("vision_tower.patch_embed.weight", (vw, 3, patch, patch)),
        ("vision_tower.patch_embed.bias", (vw,)),
        ("vision_tower.position_embedding", ((v["image_size"] // patch) ** 2, vw)),
    ]
    out += tower_tensors("vision_tower.blocks", vw, 4 * vw, v["num_hidden_layers"], VLM_TOWER)
    out += [
        ("vision_tower.post_norm.weight", (vw,)),
        ("vision_tower.post_norm.bias", (vw,)),
        ("multi_modal_projector.linear_1.weight", (h, vw)),
        ("multi_modal_projector.linear_1.bias", (h,)),
        ("multi_modal_projector.linear_2.weight", (h, h)),
        ("multi_modal_projector.linear_2.bias", (h,)),
    ]
    return out


def tensors(cfg: dict) -> list[tuple[str, tuple]]:
    t = cfg["text_config"]
    h, inter = t["hidden_size"], t["intermediate_size"]
    out = [("model.embed_tokens.weight", (t["vocab_size"], h))]
    for i in range(t["num_hidden_layers"]):
        p = f"model.layers.{i}."
        out += attention(p, t)
        out += [
            (p + "mlp.gate_proj.weight", (inter, h)),
            (p + "mlp.up_proj.weight", (inter, h)),
            (p + "mlp.down_proj.weight", (h, inter)),
        ]
        out += norms(p, h)
    out.append(("model.norm.weight", (h,)))
    if not t.get("tie_word_embeddings", True):
        out.append(("lm_head.weight", (t["vocab_size"], h)))
    return out + vision(cfg)
