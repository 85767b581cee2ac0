"""``dots3_note`` decoder (latent attention of two kinds by ``layer_types``,
an indexer in the full layers, a leading dense layer, sigmoid-routed experts
with one shared expert) behind the repo's ViT tower: the checkpoint of a
``models.vlm`` entry that names ``"tensors": "dots3"``. Names follow the
family's HF checkpoints (``kv_a_proj_with_mqa``, ``indexer.wq_b``,
``gate.e_score_correction_bias``, ``shared_experts``); ``attn_gate`` is the
headwise output gate. ``n_routed_experts`` counts the experts held here:
chip ``ep_rank`` of ``ep_size`` holds ``[rank * n, (rank + 1) * n)``, and the
router is ``n * ep_size`` wide."""

from __future__ import annotations

from benchmark.tensors.vlm import norms, vision

FULL, WINDOW = "full_attention", "sliding_attention"


def attention(p: str, t: dict, kind: str) -> list[tuple[str, tuple]]:
    h, s = t["hidden_size"], "swa_" if kind == WINDOW else ""
    heads, q_lora, kv_lora = t[s + "num_attention_heads"], t[s + "q_lora_rank"], t[s + "kv_lora_rank"]
    nope, rope, v = t[s + "qk_nope_head_dim"], t[s + "qk_rope_head_dim"], t[s + "v_head_dim"]
    a = p + "self_attn."
    out = [
        (a + "q_a_proj.weight", (q_lora, h)), (a + "q_a_layernorm.weight", (q_lora,)),
        (a + "q_b_proj.weight", (heads * (nope + rope), q_lora)),
        (a + "kv_a_proj_with_mqa.weight", (kv_lora + rope, h)), (a + "kv_a_layernorm.weight", (kv_lora,)),
        (a + "kv_b_proj.weight", (heads * (nope + v), kv_lora)),
        (a + "o_proj.weight", (h, heads * v)),
        (a + "attn_gate.weight", (heads, h)),
    ]
    if kind == FULL:
        j, di = t["index_n_heads"], t["index_head_dim"]
        out += [
            (a + "indexer.wq_b.weight", (j * di, q_lora)), (a + "indexer.wk.weight", (di, h)),
            (a + "indexer.k_norm.weight", (di,)), (a + "indexer.k_norm.bias", (di,)),
            (a + "indexer.weights_proj.weight", (j, h)),
        ]
    return out


def swiglu(p: str, h: int, inter: int) -> list[tuple[str, tuple]]:
    return [(p + "gate_proj.weight", (inter, h)), (p + "up_proj.weight", (inter, h)),
            (p + "down_proj.weight", (h, inter))]


def tensors(cfg: dict) -> list[tuple[str, tuple]]:
    t = cfg["text_config"]
    h, f = t["hidden_size"], t["moe_intermediate_size"]
    held, ep, rank = t["n_routed_experts"], t.get("ep_size", 1), t.get("ep_rank", 0)
    out = [("model.embed_tokens.weight", (t["vocab_size"], h))]
    for i in range(t["num_hidden_layers"]):
        p = f"model.layers.{i}."
        out += attention(p, t, t["layer_types"][i])
        if i < t.get("first_k_dense_replace", 0):
            out += swiglu(p + "mlp.", h, t["intermediate_size"])
        else:
            out += [(p + "mlp.gate.weight", (held * ep, h)),
                    (p + "mlp.gate.e_score_correction_bias", (held * ep,))]
            for e in range(rank * held, (rank + 1) * held):
                out += swiglu(f"{p}mlp.experts.{e}.", h, f)
            if t.get("n_shared_experts"):
                out += swiglu(p + "mlp.shared_experts.", h, f * t["n_shared_experts"])
        out += norms(p, h)
    out.append(("model.norm.weight", (h,)))
    if not t.get("tie_word_embeddings", False):
        out.append(("lm_head.weight", (t["vocab_size"], h)))
    return out + vision(cfg)
