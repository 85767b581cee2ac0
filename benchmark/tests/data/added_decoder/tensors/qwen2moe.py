"""Fixture: a Qwen2-MoE decoder (HF names: router ``mlp.gate``, per-expert
SwiGLU ``mlp.experts.<i>``, sigmoid-gated ``mlp.shared_expert``) behind the
repo's tower. Layer ``i`` is sparse unless ``mlp_only_layers`` names it or
``(i + 1) % decoder_sparse_step`` is not 0; a dense layer is Qwen2's."""

from __future__ import annotations

from benchmark.tensors import vlm as qwen2


def is_sparse(t: dict, i: int) -> bool:
    return (t.get("num_experts", 0) > 0 and i not in t.get("mlp_only_layers", ())
            and (i + 1) % t.get("decoder_sparse_step", 1) == 0)


def swiglu(p: str, h: int, inter: int) -> list[tuple[str, tuple]]:
    return [(p + "gate_proj.weight", (inter, h)), (p + "up_proj.weight", (inter, h)),
            (p + "down_proj.weight", (h, inter))]


def tensors(cfg: dict) -> list[tuple[str, tuple]]:
    t = cfg["text_config"]
    h = t["hidden_size"]
    out = [("model.embed_tokens.weight", (t["vocab_size"], h))]
    for i in range(t["num_hidden_layers"]):
        p = f"model.layers.{i}."
        out += qwen2.attention(p, t)
        if is_sparse(t, i):
            out.append((p + "mlp.gate.weight", (t["num_experts"], h)))
            for e in range(t["num_experts"]):
                out += swiglu(f"{p}mlp.experts.{e}.", h, t["moe_intermediate_size"])
            out += swiglu(p + "mlp.shared_expert.", h, t["shared_expert_intermediate_size"])
            out.append((p + "mlp.shared_expert_gate.weight", (1, h)))
        else:
            out += swiglu(p + "mlp.", h, t["intermediate_size"])
        out += qwen2.norms(p, h)
    out.append(("model.norm.weight", (h,)))
    return out + qwen2.vision(cfg)
