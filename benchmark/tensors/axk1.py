"""``axk1`` decoder (the DeepSeek-V3 layer: latent attention over every
causal key in every layer, no indexer, no window, no gate; a leading dense
layer; sigmoid-routed experts with one shared expert) behind the repo's ViT
tower: the checkpoint of a ``models.vlm`` entry that names ``"tensors":
"axk1"``. Names follow ``transformers``' ``DeepseekV3`` checkpoints
(``q_a_proj``, ``q_a_layernorm``, ``q_b_proj``, ``kv_a_proj_with_mqa``,
``kv_a_layernorm``, ``kv_b_proj``, ``o_proj``, ``mlp.gate.weight``,
``mlp.gate.e_score_correction_bias``, ``mlp.experts.N.*``,
``mlp.shared_experts.*``). ``n_routed_experts`` counts the experts held
here: chip ``ep_rank`` of ``ep_size`` holds ``[rank * n, (rank + 1) * n)``
under their ids in the whole bank, and the router is ``n * ep_size`` wide."""

from __future__ import annotations

from benchmark.tensors.dots3 import swiglu
from benchmark.tensors.vlm import norms, vision


def special_words(cfg: dict) -> dict[int, str]:
    """The ids the chat template and the configuration name, as every
    LLaVA-style entry has them. ``run.py`` asks for the vocabulary before it
    writes a checkpoint or touches a chip, so this is also where a checkout
    whose program has no such decoder (one older than the configuration) is
    told so, in seconds and with no chip held: without it the run would write
    7 GB of weights, boot a hub whose service is degraded, and fail at its
    warm-up traffic a hundred seconds later. The one place a listing asks the
    program anything: what ``VLMConfig.from_hf`` makes of ``cfg``."""
    from benchmark import cells, weights
    from lumen_tpu.models.vlm.modeling import VLMConfig

    decoder = VLMConfig.from_hf(cfg).decoder
    if not getattr(decoder, "latent", False):
        raise cells.CellError(
            f"this checkout's program reads no model_type {cfg['text_config']['model_type']!r}: "
            "VLMConfig.from_hf gives a decoder without latent layers, so the configuration cannot run here"
        )
    return weights._special_words(cfg)


def attention(p: str, t: dict) -> list[tuple[str, tuple]]:
    h, heads, q_lora, kv_lora = t["hidden_size"], t["num_attention_heads"], t["q_lora_rank"], t["kv_lora_rank"]
    nope, rope, v = t["qk_nope_head_dim"], t["qk_rope_head_dim"], t["v_head_dim"]
    a = p + "self_attn."
    return [
        (a + "q_a_proj.weight", (q_lora, h)), (a + "q_a_layernorm.weight", (q_lora,)),
        (a + "q_b_proj.weight", (heads * (nope + rope), q_lora)),
        (a + "kv_a_proj_with_mqa.weight", (kv_lora + rope, h)), (a + "kv_a_layernorm.weight", (kv_lora,)),
        (a + "kv_b_proj.weight", (heads * (nope + v), kv_lora)),
        (a + "o_proj.weight", (h, heads * v)),
    ]


def tensors(cfg: dict) -> list[tuple[str, tuple]]:
    t = cfg["text_config"]
    h, f = t["hidden_size"], t["moe_intermediate_size"]
    held, ep, rank = t["n_routed_experts"], t.get("ep_size", 1), t.get("ep_rank", 0)
    out = [("model.embed_tokens.weight", (t["vocab_size"], h))]
    for i in range(t["num_hidden_layers"]):
        p = f"model.layers.{i}."
        out += attention(p, t)
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
