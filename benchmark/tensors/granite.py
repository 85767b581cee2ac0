"""``granitemoehybrid`` decoder (Mamba-2 and grouped-query layers by
``layer_types``, softmax-routed experts held as two stacked tensors a layer,
one shared expert, tied head) behind the repo's ViT tower: the checkpoint of
a ``models.vlm`` entry that names ``"tensors": "granite"``. Names follow
``transformers``' ``GraniteMoeHybrid`` (``mamba.in_proj|conv1d|dt_bias|A_log|
D|norm|out_proj``, ``self_attn.*`` without bias,
``block_sparse_moe.router.layer|input_linear|output_linear``,
``shared_mlp.input_linear|output_linear``; an ``input_linear`` holds the gate
half of its outputs, then the up half). ``num_local_experts`` counts the
experts held here: chip ``ep_rank`` of ``ep_size`` holds ``[rank * n,
(rank + 1) * n)``, and the router is ``n * ep_size`` wide."""

from __future__ import annotations

from benchmark.tensors.vlm import norms, vision

MAMBA, ATTENTION = "mamba", "attention"


def mamba(p: str, t: dict) -> list[tuple[str, tuple]]:
    h, heads, n, k = t["hidden_size"], t["mamba_n_heads"], t["mamba_d_state"], t["mamba_d_conv"]
    inner = heads * t["mamba_d_head"]
    conv_dim = inner + 2 * t.get("mamba_n_groups", 1) * n
    m = p + "mamba."
    out = [
        (m + "in_proj.weight", (inner + conv_dim + heads, h)),
        (m + "conv1d.weight", (conv_dim, 1, k)), (m + "conv1d.bias", (conv_dim,)),
        (m + "dt_bias", (heads,)), (m + "A_log", (heads,)), (m + "D", (heads,)),
        (m + "norm.weight", (inner,)),
        (m + "out_proj.weight", (h, inner)),
    ]
    return out


def attention(p: str, t: dict) -> list[tuple[str, tuple]]:
    h = t["hidden_size"]
    dh = t.get("head_dim") or h // t["num_attention_heads"]
    q, kv = t["num_attention_heads"] * dh, t["num_key_value_heads"] * dh
    a = p + "self_attn."
    return [(a + "q_proj.weight", (q, h)), (a + "k_proj.weight", (kv, h)), (a + "v_proj.weight", (kv, h)),
            (a + "o_proj.weight", (h, q))]


def feed_forward(p: str, t: dict) -> list[tuple[str, tuple]]:
    h, f = t["hidden_size"], t["intermediate_size"]
    held, ep = t["num_local_experts"], t.get("ep_size", 1)
    out = [
        (p + "block_sparse_moe.router.layer.weight", (held * ep, h)),
        (p + "block_sparse_moe.input_linear.weight", (held, 2 * f, h)),
        (p + "block_sparse_moe.output_linear.weight", (held, h, f)),
    ]
    if t.get("shared_intermediate_size"):
        fs = t["shared_intermediate_size"]
        out += [(p + "shared_mlp.input_linear.weight", (2 * fs, h)), (p + "shared_mlp.output_linear.weight", (h, fs))]
    return out


def tensors(cfg: dict) -> list[tuple[str, tuple]]:
    t = cfg["text_config"]
    h = t["hidden_size"]
    out = [("model.embed_tokens.weight", (t["vocab_size"], h))]
    for i in range(t["num_hidden_layers"]):
        p = f"model.layers.{i}."
        out += mamba(p, t) if t["layer_types"][i] == MAMBA else attention(p, t)
        out += feed_forward(p, t)
        out += norms(p, h)
    out.append(("model.norm.weight", (h,)))
    if not t.get("tie_word_embeddings", True):
        out.append(("lm_head.weight", (t["vocab_size"], h)))
    return out + vision(cfg)
