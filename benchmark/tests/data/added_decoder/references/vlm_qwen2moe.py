"""Fixture: plain reference of a captioner whose decoder is Qwen2-MoE. All
but the decoder's MLP is the Qwen2 captioner's reference (``vlm``): chat
template, tower, splice, attention, head. A sparse layer's MLP is written
out as the dense gather HF's ``Qwen2MoeSparseMoeBlock`` is: softmax router,
the ``num_experts_per_tok`` best kept (renormalised where ``norm_topk_prob``),
every expert's SwiGLU computed for every token and weighted by what was
kept, plus the shared expert under its sigmoid gate. float32 at
``highest``, no cache, no capacity, no dispatch; imports nothing of the
program."""

from __future__ import annotations

from benchmark.references import plain
from benchmark.references import vlm as dense

fault, prompt_ids = dense.fault, dense.prompt_ids


def is_sparse(t: dict, i: int) -> bool:
    return (t.get("num_experts", 0) > 0 and i not in t.get("mlp_only_layers", ())
            and (i + 1) % t.get("decoder_sparse_step", 1) == 0)


def swiglu(y, p: dict, pre: str):
    import jax

    return plain.linear(jax.nn.silu(plain.linear(y, p[pre + "gate_w"])) * plain.linear(y, p[pre + "up_w"]),
                        p[pre + "down_w"])


def moe_mlp(y, p: dict, t: dict):
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(plain.linear(y, p["router_w"]), axis=-1)          # [B, S, E]
    kept, which = jax.lax.top_k(probs, t["num_experts_per_tok"])
    if t.get("norm_topk_prob", False):
        kept = kept / jnp.sum(kept, axis=-1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(which, probs.shape[-1]) * kept[..., None], axis=-2)  # [B, S, E]
    out = sum(weight[..., e:e + 1] * swiglu(y, p, f"e{e}_") for e in range(t["num_experts"]))
    return out + jax.nn.sigmoid(plain.linear(y, p["share_w"])) * swiglu(y, p, "shared_")


def decoder_layer(x, p: dict, t: dict):
    if "router_w" not in p:
        return dense.decoder_layer(x, p, t)
    import jax.numpy as jnp

    b, s, h = x.shape
    nh, nkv = t["num_attention_heads"], t["num_key_value_heads"]
    dh = t.get("head_dim") or h // nh
    eps, theta = t.get("rms_norm_eps", 1e-6), t.get("rope_theta", 1e6)
    y = dense.rms_norm(x, p["in_norm"], eps)
    q = plain.linear(y, p["q_w"], p["q_b"]).reshape(b, s, nh, dh).transpose(0, 2, 1, 3)
    k = plain.linear(y, p["k_w"], p["k_b"]).reshape(b, s, nkv, dh).transpose(0, 2, 1, 3)
    v = plain.linear(y, p["v_w"], p["v_b"]).reshape(b, s, nkv, dh).transpose(0, 2, 1, 3)
    q, k = dense.rope(q, theta), dense.rope(k, theta)
    k, v = jnp.repeat(k, nh // nkv, axis=1), jnp.repeat(v, nh // nkv, axis=1)
    a = plain.attention(q, k, v, causal=True).transpose(0, 2, 1, 3).reshape(b, s, nh * dh)
    x = x + plain.linear(a, p["o_w"])
    return x + moe_mlp(dense.rms_norm(x, p["post_norm"], eps), p, t)


def decoder_layer_params(ck: plain.Checkpoint, t: dict, i: int, bits) -> dict:
    if not is_sparse(t, i):
        return dense.decoder_layer_params(ck, i, bits)
    pre = f"model.layers.{i}."
    p = {"in_norm": ck.get(pre + "input_layernorm.weight"),
         "post_norm": ck.get(pre + "post_attention_layernorm.weight"),
         "router_w": plain.fake_quant(ck.get(pre + "mlp.gate.weight"), bits),
         "share_w": plain.fake_quant(ck.get(pre + "mlp.shared_expert_gate.weight"), bits)}
    for n in ("q", "k", "v", "o"):
        p[f"{n}_w"] = plain.fake_quant(ck.get(f"{pre}self_attn.{n}_proj.weight"), bits)
    for n in ("q", "k", "v"):
        p[f"{n}_b"] = ck.get(f"{pre}self_attn.{n}_proj.bias")
    banks = [(f"e{e}_", f"mlp.experts.{e}.") for e in range(t["num_experts"])] + [("shared_", "mlp.shared_expert.")]
    for short, long in banks:
        for n in ("gate", "up", "down"):
            p[f"{short}{n}_w"] = plain.fake_quant(ck.get(f"{pre}{long}{n}_proj.weight"), bits)
    return p


def compare(sample: dict, model: dict, model_dir: str, precision: str, control: bool = False) -> dict:
    """The Qwen2 captioner's comparison (same numbers, same meaning) over this
    decoder's layers: a copy of that reference module of this call's own takes
    the two functions above in place of its Qwen2 layer."""
    from benchmark import cells

    t = model["config"]["text_config"]
    ref = cells.load_module("references", "vlm")
    ref.decoder_layer = decoder_layer
    ref.decoder_layer_params = lambda ck, i, bits: decoder_layer_params(ck, t, i, bits)
    return ref.compare(sample, model, model_dir, precision, control)
