"""Plain reference of the captioner: chat template and word-level tokens,
JPEG -> canvas -> ViT tower -> projector, splice at ``<image>``, Qwen2
decoder (RMSNorm, biased q/k/v, half-split RoPE, grouped-query causal
attention, SwiGLU, tied head) as one full forward pass over the prompt with
its served tokens. float32 at ``highest`` precision, no cache, no pages, no
kernels; reads the benchmark's own checkpoint; imports nothing of the
program."""

from __future__ import annotations

import numpy as np

from benchmark.references import plain

_TOWER = {"attn": "attn", "ln1": "norm1", "ln2": "norm2"}
_PROJ = ("q_proj", "k_proj", "v_proj", "o_proj")



def fault(sample: dict) -> dict:
    """A token altered where it is produced: every fifth served token is
    replaced by its neighbour in the vocabulary."""
    return {"requests": [
        {**r, "tokens": [t + 1 if i % 5 == 4 else t for i, t in enumerate(r["tokens"])]}
        for r in sample["requests"]
    ]}


def prompt_ids(instruction: list[int], special: dict[str, int]) -> list[int]:
    """The ids of ``role_user <image> w.. w.. role_assistant``: the chat
    template the benchmark wrote, rendered for one user message."""
    return [special["role_user"], special["<image>"], *instruction, special["role_assistant"]]


def canvas(jpeg: bytes, size: int) -> np.ndarray:
    """Long side to ``size``, pasted top-left on a black square."""
    img = plain.decode_jpeg(jpeg)
    h, w = img.shape[:2]
    scale = size / max(h, w)
    nh, nw = max(1, round(h * scale)), max(1, round(w * scale))
    out = np.zeros((size, size, 3), np.uint8)
    out[:nh, :nw] = plain.resize_bilinear(img, nw, nh)
    return out


def vision_params(ck: plain.Checkpoint, cfg: dict) -> dict:
    v = cfg["vision_config"]
    return {
        "patch_w": ck.get("vision_tower.patch_embed.weight"), "patch_b": ck.get("vision_tower.patch_embed.bias"),
        "pos": ck.get("vision_tower.position_embedding"),
        "blocks": [plain.vit_block_params(ck, f"vision_tower.blocks.{i}", _TOWER, None)
                   for i in range(v["num_hidden_layers"])],
        "post_w": ck.get("vision_tower.post_norm.weight"), "post_b": ck.get("vision_tower.post_norm.bias"),
        "fc1_w": ck.get("multi_modal_projector.linear_1.weight"), "fc1_b": ck.get("multi_modal_projector.linear_1.bias"),
        "fc2_w": ck.get("multi_modal_projector.linear_2.weight"), "fc2_b": ck.get("multi_modal_projector.linear_2.bias"),
    }


def vision_embeds(p: dict, cfg: dict, pixels_u8):
    """[B, S, S, 3] uint8 canvases -> [B, tokens, hidden] image embeddings."""
    import jax.numpy as jnp

    v = cfg["vision_config"]
    x = pixels_u8.astype(jnp.float32) / 255.0  # mean 0, std 1: the tower's published default
    x = jnp.einsum("bnhwc,ochw->bno", plain.patchify(x, v["patch_size"]), p["patch_w"]) + p["patch_b"]
    x = x + p["pos"]
    for block in p["blocks"]:
        x = plain.vit_block(x, block, v["num_attention_heads"], "gelu", 1e-6)
    x = plain.layer_norm(x, p["post_w"], p["post_b"], 1e-6)
    h = plain.activation("gelu_tanh")(plain.linear(x, p["fc1_w"], p["fc1_b"]))
    return plain.linear(h, p["fc2_w"], p["fc2_b"])


def rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta: float):
    """[B, H, S, D], positions 0..S-1, HF half-split convention."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def decoder_layer(x, p: dict, t: dict):
    import jax
    import jax.numpy as jnp

    b, s, h = x.shape
    nh, nkv = t["num_attention_heads"], t["num_key_value_heads"]
    dh = t.get("head_dim") or h // nh
    eps, theta = t.get("rms_norm_eps", 1e-6), t.get("rope_theta", 1e6)
    y = rms_norm(x, p["in_norm"], eps)
    q = plain.linear(y, p["q_w"], p["q_b"]).reshape(b, s, nh, dh).transpose(0, 2, 1, 3)
    k = plain.linear(y, p["k_w"], p["k_b"]).reshape(b, s, nkv, dh).transpose(0, 2, 1, 3)
    v = plain.linear(y, p["v_w"], p["v_b"]).reshape(b, s, nkv, dh).transpose(0, 2, 1, 3)
    q, k = rope(q, theta), rope(k, theta)
    k, v = jnp.repeat(k, nh // nkv, axis=1), jnp.repeat(v, nh // nkv, axis=1)
    a = plain.attention(q, k, v, causal=True).transpose(0, 2, 1, 3).reshape(b, s, nh * dh)
    x = x + plain.linear(a, p["o_w"])
    y = rms_norm(x, p["post_norm"], eps)
    return x + plain.linear(jax.nn.silu(plain.linear(y, p["gate_w"])) * plain.linear(y, p["up_w"]), p["down_w"])


def decoder_layer_params(ck: plain.Checkpoint, i: int, bits: int | None) -> dict:
    pre = f"model.layers.{i}."
    p = {"in_norm": ck.get(pre + "input_layernorm.weight"),
         "post_norm": ck.get(pre + "post_attention_layernorm.weight")}
    for n in ("q", "k", "v", "o"):
        p[f"{n}_w"] = plain.fake_quant(ck.get(f"{pre}self_attn.{n}_proj.weight"), bits)
    for n in ("q", "k", "v"):
        p[f"{n}_b"] = ck.get(f"{pre}self_attn.{n}_proj.bias")
    for n in ("gate", "up", "down"):
        p[f"{n}_w"] = plain.fake_quant(ck.get(f"{pre}mlp.{n}_proj.weight"), bits)
    return p


def logits_at_served(model_dir: str, cfg: dict, requests: list[dict], bits: int | None):
    """For each request (``jpeg``, ``prompt_ids``, ``tokens``) the reference's
    logits at the positions that predict its served tokens:
    a list of [n_tokens, vocab] float32 device arrays."""
    import jax
    import jax.numpy as jnp

    t, v = cfg["text_config"], cfg["vision_config"]
    image_id = cfg["image_token_index"]
    ck = plain.Checkpoint(model_dir)
    with jax.default_matmul_precision("highest"):
        embed = ck.get("model.embed_tokens.weight")
        pixels = np.stack([canvas(r["jpeg"], v["image_size"]) for r in requests])
        vis = jax.jit(lambda p, px: vision_embeds(p, cfg, px))(vision_params(ck, cfg), jnp.asarray(pixels))
        n_vis = vis.shape[1]
        length = -(-max(len(r["prompt_ids"]) - 1 + n_vis + len(r["tokens"]) - 1 for r in requests) // 128) * 128
        width = max(len(r["tokens"]) for r in requests)
        ids = np.zeros((len(requests), length), np.int32)      # text id at each merged position
        src = np.full((len(requests), length), -1, np.int32)   # or the image token that stands there
        rows = np.zeros((len(requests), width), np.int32)      # merged positions that predict served tokens
        for b, r in enumerate(requests):
            seq = list(r["prompt_ids"]) + list(r["tokens"][:-1])
            at = seq.index(image_id)
            merged = seq[:at] + [0] * n_vis + seq[at + 1:]
            ids[b, :len(merged)] = merged
            src[b, at:at + n_vis] = np.arange(n_vis)
            first = len(r["prompt_ids"]) - 1 + n_vis - 1  # position that predicts token 0
            rows[b, :len(r["tokens"])] = first + np.arange(len(r["tokens"]))

        @jax.jit
        def merge(embed, vis, ids, src):
            text = embed[ids]
            image = jnp.take_along_axis(vis, jnp.maximum(src, 0)[:, :, None], axis=1)
            return jnp.where((src >= 0)[:, :, None], image, text)  # right padding: causal, so harmless

        @jax.jit
        def tail(x, norm_w, head, rows):
            x = jnp.take_along_axis(x, rows[:, :, None], axis=1)
            return rms_norm(x, norm_w, t.get("rms_norm_eps", 1e-6)) @ head.T

        x = merge(embed, vis, jnp.asarray(ids), jnp.asarray(src))
        layer = jax.jit(lambda x, p: decoder_layer(x, p, t))
        for i in range(t["num_hidden_layers"]):
            x = layer(x, decoder_layer_params(ck, i, bits))
        head = embed if t.get("tie_word_embeddings", True) else ck.get("lm_head.weight")
        logits = tail(x, ck.get("model.norm.weight"), head, jnp.asarray(rows))
        return [logits[b, :len(r["tokens"])] for b, r in enumerate(requests)]


def compare(sample: dict, model: dict, model_dir: str, precision: str, control: bool = False) -> dict:
    """``sample["requests"]``: finished requests of the window, each with
    ``jpeg``, ``prompt_ids`` and the served ``tokens``. At every served token
    the gap is how far its logit lies below the reference's best, in standard
    deviations of that position's logits (so that it reads alike at any
    vocabulary and width: an unrelated token sits about 4.5 below at 151,936
    words). Two numbers are compared: the mean gap over the sample, which is
    steady and grows with the square of the noise a lower precision adds, and
    the widest gap, which one wrong token moves. With ``control`` the token
    judged at each position is the one the reference puts first when computed
    at the precision step below."""
    import jax.numpy as jnp

    cfg = model["config"]
    ref = logits_at_served(model_dir, cfg, sample["requests"], plain.REFERENCE_BITS[precision])
    if control:
        bits = plain.CONTROL_BITS[precision]
        with plain.low_precision(bits):
            low = logits_at_served(model_dir, cfg, sample["requests"], bits)
        judged = [np.asarray(jnp.argmax(l, axis=-1)) for l in low]
    else:
        judged = [np.asarray(r["tokens"]) for r in sample["requests"]]
    gaps, agree, distinct = [], [], set()
    for logits, toks in zip(ref, judged):
        best = jnp.max(logits, axis=-1)
        picked = logits[jnp.arange(len(toks)), jnp.asarray(toks)]
        gaps.append(np.asarray((best - picked) / jnp.std(logits, axis=-1)))
        agree.append(np.asarray(jnp.argmax(logits, axis=-1)) == toks)
        distinct.update(int(x) for x in toks)
    gaps, agree = np.concatenate(gaps), np.concatenate(agree)
    return {
        "logit_gap_mean_std": float(gaps.mean()),
        "logit_gap_std": float(gaps.max()),
        "_detail": {"tokens": int(len(gaps)), "requests": len(ref), "argmax_agreement": float(agree.mean()),
                    "distinct_tokens": len(distinct)},
    }
