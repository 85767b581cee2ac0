"""Plain reference of the CLIP image path: JPEG bytes -> decode -> square
squash to the tower's input -> normalise -> ViT -> projection -> unit norm.
Follows the HF ``CLIPModel.get_image_features`` equations; reads the
benchmark's own checkpoint; imports nothing of the program."""

from __future__ import annotations

import numpy as np

from benchmark.references import plain

OPENAI_MEAN = (0.48145466, 0.4578275, 0.40821073)
OPENAI_STD = (0.26862954, 0.26130258, 0.27577711)
_NAMES = {"attn": "self_attn", "ln1": "layer_norm1", "ln2": "layer_norm2"}



def preprocess(jpeg: bytes, size: int) -> np.ndarray:
    """Scaled decode, then a bilinear squash to the tower's square input."""
    return plain.resize_bilinear(plain.decode_jpeg_scaled(jpeg, size), size, size)


def fault(sample: dict) -> dict:
    """An answer altered where it is produced: each photo is answered with
    its neighbour's vector, as rows mixed up in a batch would be."""
    return {**sample, "served": list(np.roll(np.asarray(sample["served"], np.float32), 1, axis=0))}


def embed_images(model_dir: str, cfg: dict, pixels_u8: np.ndarray, bits: int | None = None,
                 rows: int = 16) -> np.ndarray:
    """[N, S, S, 3] uint8 -> [N, D] float32 unit vectors, ``rows`` images at
    a time and one layer's weights on the device at a time."""
    import jax
    import jax.numpy as jnp

    v = cfg["vision_config"]
    eps, act, heads = v.get("layer_norm_eps", 1e-5), v.get("hidden_act", "quick_gelu"), v["num_attention_heads"]
    ck = plain.Checkpoint(model_dir)
    block = jax.jit(lambda x, p: plain.vit_block(x, p, heads, act, eps))
    with jax.default_matmul_precision("highest"):
        mean, std = jnp.asarray(OPENAI_MEAN, jnp.float32), jnp.asarray(OPENAI_STD, jnp.float32)
        w_patch = plain.fake_quant(ck.get("vision_model.embeddings.patch_embedding.weight"), bits)
        cls_tok = ck.get("vision_model.embeddings.class_embedding")
        pos = ck.get("vision_model.embeddings.position_embedding.weight")
        xs = []
        for i in range(0, len(pixels_u8), rows):
            px = (jnp.asarray(pixels_u8[i:i + rows], jnp.float32) / 255.0 - mean) / std
            x = jnp.einsum("bnhwc,ochw->bno", plain.patchify(px, v["patch_size"]), w_patch)
            x = jnp.concatenate([jnp.broadcast_to(cls_tok, (x.shape[0], 1, x.shape[2])), x], axis=1) + pos
            xs.append(plain.layer_norm(x, ck.get("vision_model.pre_layrnorm.weight"),
                                       ck.get("vision_model.pre_layrnorm.bias"), eps))
        for layer in range(v["num_hidden_layers"]):
            p = plain.vit_block_params(ck, f"vision_model.encoder.layers.{layer}", _NAMES, bits)
            xs = [block(x, p) for x in xs]
        pooled = jnp.concatenate([x[:, 0] for x in xs], axis=0)
        pooled = plain.layer_norm(pooled, ck.get("vision_model.post_layernorm.weight"),
                                  ck.get("vision_model.post_layernorm.bias"), eps)
        z = plain.linear(pooled, plain.fake_quant(ck.get("visual_projection.weight"), bits))
        z = z / jnp.linalg.norm(z, axis=-1, keepdims=True)
        return np.asarray(z, np.float32)


def cosine_gap(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    a = a / np.linalg.norm(a, axis=-1, keepdims=True)
    b = b / np.linalg.norm(b, axis=-1, keepdims=True)
    return 1.0 - np.sum(a * b, axis=-1)


def compare(sample: dict, model: dict, model_dir: str, precision: str, control: bool = False,
            activations: bool = True) -> dict:
    """``sample``: ``jpegs`` (list of bytes) and ``served`` ([N, D], what the
    timed path answered for them). Two numbers are compared, both of the
    distance 1 - cos between a served vector and the reference's: the median
    over the sample, which is steady and which a lower precision moves, and
    the widest, which one wrong answer moves. (How far the served vectors are
    from unit length is reported beside them and not compared: the control is
    of unit length too, so no limit would hold.) With ``control`` the
    reference at the precision step below stands in the program's place."""
    cfg = model["config"]
    size = cfg["vision_config"]["image_size"]
    pixels = np.stack([preprocess(j, size) for j in sample["jpegs"]])
    ref = embed_images(model_dir, cfg, pixels, plain.REFERENCE_BITS[precision])
    if control:
        bits = plain.CONTROL_BITS[precision]
        with plain.low_precision(bits, activations):
            served = embed_images(model_dir, cfg, pixels, bits)
    else:
        served = np.asarray(sample["served"], np.float32)
    gaps = cosine_gap(served, ref)
    norms = np.linalg.norm(served, axis=-1)
    return {
        "embed_cos_gap_median": float(np.median(gaps)),
        "embed_cos_gap": float(gaps.max()),
        "_detail": {"n": int(len(gaps)), "unit_norm_gap": float(np.abs(norms - 1.0).max()),
                    "distinct": float(np.min(cosine_gap(ref, np.roll(ref, 1, axis=0))))},
    }
