"""Plain building blocks shared by the references: float32 ``jax.numpy`` at
``highest`` matmul precision, no kernels, no cache, no batching tricks.
Nothing here imports the program."""

from __future__ import annotations

import os

import numpy as np


class Checkpoint:
    """Lazy reader of the benchmark's own ``model.safetensors`` (HF names)."""

    def __init__(self, model_dir: str):
        from safetensors import safe_open

        self._f = safe_open(os.path.join(model_dir, "model.safetensors"), framework="numpy")

    def get(self, name: str):
        import jax.numpy as jnp

        # through bf16, the type the weights are served in
        return jnp.asarray(self._f.get_tensor(name)).astype(jnp.bfloat16).astype(jnp.float32)


#: precision the configuration states -> what the reference computes in
REFERENCE_BITS = {"float32": None, "bfloat16": None, "float16": None, "int8": 8}
#: ... -> the nearest precision below it, which the control computes in
CONTROL_BITS = {"float32": "bf16", "bfloat16": 8, "float16": 8, "int8": 4}

_round_activations = None  # set by :func:`low_precision` around a control


class low_precision:
    """``with low_precision(bits, activations):`` - while it holds,
    :func:`linear` rounds its input too (weights go through
    :func:`fake_quant` either way): to bfloat16 for ``bits == "bf16"``, the
    control of a float32 configuration; to ``bits`` integer bits a row
    (symmetric, absmax scale, as dynamic W8A8 does) where ``activations`` is
    set, the control of a tower that an integer deployment would run with
    both operands quantized. Weight-only (a decoder's w8a16) leaves it off."""

    def __init__(self, bits, activations: bool = False):
        self.on = bits if (bits == "bf16" or (activations and bits is not None)) else None

    def __enter__(self):
        global _round_activations
        self.before, _round_activations = _round_activations, self.on

    def __exit__(self, *exc):
        global _round_activations
        _round_activations = self.before


def fake_quant(w, bits):
    """An HF ``[out, in...]`` weight at a lower precision, returned in
    float32. An integer ``bits``: symmetric per-output-channel quantization,
    scale = absmax / qmax on each output row, round to nearest even, clip.
    ``"bf16"``: rounded to bfloat16. ``None``: as it is."""
    import jax.numpy as jnp

    if bits is None:
        return w
    if bits == "bf16":
        return w.astype(jnp.bfloat16).astype(jnp.float32)
    qmax = float(2 ** (bits - 1) - 1)
    flat = w.reshape(w.shape[0], -1)
    scale = jnp.maximum(jnp.max(jnp.abs(flat), axis=1, keepdims=True), 1e-30) / qmax
    q = jnp.clip(jnp.round(flat / scale), -qmax, qmax)
    return (q * scale).reshape(w.shape)


def linear(x, w, b=None):
    """HF ``nn.Linear``: ``x @ w.T + b`` with ``w`` as stored, ``[out, in]``."""
    if _round_activations == "bf16":
        import jax.numpy as jnp

        x = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif _round_activations is not None:
        x = fake_quant(x.reshape(-1, x.shape[-1]), _round_activations).reshape(x.shape)
    y = x @ w.T
    return y if b is None else y + b


def layer_norm(x, w, b, eps: float):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def activation(name: str):
    import jax
    import jax.numpy as jnp

    if name == "quick_gelu":
        return lambda x: x * jax.nn.sigmoid(1.702 * x)
    if name == "gelu":
        return lambda x: 0.5 * x * (1.0 + jax.lax.erf(x / jnp.sqrt(2.0)))
    if name in ("gelu_tanh", "gelu_new", "gelu_pytorch_tanh"):
        return lambda x: 0.5 * x * (1.0 + jnp.tanh(jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))
    raise KeyError(name)


def attention(q, k, v, causal: bool):
    """``q``: [B, H, S, D], ``k``/``v``: [B, H, T, D]; softmax in float32."""
    import jax
    import jax.numpy as jnp

    scores = jnp.einsum("bhsd,bhtd->bhst", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    if causal:
        s, t = scores.shape[-2:]
        mask = jnp.arange(t)[None, :] <= jnp.arange(s)[:, None] + (t - s)
        scores = jnp.where(mask, scores, -jnp.inf)
    return jnp.einsum("bhst,bhtd->bhsd", jax.nn.softmax(scores, axis=-1), v)


def vit_block(x, p: dict, heads: int, act: str, eps: float):
    """One pre-LN transformer block. ``p`` holds q/k/v/out/fc1/fc2 (``_w``,
    ``_b``) and ln1/ln2 tensors of one layer."""
    b, s, w = x.shape
    dh = w // heads
    h = layer_norm(x, p["ln1_w"], p["ln1_b"], eps)
    split = lambda y: y.reshape(b, s, heads, dh).transpose(0, 2, 1, 3)
    q, k, v = (split(linear(h, p[f"{n}_w"], p[f"{n}_b"])) for n in ("q", "k", "v"))
    a = attention(q, k, v, causal=False).transpose(0, 2, 1, 3).reshape(b, s, w)
    x = x + linear(a, p["out_w"], p["out_b"])
    h = layer_norm(x, p["ln2_w"], p["ln2_b"], eps)
    return x + linear(activation(act)(linear(h, p["fc1_w"], p["fc1_b"])), p["fc2_w"], p["fc2_b"])


def vit_block_params(ck: Checkpoint, prefix: str, names: dict, bits: int | None) -> dict:
    """The tensors of one block under ``prefix`` (HF CLIP or the VLM tower's
    names), linear weights through :func:`fake_quant`."""
    a = names["attn"]
    p = {}
    for short, long in (("q", f"{a}.q_proj"), ("k", f"{a}.k_proj"), ("v", f"{a}.v_proj"),
                        ("out", f"{a}.out_proj"), ("fc1", "mlp.fc1"), ("fc2", "mlp.fc2")):
        p[f"{short}_w"] = fake_quant(ck.get(f"{prefix}.{long}.weight"), bits)
        p[f"{short}_b"] = ck.get(f"{prefix}.{long}.bias")
    for short, long in (("ln1", names["ln1"]), ("ln2", names["ln2"])):
        p[f"{short}_w"] = ck.get(f"{prefix}.{long}.weight")
        p[f"{short}_b"] = ck.get(f"{prefix}.{long}.bias")
    return p


def patchify(pixels, patch: int):
    """[B, H, W, C] -> [B, gh*gw, ph, pw, C] non-overlapping patches."""
    b, h, w, c = pixels.shape
    x = pixels.reshape(b, h // patch, patch, w // patch, patch, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, (h // patch) * (w // patch), patch, patch, c)


def decode_jpeg(data: bytes) -> np.ndarray:
    import io

    from PIL import Image

    return np.asarray(Image.open(io.BytesIO(data)).convert("RGB"))


def scaled_decode_factor(height: int, width: int, target: int) -> int:
    """The largest of 1, 2, 4, 8 by which a decoder may shrink the image
    while both sides stay at least ``target``: a JPEG decoder averages whole
    blocks at no cost, and the program's documented decode policy takes it."""
    factor = 1
    while factor < 8 and min(height, width) // (factor * 2) >= target:
        factor *= 2
    return factor


def decode_jpeg_scaled(data: bytes, target: int) -> np.ndarray:
    """Decoded in full, then box-averaged by :func:`scaled_decode_factor`."""
    import io

    from PIL import Image

    im = Image.open(io.BytesIO(data)).convert("RGB")
    factor = scaled_decode_factor(im.size[1], im.size[0], target)
    return np.asarray(im.reduce(factor) if factor > 1 else im)


def resize_bilinear(img: np.ndarray, width: int, height: int) -> np.ndarray:
    """Two-tap bilinear interpolation at half-pixel centres, written out:
    output pixel i samples input position (i + 0.5) * in/out - 0.5, edges
    clamped, result rounded to the nearest integer."""
    if img.shape[1] == width and img.shape[0] == height:
        return img

    def taps(n_in: int, n_out: int):
        x = (np.arange(n_out) + 0.5) * (n_in / n_out) - 0.5
        lo = np.floor(x).astype(int)
        return np.clip(lo, 0, n_in - 1), np.clip(lo + 1, 0, n_in - 1), (x - lo).astype(np.float32)

    a = img.astype(np.float32)
    y0, y1, ty = taps(img.shape[0], height)
    x0, x1, tx = taps(img.shape[1], width)
    rows = a[y0] * (1 - ty)[:, None, None] + a[y1] * ty[:, None, None]
    out = rows[:, x0] * (1 - tx)[None, :, None] + rows[:, x1] * tx[None, :, None]
    return np.clip(np.rint(out), 0, 255).astype(np.uint8)
