"""What the tensor listings share. A listing is ``benchmark/tensors/<name>.py``
with ``tensors(cfg) -> [(checkpoint name, shape), ...]`` for one model entry's
``config``, in the order the checkpoint is drawn; it may also define
``special_words(cfg) -> {id: word}`` (see ``weights.vlm_vocab``). Nothing here
imports the program or JAX."""

from __future__ import annotations

HF_CLIP = {"attn": "self_attn", "ln1": "layer_norm1", "ln2": "layer_norm2"}
VLM_TOWER = {"attn": "attn", "ln1": "norm1", "ln2": "norm2"}


def tower_tensors(prefix: str, width: int, inter: int, layers: int, names: dict) -> list:
    """One pre-LN transformer tower under ``names`` (HF CLIP or the VLM tower)."""
    out = []
    for i in range(layers):
        p = f"{prefix}.{i}."
        for proj in ("q_proj", "k_proj", "v_proj", "out_proj"):
            out.append((f"{p}{names['attn']}.{proj}.weight", (width, width)))
            out.append((f"{p}{names['attn']}.{proj}.bias", (width,)))
        for ln in (names["ln1"], names["ln2"]):
            out.append((f"{p}{ln}.weight", (width,)))
            out.append((f"{p}{ln}.bias", (width,)))
        out.append((f"{p}mlp.fc1.weight", (inter, width)))
        out.append((f"{p}mlp.fc1.bias", (inter,)))
        out.append((f"{p}mlp.fc2.weight", (width, inter)))
        out.append((f"{p}mlp.fc2.bias", (width,)))
    return out
