"""CLIP's counts: the counts of every ``models.clip`` entry that names no other."""

from __future__ import annotations

from benchmark.counts.common import block_flops


def image_flops(cfg: dict) -> int:
    """One image through the vision tower and the projection."""
    v = cfg["vision_config"]
    w, p = v["hidden_size"], v["patch_size"]
    n = (v["image_size"] // p) ** 2
    patch = 2 * n * (3 * p * p) * w
    blocks = v["num_hidden_layers"] * block_flops(n + 1, w, v["intermediate_size"])
    return patch + blocks + 2 * w * cfg["projection_dim"]
