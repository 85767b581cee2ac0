"""Photo-like JPEGs from a seed, and tags that make each sent copy unique.

No JAX, no program code: the load generators and the plain references both
call this, and the same (seed, index, long_side) gives the same bytes.
"""

from __future__ import annotations

import io
import struct

import numpy as np


def photo_jpeg(seed: int, index: int, long_side: int, quality: int = 88, noise: int = 10) -> bytes:
    """A 4:3 photo ``long_side`` pixels wide: a smooth random colour field
    (what survives any sensible down-scaling) under tiled sensor-like noise
    (what gives the file a photo's entropy, and the decoder a photo's work)."""
    from PIL import Image

    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, index, long_side])
    w, h = long_side, long_side * 3 // 4
    coarse = rng.integers(0, 256, (6, 8, 3), np.uint8)
    img = np.asarray(Image.fromarray(coarse).resize((w, h), Image.BICUBIC), np.int16)
    tile = rng.integers(-noise, noise + 1, (256, 256, 3), np.int16)
    reps = (-(-h // 256), -(-w // 256), 1)
    img = img + np.tile(tile, reps)[:h, :w]
    np.clip(img, 0, 255, out=img)
    buf = io.BytesIO()
    Image.fromarray(img.astype(np.uint8)).save(buf, format="JPEG", quality=quality)
    return buf.getvalue()


def tagged(jpeg: bytes, n: int) -> bytes:
    """``jpeg`` with a comment segment carrying ``n`` after the SOI marker:
    other bytes, the same pixels. A result cache keyed on the payload never
    hits, and the decoder does its whole work again."""
    if jpeg[:2] != b"\xff\xd8":
        raise ValueError("not a JPEG")
    return b"\xff\xd8\xff\xfe" + struct.pack(">HQ", 10, n & 0xFFFFFFFFFFFFFFFF) + jpeg[2:]


def pool_sizes(spec: list[dict]) -> list[int]:
    """The fixed multiset of long sides a mix declares, e.g.
    ``[{"long_side": 640, "count": 8}, ...]`` -> one entry per photo."""
    return [int(e["long_side"]) for e in spec for _ in range(int(e["count"]))]


def seeded_order(seed: int, salt: int, n: int) -> list[int]:
    """A permutation of range(n): every seed sends the same set, in another order."""
    return [int(i) for i in np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, salt]).permutation(n)]
