"""Compute kernels: attention (XLA + Pallas), image ops, NMS, CTC, sampling."""

from .attention import (
    attention,
    attention_cached,
    attention_reference,
    flash_attention,
    flash_attention_cache,
    repeat_kv,
)
from .ctc import ctc_collapse, ctc_greedy_device, load_ctc_vocab
from .image import (
    IMAGENET_MEAN,
    IMAGENET_STD,
    OPENAI_CLIP_MEAN,
    OPENAI_CLIP_STD,
    clip_preprocess,
    decode_image_bytes,
    letterbox_numpy,
    letterbox_params,
    normalize,
    resize_bilinear,
)
from .nms import nms_jax, nms_numpy
from .sampling import apply_repetition_penalty, greedy, sample, top_p_filter

__all__ = [
    "attention",
    "attention_cached",
    "attention_reference",
    "flash_attention",
    "flash_attention_cache",
    "repeat_kv",
    "ctc_greedy_device",
    "ctc_collapse",
    "load_ctc_vocab",
    "clip_preprocess",
    "decode_image_bytes",
    "letterbox_numpy",
    "letterbox_params",
    "normalize",
    "resize_bilinear",
    "OPENAI_CLIP_MEAN",
    "OPENAI_CLIP_STD",
    "IMAGENET_MEAN",
    "IMAGENET_STD",
    "nms_jax",
    "nms_numpy",
    "greedy",
    "sample",
    "top_p_filter",
    "apply_repetition_penalty",
]
