"""``glm_moe_dsa`` decoder (GLM-5: latent attention with an indexer in every
layer, interleaved rotation, leading dense layers, sigmoid-routed experts
with one shared expert, one multi-token prediction layer) behind the repo's
ViT tower: the checkpoint of a ``models.vlm`` entry that names ``"tensors":
"glm5"``. Names follow ``transformers``' DeepSeek-V3.2 / GLM-MoE-DSA
checkpoints (``q_a_proj``, ``kv_a_proj_with_mqa``, ``indexer.wq_b``,
``indexer.wk``, ``indexer.k_norm``, ``indexer.weights_proj``,
``mlp.gate.e_score_correction_bias``, ``mlp.experts.N.*``,
``mlp.shared_experts.*``); the multi-token prediction layer is layer
``num_hidden_layers``, a layer of the same kind with ``enorm``, ``hnorm``,
``eh_proj`` and ``shared_head.norm`` beside it (its copies of the embedding
and the head are left out: it shares the decoder's). ``n_routed_experts``
counts the experts held here: chip ``ep_rank`` of ``ep_size`` holds ``[rank
* n, (rank + 1) * n)`` under their ids in the whole bank, and the router is
``n * ep_size`` wide."""

from __future__ import annotations

from benchmark.tensors.axk1 import attention as latent
from benchmark.tensors.dots3 import swiglu
from benchmark.tensors.vlm import norms, vision


def special_words(cfg: dict) -> dict[int, str]:
    """The ids the chat template and the configuration name. ``run.py`` asks
    for the vocabulary before it writes a checkpoint or touches a chip, so a
    checkout whose program reads no such decoder (one older than the
    configuration) is told so here, in seconds and with no chip held, where
    the run would otherwise write 6.6 GB of weights and fail at its warm-up
    traffic: the one place a listing asks the program anything, what
    ``VLMConfig.from_hf`` makes of ``cfg``."""
    from benchmark import cells, weights
    from lumen_tpu.models.vlm.modeling import VLMConfig

    t = cfg["text_config"]
    try:
        decoder = VLMConfig.from_hf(cfg).decoder
    except (KeyError, TypeError, ValueError, NotImplementedError) as e:
        raise cells.CellError(f"this checkout's program cannot read model_type {t['model_type']!r}: {e!r}") from e
    if not getattr(decoder, "latent", False) or getattr(decoder, "mtp_layers", 0) != t.get("num_nextn_predict_layers", 0):
        raise cells.CellError(
            f"this checkout's program reads no model_type {t['model_type']!r}: VLMConfig.from_hf gives a decoder "
            "without latent layers or without its multi-token prediction layer, so the configuration cannot run here"
        )
    return weights._special_words(cfg)


def attention(p: str, t: dict) -> list[tuple[str, tuple]]:
    h, q_lora, a = t["hidden_size"], t["q_lora_rank"], p + "self_attn."
    j, di = t["index_n_heads"], t["index_head_dim"]
    return latent(p, t) + [
        (a + "indexer.wq_b.weight", (j * di, q_lora)), (a + "indexer.wk.weight", (di, h)),
        (a + "indexer.k_norm.weight", (di,)), (a + "indexer.k_norm.bias", (di,)),
        (a + "indexer.weights_proj.weight", (j, h)),
    ]


def layer(p: str, t: dict, dense: bool) -> list[tuple[str, tuple]]:
    h, f = t["hidden_size"], t["moe_intermediate_size"]
    held, ep, rank = t["n_routed_experts"], t.get("ep_size", 1), t.get("ep_rank", 0)
    out = attention(p, t)
    if dense:
        out += swiglu(p + "mlp.", h, t["intermediate_size"])
    else:
        out += [(p + "mlp.gate.weight", (held * ep, h)), (p + "mlp.gate.e_score_correction_bias", (held * ep,))]
        for e in range(rank * held, (rank + 1) * held):
            out += swiglu(f"{p}mlp.experts.{e}.", h, f)
        if t.get("n_shared_experts"):
            out += swiglu(p + "mlp.shared_experts.", h, f * t["n_shared_experts"])
    return out + norms(p, h)


def tensors(cfg: dict) -> list[tuple[str, tuple]]:
    t = cfg["text_config"]
    h, n = t["hidden_size"], t["num_hidden_layers"]
    out = [("model.embed_tokens.weight", (t["vocab_size"], h))]
    for i in range(n):
        out += layer(f"model.layers.{i}.", t, i < t.get("first_k_dense_replace", 0))
    for i in range(n, n + t.get("num_nextn_predict_layers", 0)):
        p = f"model.layers.{i}."
        out += layer(p, t, False) + [
            (p + "enorm.weight", (h,)), (p + "hnorm.weight", (h,)), (p + "eh_proj.weight", (h, 2 * h)),
            (p + "shared_head.norm.weight", (h,)),
        ]
    out.append(("model.norm.weight", (h,)))
    if not t.get("tie_word_embeddings", False):
        out.append(("lm_head.weight", (t["vocab_size"], h)))
    return out + vision(cfg)
