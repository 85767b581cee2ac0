"""Plain reference of the ``glm_moe_dsa`` (GLM-5) captioner: the repo's tower
and splice (as ``references/vlm.py``) in front of a decoder of latent
attention layers, each with an indexer, and sigmoid-routed experts, with its
multi-token prediction layer, written out from the published ``config`` as
one full forward pass over the prompt with its served tokens. float32 at
``highest`` precision; no cache, no pages, no kernels, attention un-absorbed
(keys and values expanded a head), experts by a dense pass over the held
range; reads the benchmark's own checkpoint; imports nothing of the program.
Layer by layer, a request at a time, heads and index queries in blocks.

The equations (``h`` the layer's RMS-normed input, eps ``rms_norm_eps``):

- attention, every layer alike, the multi-token prediction layer too:
  ``c_q = RMSNorm(h W_qa)``, ``q = c_q W_qb`` (heads x (nope + rope));
  ``[c_kv, k_r] = h W_kva``, ``c_kv = RMSNorm(c_kv)``, ``k_r`` shared by all
  heads; ``[k_n, v] = c_kv W_kvb`` (heads x (nope + v)); the rope parts of
  ``q`` and ``k_r`` rotated in INTERLEAVED pairs (``rope_interleave``:
  channels ``2i`` and ``2i + 1`` turn together at ``theta^(-2i/rope)``,
  ``theta`` from ``rope_parameters``); scores ``q.k * (nope + rope)^-1/2``.
  Indexer: ``q_I = c_q W_Iq`` (index heads x index dim), ``k_I =
  LayerNorm(h W_Ik)``, the first ``rope`` values of each rotated in
  interleaved pairs too (``indexer_rope_interleave``), ``w = h W_Iw *
  (heads * dim)^-1/2``, ``I(t,s) = sum_j w(t,j) relu(q_I(t,j).k_I(s))``;
  query ``t`` attends the ``index_topk`` causal keys of largest ``I(t,.)``
  (all of them while it has no more). No gate, no rescale. ``W_o`` on the
  heads' outputs.
- feed-forward: layers below ``first_k_dense_replace`` a SwiGLU of
  ``intermediate_size``; the rest ``s = sigmoid(x W_r)`` over the whole
  router width, the ``num_experts_per_tok`` largest ``s + b`` selected
  (``noaux_tc``, within the ``topk_group`` best of ``n_group`` groups: one
  group, no limit, in GLM-5), gates ``s_i / sum over the selected *
  routed_scaling_factor``; ``y = sum over selected and held g_i E_i(x) +
  E_shared(x)``.
- multi-token prediction (``num_nextn_predict_layers`` 1, layer
  ``num_hidden_layers``): at position ``i``, ``x_i = W_eh [RMSNorm_e(e_{i+1})
  ; RMSNorm_h(h_i)]`` with ``h_i`` the decoder's last hidden state before its
  final norm and ``e_{i+1}`` the embedding at ``i + 1`` of the served
  sequence (the merged prompt, then the served tokens); ``x'`` = the layer
  above over ``x`` (its own causal attention over these positions, its own
  indexer, its own experts); the draft of the token at ``i + 2`` is judged
  under ``W_head RMSNorm_s(x'_i)``. The layer runs over the served sequence
  a request at a time.

Departures and assumptions (the configuration file lists them under
``assumed``): the multi-token prediction layer has an indexer, as every other
layer has; its ``embed_tokens`` and ``shared_head.head`` copies equal the
main model's (the checkpoint holds neither; ``e`` and ``W_head`` are the
main model's); the HF names are those of ``transformers``' DeepSeek-V3.2 /
GLM-MoE-DSA (``tensors/glm5.py``), not confirmed against a checkpoint;
DeepSeek-V3.2's Hadamard rotation of the index query and key is left out (it
is orthogonal, so the index scores are unchanged in bf16 and wider); keys
that tie with the ``index_topk``-th index score are all kept; the tower is
the repo's own, in front of a text model, and an image position's ``e`` is
its image embedding. ``n_routed_experts`` counts the experts held here: chip
``ep_rank`` of ``ep_size`` holds ``[rank * n, (rank + 1) * n)`` of a router
``n * ep_size`` wide, and what the absent experts would add is left out.

A request of the sample carries its drafts after its tokens: ``tokens`` is
the served ids, then one entry a served id, the draft the program checked
for it or -1 where it checked none (``generators/caption_stream_mtp.py``:
the harness passes a request's ``tokens`` alone to the reference).
"""

from __future__ import annotations

import numpy as np

from benchmark.references import plain
from benchmark.references.vlm import canvas, prompt_ids, rms_norm, vision_embeds, vision_params  # noqa: F401
from benchmark.references.vlm_axk1 import routing
from benchmark.references.vlm_dots3 import Header, Program, held_range, swiglu

#: linear weights of a layer that a weight-only control quantizes (norms,
#: the router and its selection bias stay as they are, as in a deployment)
_ATTN = {"q_a": "q_a_proj", "q_b": "q_b_proj", "kv_a": "kv_a_proj_with_mqa", "kv_b": "kv_b_proj", "o": "o_proj"}
_INDEXER = {"iq": "indexer.wq_b", "ik": "indexer.wk", "iw": "indexer.weights_proj"}

#: seconds the last comparison spent, by phase (summed over its passes)
SECONDS: dict = {}


def rope_theta(t: dict) -> float:
    return float(t.get("rope_theta", (t.get("rope_parameters") or {}).get("rope_theta", 10000.0)))


def rope_pairs(x, theta: float, interleave: bool):
    """RoPE on ``x`` [..., S, d], positions 0..S-1: channels ``2i`` and
    ``2i + 1`` turn together at ``theta^(-2i/d)`` where ``interleave``, else
    ``i`` and ``i + d/2``."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if interleave:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def index_mask(h, cq, p: dict, t: dict, rows: int = 256):
    """[S, S] bool: the causal keys each query's indexer keeps."""
    import jax
    import jax.numpy as jnp

    s = h.shape[0]
    j, di, topk, rope = t["index_n_heads"], t["index_head_dim"], t["index_topk"], t["qk_rope_head_dim"]
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    if s <= topk:
        return causal
    spin = bool(t.get("indexer_rope_interleave", False))
    rot = lambda x: jnp.concatenate([rope_pairs(x[..., :rope], rope_theta(t), spin), x[..., rope:]], axis=-1)
    q_i = rot(plain.linear(cq, p["iq_w"]).reshape(s, j, di).transpose(1, 0, 2))  # [J, S, Di]
    k_i = rot(plain.layer_norm(plain.linear(h, p["ik_w"]), p["ik_norm_w"], p["ik_norm_b"], t["rms_norm_eps"]))
    w = plain.linear(h, p["iw_w"]) * (j ** -0.5 * di ** -0.5)  # [S, J]
    pad = -s % rows
    q_b = jnp.pad(q_i, ((0, 0), (0, pad), (0, 0))).reshape(j, -1, rows, di).transpose(1, 0, 2, 3)
    w_b = jnp.pad(w, ((0, pad), (0, 0))).reshape(-1, rows, j)

    def block(args):
        q, wt = args  # [J, rows, Di], [rows, J]
        return jnp.einsum("rj,jrs->rs", wt, jax.nn.relu(jnp.einsum("jrd,sd->jrs", q, k_i)))

    scores = jax.lax.map(block, (q_b, w_b)).reshape(-1, s)[:s]
    masked = jnp.where(causal, scores, -jnp.inf)
    kth = jax.lax.top_k(masked, topk)[0][:, -1:]
    return causal & (masked >= kth)


def attention_layer(h, p: dict, t: dict, head_block: int = 16):
    """``h`` [S, hidden], one request's normed layer input -> the attention
    output before the residual, [S, hidden]."""
    import jax
    import jax.numpy as jnp

    s = h.shape[0]
    nh, eps = t["num_attention_heads"], t["rms_norm_eps"]
    kv_lora, nope, rope, vd = t["kv_lora_rank"], t["qk_nope_head_dim"], t["qk_rope_head_dim"], t["v_head_dim"]
    spin, theta = bool(t.get("rope_interleave", False)), rope_theta(t)
    cq = rms_norm(plain.linear(h, p["q_a_w"]), p["q_a_norm"], eps)
    kv = plain.linear(h, p["kv_a_w"])
    ckv = rms_norm(kv[:, :kv_lora], p["kv_a_norm"], eps)
    k_r = rope_pairs(kv[:, kv_lora:], theta, spin)  # [S, rope], shared by the heads
    q = plain.linear(cq, p["q_b_w"]).reshape(s, nh, nope + rope).transpose(1, 0, 2)
    q = jnp.concatenate([q[..., :nope], rope_pairs(q[..., nope:], theta, spin)], axis=-1)  # [H, S, nope + rope]
    kvb = plain.linear(ckv, p["kv_b_w"]).reshape(s, nh, nope + vd).transpose(1, 0, 2)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(k_r, (nh, s, rope))], axis=-1)
    v = kvb[..., nope:]
    see = index_mask(h, cq, p, t)
    scale = (nope + rope) ** -0.5

    def heads(args):
        qb, kb, vb = args  # [hb, S, *]
        sc = jnp.where(see, jnp.einsum("hsd,htd->hst", qb, kb) * scale, -jnp.inf)
        return jnp.einsum("hst,htd->hsd", jax.nn.softmax(sc, axis=-1), vb)

    hb = head_block if nh % head_block == 0 else nh
    split = lambda x: x.reshape(nh // hb, hb, s, x.shape[-1])
    o = jax.lax.map(heads, (split(q), split(k), split(v))).reshape(nh, s, vd)
    return plain.linear(o.transpose(1, 0, 2).reshape(s, nh * vd), p["o_w"])


def expert_layer(y, p: dict, t: dict, held: tuple[int, int] | None = None, shared: bool = True):
    """``y`` [T, hidden] -> the held experts' part of the layer (every token
    densely through every held expert, weighted by its gate there; an absent
    expert adds nothing), plus the shared expert where ``shared``.
    ``p["experts"]`` maps an expert's id to its (gate, up, down) weights, or
    to a function that loads them."""
    import jax.numpy as jnp

    lo, hi = held if held is not None else held_range(t)[:2]
    run = programs(t)
    gates = run["routing"](y, p["router_w"], p["select_bias"])
    out = jnp.zeros_like(y)
    for e in range(lo, hi):
        w = p["experts"][e]
        w = w() if callable(w) else w
        out = out + run["expert"](y, *w, gates[:, e])
    if shared and "shared" in p:
        out = out + run["expert"](y, *p["shared"], jnp.ones_like(y[:, 0]))  # ungated
    return out


_PROGRAMS: dict = {}


def programs(t: dict) -> dict:
    """The compiled pieces of a layer, made once for the configuration ``t``
    and the precision the pass computes in."""
    import json

    key = (json.dumps(t, sort_keys=True), plain._round_activations)  # plain.linear reads it when traced
    if key not in _PROGRAMS:
        eps = t["rms_norm_eps"]
        _PROGRAMS[key] = {
            "norm": Program(lambda x, w: rms_norm(x, w, eps)),
            "swiglu": Program(swiglu),
            "routing": Program(lambda y, w, b: routing(y, w, b, t)),
            "expert": Program(lambda y, a, b, c, g: g[:, None] * swiglu(y, a, b, c)),
            "attn": Program(lambda h, p: attention_layer(h, p, t)),
            "mtp_in": Program(lambda h, e, p: plain.linear(
                _concat(rms_norm(e, p["enorm"], eps), rms_norm(h, p["hnorm"], eps)), p["eh_w"])),
        }
    return _PROGRAMS[key]


def _concat(a, b):
    import jax.numpy as jnp

    return jnp.concatenate([a, b], axis=-1)


_MTP_IN = ("enorm", "hnorm", "eh_w")


def decoder_layer(x, p: dict, t: dict, i: int):
    """``x`` [B, S, hidden] -> the layer's output; attention a request at a
    time, the feed-forward over all tokens."""
    import jax.numpy as jnp

    run = programs(t)
    attn, norm = run["attn"], run["norm"]
    x = jnp.stack([x[b] + attn(norm(x[b], p["in_norm"]), p["attn"]) for b in range(x.shape[0])])
    y = norm(x, p["post_norm"]).reshape(-1, x.shape[-1])
    if i < t.get("first_k_dense_replace", 0):
        f = run["swiglu"](y, *p["mlp"])
    else:
        f = expert_layer(y, p["moe"], t)
    return x + f.reshape(x.shape)


def mtp_layer(h, e_next, p: dict, t: dict):
    """The multi-token prediction layer over ``h`` [B, S, hidden] (the
    decoder's last hidden states before its final norm) and ``e_next`` (the
    embedding at each position's next): its output before the head's norm."""
    x = programs(t)["mtp_in"](h, e_next, {k: p[k] for k in _MTP_IN})
    return decoder_layer(x, p, t, t["num_hidden_layers"])


def layer_params(ck, t: dict, i: int, bits) -> dict:
    """Layer ``i``'s weights; ``i`` = ``num_hidden_layers`` is the
    multi-token prediction layer, with its norms and projection besides."""
    pre = f"model.layers.{i}."
    q = lambda name: plain.fake_quant(ck.get(pre + name + ".weight"), bits)
    attn = {f"{k}_w": q("self_attn." + n) for k, n in {**_ATTN, **_INDEXER}.items()}
    attn["q_a_norm"] = ck.get(pre + "self_attn.q_a_layernorm.weight")
    attn["kv_a_norm"] = ck.get(pre + "self_attn.kv_a_layernorm.weight")
    attn["ik_norm_w"] = ck.get(pre + "self_attn.indexer.k_norm.weight")
    attn["ik_norm_b"] = ck.get(pre + "self_attn.indexer.k_norm.bias")
    p = {"in_norm": ck.get(pre + "input_layernorm.weight"),
         "post_norm": ck.get(pre + "post_attention_layernorm.weight"), "attn": attn}
    three = lambda stem: tuple(q(f"{stem}.{n}_proj") for n in ("gate", "up", "down"))
    if i < t.get("first_k_dense_replace", 0):
        p["mlp"] = three("mlp")
    else:
        lo, hi, _ = held_range(t)
        p["moe"] = {
            "router_w": ck.get(pre + "mlp.gate.weight"),
            "select_bias": ck.get(pre + "mlp.gate.e_score_correction_bias"),
            "experts": {e: (lambda e=e: three(f"mlp.experts.{e}")) for e in range(lo, hi)},
        }
        if t.get("n_shared_experts"):
            p["moe"]["shared"] = three("mlp.shared_experts")
    if i == t["num_hidden_layers"]:
        p.update(enorm=ck.get(pre + "enorm.weight"), hnorm=ck.get(pre + "hnorm.weight"), eh_w=q("eh_proj"),
                 head_norm=ck.get(pre + "shared_head.norm.weight"))
    return p


def start_layer_programs(workers, head: Header, t: dict, batch: int, length: int) -> None:
    """Start compiling what the pass will call for ``batch`` requests of
    ``length`` positions: a dense layer, an expert layer, the multi-token
    prediction layer's input."""
    import jax
    import jax.numpy as jnp

    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    hidden, dense, n = t["hidden_size"], t.get("first_k_dense_replace", 0), t["num_hidden_layers"]
    row, rows, tokens = f32(length, hidden), f32(batch, length, hidden), f32(batch * length, hidden)
    run = programs(t)
    for i in sorted({0, min(dense, n - 1)}):
        p = layer_params(head, t, i, None)
        run["attn"].start(workers, row, p["attn"])
        run["norm"].start(workers, row, p["in_norm"])
        run["norm"].start(workers, rows, p["post_norm"])
        if i < dense:
            run["swiglu"].start(workers, tokens, *p["mlp"])
        else:
            moe = p["moe"]
            run["routing"].start(workers, tokens, moe["router_w"], moe["select_bias"])
            run["expert"].start(workers, tokens, *moe["experts"][held_range(t)[0]](), f32(batch * length))
    p = layer_params(head, t, n, None)
    run["mtp_in"].start(workers, rows, rows, {k: p[k] for k in _MTP_IN})


def split_drafts(r: dict) -> tuple[list[int], list[int]]:
    """A sampled request's served ids and, one a served id, the draft the
    program checked for it (-1: none)."""
    toks = list(r["tokens"])
    n = len(toks) // 2
    return toks[:n], toks[n:]


def logits_at_served(model_dir: str, cfg: dict, requests: list[dict], bits):
    """For each request (``jpeg``, ``prompt_ids``, ``tokens``: served ids,
    then drafts) the reference's logits at the positions that predict its
    served tokens, and the multi-token prediction layer's at the positions
    that draft them: two lists of [n_tokens, vocab] float32 device arrays
    (row ``j`` of the second drafts token ``j``; row 0, which nothing drafts,
    is the layer's at the position before)."""
    import time
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp

    t, v = cfg["text_config"], cfg["vision_config"]
    image_id = cfg["image_token_index"]
    ck, head = plain.Checkpoint(model_dir), Header(model_dir)
    served = [split_drafts(r)[0] for r in requests]
    clock = [time.perf_counter()]

    def lap(name: str, done) -> None:
        """Where the reference's own time went (``_detail`` of the result)."""
        jax.block_until_ready(done)
        clock.append(time.perf_counter())
        SECONDS[name] = round(SECONDS.get(name, 0.0) + clock[-1] - clock[-2], 2)

    def merge(embed, vis, ids, src):
        text = embed[ids]
        image = jnp.take_along_axis(vis, jnp.maximum(src, 0)[:, :, None], axis=1)
        return jnp.where((src >= 0)[:, :, None], image, text)  # right padding: causal, so harmless

    def tail(x, norm_w, head_w, rows):
        x = jnp.take_along_axis(x, rows[:, :, None], axis=1)
        return rms_norm(x, norm_w, t["rms_norm_eps"]) @ head_w.T

    with jax.default_matmul_precision("highest"), ThreadPoolExecutor(8) as workers:
        pixels = np.stack([canvas(r["jpeg"], v["image_size"]) for r in requests])
        vparams = vision_params(ck, cfg)
        tower = jax.jit(lambda p, px: vision_embeds(p, cfg, px))
        n_vis = jax.eval_shape(tower, vparams, pixels).shape[1]
        length = -(-max(len(r["prompt_ids"]) - 1 + n_vis + len(s) for r, s in zip(requests, served)) // 128) * 128
        width = max(len(s) for s in served)
        ids = np.zeros((len(requests), length), np.int32)
        src = np.full((len(requests), length), -1, np.int32)
        rows = np.zeros((len(requests), width), np.int32)
        for b, (r, s) in enumerate(zip(requests, served)):
            seq = list(r["prompt_ids"]) + list(s)  # the last served id is the last draft's input
            at = seq.index(image_id)
            merged = seq[:at] + [0] * n_vis + seq[at + 1:]
            ids[b, :len(merged)] = merged
            src[b, at:at + n_vis] = np.arange(n_vis)
            first = len(r["prompt_ids"]) - 1 + n_vis - 1  # position that predicts token 0
            rows[b, :len(s)] = first + np.arange(len(s))

        start_layer_programs(workers, head, t, len(requests), length)
        head_name = "model.embed_tokens.weight" if t.get("tie_word_embeddings", False) else "lm_head.weight"
        finish = Program(tail)
        shape = jax.ShapeDtypeStruct((len(requests), length, t["hidden_size"]), jnp.float32)
        finish.start(workers, shape, head.get("model.norm.weight"), head.get(head_name), rows)

        embed = ck.get("model.embed_tokens.weight")
        vis = tower(vparams, jnp.asarray(pixels))
        x0 = jax.jit(merge)(embed, vis, jnp.asarray(ids), jnp.asarray(src))
        del vis, vparams
        lap("tower_and_merge", x0)
        x = x0
        for i in range(t["num_hidden_layers"]):
            p = layer_params(ck, t, i, bits)
            lap("weights", p["attn"])
            x = decoder_layer(x, p, t, i)
            lap(f"layer_{i}", x)
        head_w = embed if head_name == "model.embed_tokens.weight" else ck.get(head_name)
        logits = finish(x, ck.get("model.norm.weight"), head_w, jnp.asarray(rows))
        lap("head", logits)
        p = layer_params(ck, t, t["num_hidden_layers"], bits)
        y = mtp_layer(x, jnp.roll(x0, -1, axis=1), p, t)  # position i: h_i and the embedding at i + 1
        del x, x0
        lap("mtp_layer", y)
        drafts = finish(y, p["head_norm"], head_w, jnp.asarray(np.maximum(rows - 1, 0)))
        lap("mtp_head", drafts)
        return ([logits[b, :len(s)] for b, s in enumerate(served)],
                [drafts[b, :len(s)] for b, s in enumerate(served)])


def fault(sample: dict) -> dict:
    """A token altered where it is produced: every fifth served token is
    replaced by its neighbour in the vocabulary (the drafts as they were)."""
    out = []
    for r in sample["requests"]:
        toks, drafts = split_drafts(r)
        out.append({**r, "tokens": [x + 1 if i % 5 == 4 else x for i, x in enumerate(toks)] + drafts})
    return {"requests": out}


def fault_drafts(sample: dict) -> dict:
    """A draft altered where it is produced: every fifth draft the program
    checked is replaced by its neighbour in the vocabulary."""
    out = []
    for r in sample["requests"]:
        toks, drafts = split_drafts(r)
        made = [i for i, d in enumerate(drafts) if d >= 0]
        altered = list(drafts)
        for k, i in enumerate(made):
            if k % 5 == 4:
                altered[i] = drafts[i] + 1
        out.append({**r, "tokens": toks + altered})
    return {"requests": out}


def _gaps(logits, toks) -> tuple[np.ndarray, np.ndarray]:
    """How far each judged id's logit lies below the row's best, in
    standard deviations of the row's logits; and whether it is the best."""
    import jax.numpy as jnp

    toks = jnp.asarray(toks)
    best = jnp.max(logits, axis=-1)
    picked = logits[jnp.arange(len(toks)), toks]
    return np.asarray((best - picked) / jnp.std(logits, axis=-1)), np.asarray(jnp.argmax(logits, axis=-1) == toks)


def compare(sample: dict, model: dict, model_dir: str, precision: str, control: bool = False) -> dict:
    """As ``references/vlm.py``, for the served tokens and for the drafts:
    at every served token, how far its logit lies below the reference's best,
    in standard deviations of that position's logits, the mean over the
    sample and the widest (``logit_gap_*``); the same for every draft the
    program checked, under the multi-token prediction layer's logits at the
    position that drafted it (``draft_gap_*``). With ``control`` the ids
    judged are those the reference puts first when its linear weights are
    held in the precision step below, at the same positions."""
    import jax.numpy as jnp

    cfg = model["config"]
    SECONDS.clear()
    ref, ref_drafts = logits_at_served(model_dir, cfg, sample["requests"], plain.REFERENCE_BITS[precision])
    split = [split_drafts(r) for r in sample["requests"]]
    if control:
        bits = plain.CONTROL_BITS[precision]
        with plain.low_precision(bits):
            low, low_drafts = logits_at_served(model_dir, cfg, sample["requests"], bits)
        judged = [np.asarray(jnp.argmax(l, axis=-1)) for l in low]
        judged_drafts = [np.where(np.asarray(d) >= 0, np.asarray(jnp.argmax(l, axis=-1)), -1)
                         for l, (_, d) in zip(low_drafts, split)]
    else:
        judged = [np.asarray(s) for s, _ in split]
        judged_drafts = [np.asarray(d) for _, d in split]
    gaps, agree, distinct = [], [], set()
    for logits, toks in zip(ref, judged):
        g, a = _gaps(logits, toks)
        gaps.append(g)
        agree.append(a)
        distinct.update(int(x) for x in toks)
    dgaps, dagree = [], []
    for logits, drafts in zip(ref_drafts, judged_drafts):
        made = np.flatnonzero(drafts >= 0)
        if len(made):
            g, a = _gaps(logits[jnp.asarray(made)], drafts[made])
            dgaps.append(g)
            dagree.append(a)
    widest = max(((float(g.max()), b, int(g.argmax())) for b, g in enumerate(gaps)))
    gaps, agree = np.concatenate(gaps), np.concatenate(agree)
    dgaps = np.concatenate(dgaps) if dgaps else np.zeros((0,))
    return {
        "logit_gap_mean_std": float(gaps.mean()),
        "logit_gap_std": float(gaps.max()),
        # no draft at all is no draft judged: nothing to pass
        "draft_gap_mean_std": float(dgaps.mean()) if len(dgaps) else float("inf"),
        "draft_gap_std": float(dgaps.max()) if len(dgaps) else float("inf"),
        "_detail": {"tokens": int(len(gaps)), "requests": len(ref), "argmax_agreement": float(agree.mean()),
                    "drafts": int(len(dgaps)),
                    "draft_argmax_agreement": float(np.concatenate(dagree).mean()) if dagree else None,
                    "distinct_tokens": len(distinct), "seconds": dict(SECONDS),
                    "widest_at": {"request": widest[1], "token": widest[2]}},  # where to look when it fails
    }
