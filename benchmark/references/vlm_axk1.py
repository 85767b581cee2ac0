"""Plain reference of the ``axk1`` captioner: the repo's tower and splice (as
``references/vlm.py``) in front of a DeepSeek-V3-style decoder, written out
from the published ``config`` as one full forward pass over the prompt with
its served tokens. float32 at ``highest`` precision; no cache, no pages, no
kernels, attention un-absorbed (keys and values expanded a head) under the
causal mask alone, experts by a dense pass over the held range; reads the
benchmark's own checkpoint; imports nothing of the program. Layer by layer, a
request at a time, heads in blocks, so that ~4,200 positions at width 7,168
fit beside nothing else on a chip.

The equations (``h`` the layer's RMS-normed input, eps ``rms_norm_eps``):

- attention, every layer alike: ``c_q = RMSNorm(h W_qa)``, ``q = c_q W_qb``
  (heads x (nope + rope)); ``[c_kv, k_r] = h W_kva``, ``c_kv = RMSNorm(c_kv)``;
  ``k = [W_kb_nope c_kv ; k_r]`` with ``k_r`` shared by all heads, ``v = W_vb
  c_kv`` (``W_kvb`` holds both, heads x (nope + v)); the rope parts of ``q``
  and ``k_r`` rotated at YaRN's frequencies; every causal key attended
  (no indexer, no window), softmax of ``q.k * (nope + rope)^-1/2 *
  mscale(factor, mscale_all_dim)^2``; ``W_o`` on the heads' outputs, no gate.
- YaRN (``rope_scaling`` ``type`` ``yarn``): with ``d`` = rope, pair ``i``
  turns at ``f_i = theta^(-2i/d)``; ``dim(n) = d ln(original / (2 pi n)) / (2
  ln theta)``, ``low = floor(dim(beta_fast))``, ``high = ceil(dim(beta_slow))``
  clipped to [0, d - 1]; ramp ``r_i = clip((i - low) / (high - low), 0, 1)``;
  frequency ``f_i (1 - r_i) + (f_i / factor) r_i``; cos and sin times
  ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``; ``mscale(s, m)
  = 0.1 m ln s + 1``.
- feed-forward: layers below ``first_k_dense_replace`` a SwiGLU of
  ``intermediate_size``; the rest ``s = sigmoid(x W_r)`` over the whole
  router width, ``s' = s + b``; the experts form ``n_group`` groups of
  consecutive ids, a group's score is the sum of its two largest ``s'``, the
  ``topk_group`` best groups stay, the ``num_experts_per_tok`` largest ``s'``
  among their experts are selected; gates ``s_i / sum over the selected *
  routed_scaling_factor``; ``y = sum over selected and held g_i E_i(x) +
  E_shared(x)``.

Departures and assumptions (the configuration file lists them under
``assumed``): the router is ``transformers``' ``DeepseekV3TopkRouter``, which
reads no ``topk_method``: ``e_score_correction_bias`` ``b`` is added for
selection only (``topk_method`` "none" is a key no equation here depends on;
a zero ``b`` gives the rule without one); an expert outside the kept groups
is never selected (DeepSeek-V3's own inference code masks with -inf;
``transformers`` fills 0.0, which differs only where fewer than
``num_experts_per_tok`` kept experts have a positive ``s'``); RoPE pairs
value ``i`` with ``i + d/2`` (the half-split layout of the program's
``rope_rotate``; the published interleaving is a permutation of ``W_qb`` /
``W_kva`` columns, the same function of random weights); the tower is the
repo's own, in front of a text model; the multi-token-prediction head is left
out. ``n_routed_experts`` counts the experts held here: chip ``ep_rank`` of
``ep_size`` holds ``[rank * n, (rank + 1) * n)`` of a router ``n * ep_size``
wide, and what the absent experts would add is left out.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.references import plain
from benchmark.references.vlm import canvas, fault, prompt_ids, rms_norm, vision_embeds, vision_params  # noqa: F401
from benchmark.references.vlm_dots3 import Header, Program, held_range, swiglu  # noqa: F401

#: linear weights of a layer that a weight-only control quantizes (norms,
#: the router and its selection bias stay as they are, as in a deployment)
_ATTN = {"q_a": "q_a_proj", "q_b": "q_b_proj", "kv_a": "kv_a_proj_with_mqa", "kv_b": "kv_b_proj", "o": "o_proj"}

#: seconds the last comparison spent, by phase (summed over its passes)
SECONDS: dict = {}


def mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def rope_frequencies(t: dict) -> np.ndarray:
    """The ``rope / 2`` rotary frequencies, float64: YaRN's blend where
    ``rope_scaling`` is given, ``theta^(-2i/d)`` where it is null."""
    d, theta, r = t["qk_rope_head_dim"], float(t["rope_theta"]), t.get("rope_scaling")
    i = np.arange(d // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / d)
    if not r:
        return f
    dim = lambda turns: d * math.log(r["original_max_position_embeddings"] / (2 * math.pi * turns)) / (2 * math.log(theta))
    low, high = max(math.floor(dim(r["beta_fast"])), 0), min(math.ceil(dim(r["beta_slow"])), d - 1)
    ramp = np.clip((i - low) / (high - low if high > low else 0.001), 0.0, 1.0)
    return f * (1.0 - ramp) + f / r["factor"] * ramp


def rotation_scale(t: dict) -> float:
    r = t.get("rope_scaling")
    return mscale(r["factor"], r.get("mscale", 1)) / mscale(r["factor"], r.get("mscale_all_dim", 0)) if r else 1.0


def score_scale(t: dict) -> float:
    r, base = t.get("rope_scaling"), (t["qk_nope_head_dim"] + t["qk_rope_head_dim"]) ** -0.5
    return base * mscale(r["factor"], r["mscale_all_dim"]) ** 2 if r and r.get("mscale_all_dim") else base


def rotate(x, t: dict):
    """RoPE (half-split) on ``x`` [..., S, rope], positions 0..S-1."""
    import jax.numpy as jnp

    d = x.shape[-1]
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * jnp.asarray(rope_frequencies(t), jnp.float32)
    cos, sin = jnp.cos(ang) * rotation_scale(t), jnp.sin(ang) * rotation_scale(t)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention_layer(h, p: dict, t: dict, head_block: int = 16):
    """``h`` [S, hidden], one request's normed layer input -> the attention
    output before the residual, [S, hidden]."""
    import jax
    import jax.numpy as jnp

    s = h.shape[0]
    nh, eps = t["num_attention_heads"], t["rms_norm_eps"]
    kv_lora, nope, rope, vd = t["kv_lora_rank"], t["qk_nope_head_dim"], t["qk_rope_head_dim"], t["v_head_dim"]
    cq = rms_norm(plain.linear(h, p["q_a_w"]), p["q_a_norm"], eps)
    kv = plain.linear(h, p["kv_a_w"])
    ckv = rms_norm(kv[:, :kv_lora], p["kv_a_norm"], eps)
    k_r = rotate(kv[:, kv_lora:], t)  # [S, rope], shared by the heads
    q = plain.linear(cq, p["q_b_w"]).reshape(s, nh, nope + rope).transpose(1, 0, 2)
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], t)], axis=-1)  # [H, S, nope + rope]
    kvb = plain.linear(ckv, p["kv_b_w"]).reshape(s, nh, nope + vd).transpose(1, 0, 2)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(k_r, (nh, s, rope))], axis=-1)
    v = kvb[..., nope:]
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scale = score_scale(t)

    def heads(args):
        qb, kb, vb = args  # [hb, S, *]
        sc = jnp.where(causal, jnp.einsum("hsd,htd->hst", qb, kb) * scale, -jnp.inf)
        return jnp.einsum("hst,htd->hsd", jax.nn.softmax(sc, axis=-1), vb)

    hb = head_block if nh % head_block == 0 else nh
    split = lambda x: x.reshape(nh // hb, hb, s, x.shape[-1])
    o = jax.lax.map(heads, (split(q), split(k), split(v))).reshape(nh, s, vd)
    return plain.linear(o.transpose(1, 0, 2).reshape(s, nh * vd), p["o_w"])


def selected(scores, bias, t: dict):
    """[T, k] ids of the experts each token selects: the ``k`` largest
    ``score + bias`` among the experts of the ``topk_group`` best groups."""
    import jax
    import jax.numpy as jnp

    ranked = scores + bias
    groups, keep = t.get("n_group", 1), t.get("topk_group", 1)
    if groups > 1:
        tokens, width = ranked.shape
        per_group = ranked.reshape(tokens, groups, width // groups)
        group_score = jnp.sum(jax.lax.top_k(per_group, 2)[0], axis=-1)  # [T, G]: the two largest
        best = jnp.argsort(-group_score, axis=-1, stable=True)[:, :keep]  # ties: the smaller id stays
        kept = jnp.any(jnp.arange(groups)[None, None, :] == best[:, :, None], axis=1)  # [T, G]
        ranked = jnp.where(jnp.repeat(kept, width // groups, axis=1), ranked, -jnp.inf)
    return jax.lax.top_k(ranked, t["num_experts_per_tok"])[1]


def routing(y, router_w, bias, t: dict):
    """[T, router width] float32: the gate of every expert a token selected,
    zero elsewhere."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(y @ router_w.T)
    idx = selected(s, bias, t)
    picked = jnp.take_along_axis(s, idx, axis=-1)
    if t.get("norm_topk_prob", True):
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    picked = picked * float(t.get("routed_scaling_factor", 1.0))
    return jnp.einsum("tk,tke->te", picked, jax.nn.one_hot(idx, s.shape[-1], dtype=jnp.float32))


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
        _PROGRAMS[key] = {
            "norm": Program(lambda x, w: rms_norm(x, w, t["rms_norm_eps"])),
            "swiglu": Program(swiglu),
            "routing": Program(lambda y, w, b: routing(y, w, b, t)),
            "expert": Program(lambda y, a, b, c, g: g[:, None] * swiglu(y, a, b, c)),
            "attn": Program(lambda h, p: attention_layer(h, p, t)),
        }
    return _PROGRAMS[key]


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


def layer_params(ck, t: dict, i: int, bits) -> dict:
    pre = f"model.layers.{i}."
    q = lambda name: plain.fake_quant(ck.get(pre + name + ".weight"), bits)
    attn = {f"{k}_w": q("self_attn." + n) for k, n in _ATTN.items()}
    attn["q_a_norm"] = ck.get(pre + "self_attn.q_a_layernorm.weight")
    attn["kv_a_norm"] = ck.get(pre + "self_attn.kv_a_layernorm.weight")
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
    return p


def start_layer_programs(workers, head: Header, t: dict, batch: int, length: int) -> None:
    """Start compiling what ``decoder_layer`` will call for ``batch`` requests
    of ``length`` positions: a dense layer and an expert layer."""
    import jax
    import jax.numpy as jnp

    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    hidden, dense = t["hidden_size"], t.get("first_k_dense_replace", 0)
    row, rows, tokens = f32(length, hidden), f32(batch, length, hidden), f32(batch * length, hidden)
    run = programs(t)
    for i in sorted({0, min(dense, t["num_hidden_layers"] - 1)}):
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


def logits_at_served(model_dir: str, cfg: dict, requests: list[dict], bits):
    """For each request (``jpeg``, ``prompt_ids``, ``tokens``) the reference's
    logits at the positions that predict its served tokens: a list of
    [n_tokens, vocab] float32 device arrays."""
    import time
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp

    t, v = cfg["text_config"], cfg["vision_config"]
    image_id = cfg["image_token_index"]
    ck, head = plain.Checkpoint(model_dir), Header(model_dir)
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
        length = -(-max(len(r["prompt_ids"]) - 1 + n_vis + len(r["tokens"]) - 1 for r in requests) // 128) * 128
        width = max(len(r["tokens"]) for r in requests)
        ids = np.zeros((len(requests), length), np.int32)
        src = np.full((len(requests), length), -1, np.int32)
        rows = np.zeros((len(requests), width), np.int32)
        for b, r in enumerate(requests):
            seq = list(r["prompt_ids"]) + list(r["tokens"][:-1])
            at = seq.index(image_id)
            merged = seq[:at] + [0] * n_vis + seq[at + 1:]
            ids[b, :len(merged)] = merged
            src[b, at:at + n_vis] = np.arange(n_vis)
            first = len(r["prompt_ids"]) - 1 + n_vis - 1  # position that predicts token 0
            rows[b, :len(r["tokens"])] = first + np.arange(len(r["tokens"]))

        # every shape is known: the layers' programs compile while the tower runs
        start_layer_programs(workers, head, t, len(requests), length)
        tied = t.get("tie_word_embeddings", False)
        head_name = "model.embed_tokens.weight" if tied else "lm_head.weight"
        finish = Program(tail)
        finish.start(workers, jax.ShapeDtypeStruct((len(requests), length, t["hidden_size"]), jnp.float32),
                     head.get("model.norm.weight"), head.get(head_name), rows)

        embed = ck.get("model.embed_tokens.weight")
        vis = tower(vparams, jnp.asarray(pixels))
        x = jax.jit(merge)(embed, vis, jnp.asarray(ids), jnp.asarray(src))
        del vis, vparams
        lap("tower_and_merge", x)
        for i in range(t["num_hidden_layers"]):
            p = layer_params(ck, t, i, bits)
            lap("weights", p["attn"])
            x = decoder_layer(x, p, t, i)
            lap(f"layer_{i}", x)
        logits = finish(x, ck.get("model.norm.weight"), embed if tied else ck.get(head_name), jnp.asarray(rows))
        lap("head", logits)
        return [logits[b, :len(r["tokens"])] for b, r in enumerate(requests)]


def compare(sample: dict, model: dict, model_dir: str, precision: str, control: bool = False) -> dict:
    """As ``references/vlm.py``: at every served token, how far its logit
    lies below the reference's best, in standard deviations of that
    position's logits; the mean over the sample and the widest. With
    ``control`` the token judged is the one the reference puts first when its
    linear weights are held in the precision step below."""
    import jax.numpy as jnp

    cfg = model["config"]
    SECONDS.clear()
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
    widest = max(((float(g.max()), b, int(g.argmax())) for b, g in enumerate(gaps)))
    gaps, agree = np.concatenate(gaps), np.concatenate(agree)
    return {
        "logit_gap_mean_std": float(gaps.mean()),
        "logit_gap_std": float(gaps.max()),
        "_detail": {"tokens": int(len(gaps)), "requests": len(ref), "argmax_agreement": float(agree.mean()),
                    "distinct_tokens": len(distinct), "seconds": dict(SECONDS),
                    "widest_at": {"request": widest[1], "token": widest[2]}},  # where to look when it fails
    }
