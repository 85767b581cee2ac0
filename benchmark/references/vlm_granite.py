"""Plain reference of the ``granitemoehybrid`` captioner: the repo's tower
and splice (as ``references/vlm.py``) in front of a hybrid decoder of Mamba-2
and grouped-query layers with softmax-routed experts, written out from the
published ``config`` as one full forward pass over the prompt with its served
tokens. float32 at ``highest`` precision; no cache, no pages, no state
carried between calls, no kernels, no chunked form: the recurrence is a
``lax.scan`` over single tokens, the convolution an explicit sum over its
taps, attention full-sequence and causal, the experts a dense pass over the
held range; a request at a time, layer by layer; reads the benchmark's own
checkpoint; imports nothing of the program.

The equations (``h`` the residual stream, eps ``rms_norm_eps``):

- embedding ``h = embedding_multiplier * E[ids]`` on token rows; a layer
  ``h += residual_multiplier * mixer(rmsnorm(h))``, then ``h +=
  residual_multiplier * (experts(y) + shared(y))`` with ``y = rmsnorm(h)``;
  head ``logits = (rmsnorm(h) @ E^T) / logits_scaling`` (tied).
- ``mamba`` mixer (Mamba-2, one B/C group, no projection bias, conv bias):
  ``[z | xBC | dt] = u W_in`` split inner | inner + 2 d_state | heads;
  ``xBC = silu(conv1d(xBC) + b)``, causal and depthwise over ``mamba_d_conv``
  taps; ``x`` (heads x head_dim), ``B``, ``C`` (d_state each, shared by every
  head); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; a head's state
  ``S`` [head_dim, d_state]: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``,
  ``y_t = S_t C_t + D x_t``; ``y = rmsnorm(y * silu(z)) * w`` over the whole
  inner width; ``out = y W_out``.
- ``attention`` mixer: grouped-query, no bias, NO rotation
  (``position_embedding_type`` ``nope``), scores times
  ``attention_multiplier`` (not head_dim ** -0.5), causal.
- experts: router logits over the whole bank, the ``num_experts_per_tok``
  largest, softmax over those; an expert is ``W_out (silu(a) * b)`` with
  ``[a | b] = W_in y``; the shared expert the same at
  ``shared_intermediate_size``, ungated.

Departures and assumptions (the configuration file lists them under
``assumed``): the tower is the repo's own and its projected image rows are
spliced as they come, NOT multiplied by ``embedding_multiplier`` (the source
is a text model); ``num_local_experts`` counts the experts held here: chip
``ep_rank`` of ``ep_size`` holds ``[rank * n, (rank + 1) * n)`` of a router
``n * ep_size`` wide, and what the absent experts would add is left out;
``dt`` is not clamped (``time_step_limit`` (0, inf), HF's default); the HF
tensor names are those of ``transformers``' ``GraniteMoeHybrid``
(``tensors/granite.py``), not confirmed against a downloaded checkpoint.
"""

from __future__ import annotations

import numpy as np

from benchmark.references import plain
from benchmark.references.vlm import canvas, fault, prompt_ids, rms_norm, vision_embeds, vision_params  # noqa: F401
from benchmark.references.vlm_dots3 import Header, Program

MAMBA, ATTENTION = "mamba", "attention"

#: seconds the last comparison spent, by phase (summed over its passes)
SECONDS: dict = {}


def held_range(t: dict) -> tuple[int, int, int]:
    """(lo, hi, router width): the experts this chip holds, of how many."""
    n, ep, rank = t["num_local_experts"], t.get("ep_size", 1), t.get("ep_rank", 0)
    return rank * n, (rank + 1) * n, n * ep


def mamba_layer(u, p: dict, t: dict):
    """``u`` [S, hidden], one request's normed layer input -> the mixer's
    output before the residual, [S, hidden]."""
    import jax
    import jax.numpy as jnp

    s = u.shape[0]
    nh, hd, n, k = t["mamba_n_heads"], t["mamba_d_head"], t["mamba_d_state"], t["mamba_d_conv"]
    inner = nh * hd
    conv_dim = inner + 2 * t.get("mamba_n_groups", 1) * n
    proj = plain.linear(u, p["in_w"])
    z, xbc, dt = proj[:, :inner], proj[:, inner:inner + conv_dim], proj[:, inner + conv_dim:]
    past = jnp.concatenate([jnp.zeros((k - 1, conv_dim), xbc.dtype), xbc], axis=0)
    conv = p["conv_b"] + sum(past[j:j + s] * p["conv_w"][:, 0, j] for j in range(k))  # tap k-1: the token itself
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(s, nh, hd)
    bm, cm = xbc[:, inner:inner + n], xbc[:, inner + n:]
    dt = jax.nn.softplus(dt + p["dt_bias"])  # [S, heads]
    a = -jnp.exp(p["a_log"])

    def token(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = jnp.exp(dt_t * a)[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, jnp.einsum("hpn,n->hp", state, c_t) + p["d"][:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((nh, hd, n), jnp.float32), (x, bm, cm, dt))
    y = rms_norm(y.reshape(s, inner) * jax.nn.silu(z), p["norm_w"], t["rms_norm_eps"])
    return plain.linear(y, p["out_w"])


def attention_layer(u, p: dict, t: dict):
    """``u`` [S, hidden] -> grouped-query causal attention without rotation,
    scores times ``attention_multiplier``, before the residual."""
    import jax
    import jax.numpy as jnp

    s, hidden = u.shape
    nh, nkv = t["num_attention_heads"], t["num_key_value_heads"]
    dh = t.get("head_dim") or hidden // nh
    heads = lambda w, n: plain.linear(u, w).reshape(s, n, dh).transpose(1, 0, 2)
    q, k, v = heads(p["q_w"], nh), heads(p["k_w"], nkv), heads(p["v_w"], nkv)
    k, v = jnp.repeat(k, nh // nkv, axis=0), jnp.repeat(v, nh // nkv, axis=0)
    scores = jnp.einsum("hsd,htd->hst", q, k) * float(t["attention_multiplier"])
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hst,htd->hsd", w, v).transpose(1, 0, 2).reshape(s, nh * dh)
    return plain.linear(o, p["o_w"])


def routing(y, router_w, t: dict):
    """[T, router width] float32: the gate of every expert a token selected
    (softmax over the selected logits), zero elsewhere."""
    import jax
    import jax.numpy as jnp

    logits = y @ router_w.T
    top, idx = jax.lax.top_k(logits, t["num_experts_per_tok"])
    return jnp.einsum("tk,tke->te", jax.nn.softmax(top, axis=-1),
                      jax.nn.one_hot(idx, logits.shape[-1], dtype=jnp.float32))


def fused_expert(y, in_w, out_w):
    """``W_out (silu(a) * b)``, ``[a | b] = W_in y``: ``in_w`` [2f, hidden]
    (the gate half of the outputs, then the up half), ``out_w`` [hidden, f]."""
    import jax
    import jax.numpy as jnp

    a, b = jnp.split(plain.linear(y, in_w), 2, axis=-1)
    return plain.linear(jax.nn.silu(a) * b, out_w)


def experts_pass(y, gates, in_bank, out_bank):
    """Every token densely through every expert of the bank ([E, 2f, hidden],
    [E, hidden, f]), weighted by its gate there (``gates`` [T, E])."""
    import jax
    import jax.numpy as jnp

    def one(acc, e):
        in_w, out_w, g = e
        return acc + g[:, None] * fused_expert(y, in_w, out_w), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y), (in_bank, out_bank, gates.T))
    return out


def expert_layer(y, p: dict, t: dict, held: tuple[int, int] | None = None, shared: bool = True):
    """``y`` [T, hidden] -> the held experts' part of the layer plus, where
    ``shared``, the ungated shared expert. ``p["in_bank"]`` / ``p["out_bank"]``
    hold the experts ``held`` names (default: the configuration's share)."""
    lo, hi = held if held is not None else held_range(t)[:2]
    run = programs(t)
    gates = run["routing"](y, p["router_w"])
    out = run["experts"](y, gates[:, lo:hi], p["in_bank"], p["out_bank"])
    if shared and "shared_in_w" in p:
        out = out + run["shared"](y, p["shared_in_w"], p["shared_out_w"])
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
            "routing": Program(lambda y, w: routing(y, w, t)),
            "experts": Program(experts_pass),
            "shared": Program(fused_expert),
            MAMBA: Program(lambda u, p: mamba_layer(u, p, t)),
            ATTENTION: Program(lambda u, p: attention_layer(u, p, t)),
        }
    return _PROGRAMS[key]


def decoder_layer(x, p: dict, t: dict, i: int):
    """``x`` [B, S, hidden] -> the layer's output; the mixer a request at a
    time, the feed-forward over all tokens."""
    import jax.numpy as jnp

    run = programs(t)
    mixer, norm, r = run[t["layer_types"][i]], run["norm"], float(t.get("residual_multiplier", 1.0))
    x = jnp.stack([x[b] + r * mixer(norm(x[b], p["in_norm"]), p["mixer"]) for b in range(x.shape[0])])
    y = norm(x, p["post_norm"]).reshape(-1, x.shape[-1])
    return x + r * expert_layer(y, p["moe"], t).reshape(x.shape)


def layer_params(ck, t: dict, i: int, bits) -> dict:
    """Layer ``i`` under the HF names; ``bits`` quantizes its linear weights
    (weight-only, a scale an output channel; norms, the router, the
    convolution, ``dt_bias``, ``A_log`` and ``D`` stay as they are, as in a
    deployment)."""
    pre = f"model.layers.{i}."
    q = lambda name: plain.fake_quant(ck.get(pre + name + ".weight"), bits)
    bank = lambda name: _map_experts(ck.get(pre + name + ".weight"), bits)
    if t["layer_types"][i] == MAMBA:
        m = pre + "mamba."
        mixer = {
            "in_w": q("mamba.in_proj"), "out_w": q("mamba.out_proj"),
            "conv_w": ck.get(m + "conv1d.weight"), "conv_b": ck.get(m + "conv1d.bias"),
            "dt_bias": ck.get(m + "dt_bias"), "a_log": ck.get(m + "A_log"), "d": ck.get(m + "D"),
            "norm_w": ck.get(m + "norm.weight"),
        }
    else:
        mixer = {f"{n}_w": q(f"self_attn.{n}_proj") for n in ("q", "k", "v", "o")}
    moe = {
        "router_w": ck.get(pre + "block_sparse_moe.router.layer.weight"),
        "in_bank": bank("block_sparse_moe.input_linear"), "out_bank": bank("block_sparse_moe.output_linear"),
    }
    if t.get("shared_intermediate_size"):
        moe["shared_in_w"], moe["shared_out_w"] = q("shared_mlp.input_linear"), q("shared_mlp.output_linear")
    return {"in_norm": ck.get(pre + "input_layernorm.weight"),
            "post_norm": ck.get(pre + "post_attention_layernorm.weight"), "mixer": mixer, "moe": moe}


def _map_experts(bank, bits):
    """``fake_quant`` an expert at a time (its scales are an output channel's
    of ONE expert); a shape passes through."""
    import jax

    if bits is None or isinstance(bank, jax.ShapeDtypeStruct):
        return bank
    return jax.lax.map(lambda w: plain.fake_quant(w, bits), bank)


def start_layer_programs(workers, head: Header, t: dict, batch: int, length: int) -> None:
    """Start compiling what ``decoder_layer`` will call for ``batch`` requests
    of ``length`` positions: a layer of each kind."""
    import jax
    import jax.numpy as jnp

    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    hidden = t["hidden_size"]
    row, rows, tokens = f32(length, hidden), f32(batch, length, hidden), f32(batch * length, hidden)
    run, seen = programs(t), set()
    lo, hi, width = held_range(t)
    for i, kind in enumerate(t["layer_types"][: t["num_hidden_layers"]]):
        if kind in seen:
            continue
        seen.add(kind)
        p = layer_params(head, t, i, None)
        run[kind].start(workers, row, p["mixer"])
        if len(seen) == 1:
            moe = p["moe"]
            run["norm"].start(workers, row, p["in_norm"])
            run["norm"].start(workers, rows, p["post_norm"])
            run["routing"].start(workers, tokens, moe["router_w"])
            run["experts"].start(workers, tokens, f32(batch * length, hi - lo), moe["in_bank"], moe["out_bank"])
            if "shared_in_w" in moe:
                run["shared"].start(workers, tokens, moe["shared_in_w"], moe["shared_out_w"])


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
        text = embed[ids] * float(t.get("embedding_multiplier", 1.0))  # token rows only
        image = jnp.take_along_axis(vis, jnp.maximum(src, 0)[:, :, None], axis=1)
        return jnp.where((src >= 0)[:, :, None], image, text)  # right padding: causal, so harmless

    def tail(x, norm_w, head_w, rows):
        x = jnp.take_along_axis(x, rows[:, :, None], axis=1)
        return rms_norm(x, norm_w, t["rms_norm_eps"]) @ head_w.T / float(t.get("logits_scaling", 1.0))

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
        tied = t.get("tie_word_embeddings", True)
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
            lap("weights", p["moe"]["in_bank"])
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
