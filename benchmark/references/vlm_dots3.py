"""Plain reference of the ``dots3_note`` captioner: the repo's tower and
splice (as ``references/vlm.py``) in front of a decoder of latent attention
layers of two kinds and sigmoid-routed experts, written out from the
published ``config`` as one full forward pass over the prompt with its
served tokens. float32 at ``highest`` precision; no cache, no pages, no
kernels, attention un-absorbed (keys and values expanded a head), experts by
a dense pass over the held range; reads the benchmark's own checkpoint;
imports nothing of the program. Layer by layer, a request at a time, heads
and query rows in blocks, so that five layers at width 5,120 fit beside
nothing else on a chip.

The equations (``h`` the layer's RMS-normed input, eps ``rms_norm_eps``):

- full layer: ``c_q = RMSNorm(h W_qa)``, ``q = c_q W_qb`` (heads x (nope +
  rope), RoPE on the rope part); ``[c_kv, k_r] = h W_kva``, ``c_kv =
  RMSNorm(c_kv)``, ``k_r`` gets RoPE and is shared by all heads; ``[k_n, v] =
  c_kv W_kvb`` (heads x (nope + v)); scores ``q.k / sqrt(nope + rope)``,
  causal. Indexer: ``q_I = c_q W_Iq`` (index heads x index dim, the first
  rope values of each under the layer's RoPE), ``k_I = LayerNorm(h W_Ik)``
  (same RoPE part), ``w = h W_Iw * heads^-1/2 * dim^-1/2``, ``I(t,s) = sum_j
  w(t,j) relu(q_I(t,j).k_I(s))``; the layer attends only to the
  ``index_topk`` causal keys of largest ``I(t,.)``. Gate ``g = sigmoid(h
  W_g)``, one value a head, applied to the head's output before ``W_o``.
- window layer: the same with the ``swa_`` sizes, keys ``s`` with
  ``t - s < sliding_window_size``, no indexer.
- feed-forward: layers below ``first_k_dense_replace`` a SwiGLU of
  ``intermediate_size``; the rest ``s = sigmoid(x W_r)``, the
  ``num_experts_per_tok`` largest of ``s + b`` selected (one group), gates
  ``s_i / sum over the selected * routed_scaling_factor``, ``y = sum over
  selected and held g_i E_i(x) + E_shared(x)``.

Departures and assumptions (the configuration file lists them under
``assumed``): ``apply_mla_qkv_lora_rescale`` multiplies ``c_q`` by
``sqrt(hidden / q_lora_rank)`` and ``c_kv`` by ``sqrt(hidden /
kv_lora_rank)`` after their norms; the headwise gate is computed from the
layer's normed input and applied before ``W_o``; the window counts the token
itself; keys that tie with the ``index_topk``-th index score are all kept;
the tower is the repo's own; the audio tower and the multi-token-prediction
head are left out. ``n_routed_experts`` counts the experts held here: chip
``ep_rank`` of ``ep_size`` holds ``[rank * n, (rank + 1) * n)`` of a router
``n * ep_size`` wide, and what the absent experts would add is left out.
"""

from __future__ import annotations

import numpy as np

from benchmark.references import plain
from benchmark.references.vlm import canvas, fault, prompt_ids, rms_norm, vision_embeds, vision_params  # noqa: F401

FULL, WINDOW = "full_attention", "sliding_attention"

#: linear weights of a layer that a weight-only control quantizes (norms,
#: the router and its selection bias stay as they are, as in a deployment)
_ATTN = {"q_a": "q_a_proj", "q_b": "q_b_proj", "kv_a": "kv_a_proj_with_mqa", "kv_b": "kv_b_proj",
         "o": "o_proj", "gate": "attn_gate"}
_INDEXER = {"iq": "indexer.wq_b", "ik": "indexer.wk", "iw": "indexer.weights_proj"}


def dims(t: dict, kind: str) -> dict:
    """The sizes of one kind of layer, from the published keys."""
    p = "swa_" if kind == WINDOW else ""
    return {
        "heads": t[p + "num_attention_heads"], "q_lora": t[p + "q_lora_rank"],
        "kv_lora": t[p + "kv_lora_rank"], "nope": t[p + "qk_nope_head_dim"],
        "rope": t[p + "qk_rope_head_dim"], "v": t[p + "v_head_dim"],
        "theta": float(t[p + "rope_theta"]),
    }


def held_range(t: dict) -> tuple[int, int, int]:
    """(lo, hi, router width): the experts this chip holds, of how many."""
    n, ep, rank = t["n_routed_experts"], t.get("ep_size", 1), t.get("ep_rank", 0)
    return rank * n, (rank + 1) * n, n * ep


def rope_at(x, theta: float, lo: int = 0, hi: int | None = None):
    """RoPE (HF half-split) on ``x[..., lo:hi]`` of ``[..., S, D]``,
    positions 0..S-1; the rest passes through."""
    import jax.numpy as jnp

    hi = x.shape[-1] if hi is None else hi
    part = x[..., lo:hi]
    d = part.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = part[..., : d // 2], part[..., d // 2:]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([x[..., :lo], rot, x[..., hi:]], axis=-1)


def index_mask(h, cq, p: dict, t: dict, d: dict, rows: int = 256):
    """[S, S] bool: the causal keys each token's indexer keeps."""
    import jax
    import jax.numpy as jnp

    s = h.shape[0]
    j, di, topk = t["index_n_heads"], t["index_head_dim"], t["index_topk"]
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    if s <= topk:
        return causal
    q_i = rope_at(plain.linear(cq, p["iq_w"]).reshape(s, j, di).transpose(1, 0, 2), d["theta"], 0, d["rope"])
    k_i = plain.layer_norm(plain.linear(h, p["ik_w"]), p["ik_norm_w"], p["ik_norm_b"], t["rms_norm_eps"])
    k_i = rope_at(k_i, d["theta"], 0, d["rope"])  # [S, Di]
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


def attention_layer(h, p: dict, t: dict, kind: str, head_block: int = 16):
    """``h`` [S, hidden], one request's normed layer input -> the attention
    output before the residual, [S, hidden]."""
    import jax
    import jax.numpy as jnp

    d = dims(t, kind)
    s, hidden = h.shape
    nh, eps = d["heads"], t["rms_norm_eps"]
    cq = rms_norm(plain.linear(h, p["q_a_w"]), p["q_a_norm"], eps)
    kv = plain.linear(h, p["kv_a_w"])
    ckv = rms_norm(kv[:, : d["kv_lora"]], p["kv_a_norm"], eps)
    if t.get("apply_mla_qkv_lora_rescale"):
        cq = cq * (hidden / d["q_lora"]) ** 0.5
        ckv = ckv * (hidden / d["kv_lora"]) ** 0.5
    k_r = rope_at(kv[:, d["kv_lora"]:], d["theta"])  # [S, rope], shared by the heads
    q = plain.linear(cq, p["q_b_w"]).reshape(s, nh, d["nope"] + d["rope"]).transpose(1, 0, 2)
    q = rope_at(q, d["theta"], d["nope"])  # [H, S, nope + rope]
    kvb = plain.linear(ckv, p["kv_b_w"]).reshape(s, nh, d["nope"] + d["v"]).transpose(1, 0, 2)
    k = jnp.concatenate([kvb[..., : d["nope"]], jnp.broadcast_to(k_r, (nh, s, d["rope"]))], axis=-1)
    v = kvb[..., d["nope"]:]
    if kind == WINDOW:
        gap = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
        see = (gap >= 0) & (gap < t["sliding_window_size"])
    else:
        see = index_mask(h, cq, p, t, d)
    scale = (d["nope"] + d["rope"]) ** -0.5

    def heads(args):
        qb, kb, vb = args  # [hb, S, *]
        sc = jnp.where(see, jnp.einsum("hsd,htd->hst", qb, kb) * scale, -jnp.inf)
        return jnp.einsum("hst,htd->hsd", jax.nn.softmax(sc, axis=-1), vb)

    hb = head_block if nh % head_block == 0 else nh
    split = lambda x: x.reshape(nh // hb, hb, s, x.shape[-1])
    o = jax.lax.map(heads, (split(q), split(k), split(v))).reshape(nh, s, d["v"])
    g = jax.nn.sigmoid(plain.linear(h, p["gate_w"]))  # [S, H]
    o = (o.transpose(1, 0, 2) * g[:, :, None]).reshape(s, nh * d["v"])
    return plain.linear(o, p["o_w"])


def swiglu(y, gate_w, up_w, down_w):
    import jax

    return plain.linear(jax.nn.silu(plain.linear(y, gate_w)) * plain.linear(y, up_w), down_w)


def routing(y, router_w, bias, t: dict):
    """[T, router width] float32: the gate of every expert a token selected,
    zero elsewhere."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(y @ router_w.T)
    k = t["num_experts_per_tok"]
    _, idx = jax.lax.top_k(s + bias, k)
    picked = jnp.take_along_axis(s, idx, axis=-1)
    if t.get("norm_topk_prob", True):
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    picked = picked * float(t.get("routed_scaling_factor", 1.0))
    return jnp.einsum("tk,tke->te", picked, jax.nn.one_hot(idx, s.shape[-1], dtype=jnp.float32))


def expert_layer(y, p: dict, t: dict, held: tuple[int, int] | None = None, shared: bool = True):
    """``y`` [T, hidden] -> the held experts' part of the layer (every token
    densely through every held expert, weighted by its gate there), plus the
    shared expert where ``shared``. ``p["experts"]`` maps an expert's id to
    its (gate, up, down) weights, or to a function that loads them."""
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


class Program:
    """``jax.jit(fn)`` that can be told its arguments' shapes beforehand and
    then compiles on a worker thread. At the published widths each of this
    file's programs takes the chip's compiler 3-17 s (a float32 product at
    ``highest`` is six passes) on a core or two: some 70 s one after another,
    28 s side by side, which a run's time limit needs. The mathematics is
    ``fn``'s either way."""

    def __init__(self, fn):
        import jax

        self.jit, self.started = jax.jit(fn), {}

    @staticmethod
    def _shapes(args):
        import jax

        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)

    def start(self, workers, *args) -> None:
        """``args``: arrays or shapes, as a later call will bring them."""
        import jax

        def compile_(shapes):
            with jax.default_matmul_precision("highest"):  # a context is its thread's own
                return self.jit.lower(*shapes).compile()

        shapes = self._shapes(args)
        if str(shapes) not in self.started:
            self.started[str(shapes)] = workers.submit(compile_, shapes)

    def __call__(self, *args):
        ahead = self.started.get(str(self._shapes(args)))
        return ahead.result()(*args) if ahead is not None else self.jit(*args)


_PROGRAMS: dict = {}
#: seconds the last comparison spent, by phase (summed over its passes)
SECONDS: dict = {}


def programs(t: dict, kind: str = FULL) -> dict:
    """The compiled pieces of a layer of ``kind``, made once for the
    configuration ``t`` and the precision the pass computes in."""
    import json

    # plain.linear reads the control's activation rounding when it is traced
    key = (json.dumps(t, sort_keys=True), plain._round_activations)
    if key not in _PROGRAMS:
        _PROGRAMS[key] = {
            "norm": Program(lambda x, w: rms_norm(x, w, t["rms_norm_eps"])),
            "swiglu": Program(swiglu),
            "routing": Program(lambda y, w, b: routing(y, w, b, t)),
            "expert": Program(lambda y, a, b, c, g: g[:, None] * swiglu(y, a, b, c)),
            **{k: Program(lambda h, p, k=k: attention_layer(h, p, t, k)) for k in (FULL, WINDOW)},
        }
    made = _PROGRAMS[key]
    return {**made, "attn": made[kind]}


def decoder_layer(x, p: dict, t: dict, i: int):
    """``x`` [B, S, hidden] -> the layer's output; attention a request at a
    time, the feed-forward over all tokens."""
    import jax.numpy as jnp

    run = programs(t, t["layer_types"][i])
    attn, norm = run["attn"], run["norm"]
    x = jnp.stack([x[b] + attn(norm(x[b], p["in_norm"]), p["attn"]) for b in range(x.shape[0])])
    y = norm(x, p["post_norm"]).reshape(-1, x.shape[-1])
    if i < t.get("first_k_dense_replace", 0):
        f = run["swiglu"](y, *p["mlp"])
    else:
        f = expert_layer(y, p["moe"], t)
    return x + f.reshape(x.shape)


def layer_params(ck: plain.Checkpoint, t: dict, i: int, bits) -> dict:
    pre = f"model.layers.{i}."
    q = lambda name: plain.fake_quant(ck.get(pre + name + ".weight"), bits)
    attn = {f"{k}_w": q("self_attn." + n) for k, n in _ATTN.items()}
    attn["q_a_norm"] = ck.get(pre + "self_attn.q_a_layernorm.weight")
    attn["kv_a_norm"] = ck.get(pre + "self_attn.kv_a_layernorm.weight")
    if t["layer_types"][i] == FULL:
        attn.update({f"{k}_w": q("self_attn." + n) for k, n in _INDEXER.items()})
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
    return p


class Header:
    """The checkpoint's header, read as ``plain.Checkpoint`` is: ``get`` gives
    a tensor's shape (as float32, what ``Checkpoint.get`` returns) in place of
    its values, so ``layer_params`` over it gives the shapes of a layer."""

    def __init__(self, model_dir: str):
        import os

        from safetensors import safe_open

        self._f = safe_open(os.path.join(model_dir, "model.safetensors"), framework="numpy")

    def get(self, name: str):
        import jax
        import jax.numpy as jnp

        return jax.ShapeDtypeStruct(tuple(self._f.get_slice(name).get_shape()), jnp.float32)


def start_layer_programs(workers, head: Header, t: dict, batch: int, length: int) -> None:
    """Start compiling what ``decoder_layer`` will call for ``batch`` requests
    of ``length`` positions: one layer of each kind, with and without experts."""
    import jax
    import jax.numpy as jnp

    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    hidden, dense = t["hidden_size"], t.get("first_k_dense_replace", 0)
    row, rows, tokens = f32(length, hidden), f32(batch, length, hidden), f32(batch * length, hidden)
    seen = set()
    for i, kind in enumerate(t["layer_types"]):
        if (kind, i < dense) in seen:
            continue
        seen.add((kind, i < dense))
        p, run = layer_params(head, t, i, None), programs(t, kind)
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
