"""Compiled generation programs: the paged pool's admit / block-step /
chunked-prefill programs the continuous engine serves from, and a fused
prefill + ``lax.while_loop`` loop over a contiguous cache kept as the
reference they are held to.

The reference implementation decodes with a host python loop — one
onnxruntime session call per token, rebuilding the attention mask and
renaming ``present.*`` outputs each step (``packages/lumen-vlm/src/lumen_vlm/
backends/onnxrt_backend.py:298-356``, ``:480-492``). Here a block of decode
steps — embed, decoder forward over the paged KV pool, repetition penalty,
temperature/top-p sampling, EOS check — is ONE compiled XLA program
(``_step_block_impl``); the host sees a block's tokens at a time.

Sampling semantics follow the reference (``:508-533``): greedy when
``do_sample`` is false or temperature ~ 0, else temperature + nucleus.
Generation params are traced scalars, so one compiled program serves every
request config.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.sampling import apply_repetition_penalty, sample
from ...utils.trace import SCOPE_DECODE, SCOPE_EMBED, SCOPE_INSTALL, SCOPE_PREFILL, SCOPE_SAMPLE, SCOPE_STATE
from .modeling import VLMConfig, VLMModel, init_kv_cache, init_paged_kv_cache
from .paged_kv import DEFAULT_PAGE_SIZE, RowState


@dataclass
class GenerateOutput:
    tokens: jax.Array  # [B, max_new_cap] generated ids, pad-filled after EOS
    n_generated: jax.Array  # [B] count of live tokens (EOS included)
    stopped_eos: jax.Array  # [B] bool: hit EOS (vs length cap)


class Generator:
    """Compiled generation programs for one ``VLMModel``.

    ``max_seq`` bounds prompt+vision+new tokens (the KV buffer size);
    ``max_new_cap`` is the static output-buffer size. Both are compile-time
    constants — the per-request ``max_new_tokens`` is a traced value bounded
    by the cap.
    """

    def __init__(
        self,
        model: VLMModel,
        cfg: VLMConfig,
        max_seq: int = 2048,
        max_new_cap: int = 512,
        cache_dtype=jnp.bfloat16,
        seq_buckets: tuple[int, ...] | None = None,
    ):
        self.model = model
        self.cfg = cfg
        self.max_seq = max_seq
        self.max_new_cap = max_new_cap
        self.cache_dtype = cache_dtype
        #: what a row keeps in each layer (pages of K/V, latent pages, a
        #: recurrent state) and what that allows: ``paged_kv.RowState``
        self.rows = RowState(cfg)
        # KV right-sizing (round-4 verdict): the fused path allocates its
        # cache at the smallest bucket >= prompt + budget instead of
        # worst-case max_seq — a 32-token caption request in a
        # max_seq=2048 deployment gets a 32x smaller KV buffer AND a
        # proportionally cheaper decode attention. One compiled program
        # per bucket actually used.
        buckets = sorted(set(b for b in (seq_buckets or ()) if b <= max_seq))
        if not buckets or buckets[-1] != max_seq:
            buckets.append(max_seq)
        self.seq_buckets = tuple(buckets)
        self._generate = jax.jit(self._generate_impl, static_argnames=("kv_len",))
        self._prefill = jax.jit(self._prefill_impl, static_argnames=("kv_len",))
        # The paged KV pool is the dominant buffer; donating it lets XLA
        # update in place instead of holding two copies across every
        # admit/block dispatch. Block tables are host-managed (numpy in
        # paged_kv.PagedKVPool) and ride in as a small fresh operand.
        self._admit = jax.jit(self._admit_impl, donate_argnames=("pool",))
        # KV spill tier programs: export gathers a victim row's live pages
        # + decode scalars for ONE fused device->host transfer (read-only,
        # no donation — a failed export must leave the pool intact);
        # resume scatters exported pages into a fresh grant and restores
        # the row's scalars, donating the pool exactly like _admit.
        self._export_row = jax.jit(self._export_row_impl)
        self._resume = jax.jit(self._resume_impl, donate_argnames=("pool",))
        self._step_block = jax.jit(
            self._step_block_impl, static_argnames=("block",), donate_argnames=("pool",)
        )
        # Chunked-prefill lane programs: one chunk of prompt through the
        # decoder into a donated contiguous scratch cache, and the finish
        # step that samples token 0 once the last live chunk ran.
        self._prefill_chunk = jax.jit(
            self._prefill_chunk_impl, donate_argnames=("caches",)
        )
        self._chunk_finish = jax.jit(self._chunk_finish_impl)
        # Prefix-reuse + speculative-decoding programs: seed a prefill
        # scratch from already-computed pool pages (prefix-cache hit), and
        # verify a W-token draft window in one decode forward.
        self._seed_prefix = jax.jit(self._seed_prefix_impl, donate_argnames=("caches",))
        self._verify = jax.jit(
            self._verify_impl, static_argnames=("width",), donate_argnames=("pool",)
        )

    # -- shared pieces ------------------------------------------------------

    @property
    def _counts_experts(self) -> bool:
        """Whether the decoder's expert layers count what they route
        (``moe_stats``: a held share of the bank): the programs below then
        carry the sums to the pool, where the scheduler reads them."""
        return self.cfg.decoder.moe_held is not None

    def _apply(self, params, method, *args):
        """``(logits, caches, moe stats [4] int32 or None)`` of one decoder
        pass."""
        if not self._counts_experts:
            return (*self.model.apply({"params": params}, *args, method=method), None)
        (logits, caches), state = self.model.apply(
            {"params": params}, *args, method=method, mutable=["moe_stats"]
        )
        stats = sum(jax.tree.leaves(state), jnp.zeros((4,), jnp.int32))
        return logits, caches, stats

    def _decode(self, params, embeds, positions, caches, offset, kv_valid_len):
        """A prefill segment into contiguous ``caches`` (a latent decoder's
        carry one more entry, the expert layers' counts so far)."""
        if not self._counts_experts:
            return self._apply(
                params, VLMModel.decode, embeds, positions, caches, offset, kv_valid_len
            )[:2]
        valid = positions < kv_valid_len[:, None]
        logits, new, stats = self._apply(
            params, VLMModel.decode, embeds, positions, caches[:-1], offset, kv_valid_len, valid
        )
        counts = caches[-1]["moe_stats"]
        return logits, [*new, {"moe_stats": counts.at[0].add(stats)}]

    def _scratch(self, batch: int, kv_len: int) -> list[dict]:
        caches = init_kv_cache(self.cfg, batch, kv_len, self.cache_dtype)
        if self._counts_experts:
            caches.append({"moe_stats": jnp.zeros((batch, 4), jnp.int32)})
        return caches

    def _embed(self, params, ids):
        return self.model.apply({"params": params}, ids, method=VLMModel.embed_tokens)

    def _seen_from_prompt(self, prompt_ids: jax.Array, lengths: jax.Array) -> jax.Array:
        """[B, V] bool mask of tokens present in the (unpadded) prompt, for
        the repetition penalty."""
        b, s = prompt_ids.shape
        valid = jnp.arange(s)[None, :] < lengths[:, None]
        seen = jnp.zeros((b, self.cfg.decoder.vocab_size), bool)
        bidx = jnp.arange(b)[:, None]
        return seen.at[bidx, jnp.where(valid, prompt_ids, 0)].max(valid)

    def _sample_next(self, rng, logits, seen, temperature, top_p, do_sample, rep_penalty):
        logits = logits.astype(jnp.float32)
        logits = apply_repetition_penalty(logits, seen, rep_penalty)
        return sample(rng, logits, temperature, top_p, do_sample)

    def _prefill_core(self, params, embeds, positions, lengths, kv_len: int | None = None):
        b = embeds.shape[0]
        caches = self._scratch(b, kv_len or self.max_seq)
        logits, caches = self._decode(
            params, embeds, positions, caches, jnp.zeros((), jnp.int32), lengths
        )
        last = logits[jnp.arange(b), lengths - 1]  # [B, V] next-token logits
        return caches, last

    # -- reference loop (contiguous cache; nothing serves from it) -----------

    def _generate_impl(
        self,
        params,
        embeds,  # [B, L, H] merged prompt embeddings (right-padded)
        positions,  # [B, L]
        lengths,  # [B] live token count
        prompt_ids,  # [B, S_text] original text ids (for repetition penalty)
        rng,
        max_new_tokens,  # traced scalar or per-sample [B], <= max_new_cap
        temperature,  # sampling params: traced scalars or per-sample [B]
        top_p,
        do_sample,
        repetition_penalty,
        kv_len: int | None = None,  # static: KV bucket (defaults to max_seq)
    ):
        cfg = self.cfg
        if cfg.decoder.latent:  # LatentAttention has no per-row contiguous step
            raise NotImplementedError("the fused generate program is not implemented for a latent decoder")
        b = embeds.shape[0]
        caches, last_logits = self._prefill_core(params, embeds, positions, lengths, kv_len)
        seen = self._seen_from_prompt(prompt_ids, lengths)
        rng, sub = jax.random.split(rng)
        tok0 = self._sample_next(
            sub, last_logits, seen, temperature, top_p, do_sample, repetition_penalty
        ).astype(jnp.int32)
        max_new = jnp.broadcast_to(jnp.asarray(max_new_tokens, jnp.int32), (b,))

        buf = jnp.full((b, self.max_new_cap), cfg.pad_token_id, jnp.int32)
        state = dict(
            caches=caches,
            cur_tok=tok0,
            cur_len=lengths.astype(jnp.int32),  # cache slots filled so far
            t=jnp.zeros((), jnp.int32),
            rng=rng,
            # A zero-budget row must emit nothing even when batched with
            # live rows (solo, cond already short-circuits).
            done=max_new <= 0,
            eos=jnp.zeros((b,), bool),
            buf=buf,
            seen=seen,
            n_gen=jnp.zeros((b,), jnp.int32),
        )

        def cond(s):
            return (s["t"] < jnp.max(max_new)) & ~jnp.all(s["done"])

        def body(s):
            active = ~s["done"]
            tok = jnp.where(active, s["cur_tok"], cfg.pad_token_id)
            buf = s["buf"].at[:, s["t"]].set(tok)
            n_gen = s["n_gen"] + active.astype(jnp.int32)
            seen = s["seen"].at[jnp.arange(b), s["cur_tok"]].max(active)
            eos = s["eos"] | (active & (s["cur_tok"] == cfg.eos_token_id))
            # A sample stops at its own cap (batched requests mix budgets).
            done = s["done"] | eos | (n_gen >= max_new)

            # Next-token forward (skipped work when everyone is done: the
            # while_loop cond stops the whole program instead).
            tok_embed = self._embed(params, s["cur_tok"][:, None])  # [B,1,H]
            logits, caches = self._decode(
                params,
                tok_embed.astype(embeds.dtype),
                s["cur_len"][:, None],
                s["caches"],
                s["cur_len"],
                s["cur_len"] + 1,
            )
            rng, sub = jax.random.split(s["rng"])
            nxt = self._sample_next(
                sub, logits[:, 0], seen, temperature, top_p, do_sample, repetition_penalty
            ).astype(jnp.int32)
            return dict(
                caches=caches,
                cur_tok=nxt,
                cur_len=s["cur_len"] + active.astype(jnp.int32),
                t=s["t"] + 1,
                rng=rng,
                done=done,
                eos=eos,
                buf=buf,
                seen=seen,
                n_gen=n_gen,
            )

        state = jax.lax.while_loop(cond, body, state)
        return state["buf"], state["n_gen"], state["eos"]

    def generate(
        self,
        params,
        embeds,
        positions,
        lengths,
        prompt_ids,
        rng,
        max_new_tokens=256,
        temperature=0.0,
        top_p=1.0,
        do_sample=False,
        repetition_penalty=1.0,
    ) -> GenerateOutput:
        """The contiguous-cache reference loop: prefill + one fused
        ``while_loop`` over a ``[B, kv_len]`` cache. Nothing serves from it
        (the continuous engine does, over pages); parity tests, the arch
        parity script and the manager's int8 boot A/B compare against it.
        Each generation param may be a python scalar (shared by the whole
        batch) or a length-B sequence."""
        cap = np.minimum(np.asarray(max_new_tokens, np.int32), self.max_new_cap)
        # KV bucket: smallest configured size covering prompt + budget.
        # embeds may be right-padded past the live length, and the decode
        # loop indexes the cache at cur_len positions that started from
        # lengths — the bucket must cover the PADDED prompt span.
        need = int(embeds.shape[1]) + int(np.max(cap))
        kv_len = next((b for b in self.seq_buckets if b >= need), self.max_seq)
        buf, n_gen, eos = self._generate(
            params,
            embeds,
            positions,
            lengths,
            prompt_ids,
            rng,
            jnp.asarray(cap, jnp.int32),
            jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_p, jnp.float32),
            jnp.asarray(do_sample, bool),
            jnp.asarray(repetition_penalty, jnp.float32),
            kv_len=kv_len,
        )
        return GenerateOutput(tokens=buf, n_generated=n_gen, stopped_eos=eos)

    # -- whole-prompt admission prefill --------------------------------------

    def _prefill_impl(
        self, params, embeds, positions, lengths, prompt_ids, rng,
        temperature, top_p, do_sample, repetition_penalty,
        kv_len: int | None = None,  # static KV bucket; None = max_seq.
        # Continuous admission only needs the prompt span (decode happens
        # in the pool's own pages) and passes the smallest bucket covering
        # the prompt.
    ):
        caches, last_logits = self._prefill_core(params, embeds, positions, lengths, kv_len)
        seen = self._seen_from_prompt(prompt_ids, lengths)
        tok0 = self._sample_next(
            rng, last_logits, seen, temperature, top_p, do_sample, repetition_penalty
        ).astype(jnp.int32)
        return caches, tok0, seen

    # -- continuous-batching pool programs (paged KV) ------------------------
    #
    # A fixed pool of B decode slots advances together in k-step blocks;
    # requests are admitted into free slots between blocks and retired on
    # EOS/cap without stopping the others. KV lives in a shared PAGED pool
    # ([pages, kv_heads, page_size, dh] per layer) addressed through
    # host-managed per-row block tables (``paged_kv.PagedKVPool``):
    # admission scatters the prompt's prefill cache into freshly granted
    # pages, decode writes one slot per step through the row's table, and
    # retire returns the pages — long and short generations share the pool
    # instead of every slot paying a contiguous max_seq region.

    def _decode_paged(
        self, params, embeds, positions, caches, block_tables, offset, kv_len, active=None
    ):
        """``(logits, caches, moe stats or None)``; ``active`` [B] marks the
        rows whose tokens the expert layers count."""
        valid = None if active is None else active[:, None]
        return self._apply(
            params, VLMModel.decode_paged, embeds, positions, caches, block_tables,
            offset, kv_len, valid,
        )

    def _page_size_of(self, pool: dict) -> int:
        """Tokens a page of ``pool`` holds, by the pool's description."""
        return self.rows.page_size_of(pool["caches"])

    def init_pool(
        self, slots: int, pages: int | None = None, page_size: int = DEFAULT_PAGE_SIZE,
        window_pages: int | None = None,
    ) -> dict:
        """Fresh all-slots-free paged pool state (host-callable, device
        arrays). ``pages`` defaults to the slot-era footprint (every slot
        could hold max_seq) — serving sizes it from HBM headroom instead
        (``paged_kv.resolve_pool_pages``). ``window_pages`` sizes a latent
        decoder's window layers (``paged_kv.window_pool_pages``)."""
        cfg = self.cfg
        if pages is None:
            pages = slots * (-(-self.max_seq // page_size)) + 1
        # ``moe_stats``: the expert layers' counts since the last decode block
        # (admissions add theirs); a block hands the sum over as ``moe_block``
        # and starts the next from zero, so the int32 sums never grow past one
        # block's work and the scheduler keeps the running totals in int64.
        names = ("moe_stats", "moe_block") if self._counts_experts else ()
        extra = {name: jnp.zeros((4,), jnp.int32) for name in names}
        return dict(
            **extra,
            caches=init_paged_kv_cache(
                cfg, pages, page_size, self.cache_dtype, window_pages, slots=slots
            ),
            cur_tok=jnp.zeros((slots,), jnp.int32),
            cur_len=jnp.zeros((slots,), jnp.int32),
            seen=jnp.zeros((slots, cfg.decoder.vocab_size), bool),
            n_gen=jnp.zeros((slots,), jnp.int32),
            eos=jnp.zeros((slots,), bool),
            done=jnp.ones((slots,), bool),  # free slot == done
            max_new=jnp.zeros((slots,), jnp.int32),
            temperature=jnp.zeros((slots,), jnp.float32),
            top_p=jnp.ones((slots,), jnp.float32),
            do_sample=jnp.zeros((slots,), bool),
            rep=jnp.ones((slots,), jnp.float32),
        )

    @jax.named_scope(SCOPE_INSTALL)
    def _admit_impl(
        self, pool, slot, caches1, tok0, seen1, length, bt_row,
        max_new, temperature, top_p, do_sample, rep,
    ):
        """Write one prefilled request into ``slot``, layer by layer as
        the row state's description installs it (``RowState.install``): a
        scratch of K/V or latent rows scattered into the pages ``bt_row``
        grants, a recurrent state copied into the slot's row."""
        z = jnp.zeros((), jnp.int32)
        s = jnp.asarray(slot, jnp.int32)
        page = self._page_size_of(pool)
        caches = [
            self.rows.install(i, dst, pre, s, bt_row, page)
            for i, (dst, pre) in enumerate(zip(pool["caches"], caches1))
        ]
        extra = {}
        if self._counts_experts:
            extra["moe_stats"] = pool["moe_stats"] + caches1[-1]["moe_stats"].sum(0)
        return dict(
            **extra,
            caches=caches,
            cur_tok=pool["cur_tok"].at[s].set(tok0[0]),
            cur_len=pool["cur_len"].at[s].set(length[0].astype(jnp.int32)),
            seen=jax.lax.dynamic_update_slice(pool["seen"], seen1, (s, z)),
            n_gen=pool["n_gen"].at[s].set(0),
            eos=pool["eos"].at[s].set(False),
            done=pool["done"].at[s].set(max_new <= 0),
            max_new=pool["max_new"].at[s].set(jnp.asarray(max_new, jnp.int32)),
            temperature=pool["temperature"].at[s].set(jnp.asarray(temperature, jnp.float32)),
            top_p=pool["top_p"].at[s].set(jnp.asarray(top_p, jnp.float32)),
            do_sample=pool["do_sample"].at[s].set(jnp.asarray(do_sample, bool)),
            rep=pool["rep"].at[s].set(jnp.asarray(rep, jnp.float32)),
        )

    def _export_row_impl(self, pool, slot, page_ids):
        """Gather one decode row's spillable state: its live KV pages (in
        block-table order) plus the per-slot decode scalars. ``page_ids``
        is padded to a power-of-2 length with the dump page 0 so compiled
        export shapes stay at log2(max_pages) — pad gathers read garbage
        that the resume scatter writes straight back to the dump page.
        The caller ships the result host-side with ONE ``jax.device_get``
        (the spill tier's per-victim transfer budget)."""
        self.rows.refuse("the spill tier's export")
        s = jnp.asarray(slot, jnp.int32)
        return dict(
            pages=jax.tree.map(lambda c: c[page_ids], pool["caches"]),
            seen=jax.lax.dynamic_slice_in_dim(pool["seen"], s, 1, axis=0)[0],
            cur_tok=pool["cur_tok"][s],
            cur_len=pool["cur_len"][s],
            n_gen=pool["n_gen"][s],
        )

    @jax.named_scope(SCOPE_INSTALL)
    def _resume_impl(
        self, pool, slot, pages, page_ids, seen1, cur_tok, cur_len, n_gen,
        max_new, temperature, top_p, do_sample, rep,
    ):
        """Re-install a spilled row into ``slot``: scatter the exported
        pages into the fresh grant ``page_ids`` (same padded layout as
        :meth:`_export_row_impl` — pad entries land on the dump page) and
        restore the decode scalars exactly. ``cur_tok`` is the sampled
        but not-yet-emitted next token, so a resumed greedy row continues
        token-identically and a resumed sampled row continues its own
        draw without splicing."""
        self.rows.refuse("the spill tier's resume")
        s = jnp.asarray(slot, jnp.int32)
        z = jnp.zeros((), jnp.int32)
        caches = jax.tree.map(
            lambda dst, src: dst.at[page_ids].set(src.astype(dst.dtype)),
            pool["caches"], pages,
        )
        return dict(
            caches=caches,
            cur_tok=pool["cur_tok"].at[s].set(jnp.asarray(cur_tok, jnp.int32)),
            cur_len=pool["cur_len"].at[s].set(jnp.asarray(cur_len, jnp.int32)),
            seen=jax.lax.dynamic_update_slice(pool["seen"], seen1[None], (s, z)),
            n_gen=pool["n_gen"].at[s].set(jnp.asarray(n_gen, jnp.int32)),
            eos=pool["eos"].at[s].set(False),
            done=pool["done"].at[s].set(False),
            max_new=pool["max_new"].at[s].set(jnp.asarray(max_new, jnp.int32)),
            temperature=pool["temperature"].at[s].set(jnp.asarray(temperature, jnp.float32)),
            top_p=pool["top_p"].at[s].set(jnp.asarray(top_p, jnp.float32)),
            do_sample=pool["do_sample"].at[s].set(jnp.asarray(do_sample, bool)),
            rep=pool["rep"].at[s].set(jnp.asarray(rep, jnp.float32)),
        )

    def _step_block_impl(self, params, pool, block_tables, rng, *, block: int):
        """Advance every live slot ``block`` tokens; emission semantics are
        identical to ``_generate_impl``'s while-loop body (per-slot budgets,
        EOS, repetition penalty), with free/finished slots masked out. Each
        step's K/V write and attention go through ``block_tables`` — the
        host scheduler guarantees every live row's pages cover
        ``cur_len + block`` before dispatching.

        The sampler sees ``do_sample`` of the rows live in that step only: a
        freed slot keeps its last request's ``do_sample`` until ``_admit``
        overwrites it, and ``sample()`` sorts the vocabulary whenever some
        row draws. What a done row would have drawn is never emitted
        (``tok`` is the pad id where ``active`` is false, ``done`` is sticky,
        and ``_admit`` / ``_resume`` set ``cur_tok`` anew), so its argmax
        serves as well."""
        cfg = self.cfg
        b = pool["cur_tok"].shape[0]
        capacity = block_tables.shape[-1] * self._page_size_of(pool)

        def body(carry, _):
            pool, rng = carry
            with jax.named_scope(SCOPE_STATE):
                active = ~pool["done"]
                tok = jnp.where(active, pool["cur_tok"], cfg.pad_token_id)
                n_gen = pool["n_gen"] + active.astype(jnp.int32)
                seen = pool["seen"].at[jnp.arange(b), pool["cur_tok"]].max(active)
                eos = pool["eos"] | (active & (pool["cur_tok"] == cfg.eos_token_id))
                done = pool["done"] | eos | (n_gen >= pool["max_new"])
            with jax.named_scope(SCOPE_EMBED):
                tok_embed = self._embed(params, pool["cur_tok"][:, None]).astype(self.cache_dtype)
            with jax.named_scope(SCOPE_DECODE):
                # Done rows stop advancing and a freed slot keeps its last row's
                # cur_len, so the clamp only guards a full slot past its table.
                pos = jnp.minimum(pool["cur_len"], capacity - 1)
                logits, caches, stats = self._decode_paged(
                    params, tok_embed, pos[:, None], pool["caches"], block_tables, pos, pos + 1,
                    active,
                )
            with jax.named_scope(SCOPE_SAMPLE):
                rng, sub = jax.random.split(rng)
                nxt = self._sample_next(
                    sub, logits[:, 0], seen,
                    pool["temperature"], pool["top_p"], pool["do_sample"] & active, pool["rep"],
                ).astype(jnp.int32)
            with jax.named_scope(SCOPE_STATE):
                counted = {} if stats is None else {"moe_stats": pool["moe_stats"] + stats}
                new_pool = dict(
                    pool,
                    **counted,
                    caches=caches,
                    cur_tok=nxt,
                    cur_len=pool["cur_len"] + active.astype(jnp.int32),
                    seen=seen,
                    n_gen=n_gen,
                    eos=eos,
                    done=done,
                )
            return (new_pool, rng), tok

        (pool, rng), toks = jax.lax.scan(body, (pool, rng), None, length=block)
        if self._counts_experts:
            counts = pool["moe_stats"]
            pool = dict(pool, moe_block=counts, moe_stats=jnp.zeros_like(counts))
        return pool, rng, toks.T  # [B, block]

    # -- chunked prefill lane ------------------------------------------------
    #
    # A long prompt prefilled in one shot would hold the scheduler loop
    # (and every in-flight decode row) hostage for the whole forward; the
    # chunk programs let the engine run one chunk of each waiting prompt
    # between decode blocks instead. Chunks write into a CONTIGUOUS
    # per-request scratch cache (offset semantics identical to one-shot
    # prefill — causal attention over earlier chunks already in the
    # scratch), and the finished scratch admits into pages exactly like
    # a one-shot prefill.

    def new_prefill_cache(self, kv_len: int):
        """Contiguous batch-1 scratch cache for one chunked prefill."""
        return self._scratch(1, kv_len)

    @jax.named_scope(SCOPE_PREFILL)
    def _prefill_chunk_impl(self, params, caches, embeds, positions, offset, valid_len):
        """One prompt chunk through the decoder: writes K/V at ``offset``
        into the donated scratch, returns this chunk's logits."""
        return self._decode(params, embeds, positions, caches, offset, valid_len)

    @jax.named_scope(SCOPE_SAMPLE)
    def _chunk_finish_impl(
        self, chunk_logits, idx, prompt_ids, lengths, rng,
        temperature, top_p, do_sample, repetition_penalty,
    ):
        """Sample token 0 from the final live chunk's logits at in-chunk
        index ``idx`` [B] — the tail of ``_prefill_impl`` split out for
        the chunk lane (``idx`` is traced so tail positions don't compile
        one program each)."""
        b = chunk_logits.shape[0]
        last = chunk_logits[jnp.arange(b), idx]  # [B, V]
        seen = self._seen_from_prompt(prompt_ids, lengths)
        tok0 = self._sample_next(
            rng, last, seen, temperature, top_p, do_sample, repetition_penalty
        ).astype(jnp.int32)
        return tok0, seen

    # -- prefix reuse + speculative decoding ---------------------------------

    def _seed_prefix_impl(self, caches, pool_caches, page_ids):
        """Prefix-cache hit: seed a chunked-prefill scratch cache with the
        already-computed prefix KV gathered straight from the pool pages —
        the scratch then looks exactly as if the covered prefix chunks had
        run, so only the uncovered suffix pays device prefill. ``page_ids``
        is the row's shared prefix pages padded to the scratch's page count
        with the dump page 0; pad segments land on slots the suffix chunks
        overwrite (decode writes K/V before attending) or the valid-length
        mask hides."""
        self.rows.refuse("seeding a scratch from a cached prefix")
        nseg = page_ids.shape[0]

        def seed(dst, src):
            seg = src[page_ids]  # [nseg, kvh, page, dh]
            flat = seg.transpose(1, 0, 2, 3).reshape(
                1, seg.shape[1], nseg * seg.shape[2], seg.shape[3]
            )
            return flat.astype(dst.dtype)

        return jax.tree.map(seed, caches, pool_caches)

    def _verify_impl(self, params, pool, block_tables, rng, draft, q_lens, *, width):
        """Speculative verify: ONE decode forward over a ``width``-token
        window per row (width = K+1), then an accept scan whose emission
        semantics mirror ``_step_block_impl`` exactly. ``draft[:, 0]`` is
        overwritten with the row's pending ``cur_tok`` (every turn starts
        from the sampled, not-yet-emitted token); ``draft[:, 1:]`` are the
        drafter's proposals. ``q_lens`` [B] in 1..width caps how many
        window slots each row may consume — a row with no draft runs
        q_len=1, which reduces to the plain one-token step. Greedy output
        is token-identical to non-speculative decode because window slot t
        attends over exactly the KV a sequential step at that position
        would see (the varq kernel's per-slot causal mask), and rejected
        slots' KV writes land above the row's final ``cur_len`` where the
        valid-length mask hides them until real tokens overwrite them.
        As in ``_step_block_impl`` the sampler sees ``do_sample`` of the rows
        live in a step only (``cur_tok`` takes ``nxt`` where ``step_active``
        alone)."""
        cfg = self.cfg
        self.rows.refuse("speculative verify")
        b = pool["cur_tok"].shape[0]
        capacity = block_tables.shape[1] * self._page_size_of(pool)
        toks_in = jnp.asarray(draft, jnp.int32).at[:, 0].set(pool["cur_tok"])
        # Same spirit as _step_block's clamp: the host never dispatches a
        # live row whose window would cross its block table's capacity.
        pos0 = jnp.minimum(pool["cur_len"], capacity - width)
        positions = pos0[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
        embeds = self._embed(params, toks_in).astype(self.cache_dtype)
        logits, caches, _ = self._decode_paged(
            params, embeds, positions, pool["caches"], block_tables, pos0, pos0 + 1
        )

        cur_tok, cur_len = pool["cur_tok"], pool["cur_len"]
        seen, n_gen = pool["seen"], pool["n_gen"]
        eos, done = pool["eos"], pool["done"]
        accepting = jnp.ones((b,), bool)
        toks_out = jnp.full((b, width), cfg.pad_token_id, jnp.int32)
        for t in range(width):
            step_active = ~done & accepting
            tok = jnp.where(step_active, cur_tok, cfg.pad_token_id)
            toks_out = toks_out.at[:, t].set(tok)
            n_gen = n_gen + step_active.astype(jnp.int32)
            seen = seen.at[jnp.arange(b), cur_tok].max(step_active)
            eos = eos | (step_active & (cur_tok == cfg.eos_token_id))
            done = done | eos | (n_gen >= pool["max_new"])
            rng, sub = jax.random.split(rng)
            nxt = self._sample_next(
                sub, logits[:, t], seen,
                pool["temperature"], pool["top_p"], pool["do_sample"] & step_active, pool["rep"],
            ).astype(jnp.int32)
            cur_len = cur_len + step_active.astype(jnp.int32)
            if t + 1 < width:
                # Slot t+1 survives only if its drafted token IS what the
                # target just sampled — then its precomputed logits are
                # exactly the sequential step's logits.
                accepting = step_active & ~done & (t + 1 < q_lens) & (toks_in[:, t + 1] == nxt)
            else:
                accepting = jnp.zeros((b,), bool)
            cur_tok = jnp.where(step_active, nxt, cur_tok)

        new_pool = dict(
            pool,
            caches=caches,
            cur_tok=cur_tok,
            cur_len=cur_len,
            seen=seen,
            n_gen=n_gen,
            eos=eos,
            done=done,
        )
        return new_pool, rng, toks_out
