"""Continuous batching over a paged KV pool for VLM generation.

The one engine VLM requests are served by. A batcher that launches one
fused generation program per group of same-shaped requests makes everything
behind it queue until the longest row finishes, and a contiguous ``max_seq``
KV region per decode row pays worst-case memory per slot and needs a
same-shape bucket at admission. This engine does neither:

- KV lives in a shared POOL OF PAGES (``paged_kv.PagedKVPool`` host
  accounting + ``Generator.init_pool`` device arrays); each row owns a
  block table that grows page by page as it decodes and returns its pages
  at retire, so long and short generations share memory and a request
  admits the moment a slot and its prompt's pages are free;
- decode attention is RAGGED PAGED ATTENTION (``ops.attention``): the
  Pallas kernel on TPU, the exact XLA gather reference on CPU — tier-1
  runs the same code path end to end;
- a burst of same-shaped arrivals still prefills as ONE batched forward
  (``ADMIT_BUCKETS``), and long prompts go through a CHUNKED PREFILL LANE
  — every job in the lane runs one of its chunks per scheduler turn,
  oldest first, and installs in the turn its last chunk runs — so a
  1k-token prompt never stalls in-flight decode steps and a burst of
  long prompts does not queue behind its own head;
- rows retire on EOS / per-request cap without stopping the others; if
  the pool runs dry mid-decode the newest row is PREEMPTED: its live KV
  pages are exported to a host SPILL TIER (one fused ``jax.device_get``
  into an shm-arena lease, ``utils/shm_arena.py``) together with the
  row's exact decode state, and re-admission scatters the pages back
  into a fresh grant — no re-prefill, greedy resume token-identical,
  sampled mid-stream rows resume their own draw instead of failing. The
  spill ledger is bounded (``LUMEN_VLM_SPILL_BYTES`` /
  ``LUMEN_VLM_SPILL_MAX``); any spill/resume failure — arena exhaustion,
  corrupt lease, export fault (``kv_spill``/``kv_resume`` fault points) —
  degrades to the pre-spill ladder: requeue-and-redo for rows whose
  restart is invisible (greedy, or nothing streamed yet), a typed
  retryable :class:`~lumen_tpu.utils.deadline.PreemptionShed` carrying
  the engine's drain estimate for sampled mid-stream rows. Lease and
  page accounting balance at drain, and every spill/resume lands a
  ``vlm_spill``/``vlm_resume`` flight-recorder event.

Per-step occupancy (active rows / pool pages) is published as gauges and
each decode block lands a ``batch.device`` span on every active request's
trace. The reference serves one request at a time per process
(``packages/lumen-vlm/src/lumen_vlm/backends/onnxrt_backend.py:298-356``);
this has no upstream equivalent.
"""

from __future__ import annotations

import logging
import os
import queue as queue_mod
import threading
import time
import weakref
from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.sampling import draws
from ...testing.faults import KV_RESUME, KV_SPILL, faults
from ...utils.deadline import PreemptionShed
from ...utils import telemetry
from ...utils.metrics import metrics
from ...utils.shm_arena import ShmArena
from ...utils.telemetry import record_event
from ...utils.trace import current_trace, phase
from . import migration
from .modeling import prefix_ladder
from .paged_kv import (
    DEFAULT_PAGE_SIZE,
    PagedKVPool,
    PoolExhausted,
    WindowPages,
    window_pool_pages,
)
from .prefix_cache import PrefixCache, chunk_keys, prefix_cache_enabled

logger = logging.getLogger(__name__)

_STREAM_END = object()


def _fail(req: "_Request", err: BaseException) -> None:
    """Retire a request with an error: resolve its future (if a result
    hasn't already won) and unblock any stream consumer. Every retirement
    path MUST go through here or :func:`_retire` — a missed
    ``_STREAM_END`` strands the consumer on ``stream_q.get()`` forever."""
    if not req.future.done():
        req.future.set_exception(err)
    if req.stream_q is not None:
        req.stream_q.put(_STREAM_END)


def _retire(req: "_Request", tokens: list, eos: bool) -> None:
    """Retire a request successfully with whatever tokens it produced."""
    if not req.future.done():
        req.future.set_result((np.asarray(tokens, np.int64), len(tokens), eos))
    if req.stream_q is not None:
        req.stream_q.put(_STREAM_END)


@dataclass
class _Request:
    """One continuous-batching request: the prepared prompt and its
    generation parameters, a per-request rng, an optional stream queue, a
    cancel flag (set when a stream consumer goes away so the slot stops
    decoding), and the submitter's trace (decode blocks land
    ``batch.device`` spans on it)."""

    embeds: object  # [1, L, H]
    positions: object  # [1, L]
    length: object  # [1]
    prompt_ids: object  # [1, S]
    max_new: int
    temperature: float
    top_p: float
    do_sample: bool
    repetition_penalty: float
    rng: object = None

    @property
    def key(self) -> tuple:
        # Only identically-shaped requests share one admission program.
        return (self.embeds.shape[1], self.prompt_ids.shape[1])

    future: Future = field(default_factory=Future)
    stream_q: "queue_mod.SimpleQueue | None" = None
    cancelled: bool = False
    trace: object = None
    #: carried across preemption so a resumed stream never re-delivers.
    delivered: int = 0
    #: parked :class:`_SpillRecord` while the request waits, preempted,
    #: at the queue head for pages to free — None on the normal path.
    spill: "object | None" = None
    #: [L] int64 content identity of the merged prompt (token ids, vision
    #: positions substituted by image-digest ints) — the prefix cache's
    #: key material. None when prefix caching is off.
    prefix_content: "object | None" = None
    #: fraction of the prompt served from shared prefix pages, set at
    #: admission when the cache is enabled (None = cache off) — surfaced
    #: in the final stream chunk metadata.
    prefix_hit: "float | None" = None
    #: per-request speculative decoding tally (stream metadata).
    spec_proposed: int = 0
    spec_accepted: int = 0
    #: decode-lane peer address for disaggregated serving: the row is
    #: exported right after prefill and its decode migrates there.
    #: Cleared after one attempt — any failure decodes locally.
    migrate_to: "str | None" = None
    #: decode-host side of a migration: ``(manifest_keys, n_shared)``
    #: pending prefix-cache resolution at resume. None otherwise.
    migrate_in: "tuple | None" = None
    #: scheduler-local request number, given at submit: the feeder
    #: thread's phases name requests by it, and the request's own
    #: ``batch.device`` spans carry it, so the two join.
    rid: int = 0
    #: ``perf_counter`` instants behind the cumulative wait gauges: first
    #: submit; start of the current wait in the queue (re-stamped when a
    #: preemption sends the request back); taken out of the queue for an
    #: admission unit, a resume or the lane; first token handed out.
    t_submit: float = 0.0
    t_queued: float = 0.0
    t_taken: float = 0.0
    t_first_token: float = 0.0


@dataclass
class _Slot:
    request: _Request
    prompt_len: int = 0  # live prompt tokens (host mirror of pool cur_len base)
    seq: int = 0  # admission order; preemption evicts the newest first
    tokens: list = field(default_factory=list)
    #: host mirrors for the n-gram drafter (spec decoding only): the live
    #: TEXT prompt ids and the pending sampled-but-not-emitted token.
    text_toks: "list | None" = None
    pending_tok: "int | None" = None


@dataclass(eq=False)
class _PrefillJob:
    """One long prompt moving through the chunked prefill lane. Jobs are
    compared by identity: any of them may leave the lane, not the head
    alone, and a field-wise ``==`` would compare device arrays."""

    request: _Request
    caches: object = None  # contiguous [1, kvh, Lb, dh] scratch per layer
    scratch_len: int = 0  # Lb (page- and chunk-aligned)
    offset: int = 0  # prompt tokens already processed
    length: int = 0  # live prompt tokens (host int)
    last_logits: object = None  # logits of the most recent chunk
    last_off: int = 0  # offset of that chunk
    chunk: int = 0  # tokens a chunk of this job (``prefill_chunk``, or its even share)
    #: shared prefix pages seeded into the scratch; the JOB holds one
    #: reference on each until admission or cancellation.
    shared: list = field(default_factory=list)


@dataclass
class _SpillRecord:
    """Everything needed to resume a preempted row WITHOUT re-prefill.

    The page payload (per-layer K/V page stacks, padded to a power-of-2
    page count with dump-page garbage, plus the row's ``seen`` vocab
    mask) lives OUT of line: as a self-describing
    :func:`~lumen_tpu.models.vlm.migration.pack_payload` blob in an
    shm-arena lease when the arena had budget, else as plain host
    arrays (the "pickled spill" twin — same bytes, just not recyclable
    segments). The blob carries each leaf's shape/dtype in-band (the
    same frame train ``fed_kv_put`` ships to a decode peer), so only
    ``treedef`` stays out of band; ``crc`` (crc32 over the blob)
    catches a torn or recycled-out-from-under-us lease at resume time,
    turning silent token corruption into the degradation ladder.
    The decode scalars are exact state, not hints: ``cur_tok`` is the
    sampled-but-not-yet-written next token (it exists nowhere on the
    host side), and ``rng`` snapshots the request's PRNG key so the
    record is self-contained for cross-host migration.
    """

    n_pages: int            # live pages exported — the resume grant size
    n_pad: int              # power-of-2 padded page count in the payload
    nbytes: int             # payload bytes — ledger budget accounting
    treedef: object         # payload pytree structure
    crc: int                # crc32 over the lease's blob (0 = host arrays)
    cur_tok: int            # pending next token (sampled, not yet emitted)
    cur_len: int            # prompt + generated KV length
    n_gen: int              # tokens generated so far (== len(tokens))
    rng: object             # host snapshot of the request's PRNG key
    prompt_len: int = 0
    tokens: list = field(default_factory=list)
    lease: object = None    # ArenaSlot when the shm path won
    arrays: "list | None" = None  # host-array fallback payload
    #: shared prefix pages the row held at spill time. NOT exported —
    #: their contents stay resident in the pool; the RECORD holds one
    #: reference on each so eviction cannot free them while parked, and
    #: resume re-attaches them ahead of the fresh grant.
    shared_pages: list = field(default_factory=list)


class ContinuousScheduler:
    """Paged continuous-batching decode loop on a dedicated thread.

    ``submit`` returns a Future resolving to ``(tokens_np, n_gen, eos)`` —
    the same contract as the coalescing batcher — and optionally streams
    token ids into ``stream_q`` as blocks complete (``_STREAM_END``
    sentinel on retirement, exposed via :meth:`submit_stream`).
    """

    def __init__(
        self, generator, params, slots: int = 8, block: int = 8,
        name: str = "vlm", page_size: int | None = None,
        pages: int | None = None, prefill_chunk: int | None = None,
        mesh=None,
    ):
        from ...utils.env import env_int

        self.gen = generator
        self.params = params
        #: replica mesh slice (fleet mode): the page pool is pinned to it
        #: and submitted request tensors are transferred over (prepare
        #: programs run on replica 0's devices). None = legacy placement.
        self.mesh = mesh
        # Gauge provider id: per-model-name, matching the batcher's
        # ``batcher:{name}`` semantics — distinct models coexist; a
        # same-name replacement takes over the slot (last-writer-wins
        # register, ownership-guarded unregister).
        self.name = name
        # Same ``device:{name}`` duty meter the MicroBatcher declares —
        # the autopilot's scale loop (and the capacity gossip's duty
        # report) read engine fleets through the identical sensor name.
        telemetry.set_capacity(f"device:{self.name}", 1.0, union=True)
        self.n_slots = slots
        self.block = block
        dec = generator.cfg.decoder
        self.page_size = page_size or env_int(
            "LUMEN_VLM_PAGE_SIZE", DEFAULT_PAGE_SIZE, minimum=8, maximum=256
        )
        max_pages = -(-generator.max_seq // self.page_size)
        if pages is None:
            pages = slots * max_pages + 1  # slot-era footprint fallback
        window = window_pages = None
        #: what a row keeps in each layer, and what that allows
        #: (``paged_kv.RowState``). What shares or exports whole rows (prefix
        #: cache, spill tier, speculative verify, migration) has a form for
        #: pages of K/V alone: for latent pages and recurrent state it is
        #: refused or off, never silently wrong.
        rows = self.rows = generator.rows
        if prefix_cache_enabled() or env_int("LUMEN_VLM_SPEC_K", 0, minimum=0, maximum=15):
            rows.refuse("LUMEN_VLM_PREFIX_BYTES / LUMEN_VLM_SPEC_K (a shared prefix, a verify window)")
        if rows.window_layers:
            # window layers keep pages of their own and free those behind the window
            window_pages = window_pool_pages(generator.cfg, self.page_size, slots, block)
            window = WindowPages(
                window_pages, self.page_size, slots, max_pages, dec.sliding_window
            )
        self.kv = PagedKVPool(pages, self.page_size, slots, max_pages, window=window)
        self.pool = generator.init_pool(
            slots, pages=pages, page_size=self.page_size, window_pages=window_pages
        )
        if mesh is not None:
            from ...parallel.sharding import replicate

            self.pool = replicate(self.pool, mesh)
        # Prompts longer than this (padded length) prefill through the
        # chunk lane, one chunk per job per scheduler turn; the chunk is
        # rounded to a page multiple so scratch caches scatter cleanly
        # into pages.
        # 256 tokens at the default max_seq of 2048, an eighth of a longer
        # one: a prompt is at most eight chunks (eight lane turns to its
        # first token) however long rows may get, and every chunk streams
        # the decoder's weights once, so longer rows get longer chunks.
        chunk = prefill_chunk or env_int(
            "LUMEN_VLM_PREFILL_CHUNK", max(256, -(-generator.max_seq // 8)),
            minimum=32, maximum=4096,
        )
        self.prefill_chunk = -(-chunk // self.page_size) * self.page_size
        # With a longer max_seq a prompt's chunks are its even shares (whole
        # pages) and the tail is padded up to one: every chunk of a prompt
        # bucket then runs ONE program, where a short tail would compile a
        # second as large. At the default max_seq the lane is as it was
        # (full chunks, then the tail as a program of its own) only so that
        # the benchmark's accepted cells keep the programs they were measured
        # with: even shares would serve them too (a 320-token span as two
        # chunks of 160), and ROADMAP.md S-queue holds the change that makes
        # them the one path and deletes this flag, measured on those cells.
        self._even_chunks = generator.max_seq > 2048
        from ...utils.env import env_float

        # Decode pacing floor: minimum wall time per decode STEP (a block
        # sleeps out `block * floor - elapsed`). Off by default (0.0 = no
        # branch taken on the hot path); the disagg bench phase arms it so
        # decode throughput on a shared CPU box measures topology (slots x
        # hosts) instead of this box's core count — sleeps scale across
        # host processes the way real chips do, spins don't.
        self._step_floor_s = env_float(
            "LUMEN_GEN_STEP_FLOOR_MS", 0.0, minimum=0.0, maximum=1000.0
        ) / 1e3
        # Decode sampling draws from one scheduler-level stream (sample()
        # takes a single key per batched step); entropy-seeded so sampled
        # continuations differ across processes. An admission group's
        # prefill sample is seeded from its FIRST request's key (one key
        # per batched sample call — the same group-granular semantics as
        # the coalescing batcher, which fuses mixed requests into one
        # generate under one key). Greedy requests are unaffected; a
        # sampled request's draw depends on its admission group.
        self._rng = jax.random.PRNGKey(int.from_bytes(os.urandom(4), "big"))
        self._slots: dict[int, _Slot] = {}  # slot idx -> live request
        self._pending: list[_Request] = []
        self._prefill_jobs: deque[_PrefillJob] = deque()
        self._cond = threading.Condition()
        self._closed = False
        self._admit_seq = 0
        self.blocks_run = 0  # observability
        #: of those, blocks in which no live row drew its token (``draws``):
        #: the compiled sampler then took the argmax and sorted nothing
        self.blocks_greedy = 0
        self.admitted = 0
        self.preemptions = 0
        self.chunks_run = 0
        self.lane_turns = 0  # turns in which the lane dispatched a chunk
        # -- KV spill tier: preemption victims park their pages on the
        # host instead of re-prefilling. Bounded two ways: total payload
        # bytes (also the shm arena's budget, so the lease path and the
        # host-array fallback draw on ONE pool) and entry count.
        # LUMEN_VLM_SPILL_BYTES=0 disables the tier — preemption then
        # degrades exactly as the pre-spill engine did, minus the bare
        # RuntimeError (sampled victims get the typed retryable shed).
        self._spill_budget = env_int("LUMEN_VLM_SPILL_BYTES", 256 << 20, minimum=0)
        if not rows.shareable and self._spill_budget:
            logger.info("VLM spill tier off: this decoder's rows cannot be exported; a preempted row restarts from the prompt")
            self._spill_budget = 0
        self._spill_max = env_int("LUMEN_VLM_SPILL_MAX", 32, minimum=0)
        self._spill_arena: ShmArena | None = None  # created on first spill
        self._spill_ledger: dict[int, _SpillRecord] = {}  # id(req) -> record
        self._spill_bytes_live = 0
        self.spills = 0
        self.spill_resumes = 0
        self.spill_fallbacks = 0  # arena denied -> host-array payload
        self.spill_denied = 0     # ledger full/disabled -> no spill attempt
        self.preempt_redone = 0   # victim restarted from the prompt
        self.preempt_failed = 0   # victim shed with the typed retryable error
        # -- disaggregated serving: the migration dispatcher hook. When a
        # federation with role-tagged peers is live, the serving layer
        # installs ``migrator(scheduler, req, rec, manifest, target)``
        # here; requests tagged ``migrate_to`` are then exported right
        # after prefill (the SAME record format as the spill tier) and
        # their decode runs on the target peer. None (the default, and
        # always when LUMEN_FED_ROLE is unset) never exports — the
        # unconfigured loop is byte-identical to the pre-disagg engine.
        self.migrator = None
        self.migrated_out = 0        # rows handed to the dispatcher
        self.migrate_out_failed = 0  # wire failed -> resumed/shed locally
        self.migrated_in = 0         # peer rows admitted with zero re-prefill
        self.migrate_in_rejected = 0 # bad commit (crc/manifest/pool) refused
        # -- copy-on-write prefix KV reuse: content-addressed cache of
        # page-aligned prompt prefixes. Off (None) unless
        # LUMEN_VLM_PREFIX_BYTES grants a budget — the unconfigured
        # engine allocates no cache and admission is byte-identical.
        self.prefix: PrefixCache | None = None
        if prefix_cache_enabled():
            dtype_bytes = jnp.dtype(generator.cache_dtype).itemsize
            self.prefix = PrefixCache(self.kv, rows.page_bytes(self.page_size, dtype_bytes))
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_hit_pages = 0  # shared pages attached across all hits
        # -- speculative decoding: host n-gram drafter + one-step verify.
        # LUMEN_VLM_SPEC_K=0 (default) builds no drafter and never touches
        # the verify program; acceptance below LUMEN_VLM_SPEC_MIN_RATE
        # after warmup disables drafting for the engine's lifetime (the
        # auto/off gate, like the q8 route).
        from ...utils.env import env_float

        self.spec_k = env_int("LUMEN_VLM_SPEC_K", 0, minimum=0, maximum=15)
        self.spec_ngram = env_int("LUMEN_VLM_SPEC_NGRAM", 3, minimum=1, maximum=8)
        self.spec_min_rate = env_float(
            "LUMEN_VLM_SPEC_MIN_RATE", 0.2, minimum=0.0, maximum=1.0
        )
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_turns = 0
        self.spec_disabled = False
        # Per-token decode pace (EWMA over block wall time) feeds the
        # retry-after hint on PreemptionShed — the same drain-estimate
        # idea as the batcher's queue-full hint.
        self._block_s_ewma = 0.0
        self._preempt_log_t = 0.0  # 1/s warning throttle (shed-log cadence)
        # Decode-step occupancy accumulators: active-row fill per block
        # (every step in a block shares the block-start row count).
        self._occ_rows = 0
        self._occ_blocks = 0
        # Cumulative waits, written by the loop thread alone (a window's
        # mean is a ratio of deltas): queue wait of every admission
        # (submit, or the preemption that sent it back -> taken for an
        # admission unit, a resume or the lane), time in the chunked
        # prefill lane (lane entry -> row installed, first token sampled),
        # and submit -> first token handed to the stream.
        self._submit_seq = 0
        self.pending_ms_sum = 0.0
        self.pending_count = 0
        self.lane_ms_sum = 0.0
        self.lane_jobs = 0
        self.first_token_ms_sum = 0.0
        self.first_token_count = 0
        # A latent decoder's own counters. The indexer's are host arithmetic
        # over the lengths the loop dispatches (a query whose context is
        # over ``index_topk`` has every causal key scored, in each full
        # layer); the expert layers' come back from the device with each
        # block's tokens (``Generator._apply``).
        self._indexer_layers = rows.indexer_layers
        self.indexer_rows = 0
        self.indexer_keys_scored = 0
        # keys one full latent layer's query attends (its own token among
        # them) in a block's first step, summed over the rows the block starts
        # with (``rows_stepped``'s own count): a row's length, or
        # ``index_topk`` where an indexer cuts it
        self._latent_keys_cap = (
            (dec.index_topk if dec.indexer else generator.max_seq) if rows.full_latent_layers else 0
        )
        self.latent_keys_sum = 0
        self.moe_stats = np.zeros((4,), np.int64)  # routed, held, experts touched, calls
        # A recurrent decoder's: the state every slot holds whatever its row's
        # length, rows whose finished state was copied into a slot (inside
        # ``vlm.admit`` or ``vlm.lane_finish``), and of those the ones that replaced what a
        # retired row had left there (an install writes the whole state, so
        # a reused slot starts from its own prompt alone).
        self.state_bytes = slots * rows.slot_bytes(jnp.dtype(generator.cache_dtype).itemsize)
        self.state_installs = 0
        self.state_resets = 0
        self._slots_used: set[int] = set()
        self._thread = threading.Thread(target=self._loop, name="vlm-continuous", daemon=True)
        self._thread.start()
        ref = weakref.ref(self)  # registry must not pin the pool/params

        def _gauges() -> dict:
            s = ref()
            if s is None:
                return {}
            stats = s.kv.stats()
            out = {
                "blocks_run": s.blocks_run,
                "blocks_greedy": s.blocks_greedy,
                "rows_stepped": s._occ_rows,
                "admitted": s.admitted,
                "pending_ms_sum": round(s.pending_ms_sum, 3),
                "pending_count": s.pending_count,
                "lane_ms_sum": round(s.lane_ms_sum, 3),
                "lane_jobs": s.lane_jobs,
                "first_token_ms_sum": round(s.first_token_ms_sum, 3),
                "first_token_count": s.first_token_count,
                "preempted": s.preemptions,
                "prefill_chunks_run": s.chunks_run,
                "lane_turns": s.lane_turns,
                "prefill_lane_depth": len(s._prefill_jobs),
                "slots_total": s.n_slots,
                "slots_live": len(s._slots),
                "queue_depth": len(s._pending),
                "page_size": stats.page_size,
                "pages_total": stats.pages_total,
                "pages_free": stats.pages_free,
                "pages_live": stats.pages_live,
                "pages_allocated_total": stats.allocated_total,
                "pages_freed_total": stats.freed_total,
                "pages_fill_pct": round(
                    100.0 * stats.pages_live / max(stats.pages_total - 1, 1), 1
                ),
                # Spill-tier occupancy + outcome split: resumed vs redone
                # vs failed must add up to preempted once in-flight spills
                # drain, and entries/bytes return to 0 — assertable
                # invariants, same discipline as the page accounting.
                "spill_entries": len(s._spill_ledger),
                "spill_bytes": s._spill_bytes_live,
                "spill_bytes_budget": s._spill_budget,
                "spill_max_entries": s._spill_max,
                "spilled": s.spills,
                "spill_resumed": s.spill_resumes,
                "spill_fallbacks": s.spill_fallbacks,
                "spill_denied": s.spill_denied,
                "preempt_redone": s.preempt_redone,
                "preempt_failed": s.preempt_failed,
                "migrated_out": s.migrated_out,
                "migrate_out_failed": s.migrate_out_failed,
                "migrated_in": s.migrated_in,
                "migrate_in_rejected": s.migrate_in_rejected,
            }
            if s.kv.window is not None:
                out["window_pages_freed"] = s.kv.window.freed_behind
                out["window_pages_live"] = s.kv.window.pages_live
                out["window_pages_total"] = s.kv.window.pages_total
                out["indexer_rows"] = s.indexer_rows
                out["indexer_keys_scored"] = s.indexer_keys_scored
            if s._latent_keys_cap:
                out["latent_keys_sum"] = s.latent_keys_sum
            if s.rows.state_layers:
                out["state_bytes"] = s.state_bytes
                out["state_layers"] = s.rows.state_layers
                out["state_installs"] = s.state_installs
                out["state_resets"] = s.state_resets
            if s.gen._counts_experts:
                routed, held, touched, calls = (int(v) for v in s.moe_stats)
                out["moe_tokens_routed"] = routed
                out["moe_tokens_held"] = held
                out["moe_experts_touched"] = touched
                out["moe_layer_calls"] = calls
            if s._spill_arena is not None:
                arena = s._spill_arena.stats()
                out["spill_arena_segments"] = arena["segments"]
                out["spill_arena_bytes"] = arena["bytes"]
                out["spill_arena_live"] = arena["live"]
                out["spill_arena_denied"] = arena["denied"]
            if s.prefix is not None:
                out.update(s.prefix.gauges())
                out["prefix_hits"] = s.prefix_hits
                out["prefix_misses"] = s.prefix_misses
                out["prefix_hit_pages"] = s.prefix_hit_pages
                out["pages_shared"] = stats.pages_shared
            if s.spec_k > 0:
                out["spec_k"] = s.spec_k
                out["spec_turns"] = s.spec_turns
                out["spec_proposed"] = s.spec_proposed
                out["spec_accepted"] = s.spec_accepted
                out["spec_accept_rate"] = round(
                    s.spec_accepted / max(s.spec_proposed, 1), 3
                )
                out["spec_disabled"] = int(s.spec_disabled)
            if s._occ_blocks:
                out["occupancy_pct_mean"] = round(
                    100.0 * s._occ_rows / (s._occ_blocks * s.n_slots), 1
                )
            return out

        self._gauge_fn = _gauges
        metrics.register_gauges(f"vlm-continuous:{self.name}", _gauges)

    # -- public API --------------------------------------------------------

    def submit(self, req: _Request) -> Future:
        with self._cond:
            if self._closed:
                raise RuntimeError("continuous scheduler is closed")
        # Feasibility is checked at the door: a request whose prompt +
        # budget can NEVER fit the pool (even alone) must fail loudly now,
        # not deadlock the admission queue later.
        need = int(np.asarray(req.length)[0]) + int(req.max_new) + 1
        if not self.kv.fits(need):
            raise ValueError(
                f"request needs {need} KV tokens but the paged pool holds at "
                f"most {min(self.kv.row_capacity(), (self.kv.pages_total - 1) * self.kv.page_size)} "
                "per row; raise LUMEN_VLM_KV_PAGES or lower max_new_tokens"
            )
        if req.trace is None:
            req.trace = current_trace()
        if self.mesh is not None:
            # Fleet mode: prepare ran on replica 0's devices; move the
            # request tensors onto THIS engine's slice before its jitted
            # programs see them (same-placement transfers are no-ops).
            from ...parallel.sharding import replicate

            req.embeds, req.positions, req.length, req.prompt_ids = replicate(
                (req.embeds, req.positions, req.length, req.prompt_ids), self.mesh
            )
        with self._cond:
            if self._closed:
                raise RuntimeError("continuous scheduler is closed")
            self._stamp_submit(req)
            self._pending.append(req)
            self._cond.notify()
        # Arrival counter under the batcher's ``batch_items:{name}`` key:
        # the predictive autopilot fits its trend over these buckets, so
        # engine families share the MicroBatcher sensor vocabulary.
        telemetry.count(f"batch_items:{self.name}")
        return req.future

    def _stamp_submit(self, req: _Request) -> None:
        """Number the request and start its clocks (under ``_cond``)."""
        self._submit_seq += 1
        req.rid = self._submit_seq
        req.t_submit = req.t_queued = time.perf_counter()

    def _count_admitted(self, req: _Request) -> None:
        """A row was installed: book the queue wait that ended when the
        loop took the request (``t_taken``), so ``pending_count`` is
        ``admitted`` by construction."""
        self.admitted += 1
        taken = req.t_taken or time.perf_counter()
        self.pending_ms_sum += max(0.0, taken - req.t_queued) * 1e3
        self.pending_count += 1

    def load(self) -> int:
        """Dispatch weight for the manager's least-loaded engine pick."""
        return len(self._pending) + len(self._slots) + len(self._prefill_jobs)

    def submit_stream(self, req: _Request):
        """Submit and iterate generated token ids as they decode."""
        req.stream_q = queue_mod.SimpleQueue()
        self.submit(req)

        def tokens():
            try:
                while True:
                    item = req.stream_q.get()
                    if item is _STREAM_END:
                        err = req.future.exception()
                        if err is not None:
                            raise err
                        return
                    yield item
            finally:
                # Consumer gone (stop sequence hit, client disconnect, or
                # normal end): tell the scheduler to free the slot instead
                # of decoding to the cap into an unread queue.
                req.cancelled = True

        return tokens()

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify()
        self._thread.join(timeout=10)
        with self._cond:
            pending, self._pending = self._pending, []
            live, self._slots = list(self._slots.values()), {}
            jobs, self._prefill_jobs = list(self._prefill_jobs), deque()
        err = RuntimeError("continuous scheduler closed")
        for req in pending + [s.request for s in live] + [j.request for j in jobs]:
            self._drop_spill(req)
            _fail(req, err)
        for job in jobs:
            self._drop_job_hold(job)
        if self.prefix is not None:
            self.prefix.clear()
        if self._spill_arena is not None:
            self._spill_arena.close()
        if fn := getattr(self, "_gauge_fn", None):
            metrics.unregister_gauges(f"vlm-continuous:{self.name}", fn)

    # -- scheduler loop ----------------------------------------------------

    def _idle(self) -> bool:
        return not (self._closed or self._pending or self._slots or self._prefill_jobs)

    def _take_work(self) -> list[_Request]:
        """Block until there is something to do; drain admissible requests.
        Chunk-lane jobs hold a slot reservation, so the drain never takes
        more requests than slots that will actually be free."""
        with self._cond:
            if self._idle():
                with phase("vlm.wait_work"):
                    while self._idle():
                        self._cond.wait()
            if self._closed:
                return []
            free = self.n_slots - len(self._slots) - len(self._prefill_jobs)
            if free <= 0:
                return []
            take, self._pending = self._pending[:free], self._pending[free:]
            return take

    def _requeue_front(self, reqs: list[_Request]) -> None:
        """Return unplaceable requests to the head of the queue in order."""
        if reqs:
            with self._cond:
                self._pending[:0] = reqs

    def _loop(self) -> None:
        try:
            while True:
                admit = self._take_work()
                with self._cond:
                    closed = self._closed
                if closed:
                    # close() raced us after _take_work popped these off
                    # _pending — its sweep can no longer see them, so fail
                    # them here instead of stranding their callers.
                    err = RuntimeError("continuous scheduler closed")
                    for req in admit:
                        _fail(req, err)
                    return
                with phase("vlm.gate", step=self.blocks_run + 1, taken=len(admit)):
                    placeable = self._gate(admit)
                taken = time.perf_counter()
                direct, hits = [], []
                for req in placeable:
                    req.t_taken = taken
                    if req.spill is not None:
                        # Re-admission scatters the spilled pages back in —
                        # no prefill group, no chunk lane, no device work
                        # proportional to the prompt.
                        with phase("vlm.admit", kind="resume", rows=1, rids=str(req.rid)):
                            self._resume_row(req)
                    elif req.embeds.shape[1] > self.prefill_chunk:
                        with phase("vlm.admit", kind="lane", rows=1, rids=str(req.rid)):
                            self._prefill_jobs.append(self._start_chunk_job(req))
                    elif self._prefix_lookup(req, int(np.asarray(req.length)[0])):
                        hits.append(req)
                    else:
                        direct.append(req)
                # Admission units: prefix hits go one by one (per-row
                # coverage), misses keep the batched-prefill groups. Both
                # fail like a group: the unit's requests on error, the
                # whole engine if the donation consumed the pool.
                units = [("hit", self._admit_prefix_hit, req, [req]) for req in hits]
                units += [
                    ("group", self._admit_group, g, g) for g in self._admit_groups(direct)
                ]
                for gpos, (kind, admit_fn, arg, members) in enumerate(units):
                    try:
                        with phase(
                            "vlm.admit", kind=kind, rows=len(members),
                            rids=",".join(str(r.rid) for r in members),
                        ):
                            admit_fn(arg)
                    except Exception as e:  # noqa: BLE001 - fail ONE unit
                        for req in members:
                            _fail(req, e)
                        if self._pool_invalid():
                            # The failure hit the donation-based _admit call
                            # after self.pool's buffers were consumed: the
                            # other slots' KV state is gone, so "fail one
                            # group" is impossible — escalate to the
                            # fail-everything handler below. That handler
                            # sweeps only _pending + _slots and this batch
                            # is already off _pending, so fail its
                            # unprocessed tail here first.
                            for *_, later in units[gpos + 1 :]:
                                for req in later:
                                    _fail(req, e)
                            raise RuntimeError(
                                "slot pool invalidated by failed admission"
                            ) from e
                self._advance_prefill_lane()
                if self.migrator is not None:
                    with phase("vlm.migrate"):
                        self._migrate_sweep()
                if self._slots:
                    self._run_block()
        except BaseException as e:  # noqa: BLE001 - never strand callers
            logger.exception("continuous scheduler loop died")
            with self._cond:
                self._closed = True
                pending, self._pending = self._pending, []
                live, self._slots = list(self._slots.values()), {}
                jobs, self._prefill_jobs = list(self._prefill_jobs), deque()
            for req in pending + [s.request for s in live] + [j.request for j in jobs]:
                self._drop_spill(req)
                _fail(req, RuntimeError(f"continuous scheduler died: {e!r}"))
            for job in jobs:
                self._drop_job_hold(job)

    def _gate(self, admit: list[_Request]) -> list[_Request]:
        """The head of a turn: sweep cancelled arrivals, gate the rest on
        pages in arrival order, send what does not fit back to the queue
        head. Returns the requests to place this turn."""
        live = []
        for req in admit:
            if req.cancelled:
                # Stream consumer disconnected while queued: retire
                # without wasting a prefill dispatch on a dead row.
                # A parked spill record's tokens are what the row
                # produced — deliver them, and free the lease.
                rec = self._drop_spill(req)
                _retire(req, list(rec.tokens) if rec else [], eos=False)
            else:
                live.append(req)
        # Page gating: take requests in arrival order while the
        # free list covers their prompts; the rest go back to the
        # queue head and wait for retires to free pages. A
        # finished chunk-lane job waiting on pages gets its need
        # RESERVED out of the budget first — without that, a
        # sustained stream of short arrivals re-grants every
        # freed page each turn and starves the long prompt
        # forever.
        placeable, deferred = [], []
        budget = self.kv.pages_free - self._lane_reserved_pages()
        for req in live:
            if req.spill is not None:
                # A parked victim resumes into exactly its exported
                # grant; growth past it is _ensure_growth's job.
                need = req.spill.n_pages
            else:
                n = int(np.asarray(req.length)[0])
                # A cached prefix needs no fresh grant — coverage
                # is re-checked at admission (eviction between the
                # peek and the attach degrades to a requeue).
                covered = len(self._prefix_lookup(req, n))
                need = self.kv.pages_for(n + 1) - covered
            if need > budget and self.prefix is not None and not deferred:
                # Cached history yields to live admissions before
                # any request waits on retires.
                budget += self.prefix.reclaim(need - budget)
            if deferred or need > budget:
                deferred.append(req)
            else:
                budget -= need
                placeable.append(req)
        self._requeue_front(deferred)
        return placeable

    def _pool_invalid(self) -> bool:
        """True when the page pool's buffers were deleted by a donation
        whose computation then failed (see ``Generator._admit``'s
        ``donate_argnames``)."""
        return any(
            getattr(leaf, "is_deleted", lambda: False)()
            for leaf in jax.tree.leaves(self.pool)
        )

    def _free_slot(self) -> int:
        for i in range(self.n_slots):
            if i not in self._slots:
                return i
        raise RuntimeError("no free slot (scheduler bug: admission overran pool)")

    #: Admission batch buckets: a burst of same-shaped arrivals prefills
    #: as ONE batched forward instead of K sequential batch-1 forwards
    #: (round-4 verdict: batch-1 admission serializes full-prompt prefills
    #: between decode blocks and starves the slot pool under load).
    #: Power-of-2 buckets bound the number of compiled prefill shapes.
    ADMIT_BUCKETS = (1, 2, 4, 8)

    def _admit_groups(self, reqs: list[_Request]) -> list[list[_Request]]:
        """Split admissible requests into batched-prefill groups: same
        (embeds len, prompt len) bucket, chunked to ADMIT_BUCKETS sizes."""
        by_shape: dict[tuple, list[_Request]] = {}
        for req in reqs:
            by_shape.setdefault(req.key, []).append(req)
        groups: list[list[_Request]] = []
        cap = max(self.ADMIT_BUCKETS)
        for group in by_shape.values():
            while group:
                # Largest bucket <= len(group), so a burst of 8 runs as one
                # prefill and a straggler of 3 runs as 2 + 1, not 8-padded.
                k = max(b for b in self.ADMIT_BUCKETS if b <= min(len(group), cap))
                groups.append(group[:k])
                group = group[k:]
        return groups

    def _admit_kv_len(self, span: int) -> int:
        """Prefill-scratch length for a prompt span: the generator's KV
        bucket rounded up to a page multiple (the scratch scatters into
        pages whole)."""
        kv_len = next((b for b in self.gen.seq_buckets if b >= span), self.gen.max_seq)
        kv_len = max(kv_len, span)
        return -(-kv_len // self.page_size) * self.page_size

    # -- prefix cache helpers -----------------------------------------------

    def _prefix_keys(self, req: _Request, n: int) -> list[bytes]:
        """Chain-hash keys over the request's live content identity,
        computed once per request (page-aligned, so a requeue reuses
        them)."""
        if self.prefix is None or req.prefix_content is None:
            return []
        keys = getattr(req, "_pfx_keys", None)
        if keys is None:
            content = np.asarray(req.prefix_content)[:n]
            keys = chunk_keys(content, self.page_size)
            req._pfx_keys = keys
        return keys

    def _prefix_lookup(self, req: _Request, n: int) -> list[int]:
        """Longest cached prefix for this request, capped one token short
        of the prompt so the write frontier always lands in a private
        page (``admit_shared``'s contract)."""
        keys = self._prefix_keys(req, n)
        if not keys:
            return []
        return self.prefix.lookup(keys)[: (n - 1) // self.page_size]

    def _prefix_insert(self, req: _Request, slot: int, n: int) -> None:
        """Record an installed row's full prompt pages (hit rows refresh
        their shared entries and extend coverage with the fresh suffix)."""
        keys = self._prefix_keys(req, n)
        if not keys:
            return
        pages = self.kv.owned_pages(slot)[: len(keys)]
        self.prefix.insert(keys[: len(pages)], pages)

    def _text_toks(self, req: _Request) -> list[int]:
        """Host copy of the live text prompt ids (drafter context)."""
        ids = [int(t) for t in np.asarray(req.prompt_ids)[0]]
        pad = self.gen.cfg.pad_token_id
        while ids and ids[-1] == pad:
            ids.pop()
        return ids

    def _install_row(
        self, req: _Request, caches1, tok0, seen1, length, shared_pages=None
    ) -> int:
        """Grant pages + write one prefilled row into a free slot. The
        device write donates the pool, so a failure here may invalidate
        it (callers escalate via ``_pool_invalid``). ``shared_pages``
        attaches a cached prefix ahead of the fresh grant — the device
        scatter then targets a DOCTORED table whose shared entries point
        at the dump page, so the scratch's prefix segments (already
        resident in the real pages) land harmlessly while the suffix
        segments fill the private pages."""
        slot = self._free_slot()
        n = int(np.asarray(length)[0])
        shared = list(shared_pages or ())
        if shared:
            bt_row = self.kv.admit_shared(slot, shared, n)
            bt_dev = bt_row.copy()
            bt_dev[: len(shared)] = 0
        else:
            bt_row = self.kv.admit(slot, n)
            bt_dev = bt_row
            if self.kv.window is not None:
                bt_dev = np.stack([bt_row, self.kv.window.tables[slot]])
        try:
            self.pool = self.gen._admit(
                self.pool, slot, caches1, tok0, seen1, length,
                jnp.asarray(bt_dev), req.max_new, req.temperature,
                req.top_p, req.do_sample, req.repetition_penalty,
            )
        except Exception:
            self.kv.release(slot)
            raise
        self._admit_seq += 1
        if self.rows.state_layers:
            self.state_installs += 1
            self.state_resets += slot in self._slots_used
            self._slots_used.add(slot)
        slot_state = _Slot(request=req, prompt_len=n, seq=self._admit_seq)
        if self._spec_active():
            slot_state.text_toks = self._text_toks(req)
            slot_state.pending_tok = int(np.asarray(tok0)[0])
        with self._cond:
            self._slots[slot] = slot_state
        self._count_admitted(req)
        if self.prefix is not None:
            if shared:
                self.prefix_hits += 1
                self.prefix_hit_pages += len(shared)
                metrics.count("vlm_prefix_hits")
                req.prefix_hit = len(shared) * self.page_size / max(n, 1)
            else:
                self.prefix_misses += 1
                metrics.count("vlm_prefix_misses")
                req.prefix_hit = 0.0
            self._prefix_insert(req, slot, n)
        return slot

    def _admit_prefix_hit(self, req: _Request) -> None:
        """Admit one request whose prompt prefix is cached: attach the
        shared pages as a block-table copy, seed a prefill scratch with
        their contents (a device gather — no decoder forward), and run
        the decoder over the UNCOVERED SUFFIX only. The device prefill
        cost of a hot prefix is zero. Coverage is re-resolved here (an
        eviction since the admission peek shrinks it); losing the page
        race degrades to a requeue, losing coverage entirely to a plain
        batch-of-one admission."""
        pages = self._prefix_lookup(req, int(np.asarray(req.length)[0]))
        if not pages:
            self._admit_group([req])
            return
        n = int(np.asarray(req.length)[0])
        covered = len(pages) * self.page_size
        span = int(req.embeds.shape[1])
        scratch_len = self._admit_kv_len(span)
        nseg = scratch_len // self.page_size
        ids = np.zeros((nseg,), np.int32)
        ids[: len(pages)] = pages
        caches = self.gen.new_prefill_cache(scratch_len)
        caches = self.gen._seed_prefix(caches, self.pool["caches"], jnp.asarray(ids))
        c = span - covered
        chunk = req.embeds[:, covered:span]
        positions = jnp.broadcast_to(jnp.arange(covered, span)[None, :], (1, c))
        logits, caches = self.gen._prefill_chunk(
            self.params, caches, chunk, positions,
            jnp.asarray(covered, jnp.int32), jnp.asarray([n], jnp.int32),
        )
        sub = jax.random.fold_in(req.rng, 0)
        tok0, seen = self.gen._chunk_finish(
            logits, jnp.asarray([n - 1 - covered], jnp.int32),
            req.prompt_ids, req.length, sub,
            jnp.asarray([req.temperature], jnp.float32),
            jnp.asarray([req.top_p], jnp.float32),
            jnp.asarray([req.do_sample]),
            jnp.asarray([req.repetition_penalty], jnp.float32),
        )
        try:
            self._install_row(req, caches, tok0, seen, req.length, shared_pages=pages)
        except PoolExhausted:
            # Same-turn eviction shrank coverage and the fresh need no
            # longer fits — park at the queue head and retry next turn.
            self._requeue_front([req])

    def _admit_group(self, reqs: list[_Request]) -> None:
        """One batched prefill for the group, then per-row slot admission.
        The group shares one sampling key (same semantics as the
        coalescing batcher, which fuses mixed requests into one generate
        with one key); per-request generation params stay per-row."""
        k = len(reqs)
        sub = jax.random.fold_in(reqs[0].rng, 0)
        if k == 1:
            req = reqs[0]
            embeds, positions = req.embeds, req.positions
            lengths, prompt_ids = req.length, req.prompt_ids
        else:
            embeds = jnp.concatenate([r.embeds for r in reqs], axis=0)
            positions = jnp.concatenate([r.positions for r in reqs], axis=0)
            lengths = jnp.concatenate([r.length for r in reqs], axis=0)
            prompt_ids = jnp.concatenate([r.prompt_ids for r in reqs], axis=0)
        # Right-size the admission prefill cache to the PROMPT span only:
        # decode happens in the shared page pool, so the prefill buffer
        # never needs max_seq. Without this, a burst of 8 would
        # transiently allocate a second pool-sized KV buffer — an OOM
        # spike on exactly the load batched admission exists for.
        kv_len = self._admit_kv_len(embeds.shape[1])
        caches, tok0, seen = self.gen._prefill(
            self.params, embeds, positions, lengths, prompt_ids, sub,
            jnp.asarray([r.temperature for r in reqs], jnp.float32),
            jnp.asarray([r.top_p for r in reqs], jnp.float32),
            jnp.asarray([r.do_sample for r in reqs]),
            jnp.asarray([r.repetition_penalty for r in reqs], jnp.float32),
            kv_len=kv_len,
        )
        group_slots: list[int] = []
        try:
            for i, req in enumerate(reqs):
                row = slice(i, i + 1)
                caches1 = jax.tree.map(lambda c, r=row: c[r], caches)
                slot = self._install_row(req, caches1, tok0[row], seen[row], lengths[row])
                group_slots.append(slot)
        except Exception:
            # Mid-group failure with earlier rows already admitted: the
            # caller fails EVERY request in the group, so rows already in
            # _slots must be evicted too — otherwise they keep decoding to
            # max_new for futures that already errored, burning slots and
            # pages. If the pool was invalidated (donation consumed), skip
            # the device write; the caller escalates to fail-everything.
            if group_slots and not self._pool_invalid():
                idx = jnp.asarray(group_slots, jnp.int32)
                self.pool = dict(self.pool, done=self.pool["done"].at[idx].set(True))
            with self._cond:
                for slot in group_slots:
                    self._slots.pop(slot, None)
                    self.kv.release(slot)
            raise

    # -- chunked prefill lane ----------------------------------------------

    def _lane_reserved_pages(self) -> int:
        """Pages spoken for by the chunk-lane jobs whose chunks have all
        run: each installs, oldest first, the moment the free list covers
        it, so every one of them still in the lane is waiting on pages."""
        return sum(
            self.kv.pages_for(job.length + 1) - len(job.shared)
            for job in self._prefill_jobs
            if job.offset >= job.length and not job.request.cancelled
        )

    def _start_chunk_job(self, req: _Request) -> _PrefillJob:
        n = int(np.asarray(req.length)[0])
        span = int(req.embeds.shape[1])
        # Sized to the padded span only (tail chunks shrink to fit): the
        # scratch must stay within what a block-table row can address.
        scratch_len = self._admit_kv_len(span)
        chunk = self.prefill_chunk
        if self._even_chunks:
            share = -(-span // -(-span // chunk))  # span over its chunk count
            chunk = min(chunk, -(-share // self.page_size) * self.page_size)
        job = _PrefillJob(
            request=req,
            caches=self.gen.new_prefill_cache(scratch_len),
            scratch_len=scratch_len,
            length=n,
            chunk=chunk,
        )
        # Lane jobs reuse cached prefixes too: seed the scratch from the
        # shared pages and start chunking AFTER the covered span. The job
        # holds its own reference on the pages (``shared``) so eviction
        # during the multi-turn chunk run cannot free them mid-prefill;
        # _drop_job_hold releases it on every exit path.
        hit = self._prefix_lookup(req, n)
        if hit:
            self.kv.incref(hit)
            job.shared = list(hit)
            nseg = scratch_len // self.page_size
            ids = np.zeros((nseg,), np.int32)
            ids[: len(hit)] = hit
            job.caches = self.gen._seed_prefix(
                job.caches, self.pool["caches"], jnp.asarray(ids)
            )
            job.offset = len(hit) * self.page_size
        return job

    def _drop_job_hold(self, job: _PrefillJob) -> None:
        """Release a lane job's prefix-page hold (idempotent)."""
        if job.shared:
            self.kv.decref(job.shared)
            job.shared = []

    def _advance_prefill_lane(self) -> None:
        """One lane turn: EVERY job runs one chunk, oldest first (decode
        blocks interleave between a job's chunks), then the jobs whose
        last live chunk has run install in arrival order — in this same
        turn when pages are free."""
        ran = 0
        for job in list(self._prefill_jobs):
            if job.request.cancelled:
                self._prefill_jobs.remove(job)
                self._drop_job_hold(job)
                _retire(job.request, [], eos=False)
            elif job.offset < job.length:
                self._run_lane_chunk(job)
                ran += 1
        if ran:
            self.lane_turns += 1
        for job in list(self._prefill_jobs):
            if job.offset < job.length:
                continue  # chunks left: holds no pages, blocks nobody
            if not self._lane_pages_ready(job):
                # Short of pages: wait for retires, and hold back the
                # younger jobs too — _lane_reserved_pages keeps the gate
                # from granting what they wait for to new arrivals.
                return
            with phase("vlm.lane_finish", rid=job.request.rid):
                self._finish_lane_job(job)

    def _run_lane_chunk(self, job: _PrefillJob) -> None:
        """Dispatch the job's next prompt chunk into its scratch cache."""
        req = job.request
        off = job.offset
        # Tail chunks shrink to the padded span — off and the chunk size
        # are host ints, so each (span, off) pair is one tiny compiled
        # slice; counts are bounded by the prompt buckets over the chunk
        # size.
        c = min(job.chunk, int(req.embeds.shape[1]) - off)
        with phase("vlm.prefill_chunk", rid=req.rid, offset=off, tokens=c):
            chunk = req.embeds[:, off : off + c]
            valid = jnp.asarray([min(job.length, off + c)], jnp.int32)
            if self._even_chunks and c < job.chunk and off + job.chunk <= job.scratch_len:
                # the tail, padded to the job's chunk: the padding's rows land
                # past the prompt in the scratch, where ``valid`` hides them
                chunk = jnp.pad(chunk, ((0, 0), (0, job.chunk - c), (0, 0)))
                c = job.chunk
            positions = jnp.broadcast_to(jnp.arange(off, off + c)[None, :], (1, c))
            job.last_logits, job.caches = self.gen._prefill_chunk(
                self.params, job.caches, chunk, positions,
                jnp.asarray(off, jnp.int32), valid,
            )
        job.last_off = off
        job.offset = off + c
        self.chunks_run += 1
        if self._indexer_layers:
            # the chunk ran against the smallest rung of the scratch's key ladder over its end
            rung = next(n for n in prefix_ladder(job.scratch_len) if n >= off + c)
            self._count_indexer(c, rung)

    def _count_indexer(self, rows: int, keys: int) -> None:
        """``rows`` queries were dispatched through each full layer against
        ``keys`` key slots: where that is over ``index_topk`` the indexer
        scored every slot for every row (masked ones and idle rows too: this
        counts the work dispatched, not the work a tighter dispatch needs)."""
        if keys > self.gen.cfg.decoder.index_topk:
            self.indexer_rows += self._indexer_layers * rows
            self.indexer_keys_scored += self._indexer_layers * rows * keys

    def _lane_pages_ready(self, job: _PrefillJob) -> bool:
        """Whether the free list covers a finished job's row. Shared
        prefix pages are already granted-by-reference, so only the fresh
        suffix competes for the free list; cached history yields
        (reclaim) before the job stalls."""
        if self.kv.can_admit(job.length, shared_pages=len(job.shared)):
            return True
        if self.prefix is None:
            return False
        short = self.kv.pages_for(job.length + 1) - len(job.shared) - self.kv.pages_free
        if short <= 0 or not self.prefix.reclaim(short):
            return False
        return self.kv.can_admit(job.length, shared_pages=len(job.shared))

    def _finish_lane_job(self, job: _PrefillJob) -> None:
        """Sample a finished job's first token and install its row."""
        req = job.request
        sub = jax.random.fold_in(req.rng, 0)
        tok0, seen = self.gen._chunk_finish(
            job.last_logits, jnp.asarray([job.length - 1 - job.last_off], jnp.int32),
            req.prompt_ids, req.length, sub,
            jnp.asarray([req.temperature], jnp.float32),
            jnp.asarray([req.top_p], jnp.float32),
            jnp.asarray([req.do_sample]),
            jnp.asarray([req.repetition_penalty], jnp.float32),
        )
        self._prefill_jobs.remove(job)
        try:
            self._install_row(
                req, job.caches, tok0, seen, req.length,
                shared_pages=job.shared,
            )
            self.lane_ms_sum += (time.perf_counter() - req.t_taken) * 1e3
            self.lane_jobs += 1
        except Exception as e:  # noqa: BLE001
            _fail(req, e)
            if self._pool_invalid():
                raise RuntimeError(
                    "slot pool invalidated by failed admission"
                ) from e
        finally:
            self._drop_job_hold(job)

    # -- decode blocks ------------------------------------------------------

    def _preempt_newest(self, protect: int) -> bool:
        """Evict the newest live row (except ``protect``): export its
        pages + decode state into the spill tier and park it at the queue
        head to RESUME (no re-prefill, token-identical continuation), or
        — when the tier is full, disabled, or the export itself fails —
        fall down the pre-spill ladder: requeue-and-redo for rows whose
        restart is invisible (greedy, or nothing streamed yet; greedy
        reproduces its tokens exactly and ``delivered`` is deliberately
        NOT reset so a resumed stream never re-sends its prefix), and a
        typed retryable :class:`PreemptionShed` for sampled mid-stream
        rows — splicing a fresh draw onto already-streamed tokens would
        emit a sequence no sampling run ever produced. With the spill
        tier those rows are preferred LAST as victims and, when they must
        go, usually resume instead of shedding."""
        victims = [i for i in self._slots if i != protect]
        if not victims:
            return False

        def redo_safe(i: int) -> bool:
            req = self._slots[i].request
            return not (req.do_sample and req.delivered > 0)

        clean = [i for i in victims if redo_safe(i)]
        idx = max(clean or victims, key=lambda i: self._slots[i].seq)
        record = spill_err = None
        try:
            # Export happens BEFORE the pop/release while the row still
            # owns its pages; _export_row does not donate the pool, so a
            # failed export leaves every other row intact.
            record = self._spill_victim(idx)
        except Exception as e:  # noqa: BLE001 - spill is best-effort
            spill_err = e
            logger.warning("KV spill of slot %d failed (%s); degrading", idx, e)
        self.pool = dict(
            self.pool, done=self.pool["done"].at[jnp.asarray([idx], jnp.int32)].set(True)
        )
        with self._cond:
            slot = self._slots.pop(idx)
        self.kv.release(idx)
        self.preemptions += 1
        slot.request.t_queued = time.perf_counter()  # back to the queue: a new wait
        metrics.count("vlm_paged_preemptions")
        now = time.monotonic()
        if now - self._preempt_log_t >= 1.0:
            # Throttled like the batcher's shed log: a preemption storm is
            # one line a second, not a flood.
            self._preempt_log_t = now
            logger.warning(
                "paged KV pool exhausted: preempting slot %d (%d tokens in, %s)",
                idx, len(slot.tokens),
                "spilled for resume" if record is not None else "restarts from prompt",
            )
        req = slot.request
        if record is not None:
            record.prompt_len = slot.prompt_len
            record.tokens = slot.tokens
            self._park_spill(req, record)
        elif not (req.do_sample and req.delivered > 0):
            self.preempt_redone += 1
            metrics.count("vlm_preempt_redone")
            self._requeue_front([req])
        else:
            self._fail_preempted(req, spill_err)
        return True

    # -- KV spill tier -------------------------------------------------------

    def _get_arena(self) -> ShmArena:
        """Lazily created so engines that never preempt never touch
        /dev/shm; budget shared with the ledger byte bound."""
        if self._spill_arena is None:
            tag = "".join(c if c.isalnum() else "-" for c in self.name)
            self._spill_arena = ShmArena(
                name=f"vlmspill-{tag}", max_bytes=self._spill_budget
            )
        return self._spill_arena

    def _export_state(self, idx: int, n_shared: int) -> tuple:
        """ONE export codepath for both migration sinks: gather slot
        ``idx``'s pages past the first ``n_shared`` block-table entries
        (power-of-2 padded — dump-page garbage fills the tail, bounding
        compiled export/resume shapes at log2(max_pages)) plus the row's
        exact decode scalars and rng, in ONE fused device->host
        transfer. Returns ``(record, shared_page_ids)`` with the payload
        as host-array leaves; the sink decides where the bytes live —
        the shm arena (spill), or the tensor wire (``fed_kv_put``).
        ``_export_row`` does not donate, so failure anywhere leaves the
        pool untouched."""
        owned = self.kv.owned_pages(idx)
        shared, private = owned[:n_shared], owned[n_shared:]
        n_pad = 1
        while n_pad < max(1, len(private)):
            n_pad *= 2
        ids = np.zeros((n_pad,), np.int32)
        ids[: len(private)] = private
        req = self._slots[idx].request
        exported = self.gen._export_row(self.pool, idx, jnp.asarray(ids))
        host, rng = jax.device_get((exported, req.rng))
        payload = {"pages": host["pages"], "seen": host["seen"]}
        leaves, treedef = jax.tree.flatten(payload)
        rec = _SpillRecord(
            n_pages=len(private), n_pad=n_pad,
            nbytes=sum(int(a.nbytes) for a in leaves),
            treedef=treedef, crc=0, cur_tok=int(host["cur_tok"]),
            cur_len=int(host["cur_len"]), n_gen=int(host["n_gen"]),
            rng=rng, arrays=leaves,
        )
        return rec, shared

    def _spill_victim(self, idx: int) -> "_SpillRecord | None":
        """Export slot ``idx``'s live pages + decode state into a spill
        record. ``None`` = tier disabled or ledger full (counted, caller
        degrades); raises on export/pack failure (incl. the ``kv_spill``
        fault point). Runs BEFORE the caller releases the pages, so
        failure leaves the pool untouched."""
        if self._spill_budget <= 0 or self._spill_max <= 0:
            return None
        if len(self._spill_ledger) >= self._spill_max:
            self.spill_denied += 1
            metrics.count("vlm_spill_denied")
            return None
        faults.check(KV_SPILL, f"{self.name}:{idx}")
        # A row that attached a cached prefix does not need its shared
        # pages exported — they stay resident under the cache's (and this
        # record's) reference and re-attach on resume as a block-table
        # copy. Only the PRIVATE suffix crosses to host memory.
        rec, shared = self._export_state(idx, self.kv.shared_prefix_len(idx))
        if self._spill_bytes_live + rec.nbytes > self._spill_budget:
            self.spill_denied += 1
            metrics.count("vlm_spill_denied")
            return None
        # Pack into the one migration lease blob (the same frame train
        # fed_kv_put ships) and park it in the shm arena when the budget
        # allows; else keep the plain host-array leaves — same bytes
        # against the same ledger budget, just not recyclable segments.
        blob, crc = migration.pack_payload(rec.arrays)
        got = self._get_arena().acquire(len(blob))
        if got is not None:
            np.frombuffer(got.buf, np.uint8, count=len(blob))[:] = np.frombuffer(
                blob, np.uint8
            )
            rec.lease, rec.crc, rec.nbytes, rec.arrays = got, crc, len(blob), None
        else:
            self.spill_fallbacks += 1
            metrics.count("vlm_spill_fallbacks")
        # The record's hold on the shared prefix is taken LAST — every
        # failure/denial path above returns before this line, so a record
        # exists iff the incref happened and _drop_spill's decref always
        # balances it. The caller's kv.release(idx) then drops the row's
        # own references without freeing the prefix out from under us.
        if shared:
            self.kv.incref(shared)
            rec.shared_pages = list(shared)
        return rec

    def _park_spill(self, req: _Request, record: "_SpillRecord") -> None:
        req.spill = record
        self._spill_ledger[id(req)] = record
        self._spill_bytes_live += record.nbytes
        self.spills += 1
        metrics.count("vlm_spills")
        record_event(
            "vlm_spill", self.name,
            f"row spilled for resume: {record.n_pages} pages, "
            f"{len(record.tokens)} tokens parked",
            min_interval_s=1.0,
            pages=record.n_pages, bytes=record.nbytes,
            entries=len(self._spill_ledger),
        )
        self._requeue_front([req])

    def _drop_spill(self, req: _Request) -> "_SpillRecord | None":
        """Detach and free a request's spill record (lease back to the
        arena, bytes off the ledger). Idempotent — every retirement path
        calls it, so accounting balances at drain no matter which path a
        spilled request leaves through."""
        rec = getattr(req, "spill", None)
        if rec is None:
            return None
        req.spill = None
        self._spill_ledger.pop(id(req), None)
        self._spill_bytes_live -= rec.nbytes
        if rec.lease is not None:
            rec.lease.release()
            rec.lease = None
        rec.arrays = None
        if rec.shared_pages:
            self.kv.decref(rec.shared_pages)
            rec.shared_pages = []
        return rec

    def _drain_estimate_s(self) -> float:
        """Retry-after hint for :class:`PreemptionShed`: the soonest
        retire (min remaining budget across live rows) at the engine's
        EWMA per-token pace — the batcher's queue-drain hint, page-pool
        flavored. Pre-pace (no block run yet) falls back to a half
        second so the client backoff floor still has a number."""
        per_tok = self._block_s_ewma / max(self.block, 1)
        if per_tok <= 0.0:
            return 0.5
        remaining = min(
            (s.request.max_new - len(s.tokens) for s in self._slots.values()),
            default=self.block,
        )
        return per_tok * max(remaining, self.block)

    def _fail_preempted(self, req: _Request, cause: "BaseException | None") -> None:
        err = PreemptionShed(
            "preempted by KV pool exhaustion mid-stream and the spill tier "
            "could not park the row; a sampled stream cannot restart "
            "without splicing draws — retry after the pool drains"
        )
        err.retry_after_s = self._drain_estimate_s()
        if cause is not None:
            err.__cause__ = cause
        self.preempt_failed += 1
        metrics.count("vlm_preempt_failed")
        _fail(req, err)

    def _unpack_spill(self, rec: "_SpillRecord") -> list:
        """The record's payload leaves as host arrays safe to ship to the
        device. Lease views are COPIED out — the lease recycles right
        after resume, and a zero-copy transfer could still be reading its
        pages — after the crc gate turns a torn or recycled-out-from-
        under-us lease into a clean degradation instead of silent token
        corruption."""
        if rec.lease is None:
            if rec.arrays is None:
                raise RuntimeError("spill record has no payload (double resume?)")
            return list(rec.arrays)
        try:
            leaves = migration.unpack_payload(rec.lease.buf[: rec.nbytes], rec.crc)
        except ValueError as e:
            raise RuntimeError(f"spill lease rejected: {e}") from None
        return [leaf.copy() for leaf in leaves]

    def _resume_row(self, req: _Request) -> None:
        """Scatter a parked spill record into a fresh page grant and
        re-install the row — zero re-prefill; greedy continuation is
        token-identical, sampled continuation carries on its own stream.
        Failure anywhere degrades to the spill ladder (requeue-and-redo
        or typed shed); the ONLY re-raise is pool invalidation (the
        donation-based ``_resume`` consumed the pool's buffers before
        dying), which must reach the loop's fail-everything handler."""
        rec: _SpillRecord = req.spill
        slot = granted = None
        try:
            faults.check(KV_RESUME, f"{self.name}:resume")
            if req.migrate_in is not None:
                self._attach_migrate_shared(req, rec)
            leaves = self._unpack_spill(rec)
            payload = jax.tree.unflatten(rec.treedef, leaves)
            slot = self._free_slot()
            # Shared prefix pages re-attach by reference (admit_exact
            # increfs them ahead of the fresh grant); the scatter below
            # only rewrites the PRIVATE suffix, so the resumed table is
            # [shared… | scattered private…] — byte-identical history.
            bt_row = self.kv.admit_exact(
                slot, rec.n_pages, shared_pages=rec.shared_pages or None
            )
            granted = slot
            base = len(rec.shared_pages)
            ids = np.zeros((rec.n_pad,), np.int32)
            ids[: rec.n_pages] = bt_row[base : base + rec.n_pages]
            pages = jax.tree.map(jnp.asarray, payload["pages"])
            self.pool = self.gen._resume(
                self.pool, slot, pages, jnp.asarray(ids),
                jnp.asarray(payload["seen"]), rec.cur_tok, rec.cur_len,
                rec.n_gen, req.max_new, req.temperature, req.top_p,
                req.do_sample, req.repetition_penalty,
            )
        except PoolExhausted:
            # Lost a page race (lane reservation, same-turn admissions):
            # keep the record parked and try again next turn.
            self._requeue_front([req])
            return
        except Exception as e:  # noqa: BLE001 - degrade, never wedge the loop
            if self._pool_invalid():
                raise
            if granted is not None:
                self.kv.release(granted)
            logger.warning("KV resume failed (%s); degrading", e)
            self._drop_spill(req)
            if req.migrate_in is not None:
                # A migrated-in row has no local prompt to redo from —
                # refuse it; the PREFILL host owns the fallback ladder
                # and resumes the row from its own snapshot.
                req.migrate_in = None
                self.migrate_in_rejected += 1
                metrics.count("vlm_migrate_in_rejected")
                _fail(req, e)
            elif not (req.do_sample and req.delivered > 0):
                self.preempt_redone += 1
                metrics.count("vlm_preempt_redone")
                self._requeue_front([req])
            else:
                self._fail_preempted(req, e)
            return
        self._admit_seq += 1
        slot_state = _Slot(
            request=req, prompt_len=rec.prompt_len,
            seq=self._admit_seq, tokens=rec.tokens,
        )
        if self._spec_active():
            slot_state.text_toks = self._text_toks(req)
            slot_state.pending_tok = rec.cur_tok
        with self._cond:
            self._slots[slot] = slot_state
        self._count_admitted(req)
        self.spill_resumes += 1
        metrics.count("vlm_spill_resumes")
        self._drop_spill(req)
        if req.migrate_in is not None:
            keys, _ = req.migrate_in
            req.migrate_in = None
            self.migrated_in += 1
            metrics.count("vlm_migrated_in")
            if self.prefix is not None and keys:
                # The migrated prompt's pages are cacheable history HERE
                # too: later same-prefix migrations (and local requests)
                # resolve them by reference instead of riding the wire.
                pages = self.kv.owned_pages(slot)[: len(keys)]
                self.prefix.insert(keys[: len(pages)], pages)
        record_event(
            "vlm_resume", self.name,
            f"row resumed into slot {slot}: {rec.n_pages} pages "
            f"re-installed, {len(rec.tokens)} tokens already out",
            min_interval_s=1.0,
            pages=rec.n_pages, tokens=len(rec.tokens),
        )

    # -- KV page migration (disaggregated prefill/decode) --------------------

    def _wire_manifest(self, req: _Request, n: int) -> list:
        """Content-hash chain keys over the prompt's page-aligned prefix
        (capped one page short like the prefix cache's attach cap) — the
        offer leg's reference list. Empty when the request carries no
        content identity; the whole prompt then rides the wire."""
        if req.prefix_content is None:
            return []
        content = np.asarray(req.prefix_content)[:n]
        return chunk_keys(content, self.page_size)[: (n - 1) // self.page_size]

    def _migrate_sweep(self) -> None:
        """Hand freshly prefilled rows tagged for a decode-lane peer to
        the migration dispatcher: export through the spill codepath
        (shared prefix CONTENTS included — the peer may not hold them),
        release the slot, and let the dispatcher run the wire legs
        off-thread. Every failure re-enters via :meth:`resubmit_spilled`
        — the preemption ladder with the peer as one more flaky sink, so
        a dead decode host never loses or duplicates tokens."""
        for idx in list(self._slots):
            slot = self._slots.get(idx)
            if slot is None:
                continue
            req = slot.request
            if not req.migrate_to or slot.tokens or req.cancelled:
                continue
            target, req.migrate_to = req.migrate_to, None  # one attempt
            try:
                # All owned pages export by content (n_shared=0): the
                # record is self-contained; reference-vs-contents is the
                # DISPATCHER's call after the peer answers the offer.
                rec, _ = self._export_state(idx, 0)
            except Exception as e:  # noqa: BLE001 - decode locally instead
                logger.warning(
                    "KV migrate-out export of slot %d failed (%s); "
                    "decoding locally", idx, e,
                )
                continue
            rec.prompt_len = slot.prompt_len
            manifest = self._wire_manifest(req, slot.prompt_len)
            self.pool = dict(
                self.pool,
                done=self.pool["done"].at[jnp.asarray([idx], jnp.int32)].set(True),
            )
            with self._cond:
                self._slots.pop(idx, None)
            self.kv.release(idx)
            self.migrated_out += 1
            metrics.count("vlm_migrated_out")
            try:
                self.migrator(self, req, rec, manifest, target)
            except Exception as e:  # noqa: BLE001 - ladder, not a loss
                logger.warning(
                    "KV migration dispatch to %s failed (%s); resuming "
                    "locally", target, e,
                )
                self.resubmit_spilled(req, rec)

    def resubmit_spilled(self, req: _Request, rec: _SpillRecord) -> None:
        """Thread-safe re-entry for a migration that failed before or
        mid-stream: park the record as a spill and resume locally with
        zero re-prefill (greedy replays are token-identical and the
        ``delivered`` counter suppresses any already-streamed prefix).
        A sampled row whose peer already streamed past the snapshot
        cannot resume without splicing draws — it sheds with the typed
        retryable error, exactly the preemption ladder."""
        self.migrate_out_failed += 1
        metrics.count("vlm_migrate_fallbacks")
        with self._cond:
            closed = self._closed
        if closed:
            _fail(req, RuntimeError("continuous scheduler is closed"))
            return
        if req.do_sample and req.delivered > rec.n_gen:
            self._fail_preempted(req, None)
            return
        req.spill = rec
        req.t_queued = time.perf_counter()  # back to the queue: a new wait
        self._spill_ledger[id(req)] = rec
        self._spill_bytes_live += rec.nbytes
        self._requeue_front([req])
        with self._cond:
            self._cond.notify()

    def submit_migrated(
        self, req: _Request, rec: _SpillRecord, manifest: list, n_shared: int
    ) -> None:
        """Decode-host entry for a ``fed_kv_put`` commit: park the wire
        record as a parked spill and queue the request — the ordinary
        resume path then re-installs the row with ZERO re-prefill device
        work. ``manifest``/``n_shared`` defer shared-prefix resolution
        to the loop thread (the prefix cache is loop-owned); a lost
        race fails the request with :class:`migration.ChunksMissing`,
        which the wire handler maps to a retryable refusal."""
        self.rows.refuse("admitting a migrated row")
        need = rec.cur_len + max(int(req.max_new) - rec.n_gen, 0) + 1
        if not self.kv.fits(need):
            raise ValueError(
                f"migrated row needs {need} KV tokens but this pool holds "
                f"at most {min(self.kv.row_capacity(), (self.kv.pages_total - 1) * self.kv.page_size)} "
                "per row"
            )
        req.spill = rec
        req.migrate_in = (list(manifest), int(n_shared))
        if req.trace is None:
            req.trace = current_trace()
        with self._cond:
            if self._closed:
                raise RuntimeError("continuous scheduler is closed")
            self._spill_ledger[id(req)] = rec
            self._spill_bytes_live += rec.nbytes
            self._stamp_submit(req)
            self._pending.append(req)
            self._cond.notify()

    def _attach_migrate_shared(self, req: _Request, rec: _SpillRecord) -> None:
        """Resolve a migrated-in row's shared-prefix references against
        the LOCAL prefix cache (loop thread — authoritative, unlike the
        offer leg's advisory peek) and take the record's hold on them.
        Idempotent across page-race requeues: once ``shared_pages`` is
        set the references are held and re-resolution would double-count."""
        keys, n_shared = req.migrate_in
        if n_shared <= 0 or rec.shared_pages:
            return
        got = self.prefix.lookup(keys[:n_shared]) if self.prefix is not None else []
        if len(got) < n_shared:
            raise migration.ChunksMissing(
                f"offer promised {n_shared} cached prefix pages but only "
                f"{len(got)} survive (evicted since the offer)"
            )
        got = got[:n_shared]
        self.kv.incref(got)
        rec.shared_pages = list(got)

    def _row_need(self, slot: "_Slot", horizon: "int | None" = None) -> int:
        """KV tokens a row needs covered before the next block: the
        block's writes (or a speculative verify turn's ``horizon``),
        clamped to the row's own budget (it stops at ``max_new``) and to
        what a block table can address (a row at capacity keeps
        overwriting its clamped last slot — matching the decode program's
        position clamp). Without the clamps, a feasible request ending
        within ``block`` tokens of the pool bound would ask for pages
        past the table and crash the loop."""
        return min(
            slot.prompt_len + len(slot.tokens) + (horizon or self.block),
            slot.prompt_len + slot.request.max_new + 1,
            self.kv.row_capacity(),
        )

    def _ensure_growth(self, horizon: "int | None" = None) -> None:
        """Before a block, every live row's pages must cover the next
        block's writes; cached prefixes yield first (reclaim), then the
        newest rows are preempted until the free list can satisfy the
        rest. A lone row always fits — submit() checked feasibility
        against the whole pool, and any unreclaimable cache page a lone
        row's growth could collide with is, by construction, already in
        that row's own block table (shared prefix pages never grow).

        Growth into a SHARED frontier page would trigger copy-on-write
        inside the pool; the engine's admission paths cap prefix
        attachment one token short of the prompt, so the write frontier
        is always private and a CoW here means an allocator invariant
        broke — surfaced loudly rather than silently remapped."""
        cow: list = []
        for idx in sorted(self._slots, key=lambda i: self._slots[i].seq):
            slot = self._slots.get(idx)
            if slot is None:
                continue
            need = self._row_need(slot, horizon)
            while not self.kv.grow(idx, need, cow):
                if self.prefix is not None and self.prefix.reclaim(1):
                    continue
                if not self._preempt_newest(protect=idx):
                    raise RuntimeError(
                        "paged pool cannot grow a lone row (feasibility bug)"
                    )
                if idx not in self._slots:  # we preempted ourselves? never
                    break
        if cow:
            raise RuntimeError(
                f"unexpected copy-on-write during decode growth: {cow} "
                "(prefix attachment must leave the write frontier private)"
            )

    # -- speculative decoding -----------------------------------------------

    def _spec_active(self) -> bool:
        return self.spec_k > 0 and not self.spec_disabled

    def _draft_row(self, slot: "_Slot") -> list[int]:
        """Prompt-lookup draft for one row: the longest recent n-gram
        (``spec_ngram`` down to 1) whose suffix matches the row's current
        tail is replayed for up to ``spec_k`` tokens. No draft model —
        the prompt plus the row's own output IS the drafter, which is
        exactly the traffic (templates, citations, repetitive captions)
        speculative decoding pays off on. Greedy rows only: verification
        is token-identity against argmax; a sampled row would need draw
        matching the verify program does not implement."""
        req = slot.request
        if req.do_sample or slot.pending_tok is None or slot.text_toks is None:
            return []
        ctx = slot.text_toks + slot.tokens + [slot.pending_tok]
        for n in range(min(self.spec_ngram, len(ctx) - 1), 0, -1):
            pat = ctx[-n:]
            # EARLIEST occurrence: on cycling/template text every match
            # continues identically, and the earliest one has the most
            # room before it runs into the tail being drafted.
            for start in range(len(ctx) - n):
                if ctx[start : start + n] == pat:
                    return ctx[start + n : start + n + self.spec_k]
        return []

    def _spec_try_disable(self) -> None:
        """Permanent auto-off once acceptance proves the traffic wrong:
        below ``LUMEN_VLM_SPEC_MIN_RATE`` after a fair sample every
        verify turn is pure overhead (drafting, wider attention) with no
        accepted tokens to show for it — same autopilot posture as the
        q8 route's calibration gate."""
        if self.spec_disabled or self.spec_proposed < 64:
            return
        if self.spec_accepted < self.spec_min_rate * self.spec_proposed:
            self.spec_disabled = True
            logger.warning(
                "speculative decoding disabled: acceptance %d/%d below floor %.2f",
                self.spec_accepted, self.spec_proposed, self.spec_min_rate,
            )

    def _run_block(self) -> None:
        step = self.blocks_run + 1  # the number this block's phases and spans carry
        with phase("vlm.block.prepare", step=step):
            if self._retire_cancelled():
                return
            width, drafts, bucket = self._plan_block()
        active = len(self._slots)
        # The device decides it anew in every step, over the rows still live
        # there: a subset of these, so a block counted greedy sorts nothing.
        live = [s.request for s in self._slots.values()]
        sampling = bool(
            draws([r.do_sample for r in live], [r.temperature for r in live], np).any()
        )
        t0 = time.perf_counter()
        tm0 = time.monotonic()
        with phase(
            "vlm.block.dispatch", step=step, rows=active, bucket=bucket, sampling=int(sampling)
        ):
            if width:
                q = np.zeros((self.n_slots, width), np.int32)
                ql = np.ones((self.n_slots,), np.int32)
                for i, d in drafts.items():
                    q[i, 1 : 1 + len(d)] = d
                    ql[i] = 1 + len(d)
                self.pool, self._rng, toks = self.gen._verify(
                    self.params, self.pool,
                    jnp.asarray(self.kv.block_tables[:, :bucket]),
                    self._rng, jnp.asarray(q), jnp.asarray(ql), width=width,
                )
                self.spec_turns += 1
            else:
                ql = None
                self.pool, self._rng, toks = self.gen._step_block(
                    self.params, self.pool,
                    jnp.asarray(self.kv.device_tables(bucket)),
                    self._rng, block=self.block,
                )
                if self._indexer_layers:
                    self._count_indexer(self.n_slots * self.block, bucket * self.page_size)
        self.blocks_run += 1
        self.blocks_greedy += not sampling
        self._occ_rows += active
        self._occ_blocks += 1
        if self._latent_keys_cap:
            self.latent_keys_sum += sum(
                min(s.prompt_len + len(s.tokens) + 1, self._latent_keys_cap) for s in self._slots.values()
            )
        # One fused device->host transfer for everything the bookkeeping
        # below needs (four separate np.asarray calls = four round trips
        # on the per-block hot path). cur_tok rides along ONLY when
        # speculation is configured — the unconfigured transfer is
        # byte-identical to the non-speculative build.
        with phase("vlm.block.fetch", step=step):
            if self.spec_k > 0:
                toks_np, n_gen, done, eos, cur_tok = jax.device_get(
                    (
                        toks, self.pool["n_gen"], self.pool["done"],
                        self.pool["eos"], self.pool["cur_tok"],
                    )
                )
            elif self.gen._counts_experts:
                cur_tok = None
                toks_np, n_gen, done, eos, moe_block = jax.device_get(
                    (toks, self.pool["n_gen"], self.pool["done"], self.pool["eos"],
                     self.pool["moe_block"])
                )
                self.moe_stats += moe_block  # int64 here; the device's int32 is one block's
            else:
                cur_tok = None
                toks_np, n_gen, done, eos = jax.device_get(
                    (toks, self.pool["n_gen"], self.pool["done"], self.pool["eos"])
                )
        t1 = time.perf_counter()
        # Decode pace for the PreemptionShed drain hint (first block seeds
        # the EWMA; compile-heavy first blocks wash out within a few).
        dt = t1 - t0
        if self._step_floor_s > 0.0:
            # Pace BEFORE tokens stream out so first-token latency pays
            # the floor too — a paced block models a slower chip, not a
            # faster chip with delayed bookkeeping.
            lag = self.block * self._step_floor_s - dt
            if lag > 0.0:
                time.sleep(lag)
                dt = time.perf_counter() - t0
        self._block_s_ewma = (
            dt if self._block_s_ewma == 0.0 else 0.8 * self._block_s_ewma + 0.2 * dt
        )
        # Duty credit covers the paced window too: a step floor models a
        # slower chip, and the duty meter should describe that chip.
        telemetry.busy(f"device:{self.name}", tm0, time.monotonic())
        with phase("vlm.block.emit", step=step, rows=active):
            self._emit_block(
                step, active, t0, t1, width, ql, toks_np, n_gen, done, eos, cur_tok
            )

    def _retire_cancelled(self) -> bool:
        """Retire the rows whose stream consumer went away; True when no
        live row is left to step."""
        cancelled = [
            i for i, slot in self._slots.items() if slot.request.cancelled
        ]
        if cancelled:
            idx = jnp.asarray(cancelled, jnp.int32)
            self.pool = dict(self.pool, done=self.pool["done"].at[idx].set(True))
            for i in cancelled:
                with self._cond:
                    slot = self._slots.pop(i)
                self.kv.release(i)
                _retire(slot.request, slot.tokens, eos=False)
        return not self._slots

    def _plan_block(self) -> tuple[int, dict[int, list[int]], int]:
        """Drafts, page growth and the block-table bucket of the next
        block: ``(verify width or 0, drafts by slot, table bucket)``."""
        # A verify turn runs only when some row drafted AND every live
        # row's window fits its table capacity — the verify program's
        # position clamp must never engage on a live row (it would
        # overwrite history; rows that near the edge finish on plain
        # blocks whose per-step clamp matches the non-speculative path).
        width = 0
        drafts: dict[int, list[int]] = {}
        if self._spec_active():
            cap = self.kv.row_capacity()
            if all(
                s.prompt_len + len(s.tokens) + self.spec_k + 1 <= cap
                for s in self._slots.values()
            ):
                drafts = {
                    i: d for i, s in self._slots.items() if (d := self._draft_row(s))
                }
                if drafts:
                    width = self.spec_k + 1
        if self.kv.window is not None:
            for idx, slot in self._slots.items():
                self.kv.window.trim(idx, slot.prompt_len + len(slot.tokens))
        self._ensure_growth(horizon=width or None)
        # Growth may have preempted a drafted row; verify only helps if a
        # surviving row still carries a draft.
        if width:
            drafts = {i: d for i, d in drafts.items() if i in self._slots}
            if not drafts:
                width = 0
        # Ragged page bucketing: ship only a power-of-2 prefix of the
        # block tables covering the longest live row. The CPU reference
        # gathers every table entry it is given, so a pool of short
        # generations must not pay max_seq worth of gather per step (the
        # page-granular twin of attention_cached's ragged KV ladder);
        # bucketing keeps compiled step shapes at log2(max_pages).
        maxp_live = max(
            (
                self.kv.pages_for(self._row_need(s, width or None))
                for s in self._slots.values()
            ),
            default=1,
        )
        bucket = 1
        while bucket < maxp_live:
            bucket *= 2
        if bucket * 2 > self.kv.max_pages:
            # the ladder's last rung is the whole table: where max_pages is
            # no power of two (4,608 tokens of 64: 72) the rung under it
            # would be a second program for the same rows
            bucket = self.kv.max_pages
        return width, drafts, bucket

    def _emit_block(
        self, step, active, t0, t1, width, ql, toks_np, n_gen, done, eos, cur_tok
    ) -> None:
        """A block's bookkeeping: spans, acceptance tallies, tokens out to
        the streams, finished rows retired."""
        span_meta = {
            "step": step,
            "rows": active,
            "fill_pct": round(100.0 * active / self.n_slots, 1),
            "block": self.block,
        }
        for idx in list(self._slots):
            slot = self._slots[idx]
            req = slot.request
            if req.trace is not None:
                req.trace.add_span("batch.device", t0, t1, dict(span_meta, rid=req.rid))
            new = int(n_gen[idx]) - len(slot.tokens)
            if width and int(ql[idx]) > 1:
                # First emission of a verify turn is the pending token
                # (not a draft); acceptance counts only the drafted tail.
                prop = int(ql[idx]) - 1
                acc = max(min(new - 1, prop), 0)
                self.spec_proposed += prop
                self.spec_accepted += acc
                req.spec_proposed += prop
                req.spec_accepted += acc
                metrics.count("vlm_spec_proposed", prop)
                metrics.count("vlm_spec_accepted", acc)
            if cur_tok is not None:
                slot.pending_tok = int(cur_tok[idx])
            if new > 0:
                slot.tokens.extend(int(t) for t in toks_np[idx, :new])
                if req.stream_q is not None:
                    for t in slot.tokens[req.delivered :]:
                        req.stream_q.put(t)
                    # max(): after a failed migration the remote relay
                    # has already delivered PAST this replay's position —
                    # moving the watermark backward would re-emit every
                    # token from here to the crash point as duplicates.
                    req.delivered = max(req.delivered, len(slot.tokens))
                if not req.t_first_token:
                    req.t_first_token = time.perf_counter()
                    self.first_token_ms_sum += (req.t_first_token - req.t_submit) * 1e3
                    self.first_token_count += 1
            if done[idx]:
                with self._cond:
                    del self._slots[idx]
                self.kv.release(idx)
                _retire(req, slot.tokens, bool(eos[idx]))
        if width:
            self._spec_try_disable()
