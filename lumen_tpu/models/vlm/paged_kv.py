"""Paged KV pool for continuous VLM decode: host-side page accounting.

The slot-era continuous scheduler gave every decode row a contiguous
``max_seq`` KV region, so an 8-slot pool paid ``8 x max_seq`` of HBM no
matter how short the generations were, and admission needed a same-shape
bucket. Here KV lives in fixed-size PAGES drawn from one shared pool
(device arrays: ``[num_pages, kv_heads, page_size, head_dim]`` per layer,
see ``generate.Generator.init_pool``); each sequence owns a BLOCK TABLE of
page ids that grows a page at a time as decode crosses page boundaries and
is returned to the free list at retire. Long and short generations share
the pool, and a request admits the moment a slot and its prompt's pages
are free — the Ragged Paged Attention recipe (PAPERS.md, arxiv 2604.15464)
with the O(1)-per-step cache discipline of arxiv 2603.09555 kept portable:
the same block tables drive the Pallas kernel on TPU and the exact XLA
gather reference on CPU (``ops.attention.paged_attention``).

This module is the HOST half: the free list, per-slot block tables, and
the allocated/freed/live accounting the bench asserts balances at drain.
Device-side page contents are owned by the scheduler's pool dict and only
ever addressed through these tables.

Page 0 is reserved as the DUMP page: unused block-table entries point at
it so device-side scatters always have a safe target (free rows and the
padded tail of a prompt scatter write garbage there; nothing ever reads
it back — attention masks by per-row length).

Pages are REFCOUNTED so block tables can share physical pages: the prefix
cache attaches a hot prompt prefix to a new row as a block-table copy
(``admit_shared``), every holder — rows, the prefix cache, parked spill
records — owns one reference, and a page returns to the free list only
when its last reference drops. Accounting is reference-granular: every
reference grant is one ``allocated_total`` tick and every drop one
``freed_total`` tick, so the balance-at-drain invariant (live == 0,
allocated == freed) survives sharing unchanged. Appending into a shared
page is a copy-on-write: ``grow`` swaps a fresh page into the frontier
slot and hands the (old, new) pair back so the caller can device-copy the
contents — by construction the engine never hits this (shared prefixes
are page-aligned and at least the prompt's last token always prefills
into a private page), but the allocator stays safe for any caller.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass

import numpy as np

logger = logging.getLogger(__name__)

#: default tokens per page, for a grouped-query pool ([page, head_dim] tiles
#: per KV head) and a latent one ([page, latent], shared by all heads) alike.
#: The decode kernels visit a page a grid step, and a grid step costs
#: 0.23-0.28 us whether it holds 16 keys or 64: at 16 tokens a Qwen2-1.5B
#: step moved 4 KB and the kernel's time was its step count (1,024 a layer
#: call, 0.24 ms, 1.6% of its roofline, a third of the decode step; at 64,
#: 256 steps and 0.07 ms; PERF.md section 6, PR 35). 64 is a multiple of
#: the bf16 sublane tile, divides every prompt scratch length the ladder
#: makes (320 = 5 pages), and wastes under a page per row against prompts
#: in the hundreds.
DEFAULT_PAGE_SIZE = 64

#: fraction of free HBM the pool may claim when sized from device stats.
DEFAULT_HEADROOM_FRACTION = 0.6


class PoolExhausted(RuntimeError):
    """Raised by :meth:`PagedKVPool.grow` callers that cannot free pages
    (the scheduler catches this and preempts a row instead)."""


@dataclass
class PageStats:
    pages_total: int
    page_size: int
    pages_free: int
    pages_live: int
    allocated_total: int  # cumulative reference grants since boot
    freed_total: int  # cumulative reference drops since boot
    pages_held: int = 0  # physical pages out of the free list
    pages_shared: int = 0  # physical pages with more than one reference


class WindowPages:
    """The window layers' pages of a latent decoder: an id space of their
    own beside the pool's, under the same logical page numbers.

    A window layer reads only the last ``window`` keys of a row, so a page
    wholly behind the window is dead: :meth:`trim` returns it to this free
    list (at install after prefill and as the row advances) and points its
    table entry back at the dump page 0, while the full layers keep theirs.
    A row therefore never holds more than :meth:`row_pages` of them, the
    space is sized for every slot holding that many (:func:`window_pool_pages`)
    and cannot run dry: admission and preemption go on gating on the full
    layers' pages alone. Owned by :class:`PagedKVPool`, which keeps the two
    id spaces in step.
    """

    def __init__(self, pages_total: int, page_size: int, slots: int, max_pages: int, window: int):
        self.pages_total = pages_total
        self.page_size = page_size
        self.window = window
        self._free = list(range(pages_total - 1, 0, -1))
        self._first: dict[int, int] = {}  # slot -> first live logical page
        self._count: dict[int, int] = {}  # slot -> logical pages granted so far
        self.tables = np.zeros((slots, max_pages), np.int32)
        #: pages let go behind a window: at install (never granted) and as
        #: rows advance (returned by ``trim``); not those a retiring row returns
        self.freed_behind = 0

    @staticmethod
    def row_pages(window: int, page_size: int, block: int) -> int:
        """Most pages one row can hold: the window at its worst alignment,
        grown by a decode block before the next trim."""
        return (window + block + page_size - 2) // page_size + 1

    @property
    def pages_live(self) -> int:
        return self.pages_total - 1 - len(self._free)

    def first_live(self, length: int) -> int:
        """First logical page the next query (position ``length``) can see."""
        return max(0, (length - self.window + 1) // self.page_size)

    def cover(self, slot: int, tokens: int) -> None:
        """Grant pages so the row's table covers ``tokens`` positions."""
        need = min(max(1, -(-int(tokens) // self.page_size)), self.tables.shape[1])
        count = self._count.get(slot, 0)
        self._first.setdefault(slot, 0)
        while count < need:
            if not self._free:
                raise RuntimeError("window page space ran dry (it is sized so that it cannot)")
            self.tables[slot, count] = self._free.pop()
            count += 1
        self._count[slot] = count

    def install(self, slot: int, prompt_tokens: int) -> None:
        """A prefilled row: pages for the window's tail of the prompt and
        the first decode write; those behind the window are never granted."""
        behind = self.first_live(prompt_tokens)
        self._first[slot] = self._count[slot] = behind
        self.freed_behind += behind  # the scratch held them; the pool never does
        self.cover(slot, prompt_tokens + 1)

    def trim(self, slot: int, length: int) -> int:
        """Free the row's pages wholly behind the window of a query at
        position ``length``; returns how many."""
        first, live = self._first.get(slot, 0), self.first_live(length)
        live = min(live, self._count.get(slot, 0))
        for j in range(first, live):
            self._free.append(int(self.tables[slot, j]))
            self.tables[slot, j] = 0
        if live > first:
            self._first[slot] = live
            self.freed_behind += live - first
        return max(0, live - first)

    def release(self, slot: int) -> None:
        for j in range(self._first.pop(slot, 0), self._count.pop(slot, 0)):
            self._free.append(int(self.tables[slot, j]))
        self.tables[slot] = 0


class PagedKVPool:
    """Free-list page allocator with per-slot block tables.

    NOT thread-safe: the continuous scheduler owns it from its single
    loop thread. ``block_tables`` is the numpy source of truth shipped to
    the device programs each dispatch (a [slots, max_pages] int32 is a
    few hundred bytes — re-uploading per block is noise next to a decode
    step).
    """

    def __init__(
        self, pages_total: int, page_size: int, slots: int, max_pages: int,
        window: WindowPages | None = None,
    ):
        #: a latent decoder's window layers (None: every layer keeps every page)
        self.window = window
        if pages_total < 2:
            raise ValueError(f"pages_total must be >= 2 (page 0 is the dump page), got {pages_total}")
        self.pages_total = pages_total
        self.page_size = page_size
        self.max_pages = max_pages
        # LIFO free list: hot pages are reused first (their HBM lines are
        # the most recently touched). Page 0 is never in the list.
        self._free = list(range(pages_total - 1, 0, -1))
        self._owned: dict[int, list[int]] = {}  # slot -> owned page ids
        # page id -> outstanding references, for every page out of the
        # free list. A slot's grant, a prefix-cache entry and a parked
        # spill record each hold ONE reference; the page is physically
        # freed when the count hits zero.
        self._ref: dict[int, int] = {}
        # slot -> how many LEADING pages of its grant were attached from
        # a shared prefix (never written by this row; the spill tier must
        # not export them and decode never lands a write in them).
        self._shared: dict[int, int] = {}
        self.block_tables = np.zeros((slots, max_pages), np.int32)
        self.allocated_total = 0
        self.freed_total = 0

    # -- queries -----------------------------------------------------------

    @property
    def pages_free(self) -> int:
        return len(self._free)

    @property
    def pages_live(self) -> int:
        return self.allocated_total - self.freed_total

    def pages_for(self, tokens: int) -> int:
        """Pages needed to hold ``tokens`` KV slots."""
        return max(1, -(-int(tokens) // self.page_size))

    def row_capacity(self) -> int:
        """Max tokens one row's block table can address."""
        return self.max_pages * self.page_size

    def fits(self, tokens: int) -> bool:
        """Feasibility: could ``tokens`` EVER fit (full pool, one row)?
        Admission must reject what can never run; mid-flight shortage is
        handled by preemption instead."""
        return tokens <= self.row_capacity() and self.pages_for(tokens) <= self.pages_total - 1

    def can_admit(self, tokens: int, shared_pages: int = 0) -> bool:
        """Are enough pages free RIGHT NOW for a prompt of ``tokens``
        (plus the first decode write)? ``shared_pages`` leading pages
        attached from the prefix cache need no fresh grant."""
        return self.pages_for(tokens + 1) - shared_pages <= len(self._free)

    def refcount(self, page: int) -> int:
        """Outstanding references on ``page`` (0 = free / dump page)."""
        return self._ref.get(page, 0)

    def shared_prefix_len(self, slot: int) -> int:
        """How many leading pages of the slot's grant are attached shared
        prefix (read-only for this row)."""
        return self._shared.get(slot, 0)

    # -- transitions -------------------------------------------------------

    def _pop_fresh(self, n: int) -> list[int]:
        """Pop ``n`` fresh pages (one reference each, counted)."""
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._ref[p] = 1
        self.allocated_total += n
        return pages

    def incref(self, pages: list[int]) -> None:
        """Take one additional reference on each page (a new holder —
        a sharing row, the prefix cache, or a parked spill record).
        Reference-granular accounting: each grant is an allocation."""
        for p in pages:
            ref = self._ref.get(p)
            if not ref:
                raise RuntimeError(f"incref of free page {p} (allocator bug)")
            self._ref[p] = ref + 1
        self.allocated_total += len(pages)

    def decref(self, pages: list[int]) -> int:
        """Drop one reference per page; pages reaching zero return to the
        free list. Returns how many pages were physically freed."""
        freed = 0
        for p in pages:
            ref = self._ref.get(p)
            if not ref:
                raise RuntimeError(f"decref of free page {p} (double free)")
            if ref > 1:
                self._ref[p] = ref - 1
            else:
                del self._ref[p]
                self._free.append(p)
                freed += 1
        self.freed_total += len(pages)
        return freed

    def _install(self, slot: int, pages: list[int], shared: int) -> np.ndarray:
        row = np.zeros((self.max_pages,), np.int32)
        row[: len(pages)] = pages
        self.block_tables[slot] = row
        self._owned[slot] = pages
        if shared:
            self._shared[slot] = shared
        return self.block_tables[slot]

    def device_tables(self, bucket: int) -> np.ndarray:
        """What a decode program is given: the first ``bucket`` table
        entries of every slot, ``[slots, bucket]``; with window layers the
        full layers' table and theirs, ``[slots, 2, bucket]``."""
        if self.window is None:
            return self.block_tables[:, :bucket]
        return np.stack([self.block_tables[:, :bucket], self.window.tables[:, :bucket]], axis=1)

    def admit(self, slot: int, prompt_tokens: int) -> np.ndarray:
        """Grant pages covering ``prompt_tokens`` + the first decode write
        and install the slot's block table row. Returns the row (view)."""
        if slot in self._owned:
            raise RuntimeError(f"slot {slot} already owns pages (allocator bug)")
        need = self.pages_for(prompt_tokens + 1)
        if need > len(self._free):
            raise PoolExhausted(f"need {need} pages, {len(self._free)} free")
        if self.window is not None:
            self.window.install(slot, prompt_tokens)
        return self._install(slot, self._pop_fresh(need), 0)

    def admit_shared(
        self, slot: int, shared_pages: list[int], prompt_tokens: int
    ) -> np.ndarray:
        """Prefix-cache hit admission: attach ``shared_pages`` (one new
        reference each — their contents are the page-aligned prompt
        prefix, already resident) as the row's leading pages and grant
        fresh pages for the rest of the prompt + the first decode write.
        The shared prefix is strictly shorter than the prompt (the hit
        path caps coverage at ``prompt_tokens - 1``), so the write
        frontier always lands in a private page and the row never
        mutates shared contents."""
        if self.window is not None:
            raise NotImplementedError("a shared prefix cannot be attached to window layers")
        if slot in self._owned:
            raise RuntimeError(f"slot {slot} already owns pages (allocator bug)")
        if len(shared_pages) * self.page_size > prompt_tokens:
            raise ValueError("shared prefix covers the whole prompt (hit-path bug)")
        need = self.pages_for(prompt_tokens + 1)
        fresh_need = need - len(shared_pages)
        if fresh_need < 1:
            raise ValueError("shared prefix leaves no private frontier page")
        if fresh_need > len(self._free):
            raise PoolExhausted(f"need {fresh_need} pages, {len(self._free)} free")
        self.incref(shared_pages)
        pages = list(shared_pages) + self._pop_fresh(fresh_need)
        return self._install(slot, pages, len(shared_pages))

    def owned_pages(self, slot: int) -> list[int]:
        """The slot's owned page ids in block-table order (grant order) —
        the spill tier exports page contents in exactly this order so a
        resume can re-install them into a fresh grant positionally."""
        return list(self._owned.get(slot, ()))

    def admit_exact(
        self, slot: int, n_pages: int, shared_pages: list[int] | None = None
    ) -> np.ndarray:
        """Grant exactly ``n_pages`` fresh pages and install the slot's
        block table row — the resume half of the spill tier, where the
        page count is the victim's exported PRIVATE grant, not a prompt
        length. ``shared_pages`` (a spilled row's shared prefix, kept
        alive by the spill record's reference) are re-attached ahead of
        the fresh grant. Returns the row (view); same accounting as
        :meth:`admit`."""
        shared = list(shared_pages or ())
        if self.window is not None:
            raise NotImplementedError("the spill tier does not export window layers' pages")
        if slot in self._owned:
            raise RuntimeError(f"slot {slot} already owns pages (allocator bug)")
        if not 1 <= n_pages <= self.max_pages - len(shared):
            raise ValueError(
                f"resume grant of {n_pages} pages outside [1, {self.max_pages - len(shared)}]"
            )
        if n_pages > len(self._free):
            raise PoolExhausted(f"need {n_pages} pages, {len(self._free)} free")
        self.incref(shared)
        pages = shared + self._pop_fresh(n_pages)
        return self._install(slot, pages, len(shared))

    def grow(self, slot: int, tokens: int, cow_out: list | None = None) -> bool:
        """Ensure the slot's pages cover ``tokens`` KV slots; allocate as
        needed. False when the free list runs dry mid-growth (partial
        grants stand — accounting stays balanced; the caller preempts a
        row and retries). ``tokens`` beyond the block table's reach clamp
        to ``row_capacity()`` — the decode program clamps its writes the
        same way, so a full row keeps overwriting its last slot instead
        of the allocator indexing past the table.

        Copy-on-write: growth means the caller is about to APPEND into
        the current frontier page; if that page is shared (refcount > 1),
        it is swapped for a fresh private page first and the ``(old,
        new)`` id pair appended to ``cow_out`` so the caller can
        device-copy the contents before writing. The engine's page-
        aligned prefix sharing never triggers this (the frontier is
        always private by construction) — a trigger with no ``cow_out``
        to report through is therefore an allocator-contract bug."""
        pages = self._owned[slot]
        need = min(self.pages_for(tokens), self.max_pages)
        if self.window is not None:
            self.window.cover(slot, tokens)
        if need > len(pages) and pages and self._ref.get(pages[-1], 0) > 1:
            if not self._free:
                return False
            old = pages[-1]
            new = self._pop_fresh(1)[0]
            pages[-1] = new
            self.block_tables[slot, len(pages) - 1] = new
            self.decref([old])
            if self._shared.get(slot, 0) >= len(pages):
                self._shared[slot] = len(pages) - 1
            if cow_out is None:
                raise RuntimeError(
                    f"copy-on-write of shared frontier page {old} with no "
                    "copy sink (allocator-contract bug)"
                )
            cow_out.append((old, new))
        while len(pages) < need:
            if not self._free:
                return False
            page = self._pop_fresh(1)[0]
            self.block_tables[slot, len(pages)] = page
            pages.append(page)
        return True

    def release(self, slot: int) -> int:
        """Drop a retired slot's reference on each of its pages (last
        holder returns them to the free list); the block-table row resets
        to the dump page. Returns the reference count dropped."""
        pages = self._owned.pop(slot, [])
        self._shared.pop(slot, None)
        self.block_tables[slot] = 0
        if self.window is not None:
            self.window.release(slot)
        # Reversed: the row's FIRST page ends on top of the LIFO free
        # list, preserving the pre-refcount reuse order exactly.
        self.decref(list(reversed(pages)))
        return len(pages)

    def stats(self) -> PageStats:
        return PageStats(
            pages_total=self.pages_total,
            page_size=self.page_size,
            pages_free=len(self._free),
            pages_live=self.pages_live,
            allocated_total=self.allocated_total,
            freed_total=self.freed_total,
            pages_held=len(self._ref),
            pages_shared=sum(1 for r in self._ref.values() if r > 1),
        )


class RowState:
    """What one decode row keeps in each layer of a decoder, and what that
    allows: the one description the pool's sizing, the admit program and the
    scheduler's refusals ask.

    A layer's row state is one of three kinds: **paged K/V** (a grouped-query
    layer: a ``[page, head_dim]`` K and V tile a KV head under every page id
    of the row's block table), **latent pages** (a latent layer: one
    ``[page, latent + rope (+ index key)]`` tile; the full layers share the
    pool's id space, the window layers keep one of their own), or
    **recurrent** (a Mamba layer: a convolution tail and a scan state a SLOT,
    whatever the row's length; no page at all). ``kinds`` names each layer's.

    Only a row of paged K/V alone can be shared or exported whole today
    (prefix cache, spill tier, speculative verify, migration): window layers
    free the pages a shared prefix would need, a recurrent state is not a
    function of a page-aligned prefix that a later row could attach (it
    would need a snapshot at every page boundary), and a latent decoder
    without window layers keeps every page of a row, so nothing of principle
    stands in its way: its ``c`` / ``r`` (/ ``ik``) leaves are simply not
    what the prefix cache's seeding, the spill tier's arena or the
    migration wire format read and write yet. :meth:`refuse` is the one place
    that says so."""

    PAGED, LATENT, WINDOW, RECURRENT = "paged_kv", "latent_pages", "latent_window_pages", "recurrent"

    def __init__(self, cfg):
        from .modeling import FULL_ATTENTION, MAMBA, WINDOW_ATTENTION

        d = self.decoder = cfg.decoder
        by_type = {FULL_ATTENTION: self.LATENT, WINDOW_ATTENTION: self.WINDOW, MAMBA: self.RECURRENT}
        self.kinds = tuple(by_type.get(d.layer_kind(i), self.PAGED) for i in range(d.layers))

    def layers_of(self, kind: str) -> int:
        return self.kinds.count(kind)

    @property
    def window_layers(self) -> int:
        return self.layers_of(self.WINDOW)

    @property
    def full_latent_layers(self) -> int:
        """Latent layers that keep every page of a row."""
        return self.layers_of(self.LATENT)

    @property
    def indexer_layers(self) -> int:
        """Full latent layers whose indexer scores every causal key and keeps
        an index key a token (none where the decoder has no indexer)."""
        return self.full_latent_layers if self.decoder.indexer else 0

    @property
    def state_layers(self) -> int:
        return self.layers_of(self.RECURRENT)

    @property
    def shareable(self) -> bool:
        """Whether a row's state can be attached to another row or exported."""
        return all(k == self.PAGED for k in self.kinds)

    def refuse(self, what: str) -> None:
        """Raise unless rows can be shared or exported (``what`` needs it)."""
        if self.shareable:
            return
        if self.window_layers:
            held, why = "latent", "window layers free the pages a shared prefix would need"
        elif self.full_latent_layers:
            held, why = "latent", (
                "a row keeps all its latent pages, but the prefix cache, the spill tier and the "
                "migration wire format do not read or write latent leaves yet"
            )
        else:
            held, why = "recurrent", (
                "a row's recurrent state is no function of its pages: sharing or exporting it needs a snapshot"
            )
        raise NotImplementedError(f"{what} is not implemented for a {held} decoder ({why})")

    def page_bytes(self, page_size: int, dtype_bytes: int) -> int:
        """HBM cost of ONE page id of the pool's id space across every layer
        that keeps it: K and V tiles in the grouped-query layers, a latent
        tile (with its index key, where there is an indexer) in the FULL
        layers (the window layers' pages are
        :meth:`window_page_bytes` each, in their own id space); a recurrent
        layer keeps none."""
        d = self.decoder
        kv = 2 * d.kv_heads * d.dim_per_head
        row = self.layers_of(self.PAGED) * kv
        if self.full_latent_layers:
            index_key = d.index_head_dim if d.indexer else 0
            row += self.full_latent_layers * (d.latent_full.kv_lora + d.latent_full.rope + index_key)
        return page_size * row * dtype_bytes

    def window_page_bytes(self, page_size: int, dtype_bytes: int) -> int:
        """HBM cost of one window page id across the window layers."""
        if not self.window_layers:
            return 0
        d = self.decoder
        return self.window_layers * page_size * (d.latent_window.kv_lora + d.latent_window.rope) * dtype_bytes

    def slot_bytes(self, dtype_bytes: int) -> int:
        """HBM cost of one SLOT's recurrent state across the recurrent
        layers, whatever the row's length: the scan state in float32 and the
        convolution tail in the cache's type."""
        d = self.decoder
        if not self.state_layers:
            return 0
        ssm = d.mamba_state * d.mamba_inner * 4
        conv = (d.mamba_conv - 1) * d.mamba_conv_dim * dtype_bytes
        return self.state_layers * (ssm + conv)

    def page_size_of(self, caches: list) -> int:
        """Tokens a page of pool ``caches`` holds, read off the first layer
        that keeps pages."""
        for kind, layer in zip(self.kinds, caches):
            if kind == self.PAGED:
                return layer["k"].shape[2]
            if kind in (self.LATENT, self.WINDOW):
                return layer["c"].shape[1]
        raise ValueError("no layer of this decoder keeps pages: its block tables have no page to address")

    def install(self, i: int, dst: dict, pre: dict, slot, bt_row, page: int) -> dict:
        """Layer ``i`` of the pool with one prefilled request's batch-1
        scratch entry ``pre`` written in: a contiguous ``[1, kvh, Lb, dh]``
        (or latent ``[1, Lb, width]``) scratch scattered page by page into
        the ids ``bt_row`` grants (entries past the prompt's live pages are
        the dump page 0, so the scatter needs no masking; a latent decoder
        with window layers gives ``bt_row`` [2, MAXP], the full layers' table
        and the window layers'); a recurrent state copied whole into ``slot``'s row, so a
        reused slot keeps nothing of the row before."""
        kind = self.kinds[i]
        if kind == self.RECURRENT:
            return {name: arr.at[slot].set(pre[name][0].astype(arr.dtype)) for name, arr in dst.items()}
        if kind == self.PAGED:
            kvh, lb, dh = pre["k"].shape[1:]
            nseg = lb // page
            ids = bt_row[:nseg]
            return {
                name: arr.at[ids].set(
                    pre[name][0].reshape(kvh, nseg, page, dh).transpose(1, 0, 2, 3).astype(arr.dtype)
                )
                for name, arr in dst.items()
            }
        nseg = pre["c"].shape[1] // page
        table = bt_row if bt_row.ndim == 1 else bt_row[0 if kind == self.LATENT else 1]
        ids = table[:nseg]
        return {
            name: arr.at[ids].set(pre[name][0].reshape(nseg, page, -1).astype(arr.dtype))
            for name, arr in dst.items()
        }


def window_pool_pages(cfg, page_size: int, slots: int, block: int) -> int:
    """Size of the window layers' id space: every slot at the most a row
    can hold (:meth:`WindowPages.row_pages`), and the dump page."""
    return slots * WindowPages.row_pages(cfg.decoder.sliding_window, page_size, block) + 1


def resolve_pool_pages(
    cfg,
    page_size: int,
    slots: int,
    max_seq: int,
    dtype_bytes: int = 2,
    block: int = 8,
) -> tuple[int, str]:
    """Pool size in pages, and where it came from (``"pinned"``,
    ``"device_memory"`` or ``"no_device_stats"``). ``LUMEN_VLM_KV_PAGES``
    pins it; otherwise it is sized against live HBM headroom as the
    devices report it (``memory_stats()``), claiming
    ``LUMEN_VLM_KV_HEADROOM`` of the free bytes on the tightest device. A
    backend without memory stats (the CPU, tier-1) gets the slot-era
    footprint — ``slots`` full-length rows — so tests and laptops behave
    exactly as the contiguous pool did memory-wise while still getting
    page sharing. A TPU without them is an error: a pool sized by guess
    either wastes the chip or runs it out of memory."""
    import jax

    from ...utils.env import env_float, env_int

    maxp = -(-max_seq // page_size)
    # Floor: every slot can hold at least one modest row (1/4 max_seq)
    # concurrently; below that the pool thrashes on preemption.
    floor = slots * max(1, maxp // 4) + 1
    # Cap at what block tables can even address (slots x max_pages) — a
    # bigger pool than addressable is pure waste.
    cap = slots * maxp + 1
    explicit = env_int("LUMEN_VLM_KV_PAGES", None, minimum=2)
    if explicit is not None:
        return max(explicit, 2), "pinned"
    frac = env_float(
        "LUMEN_VLM_KV_HEADROOM", DEFAULT_HEADROOM_FRACTION, minimum=0.05, maximum=0.95
    )
    rows = RowState(cfg)
    per_page = rows.page_bytes(page_size, dtype_bytes)
    headroom = None
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        limit = stats.get("bytes_limit")
        if not limit:
            if dev.platform == "tpu":
                raise RuntimeError(
                    f"{dev} reports no memory_stats()['bytes_limit']: the paged "
                    "KV pool cannot be sized from HBM headroom (pin it with "
                    "LUMEN_VLM_KV_PAGES to go on)"
                )
            continue
        free = max(0, int(limit) - int(stats.get("bytes_in_use") or 0))
        headroom = free if headroom is None else min(headroom, free)
    if headroom is None:
        logger.info(
            "VLM paged-KV pool: %d pages x %d tokens (slot-era footprint: the %s "
            "backend reports no device memory stats)",
            cap, page_size, jax.default_backend(),
        )
        return cap, "no_device_stats"
    budget = int(headroom * frac)
    # what does not grow with a row's length is a fixed size (the window
    # layers' id space, every slot's recurrent state): what is left buys pages
    if rows.window_layers:
        budget -= window_pool_pages(cfg, page_size, slots, block) * rows.window_page_bytes(
            page_size, dtype_bytes
        )
    budget -= slots * rows.slot_bytes(dtype_bytes)
    pages = max(budget, 0) // max(per_page, 1)
    sized = max(floor, min(pages, cap))
    logger.info(
        "VLM paged-KV pool: %d pages x %d tokens (%.1f MB of %.1f MB headroom, cap %d)",
        sized, page_size, sized * per_page / 1e6, headroom / 1e6, cap,
    )
    return sized, "device_memory"
