"""CPU-CI coverage for the ragged paged-attention decode path.

Three layers, mirroring ``test_quant_pallas.py``'s structure:

- the Pallas kernel in interpret mode (``LUMEN_PAGED_KERNEL=1`` off-TPU)
  must match the XLA gather reference to f32 rounding (``F32_BOUND``);
- the dispatch gates (env kill-switch, head_dim VMEM limit, off-TPU
  default) must route to the reference;
- the host page allocator's invariants (exclusive ownership, balanced
  accounting, dump-page reservation) and the page-table indirection's
  row isolation must survive random admit/grow/retire orders.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

import importlib

# ``lumen_tpu.ops`` re-exports the ``attention`` FUNCTION over the
# submodule attribute, so a plain ``import ... as`` grabs the wrong one.
att_mod = importlib.import_module("lumen_tpu.ops.attention")

from lumen_tpu.models.vlm.paged_kv import PagedKVPool, PoolExhausted

#: Kernel-vs-reference bound for f32 inputs (atol and rtol). Both compute
#: in f32 with the same logits contraction, but the kernel folds a row's
#: softmax page by page with running-max rescaling where the reference
#: takes one max / exp / sum over the gathered row, so the sums round in a
#: different order: a few f32 ulps (eps 1.2e-7; <= 6e-7 seen on these cases,
#: |out| <= 3). Logits or weights computed in bf16 (eps 7.8e-3) move the
#: outputs by >= 1e-3 on the same cases, a hundred times the bound —
#: ``test_bound_rejects_bf16_compute`` keeps it that tight.
F32_BOUND = 1e-5
#: bf16 in/out: compute stays f32, the final cast may round an f32 result
#: that differs by an ulp to the neighbouring bf16 value (spacing <= 2^-7
#: relative).
BF16_BOUND = 2.0**-7


def _token_major(pages):
    """Head-major pages [P, kv_heads, page, d] laid out as the pool holds
    them: token-major [P, page, kv_heads * d], a token's heads one row."""
    n, kvh, page, d = pages.shape
    return pages.transpose(0, 2, 1, 3).reshape(n, page, kvh * d)


def _case(b, h, kvh, d, page, maxp, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    n_pages = maxp * b + 1
    q = jnp.asarray(rng.standard_normal((b, h, d)), dtype)
    kp = _token_major(jnp.asarray(rng.standard_normal((n_pages, kvh, page, d)), dtype))
    vp = _token_major(jnp.asarray(rng.standard_normal((n_pages, kvh, page, d)), dtype))
    bt = jnp.asarray(rng.integers(0, n_pages, size=(b, maxp)), np.int32)
    kl = jnp.asarray(rng.integers(1, maxp * page + 1, size=(b,)), np.int32)
    return q, kp, vp, bt, kl


class TestKernelInterpretExact:
    """Name kept from when the bound was bitwise equality (ROADMAP D1)."""

    @pytest.mark.parametrize(
        "b,h,kvh,d,page,maxp",
        [
            (3, 4, 2, 8, 4, 5),  # tiny-config GQA shape
            (2, 14, 2, 64, 16, 8),  # Qwen2-0.5B decode shape
            (4, 4, 4, 16, 8, 3),  # MHA (group of 1: the matvec corner)
            (1, 8, 2, 32, 8, 16),  # single row, long table
            (5, 6, 3, 24, 4, 7),  # odd everything
            (2, 12, 2, 128, 64, 8),  # Qwen2-1.5B decode shape at the default page
            (3, 4, 2, 16, 128, 3),  # a page as wide as the lane tile
        ],
    )
    def test_matches_reference_exactly(self, monkeypatch, b, h, kvh, d, page, maxp):
        monkeypatch.setenv("LUMEN_PAGED_KERNEL", "1")
        q, kp, vp, bt, kl = _case(b, h, kvh, d, page, maxp, seed=b * 7 + maxp)
        assert att_mod._paged_kernel_usable(d)
        ref = att_mod.paged_attention_reference(q, kp, vp, bt, kl)
        ker = att_mod.paged_attention(q, kp, vp, bt, kl)
        assert ker.shape == (b, h, d) and ker.dtype == q.dtype
        np.testing.assert_allclose(
            np.asarray(ker), np.asarray(ref), rtol=F32_BOUND, atol=F32_BOUND
        )

    def test_matches_reference_bf16(self, monkeypatch):
        monkeypatch.setenv("LUMEN_PAGED_KERNEL", "1")
        q, kp, vp, bt, kl = _case(2, 4, 2, 16, 8, 4, seed=9, dtype=jnp.bfloat16)
        ref = att_mod.paged_attention_reference(q, kp, vp, bt, kl)
        ker = att_mod.paged_attention(q, kp, vp, bt, kl)
        assert ker.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(ker, np.float32), np.asarray(ref, np.float32),
            rtol=BF16_BOUND, atol=BF16_BOUND,
        )

    def test_bound_rejects_bf16_compute(self):
        """The bound must stay tight enough to catch a drop in compute
        precision: the reference itself, fed the Qwen2-0.5B case rounded
        to bf16, lands far outside it."""
        q, kp, vp, bt, kl = _case(2, 14, 2, 64, 16, 8, seed=22)
        ref = np.asarray(att_mod.paged_attention_reference(q, kp, vp, bt, kl))
        low = np.asarray(
            att_mod.paged_attention_reference(
                *(x.astype(jnp.bfloat16).astype(jnp.float32) for x in (q, kp, vp)), bt, kl
            )
        )
        assert np.abs(low - ref).max() > 50 * F32_BOUND

    def test_reference_masks_by_row_length(self):
        """Keys past kv_len must not influence the output: doubling the
        garbage beyond the live prefix changes nothing."""
        q, kp, vp, bt, kl = _case(3, 4, 2, 8, 4, 6, seed=3)
        kl = jnp.asarray([5, 13, 20], np.int32)
        out1 = att_mod.paged_attention_reference(q, kp, vp, bt, kl)
        # Perturb every key/value slot at positions >= kv_len via a fresh
        # pool where all pages differ; only the table entries mapping the
        # live prefix are pinned to the originals.
        page = 4
        live_pages = [int(np.ceil(int(n) / page)) for n in np.asarray(kl)]
        rng = np.random.default_rng(99)
        kp2 = jnp.asarray(rng.standard_normal(kp.shape), kp.dtype)
        vp2 = jnp.asarray(rng.standard_normal(vp.shape), vp.dtype)
        bt_np = np.asarray(bt)
        for row, n_live in enumerate(live_pages):
            for j in range(n_live):
                pid = bt_np[row, j]
                kp2 = kp2.at[pid].set(kp[pid])
                vp2 = vp2.at[pid].set(vp[pid])
        # Partially-live last pages still carry stale tail slots inside a
        # LIVE page; zero them in both pools so only dead PAGES differ.
        for row, n_live in enumerate(live_pages):
            n = int(np.asarray(kl)[row])
            tail = n % page
            if tail:
                pid = bt_np[row, n_live - 1]
                kp2 = kp2.at[pid, tail:].set(0)
                vp2 = vp2.at[pid, tail:].set(0)
                kp = kp.at[pid, tail:].set(0)
                vp = vp.at[pid, tail:].set(0)
        out1 = att_mod.paged_attention_reference(q, kp, vp, bt, kl)
        out2 = att_mod.paged_attention_reference(q, kp2, vp2, bt, kl)
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


class TestDispatchGates:
    def test_kill_switch(self, monkeypatch):
        monkeypatch.setenv("LUMEN_PAGED_KERNEL", "0")
        assert not att_mod._paged_kernel_usable(64)

    def test_off_tpu_default_is_reference(self, monkeypatch):
        monkeypatch.delenv("LUMEN_PAGED_KERNEL", raising=False)
        assert not att_mod._paged_kernel_usable(64)

    def test_vmem_limits(self, monkeypatch):
        monkeypatch.setenv("LUMEN_PAGED_KERNEL", "1")
        assert not att_mod._paged_kernel_usable(512)  # head_dim
        assert att_mod._paged_kernel_usable(64)

    def test_no_row_capacity_limit(self, monkeypatch):
        """The kernel's scratch does not grow with the block table, so a
        row past the old 8,192-token cap still takes the kernel."""
        monkeypatch.setenv("LUMEN_PAGED_KERNEL", "1")
        b, h, kvh, d, page, maxp = 1, 4, 2, 16, 16, 520  # 8,320 tokens
        q, kp, vp, bt, kl = _case(b, h, kvh, d, page, maxp, seed=5)
        kl = jnp.asarray([maxp * page - 3], np.int32)
        ref = att_mod.paged_attention_reference(q, kp, vp, bt, kl)
        ker = att_mod.paged_attention(q, kp, vp, bt, kl)
        np.testing.assert_allclose(
            np.asarray(ker), np.asarray(ref), rtol=F32_BOUND, atol=F32_BOUND
        )


class TestPagedKVPool:
    def test_admit_grow_release_accounting(self):
        pool = PagedKVPool(pages_total=33, page_size=16, slots=4, max_pages=8)
        row = pool.admit(0, prompt_tokens=30)  # 31 slots -> 2 pages
        assert pool.pages_live == 2 and row[0] != 0 and row[1] != 0 and row[2] == 0
        assert pool.grow(0, 33)  # 3 pages
        assert pool.pages_live == 3
        assert pool.grow(0, 33)  # idempotent
        assert pool.pages_live == 3
        released = pool.release(0)
        assert released == 3
        assert pool.pages_live == 0
        assert pool.allocated_total == 3 and pool.freed_total == 3
        assert pool.pages_free == 32  # page 0 never enters the free list
        assert np.all(pool.block_tables[0] == 0)

    def test_dump_page_never_granted(self):
        pool = PagedKVPool(pages_total=8, page_size=4, slots=4, max_pages=4)
        granted = []
        for slot in range(3):
            row = pool.admit(slot, prompt_tokens=5)  # 2 pages each
            granted.extend(int(p) for p in row[row != 0])
        assert 0 not in granted
        assert len(set(granted)) == len(granted)  # exclusive ownership

    def test_grow_clamps_at_row_capacity(self):
        """Asking to cover more tokens than a block table can address must
        clamp to max_pages, not index past the table: the decode program
        clamps its writes the same way, so a row at capacity keeps
        overwriting its last slot."""
        pool = PagedKVPool(pages_total=20, page_size=4, slots=2, max_pages=4)
        pool.admit(0, prompt_tokens=3)
        assert pool.grow(0, pool.row_capacity() + 13)  # way past the table
        assert len(pool._owned[0]) == 4  # capped at max_pages
        assert pool.pages_live == 4

    def test_exhaustion_and_double_admit(self):
        pool = PagedKVPool(pages_total=4, page_size=4, slots=4, max_pages=4)
        pool.admit(0, prompt_tokens=10)  # 3 pages: pool drained
        assert not pool.grow(0, 32)
        with pytest.raises(PoolExhausted):
            pool.admit(1, prompt_tokens=10)
        with pytest.raises(RuntimeError):
            pool.admit(0, prompt_tokens=1)

    def test_random_order_invariants(self):
        """Property: under random admit/grow/release orders, no page is
        ever owned by two slots, the dump page is never granted, and
        allocated - freed == live owned pages at every step."""
        rng = np.random.default_rng(1234)
        pool = PagedKVPool(pages_total=40, page_size=8, slots=6, max_pages=10)
        live: dict[int, int] = {}  # slot -> tokens covered
        for _ in range(500):
            op = rng.integers(0, 3)
            if op == 0 and len(live) < 6:
                slot = next(i for i in range(6) if i not in live)
                tokens = int(rng.integers(1, 40))
                if pool.can_admit(tokens):
                    pool.admit(slot, tokens)
                    live[slot] = tokens + 1
            elif op == 1 and live:
                slot = int(rng.choice(list(live)))
                target = live[slot] + int(rng.integers(1, 16))
                if target <= pool.row_capacity() and pool.grow(slot, target):
                    live[slot] = target
            elif op == 2 and live:
                slot = int(rng.choice(list(live)))
                pool.release(slot)
                del live[slot]
            # invariants
            owned = [p for s in live for p in pool.block_tables[s] if p != 0]
            assert 0 not in owned
            assert len(set(owned)) == len(owned), "page owned twice"
            assert pool.pages_live == len(owned)
            assert pool.pages_live + pool.pages_free == pool.pages_total - 1
        for slot in list(live):
            pool.release(slot)
        assert pool.pages_live == 0
        assert pool.allocated_total == pool.freed_total

    def test_row_isolation_under_random_tables(self):
        """Page-table indirection must never mix rows: attention over a
        row's pages equals attention over that row's own contiguous KV,
        whatever interleaved order the allocator granted pages in."""
        rng = np.random.default_rng(7)
        b, h, kvh, d, page, maxp = 4, 4, 2, 16, 8, 6
        pool = PagedKVPool(pages_total=b * maxp + 1, page_size=page, slots=b, max_pages=maxp)
        kv_lens = [int(rng.integers(1, maxp * page)) for _ in range(b)]
        # Interleaved growth: admit everyone, then grow rows in random
        # order so page ids end up shuffled across rows.
        for row in range(b):
            pool.admit(row, 1)
        targets = dict(enumerate(kv_lens))
        grown = {row: 2 for row in range(b)}
        order = list(range(b)) * maxp
        rng.shuffle(order)
        for row in order:
            if grown[row] < targets[row]:
                step = min(targets[row], grown[row] + page)
                assert pool.grow(row, step)
                grown[row] = step
        # Fill each row's live KV with per-row content through its table.
        k_pages = np.zeros((pool.pages_total, page, kvh * d), np.float32)
        v_pages = np.zeros_like(k_pages)
        own_k = [rng.standard_normal((kvh, n, d)).astype(np.float32) for n in kv_lens]
        own_v = [rng.standard_normal((kvh, n, d)).astype(np.float32) for n in kv_lens]
        for row in range(b):
            for t in range(kv_lens[row]):
                pid = pool.block_tables[row, t // page]
                assert pid != 0
                k_pages[pid, t % page] = own_k[row][:, t].reshape(-1)
                v_pages[pid, t % page] = own_v[row][:, t].reshape(-1)
        q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
        out = att_mod.paged_attention_reference(
            jnp.asarray(q), jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(pool.block_tables), jnp.asarray(kv_lens, np.int32),
        )
        # Per-row ground truth: plain attention over the row's OWN kv.
        for row in range(b):
            k = np.repeat(own_k[row], h // kvh, axis=0)  # [h, n, d]
            v = np.repeat(own_v[row], h // kvh, axis=0)
            s = np.einsum("hd,hnd->hn", np.asarray(q[row], np.float32), k) / np.sqrt(d)
            w = np.exp(s - s.max(-1, keepdims=True))
            w /= w.sum(-1, keepdims=True)
            want = np.einsum("hn,hnd->hd", w, v)
            np.testing.assert_allclose(
                np.asarray(out[row]), want, rtol=2e-5, atol=2e-5
            )


def _vcase(b, w, h, kvh, d, page, maxp, seed=0, dtype=jnp.float32):
    """Verify-window case: q is [B, W, H, d]; kv_lens leaves room for the
    window (slot t sees kv_len + t keys, which must stay addressable)."""
    rng = np.random.default_rng(seed)
    n_pages = maxp * b + 1
    q = jnp.asarray(rng.standard_normal((b, w, h, d)), dtype)
    kp = _token_major(jnp.asarray(rng.standard_normal((n_pages, kvh, page, d)), dtype))
    vp = _token_major(jnp.asarray(rng.standard_normal((n_pages, kvh, page, d)), dtype))
    bt = jnp.asarray(rng.integers(0, n_pages, size=(b, maxp)), np.int32)
    kl = jnp.asarray(rng.integers(1, maxp * page - w + 1, size=(b,)), np.int32)
    return q, kp, vp, bt, kl


class TestVarqKernelExact:
    """The verify-window path (speculative decoding) folds the window into
    the query-row axis; its kernel must match its reference within
    ``F32_BOUND``, and on the reference path each window slot must equal
    the single-token path at the slot's own visibility bitwise — the
    contract that makes verified drafts token-identical to sequential
    decode. (Name kept from when the kernel bound was bitwise too.)"""

    @pytest.mark.parametrize(
        "b,w,h,kvh,d,page,maxp",
        [
            (3, 4, 4, 2, 8, 4, 5),   # tiny-config GQA shape
            (2, 5, 14, 2, 64, 16, 8),  # Qwen2-0.5B verify shape
            (4, 2, 4, 4, 16, 8, 3),  # MHA (group of 1)
            (1, 8, 8, 2, 32, 8, 16),  # single row, wide window
            (2, 3, 12, 2, 128, 64, 4),  # Qwen2-1.5B verify shape at the default page
            (2, 3, 4, 2, 16, 128, 3),  # a page as wide as the lane tile
        ],
    )
    def test_matches_reference_exactly(self, monkeypatch, b, w, h, kvh, d, page, maxp):
        monkeypatch.setenv("LUMEN_PAGED_KERNEL", "1")
        q, kp, vp, bt, kl = _vcase(b, w, h, kvh, d, page, maxp, seed=b * 13 + w)
        ref = att_mod.paged_attention_varq_reference(q, kp, vp, bt, kl)
        ker = att_mod.paged_attention(q, kp, vp, bt, kl)
        assert ker.shape == (b, w, h, d) and ker.dtype == q.dtype
        np.testing.assert_allclose(
            np.asarray(ker), np.asarray(ref), rtol=F32_BOUND, atol=F32_BOUND
        )

    def test_matches_reference_bf16(self, monkeypatch):
        monkeypatch.setenv("LUMEN_PAGED_KERNEL", "1")
        q, kp, vp, bt, kl = _vcase(2, 3, 4, 2, 16, 8, 4, seed=17, dtype=jnp.bfloat16)
        ref = att_mod.paged_attention_varq_reference(q, kp, vp, bt, kl)
        ker = att_mod.paged_attention(q, kp, vp, bt, kl)
        assert ker.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(ker, np.float32), np.asarray(ref, np.float32),
            rtol=BF16_BOUND, atol=BF16_BOUND,
        )

    def test_kernel_window_slot_tracks_single_token_kernel(self, monkeypatch):
        """Kernel-path twin of the slot contract below: slot t of the
        window kernel against the one-token kernel at ``kv_lens + t``."""
        monkeypatch.setenv("LUMEN_PAGED_KERNEL", "1")
        w = 3
        q, kp, vp, bt, kl = _vcase(2, w, 14, 2, 64, 16, 8, seed=41)
        out = att_mod.paged_attention(q, kp, vp, bt, kl)
        for t in range(w):
            single = att_mod.paged_attention(q[:, t], kp, vp, bt, kl + t)
            np.testing.assert_allclose(
                np.asarray(out[:, t]), np.asarray(single), rtol=F32_BOUND, atol=F32_BOUND
            )

    def test_window_slot_equals_single_token_at_extended_len(self):
        """Slot t of the verify window == the single-token reference with
        kv_lens + t: the window is EXACTLY w sequential decode steps whose
        KV was pre-written, which is what lets one verify forward replace
        w target steps without changing a single output bit."""
        w = 4
        q, kp, vp, bt, kl = _vcase(3, w, 4, 2, 8, 4, 5, seed=23)
        out = att_mod.paged_attention_varq_reference(q, kp, vp, bt, kl)
        for t in range(w):
            single = att_mod.paged_attention_reference(
                q[:, t], kp, vp, bt, kl + t
            )
            np.testing.assert_array_equal(np.asarray(out[:, t]), np.asarray(single))

    def test_w1_degenerates_to_single_token(self):
        q, kp, vp, bt, kl = _vcase(2, 1, 4, 2, 16, 8, 4, seed=31)
        out = att_mod.paged_attention_varq_reference(q, kp, vp, bt, kl)
        single = att_mod.paged_attention_reference(q[:, 0], kp, vp, bt, kl)
        np.testing.assert_array_equal(np.asarray(out[:, 0]), np.asarray(single))

    @pytest.mark.parametrize("page", [64, 128])
    @pytest.mark.parametrize("w", [1, 3], ids=["decode", "verify3"])
    def test_ragged_rows_at_large_pages(self, monkeypatch, page, w):
        """What a step of many keys has to get right, row by row: an idle
        slot (table all dump page, one visible key), a row that ends on a
        page's last slot, one key into the next page, a last page partly
        live, and a full table; for the window, its last slot crossing
        into a page the first slot cannot see."""
        monkeypatch.setenv("LUMEN_PAGED_KERNEL", "1")
        maxp = 4
        lens = [1, page, page + 1, 2 * page + page // 3, page - w + 2, maxp * page - w + 1]
        q, kp, vp, bt, _ = _vcase(len(lens), w, 4, 2, 16, page, maxp, seed=page + w)
        bt = bt.at[0].set(0)
        kl = jnp.asarray(lens, np.int32)
        ref = att_mod.paged_attention_varq_reference(q, kp, vp, bt, kl)
        ker = att_mod.paged_attention(q, kp, vp, bt, kl)
        np.testing.assert_allclose(
            np.asarray(ker), np.asarray(ref), rtol=F32_BOUND, atol=F32_BOUND
        )
        if w == 1:  # and the one-token entry points against each other
            one = att_mod.paged_attention(q[:, 0], kp, vp, bt, kl)
            np.testing.assert_allclose(
                np.asarray(one),
                np.asarray(att_mod.paged_attention_reference(q[:, 0], kp, vp, bt, kl)),
                rtol=F32_BOUND, atol=F32_BOUND,
            )


def _head_major_reference(q, k_heads, v_heads, bt, kl):
    """The one-token reference as it read a head-major pool
    [P, kv_heads, page, d], kept to pin the token-major one against."""
    b, h, d = q.shape
    _, kvh, page, _ = k_heads.shape
    maxp = bt.shape[1]
    g = h // kvh
    gp = att_mod._q_group_pad(g)
    k = k_heads[bt].transpose(0, 2, 1, 3, 4).reshape(b, kvh, maxp * page, d)
    v = v_heads[bt].transpose(0, 2, 1, 3, 4).reshape(b, kvh, maxp * page, d)
    qg = jnp.pad(q.reshape(b, kvh, g, d).astype(jnp.float32), ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    s = jnp.einsum("bkgd,bksd->bkgs", qg, k.astype(jnp.float32), preferred_element_type=jnp.float32)
    s = s * (1.0 / np.sqrt(d))
    live = jnp.arange(maxp * page)[None, :] < kl[:, None]
    s = jnp.where(live[:, None, None, :], s, att_mod.NEG_INF)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    w = p / jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,bksd->bkgd", w, v.astype(jnp.float32), preferred_element_type=jnp.float32)
    return out[:, :, :g].reshape(b, h, d).astype(q.dtype)


class TestTokenMajorPages:
    """The pool's pages are token-major, ``[P, page, kv_heads * d]``: one grid
    step of the kernel reads a whole page, every KV head, and slices each
    head's lanes. At the widths the served decoders have (Qwen2-0.5B's
    (2, 64), Qwen2-1.5B's (2, 128), Granite's attention layer's (8, 128)),
    for one token and for a verify window, the kernel is its reference."""

    @pytest.mark.parametrize("w", [1, 4], ids=["decode", "verify4"])
    @pytest.mark.parametrize("h,kvh,d", [(14, 2, 64), (12, 2, 128), (32, 8, 128)],
                             ids=["qwen2_0p5b", "qwen2_1p5b", "granite_attn"])
    def test_kernel_matches_reference_at_served_widths(self, monkeypatch, w, h, kvh, d):
        monkeypatch.setenv("LUMEN_PAGED_KERNEL", "1")
        page, maxp = 64, 3
        lens = [1, page - w + 1, page + 5, maxp * page - w + 1]
        q, kp, vp, bt, _ = _vcase(len(lens), w, h, kvh, d, page, maxp, seed=h + kvh + w)
        assert kp.shape == (len(lens) * maxp + 1, page, kvh * d)
        bt = bt.at[0].set(0)  # an idle slot: the dump page, one key
        kl = jnp.asarray(lens, np.int32)
        ref = att_mod.paged_attention_varq_reference(q, kp, vp, bt, kl)
        ker = att_mod.paged_attention(q, kp, vp, bt, kl)
        assert ker.shape == (len(lens), w, h, d)
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), rtol=F32_BOUND, atol=F32_BOUND)

    @pytest.mark.parametrize("b,h,kvh,d,page,maxp", [(3, 4, 2, 8, 4, 5), (2, 12, 2, 128, 64, 4), (2, 32, 8, 128, 16, 3)])
    def test_reference_is_the_head_major_formula_on_the_transposed_pool(self, b, h, kvh, d, page, maxp):
        """The layout moves no number: the reference over token-major pages
        is the head-major reference over the same pages transposed."""
        rng = np.random.default_rng(b * h + page)
        n_pages = b * maxp + 1
        k_heads = jnp.asarray(rng.standard_normal((n_pages, kvh, page, d)), jnp.float32)
        v_heads = jnp.asarray(rng.standard_normal((n_pages, kvh, page, d)), jnp.float32)
        q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
        bt = jnp.asarray(rng.integers(0, n_pages, size=(b, maxp)), np.int32)
        kl = jnp.asarray(rng.integers(1, maxp * page + 1, size=(b,)), np.int32)
        got = att_mod.paged_attention_reference(q, _token_major(k_heads), _token_major(v_heads), bt, kl)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(_head_major_reference(q, k_heads, v_heads, bt, kl)))

    def test_the_route_gauge_counts_the_lanes_of_a_page(self, monkeypatch):
        """``attention-route`` counts ``paged-page:{kv_heads * d}`` once for
        each layer traced, on the reference path as on the kernel's."""
        from lumen_tpu.utils.metrics import metrics

        def count():
            return metrics.snapshot().get("gauges", {}).get("attention-route", {}).get("paged-page:48", 0)

        q, kp, vp, bt, kl = _case(2, 6, 3, 16, 4, 3, seed=8)
        before = count()
        att_mod.paged_attention(q, kp, vp, bt, kl)
        monkeypatch.setenv("LUMEN_PAGED_KERNEL", "1")
        att_mod.paged_attention(q, kp, vp, bt, kl)
        assert count() == before + 2


class TestPagedKVPoolSharing:
    """Copy-on-write page sharing: reference counts, shared admission, and
    the CoW frontier swap must keep the pool's exclusive-ownership story
    intact for WRITES while letting reads share."""

    def test_admit_shared_attaches_and_balances(self):
        pool = PagedKVPool(pages_total=16, page_size=4, slots=4, max_pages=4)
        pool.admit(0, prompt_tokens=10)  # 3 pages (11 slots)
        owner = pool.owned_pages(0)
        # Second row shares the first two pages (prefix) + fresh tail.
        pool.admit_shared(1, owner[:2], prompt_tokens=10)
        assert pool.owned_pages(1)[:2] == owner[:2]
        assert pool.refcount(owner[0]) == 2 and pool.refcount(owner[2]) == 1
        assert pool.shared_prefix_len(1) == 2 and pool.shared_prefix_len(0) == 0
        assert pool.stats().pages_shared == 2
        # Releasing the sharer drops its three references but physically
        # frees only its private page; the owner's pages stay resident.
        free_before = pool.pages_free
        assert pool.release(1) == 3  # references dropped
        assert pool.pages_free == free_before + 1  # pages actually freed
        assert pool.refcount(owner[0]) == 1
        pool.release(0)
        assert pool.pages_live == 0
        assert pool.allocated_total == pool.freed_total

    def test_admit_shared_must_leave_frontier_private(self):
        """Shared coverage may never reach the prompt's write frontier:
        the next decode write would land in a page someone else reads."""
        pool = PagedKVPool(pages_total=16, page_size=4, slots=4, max_pages=4)
        pool.admit(0, prompt_tokens=8)  # 3 pages (9 slots)
        owner = pool.owned_pages(0)
        with pytest.raises(ValueError):
            pool.admit_shared(1, owner[:3], prompt_tokens=8)

    def test_admit_shared_exhaustion_keeps_refcounts(self):
        """PoolExhausted must fire BEFORE the shared incref — a failed
        shared admission leaves every refcount untouched."""
        pool = PagedKVPool(pages_total=4, page_size=4, slots=4, max_pages=4)
        pool.admit(0, prompt_tokens=6)  # 2 pages: pool drained (3 usable)
        owner = pool.owned_pages(0)
        before = [pool.refcount(p) for p in owner]
        with pytest.raises(PoolExhausted):
            pool.admit_shared(1, owner[:1], prompt_tokens=14)  # needs 3 fresh
        assert [pool.refcount(p) for p in owner] == before

    def test_grow_into_shared_frontier_copies_on_write(self):
        """Growing a row whose LAST owned page is shared must swap in a
        private copy (CoW) and report the (old, new) pair; the shared
        page keeps its other holder's reference. The ENGINE never builds
        this state (prefix attachment stays behind the frontier) — the
        pool-level contract is tested directly with an incref standing in
        for a second holder."""
        pool = PagedKVPool(pages_total=16, page_size=4, slots=2, max_pages=4)
        pool.admit(0, prompt_tokens=3)  # 1 page
        page = pool.owned_pages(0)[0]
        pool.incref([page])  # cache-style second hold on the frontier
        cow: list = []
        assert pool.grow(0, 8, cow)
        assert cow and cow[0][0] == page
        old, new = cow[0]
        assert pool.owned_pages(0)[0] == new != old
        assert pool.refcount(old) == 1  # only the cache hold remains
        assert pool.refcount(new) == 1  # the row owns its private copy
        # The same growth with NO copy sink is an allocator-contract bug
        # and must fail loudly, not silently remap.
        pool.incref([pool.owned_pages(0)[-1]])
        with pytest.raises(RuntimeError):
            pool.grow(0, 16)

    def test_grow_shared_frontier_with_dry_free_list_degrades(self):
        """CoW needs a fresh page; a dry free list returns False (the
        caller preempts/reclaims) without corrupting the shared page."""
        pool = PagedKVPool(pages_total=2, page_size=4, slots=2, max_pages=2)
        pool.admit(0, prompt_tokens=3)  # the single usable page
        page = pool.owned_pages(0)[0]
        pool.incref([page])
        assert not pool.grow(0, 8, [])
        assert pool.refcount(page) == 2  # untouched

    def test_decref_double_free_raises(self):
        pool = PagedKVPool(pages_total=8, page_size=4, slots=2, max_pages=4)
        pool.admit(0, prompt_tokens=3)
        page = pool.owned_pages(0)[0]
        pool.incref([page])
        assert pool.decref([page]) == 0  # still held by the slot
        pool.release(0)
        with pytest.raises(RuntimeError):
            pool.decref([page])
        with pytest.raises(RuntimeError):
            pool.incref([page])  # resurrection of a freed page


class _FakeDevice:
    def __init__(self, platform, stats):
        self.platform, self._stats = platform, stats

    def memory_stats(self):
        return self._stats

    def __repr__(self):
        return f"FakeDevice({self.platform})"


class TestResolvePoolPages:
    """Pool sizing says where its number came from, and never guesses on
    a TPU: the addressable cap and the no-stats footprint are the same
    number, so only the reported source tells them apart."""

    @staticmethod
    def _resolve(monkeypatch, devices, **env):
        import jax

        from lumen_tpu.models.vlm.modeling import VLMConfig
        from lumen_tpu.models.vlm.paged_kv import resolve_pool_pages

        for name in ("LUMEN_VLM_KV_PAGES", "LUMEN_VLM_KV_HEADROOM"):
            monkeypatch.delenv(name, raising=False)
        for name, value in env.items():
            monkeypatch.setenv(name, value)
        if devices is not None:
            monkeypatch.setattr(jax, "local_devices", lambda: devices)
        return resolve_pool_pages(VLMConfig.tiny(), page_size=16, slots=4, max_seq=256)

    def test_cpu_backend_takes_slot_era_footprint(self, monkeypatch):
        assert self._resolve(monkeypatch, None) == (4 * 16 + 1, "no_device_stats")

    @pytest.mark.parametrize("stats", [None, {}, {"bytes_in_use": 5}])
    def test_tpu_without_memory_stats_is_an_error(self, monkeypatch, stats):
        with pytest.raises(RuntimeError, match="bytes_limit"):
            self._resolve(monkeypatch, [_FakeDevice("tpu", stats)])

    def test_sized_from_tightest_device(self, monkeypatch):
        from lumen_tpu.models.vlm.modeling import VLMConfig
        from lumen_tpu.models.vlm.paged_kv import RowState

        per_page = RowState(VLMConfig.tiny()).page_bytes(16, 2)
        roomy = _FakeDevice("tpu", {"bytes_limit": 10**9, "bytes_in_use": 0})
        tight = _FakeDevice("tpu", {"bytes_limit": 100 * per_page, "bytes_in_use": 50 * per_page})
        pages, source = self._resolve(monkeypatch, [roomy, tight])
        assert source == "device_memory"
        assert pages == 30  # 0.6 of the tight device's 50 free pages
        pages, source = self._resolve(monkeypatch, [roomy])
        assert (pages, source) == (4 * 16 + 1, "device_memory")  # addressable cap

    def test_pinned_by_env(self, monkeypatch):
        tpu = [_FakeDevice("tpu", None)]  # never consulted
        assert self._resolve(monkeypatch, tpu, LUMEN_VLM_KV_PAGES="12") == (12, "pinned")
