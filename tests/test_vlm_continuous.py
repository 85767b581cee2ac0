"""Continuous-batching VLM scheduler tests.

The slot-pool scheduler (``models/vlm/continuous.py``) must produce
exactly the tokens the contiguous-cache reference loop produces, while
admitting requests into free slots mid-decode instead of queueing them
behind running generations.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from lumen_tpu.models.vlm import ChatMessage, VLMManager
from lumen_tpu.models.vlm.paged_kv import DEFAULT_PAGE_SIZE
from tests.test_vlm import make_vlm_model_dir
from tests.test_vlm_latent import latent_mgr  # noqa: F401 - the fixture, for the gauge test below

import pytest


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    return make_vlm_model_dir(tmp_path_factory.mktemp("vlmc"))


@pytest.fixture(scope="module")
def cont_mgr(model_dir):
    mgr = VLMManager(
        model_dir,
        dtype="float32",
        max_seq=128,
        max_new_cap=16,
        prefill_buckets=(16, 32),
        gen_slots=4,
        gen_block=4,
    )
    mgr.initialize()
    yield mgr
    mgr.close()


class TestContinuousCorrectness:
    @pytest.mark.parametrize(
        "content, image, penalty",
        [
            ("the quick brown fox", False, 1.0),  # first prompt bucket (16)
            (" ".join(["word"] * 20), False, 1.0),  # second bucket (32)
            ("the dog", True, 1.0),  # image tokens spliced into the prompt
            ("the quick brown fox", False, 1.3),  # greedy under a repetition penalty
        ],
        ids=["bucket-16", "bucket-32", "image", "repetition-penalty"],
    )
    def test_greedy_matches_the_reference_loop(self, cont_mgr, content, image, penalty):
        """The engine's tokens are the contiguous-cache reference loop's on
        the same prepared inputs: the step-block body claims the fused
        loop's body (sampling, penalty, stop), over pages instead."""
        import jax

        from tests.test_vlm import png_bytes

        msgs = [ChatMessage(role="user", content=content)]
        image_bytes = png_bytes() if image else None
        served = cont_mgr.generate(
            msgs, image_bytes=image_bytes, max_new_tokens=8, repetition_penalty=penalty
        )
        embeds, positions, lengths, prompt_ids, n = cont_mgr._prepare_inputs(msgs, image_bytes)
        assert prompt_ids.shape[1] == (32 if content.startswith("word") else 16)
        assert (embeds.shape[1] > prompt_ids.shape[1]) == image  # the vision tokens
        ref = cont_mgr.generator.generate(
            cont_mgr.params, embeds, positions, lengths, prompt_ids,
            jax.random.PRNGKey(0), max_new_tokens=8, repetition_penalty=penalty,
        )
        want = [int(t) for t in np.asarray(ref.tokens[0][: int(ref.n_generated[0])])]
        assert served.tokens == want, (served.text, want)
        assert (served.finish_reason == "eos_token") == bool(ref.stopped_eos[0])

    @pytest.mark.parametrize("page", [16, DEFAULT_PAGE_SIZE], ids=lambda p: f"page-{p}")
    def test_greedy_is_the_reference_loops_at_either_page(self, model_dir, page):
        """The served tokens do not depend on the page: at 16 tokens and at
        the default, a 58-token prompt that decodes across the 64th
        position (a page boundary at either size) serves what the
        contiguous-cache reference loop serves, so both serve the same."""
        import jax

        mgr = _make_lane_mgr(
            model_dir, chunk=None, page=page, max_seq=128, max_new_cap=16,
            prefill_buckets=(16, 32, 64),
        )
        try:
            assert mgr._continuous.page_size == page
            msgs = [ChatMessage(role="user", content=_lane_prompt(7, words=52))]
            embeds, positions, lengths, prompt_ids, n = mgr._prepare_inputs(msgs, None)
            assert n == 58 and prompt_ids.shape[1] == 64
            served = mgr.generate(msgs, max_new_tokens=16)
            ref = mgr.generator.generate(
                mgr.params, embeds, positions, lengths, prompt_ids,
                jax.random.PRNGKey(0), max_new_tokens=16,
            )
            want = [int(t) for t in np.asarray(ref.tokens[0][: int(ref.n_generated[0])])]
            assert len(want) > 64 - n  # the row crossed into the next page
            assert served.tokens == want, (served.text, want)
        finally:
            mgr.close()

    def test_concurrent_mixed_budgets_match_serial(self, cont_mgr):
        prompts = [("hello", 3), ("the quick brown fox", 8), ("a", 5), ("count", 1)]
        serial = [
            cont_mgr.generate([ChatMessage(role="user", content=p)], max_new_tokens=n)
            for p, n in prompts
        ]
        results: dict[int, object] = {}
        errors: list[Exception] = []
        barrier = threading.Barrier(len(prompts))

        def run(i, p, n):
            try:
                barrier.wait()
                results[i] = cont_mgr.generate(
                    [ChatMessage(role="user", content=p)], max_new_tokens=n
                )
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [
            threading.Thread(target=run, args=(i, p, n))
            for i, (p, n) in enumerate(prompts)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        for i, want in enumerate(serial):
            assert results[i].tokens == want.tokens, (i, results[i].text, want.text)

    def test_late_admission_does_not_wait_for_long_row(self, model_dir):
        """A request arriving while a long generation is mid-decode joins a
        free slot and finishes first, instead of queueing until the long
        row completed."""
        mgr = VLMManager(
            model_dir,
            dtype="float32",
            max_seq=128,
            max_new_cap=64,
            prefill_buckets=(16,),
            gen_slots=2,
            gen_block=2,  # 32 blocks for the long row: plenty of admit windows
        )
        mgr.initialize()
        try:
            sched = mgr._continuous
            # Warm every program (prefill/admit/step-block) so the timed
            # phase below measures scheduling, not compilation.
            mgr.generate([ChatMessage(role="user", content="warm")], max_new_tokens=2)
            order: list[str] = []
            t_long = threading.Thread(
                target=lambda: (
                    mgr.generate(
                        [ChatMessage(role="user", content="long request")],
                        max_new_tokens=64,
                    ),
                    order.append("long"),
                )
            )
            t_long.start()
            # Wait until the long row is genuinely mid-decode.
            deadline = time.time() + 30
            start_blocks = sched.blocks_run
            while sched.admitted < 2 or sched.blocks_run <= start_blocks:
                assert time.time() < deadline, "long row never started decoding"
                time.sleep(0.005)
            short = mgr.generate(
                [ChatMessage(role="user", content="short")], max_new_tokens=1
            )
            order.append("short")
            t_long.join()
            assert short.tokens  # completed with real tokens
            assert order[0] == "short", "short request waited behind the long one"
            assert sched.admitted >= 3
        finally:
            mgr.close()

    def test_zero_budget(self, cont_mgr):
        out = cont_mgr.generate(
            [ChatMessage(role="user", content="x")], max_new_tokens=0
        )
        assert out.tokens == []

    def test_streaming_matches_generate(self, cont_mgr):
        msgs = [ChatMessage(role="user", content="stream me")]
        full = cont_mgr.generate(msgs, max_new_tokens=6)
        chunks = list(cont_mgr.generate_stream(msgs, max_new_tokens=6))
        assert chunks[-1].is_final
        text = "".join(c.text for c in chunks[:-1])
        assert text == full.text
        assert chunks[-1].metadata["generated_tokens"] == len(full.tokens)

    def test_close_fails_pending(self, model_dir):
        mgr = VLMManager(
            model_dir,
            dtype="float32",
            max_seq=128,
            max_new_cap=16,
            prefill_buckets=(16,),
            gen_slots=2,
            gen_block=2,
        )
        mgr.initialize()
        mgr.close()
        with pytest.raises(RuntimeError):
            mgr._continuous.submit(object())

    def test_abandoned_stream_frees_slot(self, cont_mgr):
        """Breaking out of a stream (client disconnect / stop sequence)
        cancels the request so the slot doesn't decode to the cap."""
        sched = cont_mgr._continuous
        it = cont_mgr.generate_stream(
            [ChatMessage(role="user", content="endless")], max_new_tokens=16
        )
        got = next(it)  # consume one chunk, then walk away
        assert got is not None
        it.close()  # GeneratorExit -> cancelled flag
        deadline = time.time() + 20
        while sched._slots and time.time() < deadline:
            time.sleep(0.01)
        assert not sched._slots, "cancelled stream's slot never freed"


class TestPoolInvalidationEscalation:
    def test_failed_donated_admit_fails_all_and_strands_nobody(self, model_dir):
        """When _admit dies AFTER the donation consumed the pool buffers,
        the scheduler must fail every in-flight AND same-batch request
        (futures resolved, _STREAM_END delivered) instead of stranding
        callers or serving from deleted arrays."""
        import queue as queue_mod
        from concurrent.futures import Future

        import jax

        from lumen_tpu.models.vlm.continuous import ContinuousScheduler, _Request

        mgr = VLMManager(
            model_dir,
            dtype="float32",
            max_seq=128,
            max_new_cap=8,
            prefill_buckets=(16,),
            gen_slots=2,
            gen_block=2,
        )
        mgr.initialize()
        try:
            sched: ContinuousScheduler = mgr._continuous

            # A working request first proves the scheduler is live.
            ok = mgr.generate([ChatMessage(role="user", content="warm")], max_new_tokens=2)
            assert ok.tokens is not None

            # Sabotage: _admit consumes (donates) the pool, then raises.
            real_admit = sched.gen._admit

            def bad_admit(pool, *a, **kw):
                jax.tree.map(
                    lambda leaf: leaf.delete() if hasattr(leaf, "delete") else None, pool
                )
                raise RuntimeError("synthetic admit failure after donation")

            sched.gen._admit = bad_admit

            def make_req(stream=False):
                r = _Request(
                    embeds=None, positions=None, length=None, prompt_ids=None,
                    max_new=4, temperature=0.0, top_p=1.0, do_sample=False,
                    repetition_penalty=1.0, rng=jax.random.PRNGKey(0),
                    future=Future(),
                )
                # Bypass prefill shape plumbing: feed the prepared tensors a
                # real request would carry (reuse the manager's prepare).
                prepared = mgr._prepare_inputs(
                    [ChatMessage(role="user", content="x")], None
                )
                emb, pos, ln, ids = prepared[:4]
                r.embeds, r.positions, r.length, r.prompt_ids = emb, pos, ln, ids
                if stream:
                    r.stream_q = queue_mod.SimpleQueue()
                return r

            r1, r2 = make_req(), make_req(stream=True)
            # Enqueue both atomically: submitting one at a time races the
            # loop (it can admit r1, die, and close the queue before the
            # second submit, which would then raise outside the asserts).
            with sched._cond:
                sched._pending.extend([r1, r2])
                sched._cond.notify()
            with pytest.raises(RuntimeError):
                r1.future.result(timeout=30)
            with pytest.raises(RuntimeError):
                r2.future.result(timeout=30)
            # Stream consumer gets its end sentinel — no stranding.
            from lumen_tpu.models.vlm.continuous import _STREAM_END

            assert r2.stream_q.get(timeout=10) is _STREAM_END
            # Scheduler is dead-closed; new submits are rejected loudly.
            # (Wait for the loop thread to finish its death sweep first —
            # a submit racing the sweep is accepted and failed by the
            # sweep instead, which is also correct but not this assert.)
            sched._thread.join(timeout=10)
            sched.gen._admit = real_admit
            with pytest.raises(RuntimeError, match="closed"):
                sched.submit(make_req())
        finally:
            mgr.close()


class TestPagedPoolBehavior:
    def test_accounting_balances_at_drain(self, model_dir):
        """allocated - freed == live == 0 once every request retires, and
        the gauges expose the same balance (the bench asserts this too)."""
        mgr = VLMManager(
            model_dir, dtype="float32", max_seq=128, max_new_cap=16,
            prefill_buckets=(16, 32),
            gen_slots=4, gen_block=4,
        )
        mgr.initialize()
        try:
            sched = mgr._continuous
            threads = [
                threading.Thread(
                    target=mgr.generate,
                    args=([ChatMessage(role="user", content=f"p{i}")],),
                    kwargs={"max_new_tokens": 3 + i},
                )
                for i in range(6)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            deadline = time.time() + 20
            while sched._slots and time.time() < deadline:
                time.sleep(0.01)
            stats = sched.kv.stats()
            assert stats.pages_live == 0
            assert stats.allocated_total == stats.freed_total > 0
            from lumen_tpu.utils.metrics import metrics

            gauges = metrics.snapshot()["gauges"][f"vlm-continuous:{mgr.info.name}"]
            assert gauges["pages_allocated_total"] == gauges["pages_freed_total"]
            assert gauges["pages_live"] == 0
            assert gauges["pages_total"] == stats.pages_total
            assert gauges["occupancy_pct_mean"] > 0
        finally:
            mgr.close()

    def test_preemption_under_tiny_pool_matches_serial(self, model_dir):
        """A pool too small for every row's worst case preempts the newest
        row instead of wedging; greedy results still match serial runs."""
        from lumen_tpu.models.vlm.continuous import ContinuousScheduler

        mgr = VLMManager(
            model_dir, dtype="float32", max_seq=128, max_new_cap=64,
            prefill_buckets=(16,),
            gen_slots=2, gen_block=4,
        )
        mgr.initialize()
        try:
            serial = [
                mgr.generate([ChatMessage(role="user", content=p)], max_new_tokens=40)
                for p in ("alpha beta", "gamma delta")
            ]
            # Swap in a pool where two full rows cannot coexist: each row
            # peaks at ceil((~8 prompt + 40 gen + 4 block)/16) = 3-4
            # pages, the pool holds 5 usable.
            mgr._continuous.close()
            tiny = ContinuousScheduler(
                mgr.generator, mgr.params, slots=2, block=4,
                name=mgr.info.name, page_size=16, pages=6,
            )
            mgr._continuous = tiny
            mgr._engines = [tiny]
            results: dict[int, object] = {}
            barrier = threading.Barrier(2)

            def run(i, p):
                barrier.wait()
                results[i] = mgr.generate(
                    [ChatMessage(role="user", content=p)], max_new_tokens=40
                )

            threads = [
                threading.Thread(target=run, args=(i, p))
                for i, p in enumerate(("alpha beta", "gamma delta"))
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for i, want in enumerate(serial):
                assert results[i].tokens == want.tokens, (i, results[i].text)
            # Preemption must actually have fired iff both rows outgrew
            # the shared pool concurrently (peak per-row demand includes
            # the next block's writes).
            need = sum(
                -(-(r.input_tokens + len(r.tokens) + 4) // 16) for r in serial
            )
            if need > 5:
                assert tiny.preemptions >= 1
            stats = tiny.kv.stats()
            assert stats.pages_live == 0
            assert stats.allocated_total == stats.freed_total
        finally:
            mgr.close()

    def test_row_need_clamps_at_budget_and_capacity(self, cont_mgr):
        """Near a row's end, the next block's page demand must clamp to
        the request's own budget and the block table's reach — the
        unclamped prompt+tokens+block formula asks for pages past the
        table for feasible requests ending within `block` of the bound
        (allocator-side IndexError; see PagedKVPool.grow's clamp)."""
        from lumen_tpu.models.vlm.continuous import _Request, _Slot

        sched = cont_mgr._continuous
        req = _Request(
            embeds=None, positions=None, length=None, prompt_ids=None,
            max_new=10, temperature=0.0, top_p=1.0, do_sample=False,
            repetition_penalty=1.0,
        )
        slot = _Slot(request=req, prompt_len=9, tokens=list(range(8)))
        # Budget clamp: 9 + 8 + block would over-reserve; the row stops
        # at max_new, so only 9 + 10 + 1 tokens ever need pages.
        assert sched._row_need(slot) == 9 + 10 + 1
        # Capacity clamp: a budget at the feasibility bound never asks
        # past what the block table can address.
        req.max_new = sched.kv.row_capacity()  # absurd budget
        assert sched._row_need(slot) == min(
            slot.prompt_len + len(slot.tokens) + sched.block,
            sched.kv.row_capacity(),
        )

    def test_infeasible_request_fails_loudly(self, model_dir):
        from lumen_tpu.models.vlm.continuous import ContinuousScheduler

        mgr = VLMManager(
            model_dir, dtype="float32", max_seq=128, max_new_cap=64,
            prefill_buckets=(16,),
            gen_slots=2, gen_block=4,
        )
        mgr.initialize()
        try:
            mgr._continuous.close()
            tiny = ContinuousScheduler(
                mgr.generator, mgr.params, slots=2, block=4,
                name=mgr.info.name, page_size=16, pages=3,  # 2 usable pages
            )
            mgr._continuous = tiny
            mgr._engines = [tiny]
            with pytest.raises(ValueError, match="paged pool"):
                mgr.generate(
                    [ChatMessage(role="user", content="too big")], max_new_tokens=60
                )
        finally:
            mgr.close()


def _lane_prompt(k: int, words: int = 39) -> str:
    """A distinct in-vocabulary prompt (the test tokenizer is word-level):
    ``words`` + 6 scaffolding tokens, so 39 words is a 45-token prompt in
    the 64 bucket (three 16-token pages, two 32-token chunks) and 100
    words one in the 128 bucket (four chunks)."""
    return " ".join(f"w{16 + (37 * k + 5 * j) % 200}" for j in range(words))


def _make_lane_mgr(model_dir, chunk: "int | None" = 32, page: int = 16, **kw):
    """A continuous engine whose 64 and 128 buckets take the chunk lane
    (``chunk`` 32) or, with ``chunk`` None, the one-shot prefill. The
    page is stated: a chunk is whole pages, so the lane tests' counts
    (chunks a prompt, pages a prompt caches) are in 16-token pages."""
    cfg = dict(
        dtype="float32", max_seq=256, max_new_cap=32,
        prefill_buckets=(16, 64, 128),
        gen_slots=4, gen_block=4,
    )
    cfg.update(kw)
    mp = pytest.MonkeyPatch()
    mp.setenv("LUMEN_VLM_PAGE_SIZE", str(page))  # both read once, at construction
    if chunk is not None:
        mp.setenv("LUMEN_VLM_PREFILL_CHUNK", str(chunk))
    try:
        mgr = VLMManager(model_dir, **cfg)
        mgr.initialize()
    finally:
        mp.undo()
    return mgr


def _enqueue(mgr, prompts, max_new, prefix=False):
    """Build every request up front and queue them under the scheduler's
    lock with ONE notify, so the whole burst is there when the loop wakes
    (submitting from threads would race its turns)."""
    sched = mgr._continuous
    reqs = []
    for p, n_new in zip(prompts, max_new):
        e, pos, ln, ids, n = mgr._prepare_inputs([ChatMessage(role="user", content=p)], None, True)
        content = mgr._prefix_content(ids, n, None) if prefix else None
        reqs.append(mgr._make_gen_request(e, pos, ln, ids, n_new, 0.0, 1.0, False, 1.0, prefix_content=content))
    with sched._cond:
        for req in reqs:
            sched._stamp_submit(req)
            sched._pending.append(req)
        sched._cond.notify()
    return reqs


def _tokens(req, timeout=120):
    tokens, n_gen, _eos = req.future.result(timeout=timeout)
    return [int(t) for t in tokens[:n_gen]]


def _wait_drained(sched, timeout=20):
    deadline = time.time() + timeout
    while (sched._slots or sched._prefill_jobs or sched._pending) and time.time() < deadline:
        time.sleep(0.01)
    assert not (sched._slots or sched._prefill_jobs or sched._pending)


@pytest.fixture(scope="module")
def oneshot_mgr(model_dir):
    """Same buckets, default chunk (256): every prompt prefills one-shot."""
    mgr = _make_lane_mgr(model_dir, chunk=None)
    assert mgr._continuous.prefill_chunk == 256
    yield mgr
    mgr.close()


@pytest.fixture(scope="module")
def lane_mgr(model_dir):
    mgr = _make_lane_mgr(model_dir)
    assert mgr._continuous.prefill_chunk == 32
    yield mgr
    mgr.close()


@pytest.fixture(scope="module")
def lane_burst(lane_mgr, oneshot_mgr):
    """Four two-chunk prompts queued at once on four free slots: what the
    lane did with them, and what the one-shot prefill makes of each."""
    prompts = [_lane_prompt(k) for k in range(4)]
    want = [
        oneshot_mgr.generate([ChatMessage(role="user", content=p)], max_new_tokens=8).tokens
        for p in prompts
    ]
    sched = lane_mgr._continuous
    before = sched._gauge_fn()
    installed_at = []  # blocks run when each row was installed
    real_install = sched._install_row

    def spying_install(*a, **kw):
        installed_at.append(sched.blocks_run)
        return real_install(*a, **kw)

    sched._install_row = spying_install
    try:
        got = [_tokens(r) for r in _enqueue(lane_mgr, prompts, [8] * 4)]
    finally:
        sched._install_row = real_install
    _wait_drained(sched)
    return dict(
        want=want, got=got, before=before, after=sched._gauge_fn(),
        installed_at=installed_at, pages_live=sched.kv.stats().pages_live,
    )


class TestChunkedPrefillLane:
    def test_long_prompt_chunks_and_matches_oneshot(self, model_dir, monkeypatch):
        """A prompt bucket above LUMEN_VLM_PREFILL_CHUNK runs the chunk
        lane (several _prefill_chunk dispatches, zero one-shot prefills)
        and produces exactly the tokens the one-shot path produces."""
        long_prompt = "word " * 40  # ~40+ tokens -> the 64 bucket
        msgs = [ChatMessage(role="user", content=long_prompt)]

        mgr_direct = VLMManager(
            model_dir, dtype="float32", max_seq=256, max_new_cap=16,
            prefill_buckets=(64,),
            gen_slots=2, gen_block=4,
        )
        mgr_direct.initialize()
        try:
            want = mgr_direct.generate(msgs, max_new_tokens=8)
        finally:
            mgr_direct.close()

        monkeypatch.setenv("LUMEN_VLM_PREFILL_CHUNK", "32")
        monkeypatch.setenv("LUMEN_VLM_PAGE_SIZE", "16")  # a chunk is whole pages
        mgr = VLMManager(
            model_dir, dtype="float32", max_seq=256, max_new_cap=16,
            prefill_buckets=(64,),
            gen_slots=2, gen_block=4,
        )
        mgr.initialize()
        try:
            sched = mgr._continuous
            assert sched.prefill_chunk == 32
            out = mgr.generate(msgs, max_new_tokens=8)
            assert sched.chunks_run == 2  # 64-token bucket / 32-token chunk
            assert out.tokens == want.tokens, (out.text, want.text)
            # Decode keeps running between chunks: a short request behind
            # a chunked long one is not stalled by the whole prefill.
            assert sched.kv.stats().pages_live == 0
        finally:
            mgr.close()


    def test_burst_advances_every_job_each_turn(self, lane_burst):
        """Every lane job runs a chunk a turn and installs in the turn of
        its last chunk: four two-chunk prompts are all in within two lane
        turns, not four times (chunk, chunk, finish)."""
        b, before, after = lane_burst, lane_burst["before"], lane_burst["after"]
        chunks_per_job = 2
        assert after["lane_jobs"] - before["lane_jobs"] == 4
        chunks = after["prefill_chunks_run"] - before["prefill_chunks_run"]
        turns = after["lane_turns"] - before["lane_turns"]
        assert chunks == 4 * chunks_per_job
        assert turns == chunks_per_job
        assert chunks / turns > 1
        assert len(b["installed_at"]) == 4
        assert max(b["installed_at"]) - before["blocks_run"] <= chunks_per_job + 1
        assert b["pages_live"] == 0

    @pytest.mark.parametrize("k", range(4))
    def test_burst_rows_match_oneshot(self, lane_burst, k):
        """Chunks of several jobs interleaved in one turn: each row is
        still token for token what its one-shot prefill decodes (greedy)."""
        assert lane_burst["got"][k] == lane_burst["want"][k]
        assert len(lane_burst["got"][k]) > 1

    def test_long_prompt_still_one_chunk_a_turn_beside_decode(self, lane_mgr, oneshot_mgr):
        """The contract the lane exists for: a four-chunk prompt is spread
        over four turns, and the rows already decoding get a block in
        every one of them."""
        prompts = ["describe the cat", "describe a dog", _lane_prompt(9, words=100)]
        budgets = [24, 24, 8]
        want = [
            oneshot_mgr.generate([ChatMessage(role="user", content=p)], max_new_tokens=n).tokens
            for p, n in zip(prompts, budgets)
        ]
        assert len(want[0]) > 17 and len(want[1]) > 17  # alive through four blocks
        sched = lane_mgr._continuous
        before = sched._gauge_fn()
        chunk_at = []  # blocks run when each chunk was dispatched
        real_chunk = sched.gen._prefill_chunk

        def spying_chunk(*a, **kw):
            chunk_at.append(sched.blocks_run)
            return real_chunk(*a, **kw)

        sched.gen._prefill_chunk = spying_chunk
        try:
            got = [_tokens(r) for r in _enqueue(lane_mgr, prompts, budgets)]
        finally:
            sched.gen._prefill_chunk = real_chunk
        _wait_drained(sched)
        after = sched._gauge_fn()
        base = before["blocks_run"]
        assert chunk_at == [base, base + 1, base + 2, base + 3]
        assert after["lane_turns"] - before["lane_turns"] == 4
        assert after["prefill_chunks_run"] - before["prefill_chunks_run"] == 4
        assert got == want

    def test_short_pool_installs_in_arrival_order(self, model_dir, oneshot_mgr):
        """Two finished jobs wait on pages: both are reserved against new
        arrivals, the older installs first, and a later short prompt that
        would fit does not get in before the younger. Pages balance."""
        from lumen_tpu.models.vlm.continuous import ContinuousScheduler

        prompts = [_lane_prompt(20), _lane_prompt(21), "describe the image"]
        budgets = [2, 2, 2]  # 45 + 2 + 1 tokens: a long row never outgrows its 3 pages
        want = [
            oneshot_mgr.generate([ChatMessage(role="user", content=p)], max_new_tokens=n).tokens
            for p, n in zip(prompts, budgets)
        ]
        mgr = _make_lane_mgr(model_dir)
        try:
            mgr._continuous.close()
            sched = ContinuousScheduler(
                mgr.generator, mgr.params, slots=4, block=4, name=mgr.info.name,
                page_size=16, pages=9, prefill_chunk=32,
            )
            mgr._continuous = sched
            mgr._engines = [sched]
            held, arrival = [], [False]
            turn = [0]
            seen_at, installed_at = {}, {}
            real_gate, real_install = sched._gate, sched._install_row

            def spying_gate(admit):
                # Pages leave and come back on the loop's own thread, at
                # the head of a turn, so every turn sees one state.
                turn[0] += 1
                for req in admit:
                    seen_at.setdefault(id(req), turn[0])
                if admit and arrival[0]:
                    # Pages for ONE long row come free as the short prompt
                    # arrives: 4 free covers the older job and the short
                    # one, which a head-only reservation would let through.
                    arrival[0] = False
                    sched.kv.decref(held[:2])
                placeable = real_gate(admit)
                if len(placeable) == 2 and not held:
                    held.extend(sched.kv._pop_fresh(6))  # both in the lane: 2 of 8 pages stay free
                return placeable

            def spying_install(req, *a, **kw):
                installed_at[id(req)] = turn[0]
                return real_install(req, *a, **kw)

            sched._gate, sched._install_row = spying_gate, spying_install
            a, b = _enqueue(mgr, prompts[:2], budgets[:2])
            deadline = time.time() + 60
            while sched.lane_turns < 2 and time.time() < deadline:
                time.sleep(0.005)
            time.sleep(0.1)  # a few more turns: nothing may install on 2 free pages
            with sched._cond:
                assert sched.chunks_run == 4 and sched.admitted == 0
                assert len(sched._prefill_jobs) == 2 and sched.kv.pages_free == 2
                assert sched._lane_reserved_pages() == 6  # both, not the head alone
            arrival[0] = True
            (c,) = _enqueue(mgr, prompts[2:], budgets[2:])
            got = [_tokens(r) for r in (a, b, c)]
            _wait_drained(sched)
            assert got == want
            assert installed_at[id(a)] < installed_at[id(b)] <= installed_at[id(c)]
            assert installed_at[id(c)] > seen_at[id(c)]  # held back at its first gate
            assert sched.preemptions == 0
            sched.kv.decref(held[2:])
            stats = sched.kv.stats()
            assert stats.pages_live == 0
            assert stats.allocated_total == stats.freed_total > 0
            assert not sched._spill_ledger and sched._spill_arena is None
        finally:
            mgr.close()

    def test_cancelled_job_behind_the_head_is_retired(self, model_dir, monkeypatch):
        """A lane job that is not the head and whose consumer went away
        is retired in the next lane turn, and the reference it held on
        its cached prefix pages is dropped."""
        monkeypatch.setenv("LUMEN_VLM_PREFIX_BYTES", str(8 << 20))
        mgr = _make_lane_mgr(model_dir)
        try:
            sched = mgr._continuous
            assert sched.prefix is not None
            head, repeat = _lane_prompt(30), _lane_prompt(31)
            want = mgr.generate([ChatMessage(role="user", content=head)], max_new_tokens=6).tokens
            mgr.generate([ChatMessage(role="user", content=repeat)], max_new_tokens=6)
            _wait_drained(sched)
            sched.prefix.clear()
            assert sched.kv.stats().pages_live == 0
            mgr.generate([ChatMessage(role="user", content=repeat)], max_new_tokens=6)  # cached again
            _wait_drained(sched)
            cached = sched.kv.stats().pages_live
            assert cached == 2  # 45 tokens: two full pages in the cache

            jobs = []
            real_start = sched._start_chunk_job

            def cancelling_start(req):
                job = real_start(req)
                jobs.append((job, list(job.shared)))
                if len(jobs) == 2:
                    req.cancelled = True  # in the lane, behind the head
                return job

            sched._start_chunk_job = cancelling_start
            chunks0 = sched.chunks_run
            try:
                a, b = _enqueue(mgr, [head, repeat], [6, 6], prefix=True)
                assert _tokens(b) == []
                assert _tokens(a) == want
            finally:
                sched._start_chunk_job = real_start
            _wait_drained(sched)
            (_, head_shared), (job, shared) = jobs
            assert head_shared == [] and len(shared) == 2
            assert job.shared == []  # hold dropped
            assert all(sched.kv.refcount(p) == 1 for p in shared)  # the cache's own
            assert sched.chunks_run - chunks0 == 2  # the head's; the retired job ran none
            sched.prefix.clear()
            stats = sched.kv.stats()
            assert stats.pages_live == 0
            assert stats.allocated_total == stats.freed_total
        finally:
            mgr.close()


@pytest.mark.parametrize("which", ["cont_mgr", "latent_mgr"], ids=["grouped-query", "latent"])
def test_the_gauge_reports_the_default_page_with_no_setting(request, which):
    """One default page for both kinds of pool: with ``LUMEN_VLM_PAGE_SIZE``
    unset, a grouped-query and a latent manager size their pools by
    ``DEFAULT_PAGE_SIZE`` and say so in the ``vlm-continuous:*`` gauge."""
    import os

    assert "LUMEN_VLM_PAGE_SIZE" not in os.environ
    mgr = request.getfixturevalue(which)
    mgr = mgr[0] if isinstance(mgr, tuple) else mgr
    sched = mgr._continuous
    assert sched.gen.cfg.decoder.latent == (which == "latent_mgr")
    # the ``vlm-continuous:<name>`` provider itself: the registry's slot for a
    # name is last-writer-wins, and other tests build engines of the same name
    assert sched._gauge_fn()["page_size"] == DEFAULT_PAGE_SIZE == sched.kv.page_size == 64


class TestObservabilitySurface:
    def test_ttft_and_tps_histograms(self, cont_mgr):
        from lumen_tpu.utils.metrics import metrics

        before = metrics.snapshot()["tasks"].get("vlm.ttft", {}).get("count", 0)
        chunks = list(
            cont_mgr.generate_stream(
                [ChatMessage(role="user", content="observe me")], max_new_tokens=6
            )
        )
        final = chunks[-1]
        assert final.is_final
        assert final.metadata["ttft_ms"] > 0
        assert final.metadata["tokens_per_second"] > 0
        snap = metrics.snapshot()["tasks"]
        assert snap["vlm.ttft"]["count"] == before + 1
        assert snap["vlm.decode_tps"]["count"] >= 1

    def test_capability_reports_scheduler_and_kv_layout(self, cont_mgr):
        from lumen_tpu.serving.services.vlm_service import VlmService

        cap = VlmService(cont_mgr).capability()
        assert cap.extra["scheduler"] == "continuous"
        kv = cont_mgr._continuous.kv
        assert cap.extra["kv_layout"] == (
            f"paged(page={kv.page_size},pages={kv.pages_total},slots={cont_mgr.gen_slots})"
        )

    def test_a_config_that_names_a_scheduler_is_rejected_at_load(self):
        """One engine: ``backend_settings.scheduler`` is no field any more, and
        ``extra="forbid"`` answers a config that still asks for another."""
        import os

        import yaml

        from lumen_tpu.core.config import load_config, validate_config_dict
        from lumen_tpu.core.exceptions import ConfigError

        example = os.path.join(os.path.dirname(__file__), "..", "examples", "lumen-config-tp.yaml")
        assert load_config(example).services["vlm"].backend_settings.decode_block == 8
        with open(example, encoding="utf-8") as f:
            raw = yaml.safe_load(f)
        for name in ("continuous", "anything"):
            raw["services"]["vlm"]["backend_settings"]["scheduler"] = name
            with pytest.raises(ConfigError, match="backend_settings.scheduler"):
                validate_config_dict(raw)

    def test_max_concurrency_is_slots_times_engines(self, cont_mgr):
        from lumen_tpu.serving.services.vlm_service import VlmService

        svc = VlmService(cont_mgr)
        assert svc.capability().max_concurrency == cont_mgr.gen_slots == 4
        engines = cont_mgr._engines
        try:
            cont_mgr._engines = engines * 3  # a three-replica fleet's list
            assert svc.capability().max_concurrency == 12
        finally:
            cont_mgr._engines = engines

    def test_from_config_hands_the_decode_width_as_slots(self, monkeypatch):
        """A headline ``batch_size`` of 256 reaches the manager as sixteen
        slots with the configured block, and no argument of the engine that
        went (``scheduler``, ``gen_batch_*``) is passed."""
        from lumen_tpu.core.config import BackendSettings
        from lumen_tpu.serving.services import vlm_service

        seen = {}

        class Manager:
            def __init__(self, model_dir, **kw):
                seen.update(kw)

            def initialize(self):
                pass

        monkeypatch.setattr(vlm_service, "VLMManager", Manager)
        monkeypatch.setattr(vlm_service, "require_executable_runtime", lambda mc: None)

        class Cfg:
            models = {"vlm": type("M", (), {"model": "x/y"})()}
            backend_settings = BackendSettings(batch_size=256, decode_block=4)

        vlm_service.VlmService.from_config(Cfg, "/nowhere")
        assert seen["gen_slots"] == 16 and seen["gen_block"] == 4
        assert set(seen) == {"dtype", "warmup", "gen_slots", "gen_block", "quantize", "mesh_axes"}

    def test_batch_device_span_lands_on_request_trace(self, cont_mgr):
        from lumen_tpu.utils import trace as trace_mod

        t = trace_mod.Trace("vlm_generate")
        token = trace_mod.activate(t)
        try:
            cont_mgr.generate(
                [ChatMessage(role="user", content="traced")], max_new_tokens=4
            )
        finally:
            trace_mod.deactivate(token)
        names = [s[0] for s in t.spans]
        assert "batch.device" in names
        meta = next(s[5] for s in t.spans if s[0] == "batch.device")
        assert meta["rows"] >= 1 and 0 < meta["fill_pct"] <= 100


class TestKVSpillTier:
    """Preemption victims spill their KV pages to the host and resume
    without re-prefill; every failure on that path must degrade to the
    pre-spill ladder (requeue-and-redo or the typed retryable shed) with
    lease + page accounting that balances at drain — never a hang, leak,
    or wrong tokens."""

    #: short prompt for the OLDEST (greedy) row, longer prompt for the
    #: NEWEST (sampled) one: the long row grabs its extra page first, so
    #: it is the greedy row's later growth that fails — and preemption
    #: excludes the protected grower, making the sampled newest row the
    #: victim deterministically.
    SHORT, LONG = "hi", "gamma delta epsilon zeta eta theta"

    def _tiny(self, mgr):
        from lumen_tpu.models.vlm.continuous import ContinuousScheduler

        mgr._continuous.close()
        tiny = ContinuousScheduler(
            mgr.generator, mgr.params, slots=2, block=4,
            name=mgr.info.name, page_size=16, pages=6,
        )
        mgr._continuous = tiny
        mgr._engines = [tiny]
        return tiny

    def _make_mgr(self, model_dir):
        mgr = VLMManager(
            model_dir, dtype="float32", max_seq=128, max_new_cap=64,
            prefill_buckets=(16,),
            gen_slots=2, gen_block=4,
        )
        mgr.initialize()
        return mgr

    def _assert_balanced(self, sched):
        deadline = time.time() + 20
        while sched._slots and time.time() < deadline:
            time.sleep(0.01)
        assert not sched._slots
        stats = sched.kv.stats()
        assert stats.pages_live == 0
        assert stats.allocated_total == stats.freed_total
        assert not sched._spill_ledger
        assert sched._spill_bytes_live == 0
        if sched._spill_arena is not None:
            assert sched._spill_arena.live() == 0

    def _run_pair_greedy(self, mgr):
        results: dict[int, object] = {}
        barrier = threading.Barrier(2)

        def run(i, p):
            barrier.wait()
            results[i] = mgr.generate(
                [ChatMessage(role="user", content=p)], max_new_tokens=40
            )

        threads = [
            threading.Thread(target=run, args=(i, p))
            for i, p in enumerate(("alpha beta", "gamma delta"))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return results

    def test_spill_resume_greedy_token_identical_no_reprefill(self, model_dir):
        """Spilled + resumed greedy rows produce exactly the unpressured
        tokens, and resume does ZERO prefill device work — each request
        prefills once, ever."""
        mgr = self._make_mgr(model_dir)
        try:
            serial = [
                mgr.generate([ChatMessage(role="user", content=p)], max_new_tokens=40)
                for p in ("alpha beta", "gamma delta")
            ]
            tiny = self._tiny(mgr)
            calls: list[int] = []
            real_prefill = tiny.gen._prefill

            def counting_prefill(params, embeds, *a, **kw):
                calls.append(int(embeds.shape[0]))
                return real_prefill(params, embeds, *a, **kw)

            tiny.gen._prefill = counting_prefill
            try:
                results = self._run_pair_greedy(mgr)
            finally:
                tiny.gen._prefill = real_prefill
            for i, want in enumerate(serial):
                assert results[i].tokens == want.tokens, (i, results[i].text)
            need = sum(
                -(-(r.input_tokens + len(r.tokens) + 4) // 16) for r in serial
            )
            if need > 5:
                assert tiny.preemptions >= 1
                assert tiny.spills >= 1
                assert tiny.spill_resumes == tiny.spills  # every spill resumed
                assert tiny.preempt_redone == 0
                assert tiny.preempt_failed == 0
                # Zero re-prefill on resume: one prefill row per request.
                assert sum(calls) == 2, calls
            self._assert_balanced(tiny)
        finally:
            mgr.close()

    def _pressure_sampled_stream(self, mgr, tiny):
        """Oldest greedy row + newest sampled stream under a pool that
        cannot hold both; returns (chunks, stream_error)."""
        done: dict[str, object] = {}

        def run_greedy():
            done["r"] = mgr.generate(
                [ChatMessage(role="user", content=self.SHORT)], max_new_tokens=40
            )

        t = threading.Thread(target=run_greedy)
        t.start()
        deadline = time.time() + 30
        while tiny.admitted < 1 and time.time() < deadline:
            time.sleep(0.005)
        # Raw scheduler stream: token ids, one put per generated token —
        # the right level to assert exactly-once delivery. Near-greedy
        # sampling (temperature 0.01) exercises the sampled path without
        # the EOS-lottery flakiness of a hot temperature.
        e, pos, ln, ids, _n = mgr._prepare_inputs(
            [ChatMessage(role="user", content=self.LONG)], None, True
        )
        req = mgr._make_gen_request(e, pos, ln, ids, 40, 0.01, 1.0, True, 1.0)
        toks, err = [], None
        try:
            for tok in tiny.submit_stream(req):
                toks.append(int(tok))
        except Exception as exc:  # noqa: BLE001 - asserted by callers
            err = exc
        t.join()
        assert done["r"].tokens  # the greedy row always completes
        return req, toks, err

    def test_sampled_midstream_spill_resumes_stream(self, model_dir):
        """A sampled row preempted mid-stream RESUMES through the spill
        tier: the stream runs to completion and its delivered tokens are
        byte-identical to the row's final tokens (exactly once, in
        order) — the exact case the pre-spill engine failed."""
        mgr = self._make_mgr(model_dir)
        try:
            tiny = self._tiny(mgr)
            req, toks, err = self._pressure_sampled_stream(mgr, tiny)
            assert err is None, err
            tokens_np, n_gen, _eos = req.future.result(timeout=5)
            assert toks == [int(x) for x in tokens_np[:n_gen]]
            assert toks  # produced tokens across the preemption boundary
            if tiny.preemptions:
                assert tiny.spills >= 1
                assert tiny.spill_resumes == tiny.spills
                assert tiny.preempt_failed == 0
            self._assert_balanced(tiny)
        finally:
            mgr.close()

    def test_spill_disabled_sampled_midstream_sheds_typed(self, model_dir, monkeypatch):
        """LUMEN_VLM_SPILL_BYTES=0 disables the tier: a sampled
        mid-stream victim gets the typed retryable PreemptionShed (a
        QueueFull, so the serving layer attaches lumen-retry-after-ms)
        with a positive drain estimate — not a bare RuntimeError."""
        from lumen_tpu.utils.deadline import PreemptionShed, QueueFull

        monkeypatch.setenv("LUMEN_VLM_SPILL_BYTES", "0")
        mgr = self._make_mgr(model_dir)
        try:
            tiny = self._tiny(mgr)
            assert tiny._spill_budget == 0
            _req, _toks, err = self._pressure_sampled_stream(mgr, tiny)
            if not tiny.preemptions:
                pytest.skip("pool pressure never forced a preemption")
            assert tiny.spills == 0 and tiny.spill_resumes == 0
            if tiny.preempt_failed:
                assert isinstance(err, PreemptionShed)
                assert isinstance(err, QueueFull)  # overload machinery applies
                assert getattr(err, "retry_after_s", 0) > 0
            elif err is not None:
                raise err
            self._assert_balanced(tiny)
        finally:
            mgr.close()

    def test_kv_spill_fault_degrades_to_redo(self, model_dir):
        """An armed kv_spill fault fails every export: greedy victims
        fall back to requeue-and-redo with tokens still exactly right,
        and nothing leaks into the ledger."""
        from lumen_tpu.testing import faults

        mgr = self._make_mgr(model_dir)
        faults.configure("kv_spill")
        try:
            serial = [
                mgr.generate([ChatMessage(role="user", content=p)], max_new_tokens=40)
                for p in ("alpha beta", "gamma delta")
            ]
            tiny = self._tiny(mgr)
            results = self._run_pair_greedy(mgr)
            for i, want in enumerate(serial):
                assert results[i].tokens == want.tokens, (i, results[i].text)
            need = sum(
                -(-(r.input_tokens + len(r.tokens) + 4) // 16) for r in serial
            )
            if need > 5:
                assert tiny.preemptions >= 1
                assert tiny.spills == 0
                assert tiny.preempt_redone >= 1
            self._assert_balanced(tiny)
        finally:
            faults.reset()
            mgr.close()

    def test_kv_resume_fault_degrades_to_redo(self, model_dir):
        """An armed kv_resume fault kills the re-install of a parked
        record: the row restarts from its prompt (greedy parity intact)
        and the dead record's lease is freed — accounting still balances."""
        from lumen_tpu.testing import faults

        mgr = self._make_mgr(model_dir)
        faults.configure("kv_resume", times=1)
        try:
            serial = [
                mgr.generate([ChatMessage(role="user", content=p)], max_new_tokens=40)
                for p in ("alpha beta", "gamma delta")
            ]
            tiny = self._tiny(mgr)
            results = self._run_pair_greedy(mgr)
            for i, want in enumerate(serial):
                assert results[i].tokens == want.tokens, (i, results[i].text)
            need = sum(
                -(-(r.input_tokens + len(r.tokens) + 4) // 16) for r in serial
            )
            if need > 5:
                assert tiny.spills >= 1
                assert tiny.preempt_redone >= 1  # the faulted resume
            self._assert_balanced(tiny)
        finally:
            faults.reset()
            mgr.close()

    def test_drop_spill_idempotent_and_lease_balance(self, cont_mgr):
        """Every retirement path calls _drop_spill; it must be idempotent
        and return the lease so arena live() hits zero at drain."""
        from lumen_tpu.models.vlm.continuous import _Request, _SpillRecord

        sched = cont_mgr._continuous
        lease = sched._get_arena().acquire(1 << 10)
        assert lease is not None
        req = _Request(
            embeds=None, positions=None, length=None, prompt_ids=None,
            max_new=1, temperature=0.0, top_p=1.0, do_sample=False,
            repetition_penalty=1.0,
        )
        rec = _SpillRecord(
            n_pages=1, n_pad=1, nbytes=1 << 10, treedef=None,
            crc=0, cur_tok=0, cur_len=0, n_gen=0, rng=None, lease=lease,
        )
        req.spill = rec
        sched._spill_ledger[id(req)] = rec
        sched._spill_bytes_live += rec.nbytes
        assert sched._drop_spill(req) is rec
        assert sched._drop_spill(req) is None  # idempotent
        assert not sched._spill_ledger
        assert sched._spill_bytes_live == 0
        assert sched._spill_arena.live() == 0

    def test_spill_gauges_surface_ledger(self, model_dir):
        # Own manager (not the module fixture): gauge registration is
        # last-writer-wins by name, so this test must hold the newest
        # same-named engine while it reads the snapshot.
        from lumen_tpu.utils.metrics import metrics

        mgr = self._make_mgr(model_dir)
        try:
            gauges = metrics.snapshot()["gauges"][f"vlm-continuous:{mgr.info.name}"]
            for key in (
                "spill_entries", "spill_bytes", "spill_bytes_budget",
                "spill_max_entries", "spilled", "spill_resumed",
                "spill_fallbacks", "spill_denied", "preempt_redone",
                "preempt_failed",
            ):
                assert key in gauges, key
            assert gauges["spill_entries"] == 0
            assert gauges["spill_bytes_budget"] == 256 << 20
        finally:
            mgr.close()


class TestBatchedAdmission:
    """A burst of same-bucket arrivals admits via batched prefills
    (ADMIT_BUCKETS), not one batch-1 prefill per request (round-4 verdict:
    serialized admission starves the slot pool under load)."""

    def test_burst_prefill_count_and_parity(self, model_dir):
        mgr = VLMManager(
            model_dir,
            dtype="float32",
            max_seq=128,
            max_new_cap=16,
            prefill_buckets=(16,),
            gen_slots=8,
            gen_block=4,
        )
        mgr.initialize()
        try:
            sched = mgr._continuous
            prompts = [f"prompt number {i}" for i in range(8)]
            serial = [
                mgr.generate([ChatMessage(role="user", content=p)], max_new_tokens=6)
                for p in prompts
            ]

            calls = []
            real_prefill = sched.gen._prefill

            def counting_prefill(params, embeds, *a, **kw):
                calls.append(int(embeds.shape[0]))
                return real_prefill(params, embeds, *a, **kw)

            sched.gen._prefill = counting_prefill
            try:
                # The backlog is fully formed before the scheduler thread
                # wakes (see _enqueue), so grouping is deterministic.
                reqs = _enqueue(mgr, prompts, [6] * len(prompts))
                results = [r.future.result(timeout=120) for r in reqs]
            finally:
                sched.gen._prefill = real_prefill

            for i, want in enumerate(serial):
                tokens, n_gen, _eos = results[i]
                assert [int(t) for t in tokens[:n_gen]] == want.tokens, (i, want.text)
            # 8 same-bucket requests, fully backlogged, 8 free slots ->
            # exactly one ADMIT_BUCKETS group of 8, one batched prefill.
            assert calls == [8], calls
        finally:
            mgr.close()


class TestPrefixReuseAndSpec:
    """Copy-on-write prefix KV reuse + prompt-lookup speculative decoding.

    Unconfigured engines must be byte-identical to the pre-feature
    scheduler: no cache allocated, no drafter built, no new gauge or
    metadata keys. Configured engines must turn a repeat-prefix prefill
    into a block-table attach plus ONE suffix-only chunk (zero full
    prefills), and speculative greedy decoding must be token-identical
    to the plain step path while actually accepting drafted tokens.
    """

    #: 20 live tokens under the (16, 32) buckets -> exactly one full
    #: cached page (16 tokens), hit coverage 16/20 = 0.8. The repeated
    #: tail also gives the prompt-lookup drafter n-gram matches.
    PROMPT = "the quick brown fox jumps over the lazy dog again and again and again"
    #: by page; at 64 tokens the same: 80 live tokens in a 96 bucket, one
    #: full cached page, hit coverage 64/80 = 0.8
    PROMPTS = {16: PROMPT, 64: " ".join([PROMPT] * 5 + ["the quick brown fox"])}

    def _make_mgr(self, model_dir, page: int = 16, **kw):
        """A manager at a stated page: what a prompt shares through the
        cache, and where a verify window crosses a page, is counted in
        pages."""
        cfg = dict(
            dtype="float32", max_seq=128, max_new_cap=16,
            prefill_buckets=(16, 32),
            gen_slots=4, gen_block=4,
        )
        cfg.update(kw)
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("LUMEN_VLM_PAGE_SIZE", str(page))  # read once, at construction
            mgr = VLMManager(model_dir, **cfg)
            mgr.initialize()
        assert mgr._continuous.page_size == page
        return mgr

    def _count_prefills(self, sched):
        """Wrap the generator's prefill entry points with call counters;
        returns (full_calls, chunk_calls, restore_fn)."""
        full, chunk = [], []
        real_prefill, real_chunk = sched.gen._prefill, sched.gen._prefill_chunk

        def counting_prefill(*a, **kw):
            full.append(1)
            return real_prefill(*a, **kw)

        def counting_chunk(*a, **kw):
            chunk.append(1)
            return real_chunk(*a, **kw)

        sched.gen._prefill = counting_prefill
        sched.gen._prefill_chunk = counting_chunk

        def restore():
            sched.gen._prefill = real_prefill
            sched.gen._prefill_chunk = real_chunk

        return full, chunk, restore

    def test_unconfigured_engine_identical_path(self, cont_mgr):
        """Neither knob set (conftest strips them): no cache object, no
        drafter state, gauges and response metadata carry no new keys."""
        sched = cont_mgr._continuous
        assert sched.prefix is None
        assert sched.spec_k == 0
        res = cont_mgr.generate(
            [ChatMessage(role="user", content=self.PROMPT)], max_new_tokens=4
        )
        assert "prefix_hit" not in res.metadata
        assert "spec_accept_rate" not in res.metadata
        g = sched._gauge_fn()
        for key in ("prefix_entries", "prefix_hits", "spec_k", "spec_accept_rate"):
            assert key not in g, key

    @pytest.mark.parametrize("page", [16, 64])
    def test_prefix_hit_skips_covered_prefill(self, model_dir, monkeypatch, page):
        """Second identical prompt admits via the cache: zero full
        prefills, ONE suffix-only chunk, identical tokens, and the final
        metadata reports the covered fraction."""
        monkeypatch.setenv("LUMEN_VLM_PREFIX_BYTES", str(8 << 20))
        mgr = self._make_mgr(model_dir, page=page, prefill_buckets=(16, 32, 96))
        try:
            sched = mgr._continuous
            assert sched.prefix is not None
            msgs = [ChatMessage(role="user", content=self.PROMPTS[page])]
            assert mgr._prepare_inputs(msgs, None, True)[4] == 5 * page // 4  # live tokens
            hits0, miss0 = sched.prefix_hits, sched.prefix_misses
            first = mgr.generate(msgs, max_new_tokens=8)
            assert sched.prefix_misses == miss0 + 1
            assert sched.prefix_hits == hits0
            assert first.metadata.get("prefix_hit") == 0.0  # enabled, cold
            assert len(sched.prefix) >= 1  # prompt pages inserted

            full, chunk, restore = self._count_prefills(sched)
            try:
                second = mgr.generate(msgs, max_new_tokens=8)
            finally:
                restore()
            assert second.tokens == first.tokens, (second.text, first.text)
            assert sched.prefix_hits == hits0 + 1
            assert sched.prefix_hit_pages >= 1
            # The covered prefix never touches the device again: the hit
            # admission runs no full prefill and exactly one suffix chunk.
            assert full == [], full
            assert len(chunk) == 1, chunk
            assert second.metadata.get("prefix_hit") == 0.8  # 16/20, 64/80 tokens
            assert sched.prefix_hit_pages == 1

            g = sched._gauge_fn()
            assert g["prefix_entries"] >= 1
            assert g["prefix_hits"] == sched.prefix_hits
            assert g["pages_shared"] >= 0
        finally:
            mgr.close()

    @pytest.mark.parametrize("page", [16, 64])
    def test_spec_greedy_token_identical_with_acceptance(
        self, model_dir, monkeypatch, cont_mgr, page
    ):
        """LUMEN_VLM_SPEC_K=4: greedy output matches the non-speculative
        engine token for token, with real proposals AND acceptances (the
        tiny model's repetitive output is ideal prompt-lookup traffic)."""
        monkeypatch.setenv("LUMEN_VLM_SPEC_K", "4")
        mgr = self._make_mgr(model_dir, page=page)
        try:
            sched = mgr._continuous
            assert sched.spec_k == 4 and sched._spec_active()
            msgs = [ChatMessage(role="user", content=self.PROMPT)]
            base = cont_mgr.generate(msgs, max_new_tokens=12)
            res = mgr.generate(msgs, max_new_tokens=12)
            assert res.tokens == base.tokens, (res.text, base.text)
            assert sched.spec_turns >= 1
            assert sched.spec_proposed > 0
            assert sched.spec_accepted > 0
            rate = res.metadata.get("spec_accept_rate")
            assert rate is not None and 0.0 < rate <= 1.0
            assert "spec_accept_rate" not in base.metadata
            g = sched._gauge_fn()
            assert g["spec_k"] == 4
            assert g["spec_accepted"] == sched.spec_accepted
            assert g["spec_disabled"] == 0
        finally:
            mgr.close()

    def test_draft_row_prompt_lookup(self, cont_mgr, monkeypatch):
        """Drafter unit semantics: earliest n-gram continuation, greedy
        rows only, capped at spec_k tokens."""
        from types import SimpleNamespace

        sched = cont_mgr._continuous
        monkeypatch.setattr(sched, "spec_k", 4)
        monkeypatch.setattr(sched, "spec_ngram", 3)

        def slot(toks, tokens, pending, sample=False):
            return SimpleNamespace(
                request=SimpleNamespace(do_sample=sample),
                text_toks=toks, tokens=tokens, pending_tok=pending,
            )

        # Cycling text: tail (7, 8) first occurs at index 1 -> the draft
        # replays the full continuation 9, 7, 8, 9.
        s = slot([5, 7, 8, 9, 7, 8, 9, 7], [8], 9)
        assert sched._draft_row(s) == [7, 8, 9, 7]
        # No recurring n-gram -> no draft.
        assert sched._draft_row(slot([1, 2, 3, 4], [], 5)) == []
        # Sampled rows never draft (verify is argmax-identity only).
        assert sched._draft_row(slot([5, 7, 8, 9, 7, 8], [], 9, sample=True)) == []
        # Before the first step there is no pending token to extend.
        assert sched._draft_row(slot([7, 8, 7, 8], [], None)) == []

    def test_spec_auto_disable_below_floor(self, cont_mgr, monkeypatch):
        """Acceptance below LUMEN_VLM_SPEC_MIN_RATE after a fair sample
        permanently disables drafting (pure counter logic — exercised
        here without burning a low-acceptance end-to-end run)."""
        sched = cont_mgr._continuous
        monkeypatch.setattr(sched, "spec_k", 4)
        monkeypatch.setattr(sched, "spec_min_rate", 0.2)
        monkeypatch.setattr(sched, "spec_disabled", False)
        # Fair sample, healthy acceptance: stays on.
        monkeypatch.setattr(sched, "spec_proposed", 100)
        monkeypatch.setattr(sched, "spec_accepted", 30)
        sched._spec_try_disable()
        assert not sched.spec_disabled and sched._spec_active()
        # Same sample size, acceptance below the floor: off for good.
        monkeypatch.setattr(sched, "spec_accepted", 10)
        sched._spec_try_disable()
        assert sched.spec_disabled and not sched._spec_active()
        # Too few proposals is never enough evidence to disable.
        monkeypatch.setattr(sched, "spec_disabled", False)
        monkeypatch.setattr(sched, "spec_proposed", 10)
        monkeypatch.setattr(sched, "spec_accepted", 0)
        sched._spec_try_disable()
        assert not sched.spec_disabled

    def test_shared_prefix_spill_resume_balanced(self, model_dir, monkeypatch):
        """Preemption under sharing: BOTH concurrent rows attach the same
        cached prefix page, so whichever row the preemptor picks holds
        shared pages — the spill must export only the private suffix,
        re-attach the shared prefix on resume, and the page accounting
        must balance exactly at drain."""
        monkeypatch.setenv("LUMEN_VLM_PREFIX_BYTES", str(8 << 20))
        mgr = self._make_mgr(
            model_dir, max_new_cap=64, gen_slots=2, gen_block=4
        )
        try:
            msgs = [ChatMessage(role="user", content=self.PROMPT)]
            want = mgr.generate(msgs, max_new_tokens=40)

            from lumen_tpu.models.vlm.continuous import ContinuousScheduler

            mgr._continuous.close()
            tiny = ContinuousScheduler(
                mgr.generator, mgr.params, slots=2, block=4,
                name=mgr.info.name, page_size=16, pages=6,
            )
            mgr._continuous = tiny
            mgr._engines = [tiny]
            assert tiny.prefix is not None

            # Seed the tiny engine's cache: the follow-up pair then admits
            # through the hit path sharing ONE physical prefix page.
            seeded = mgr.generate(msgs, max_new_tokens=40)
            assert seeded.tokens == want.tokens

            full, chunk, restore = self._count_prefills(tiny)
            results: dict[int, object] = {}
            barrier = threading.Barrier(2)

            def run(i):
                barrier.wait()
                results[i] = mgr.generate(msgs, max_new_tokens=40)

            threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
            try:
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
            finally:
                restore()

            for i in range(2):
                assert results[i].tokens == want.tokens, (i, results[i].text)
            # Both admissions were hits, and resume never re-prefills:
            # zero full prefills, one suffix chunk per request — across
            # a forced preemption.
            assert full == [], full
            assert len(chunk) == 2, chunk
            assert tiny.prefix_hits >= 2
            # 2 rows x 4 pages + 1 cached page > 5 usable pages: the pool
            # cannot hold both, so preemption (of a shared-prefix holder —
            # both rows share) is guaranteed, and must ride the spill tier.
            assert tiny.preemptions >= 1
            assert tiny.spills >= 1
            assert tiny.spill_resumes == tiny.spills
            assert tiny.preempt_failed == 0

            deadline = time.time() + 20
            while tiny._slots and time.time() < deadline:
                time.sleep(0.01)
            assert not tiny._slots
            tiny.prefix.clear()  # cache holds the last references
            stats = tiny.kv.stats()
            assert stats.pages_live == 0
            assert stats.allocated_total == stats.freed_total
            assert not tiny._spill_ledger
        finally:
            mgr.close()


class TestWaitCounters:
    """The cumulative sums behind ``admit_wait``/``prefill_lane``/``server
    ttft``: written by the loop thread, read as ratios of deltas."""

    def _gauges(self, sched):
        # The provider itself: the registry's slot for this name is
        # last-writer-wins, and other tests build engines of the same name.
        return sched._gauge_fn()

    def test_every_admission_books_one_wait_and_one_first_token(self, cont_mgr):
        sched = cont_mgr._continuous
        before = self._gauges(sched)
        prompts = ["alpha", "beta gamma", "delta", "epsilon zeta eta", "theta", "iota"]
        threads = [
            threading.Thread(
                target=cont_mgr.generate,
                args=([ChatMessage(role="user", content=p)],),
                kwargs={"max_new_tokens": 6},
            )
            for p in prompts
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
        after = self._gauges(sched)
        # drained: every request taken out of the queue was installed once
        assert after["pending_count"] == after["admitted"]
        assert after["pending_count"] - before["pending_count"] == len(prompts)
        assert after["first_token_count"] - before["first_token_count"] == len(prompts)
        assert after["rows_stepped"] - before["rows_stepped"] >= after["blocks_run"] - before["blocks_run"] > 0
        for key in ("pending_ms_sum", "first_token_ms_sum", "lane_ms_sum"):
            assert after[key] >= before[key]
        # six requests on four slots: somebody waited for a slot, and a
        # first token comes no sooner than the admission it follows
        waited = after["pending_ms_sum"] - before["pending_ms_sum"]
        first = after["first_token_ms_sum"] - before["first_token_ms_sum"]
        assert 0 < waited < first
        assert after["lane_jobs"] == before["lane_jobs"]  # short prompts never enter the lane

    def test_lane_jobs_book_lane_time(self, model_dir, monkeypatch):
        monkeypatch.setenv("LUMEN_VLM_PREFILL_CHUNK", "32")
        monkeypatch.setenv("LUMEN_VLM_PAGE_SIZE", "16")  # a chunk is whole pages
        mgr = VLMManager(
            model_dir, dtype="float32", max_seq=256, max_new_cap=16,
            prefill_buckets=(64,), gen_slots=2, gen_block=4,
        )
        mgr.initialize()
        try:
            msgs = [ChatMessage(role="user", content="word " * 40)]  # the 64 bucket: two chunks
            for _ in range(2):
                mgr.generate(msgs, max_new_tokens=4)
            g = self._gauges(mgr._continuous)
            assert g["lane_jobs"] == 2 == g["pending_count"] == g["admitted"] == g["first_token_count"]
            assert g["lane_ms_sum"] > 0
            # submit -> lane entry -> first token sampled -> first token out
            assert g["pending_ms_sum"] + g["lane_ms_sum"] <= g["first_token_ms_sum"]
        finally:
            mgr.close()

    def test_the_step_programs_name_is_the_one_the_benchmark_reads(self, cont_mgr):
        """``vlm_step_hbm_pct`` finds the decode program's runs on the
        device's ``XLA Modules`` line by ``step_block``: pin the name XLA
        gives the jitted step."""
        import re

        sched = cont_mgr._continuous
        lowered = sched.gen._step_block.lower(
            sched.params, sched.pool, sched.kv.block_tables[:, :1], sched._rng, block=sched.block
        )
        module = re.search(r"module @(\S+)", lowered.as_text()).group(1)
        assert module == "jit__step_block_impl" and re.search("step_block", module)

    def test_the_lanes_counters_are_the_ones_the_benchmark_reads(self, lane_mgr, lane_burst):
        """``lane_chunks_per_turn`` is a data file: pin the gauge fields it
        names, and what their ratio says (1.0 for a lone lane job, more
        when a turn advances several)."""
        import json
        import os

        from benchmark.cells import load_module

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "benchmark", "layer_metrics", "lane_chunks_per_turn.json")) as f:
            spec = json.load(f)
        reader = load_module("readers", spec["reader"])
        sched = lane_mgr._continuous
        name = f"vlm-continuous:{sched.name}"
        assert name.startswith(spec["gauge"])
        assert {spec["numerator"], spec["denominator"]} <= set(self._gauges(sched))

        def ratio(before, after):
            ctx = {"result": {"before": {"gauges": {name: before}}, "after": {"gauges": {name: after}}}}
            return reader.read(ctx, spec)

        before = self._gauges(sched)
        assert ratio(before, before) is None  # nothing went through the lane
        lane_mgr.generate([ChatMessage(role="user", content=_lane_prompt(40))], max_new_tokens=4)
        assert ratio(before, self._gauges(sched)) == 1.0
        assert ratio(lane_burst["before"], lane_burst["after"]) == 4.0


@pytest.fixture(scope="module")
def profiled(cont_mgr, tmp_path_factory):
    """ONE profiler session over a few generations and a few micro-batches:
    ``lumen:`` events by (plane, line), and how far ``blocks_run`` rose."""
    import glob
    import os

    import jax
    import numpy as np
    from jax.profiler import ProfileData

    from lumen_tpu.runtime.batcher import MicroBatcher

    sched = cont_mgr._continuous
    cont_mgr.generate([ChatMessage(role="user", content="warm")], max_new_tokens=4)
    batcher = MicroBatcher(lambda tree, n: tree, max_batch=4, max_latency_ms=2, name="prof-b").start()
    batcher(np.zeros(2, np.float32))
    trace_dir = str(tmp_path_factory.mktemp("profile"))
    blocks0 = sched.blocks_run
    jax.profiler.start_trace(trace_dir)
    try:
        for prompt in ("profile me", "the quick brown fox"):
            cont_mgr.generate([ChatMessage(role="user", content=prompt)], max_new_tokens=8)
        for i in range(3):
            batcher(np.full(2, i, np.float32))
        time.sleep(0.05)  # the fetch worker leaves batch.settle after the caller wakes
    finally:
        jax.profiler.stop_trace()
        batcher.close()
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            events = [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name, dict(e.stats))
                      for e in line.events if e.name.startswith("lumen:")]
            if events:
                lines[(plane.name, i)] = sorted(events)
    return {"lines": lines, "blocks": sched.blocks_run - blocks0}


class TestFeederThreadPhases:
    def _named(self, profiled, name):
        return [e for events in profiled["lines"].values() for e in events if e[2] == name]

    def test_one_dispatch_phase_for_every_block(self, profiled):
        assert profiled["blocks"] > 0
        for name in ("dispatch", "fetch", "emit", "prepare"):
            assert len(self._named(profiled, f"lumen:vlm.block.{name}")) == profiled["blocks"], name
        steps = [e[3]["step"] for e in self._named(profiled, "lumen:vlm.block.dispatch")]
        assert steps == list(range(steps[0], steps[0] + len(steps)))  # the numbers batch.device spans carry
        assert all(e[3]["rows"] >= 1 for e in self._named(profiled, "lumen:vlm.block.dispatch"))

    def test_a_dispatch_phase_says_whether_a_live_row_draws(self, profiled, cont_mgr):
        """Every generation of the session is greedy: ``sampling`` is 0 in
        the profiler's record, and the gauge counted each block greedy."""
        assert {e[3]["sampling"] for e in self._named(profiled, "lumen:vlm.block.dispatch")} == {0}
        sched = cont_mgr._continuous
        assert 0 < sched.blocks_greedy <= sched.blocks_run

    def test_admissions_name_their_requests(self, profiled):
        admits = self._named(profiled, "lumen:vlm.admit")
        assert len(admits) == 2 and all(e[3]["rows"] == 1 and e[3]["kind"] == "group" for e in admits)
        assert len({e[3]["rids"] for e in admits}) == 2

    def test_batcher_phases_share_the_batch_number(self, profiled):
        by_seq = {}
        for name in ("window", "stack", "dispatch", "fetch", "settle"):
            for e in self._named(profiled, f"lumen:batch.{name}"):
                assert e[3]["batcher"] == "prof-b"
                by_seq.setdefault(e[3]["seq"], set()).add(name)
        whole = [seq for seq, names in by_seq.items() if len(names) == 5]
        assert len(whole) == 3, by_seq  # the three batches wholly inside the session
        assert not self._named(profiled, "lumen:batch.put")  # no mesh placement on this batcher

    def test_no_phase_encloses_another_on_one_thread(self, profiled):
        for key, events in profiled["lines"].items():
            for a, b in zip(events, events[1:]):
                assert b[0] >= a[1], (key, a, b)
        threads = {key for key, events in profiled["lines"].items()}
        assert len(threads) >= 3  # the scheduler, the collector, the fetch worker
