"""Batched VLM generation tests (round-1 verdict item 6: replace the
single-flight lock with batched decode).

Covers: per-sample sampling params (ops/sampling), per-sample stop caps,
the engine stepping concurrent generates in shared decode blocks, and
correctness of batched results vs serial B=1 runs.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lumen_tpu.models.vlm import ChatMessage, VLMManager
from lumen_tpu.ops.sampling import apply_repetition_penalty, sample
from lumen_tpu.utils.metrics import metrics
from tests.test_vlm import make_vlm_model_dir


class TestPerSampleSampling:
    def test_mixed_greedy_and_sampled_rows(self):
        rng = jax.random.PRNGKey(0)
        logits = jnp.asarray(
            [[5.0, 4.9, 0.0, 0.0], [5.0, 4.9, 0.0, 0.0]], jnp.float32
        )
        # row 0 greedy (temp 0), row 1 hot sampling
        temps = jnp.asarray([0.0, 5.0])
        outs = set()
        for i in range(40):
            ids = sample(
                jax.random.fold_in(rng, i),
                logits,
                temperature=temps,
                top_p=jnp.asarray([1.0, 1.0]),
                do_sample=jnp.asarray([True, True]),
            )
            assert int(ids[0]) == 0  # greedy row always argmax
            outs.add(int(ids[1]))
        assert len(outs) > 1  # hot row actually samples

    def test_per_sample_top_p(self):
        rng = jax.random.PRNGKey(1)
        # top_p tiny -> nucleus = {argmax} even at high temperature
        logits = jnp.asarray([[3.0, 2.9, 2.8, 0.0]] * 2, jnp.float32)
        for i in range(25):
            ids = sample(
                jax.random.fold_in(rng, i),
                logits,
                temperature=jnp.asarray([8.0, 8.0]),
                top_p=jnp.asarray([1e-6, 1.0]),
                do_sample=jnp.asarray([True, True]),
            )
            assert int(ids[0]) == 0

    def test_per_sample_repetition_penalty(self):
        logits = jnp.asarray([[2.0, 1.0], [2.0, 1.0]], jnp.float32)
        mask = jnp.asarray([[True, False], [True, False]])
        out = apply_repetition_penalty(logits, mask, jnp.asarray([2.0, 1.0]))
        assert float(out[0, 0]) == pytest.approx(1.0)  # penalized
        assert float(out[1, 0]) == pytest.approx(2.0)  # penalty 1 = no-op
        assert float(out[0, 1]) == pytest.approx(1.0)  # unmasked untouched


@pytest.fixture(scope="module")
def manager(tmp_path_factory):
    model_dir = make_vlm_model_dir(tmp_path_factory.mktemp("vlmb"))
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


def _engine_gauges(mgr) -> dict:
    return metrics.snapshot()["gauges"][f"vlm-continuous:{mgr.info.name}"]


class TestBatchedGeneration:
    def test_concurrent_greedy_matches_serial(self, manager):
        """N concurrent generates return exactly what serial runs return,
        and the engine actually stepped them in shared decode blocks."""
        prompts = ["hello", "the quick brown fox", "a", "count to three"]
        serial = [
            manager.generate(
                [ChatMessage(role="user", content=p)], max_new_tokens=12
            )
            for p in prompts
        ]

        before = _engine_gauges(manager)
        results: dict[int, object] = {}
        errors: list[Exception] = []
        barrier = threading.Barrier(len(prompts))

        def run(i, p):
            try:
                barrier.wait()
                results[i] = manager.generate(
                    [ChatMessage(role="user", content=p)], max_new_tokens=12
                )
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [
            threading.Thread(target=run, args=(i, p)) for i, p in enumerate(prompts)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        for i, want in enumerate(serial):
            assert results[i].tokens == want.tokens, (i, results[i].text, want.text)
            assert results[i].finish_reason == want.finish_reason
        after = _engine_gauges(manager)
        assert after["admitted"] - before["admitted"] == len(prompts)
        rows = after["rows_stepped"] - before["rows_stepped"]
        blocks = after["blocks_run"] - before["blocks_run"]
        assert blocks < rows, "concurrent requests never shared a decode block"

    def test_mixed_max_new_tokens(self, manager):
        """Batched rows stop at their own budget."""
        short = manager.generate(
            [ChatMessage(role="user", content="hello")], max_new_tokens=2
        )
        long = manager.generate(
            [ChatMessage(role="user", content="hello")], max_new_tokens=8
        )
        # random-weight model never emits EOS this early; budgets honored
        if short.finish_reason == "length":
            assert len(short.tokens) == 2
        if long.finish_reason == "length":
            assert len(long.tokens) == 8
        assert short.tokens == long.tokens[: len(short.tokens)]

        barrier = threading.Barrier(2)
        results: dict[int, object] = {}

        def run(i, budget):
            barrier.wait()
            results[i] = manager.generate(
                [ChatMessage(role="user", content="hello")], max_new_tokens=budget
            )

        threads = [
            threading.Thread(target=run, args=(0, 2)),
            threading.Thread(target=run, args=(1, 8)),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert results[0].tokens == short.tokens
        assert results[1].tokens == long.tokens

    def test_zero_budget_row_in_batch_emits_nothing(self, manager):
        """A max_new_tokens=0 request batched with live rows must return 0
        tokens, exactly like a solo run (review finding: done-init)."""
        barrier = threading.Barrier(2)
        results: dict[int, object] = {}

        def run(i, budget):
            barrier.wait()
            results[i] = manager.generate(
                [ChatMessage(role="user", content="hello")], max_new_tokens=budget
            )

        threads = [
            threading.Thread(target=run, args=(0, 0)),
            threading.Thread(target=run, args=(1, 8)),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert results[0].tokens == []
        assert len(results[1].tokens) > 0

    def test_different_buckets_never_mixed(self, manager):
        """Requests landing in different prompt buckets run as separate
        programs but still all succeed."""
        barrier = threading.Barrier(2)
        results: dict[int, object] = {}

        def run(i, content):
            barrier.wait()
            results[i] = manager.generate(
                [ChatMessage(role="user", content=content)], max_new_tokens=4
            )

        long_prompt = " ".join(["word"] * 20)  # > 16-token bucket
        threads = [
            threading.Thread(target=run, args=(0, "hi")),
            threading.Thread(target=run, args=(1, long_prompt)),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(results) == 2
        for r in results.values():
            assert len(r.tokens) > 0

    def test_stream_concurrent_with_generate(self, manager):
        """Streams no longer serialize behind a global lock."""
        barrier = threading.Barrier(2)
        out: dict[str, object] = {}

        def run_stream():
            barrier.wait()
            chunks = list(
                manager.generate_stream(
                    [ChatMessage(role="user", content="hello")], max_new_tokens=4
                )
            )
            out["stream"] = chunks

        def run_gen():
            barrier.wait()
            out["gen"] = manager.generate(
                [ChatMessage(role="user", content="hello")], max_new_tokens=4
            )

        threads = [threading.Thread(target=run_stream), threading.Thread(target=run_gen)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert out["stream"][-1].is_final
        stream_text = "".join(c.text for c in out["stream"] if not c.is_final)
        assert stream_text == out["gen"].text

    def test_close_rejects_new_submissions(self, tmp_path):
        model_dir = make_vlm_model_dir(tmp_path)
        mgr = VLMManager(
            model_dir, dtype="float32", max_seq=128, max_new_cap=8, prefill_buckets=(16,)
        )
        mgr.initialize()
        mgr.close()
        with pytest.raises(RuntimeError):
            mgr.generate([ChatMessage(role="user", content="hi")], max_new_tokens=1)


class TestKvRightSizing:
    """The fused path allocates its KV cache at the smallest seq bucket
    covering prompt + budget, not worst-case max_seq (round-4 verdict:
    worst-case per-slot KV blocks scaling batch/slots)."""

    def test_bucket_selection(self):
        import jax.numpy as jnp

        from lumen_tpu.models.vlm.generate import Generator
        from lumen_tpu.models.vlm.modeling import VLMConfig, VLMModel

        cfg = VLMConfig.tiny()
        gen = Generator(
            VLMModel(cfg), cfg, max_seq=512, max_new_cap=16,
            cache_dtype=jnp.float32, seq_buckets=(64, 128),
        )
        assert gen.seq_buckets == (64, 128, 512)

    def test_small_request_uses_small_cache_same_tokens(self):
        """Same request through seq_buckets=(64,) vs max_seq-only -> same
        tokens, and the bucketed path's cache is provably smaller (watch
        the kv_len the compiled call receives)."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from lumen_tpu.models.vlm.generate import Generator
        from lumen_tpu.models.vlm.modeling import VLMConfig, VLMModel

        cfg = VLMConfig.tiny()
        model = VLMModel(cfg)
        params = model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, 4), jnp.int32),
            jnp.zeros((1, cfg.vision.image_size, cfg.vision.image_size, 3)),
        )["params"]

        rng = np.random.RandomState(3)
        ids = rng.randint(3, 200, size=(1, 12)).astype(np.int32)

        def run(gen):
            embeds = model.apply({"params": params}, jnp.asarray(ids), method=VLMModel.embed_tokens)
            positions = jnp.broadcast_to(jnp.arange(12), (1, 12))
            out = gen.generate(
                params, embeds, positions, jnp.asarray([12], jnp.int32),
                jnp.asarray(ids), jax.random.PRNGKey(0), max_new_tokens=8,
            )
            n = int(out.n_generated[0])
            return [int(t) for t in np.asarray(out.tokens[0][:n])]

        big = Generator(model, cfg, max_seq=512, max_new_cap=16, cache_dtype=jnp.float32)
        small = Generator(
            model, cfg, max_seq=512, max_new_cap=16, cache_dtype=jnp.float32,
            seq_buckets=(64,),
        )
        # capture the kv_len actually passed to the compiled program
        seen_kv = []
        orig = small._generate

        def spy(*a, **kw):
            seen_kv.append(kw.get("kv_len"))
            return orig(*a, **kw)

        small._generate = spy
        assert run(big) == run(small)
        assert seen_kv == [64]
