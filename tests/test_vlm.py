"""VLM family tests: KV-cache decode parity, image-token splice, fused
generation vs a naive full-recompute loop, streaming, chat templating,
checkpoint conversion, manager pipeline, and the gRPC service handlers."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lumen_tpu.models.vlm import (
    ChatMessage,
    Generator,
    VLMConfig,
    VLMManager,
    VLMModel,
    merge_image_embeddings,
    render_chat,
)


@pytest.fixture(scope="module")
def tiny():
    cfg = VLMConfig.tiny()
    model = VLMModel(cfg)
    params = model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, 4), jnp.int32),
        jnp.zeros((1, cfg.vision.image_size, cfg.vision.image_size, 3), jnp.float32),
    )["params"]
    return cfg, model, params


def naive_greedy(model, cfg, params, prompt_ids, pixels, steps):
    """Reference decode: recompute the full sequence each step with the
    cacheless forward, take argmax — the semantics the fused loop must match."""
    ids = list(prompt_ids)
    out = []
    for _ in range(steps):
        logits = model.apply(
            {"params": params}, jnp.asarray([ids], jnp.int32), pixels
        )
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        if nxt == cfg.eos_token_id:
            break
        ids.append(nxt)
    return out


class TestMergeImageEmbeddings:
    def test_splice_layout(self):
        b, s, v, h = 1, 6, 4, 8
        text = jnp.arange(b * s * h, dtype=jnp.float32).reshape(b, s, h)
        vis = -jnp.arange(b * v * h, dtype=jnp.float32).reshape(b, v, h) - 1.0
        ids = jnp.asarray([[5, 9, 7, 7, 7, 7]])  # image token id 9 at idx 1
        merged, positions, lengths = merge_image_embeddings(text, vis, ids, 9)
        assert merged.shape == (b, s - 1 + v, h)
        np.testing.assert_allclose(merged[0, 0], text[0, 0])  # before splice
        np.testing.assert_allclose(merged[0, 1:5], vis[0])  # vision block
        np.testing.assert_allclose(merged[0, 5], text[0, 2])  # after splice
        assert int(lengths[0]) == s - 1 + v
        np.testing.assert_array_equal(positions[0], np.arange(s - 1 + v))

    def test_no_image_passthrough(self):
        text = jnp.ones((1, 5, 8))
        vis = jnp.zeros((1, 3, 8))
        ids = jnp.asarray([[1, 2, 3, 4, 5]])
        merged, _, lengths = merge_image_embeddings(text, vis, ids, 99)
        np.testing.assert_allclose(merged[0, :5], text[0])
        assert int(lengths[0]) == 5

    def test_padded_lengths(self):
        text = jnp.ones((1, 6, 8))
        vis = jnp.zeros((1, 2, 8))
        ids = jnp.asarray([[9, 1, 2, 0, 0, 0]])  # 3 live tokens, 3 pads
        _, _, lengths = merge_image_embeddings(
            text, vis, ids, 9, input_lengths=jnp.asarray([3])
        )
        assert int(lengths[0]) == 3 - 1 + 2


class TestDecodeParity:
    def test_prefill_then_steps_match_full_forward(self, tiny):
        """Prefill + single-token cached steps == cacheless full forward."""
        cfg, model, params = tiny
        gen = Generator(model, cfg, max_seq=64, max_new_cap=8, cache_dtype=jnp.float32)
        rng = np.random.RandomState(0)
        ids = rng.randint(3, 200, size=(1, 7)).astype(np.int32)

        full_logits = model.apply({"params": params}, jnp.asarray(ids), None)

        embeds = model.apply({"params": params}, jnp.asarray(ids[:, :4]), method=VLMModel.embed_tokens)
        positions = jnp.arange(4)[None, :]
        caches, last = gen._prefill_core(params, embeds, positions, jnp.asarray([4]))
        np.testing.assert_allclose(np.asarray(last[0]), np.asarray(full_logits[0, 3]), rtol=2e-4, atol=2e-4)

        cur_len = jnp.asarray([4], jnp.int32)
        for t in range(4, 7):
            tok_embed = model.apply(
                {"params": params}, jnp.asarray(ids[:, t : t + 1]), method=VLMModel.embed_tokens
            )
            logits, caches = gen._decode(
                params, tok_embed, cur_len[:, None], caches, cur_len, cur_len + 1
            )
            np.testing.assert_allclose(
                np.asarray(logits[0, 0]), np.asarray(full_logits[0, t]), rtol=2e-4, atol=2e-4
            )
            cur_len = cur_len + 1

    def test_padded_prefill_matches_unpadded(self, tiny):
        """Right-padding the prompt to a bucket must not change logits at
        the live positions (kv_valid_len masking)."""
        cfg, model, params = tiny
        gen = Generator(model, cfg, max_seq=64, max_new_cap=8, cache_dtype=jnp.float32)
        ids = np.asarray([[11, 23, 35, 47, 59]], np.int32)
        emb = lambda x: model.apply({"params": params}, jnp.asarray(x), method=VLMModel.embed_tokens)

        _, last_unpadded = gen._prefill_core(
            params, emb(ids), jnp.arange(5)[None, :], jnp.asarray([5])
        )
        padded = np.concatenate([ids, np.zeros((1, 3), np.int32)], axis=1)
        _, last_padded = gen._prefill_core(
            params, emb(padded), jnp.arange(8)[None, :], jnp.asarray([5])
        )
        np.testing.assert_allclose(
            np.asarray(last_unpadded), np.asarray(last_padded), rtol=2e-4, atol=2e-4
        )


class TestGenerate:
    def test_fused_greedy_matches_naive(self, tiny):
        cfg, model, params = tiny
        gen = Generator(model, cfg, max_seq=64, max_new_cap=8, cache_dtype=jnp.float32)
        ids = np.asarray([[5, 17, 29, 41]], np.int32)
        expected = naive_greedy(model, cfg, params, ids[0].tolist(), None, steps=6)

        embeds = model.apply({"params": params}, jnp.asarray(ids), method=VLMModel.embed_tokens)
        out = gen.generate(
            params,
            embeds,
            jnp.arange(4)[None, :],
            jnp.asarray([4]),
            jnp.asarray(ids),
            jax.random.PRNGKey(0),
            max_new_tokens=6,
        )
        got = [int(t) for t in np.asarray(out.tokens[0][: int(out.n_generated[0])])]
        assert got == expected

    def test_eos_early_stop(self, tiny):
        """Re-badge the first greedy token as EOS: generation must stop at 1."""
        cfg, model, params = tiny
        probe = Generator(model, cfg, max_seq=64, max_new_cap=8, cache_dtype=jnp.float32)
        ids = np.asarray([[5, 17, 29, 41]], np.int32)
        embeds = model.apply({"params": params}, jnp.asarray(ids), method=VLMModel.embed_tokens)
        first = naive_greedy(model, cfg, params, ids[0].tolist(), None, steps=1)[0]

        eos_cfg = dataclasses.replace(cfg, eos_token_id=first)
        gen = Generator(model, eos_cfg, max_seq=64, max_new_cap=8, cache_dtype=jnp.float32)
        out = gen.generate(
            params,
            embeds,
            jnp.arange(4)[None, :],
            jnp.asarray([4]),
            jnp.asarray(ids),
            jax.random.PRNGKey(0),
            max_new_tokens=8,
        )
        assert int(out.n_generated[0]) == 1
        assert bool(out.stopped_eos[0])
        # post-EOS slots are pad-filled
        assert all(int(t) == eos_cfg.pad_token_id for t in np.asarray(out.tokens[0][1:]))

    def test_sampling_smoke(self, tiny):
        cfg, model, params = tiny
        gen = Generator(model, cfg, max_seq=64, max_new_cap=4, cache_dtype=jnp.float32)
        ids = np.asarray([[5, 17]], np.int32)
        embeds = model.apply({"params": params}, jnp.asarray(ids), method=VLMModel.embed_tokens)
        out = gen.generate(
            params,
            embeds,
            jnp.arange(2)[None, :],
            jnp.asarray([2]),
            jnp.asarray(ids),
            jax.random.PRNGKey(42),
            max_new_tokens=4,
            temperature=1.0,
            top_p=0.9,
            do_sample=True,
            repetition_penalty=1.2,
        )
        toks = np.asarray(out.tokens[0][: int(out.n_generated[0])])
        assert len(toks) >= 1
        assert ((toks >= 0) & (toks < cfg.decoder.vocab_size)).all()

    def test_multimodal_forward_and_generate(self, tiny):
        """End-to-end with an image: splice + generate stays finite and
        matches the naive multimodal loop."""
        cfg, model, params = tiny
        gen = Generator(model, cfg, max_seq=64, max_new_cap=4, cache_dtype=jnp.float32)
        pixels = jnp.asarray(
            np.random.RandomState(0).rand(1, cfg.vision.image_size, cfg.vision.image_size, 3),
            jnp.float32,
        )
        ids = np.asarray([[5, cfg.image_token_id, 17, 29]], np.int32)
        expected = naive_greedy(model, cfg, params, ids[0].tolist(), pixels, steps=4)

        text = model.apply({"params": params}, jnp.asarray(ids), method=VLMModel.embed_tokens)
        vis = model.apply({"params": params}, pixels, method=VLMModel.encode_vision)
        merged, positions, lengths = merge_image_embeddings(
            text, vis, jnp.asarray(ids), cfg.image_token_id
        )
        out = gen.generate(
            params, merged, positions, lengths, jnp.asarray(ids),
            jax.random.PRNGKey(0), max_new_tokens=4,
        )
        got = [int(t) for t in np.asarray(out.tokens[0][: int(out.n_generated[0])])]
        assert got == expected


class TestChat:
    def test_fallback_format(self):
        msgs = [ChatMessage("system", "be brief"), ChatMessage("user", "hi")]
        text = render_chat(msgs, None)
        assert "<|system|>\nbe brief" in text
        assert text.endswith("<|assistant|>\n")

    def test_jinja_template(self):
        pytest.importorskip("jinja2")
        template = (
            "{% for m in messages %}[{{ m.role }}]{{ m.content }}{% endfor %}"
            "{% if add_generation_prompt %}[assistant]{% endif %}"
        )
        text = render_chat([ChatMessage("user", "hello")], template)
        assert text == "[user]hello[assistant]"

    def test_bad_template_falls_back(self):
        text = render_chat([ChatMessage("user", "x")], "{% bogus %}")
        assert "<|user|>" in text

    def test_empty_messages_raises(self):
        with pytest.raises(ValueError):
            render_chat([], None)


class TestConvert:
    def test_qwen2_style_rules(self, tiny):
        """A torch-style state dict with Qwen2/LLaVA naming converts onto
        the exact init tree."""
        from lumen_tpu.models.vlm.convert import convert_vlm_checkpoint
        from lumen_tpu.runtime.weights import flatten

        cfg, model, params = tiny
        d = cfg.decoder
        rng = np.random.RandomState(0)
        state = {}

        def put(key, shape):
            state[key] = rng.randn(*shape).astype(np.float32)

        put("model.embed_tokens.weight", (d.vocab_size, d.hidden_size))
        put("model.norm.weight", (d.hidden_size,))
        dh = d.dim_per_head
        for i in range(d.layers):
            p = f"model.layers.{i}."
            put(p + "self_attn.q_proj.weight", (d.heads * dh, d.hidden_size))
            put(p + "self_attn.q_proj.bias", (d.heads * dh,))
            put(p + "self_attn.k_proj.weight", (d.kv_heads * dh, d.hidden_size))
            put(p + "self_attn.k_proj.bias", (d.kv_heads * dh,))
            put(p + "self_attn.v_proj.weight", (d.kv_heads * dh, d.hidden_size))
            put(p + "self_attn.v_proj.bias", (d.kv_heads * dh,))
            put(p + "self_attn.o_proj.weight", (d.hidden_size, d.heads * dh))
            put(p + "mlp.gate_proj.weight", (d.intermediate_size, d.hidden_size))
            put(p + "mlp.up_proj.weight", (d.intermediate_size, d.hidden_size))
            put(p + "mlp.down_proj.weight", (d.hidden_size, d.intermediate_size))
            put(p + "input_layernorm.weight", (d.hidden_size,))
            put(p + "post_attention_layernorm.weight", (d.hidden_size,))
        v = cfg.vision
        put("vision_tower.patch_embed.weight", (v.width, 3, v.patch_size, v.patch_size))
        put("vision_tower.patch_embed.bias", (v.width,))
        put("vision_tower.position_embedding", (v.num_tokens, v.width))
        for i in range(v.layers):
            p = f"vision_tower.blocks.{i}."
            put(p + "attn.q_proj.weight", (v.width, v.width))
            put(p + "attn.q_proj.bias", (v.width,))
            put(p + "attn.k_proj.weight", (v.width, v.width))
            put(p + "attn.k_proj.bias", (v.width,))
            put(p + "attn.v_proj.weight", (v.width, v.width))
            put(p + "attn.v_proj.bias", (v.width,))
            put(p + "attn.out_proj.weight", (v.width, v.width))
            put(p + "attn.out_proj.bias", (v.width,))
            put(p + "norm1.weight", (v.width,))
            put(p + "norm1.bias", (v.width,))
            put(p + "norm2.weight", (v.width,))
            put(p + "norm2.bias", (v.width,))
            put(p + "mlp.fc1.weight", (v.width * 4, v.width))
            put(p + "mlp.fc1.bias", (v.width * 4,))
            put(p + "mlp.fc2.weight", (v.width, v.width * 4))
            put(p + "mlp.fc2.bias", (v.width,))
        put("vision_tower.post_norm.weight", (v.width,))
        put("vision_tower.post_norm.bias", (v.width,))
        put("multi_modal_projector.linear_1.weight", (d.hidden_size, v.width))
        put("multi_modal_projector.linear_1.bias", (d.hidden_size,))
        put("multi_modal_projector.linear_2.weight", (d.hidden_size, d.hidden_size))
        put("multi_modal_projector.linear_2.bias", (d.hidden_size,))
        # tied lm_head + junk that must be dropped
        put("lm_head.weight", (d.vocab_size, d.hidden_size))
        put("model.layers.0.self_attn.rotary_emb.inv_freq", (dh // 2,))

        converted = convert_vlm_checkpoint(state, params, tie_word_embeddings=True)
        assert set(flatten(converted)) == set(flatten(params))
        # value spot-check incl. transpose
        np.testing.assert_allclose(
            converted["decoder"]["layers_0"]["attn"]["q_proj"]["kernel"],
            state["model.layers.0.self_attn.q_proj.weight"].T,
        )

    def test_language_model_prefix(self, tiny):
        from lumen_tpu.models.vlm.convert import convert_vlm_checkpoint

        state = {"language_model.model.norm.weight": np.ones((8,), np.float32)}
        out = convert_vlm_checkpoint(state)
        assert out["decoder"]["final_norm"]["scale"].shape == (8,)


# -- manager + service -------------------------------------------------------


def write_vlm_tokenizer(path: str, vocab_size: int = 256):
    from tokenizers import Tokenizer, models, pre_tokenizers

    words = {"<pad>": 0, "<bos>": 1, "<eos>": 2, "describe": 10, "the": 11, "image": 12,
             "a": 13, "cat": 14, "dog": 15, "<unk>": 3}
    # filler ids so decode of arbitrary generated ids stays in-vocab
    for i in range(16, vocab_size):
        words[f"w{i}"] = i
    tok = Tokenizer(models.WordLevel(words, unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    tok.save(path)


def make_vlm_model_dir(tmp_path) -> str:
    from safetensors.numpy import save_file

    from lumen_tpu.runtime.weights import flatten_variables

    cfg = VLMConfig.tiny()
    model = VLMModel(cfg)
    variables = model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, 4), jnp.int32),
        jnp.zeros((1, cfg.vision.image_size, cfg.vision.image_size, 3), jnp.float32),
    )
    model_dir = tmp_path / "models" / "TinyVLM"
    model_dir.mkdir(parents=True, exist_ok=True)
    save_file(flatten_variables(dict(variables)), str(model_dir / "model.safetensors"))
    d, v = cfg.decoder, cfg.vision
    config = {
        "text_config": {
            "hidden_size": d.hidden_size,
            "num_hidden_layers": d.layers,
            "num_attention_heads": d.heads,
            "num_key_value_heads": d.kv_heads,
            "intermediate_size": d.intermediate_size,
            "vocab_size": d.vocab_size,
            "rope_theta": d.rope_theta,
            "max_position_embeddings": d.max_position_embeddings,
            "bos_token_id": cfg.bos_token_id,
            "eos_token_id": cfg.eos_token_id,
            "pad_token_id": cfg.pad_token_id,
            "tie_word_embeddings": True,
        },
        "vision_config": {
            "image_size": v.image_size,
            "patch_size": v.patch_size,
            "hidden_size": v.width,
            "num_hidden_layers": v.layers,
            "num_attention_heads": v.heads,
        },
        "image_token_index": cfg.image_token_id,
    }
    (model_dir / "config.json").write_text(json.dumps(config))
    write_vlm_tokenizer(str(model_dir / "tokenizer.json"))
    (model_dir / "tokenizer_config.json").write_text(json.dumps({
        "chat_template": (
            "{% for m in messages %}<|{{ m.role }}|> {{ m.content }} {% endfor %}"
            "{% if add_generation_prompt %}<|assistant|>{% endif %}"
        )
    }))
    info = {
        "name": "TinyVLM",
        "version": "1.0.0",
        "description": "tiny test vlm",
        "model_type": "vlm",
        "source": {"format": "custom", "repo_id": "LumilioPhotos/TinyVLM"},
        "runtimes": {"jax": {"available": True, "files": ["model.safetensors"]}},
    }
    (model_dir / "model_info.json").write_text(json.dumps(info))
    return str(model_dir)


def png_bytes(size=24, seed=0):
    import cv2

    rng = np.random.default_rng(seed)
    img = rng.integers(0, 255, (size, size, 3), np.uint8)
    ok, buf = cv2.imencode(".png", img)
    assert ok
    return buf.tobytes()


@pytest.fixture(scope="module")
def manager(tmp_path_factory):
    model_dir = make_vlm_model_dir(tmp_path_factory.mktemp("vlm"))
    mgr = VLMManager(
        model_dir, dtype="float32", max_seq=128, max_new_cap=16, prefill_buckets=(16, 32)
    )
    mgr.initialize()
    yield mgr
    mgr.close()


class TestManager:
    def test_generate_with_image(self, manager):
        res = manager.generate(
            [ChatMessage("user", "describe the image")],
            image_bytes=png_bytes(),
            max_new_tokens=6,
        )
        assert res.finish_reason in ("eos_token", "length", "stop_sequence")
        assert res.input_tokens > 0
        assert len(res.tokens) <= 6
        assert "tokens_per_second" in res.metadata

    def test_generate_text_only(self, manager):
        res = manager.generate([ChatMessage("user", "a cat")], max_new_tokens=4)
        assert len(res.tokens) <= 4

    def test_generate_deterministic(self, manager):
        a = manager.generate([ChatMessage("user", "the dog")], image_bytes=png_bytes(), max_new_tokens=5)
        b = manager.generate([ChatMessage("user", "the dog")], image_bytes=png_bytes(), max_new_tokens=5)
        assert a.tokens == b.tokens

    def test_stream_concatenates_to_full(self, manager):
        msgs = [ChatMessage("user", "describe the image")]
        full = manager.generate(msgs, image_bytes=png_bytes(1), max_new_tokens=6)
        chunks = list(manager.generate_stream(msgs, image_bytes=png_bytes(1), max_new_tokens=6))
        assert chunks[-1].is_final
        streamed_text = "".join(c.text for c in chunks if not c.is_final)
        assert streamed_text.strip() == full.text
        assert chunks[-1].metadata["generated_tokens"] == len(full.tokens)

    def test_stop_sequences(self, manager):
        # Whatever greedy emits first, use its text as the stop sequence.
        probe = manager.generate([ChatMessage("user", "a")], max_new_tokens=3)
        if not probe.text:
            pytest.skip("tiny model generated empty text")
        stop = probe.text.split()[0]
        res = manager.generate(
            [ChatMessage("user", "a")], max_new_tokens=3, stop_sequences=[stop]
        )
        assert res.finish_reason == "stop_sequence"
        assert stop not in res.text

    def test_uninitialized_raises(self, tmp_path):
        model_dir = make_vlm_model_dir(tmp_path)
        mgr = VLMManager(model_dir, dtype="float32", max_seq=128, max_new_cap=8,
                         prefill_buckets=(16,))
        with pytest.raises(RuntimeError):
            mgr.generate([ChatMessage("user", "x")])


class TestService:
    @pytest.fixture(scope="class")
    def service(self, manager):
        from lumen_tpu.serving.services.vlm_service import VlmService

        return VlmService(manager)

    def test_capability(self, service):
        cap = service.capability()
        names = [t.name for t in cap.tasks]
        assert "vlm_generate" in names and "vlm_generate_stream" in names

    def test_generate_handler(self, service):
        from lumen_tpu.core.result_schemas import validate_result

        meta = {
            "messages": json.dumps([{"role": "user", "content": "describe the image"}]),
            "max_new_tokens": "5",
        }
        body, mime, _ = service._generate(png_bytes(), "image/png", meta)
        parsed = validate_result("text_generation_v1", body)
        assert parsed.model_id == "TinyVLM"
        assert parsed.generated_tokens <= 5
        assert "text_generation_v1" in mime

    def test_stream_handler(self, service):
        from lumen_tpu.core.result_schemas import validate_result

        meta = {
            "messages": json.dumps([{"role": "user", "content": "describe the image"}]),
            "max_new_tokens": "5",
        }
        out = list(service._generate_stream(png_bytes(), "image/png", meta))
        assert len(out) >= 1
        final_body, final_mime, _ = out[-1]
        parsed = validate_result("text_generation_v1", final_body)
        deltas = "".join(b.decode() for b, m, _ in out[:-1])
        assert parsed.text == deltas
        assert "streaming_chunks" in parsed.metadata

    def test_missing_messages_rejected(self, service):
        from lumen_tpu.serving.base_service import InvalidArgument

        with pytest.raises(InvalidArgument):
            service._generate(b"", "image/png", {})

    def test_bad_messages_rejected(self, service):
        from lumen_tpu.serving.base_service import InvalidArgument

        with pytest.raises(InvalidArgument):
            service._generate(b"", "image/png", {"messages": "not json"})
        with pytest.raises(InvalidArgument):
            service._generate(b"", "image/png", {"messages": json.dumps([{"role": "u"}])})

    def test_bad_image_maps_to_invalid_argument(self, service):
        from lumen_tpu.serving.base_service import InvalidArgument

        meta = {"messages": json.dumps([{"role": "user", "content": "x"}])}
        with pytest.raises(InvalidArgument):
            service._generate(b"not-an-image", "image/png", meta)
        with pytest.raises(InvalidArgument):
            list(service._generate_stream(b"not-an-image", "image/png", meta))
