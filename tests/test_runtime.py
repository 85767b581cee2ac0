"""Runtime core tests: mesh resolution, batcher semantics, weight loading."""

import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lumen_tpu.runtime import (
    MicroBatcher,
    apply_rules,
    assert_tree_shapes,
    bucket_for,
    build_mesh,
    conv_kernel,
    default_buckets,
    flatten,
    get_policy,
    linear_kernel,
    load_state_dict,
    resolve_axes,
    unflatten,
)
from lumen_tpu.runtime.weights import WeightLoadError


class TestMesh:
    def test_resolve_wildcard(self):
        assert resolve_axes({"data": -1}, 8) == {"data": 8}
        assert resolve_axes({"data": -1, "model": 2}, 8) == {"data": 4, "model": 2}

    def test_resolve_exact(self):
        assert resolve_axes({"data": 4, "model": 2}, 8) == {"data": 4, "model": 2}

    def test_resolve_mismatch(self):
        # A data axis that does not fit degrades to the largest size that
        # does (ISSUE 7 satellite: LUMEN_REPLICAS=8 on a 4-chip host must
        # serve 4 ways, not fail boot) ...
        assert resolve_axes({"data": 8}, 4) == {"data": 4}
        assert resolve_axes({"data": 3}, 8) == {"data": 2}
        assert resolve_axes({"data": 6, "model": 2}, 8) == {"data": 4, "model": 2}
        # Exact-divisor under-cover serves on the device prefix (same
        # graceful policy as the non-dividing case above, which also
        # lands on a 4-of-8 mesh).
        assert resolve_axes({"data": 4}, 8) == {"data": 4}
        # ... but a non-data axis (TP) still raises: silently shrinking it
        # would change which checkpoints even fit.
        with pytest.raises(ValueError):
            resolve_axes({"data": -1, "model": 3}, 8)

    @pytest.mark.multichip
    def test_build_mesh_8_devices(self):
        mesh = build_mesh({"data": -1, "model": 2})
        assert mesh.shape["data"] == 4 and mesh.shape["model"] == 2

    @pytest.mark.multichip
    def test_data_parallel_psum(self):
        # Sanity: a shard_map psum over the data axis actually reduces.
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        mesh = build_mesh({"data": -1})
        x = np.arange(8, dtype=np.float32)
        f = shard_map(
            lambda v: jax.lax.psum(v, "data"), mesh=mesh,
            in_specs=P("data"), out_specs=P(),
        )
        out = jax.jit(f)(x)
        assert float(out[0]) == x.sum()


class TestPolicy:
    def test_bf16_policy_casts_floats_only(self):
        p = get_policy("bfloat16")
        tree = {"w": jnp.ones((2, 2), jnp.float32), "idx": jnp.ones((2,), jnp.int32)}
        out = p.cast_params(tree)
        assert out["w"].dtype == jnp.bfloat16
        assert out["idx"].dtype == jnp.int32

    def test_unknown_policy(self):
        with pytest.raises(ValueError):
            get_policy("fp8")


class TestBatcher:
    def test_buckets(self):
        assert default_buckets(8) == [1, 2, 4, 8]
        assert default_buckets(6) == [1, 2, 4, 6]
        assert bucket_for(3, [1, 2, 4, 8]) == 4
        assert bucket_for(9, [1, 2, 4, 8]) == 8

    def test_single_item(self):
        calls = []

        def fn(tree, n):
            calls.append((tree["x"].shape, n))
            return {"y": tree["x"] * 2}

        b = MicroBatcher(fn, max_batch=4, max_latency_ms=1).start()
        try:
            out = b({"x": np.array([1.0, 2.0])})
            assert np.allclose(out["y"], [2.0, 4.0])
            assert calls[0] == ((1, 2), 1)
        finally:
            b.close()

    def test_close_never_started_spares_live_gauges(self):
        """ADVICE r3: closing a never-started same-name batcher must not
        evict a live batcher's gauge provider (gauges register at start();
        an unstarted instance has none to unregister)."""
        from lumen_tpu.utils.metrics import metrics

        fn = lambda tree, n: tree  # noqa: E731
        live = MicroBatcher(fn, max_batch=2, max_latency_ms=1, name="gauge-t").start()
        try:
            stale = MicroBatcher(fn, max_batch=2, max_latency_ms=1, name="gauge-t")
            stale.close()  # never started
            assert "batcher:gauge-t" in (metrics.snapshot().get("gauges") or {})
        finally:
            live.close()
        assert "batcher:gauge-t" not in (metrics.snapshot().get("gauges") or {})

    def test_concurrent_submissions_batch_together(self):
        seen_batches = []

        def fn(tree, n):
            time.sleep(0.01)
            seen_batches.append(n)
            return tree * 10

        b = MicroBatcher(fn, max_batch=8, max_latency_ms=50).start()
        try:
            results = [None] * 8
            def worker(i):
                results[i] = b(np.array([float(i)]))
            threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert [float(r[0]) for r in results] == [i * 10.0 for i in range(8)]
            # With a 50ms window, the 8 near-simultaneous items should land
            # in far fewer than 8 batches.
            assert sum(seen_batches) == 8 and len(seen_batches) <= 4
        finally:
            b.close()

    def test_padding_to_bucket(self):
        shapes = []

        def fn(tree, n):
            shapes.append((tree.shape[0], n))
            return tree + 1

        b = MicroBatcher(fn, max_batch=8, max_latency_ms=30).start()
        try:
            futs = [b.submit(np.zeros((3,))) for _ in range(3)]
            outs = [f.result(timeout=5) for f in futs]
            assert all(o.shape == (3,) for o in outs)
            assert shapes[0] == (4, 3)  # 3 items padded to bucket 4
            assert b.stats["padded"] == 1
        finally:
            b.close()

    def test_error_fans_out(self):
        def fn(tree, n):
            raise RuntimeError("device on fire")

        b = MicroBatcher(fn, max_batch=2, max_latency_ms=1).start()
        try:
            with pytest.raises(RuntimeError, match="device on fire"):
                b(np.zeros((1,)))
        finally:
            b.close()

    def test_submit_after_close(self):
        b = MicroBatcher(lambda t, n: t, max_batch=2).start()
        b.close()
        with pytest.raises(RuntimeError):
            b.submit(np.zeros((1,)))

    def test_jitted_fn_with_static_buckets_compiles_once_per_bucket(self):
        traces = []

        @jax.jit
        def model(x):
            traces.append(x.shape)
            return x * 2.0

        b = MicroBatcher(lambda t, n: model(t), max_batch=4, max_latency_ms=5).start()
        try:
            for _ in range(3):
                b(np.ones((2, 2), np.float32))
            # All single-item calls hit bucket 1 -> one trace only.
            assert traces == [(1, 2, 2)]
        finally:
            b.close()


class TestWeights:
    def test_layout_helpers(self):
        w = np.arange(6).reshape(2, 3)
        assert linear_kernel(w).shape == (3, 2)
        c = np.zeros((8, 4, 3, 3))
        assert conv_kernel(c).shape == (3, 3, 4, 8)

    def test_apply_rules_and_unflatten(self):
        state = {
            "visual.blocks.0.attn.weight": np.zeros((4, 4)),
            "visual.blocks.0.attn.bias": np.zeros((4,)),
            "logit_scale": np.array(4.6),
            "ignored.num_batches_tracked": np.array(0),
        }
        rules = [
            (r"visual\.blocks\.(\d+)\.attn\.weight", r"vision/block_\1/attn/kernel", linear_kernel),
            (r"visual\.blocks\.(\d+)\.attn\.bias", r"vision/block_\1/attn/bias", None),
            (r"logit_scale", r"logit_scale", None),
        ]
        flat = apply_rules(state, rules, drop=[r"num_batches_tracked"])
        tree = unflatten(flat)
        assert tree["vision"]["block_0"]["attn"]["kernel"].shape == (4, 4)
        assert "logit_scale" in tree

    def test_apply_rules_strict_unmatched(self):
        with pytest.raises(WeightLoadError):
            apply_rules({"mystery": np.zeros(1)}, [], strict=True)

    def test_tree_shape_gate(self):
        good = {"a": {"w": np.zeros((2, 2))}}
        assert_tree_shapes(good, {"a": {"w": np.ones((2, 2))}})
        with pytest.raises(WeightLoadError):
            assert_tree_shapes(good, {"a": {"w": np.ones((3, 2))}})
        with pytest.raises(WeightLoadError):
            assert_tree_shapes(good, {"a": {"w": np.ones((2, 2)), "b": np.ones(1)}})

    def test_flatten_roundtrip(self):
        tree = {"a": {"b": np.ones(1), "c": {"d": np.zeros(2)}}}
        assert unflatten(flatten(tree)).keys() == tree.keys()

    def test_load_safetensors_roundtrip(self, tmp_path):
        from safetensors.numpy import save_file

        save_file({"x": np.arange(4, dtype=np.float32)}, str(tmp_path / "model.safetensors"))
        state = load_state_dict(str(tmp_path))
        assert np.allclose(state["x"], np.arange(4))

    def test_load_torch_checkpoint(self, tmp_path):
        import torch

        torch.save({"w": torch.ones(2, 2, dtype=torch.bfloat16)}, str(tmp_path / "model.bin"))
        state = load_state_dict(str(tmp_path))
        assert state["w"].dtype == np.float32 and state["w"].shape == (2, 2)

    def test_no_checkpoint_raises(self, tmp_path):
        with pytest.raises(WeightLoadError):
            load_state_dict(str(tmp_path))


class TestMeshBatching:
    def test_mesh_buckets_multiples(self):
        from lumen_tpu.runtime.batcher import mesh_buckets

        assert mesh_buckets(8, 1) == [1, 2, 4, 8]
        assert mesh_buckets(32, 8) == [8, 16, 32]
        assert mesh_buckets(8, 8) == [8]
        # max_batch rounded up to a dp multiple
        assert mesh_buckets(12, 8) == [8, 16]

    def test_mesh_sharded_places_on_data_axis(self):
        import jax
        import numpy as np

        from lumen_tpu.runtime.batcher import mesh_sharded
        from lumen_tpu.runtime.mesh import build_mesh

        mesh = build_mesh({"data": -1})
        seen = {}

        def fn(x, n):
            seen["spec"] = x.sharding.spec
            return np.asarray(x)

        wrapped = mesh_sharded(fn, mesh)
        out = wrapped(np.zeros((8, 4), np.float32), 8)
        assert seen["spec"][0] == "data"
        assert out.shape == (8, 4)


class TestCompileCache:
    """Where the cache lives: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX
    reads it itself; the helper configures nothing), else the one fixed
    path inside the checkout."""

    @pytest.fixture()
    def restore_cache_dir(self):
        prev = jax.config.jax_compilation_cache_dir
        yield prev
        jax.config.update("jax_compilation_cache_dir", prev)

    def test_unset_env_points_jax_at_checkout_dir(self, monkeypatch, restore_cache_dir):
        from lumen_tpu.runtime import enable_persistent_cache
        from lumen_tpu.runtime.compile_cache import DEFAULT_CACHE_DIR

        monkeypatch.delenv("LUMEN_COMPILE_CACHE", raising=False)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")
        assert enable_persistent_cache() == DEFAULT_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == DEFAULT_CACHE_DIR

    def test_env_opt_out(self, monkeypatch, restore_cache_dir):
        from lumen_tpu.runtime import enable_persistent_cache

        monkeypatch.setenv("LUMEN_COMPILE_CACHE", "0")
        jax.config.update("jax_compilation_cache_dir", "/untouched")
        assert enable_persistent_cache() is None
        assert jax.config.jax_compilation_cache_dir == "/untouched"

    def test_env_dir_is_left_to_jax(self, tmp_path, monkeypatch, restore_cache_dir):
        """With JAX_COMPILATION_CACHE_DIR set the helper reports that
        directory and calls no ``jax.config.update`` for it: whatever JAX
        holds (it read the variable at import) stays as it is."""
        from lumen_tpu.runtime import enable_persistent_cache

        monkeypatch.delenv("LUMEN_COMPILE_CACHE", raising=False)
        target = str(tmp_path / "envdir")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", target)
        jax.config.update("jax_compilation_cache_dir", "/untouched")
        assert enable_persistent_cache() == target
        assert jax.config.jax_compilation_cache_dir == "/untouched"
        assert not os.path.exists(target)  # JAX makes it on first write, not us

    def test_two_calls_agree(self, monkeypatch, restore_cache_dir):
        """The path is part of what makes an entry findable: it may not
        depend on the call, the pid or the clock."""
        from lumen_tpu.runtime import enable_persistent_cache

        monkeypatch.delenv("LUMEN_COMPILE_CACHE", raising=False)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        first = enable_persistent_cache()
        assert enable_persistent_cache() == first
        assert str(os.getpid()) not in first and not first.startswith(
            (os.path.expanduser("~") + os.sep + ".cache", "/tmp")
        )

    def test_jax_reads_the_variable_itself(self, tmp_path):
        """The rule above rests on JAX taking the directory from the
        environment at import — checked in a fresh interpreter."""
        import subprocess
        import sys

        target = str(tmp_path / "from-env")
        out = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(jax.config.jax_compilation_cache_dir)"],
            env={**os.environ, "JAX_COMPILATION_CACHE_DIR": target, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=120,
        )
        assert out.stdout.strip() == target, out.stderr[-500:]
