"""Disaggregated prefill/decode: roles, the KV-migration wire, parity.

Covers the PR's whole surface in one place:

- the ``tensor/bundle`` multi-tensor codec's malformed-frame matrix
  (every reject is typed, indexed, and never a misparse);
- randomized pack→unpack round-trips of the migration payload over
  arbitrary page counts / shapes / dtypes (bfloat16 included), with
  crc-corruption and truncation rejected;
- commit-meta codec round-trip + per-field validation errors;
- role advertisement (`LUMEN_FED_ROLE` parsing, the Health trailer,
  byte-identical unconfigured payloads);
- role-aware forward planning (`disagg_plan`) and the one-shot
  unservable-role warning;
- the router's reserved ``fed_kv_put`` task (no-sink refusal, drain
  gate, sink crash containment, front-tier refusal);
- the decode-host service handler's refusal ladder (bad op, bad meta,
  bad crc, truncated stream, infeasible row);
- END-TO-END in-process migration over the REAL federation dispatcher
  (`kv_migrate` → offer → chunked commit → `submit_migrated` → token
  relay): greedy output token-identical to a colocated run with zero
  decode-host prefill, counters and page accounting balanced on both
  engines, and the local-fallback ladder when the wire dies;
- the ``client.py peers`` printer's role / migration-counter columns.
"""

from __future__ import annotations

import json
import threading
import time
import zlib

import numpy as np
import pytest

from lumen_tpu.models.vlm import ChatMessage, VLMManager, migration
from lumen_tpu.models.vlm.migration import (
    MIGRATE_VERSION,
    commit_meta,
    manifest_csv,
    manifest_from_csv,
    pack_payload,
    parse_commit_meta,
    unpack_payload,
)
from lumen_tpu.runtime.federation import (
    FederationManager,
    MIGRATION,
    PeerSpec,
    ROLE_BOTH,
    ROLE_DECODE,
    ROLE_PREFILL,
)
from lumen_tpu.serving import router as router_mod
from lumen_tpu.serving.echo import EchoService
from lumen_tpu.serving.proto import ml_service_pb2 as pb
from lumen_tpu.serving.router import (
    FED_KV_PUT_TASK,
    FED_ROLE_META,
    FederationRouter,
    HubRouter,
    advertised_fed_role,
)
from lumen_tpu.serving.services.vlm_service import VlmService
from lumen_tpu.utils.tensorwire import (
    _BUNDLE_MAGIC,
    BUNDLE_MIME,
    pack_bundle,
    unpack_bundle,
)
from tests.test_vlm import make_vlm_model_dir


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    return make_vlm_model_dir(tmp_path_factory.mktemp("vlmd"))


def _make_mgr(model_dir, **over):
    kwargs = dict(
        dtype="float32", max_seq=128, max_new_cap=16,
        prefill_buckets=(16, 32),
        gen_slots=4, gen_block=4,
    )
    kwargs.update(over)
    mgr = VLMManager(model_dir, **kwargs)
    mgr.initialize()
    return mgr


def _reset_migration_counters():
    for k in MIGRATION:
        MIGRATION[k] = 0


# ---------------------------------------------------------------------------
# tensor/bundle codec: round-trip + the malformed-frame matrix
# ---------------------------------------------------------------------------


class TestBundleCodec:
    def test_round_trip_multi_tensor(self):
        arrays = [
            np.arange(24, dtype=np.float32).reshape(2, 3, 4),
            np.array([[1, 2, 3]], dtype=np.int64),
            np.zeros((0, 5), dtype=np.uint8),  # zero-size tensor survives
            np.array(7, dtype=np.int32),  # scalar (ndim 0)
        ]
        out = unpack_bundle(pack_bundle(arrays))
        assert len(out) == len(arrays)
        for a, b in zip(arrays, out):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(a, b)

    def test_round_trip_empty_list(self):
        assert unpack_bundle(pack_bundle([])) == []

    def test_round_trip_bfloat16(self):
        ml_dtypes = pytest.importorskip("ml_dtypes")
        a = np.arange(8, dtype=np.float32).astype(ml_dtypes.bfloat16)
        (b,) = unpack_bundle(pack_bundle([a]))
        assert b.dtype == a.dtype
        np.testing.assert_array_equal(
            a.astype(np.float32), b.astype(np.float32)
        )

    def test_non_contiguous_input_packs(self):
        a = np.arange(24, dtype=np.float32).reshape(4, 6).T  # F-order view
        (b,) = unpack_bundle(pack_bundle([a]))
        np.testing.assert_array_equal(a, b)

    def test_unpacked_views_are_read_only(self):
        (b,) = unpack_bundle(pack_bundle([np.zeros(3, np.float32)]))
        with pytest.raises(ValueError):
            b[0] = 1.0

    # -- malformed-frame matrix: every reject typed and indexed ------------

    def test_bad_magic(self):
        blob = bytearray(pack_bundle([np.zeros(2, np.int32)]))
        blob[:4] = b"XXXX"
        with pytest.raises(ValueError, match="bad magic"):
            unpack_bundle(bytes(blob))

    def test_shorter_than_header(self):
        with pytest.raises(ValueError, match="shorter than"):
            unpack_bundle(_BUNDLE_MAGIC + b"\x01")

    def test_count_over_cap(self):
        import struct

        blob = _BUNDLE_MAGIC + struct.pack("<I", 1 << 20)
        with pytest.raises(ValueError, match="cap"):
            unpack_bundle(blob)

    def test_truncated_in_every_section(self):
        full = pack_bundle([np.arange(6, dtype=np.float64).reshape(2, 3)])
        # Cutting the payload ANYWHERE after the header must raise with a
        # frame-indexed message, never return a partial tensor.
        for cut in range(8, len(full) - 1):
            with pytest.raises(ValueError, match="tensor #0 truncated"):
                unpack_bundle(full[:cut])

    def test_truncated_second_tensor_names_its_index(self):
        full = pack_bundle([np.zeros(2, np.int32), np.zeros(4, np.int32)])
        with pytest.raises(ValueError, match="tensor #1 truncated"):
            unpack_bundle(full[: len(full) - 3])

    def test_declared_bytes_mismatch(self):
        blob = bytearray(pack_bundle([np.zeros((2, 2), np.float32)]))
        # nbytes field sits 8 bytes before the 16 payload bytes.
        off = len(blob) - 16 - 8
        blob[off] = 0xFF
        with pytest.raises(ValueError, match="declares .* bytes"):
            unpack_bundle(bytes(blob))

    def test_negative_dim_rejected(self):
        import struct

        blob = bytearray(pack_bundle([np.zeros((2, 2), np.float32)]))
        # First dim is the 8 little-endian bytes after magic+count+
        # name_len+name("float32")+ndim.
        off = 8 + 1 + len(b"float32") + 1
        blob[off : off + 8] = struct.pack("<q", -2)
        with pytest.raises(ValueError, match="negative dim"):
            unpack_bundle(bytes(blob))

    def test_unknown_dtype_rejected(self):
        blob = bytearray(pack_bundle([np.zeros(2, np.float32)]))
        # Overwrite the 7-char dtype name "float32" -> garbage.
        off = 8 + 1
        blob[off : off + 7] = b"zzzzzzz"
        with pytest.raises(ValueError, match="unknown dtype"):
            unpack_bundle(bytes(blob))

    def test_ndim_over_cap_rejected(self):
        blob = bytearray(pack_bundle([np.zeros(2, np.float32)]))
        off = 8 + 1 + len(b"float32")
        blob[off] = 200
        with pytest.raises(ValueError, match="dims"):
            unpack_bundle(bytes(blob))

    def test_trailing_garbage_rejected(self):
        blob = pack_bundle([np.zeros(2, np.float32)]) + b"\x00garbage"
        with pytest.raises(ValueError, match="trailing"):
            unpack_bundle(blob)

    def test_too_many_tensors_rejected_at_pack(self):
        arrays = [np.zeros(1, np.uint8)] * 4097
        with pytest.raises(ValueError, match="exceeds"):
            pack_bundle(arrays)


# ---------------------------------------------------------------------------
# migration payload: randomized round-trip sweep + crc / truncation gates
# ---------------------------------------------------------------------------


class TestMigrationPayloadProps:
    """Property-style sweeps without a hypothesis dependency: a seeded
    rng drives many random (page count, layer count, dtype, page size)
    configurations through pack→unpack; the invariants must hold for
    every draw."""

    DTYPES = ("float32", "float16", "int8", "bfloat16")

    def _leaves(self, rng):
        import ml_dtypes

        n_layers = int(rng.integers(1, 5))
        n_pages = int(rng.integers(1, 9))
        page = int(rng.integers(1, 17))
        heads, dim = int(rng.integers(1, 3)), int(rng.integers(1, 9))
        name = self.DTYPES[int(rng.integers(0, len(self.DTYPES)))]
        dt = np.dtype(getattr(ml_dtypes, name)) if name == "bfloat16" else np.dtype(name)
        leaves = [
            (rng.standard_normal((n_pages, 2, heads, page, dim)) * 3).astype(dt)
            for _ in range(n_layers)
        ]
        leaves.append(rng.integers(0, 2, size=(1, 64)).astype(np.bool_))
        return leaves

    def test_round_trip_many_random_configs(self):
        rng = np.random.default_rng(7)
        for _ in range(40):
            leaves = self._leaves(rng)
            blob, crc = pack_payload(leaves)
            assert crc == zlib.crc32(blob)
            out = unpack_payload(blob, crc)
            assert len(out) == len(leaves)
            for a, b in zip(leaves, out):
                assert a.dtype == b.dtype and a.shape == b.shape
                np.testing.assert_array_equal(
                    np.asarray(a, np.float32) if a.dtype.kind not in "biu" else a,
                    np.asarray(b, np.float32) if b.dtype.kind not in "biu" else b,
                )

    def test_any_single_byte_corruption_rejected(self):
        rng = np.random.default_rng(11)
        blob, crc = pack_payload(self._leaves(rng))
        for _ in range(20):
            pos = int(rng.integers(0, len(blob)))
            mutated = bytearray(blob)
            mutated[pos] ^= 0xFF
            with pytest.raises(ValueError):
                unpack_payload(bytes(mutated), crc)

    def test_any_truncation_rejected(self):
        rng = np.random.default_rng(13)
        blob, crc = pack_payload(self._leaves(rng))
        for _ in range(20):
            cut = int(rng.integers(0, len(blob)))
            with pytest.raises(ValueError):
                unpack_payload(blob[:cut], crc)

    def test_crc_none_skips_the_gate(self):
        blob, _ = pack_payload([np.zeros(3, np.float32)])
        assert len(unpack_payload(blob, None)) == 1

    def test_slice_pages_copies_the_list(self):
        """The local-fallback contract: slicing for the wire must leave
        the caller's snapshot list intact."""
        leaves = [np.arange(12, dtype=np.float32).reshape(3, 4),
                  np.zeros((1, 8), np.bool_)]
        sliced = migration.slice_pages(leaves, 1, 2)
        assert sliced is not leaves
        assert sliced[0].shape == (1, 4)
        assert leaves[0].shape == (3, 4)  # untouched

    def test_slice_pages_stop_drops_pad_tail(self):
        """``stop`` strips the export gather's power-of-2 pad rows so
        only real pages ride the wire."""
        leaves = [np.arange(16, dtype=np.float32).reshape(4, 4),
                  np.zeros((1, 8), np.bool_)]
        sliced = migration.slice_pages(leaves, 1, 0, stop=3)
        assert sliced[0].shape == (3, 4)
        assert sliced[1].shape == (1, 8)  # non-page leaf untouched
        both = migration.slice_pages(leaves, 1, 1, stop=3)
        assert both[0].shape == (2, 4)
        assert leaves[0].shape == (4, 4)  # caller's snapshot intact

    def test_manifest_csv_round_trip(self):
        keys = [bytes([i] * 16) for i in range(5)]
        assert manifest_from_csv(manifest_csv(keys)) == keys
        assert manifest_from_csv("") == []
        with pytest.raises(ValueError):
            manifest_from_csv("not-hex,zz")


class TestCommitMeta:
    def _meta(self, **over):
        kw = dict(
            crc=123, n_page_leaves=3, n_pages=4, n_shared=1, page_size=16,
            cur_tok=9, cur_len=33, n_gen=2, prompt_len=31, max_new=8,
            temperature=0.5, top_p=0.9, do_sample=True,
            repetition_penalty=1.1, manifest=[b"\x01" * 16, b"\x02" * 16],
        )
        kw.update(over)
        return commit_meta(**kw)

    def test_round_trip(self):
        m = parse_commit_meta(self._meta())
        assert m["crc"] == 123 and m["n_pages"] == 4 and m["n_shared"] == 1
        assert m["page_size"] == 16 and m["prompt_len"] == 31
        assert m["temperature"] == 0.5 and m["do_sample"] is True
        assert m["manifest"] == [b"\x01" * 16, b"\x02" * 16]

    def test_float_repr_is_exact(self):
        m = parse_commit_meta(self._meta(top_p=0.1 + 0.2))
        assert m["top_p"] == 0.1 + 0.2  # bit-exact through the wire

    def test_version_mismatch(self):
        meta = self._meta()
        meta["ver"] = "99"
        with pytest.raises(ValueError, match="version"):
            parse_commit_meta(meta)

    def test_missing_and_non_integer_fields_named(self):
        meta = self._meta()
        del meta["cur_len"]
        with pytest.raises(ValueError, match="cur_len"):
            parse_commit_meta(meta)
        meta = self._meta()
        meta["n_pages"] = "many"
        with pytest.raises(ValueError, match="n_pages"):
            parse_commit_meta(meta)
        meta = self._meta()
        meta["top_p"] = "hot"
        with pytest.raises(ValueError, match="top_p"):
            parse_commit_meta(meta)

    def test_page_invariants(self):
        with pytest.raises(ValueError, match="n_pages"):
            parse_commit_meta(self._meta(n_pages=0, n_shared=0))
        # n_shared == n_pages: at least one page must ride the wire.
        meta = self._meta()
        meta["n_shared"] = meta["n_pages"]
        with pytest.raises(ValueError, match="n_shared"):
            parse_commit_meta(meta)
        with pytest.raises(ValueError, match="manifest"):
            parse_commit_meta(self._meta(n_shared=2, manifest=[b"\x01" * 16]))
        meta = self._meta()
        meta["manifest"] = "zz-not-hex"
        with pytest.raises(ValueError, match="manifest"):
            parse_commit_meta(meta)


# ---------------------------------------------------------------------------
# Role advertisement
# ---------------------------------------------------------------------------


class _TrailerContext:
    """Captures set_trailing_metadata; abort raises like live gRPC."""

    def __init__(self):
        self.trailing = ()

    def set_trailing_metadata(self, md):
        self.trailing = tuple(md)

    def abort(self, code, detail):
        raise RuntimeError(f"abort {code}: {detail}")


class TestRoleAdvertisement:
    def test_env_parsing(self, monkeypatch):
        monkeypatch.delenv("LUMEN_FED_ROLE", raising=False)
        assert advertised_fed_role() is None
        monkeypatch.setenv("LUMEN_FED_ROLE", "prefill")
        assert advertised_fed_role() == "prefill"
        monkeypatch.setenv("LUMEN_FED_ROLE", "  Decode ")
        assert advertised_fed_role() == "decode"
        monkeypatch.setenv("LUMEN_FED_ROLE", "both")
        assert advertised_fed_role() == "both"

    def test_malformed_value_warns_once_and_disables(self, monkeypatch, caplog):
        monkeypatch.setenv("LUMEN_FED_ROLE", "turbo")
        monkeypatch.setattr(router_mod, "_ROLE_WARNED", False)
        with caplog.at_level("WARNING"):
            assert advertised_fed_role() is None
            assert advertised_fed_role() is None
        warned = [r for r in caplog.records if "LUMEN_FED_ROLE" in r.getMessage()]
        assert len(warned) == 1

    def test_health_trailer_carries_role_only_when_set(self, monkeypatch):
        router = HubRouter({"echo": EchoService()})
        monkeypatch.delenv("LUMEN_FED_ROLE", raising=False)
        ctx = _TrailerContext()
        router.Health(None, ctx)
        keys = [k for k, _ in ctx.trailing]
        assert FED_ROLE_META not in keys  # unconfigured: byte-identical

        monkeypatch.setenv("LUMEN_FED_ROLE", "decode")
        ctx = _TrailerContext()
        router.Health(None, ctx)
        assert (FED_ROLE_META, "decode") in ctx.trailing

    def test_explicit_both_is_advertised(self, monkeypatch):
        """An explicit `both` DOES ride the trailer — that is how a host
        reverting from a dedicated lane propagates the change to peers
        (only the UNSET path must stay byte-identical)."""
        monkeypatch.setenv("LUMEN_FED_ROLE", "both")
        router = HubRouter({"echo": EchoService()})
        ctx = _TrailerContext()
        router.Health(None, ctx)
        assert (FED_ROLE_META, "both") in ctx.trailing


# ---------------------------------------------------------------------------
# Role-aware planning
# ---------------------------------------------------------------------------


class _IdleStub:
    def Infer(self, request_iterator, timeout=None, metadata=None):  # noqa: N802, ARG002
        raise AssertionError("plan tests never dispatch")

    Health = Infer


def _manager(names, roles=None, **kw) -> FederationManager:
    m = FederationManager(
        [PeerSpec(n) for n in names],
        stub_factory=lambda addr: _IdleStub(),
        **kw,
    )
    for n, r in (roles or {}).items():
        m.peers[n].role = r
    return m


class TestDisaggPlan:
    NAMES = ["a:1", "b:1", "c:1"]

    def _plan(self, m, task="vlm_generate"):
        plan = [m.peers[n] for n in self.NAMES]
        return m.disagg_plan(task, plan)

    def test_identity_when_roles_unconfigured(self):
        m = _manager(self.NAMES)
        try:
            plan, owner = self._plan(m)
            assert [p.name for p in plan] == self.NAMES and owner is None
        finally:
            m.close()

    def test_identity_for_non_generation_tasks(self):
        m = _manager(self.NAMES, {"a:1": ROLE_PREFILL, "b:1": ROLE_DECODE})
        try:
            plan, owner = self._plan(m, task="clip_image_embed")
            assert [p.name for p in plan] == self.NAMES and owner is None
        finally:
            m.close()

    def test_prefill_leads_and_decode_owner_pinned(self):
        m = _manager(
            self.NAMES,
            {"a:1": ROLE_DECODE, "b:1": ROLE_PREFILL, "c:1": ROLE_BOTH},
        )
        try:
            plan, owner = self._plan(m)
            names = [p.name for p in plan]
            # Prefill-capable first (ring order among them), pure-decode
            # peers trail as last-resort forwards.
            assert names == ["b:1", "c:1", "a:1"]
            # First decode-capable peer in ring order owns the decode.
            assert owner == "a:1"
        finally:
            m.close()

    def test_colocated_owner_is_none(self):
        """When the forward target is itself the decode owner there is
        no phase boundary to cross — no migration metadata."""
        m = _manager(self.NAMES, {"a:1": ROLE_BOTH, "b:1": ROLE_BOTH,
                                  "c:1": ROLE_PREFILL})
        try:
            plan, owner = self._plan(m)
            assert plan[0].name == "a:1"
            assert owner is None  # a:1 is both: it prefills AND decodes
        finally:
            m.close()

    def test_single_peer_plan_is_identity(self):
        m = _manager(["a:1"], {"a:1": ROLE_PREFILL})
        try:
            plan, owner = m.disagg_plan("vlm_generate", [m.peers["a:1"]])
            assert [p.name for p in plan] == ["a:1"] and owner is None
        finally:
            m.close()

    def test_unservable_roles_warn_once_and_fall_back(self, caplog):
        m = _manager(self.NAMES, {n: ROLE_PREFILL for n in self.NAMES})
        try:
            with caplog.at_level("ERROR"):
                plan, owner = self._plan(m)
                assert [p.name for p in plan] == self.NAMES and owner is None
                self._plan(m)  # second call must stay silent
            errs = [r for r in caplog.records if "UNSERVABLE" in r.getMessage()]
            assert len(errs) == 1
            assert m._role_warned
        finally:
            m.close()

    def test_poll_coverage_check_warns_once(self, caplog):
        m = _manager(self.NAMES, {n: ROLE_DECODE for n in self.NAMES})
        try:
            with caplog.at_level("ERROR"):
                m._check_role_coverage()
                m._check_role_coverage()
            errs = [r for r in caplog.records if "UNSERVABLE" in r.getMessage()]
            assert len(errs) == 1
        finally:
            m.close()

    def test_all_both_coverage_is_silent(self, caplog):
        m = _manager(self.NAMES)
        try:
            with caplog.at_level("ERROR"):
                m._check_role_coverage()
            assert not [r for r in caplog.records if "UNSERVABLE" in r.getMessage()]
        finally:
            m.close()

    def test_export_status_carries_roles_and_migration(self):
        m = _manager(self.NAMES, {"a:1": ROLE_PREFILL})
        try:
            st = m.export_status()
            assert st["peers"]["a:1"]["state"] == "serving"
            assert st["peers"]["a:1"]["fed_role"] == ROLE_PREFILL
            assert st["role"] in ("both", "prefill", "decode")
            assert set(MIGRATION) <= set(st["kv_migration"])
        finally:
            m.close()


# ---------------------------------------------------------------------------
# Router: the reserved fed_kv_put task
# ---------------------------------------------------------------------------


def _kv_req(meta=None, **kw):
    return pb.InferRequest(
        correlation_id="k1", task=FED_KV_PUT_TASK, meta=meta or {}, **kw
    )


class TestRouterKvPut:
    def test_no_sink_is_typed_refusal(self):
        router = HubRouter({"echo": EchoService()})
        (resp,) = list(router.Infer(iter([_kv_req()]), None))
        assert resp.meta["fed_kv"] == "refused"
        assert resp.error.code == pb.ERROR_CODE_UNAVAILABLE
        assert "no KV migrations" in resp.error.message

    def test_drain_gate_applies(self):
        router = HubRouter({"echo": EchoService()})
        router.kv_migration = object()  # would crash if reached
        router._draining = True
        (resp,) = list(router.Infer(iter([_kv_req()]), None))
        assert resp.HasField("error")
        assert resp.meta.get("fed_kv") != "tok"

    def test_sink_crash_answers_in_band(self):
        class Boom:
            def handle_kv_put(self, first, it, ctx):
                raise RuntimeError("sink exploded")
                yield  # pragma: no cover

        router = HubRouter({"echo": EchoService()})
        router.kv_migration = Boom()
        (resp,) = list(router.Infer(iter([_kv_req()]), None))
        assert resp.meta["fed_kv"] == "refused"
        assert resp.error.code == pb.ERROR_CODE_INTERNAL
        assert "sink exploded" in resp.error.message

    def test_sink_delegation(self):
        seen = {}

        class Sink:
            def handle_kv_put(self, first, it, ctx):
                seen["op"] = first.meta.get("op")
                yield pb.InferResponse(
                    correlation_id=first.correlation_id, is_final=True,
                    meta={"fed_kv": "ok", "hit": "2"},
                )

        router = HubRouter({"echo": EchoService()})
        router.kv_migration = Sink()
        (resp,) = list(router.Infer(iter([_kv_req({"op": "offer"})]), None))
        assert seen["op"] == "offer" and resp.meta["hit"] == "2"

    def test_front_tier_refuses_without_forwarding(self):
        m = _manager(["a:1"])
        try:
            front = FederationRouter(m)
            (resp,) = list(front.Infer(iter([_kv_req()]), None))
            assert resp.meta["fed_kv"] == "refused"
            assert resp.error.code == pb.ERROR_CODE_UNAVAILABLE
        finally:
            m.close()


# ---------------------------------------------------------------------------
# Decode-host service handler: the refusal ladder
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def kv_mgr(model_dir):
    mgr = _make_mgr(model_dir)
    yield mgr
    mgr.close()


@pytest.fixture(scope="module")
def kv_service(kv_mgr):
    return VlmService(kv_mgr, service_name="vlm")


class TestKvPutService:
    def _run(self, svc, first, rest=()):
        return list(svc.handle_kv_put(first, iter(rest), None))

    def test_unknown_op_refused(self, kv_service):
        (resp,) = self._run(kv_service, _kv_req({"op": "teleport"}))
        assert resp.meta["fed_kv"] == "refused"
        assert resp.error.code == pb.ERROR_CODE_INVALID_ARGUMENT

    def test_offer_without_prefix_cache_answers_zero(self, kv_service, kv_mgr):
        eng = kv_mgr._pick_engine()
        manifest = manifest_csv([b"\x01" * 32])
        (resp,) = self._run(
            kv_service, _kv_req({"op": "offer", "manifest": manifest})
        )
        assert resp.meta["fed_kv"] == "ok"
        hit = int(resp.meta["hit"])
        if eng.prefix is None:
            assert hit == 0
        assert hit >= 0

    def test_offer_malformed_manifest_answers_zero(self, kv_service):
        (resp,) = self._run(
            kv_service, _kv_req({"op": "offer", "manifest": "zz-not-hex"})
        )
        assert resp.meta["fed_kv"] == "ok" and resp.meta["hit"] == "0"

    def test_truncated_commit_stream_refused(self, kv_service):
        meta = dict(commit_meta(
            crc=0, n_page_leaves=1, n_pages=1, n_shared=0, page_size=16,
            cur_tok=1, cur_len=17, n_gen=0, prompt_len=16, max_new=4,
            temperature=0.0, top_p=1.0, do_sample=False,
            repetition_penalty=1.0, manifest=[],
        ))
        first = _kv_req(meta, payload=b"part0", seq=0, total=3)
        (resp,) = self._run(kv_service, first, rest=())
        assert resp.meta["fed_kv"] == "refused"
        assert "chunk" in resp.error.message

    def test_bad_crc_refused(self, kv_service):
        blob, crc = pack_payload([np.zeros((1, 16, 8), np.float32),
                                  np.zeros((1, 8), np.bool_)])
        meta = dict(commit_meta(
            crc=crc ^ 0xDEAD, n_page_leaves=1, n_pages=1, n_shared=0,
            page_size=16, cur_tok=1, cur_len=17, n_gen=0, prompt_len=16,
            max_new=4, temperature=0.0, top_p=1.0, do_sample=False,
            repetition_penalty=1.0, manifest=[],
        ))
        first = _kv_req(meta, payload=blob, payload_mime=BUNDLE_MIME,
                        seq=0, total=1)
        (resp,) = self._run(kv_service, first)
        assert resp.meta["fed_kv"] == "refused"
        assert "crc" in resp.error.message

    def test_layout_mismatch_refused(self, kv_service):
        """A peer shipping the wrong number of page leaves (different
        model depth) must be refused by name, not scattered into the
        pool."""
        blob, crc = pack_payload([np.zeros((1, 4), np.float32),
                                  np.zeros((1, 8), np.bool_)])
        meta = dict(commit_meta(
            crc=crc, n_page_leaves=1, n_pages=1, n_shared=0, page_size=16,
            cur_tok=1, cur_len=17, n_gen=0, prompt_len=16, max_new=4,
            temperature=0.0, top_p=1.0, do_sample=False,
            repetition_penalty=1.0, manifest=[],
        ))
        first = _kv_req(meta, payload=blob, payload_mime=BUNDLE_MIME,
                        seq=0, total=1)
        (resp,) = self._run(kv_service, first)
        assert resp.meta["fed_kv"] == "refused"
        assert resp.error.code == pb.ERROR_CODE_INVALID_ARGUMENT

    def test_a_version_1_payload_is_refused(self, kv_service):
        """Version 1 shipped head-major pages; this host's pool is
        token-major, so an old peer's commit is refused by version before
        a byte of it is read."""
        blob, crc = pack_payload([np.zeros((1, 16, 8), np.float32),
                                  np.zeros((1, 8), np.bool_)])
        meta = dict(commit_meta(
            crc=crc, n_page_leaves=1, n_pages=1, n_shared=0, page_size=16,
            cur_tok=1, cur_len=17, n_gen=0, prompt_len=16, max_new=4,
            temperature=0.0, top_p=1.0, do_sample=False,
            repetition_penalty=1.0, manifest=[],
        ), ver="1")
        assert MIGRATE_VERSION == "2"
        first = _kv_req(meta, payload=blob, payload_mime=BUNDLE_MIME,
                        seq=0, total=1)
        (resp,) = self._run(kv_service, first)
        assert resp.meta["fed_kv"] == "refused"
        assert resp.error.code == pb.ERROR_CODE_INVALID_ARGUMENT
        assert "version '1'" in resp.error.message

    def test_rejections_count(self, kv_service):
        _reset_migration_counters()
        self._run(kv_service, _kv_req({"op": "teleport"}))
        assert MIGRATION["in_rejected"] == 1


# ---------------------------------------------------------------------------
# End-to-end in-process migration over the real dispatcher
# ---------------------------------------------------------------------------


class _InProcPeerStub:
    """Route the federation dispatcher's Infer calls straight into a
    decode host's router — the wire without the socket."""

    def __init__(self, servicer):
        self.servicer = servicer
        self.commits = 0

    def Infer(self, request_iterator, timeout=None, metadata=None):  # noqa: N802, ARG002
        msgs = list(request_iterator)
        if msgs and msgs[0].meta.get("op") != "offer":
            self.commits += 1
        return self.servicer.Infer(iter(msgs), None)

    def Health(self, request, timeout=None):  # noqa: N802, ARG002
        from google.protobuf import empty_pb2

        return empty_pb2.Empty()


class TestEndToEndMigration:
    PROMPTS = ["the quick brown fox", "alpha beta gamma", "hello"]

    def _fleet(self, model_dir, **over):
        """Prefill manager A + decode manager B joined by a real
        FederationManager whose stub lands on B's router in-process."""
        mgr_a = _make_mgr(model_dir, **over)
        mgr_b = _make_mgr(model_dir, **over)
        svc_b = VlmService(mgr_b, service_name="vlm")
        router_b = HubRouter({"vlm": svc_b})
        router_b.kv_migration = svc_b
        stub_b = _InProcPeerStub(router_b)
        fed = FederationManager(
            [PeerSpec("a:1"), PeerSpec("b:1")],
            self_name="a:1",
            stub_factory=lambda addr: stub_b if addr == "b:1" else _IdleStub(),
        )
        eng_a = mgr_a._pick_engine()
        eng_a.migrator = fed.kv_migrate
        return mgr_a, mgr_b, eng_a, fed, stub_b

    def _migrate_generate(self, mgr_a, prompt, max_new=8):
        e, pos, ln, ids, _ = mgr_a._prepare_inputs(
            [ChatMessage(role="user", content=prompt)], None, True
        )
        req = mgr_a._make_gen_request(e, pos, ln, ids, max_new, 0.0, 1.0,
                                      False, 1.0)
        req.migrate_to = "b:1"
        eng_a = mgr_a._pick_engine()
        fut = eng_a.submit(req)
        toks, _n, _eos = fut.result(timeout=60)
        return [int(t) for t in np.asarray(toks)]

    def _assert_balanced(self, eng):
        deadline = time.time() + 20
        while eng._slots and time.time() < deadline:
            time.sleep(0.01)
        stats = eng.kv.stats()
        assert stats.pages_live == 0
        assert stats.allocated_total == stats.freed_total
        # The oracle: every live page is exactly the referenced set.
        assert stats.pages_live == sum(
            1 for v in eng.kv._ref.values() if v > 0
        )

    def test_migrated_greedy_is_token_identical_with_zero_decode_prefill(
        self, model_dir
    ):
        _reset_migration_counters()
        mgr_a, mgr_b, eng_a, fed, stub_b = self._fleet(model_dir)
        try:
            want = [
                mgr_b.generate(
                    [ChatMessage(role="user", content=p)], max_new_tokens=8
                ).tokens
                for p in self.PROMPTS
            ]
            eng_b = mgr_b._pick_engine()
            prefills: list[int] = []
            real_prefill = eng_b.gen._prefill

            def counting_prefill(params, embeds, *a, **kw):
                prefills.append(int(embeds.shape[0]))
                return real_prefill(params, embeds, *a, **kw)

            eng_b.gen._prefill = counting_prefill
            try:
                got = [self._migrate_generate(mgr_a, p) for p in self.PROMPTS]
            finally:
                eng_b.gen._prefill = real_prefill
            for i, (g, w) in enumerate(zip(got, want)):
                assert g == w, (i, g, w)
            # Zero re-prefill on the decode host: migration admits pages,
            # never replays the prompt.
            assert prefills == []
            assert stub_b.commits == len(self.PROMPTS)
            assert eng_a.migrated_out == len(self.PROMPTS)
            assert eng_a.migrate_out_failed == 0
            assert eng_b.migrated_in == len(self.PROMPTS)
            assert eng_b.migrate_in_rejected == 0
            assert MIGRATION["puts"] == len(self.PROMPTS)
            assert MIGRATION["put_bytes"] > 0
            assert MIGRATION["in_commits"] == len(self.PROMPTS)
            assert MIGRATION["put_failures"] == 0
            self._assert_balanced(eng_a)
            self._assert_balanced(eng_b)
        finally:
            fed.close()
            mgr_a.close()
            mgr_b.close()

    def test_non_power_of_two_page_count_migrates(self, model_dir, monkeypatch):
        """Regression: the export gather pads page leaves up to a power
        of two for its compiled shape. The wire must ship only the REAL
        pages — a 3-page prompt (padded to 4) used to be refused by the
        decode host on every commit ("page leaf carries 4 page(s);
        commit declared 3") and silently fall back to local decode."""
        monkeypatch.setenv("LUMEN_VLM_PAGE_SIZE", "16")  # 46 tokens: three pages
        _reset_migration_counters()
        mgr_a, mgr_b, eng_a, fed, stub_b = self._fleet(
            model_dir, prefill_buckets=(16, 32, 64)
        )
        prompt = " ".join(f"w{i}" for i in range(40))
        try:
            _e, _pos, ln, _ids, _ = mgr_a._prepare_inputs(
                [ChatMessage(role="user", content=prompt)], None, True
            )
            n_pages = -(-int(np.asarray(ln)[0]) // eng_a.page_size)
            assert n_pages & (n_pages - 1), (
                f"prompt spans {n_pages} pages; the regression needs a "
                "non-power-of-2 count"
            )
            want = mgr_b.generate(
                [ChatMessage(role="user", content=prompt)], max_new_tokens=8
            ).tokens
            got = self._migrate_generate(mgr_a, prompt)
            assert got == want
            assert eng_a.migrate_out_failed == 0
            assert MIGRATION["put_failures"] == 0
            assert mgr_b._pick_engine().migrated_in == 1
            self._assert_balanced(eng_a)
            self._assert_balanced(mgr_b._pick_engine())
        finally:
            fed.close()
            mgr_a.close()
            mgr_b.close()

    def test_dead_peer_falls_back_to_local_decode(self, model_dir):
        """The ladder's safe rung: an unreachable decode host costs
        latency, never tokens — output matches the colocated run."""
        _reset_migration_counters()
        mgr_a = _make_mgr(model_dir)
        try:
            want = mgr_a.generate(
                [ChatMessage(role="user", content="the quick brown fox")],
                max_new_tokens=8,
            ).tokens

            class DeadStub:
                def Infer(self, it, timeout=None, metadata=None):  # noqa: N802, ARG002
                    import grpc

                    class E(grpc.RpcError):
                        def code(self):
                            return grpc.StatusCode.UNAVAILABLE

                    raise E()

                Health = Infer

            fed = FederationManager(
                [PeerSpec("a:1"), PeerSpec("b:1")],
                self_name="a:1",
                stub_factory=lambda addr: DeadStub(),
            )
            eng_a = mgr_a._pick_engine()
            eng_a.migrator = fed.kv_migrate
            try:
                got = self._migrate_generate(mgr_a, "the quick brown fox")
            finally:
                fed.close()
            assert got == want
            assert eng_a.migrated_out == 1
            assert eng_a.migrate_out_failed == 1
            assert MIGRATION["put_failures"] == 1
            self._assert_balanced(eng_a)
        finally:
            mgr_a.close()

    def test_mid_stream_peer_death_never_duplicates_tokens(self, model_dir):
        """Regression: when the peer dies AFTER the relay has streamed k
        tokens to the client, the local replay's delivered watermark
        must not move backward — it used to reset to the replay's block
        position and re-emit every token from there to the crash point
        as client-visible duplicates."""
        import queue as _queue

        import grpc

        _reset_migration_counters()
        mgr_a = _make_mgr(model_dir)
        mgr_b = _make_mgr(model_dir)
        svc_b = VlmService(mgr_b, service_name="vlm")
        router_b = HubRouter({"vlm": svc_b})
        router_b.kv_migration = svc_b
        inner = _InProcPeerStub(router_b)

        class CutMidStream:
            """Relay the real commit stream; cut the wire once >= 8
            tokens (two decode blocks) have crossed, so the watermark
            sits strictly past the replay's first block."""

            def Infer(self, it, timeout=None, metadata=None):  # noqa: N802, ARG002
                msgs = list(it)
                resps = inner.Infer(iter(msgs), None)
                if msgs and msgs[0].meta.get("op") == "offer":
                    yield from resps
                    return
                relayed = 0
                for resp in resps:
                    if resp.meta.get("fed_kv") == "tok":
                        yield resp
                        relayed += sum(
                            1 for p in resp.meta.get("toks", "").split(",") if p
                        )
                        if relayed >= 8:
                            class E(grpc.RpcError):
                                def code(self):
                                    return grpc.StatusCode.UNAVAILABLE

                            raise E()
                    else:
                        yield resp

            def Health(self, request, timeout=None):  # noqa: N802, ARG002
                return inner.Health(request, timeout)

        fed = FederationManager(
            [PeerSpec("a:1"), PeerSpec("b:1")],
            self_name="a:1",
            stub_factory=lambda addr: CutMidStream() if addr == "b:1" else _IdleStub(),
        )
        eng_a = mgr_a._pick_engine()
        eng_a.migrator = fed.kv_migrate
        try:
            prompt = "the quick brown fox"
            want = mgr_a.generate(
                [ChatMessage(role="user", content=prompt)], max_new_tokens=16
            ).tokens
            e, pos, ln, ids, _ = mgr_a._prepare_inputs(
                [ChatMessage(role="user", content=prompt)], None, True
            )
            req = mgr_a._make_gen_request(e, pos, ln, ids, 16, 0.0, 1.0,
                                          False, 1.0)
            req.stream_q = _queue.SimpleQueue()
            req.migrate_to = "b:1"
            toks, _n, _eos = eng_a.submit(req).result(timeout=60)
            assert [int(t) for t in np.asarray(toks)] == want
            streamed = []
            while True:
                try:
                    item = req.stream_q.get_nowait()
                except _queue.Empty:
                    break
                if isinstance(item, int):
                    streamed.append(item)
            # The client-visible stream: relay prefix + replay suffix,
            # no token lost, none duplicated.
            assert streamed == want
            assert eng_a.migrate_out_failed == 1
            assert MIGRATION["put_failures"] == 1
            self._assert_balanced(eng_a)
        finally:
            fed.close()
            mgr_a.close()
            mgr_b.close()

    def test_refusing_peer_falls_back_to_local_decode(self, model_dir):
        """A typed in-band refusal (no sink on the target) lands on the
        same rung as a dead transport."""
        _reset_migration_counters()
        mgr_a = _make_mgr(model_dir)
        try:
            want = mgr_a.generate(
                [ChatMessage(role="user", content="alpha beta")],
                max_new_tokens=8,
            ).tokens
            sinkless = HubRouter({"echo": EchoService()})  # kv_migration None
            stub = _InProcPeerStub(sinkless)
            fed = FederationManager(
                [PeerSpec("a:1"), PeerSpec("b:1")],
                self_name="a:1",
                stub_factory=lambda addr: stub,
            )
            eng_a = mgr_a._pick_engine()
            eng_a.migrator = fed.kv_migrate
            try:
                got = self._migrate_generate(mgr_a, "alpha beta")
            finally:
                fed.close()
            assert got == want
            assert eng_a.migrate_out_failed == 1
            assert MIGRATION["in_rejected"] == 0  # refused at the router
            self._assert_balanced(eng_a)
        finally:
            mgr_a.close()

    def test_lane_exhaustion_decodes_locally(self, model_dir, monkeypatch):
        _reset_migration_counters()
        monkeypatch.setenv("LUMEN_FED_KV_LANES", "1")
        mgr_a = _make_mgr(model_dir)
        try:
            fed = FederationManager(
                [PeerSpec("a:1"), PeerSpec("b:1")],
                self_name="a:1",
                stub_factory=lambda addr: _IdleStub(),
            )
            # Drain the only lane so the next dispatch refuses pre-wire.
            assert fed._kv_lanes.acquire(blocking=False)
            eng_a = mgr_a._pick_engine()
            eng_a.migrator = fed.kv_migrate
            try:
                want = mgr_a.generate(
                    [ChatMessage(role="user", content="hello")],
                    max_new_tokens=6,
                ).tokens
                got = self._migrate_generate(mgr_a, "hello", max_new=6)
            finally:
                fed._kv_lanes.release()
                fed.close()
            assert got == want
            assert MIGRATION["lane_busy"] == 1
            assert MIGRATION["puts"] == 0
            self._assert_balanced(eng_a)
        finally:
            mgr_a.close()

    def test_migration_interleaved_with_local_load_balances(self, model_dir):
        """Accounting oracle under interleaving: migrated-in rows land
        while LOCAL requests run (and may preempt/spill) on the decode
        engine; at drain every page is freed on both engines and
        refcounts match live pages."""
        _reset_migration_counters()
        mgr_a, mgr_b, eng_a, fed, _stub = self._fleet(model_dir)
        try:
            local: dict[int, object] = {}

            def run_local(i, p):
                local[i] = mgr_b.generate(
                    [ChatMessage(role="user", content=p)], max_new_tokens=8
                )

            threads = [
                threading.Thread(target=run_local, args=(i, p))
                for i, p in enumerate(("gamma delta epsilon", "count to ten"))
            ]
            for t in threads:
                t.start()
            got = [self._migrate_generate(mgr_a, p) for p in self.PROMPTS]
            for t in threads:
                t.join()
            assert all(len(g) > 0 for g in got)
            assert len(local) == 2 and all(r.tokens for r in local.values())
            self._assert_balanced(eng_a)
            self._assert_balanced(mgr_b._pick_engine())
        finally:
            fed.close()
            mgr_a.close()
            mgr_b.close()


# ---------------------------------------------------------------------------
# client.py peers: role + migration counters
# ---------------------------------------------------------------------------


class TestClientPeersDisagg:
    PAYLOAD = {
        "enabled": True,
        "mode": "peer",
        "self": "10.0.0.1:50051",
        "hops": 3,
        "role": "prefill",
        "peers": {
            "10.0.0.1:50051": {
                "state": "serving", "dispatches": 10, "failovers": 0,
                "sheds": 0, "ring_share": 0.5, "fed_role": "prefill",
            },
            "10.0.0.2:50051": {
                "state": "serving", "dispatches": 4, "failovers": 0,
                "sheds": 0, "ring_share": 0.5, "fed_role": "decode",
            },
        },
        "kv_migration": {
            "puts": 6, "put_bytes": 123456, "put_failures": 1,
            "ref_pages": 9, "lane_busy": 2, "in_commits": 2,
            "in_bytes": 777, "in_rejected": 0,
        },
        "cache_peer_hit_rate": 0.0,
    }

    def _serve(self, payload):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):  # noqa: A002
                pass

            def do_GET(self):  # noqa: N802
                body = json.dumps(payload).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        return httpd, httpd.server_address[1]

    def test_printer_shows_roles_and_migration(self, capsys):
        from lumen_tpu import client

        httpd, port = self._serve(self.PAYLOAD)
        try:
            rc = client.main(["peers", "--metrics-addr", f"127.0.0.1:{port}"])
            assert rc == 0
            out = capsys.readouterr().out
            assert "role=prefill" in out  # header AND the prefill peer
            assert "role=decode" in out
            assert "kv migration:" in out
            assert "out=6" in out and "123456B wire" in out
            assert "9 pages by-ref" in out and "1 failed" in out
            assert "2 lane-busy" in out
            assert "in=2" in out and "0 rejected" in out
            # 6 outbound vs 2 inbound -> 75% / 25%.
            assert "duty split: prefill 75% / decode 25%" in out
            rc = client.main(
                ["peers", "--metrics-addr", f"127.0.0.1:{port}", "--json"]
            )
            assert rc == 0
            parsed = json.loads(capsys.readouterr().out)
            assert parsed["kv_migration"]["puts"] == 6
            assert parsed["peers"]["10.0.0.2:50051"]["fed_role"] == "decode"
        finally:
            httpd.shutdown()
            httpd.server_close()

    def test_printer_quiet_without_disagg(self, capsys):
        """A fleet that never migrated prints exactly the old summary —
        no role column, no migration block."""
        from lumen_tpu import client

        payload = dict(self.PAYLOAD)
        payload.pop("role")
        payload["kv_migration"] = {k: 0 for k in self.PAYLOAD["kv_migration"]}
        payload["peers"] = {
            n: {k: v for k, v in p.items() if k != "fed_role"}
            for n, p in self.PAYLOAD["peers"].items()
        }
        httpd, port = self._serve(payload)
        try:
            rc = client.main(["peers", "--metrics-addr", f"127.0.0.1:{port}"])
            assert rc == 0
            out = capsys.readouterr().out
            assert "role=" not in out
            assert "kv migration" not in out
            assert "duty split" not in out
        finally:
            httpd.shutdown()
            httpd.server_close()
