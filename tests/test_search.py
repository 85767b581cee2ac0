"""Semantic-search tests: the ANN runtime (static-shape device index),
the search gRPC service, and the federation front's sharded fan-out.

The load-bearing properties:

- **merge == oracle** (hypothesis): splitting a corpus into shards,
  taking per-shard top-k and merging MUST equal one global numpy oracle
  for any corpus — including heavy ties, k past the shard size, and
  empty shards. This is what makes the fleet answer identical to a
  single-host answer.
- **upsert-during-query**: a search racing index growth returns only
  fully-committed vectors (each returned score matches the committed
  row's true cosine — no torn buffers, no phantom ids).
- **tensorwire round-trip**: float32 embedding payloads survive the
  wire bit-exactly, in both raw-tensor and bundle form.
"""

from __future__ import annotations

import hashlib
import json
import threading

import grpc
import numpy as np
import pytest

from lumen_tpu.runtime.ann import (
    AnnIndex,
    AnnShard,
    exact_oracle,
    merge_topk,
    normalize,
    shard_of,
)
from lumen_tpu.runtime.federation import FederationManager, PeerSpec
from lumen_tpu.serving.proto import ml_service_pb2 as pb
from lumen_tpu.serving.router import FederationRouter, HubRouter
from lumen_tpu.serving.services.search_service import (
    SEARCH_QUERY_TASK,
    SEARCH_UPSERT_TASK,
    SearchService,
)
from lumen_tpu.utils.tensorwire import (
    BUNDLE_MIME,
    TENSOR_MIME,
    pack_bundle,
    tensor_from_payload,
    tensor_payload,
    unpack_bundle,
)

DIM = 32


def _vecs(rng, n: int, dim: int = DIM) -> np.ndarray:
    return rng.standard_normal((n, dim)).astype(np.float32)


def _ids(n: int) -> list[str]:
    return [f"v{i:04d}" for i in range(n)]


# ---------------------------------------------------------------------------
# merge_topk == global oracle (hypothesis)
# ---------------------------------------------------------------------------


class TestMergeOracle:
    def test_sharded_merge_matches_global_oracle_fixed(self):
        rng = np.random.default_rng(3)
        vecs, ids = _vecs(rng, 200), _ids(200)
        q = rng.standard_normal(DIM).astype(np.float32)
        parts = []
        for s in range(4):
            rows = [i for i in range(200) if shard_of(ids[i], 4) == s]
            parts.append(
                exact_oracle([ids[i] for i in rows], vecs[rows], q, 10)
            )
        got_ids, got_scores = merge_topk(parts, 10)
        want_ids, want_scores = exact_oracle(ids, vecs, q, 10)
        assert got_ids == want_ids
        assert np.allclose(got_scores, want_scores)

    def test_empty_parts_and_k_past_corpus(self):
        rng = np.random.default_rng(4)
        vecs, ids = _vecs(rng, 3), _ids(3)
        q = rng.standard_normal(DIM).astype(np.float32)
        parts = [([], []), exact_oracle(ids, vecs, q, 50), ([], [])]
        got_ids, got_scores = merge_topk(parts, 50)
        assert got_ids == exact_oracle(ids, vecs, q, 50)[0]
        assert len(got_ids) == 3  # never pads past the corpus
        assert merge_topk([([], []), ([], [])], 5) == ([], [])

    def test_exact_ties_break_by_id(self):
        # Two identical vectors tie exactly; the smaller id must win in
        # BOTH the oracle and the merge, whatever shard each landed in.
        v = np.ones((1, DIM), np.float32)
        q = np.ones(DIM, np.float32)
        a = exact_oracle(["b"], v, q, 2)
        b = exact_oracle(["a"], v, q, 2)
        ids, _ = merge_topk([a, b], 2)
        assert ids == ["a", "b"]

    @pytest.mark.parametrize(
        "parts, k, want",
        [
            # a tie across shards: the smaller id wins whichever shard holds it
            ([(["d", "b"], [0.9, 0.5]), (["c", "a"], [0.9, 0.5])], 3, ["c", "d", "a"]),
            # a tie inside one shard, listed larger id first
            ([(["z", "y", "x"], [0.7, 0.7, 0.2])], 2, ["y", "z"]),
            # k cuts a tie group: the smallest ids of the group stay
            ([(["e", "c"], [0.4, 0.4]), (["d", "b"], [0.4, 0.4]), (["a"], [0.8])], 3, ["a", "b", "c"]),
            # every score equal: plain id order
            ([(["m", "k"], [0.0, 0.0]), (["n", "j", "l"], [0.0, 0.0, 0.0])], 4, ["j", "k", "l", "m"]),
            # an empty shard, k above every candidate: all of them, none invented
            ([([], []), (["q", "p"], [0.3, 0.3]), ([], [])], 9, ["p", "q"]),
        ],
        ids=["across-shards", "inside-a-shard", "k-cuts-a-group", "all-equal", "empty-shard-large-k"],
    )
    def test_tied_scores_keep_ascending_ids(self, parts, k, want):
        scores = {vid: s for ids, ss in parts for vid, s in zip(ids, ss)}
        got_ids, got_scores = merge_topk(parts, k)
        assert got_ids == want
        assert got_scores == [scores[v] for v in want]


try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional dev dependency
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    @settings(max_examples=40, deadline=None)
    @given(
        n=st.integers(min_value=0, max_value=64),
        k=st.integers(min_value=1, max_value=24),
        shards=st.integers(min_value=1, max_value=6),
        seed=st.integers(min_value=0, max_value=2**31 - 1),
        quantize=st.booleans(),
    )
    def test_sharded_merge_equals_global_oracle(n, k, shards, seed, quantize):
        rng = np.random.default_rng(seed)
        vecs = rng.standard_normal((n, 6)).astype(np.float32)
        if quantize:
            # Draw rows from a tiny pool so exact score ties are common
            # and the deterministic (-score, id) tie-break is exercised.
            pool = rng.standard_normal((3, 6)).astype(np.float32)
            vecs = pool[rng.integers(0, 3, size=n)] if n else vecs
        ids = [f"v{i:03d}" for i in range(n)]
        q = rng.standard_normal(6).astype(np.float32)
        parts = []
        for s in range(shards):
            rows = [i for i in range(n) if shard_of(ids[i], shards) == s]
            if rows:
                parts.append(
                    exact_oracle([ids[i] for i in rows], vecs[rows], q, k)
                )
            else:
                parts.append(([], []))  # empty shard: merge must skip it
        got_ids, got_scores = merge_topk(parts, k)
        want_ids, want_scores = exact_oracle(ids, vecs, q, k)
        assert got_ids == want_ids
        assert np.allclose(got_scores, want_scores)


# ---------------------------------------------------------------------------
# tensorwire round-trip for embedding payloads
# ---------------------------------------------------------------------------


class TestEmbeddingWire:
    def test_f32_tensor_round_trip_is_bit_exact(self):
        rng = np.random.default_rng(5)
        vec = rng.standard_normal(512).astype(np.float32)
        payload, meta = tensor_payload(vec)
        back = tensor_from_payload(bytes(payload), meta)
        assert back.dtype == np.float32
        assert back.shape == (512,)
        assert np.array_equal(
            np.asarray(back).view(np.uint32), vec.view(np.uint32)
        )  # bitwise, not just allclose: NaN payloads and -0.0 survive too

    def test_bundle_round_trip(self):
        rng = np.random.default_rng(6)
        vecs = _vecs(rng, 17)
        ids_blob = np.frombuffer(
            json.dumps(_ids(17)).encode(), np.uint8
        )
        out = unpack_bundle(pack_bundle([vecs, ids_blob]))
        assert len(out) == 2
        assert np.array_equal(np.asarray(out[0]), vecs)
        assert json.loads(bytes(np.asarray(out[1]))) == _ids(17)


# ---------------------------------------------------------------------------
# AnnShard / AnnIndex
# ---------------------------------------------------------------------------


class TestAnnShard:
    def test_recall_is_exact_across_growth(self):
        rng = np.random.default_rng(7)
        shard = AnnShard(DIM, name="t")
        vecs, ids = _vecs(rng, 300), _ids(300)
        # Three upserts forcing at least one capacity doubling past the
        # floor; results must be identical to one big oracle.
        for lo in (0, 100, 200):
            shard.upsert(ids[lo : lo + 100], vecs[lo : lo + 100])
        q = rng.standard_normal(DIM).astype(np.float32)
        got_ids, got_scores = shard.query(q, 10)
        want_ids, want_scores = exact_oracle(ids, vecs, q, 10)
        assert got_ids == want_ids
        assert np.allclose(got_scores, want_scores, atol=1e-5)

    def test_update_in_place_changes_ranking_not_count(self):
        rng = np.random.default_rng(8)
        shard = AnnShard(DIM, name="t")
        vecs, ids = _vecs(rng, 20), _ids(20)
        shard.upsert(ids, vecs)
        q = rng.standard_normal(DIM).astype(np.float32)
        added, updated = shard.upsert(["v0005"], q[None, :])
        assert (added, updated) == (0, 1)
        assert shard.count == 20
        got_ids, got_scores = shard.query(q, 1)
        assert got_ids == ["v0005"]
        assert got_scores[0] == pytest.approx(1.0, abs=1e-5)

    def test_tiled_path_matches_single_program(self, monkeypatch):
        rng = np.random.default_rng(9)
        vecs, ids = _vecs(rng, 700), _ids(700)
        q = rng.standard_normal(DIM).astype(np.float32)
        monkeypatch.setenv("LUMEN_ANN_TILE", "128")
        monkeypatch.setenv("LUMEN_ANN_MIN_CAPACITY", "1024")
        tiled = AnnShard(DIM, name="tiled")
        tiled.upsert(ids, vecs)
        got_ids, got_scores = tiled.query(q, 15)
        want_ids, want_scores = exact_oracle(ids, vecs, q, 15)
        assert got_ids == want_ids
        assert np.allclose(got_scores, want_scores, atol=1e-5)

    def test_k_past_count_and_empty_shard(self):
        rng = np.random.default_rng(10)
        shard = AnnShard(DIM, name="t")
        assert shard.query(rng.standard_normal(DIM).astype(np.float32), 5) == ([], [])
        shard.upsert(_ids(3), _vecs(rng, 3))
        ids, scores = shard.query(rng.standard_normal(DIM).astype(np.float32), 50)
        assert len(ids) == 3 and len(scores) == 3

    def test_in_batch_duplicate_last_write_wins(self):
        rng = np.random.default_rng(11)
        shard = AnnShard(DIM, name="t")
        a, b = _vecs(rng, 1)[0], _vecs(rng, 1)[0]
        added, updated = shard.upsert(["x", "x"], np.stack([a, b]))
        assert (added, updated) == (1, 0)
        assert shard.count == 1
        got_ids, got_scores = shard.query(b, 1)
        assert got_ids == ["x"]
        assert got_scores[0] == pytest.approx(1.0, abs=1e-5)

    def test_max_vectors_refused_with_clear_error(self, monkeypatch):
        monkeypatch.setenv("LUMEN_ANN_MAX_VECTORS", "4")
        rng = np.random.default_rng(12)
        shard = AnnShard(DIM, name="t")
        shard.upsert(_ids(4), _vecs(rng, 4))
        with pytest.raises(ValueError, match="LUMEN_ANN_MAX_VECTORS"):
            shard.upsert(["overflow"], _vecs(rng, 1))

    def test_index_partitions_and_merges_like_oracle(self):
        rng = np.random.default_rng(13)
        index = AnnIndex(DIM)
        vecs, ids = _vecs(rng, 120), _ids(120)
        index.upsert("tenant-a", ids, vecs)
        q = rng.standard_normal(DIM).astype(np.float32)
        got_ids, got_scores, shards_read = index.query("tenant-a", q, 10)
        want_ids, want_scores = exact_oracle(ids, vecs, q, 10)
        assert got_ids == want_ids
        assert np.allclose(got_scores, want_scores, atol=1e-5)
        assert shards_read == len(index.shards_for("tenant-a"))
        # Tenants are hard-isolated: an unknown tenant owns nothing.
        assert index.query("tenant-b", q, 10)[0] == []

    def test_upsert_during_query_returns_only_committed_vectors(self):
        """The race the ISSUE names: searches concurrent with index
        growth must see only fully-committed rows. Every returned id
        must already be in the writer's committed log, and its score
        must equal the true cosine of that row — a torn buffer or a
        phantom index would fail one of the two."""
        shard = AnnShard(DIM, name="race")
        committed: dict[str, np.ndarray] = {}
        stop = threading.Event()
        failures: list[Exception] = []

        def writer():
            wrng = np.random.default_rng(99)
            try:
                for batch in range(50):
                    if stop.is_set():
                        return
                    ids = [f"w{batch:02d}-{j}" for j in range(8)]
                    vs = wrng.standard_normal((8, DIM)).astype(np.float32)
                    for vid, v in zip(ids, vs):
                        committed[vid] = v  # recorded BEFORE the commit
                    shard.upsert(ids, vs)
            except Exception as e:  # noqa: BLE001 - surfaced below
                failures.append(e)

        t = threading.Thread(target=writer, name="ann-writer")
        t.start()
        qrng = np.random.default_rng(100)
        try:
            for _ in range(120):
                q = qrng.standard_normal(DIM).astype(np.float32)
                ids, scores = shard.query(q, 5)
                qn = normalize(q)[0]
                for vid, score in zip(ids, scores):
                    assert vid in committed, f"phantom id {vid!r}"
                    vn = normalize(committed[vid])[0]
                    assert float(qn @ vn) == pytest.approx(score, abs=5e-3)
        finally:
            stop.set()
            t.join()
        assert not failures, failures
        assert shard.count == len(committed) == 400


# ---------------------------------------------------------------------------
# SearchService over the gRPC surface
# ---------------------------------------------------------------------------


def _collect(svc, req):
    out = list(svc.Infer(iter([req]), None))
    assert len(out) == 1, out
    return out[0]


def _bundle(ids, vecs) -> bytes:
    return pack_bundle(
        [np.asarray(vecs, np.float32), np.frombuffer(json.dumps(ids).encode(), np.uint8)]
    )


class TestSearchService:
    @pytest.fixture()
    def svc(self):
        s = SearchService(dim=DIM)
        yield s
        s.close()

    def test_upsert_then_query_tensor_path(self, svc):
        rng = np.random.default_rng(20)
        vecs, ids = _vecs(rng, 64), _ids(64)
        resp = _collect(
            svc,
            pb.InferRequest(
                correlation_id="u", task=SEARCH_UPSERT_TASK,
                payload=_bundle(ids, vecs), payload_mime=BUNDLE_MIME,
                meta={"tenant": "t1"},
            ),
        )
        assert not resp.HasField("error"), resp
        body = json.loads(resp.result)
        assert body["added"] == 64 and body["updated"] == 0

        q = rng.standard_normal(DIM).astype(np.float32)
        payload, meta = tensor_payload(q)
        meta = {**meta, "tenant": "t1", "k": "7"}
        resp = _collect(
            svc,
            pb.InferRequest(
                correlation_id="q", task=SEARCH_QUERY_TASK,
                payload=bytes(payload), payload_mime=TENSOR_MIME, meta=meta,
            ),
        )
        assert not resp.HasField("error"), resp
        got = json.loads(resp.result)
        want_ids, want_scores = exact_oracle(ids, vecs, q, 7)
        assert got["ids"] == want_ids
        assert np.allclose(got["scores"], want_scores, atol=1e-5)

    def test_json_paths_and_shard_pinning(self, svc):
        rng = np.random.default_rng(21)
        v = rng.standard_normal(DIM).astype(np.float32)
        resp = _collect(
            svc,
            pb.InferRequest(
                correlation_id="u", task=SEARCH_UPSERT_TASK,
                payload=json.dumps(
                    {"ids": ["only"], "vectors": [v.tolist()]}
                ).encode(),
                payload_mime="application/json",
                meta={"tenant": "t2", "shard": "1"},
            ),
        )
        assert json.loads(resp.result)["added"] == 1
        # Pinned to shard 1: querying shard 0 sees nothing, shard 1 hits.
        for shard, want in (("0", []), ("1", ["only"])):
            resp = _collect(
                svc,
                pb.InferRequest(
                    correlation_id="q", task=SEARCH_QUERY_TASK,
                    payload=json.dumps({"vector": v.tolist()}).encode(),
                    payload_mime="application/json",
                    meta={"tenant": "t2", "shard": shard, "k": "3"},
                ),
            )
            assert json.loads(resp.result)["ids"] == want

    def test_invalid_inputs_answer_in_band(self, svc):
        bad_k = _collect(
            svc,
            pb.InferRequest(
                correlation_id="q", task=SEARCH_QUERY_TASK,
                payload=json.dumps({"vector": [0.0] * DIM}).encode(),
                payload_mime="application/json", meta={"k": "zero"},
            ),
        )
        assert bad_k.error.code == pb.ERROR_CODE_INVALID_ARGUMENT
        wrong_dim = _collect(
            svc,
            pb.InferRequest(
                correlation_id="q", task=SEARCH_QUERY_TASK,
                payload=json.dumps({"vector": [0.0] * (DIM + 1)}).encode(),
                payload_mime="application/json", meta={},
            ),
        )
        assert wrong_dim.error.code == pb.ERROR_CODE_INVALID_ARGUMENT
        ragged = _collect(
            svc,
            pb.InferRequest(
                correlation_id="u", task=SEARCH_UPSERT_TASK,
                payload=json.dumps(
                    {"ids": ["a", "b"], "vectors": [[0.0] * DIM]}
                ).encode(),
                payload_mime="application/json", meta={},
            ),
        )
        assert ragged.error.code == pb.ERROR_CODE_INVALID_ARGUMENT

    def test_capability_advertises_tensor_specs(self, svc):
        cap = svc.capability()
        tasks = {t.name for t in cap.tasks}
        assert {SEARCH_QUERY_TASK, SEARCH_UPSERT_TASK} <= tasks
        assert cap.extra[f"tensor_input:{SEARCH_QUERY_TASK}"] == f"float32:{DIM}"
        assert cap.extra["ann_dim"] == str(DIM)


# ---------------------------------------------------------------------------
# Federation front: sharded fan-out
# ---------------------------------------------------------------------------


class _InProcStub:
    """A 'peer' without a socket: stub calls route into a servicer."""

    def __init__(self, servicer):
        self.servicer = servicer
        self.infer_calls = 0

    def Infer(self, request_iterator, timeout=None, metadata=None):  # noqa: N802, ARG002
        self.infer_calls += 1
        return self.servicer.Infer(request_iterator, None)

    def Health(self, request, timeout=None):  # noqa: N802, ARG002
        raise _FakeRpcError(grpc.StatusCode.UNIMPLEMENTED)


class _FakeRpcError(grpc.RpcError):
    def __init__(self, code=grpc.StatusCode.UNAVAILABLE):
        super().__init__()
        self._code = code

    def code(self):
        return self._code


class _DeadStub:
    def Infer(self, request_iterator, timeout=None, metadata=None):  # noqa: N802, ARG002
        raise _FakeRpcError()


def _fleet(n=3, dead=()):
    """A front over n single-service search peers (in-process)."""
    services, stubs = [], {}
    for i in range(n):
        name = f"peer{i}:1"
        if name in dead:
            stubs[name] = _DeadStub()
            continue
        svc = SearchService(dim=DIM)
        services.append(svc)
        stubs[name] = _InProcStub(HubRouter({"search": svc}))
    fed = FederationManager(
        [PeerSpec(name) for name in stubs],
        stub_factory=lambda addr: stubs[addr],
    )
    return FederationRouter(fed), services, stubs


class TestSearchFanout:
    def test_fanout_parity_with_oracle(self, monkeypatch):
        monkeypatch.setenv("LUMEN_ANN_SHARDS", "3")
        front, services, stubs = _fleet(3)
        try:
            rng = np.random.default_rng(30)
            vecs, ids = _vecs(rng, 240), _ids(240)
            resp = _collect(
                front,
                pb.InferRequest(
                    correlation_id="u", task=SEARCH_UPSERT_TASK,
                    payload=_bundle(ids, vecs), payload_mime=BUNDLE_MIME,
                    meta={"tenant": "t1"},
                ),
            )
            body = json.loads(resp.result)
            assert body["added"] == 240 and body["shards"] == 3
            # The batch was PARTITIONED: every vector lives exactly once
            # somewhere in the fleet.
            held = sum(
                s.count
                for svc in services
                for s in svc.index.shards_for("t1").values()
            )
            assert held == 240

            q = rng.standard_normal(DIM).astype(np.float32)
            payload, meta = tensor_payload(q)
            resp = _collect(
                front,
                pb.InferRequest(
                    correlation_id="q", task=SEARCH_QUERY_TASK,
                    payload=bytes(payload), payload_mime=TENSOR_MIME,
                    meta={**meta, "tenant": "t1", "k": "10"},
                ),
            )
            assert not resp.HasField("error"), resp
            got = json.loads(resp.result)
            want_ids, want_scores = exact_oracle(ids, vecs, q, 10)
            assert got["ids"] == want_ids
            assert np.allclose(got["scores"], want_scores, atol=1e-5)
            assert got["shards"] == 3
        finally:
            for svc in services:
                svc.close()

    def test_dead_owner_fails_over_to_ring_successor(self, monkeypatch):
        monkeypatch.setenv("LUMEN_ANN_SHARDS", "2")
        front, services, stubs = _fleet(3, dead=("peer1:1",))
        try:
            rng = np.random.default_rng(31)
            vecs, ids = _vecs(rng, 60), _ids(60)
            resp = _collect(
                front,
                pb.InferRequest(
                    correlation_id="u", task=SEARCH_UPSERT_TASK,
                    payload=_bundle(ids, vecs), payload_mime=BUNDLE_MIME,
                    meta={"tenant": "t1"},
                ),
            )
            assert not resp.HasField("error"), resp
            q = rng.standard_normal(DIM).astype(np.float32)
            resp = _collect(
                front,
                pb.InferRequest(
                    correlation_id="q", task=SEARCH_QUERY_TASK,
                    payload=json.dumps({"vector": q.tolist()}).encode(),
                    payload_mime="application/json",
                    meta={"tenant": "t1", "k": "5"},
                ),
            )
            assert not resp.HasField("error"), resp
            got = json.loads(resp.result)
            # Even with one peer dead, the surviving owners hold every
            # vector and the merged answer still equals the oracle.
            want_ids, _ = exact_oracle(ids, vecs, q, 5)
            assert got["ids"] == want_ids
        finally:
            for svc in services:
                svc.close()

    def test_malformed_upsert_answers_invalid_argument(self, monkeypatch):
        monkeypatch.setenv("LUMEN_ANN_SHARDS", "2")
        front, services, stubs = _fleet(1)
        try:
            resp = _collect(
                front,
                pb.InferRequest(
                    correlation_id="u", task=SEARCH_UPSERT_TASK,
                    payload=b"not json", payload_mime="application/json",
                    meta={},
                ),
            )
            assert resp.error.code == pb.ERROR_CODE_INVALID_ARGUMENT
        finally:
            for svc in services:
                svc.close()

    def test_ring_key_is_per_shard_not_per_payload(self, monkeypatch):
        # The SAME query payload must fan out to EVERY shard owner, not
        # consistent-hash to one peer — the defining difference between
        # search routing and ordinary content-address routing.
        monkeypatch.setenv("LUMEN_ANN_SHARDS", "4")
        front, services, stubs = _fleet(3)
        try:
            keys = {
                hashlib.sha256(f"ann/t1/{i}".encode()).hexdigest()
                for i in range(4)
            }
            owners = {front.federation.plan(k)[0].name for k in keys}
            assert len(owners) > 1  # 4 shard keys spread over 3 peers
            q = np.zeros(DIM, np.float32)
            _collect(
                front,
                pb.InferRequest(
                    correlation_id="q", task=SEARCH_QUERY_TASK,
                    payload=json.dumps({"vector": q.tolist()}).encode(),
                    payload_mime="application/json",
                    meta={"tenant": "t1", "k": "1"},
                ),
            )
            called = {
                name for name, stub in stubs.items()
                if getattr(stub, "infer_calls", 0) > 0
            }
            assert called == owners
        finally:
            for svc in services:
                svc.close()


# ---------------------------------------------------------------------------
# CLI subcommands: `client search` / `client upsert` over a fake stub
# ---------------------------------------------------------------------------


class _CliStub:
    """Channel-less InferenceStub: records each call's first request +
    invocation metadata, then routes into a real HubRouter servicer."""

    def __init__(self, servicer):
        self.servicer = servicer
        self.calls: list[tuple] = []

    def Infer(self, request_iterator, timeout=None, metadata=None):  # noqa: N802, ARG002
        msgs = list(request_iterator)
        self.calls.append((msgs[0], metadata))
        return self.servicer.Infer(iter(msgs), None)


class TestSearchCli:
    @pytest.fixture()
    def cli(self, monkeypatch):
        import types

        from lumen_tpu import client

        svc = SearchService(dim=DIM)
        stub = _CliStub(HubRouter({"search": svc}))
        monkeypatch.setattr(client.grpc, "insecure_channel", lambda addr: object())
        monkeypatch.setattr(
            client.grpc, "channel_ready_future",
            lambda chan: types.SimpleNamespace(result=lambda timeout=None: None),
        )
        monkeypatch.setattr(client.pbg, "InferenceStub", lambda chan: stub)
        yield client, stub, svc
        svc.close()

    def test_upsert_then_search_roundtrip(self, cli, tmp_path, capsys):
        client, stub, _svc = cli
        rng = np.random.default_rng(17)
        vecs, ids = _vecs(rng, 40), _ids(40)
        batch = tmp_path / "batch.json"
        batch.write_text(json.dumps({"ids": ids, "vectors": vecs.tolist()}))
        assert client.main(["upsert", str(batch)]) == 0
        out = capsys.readouterr().out
        assert "added=40 updated=0" in out
        # The batch crossed the wire as a tensor/bundle, not JSON.
        first, _md = stub.calls[0]
        assert first.payload_mime == BUNDLE_MIME

        qfile = tmp_path / "q.json"
        qfile.write_text(json.dumps(vecs[7].tolist()))
        assert client.main(["search", str(qfile), "-k", "5", "--json"]) == 0
        got = json.loads(capsys.readouterr().out)
        want_ids, _ = exact_oracle(ids, vecs, vecs[7], 5)
        assert got["ids"] == want_ids
        assert got["ids"][0] == ids[7]
        # The query vector rode the raw-tensor path (zero server decode).
        first, _md = stub.calls[1]
        assert first.payload_mime == TENSOR_MIME
        assert first.meta["k"] == "5"

    def test_search_ranked_output_and_empty_index(self, cli, tmp_path, capsys):
        client, _stub, _svc = cli
        rng = np.random.default_rng(3)
        vecs, ids = _vecs(rng, 8), _ids(8)
        batch = tmp_path / "batch.json"
        batch.write_text(json.dumps({"ids": ids, "vectors": vecs.tolist()}))
        qfile = tmp_path / "q.json"
        qfile.write_text(json.dumps(vecs[2].tolist()))

        # Empty index first: a friendly no-hits line, not a stack trace.
        assert client.main(["--tenant", "nobody", "search", str(qfile)]) == 0
        assert "no hits" in capsys.readouterr().out

        assert client.main(["upsert", str(batch)]) == 0
        capsys.readouterr()
        assert client.main(["search", str(qfile), "-k", "3"]) == 0
        lines = capsys.readouterr().out.strip().splitlines()
        assert len(lines) == 3
        assert lines[0].lstrip().startswith("1.") and ids[2] in lines[0]

    def test_tenant_rides_invocation_metadata(self, cli, tmp_path):
        client, stub, _svc = cli
        batch = tmp_path / "batch.json"
        batch.write_text(json.dumps(
            {"ids": ["a"], "vectors": [[0.1] * DIM]}
        ))
        assert client.main(["--tenant", "alice", "upsert", str(batch)]) == 0
        _first, md = stub.calls[0]
        assert ("lumen-tenant", "alice") in (md or ())

    def test_malformed_inputs_fail_loudly(self, cli, tmp_path):
        client, _stub, _svc = cli
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"vectors": [[0.1] * DIM]}))  # ids missing
        with pytest.raises(SystemExit, match="ids"):
            client.main(["upsert", str(bad)])
        wrong_dim = tmp_path / "wrong.json"
        wrong_dim.write_text(json.dumps([0.5] * (DIM + 1)))
        with pytest.raises(SystemExit):
            client.main(["search", str(wrong_dim)])
        with pytest.raises(SystemExit, match="cannot read"):
            client.main(["search", str(tmp_path / "absent.json")])
