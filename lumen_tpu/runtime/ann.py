"""Device-resident exact-ANN index over L2-normalized embeddings.

The product surface ROADMAP item 3 names: the CLIP embeddings the photo
pipeline already produces become queryable — "search my library" — by
brute-force cosine scoring on the chip. Brute force is the right call at
this scale: one fused ``scores = q @ buf.T`` + ``jax.lax.top_k`` over a
100k x 512 f32 shard is a fraction of a millisecond of MXU time, recall
is exactly 1.0 by construction (the bench asserts it against a numpy
oracle), and there is no graph/tree structure to rebuild on upsert.

Static-shape discipline (the same contract as every other device
structure in this repo):

- vectors live in a fixed-capacity ``(capacity, dim)`` f32 device buffer;
  growth DOUBLES the capacity (``LUMEN_ANN_MIN_CAPACITY`` floor), so XLA
  compiles one program per capacity bucket, never per upsert;
- upserts land via one jitted scatter per (capacity, write-bucket) pair —
  write batches pad to power-of-two buckets by repeating the last
  (row, index) pair, which is idempotent;
- queries run one jitted matmul + ``lax.top_k`` per (capacity, Q-bucket,
  k-bucket); shards past the VMEM-friendly tile (``LUMEN_ANN_TILE`` rows)
  score tile-by-tile under ``lax.map`` and merge the per-tile top-k, so
  the scratch footprint stays one tile no matter how big the shard grows.

Concurrency contract (the upsert-during-query guarantee): jax arrays are
immutable, so a write builds a NEW buffer and the shard commits
``(buffer, count, ids)`` as one atomic snapshot under its lock only
after the device write has been dispatched. A query snapshots the triple
once; it either sees the index entirely before or entirely after any
upsert — never a torn state — and row ids are append-only, so resolving
indices against a LATER ids list is always safe for committed rows.

jax is imported lazily (module level would break the jax-free serving
imports this package keeps deliberately light).
"""

from __future__ import annotations

import heapq
import logging
import threading
from typing import Any, Sequence

import numpy as np

from ..utils.env import env_int
from ..utils.metrics import metrics

logger = logging.getLogger(__name__)

#: rows per ``lax.map`` scoring tile. 8192 x 512 f32 is 16MB of operand —
#: it streams through VMEM comfortably; buffers at or under one tile
#: score in a single fused matmul with no map overhead.
TILE_ENV = "LUMEN_ANN_TILE"
#: smallest device buffer allocated per shard (doubling growth above it).
MIN_CAP_ENV = "LUMEN_ANN_MIN_CAPACITY"
#: hard per-shard row cap — an upsert past it is refused with a clear
#: error instead of growing until HBM dies under someone's feet.
MAX_VECTORS_ENV = "LUMEN_ANN_MAX_VECTORS"
#: logical shards per tenant: the federation front fans a query out to
#: the ring owners of ``ann/<tenant>/<shard>`` keys and merges the heaps.
SHARDS_ENV = "LUMEN_ANN_SHARDS"
#: ceiling on a single query's k (results per shard before the merge).
K_CAP_ENV = "LUMEN_ANN_K_CAP"


def ann_tile() -> int:
    return env_int(TILE_ENV, 8192, minimum=128)


def ann_min_capacity() -> int:
    return env_int(MIN_CAP_ENV, 1024, minimum=8)


def ann_max_vectors() -> int:
    return env_int(MAX_VECTORS_ENV, 1_000_000, minimum=1)


def ann_shards() -> int:
    return env_int(SHARDS_ENV, 3, minimum=1)


def ann_k_cap() -> int:
    return env_int(K_CAP_ENV, 128, minimum=1)


def _pow2_at_least(n: int, floor: int = 1) -> int:
    out = max(1, floor)
    while out < n:
        out *= 2
    return out


def normalize(vecs: np.ndarray) -> np.ndarray:
    """L2-normalize rows (host-side, float32). Zero vectors stay zero
    instead of dividing into NaNs — they simply never score above any
    real match."""
    vecs = np.asarray(vecs, dtype=np.float32)
    if vecs.ndim == 1:
        vecs = vecs[None, :]
    norms = np.linalg.norm(vecs, axis=-1, keepdims=True)
    return vecs / np.maximum(norms, 1e-12)


def shard_of(vec_id: str, shards: int) -> int:
    """Stable shard assignment for one vector id — the SAME function on
    the front tier (which partitions upsert batches) and on a single host
    (which partitions locally), so a library indexed standalone reshards
    identically when a fleet grows around it."""
    import hashlib

    if shards <= 1:
        return 0
    digest = hashlib.sha256(vec_id.encode("utf-8")).digest()
    return int.from_bytes(digest[:8], "big") % shards


def merge_topk(
    parts: Sequence[tuple[Sequence[str], Sequence[float]]], k: int
) -> tuple[list[str], list[float]]:
    """Merge per-shard ``(ids, scores)`` top-k lists into one global
    top-k. Deterministic tie-break — score descending, then id ascending
    — so a sharded merge is bit-reproducible and comparable against a
    sorted oracle. Tolerates empty shards and k larger than any shard's
    contribution (the hypothesis property test exercises both)."""
    ordered = heapq.nsmallest(
        k,
        ((-float(score), str(vid)) for ids, scores in parts for vid, score in zip(ids, scores)),
    )
    return [vid for _, vid in ordered], [-neg for neg, _ in ordered]


class AnnShard:
    """One tenant-shard's device buffer + id table. Thread-safe."""

    def __init__(self, dim: int, name: str = "ann"):
        if dim < 1:
            raise ValueError(f"embedding dim must be >= 1, got {dim}")
        self.dim = int(dim)
        self.name = name
        self._lock = threading.Lock()
        # Committed snapshot: queries read (buffer, count) under the lock
        # and compute outside it. ids is APPEND-ONLY (updates rewrite the
        # row in place under the same id), so index -> id resolution after
        # the device call needs no snapshot of its own.
        self._buf = None  # lazy: allocated on first upsert
        self._n = 0
        self._ids: list[str] = []
        self._row: dict[str, int] = {}
        self._capacity = 0

    # -- internals --------------------------------------------------------

    def _grow_to(self, need: int) -> None:
        """Ensure capacity >= need (doubling; caller holds the lock)."""
        import jax.numpy as jnp

        cap = self._capacity or ann_min_capacity()
        cap = _pow2_at_least(need, floor=max(cap, ann_min_capacity()))
        if cap == self._capacity:
            return
        new = jnp.zeros((cap, self.dim), dtype=jnp.float32)
        if self._buf is not None and self._n:
            new = new.at[: self._capacity].set(self._buf)
        self._buf = new
        self._capacity = cap
        metrics.count("ann_grows")

    # -- public API -------------------------------------------------------

    @property
    def count(self) -> int:
        with self._lock:
            return self._n

    @property
    def capacity(self) -> int:
        with self._lock:
            return self._capacity

    def upsert(self, ids: Sequence[str], vecs: np.ndarray) -> tuple[int, int]:
        """Insert-or-replace ``vecs[i]`` under ``ids[i]``. Returns
        ``(added, updated)``. Vectors are L2-normalized here so scoring
        is cosine similarity regardless of what the caller sends."""
        import jax.numpy as jnp

        vecs = normalize(vecs)
        if len(ids) != vecs.shape[0]:
            raise ValueError(
                f"{len(ids)} ids but {vecs.shape[0]} vectors"
            )
        if vecs.shape[1] != self.dim:
            raise ValueError(
                f"vector dim {vecs.shape[1]} != index dim {self.dim}"
            )
        if not len(ids):
            return 0, 0
        # Last-write-wins WITHIN the batch too: a duplicated id writes its
        # final vector once instead of burning two scatter rows.
        dedup: dict[str, np.ndarray] = {}
        for vid, vec in zip(ids, vecs):
            dedup[str(vid)] = vec
        with self._lock:
            added = sum(1 for vid in dedup if vid not in self._row)
            updated = len(dedup) - added
            new_n = self._n + added
            if new_n > ann_max_vectors():
                raise ValueError(
                    f"shard {self.name!r} would hold {new_n} vectors, over "
                    f"the {MAX_VECTORS_ENV}={ann_max_vectors()} cap"
                )
            self._grow_to(new_n)
            idx_list: list[int] = []
            next_row = self._n
            for vid in dedup:
                row = self._row.get(vid)
                if row is None:
                    row = next_row
                    next_row += 1
                idx_list.append(row)
            rows = np.stack(list(dedup.values()))
            # Pad to a power-of-two bucket by REPEATING the last real
            # (index, row) pair — an idempotent rewrite, so each
            # (capacity, bucket) pair compiles exactly once.
            bucket = _pow2_at_least(len(idx_list))
            pad = bucket - len(idx_list)
            if pad:
                idx_arr = np.concatenate(
                    [idx_list, np.full(pad, idx_list[-1], np.int32)]
                ).astype(np.int32)
                rows = np.concatenate([rows, np.repeat(rows[-1:], pad, 0)])
            else:
                idx_arr = np.asarray(idx_list, np.int32)
            new_buf = _scatter_write(
                self._buf, jnp.asarray(idx_arr), jnp.asarray(rows)
            )
            # COMMIT: publish buffer, ids and count together. A query that
            # snapshotted before this line sees none of this batch; one
            # after sees all of it.
            self._buf = new_buf
            for vid in dedup:
                if vid not in self._row:
                    self._row[vid] = len(self._ids)
                    self._ids.append(vid)
            self._n = len(self._ids)
        metrics.count("ann_upserts", len(dedup))
        if updated:
            metrics.count("ann_updates", updated)
        return added, updated

    def snapshot(self):
        """Atomic ``(buffer, committed_count)`` view for a device query."""
        with self._lock:
            return self._buf, self._n

    def resolve(self, indices: Sequence[int]) -> list[str]:
        """Row indices -> vector ids. Safe without the query's snapshot:
        ids are append-only and the indices came from a masked top_k, so
        every index was committed when the query launched."""
        ids = self._ids  # list reference; rows < committed n never mutate
        return [ids[i] for i in indices]

    def query(self, q: np.ndarray, k: int) -> tuple[list[str], list[float]]:
        """Exact top-k over the committed rows for one or more query
        vectors. ``q`` is ``(dim,)`` or ``(Q, dim)``; returns the merged
        ids/scores for the FIRST query row when 1-D (the common case) —
        multi-row callers use :meth:`query_many`."""
        ids, scores = self.query_many(np.atleast_2d(np.asarray(q)), k)
        return ids[0], scores[0]

    def query_raw(self, q: np.ndarray, k: int):
        """Batched scoring core: ``(B, dim)`` raw query vectors -> device
        arrays ``(scores (B, k'), row_indices (B, k'))`` with ``k' =
        min(k, k_cap, committed_n)``. DISPATCHES without fetching — this
        is the MicroBatcher ``fn`` body (the batcher's fetch worker does
        the one blocking transfer per batch), so queries coalesced into
        one device call overlap the next batch's collection. Resolve the
        indices later via :meth:`resolve` (safe: append-only id table)."""
        q = normalize(q)
        if q.shape[1] != self.dim:
            raise ValueError(f"query dim {q.shape[1]} != index dim {self.dim}")
        buf, n = self.snapshot()
        metrics.count("ann_queries", q.shape[0])
        if buf is None or n == 0:
            return (
                np.zeros((q.shape[0], 0), np.float32),
                np.zeros((q.shape[0], 0), np.int32),
            )
        k_eff = min(max(1, int(k)), ann_k_cap(), n)
        # Static-shape k bucket (power of two, lazily sliced back): the
        # jit cache holds one program per (capacity, B, k-bucket) triple.
        k_bucket = min(_pow2_at_least(k_eff), self._cap_for_topk(buf.shape[0]))
        scores_d, idx_d = _topk_scores(buf, q, n, k_bucket, ann_tile())
        return scores_d[:, :k_eff], idx_d[:, :k_eff]

    def query_many(
        self, q: np.ndarray, k: int
    ) -> tuple[list[list[str]], list[list[float]]]:
        import jax

        q = np.atleast_2d(np.asarray(q, dtype=np.float32))
        n_queries = q.shape[0]
        # Pad B to a power-of-two bucket so direct (non-batcher) callers
        # hit the same compiled programs the batcher's buckets do.
        q_bucket = _pow2_at_least(n_queries)
        if q_bucket != n_queries and q.shape[1] == self.dim:
            q = np.concatenate(
                [q, np.zeros((q_bucket - n_queries, q.shape[1]), np.float32)]
            )
        scores_d, idx_d = self.query_raw(q, k)
        scores_np = np.asarray(jax.device_get(scores_d))
        idx_np = np.asarray(jax.device_get(idx_d))
        return self.resolve_rows(scores_np[:n_queries], idx_np[:n_queries])

    def resolve_rows(
        self, scores: np.ndarray, indices: np.ndarray
    ) -> tuple[list[list[str]], list[list[float]]]:
        """Fetched ``query_raw`` rows -> per-query ``(ids, scores)`` lists,
        dropping -inf padding (masked rows that leaked past a small n)."""
        out_ids: list[list[str]] = []
        out_scores: list[list[float]] = []
        for raw_sc, raw_idx in zip(np.atleast_2d(scores), np.atleast_2d(indices)):
            keep = raw_sc > -np.inf
            out_ids.append(self.resolve([int(i) for i in raw_idx[keep]]))
            out_scores.append([float(s) for s in raw_sc[keep]])
        return out_ids, out_scores

    @staticmethod
    def _cap_for_topk(capacity: int) -> int:
        """top_k's k cannot exceed the scored width (the tile width when
        mapping, the capacity otherwise)."""
        return max(1, min(capacity, ann_tile()))

    def gauges(self) -> dict:
        with self._lock:
            return {
                "vectors": self._n,
                "capacity": self._capacity,
                "dim": self.dim,
            }


def _topk_scores(buf, q, n: int, k: int, tile: int):
    """Dispatch the jitted scoring program: one fused matmul + top_k when
    the buffer fits a tile, else tile-by-tile under ``lax.map`` with a
    final merge. Returns device arrays ``(scores (Q,k), indices (Q,k))``
    — the caller fetches."""
    import jax
    import jax.numpy as jnp

    capacity = buf.shape[0]
    if capacity <= tile or capacity % tile:
        # Fits one tile — or a hand-set odd tile doesn't divide the
        # power-of-two capacity: fall back to the single fused program
        # (correct, bigger scratch) rather than a ragged map.
        return _topk_single_jit(buf, q, jnp.asarray(n, jnp.int32), k)
    return _topk_tiled(buf, q, jnp.asarray(n, jnp.int32), k, tile)


def _get_single_jit():
    import jax
    import jax.numpy as jnp
    from functools import partial

    @partial(jax.jit, static_argnums=(3,))
    def run(buf, q, n, k):
        scores = q @ buf.T  # (Q, capacity) — one MXU call
        mask = jnp.arange(buf.shape[0]) < n
        scores = jnp.where(mask[None, :], scores, -jnp.inf)
        return jax.lax.top_k(scores, k)

    return run


def _get_tiled_jit():
    import jax
    import jax.numpy as jnp
    from functools import partial

    @partial(jax.jit, static_argnums=(3, 4))
    def run(buf, q, n, k, tile):
        tiles = buf.shape[0] // tile
        tiled = buf.reshape(tiles, tile, buf.shape[1])

        def score_tile(args):
            t_idx, t_buf = args
            scores = q @ t_buf.T  # (Q, tile)
            base = t_idx * tile
            mask = (base + jnp.arange(tile)) < n
            scores = jnp.where(mask[None, :], scores, -jnp.inf)
            s, i = jax.lax.top_k(scores, k)
            return s, i + base

        # lax.map: one tile of scratch live at a time — the VMEM story.
        s_all, i_all = jax.lax.map(
            score_tile, (jnp.arange(tiles), tiled)
        )  # (tiles, Q, k) each
        qn = q.shape[0]
        s_flat = jnp.transpose(s_all, (1, 0, 2)).reshape(qn, tiles * k)
        i_flat = jnp.transpose(i_all, (1, 0, 2)).reshape(qn, tiles * k)
        s_top, pos = jax.lax.top_k(s_flat, k)
        i_top = jnp.take_along_axis(i_flat, pos, axis=1)
        return s_top, i_top

    return run


_SINGLE_JIT = None
_TILED_JIT = None
_WRITE_JIT = None
_JIT_LOCK = threading.Lock()


def _scatter_write(buf, idx, rows):
    """One module-level jitted scatter — jax's jit cache keys on the
    (capacity, write-bucket) shapes, so each pair compiles exactly once
    process-wide."""
    global _WRITE_JIT
    if _WRITE_JIT is None:
        with _JIT_LOCK:
            if _WRITE_JIT is None:
                import jax

                _WRITE_JIT = jax.jit(lambda b, i, r: b.at[i].set(r))
    return _WRITE_JIT(buf, idx, rows)


def _topk_single_jit(buf, q, n, k):
    global _SINGLE_JIT
    if _SINGLE_JIT is None:
        with _JIT_LOCK:
            if _SINGLE_JIT is None:
                _SINGLE_JIT = _get_single_jit()
    return _SINGLE_JIT(buf, q, n, k)


def _topk_tiled(buf, q, n, k, tile):
    global _TILED_JIT
    if _TILED_JIT is None:
        with _JIT_LOCK:
            if _TILED_JIT is None:
                _TILED_JIT = _get_tiled_jit()
    return _TILED_JIT(buf, q, n, k, tile)


class AnnIndex:
    """Per-tenant, per-shard index map for one host. Shards materialize
    lazily on first upsert; gauges register per (tenant, shard) so
    ``/metrics`` shows which tenants hold rows where."""

    def __init__(self, dim: int):
        self.dim = int(dim)
        self._lock = threading.Lock()
        self._shards: dict[tuple[str, str], AnnShard] = {}

    def shard(self, tenant: str, shard: str, create: bool = True) -> AnnShard | None:
        key = (tenant or "default", str(shard))
        with self._lock:
            got = self._shards.get(key)
            if got is None and create:
                got = AnnShard(self.dim, name=f"{key[0]}/{key[1]}")
                self._shards[key] = got
                import weakref

                ref = weakref.ref(got)
                metrics.register_gauges(
                    f"ann:{key[0]}:{key[1]}",
                    lambda r=ref: (s.gauges() if (s := r()) is not None else {}),
                )
            return got

    def shards_for(self, tenant: str) -> dict[str, AnnShard]:
        tenant = tenant or "default"
        with self._lock:
            return {
                sh: shard
                for (t, sh), shard in self._shards.items()
                if t == tenant
            }

    def upsert(
        self, tenant: str, ids: Sequence[str], vecs: np.ndarray,
        shard: str | None = None,
    ) -> tuple[int, int]:
        """Upsert a batch. With an explicit ``shard`` label (the
        fleet-routed path) everything lands there; without one (direct
        single-host use) rows partition by :func:`shard_of` so a later
        fleet sees the same placement function."""
        vecs = np.atleast_2d(np.asarray(vecs))
        if shard is not None:
            return self.shard(tenant, shard).upsert(ids, vecs)
        n_shards = ann_shards()
        added = updated = 0
        groups: dict[int, list[int]] = {}
        for i, vid in enumerate(ids):
            groups.setdefault(shard_of(str(vid), n_shards), []).append(i)
        for sh, rows in sorted(groups.items()):
            a, u = self.shard(tenant, str(sh)).upsert(
                [str(ids[i]) for i in rows], vecs[rows]
            )
            added += a
            updated += u
        return added, updated

    def query(
        self, tenant: str, q: np.ndarray, k: int,
        shards: Sequence[str] | None = None,
    ) -> tuple[list[str], list[float], int]:
        """Top-k over the named shards (fleet hop) or every local shard of
        the tenant (direct use). Returns ``(ids, scores, shards_read)``."""
        if shards is None:
            local = self.shards_for(tenant)
        else:
            local = {
                sh: s
                for sh in shards
                if (s := self.shard(tenant, sh, create=False)) is not None
            }
        parts = [s.query(q, k) for s in local.values()]
        ids, scores = merge_topk(parts, k)
        return ids, scores, len(local)

    def stats(self) -> dict:
        with self._lock:
            return {
                f"{t}/{sh}": shard.gauges()
                for (t, sh), shard in sorted(self._shards.items())
            }


def exact_oracle(
    ids: Sequence[str], vecs: np.ndarray, q: np.ndarray, k: int
) -> tuple[list[str], list[float]]:
    """Numpy reference: full cosine scoring + the same deterministic
    tie-break as :func:`merge_topk`. The recall@k arbiter for tests."""
    vecs = normalize(vecs)
    q = normalize(q)[0]
    # Row by row, not ``vecs @ q``: BLAS rounds a row's product by where the
    # row sits in the matrix, so equal vectors would not tie exactly.
    scores = (vecs * q).sum(axis=-1)
    order = sorted(range(len(ids)), key=lambda i: (-float(scores[i]), str(ids[i])))
    top = order[: min(k, len(order))]
    return [str(ids[i]) for i in top], [float(scores[i]) for i in top]
