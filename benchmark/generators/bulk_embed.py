"""Closed-loop bulk streams: ``bulk_streams`` Infer streams on the server's
bulk lane, each keeping ``outstanding_per_stream`` tagged items in flight
and sending the next when a tagged response returns.

Traffic parameters: ``task``, ``mime``, ``bulk_streams``,
``outstanding_per_stream``, ``photo_pool`` (the fixed multiset of long
sides), ``jpeg_quality``, ``noise``. Every item is a pool photo under a tag
of its own, so no two payloads are the same bytes.
"""

from __future__ import annotations

import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from benchmark.generators.common import chunked, open_stub
from benchmark.photos import photo_jpeg, pool_sizes, seeded_order, tagged


class Generator:
    def __init__(self, traffic: dict, port: int, context: dict):
        self.t = traffic
        self.port = port
        self.sizes = pool_sizes(traffic["photo_pool"])
        self.pool: list[bytes] = []
        self.seed = 0
        self.sent_total = 0  # tags never repeat across the runs of one process

    def prepare(self, seed: int) -> dict:
        self.seed = seed
        with ThreadPoolExecutor(4) as ex:
            self.pool = list(ex.map(
                lambda i: photo_jpeg(seed, i, self.sizes[i], self.t["jpeg_quality"], self.t["noise"]),
                range(len(self.sizes)),
            ))
        return {"photos": len(self.pool), "pool_bytes": sum(map(len, self.pool))}

    def run(self, seconds: float, warm: bool) -> dict:
        from lumen_tpu.serving.proto import ml_service_pb2 as pb

        streams, window = int(self.t["bulk_streams"]), int(self.t["outstanding_per_stream"])
        task, mime = self.t["task"], self.t["mime"]
        channel, stub = open_stub(self.port)
        lock = threading.Lock()
        stop = threading.Event()
        done: list[tuple[float, float, int, bool]] = []  # sent, finished, photo, ok
        last_vector: dict[int, list] = {}
        errors: list[str] = []
        base_tag = self.sent_total
        self.sent_total += 10_000_000
        t_open = time.perf_counter()
        t_close = t_open + seconds

        def one_stream(s: int) -> None:
            order = seeded_order(self.seed, 1000 + s + (500 if warm else 0), len(self.pool))
            slots = threading.Semaphore(window)
            sent: dict[str, tuple[float, int]] = {}

            def requests():
                n = 0
                while not stop.is_set() and time.perf_counter() < t_close:
                    if not slots.acquire(timeout=0.05):
                        continue
                    if stop.is_set() or time.perf_counter() >= t_close:
                        return
                    photo = order[n % len(order)]
                    cid = f"{s}-{n}"
                    payload = tagged(self.pool[photo], base_tag + s * 1_000_000 + n)
                    sent[cid] = (time.perf_counter(), photo)
                    yield from chunked(pb, cid, task, payload, mime, {"bulk": "1"})
                    n += 1

            try:
                for resp in stub.Infer(requests(), timeout=seconds + 120):
                    failed = bool(resp.error.code or resp.error.message)
                    if not (resp.is_final or failed):
                        continue
                    now = time.perf_counter()
                    t_sent, photo = sent.pop(resp.correlation_id)
                    vec = None
                    if failed:
                        with lock:
                            errors.append(f"[{resp.error.code}] {resp.error.message}"[:200])
                    else:
                        vec = json.loads(resp.result).get("vector")
                        failed = not vec
                    with lock:
                        done.append((t_sent, now, photo, not failed))
                        if vec and now <= t_close:
                            last_vector[photo] = vec
                    slots.release()
            except Exception as e:  # noqa: BLE001 - a broken stream fails its items
                with lock:
                    errors.append(f"stream {s}: {type(e).__name__}: {e}"[:300])
                    for t_sent, photo in sent.values():
                        done.append((t_sent, time.perf_counter(), photo, False))

        threads = [threading.Thread(target=one_stream, args=(s,), daemon=True) for s in range(streams)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(seconds + 150)
        hung = [th.name for th in threads if th.is_alive()]
        stop.set()
        channel.close()
        in_window = [d for d in done if d[1] <= t_close and d[3]]
        return {
            "window_s": seconds,
            "attempted": len(done) + len(hung) * window,
            "failed": sum(1 for d in done if not d[3]) + len(hung) * window,
            "completed_in_window": len(in_window),
            "latency_ms": [(d[1] - d[0]) * 1e3 for d in done if d[3]],
            "drain_s": max([d[1] for d in done], default=t_close) - t_close,
            "sample": {str(k): v for k, v in last_vector.items()},
            "errors": errors[:10],
        }
