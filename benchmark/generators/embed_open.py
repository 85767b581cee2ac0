"""Open-loop single embeds: unary requests arriving as a Poisson process at
a rate fixed in the mix, a share of them ``clip_image_embed`` of one pool
photo and the rest ``clip_text_embed`` queries of a few words, each on a
stream of its own (no bulk lane). The schedule (arrival times, kinds,
photos, words) is drawn from the seed before the window opens; a request's
latency runs from the moment it was DUE, so a generator or a server that
falls behind shows in the tail, and how late each was sent is reported
beside it (``lateness_ms``).

Traffic parameters: ``rate_rps``, ``image_share``, ``image_task``,
``text_task``, ``mime``, ``photo_pool``, ``jpeg_quality``, ``noise``,
``query_words`` (``min``/``max``), ``text_vocab`` (word ids a query draws
from), ``workers`` (threads that hold the open requests).
"""

from __future__ import annotations

import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.generators.common import chunked, open_stub
from benchmark.photos import photo_jpeg, pool_sizes, tagged


def schedule(seed: int, salt: int, seconds: float, traffic: dict, photos: int) -> list[dict]:
    """Every arrival of a window: ``{"due": s, "kind": "image"|"text",
    "photo": i | "text": "w.. w.."}``, a function of the seed alone."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 4000 + salt])
    rate = float(traffic["rate_rps"])
    gaps = rng.exponential(1.0 / rate, int(rate * seconds * 1.5) + 64)
    due = np.cumsum(gaps)
    due = due[due < seconds]
    lo, hi = int(traffic["query_words"]["min"]), int(traffic["query_words"]["max"])
    out = []
    for t in due:
        if rng.random() < float(traffic["image_share"]):
            out.append({"due": float(t), "kind": "image", "photo": int(rng.integers(photos))})
        else:
            words = rng.integers(1, int(traffic["text_vocab"]), int(rng.integers(lo, hi + 1)))
            out.append({"due": float(t), "kind": "text", "text": " ".join(f"w{w}" for w in words)})
    return out


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

        plan = schedule(self.seed, 500 if warm else 0, seconds, self.t, len(self.pool))
        channel, stub = open_stub(self.port)
        lock = threading.Lock()
        done: list[dict] = []
        last_vector: dict[int, list] = {}
        errors: list[str] = []
        base_tag = self.sent_total
        self.sent_total += 10_000_000
        t_open = time.perf_counter()
        t_close = t_open + seconds

        def one(n: int, item: dict) -> None:
            due = t_open + item["due"]
            sent = time.perf_counter()
            if item["kind"] == "image":
                payload = tagged(self.pool[item["photo"]], base_tag + n)
                reqs = chunked(pb, f"i{n}", self.t["image_task"], payload, self.t["mime"], {})
            else:
                reqs = chunked(pb, f"t{n}", self.t["text_task"], item["text"].encode(), "text/plain", {})
            vec, error = None, None
            try:
                for resp in stub.Infer(reqs, timeout=seconds + 120):
                    if resp.error.code or resp.error.message:
                        error = f"[{resp.error.code}] {resp.error.message}"[:200]
                    elif resp.is_final:
                        vec = json.loads(resp.result).get("vector")
            except Exception as e:  # noqa: BLE001 - a broken stream fails its request
                error = f"{type(e).__name__}: {e}"[:300]
            now = time.perf_counter()
            if error is None and not vec:
                error = "no vector in the reply"
            with lock:
                done.append({"kind": item["kind"], "due": due, "sent": sent, "finished": now, "ok": error is None})
                if error:
                    errors.append(error)
                elif item["kind"] == "image" and now <= t_close:
                    last_vector[item["photo"]] = vec

        with ThreadPoolExecutor(int(self.t["workers"])) as ex:
            for n, item in enumerate(plan):
                wait = t_open + item["due"] - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                ex.submit(one, n, item)
        channel.close()
        ok = [d for d in done if d["ok"]]
        return {
            "window_s": seconds,
            "offered": len(plan),
            "attempted": len(done),
            "failed": len(done) - len(ok),
            "completed_in_window": sum(1 for d in ok if d["kind"] == "image" and d["finished"] <= t_close),
            "texts_in_window": sum(1 for d in ok if d["kind"] == "text" and d["finished"] <= t_close),
            "latency_ms": [(d["finished"] - d["due"]) * 1e3 for d in ok],
            "lateness_ms": [(d["sent"] - d["due"]) * 1e3 for d in done],
            "drain_s": max([d["finished"] for d in done], default=t_close) - t_close,
            "sample": {str(k): v for k, v in last_vector.items()},
            "errors": errors[:10],
        }
