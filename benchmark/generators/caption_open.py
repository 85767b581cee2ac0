"""Open-loop captioning: ``vlm_generate_stream`` requests arriving as a
Poisson process at a rate fixed in the mix, each on a stream of its own with
a seeded image and the run's shared instruction (``caption_stream``'s
request and checks around ``embed_open``'s seeded schedule); greedy. The
schedule (arrival times, images, ``max_new_tokens``) is drawn from the seed
before the window opens; a request's first-token latency runs from the
moment it was DUE, so a generator or a server that falls behind shows in it,
and how late each was sent is reported beside it (``lateness_ms``).

At three requests a second a 40-s window holds some 110 arrivals, so the
count (standard deviation 10-11) and the lengths drawn one by one move the
offered tokens of a window by a tenth from seed to seed: a rate or a median
over one such window is a draw, not a constant of the generator.

Traffic parameters: ``caption_stream``'s (``task``, ``instruction_tokens``,
``new_tokens``, ``image_pool``, ``image_long_side``, ``jpeg_quality``,
``noise``) and ``rate_rps``, ``workers`` (threads that hold the open
streams). A warm-up run asks every request for the longest caption, so that
every page-table width compiles.
"""

from __future__ import annotations

import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.generators import caption_stream
from benchmark.generators.common import chunked, open_stub
from benchmark.photos import tagged


def schedule(seed: int, salt: int, seconds: float, traffic: dict, images: int) -> list[dict]:
    """Every arrival of a window: ``{"due": s, "image": i, "max_new": n}``, a
    function of the seed alone."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 5000 + salt])
    rate = float(traffic["rate_rps"])
    gaps = rng.exponential(1.0 / rate, int(rate * seconds * 1.5) + 64)
    due = np.cumsum(gaps)
    due = due[due < seconds]
    lo, hi = int(traffic["new_tokens"]["min"]), int(traffic["new_tokens"]["max"])
    return [{"due": float(t), "image": int(rng.integers(images)), "max_new": int(rng.integers(lo, hi + 1))}
            for t in due]


class Generator(caption_stream.Generator):
    """``prepare`` is the closed loop's: the image pool and the instruction."""

    def run(self, seconds: float, warm: bool) -> dict:
        from lumen_tpu.serving.proto import ml_service_pb2 as pb

        plan = schedule(self.seed, 500 if warm else 0, seconds, self.t, len(self.pool))
        if warm:
            plan = [{**item, "max_new": self.lengths[-1]} for item in plan]
        channel, stub = open_stub(self.port)
        lock = threading.Lock()
        records: list[dict] = []
        base_tag = self.sent_total
        self.sent_total += 10_000_000
        t_open = time.perf_counter()
        t_close = t_open + seconds
        meta_messages = json.dumps([{"role": "user", "content": self.content}])

        def one(n: int, item: dict) -> None:
            payload = tagged(self.pool[item["image"]], base_tag + n)
            meta = {"messages": meta_messages, "max_new_tokens": str(item["max_new"])}
            rec = {"n": n, "image": item["image"], "max_new": item["max_new"], "due": t_open + item["due"],
                   "sent": time.perf_counter(), "delta_t": [], "delta_words": [], "ok": False, "error": None}
            deltas: list[str] = []
            final = None
            try:
                for resp in stub.Infer(chunked(pb, "c", self.t["task"], payload, "image/jpeg", meta),
                                       timeout=seconds + 300):
                    if resp.error.code or resp.error.message:
                        rec["error"] = f"[{resp.error.code}] {resp.error.message}"[:200]
                        break
                    if resp.is_final:
                        final = json.loads(resp.result)
                    else:
                        text = resp.result.decode("utf-8")
                        rec["delta_t"].append(time.perf_counter())
                        rec["delta_words"].append(len(text.split()))
                        deltas.append(text)
            except Exception as e:  # noqa: BLE001 - a broken stream fails its request
                rec["error"] = f"{type(e).__name__}: {e}"[:300]
            rec["finished"] = time.perf_counter()
            if rec["error"] is None and final is not None:
                streamed = "".join(deltas)
                ids = caption_stream.words_to_ids(streamed, self.special)
                equal = streamed.strip() == final.get("text", "").strip()
                rec.update(tokens=ids, finish_reason=final.get("finish_reason"),
                           server_ttft_ms=(final.get("metadata") or {}).get("ttft_ms"))
                rec["ok"] = bool(ids) and equal and len(ids) == final.get("generated_tokens")
                if not rec["ok"]:
                    rec["error"] = (f"deltas/final mismatch: {len(ids or [])} words streamed, "
                                    f"{final.get('generated_tokens')} generated, equal={equal}")
            elif rec["error"] is None:
                rec["error"] = "stream ended without a final message"
            with lock:
                records.append(rec)

        with ThreadPoolExecutor(int(self.t["workers"])) as ex:
            for n, item in enumerate(plan):
                wait = t_open + item["due"] - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                ex.submit(one, n, item)
        channel.close()
        ok = [r for r in records if r["ok"]]
        return {
            "window_s": seconds,
            "offered": len(plan),
            "attempted": len(records),
            "failed": len(records) - len(ok),
            "tokens_in_window": sum(
                w for r in records for t, w in zip(r["delta_t"], r["delta_words"]) if t <= t_close
            ),
            "tokens_total": sum(sum(r["delta_words"]) for r in records),
            "ttft_ms": [(r["delta_t"][0] - r["due"]) * 1e3 for r in ok if r["delta_t"]],
            "itl_ms": [(b - a) * 1e3 for r in ok for a, b in zip(r["delta_t"], r["delta_t"][1:])],
            "server_ttft_ms": [r["server_ttft_ms"] for r in ok if r["server_ttft_ms"] is not None],
            "lateness_ms": [(r["sent"] - r["due"]) * 1e3 for r in records],
            "drain_s": max([r["finished"] for r in records], default=t_close) - t_close,
            "finished": [
                {"n": r["n"], "image": r["image"], "max_new": r["max_new"], "tokens": r["tokens"],
                 "finish_reason": r["finish_reason"]}
                for r in ok if r["finished"] <= t_close
            ],
            "errors": [r["error"] for r in records if r["error"]][:10],
        }
