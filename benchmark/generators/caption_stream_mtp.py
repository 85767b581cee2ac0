"""Closed-loop captioning from a captioner that drafts with a multi-token
prediction layer: ``caption_stream``'s traffic to the letter (its parameters,
its requests, its window), and each finished request's drafts kept beside
its tokens for the reference to judge.

The stream's final metadata carries ``mtp_drafts``: a request's drafts by
the index of the emitted token each stood for, space-separated (-1 where
none did). The
harness hands the reference a finished request's ``tokens`` alone, so a
finished record's ``tokens`` is the served ids, then one entry a served id,
its draft or -1 (``references/vlm_glm5.split_drafts`` reads them apart); the
window's rates and gaps are counted from the streamed deltas, as
``caption_stream`` counts them.
"""

from __future__ import annotations

import json
import threading
import time

from benchmark.generators import caption_stream
from benchmark.generators.caption_stream import words_to_ids
from benchmark.generators.common import chunked, open_stub
from benchmark.photos import seeded_order, tagged


def aligned(drafts: list, n: int) -> list[int]:
    """One entry a served token: its draft, or -1."""
    return [int(d) for d in (list(drafts) + [-1] * n)[:n]]


class Generator(caption_stream.Generator):
    def run(self, seconds: float, warm: bool) -> dict:
        from lumen_tpu.serving.proto import ml_service_pb2 as pb

        task = self.t["task"]
        channel, stub = open_stub(self.port)
        lock = threading.Lock()
        salt = 500 if warm else 0
        image_order = seeded_order(self.seed, 2000 + salt, len(self.pool))
        length_order = seeded_order(self.seed, 3000 + salt, len(self.lengths))
        if warm:  # the longest first, so that every page-table width compiles
            length_order.sort(key=lambda i: -self.lengths[i])
        counter = [0]
        base_tag = self.sent_total
        self.sent_total += 10_000_000
        records: list[dict] = []
        t_open = time.perf_counter()
        t_close = t_open + seconds
        meta_messages = json.dumps([{"role": "user", "content": self.content}])

        def worker() -> None:
            while True:
                with lock:
                    n = counter[0]
                    counter[0] += 1
                if time.perf_counter() >= t_close:
                    return
                image = image_order[n % len(image_order)]
                max_new = self.lengths[length_order[n % len(length_order)]]
                payload = tagged(self.pool[image], base_tag + n)
                meta = {"messages": meta_messages, "max_new_tokens": str(max_new)}
                rec = {"n": n, "image": image, "max_new": max_new, "sent": time.perf_counter(),
                       "delta_t": [], "delta_words": [], "ok": False, "error": None}
                deltas: list[str] = []
                final = None
                try:
                    for resp in stub.Infer(chunked(pb, "c", task, payload, "image/jpeg", meta),
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
                    ids = words_to_ids(streamed, self.special)
                    rec["text_equal"] = streamed.strip() == final.get("text", "").strip()
                    rec["tokens"] = ids
                    rec["generated_tokens"] = final.get("generated_tokens")
                    rec["finish_reason"] = final.get("finish_reason")
                    rec["server_ttft_ms"] = (final.get("metadata") or {}).get("ttft_ms")
                    rec["drafts"] = [int(d) for d in str((final.get("metadata") or {}).get("mtp_drafts") or "").split()]
                    rec["ok"] = bool(ids) and rec["text_equal"] and len(ids) == final.get("generated_tokens")
                    if not rec["ok"] and rec["error"] is None:
                        rec["error"] = (f"deltas/final mismatch: {len(ids or [])} words streamed, "
                                        f"{final.get('generated_tokens')} generated, equal={rec['text_equal']}")
                elif rec["error"] is None:
                    rec["error"] = "stream ended without a final message"
                with lock:
                    records.append(rec)

        threads = [threading.Thread(target=worker, daemon=True) for _ in range(int(self.t["in_flight"]))]
        for th in threads:
            th.start()
        for th in threads:
            th.join(seconds + 330)
        hung = sum(th.is_alive() for th in threads)
        channel.close()
        tokens_in_window = sum(
            w for r in records for t, w in zip(r["delta_t"], r["delta_words"]) if t <= t_close
        )
        gaps = [
            (b - a) * 1e3 for r in records if r["ok"]
            for a, b in zip(r["delta_t"], r["delta_t"][1:])
        ]
        finished_in_window = [r for r in records if r["ok"] and r["finished"] <= t_close]
        return {
            "window_s": seconds,
            "attempted": len(records) + hung,
            "failed": sum(1 for r in records if not r["ok"]) + hung,
            "tokens_in_window": tokens_in_window,
            "tokens_total": sum(sum(r["delta_words"]) for r in records),
            "ttft_ms": [(r["delta_t"][0] - r["sent"]) * 1e3 for r in records if r["ok"] and r["delta_t"]],
            "itl_ms": gaps,
            "server_ttft_ms": [r["server_ttft_ms"] for r in records if r["ok"] and r["server_ttft_ms"] is not None],
            "drain_s": max([r["finished"] for r in records], default=t_close) - t_close,
            "finished": [
                {"n": r["n"], "image": r["image"], "max_new": r["max_new"],
                 "tokens": r["tokens"] + aligned(r["drafts"], len(r["tokens"])),
                 "finish_reason": r["finish_reason"]}
                for r in finished_in_window
            ],
            "errors": [r["error"] for r in records if r["error"]][:10],
        }
