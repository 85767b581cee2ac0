"""What both generators share: the channel, chunking, percentiles."""

from __future__ import annotations

import statistics

CHUNK = 1 << 20  # the wire contract's chunk size (lumen_tpu/client.py)


def open_stub(port: int):
    import grpc

    from lumen_tpu.serving.proto import ml_service_pb2_grpc as pbg

    channel = grpc.insecure_channel(
        f"127.0.0.1:{port}",
        options=[("grpc.max_receive_message_length", 64 << 20),
                 ("grpc.max_send_message_length", 64 << 20)],
    )
    return channel, pbg.InferenceStub(channel)


def chunked(pb, cid: str, task: str, payload: bytes, mime: str, meta: dict):
    """The InferRequests of one item (one message when it fits a chunk)."""
    if len(payload) <= CHUNK:
        yield pb.InferRequest(correlation_id=cid, task=task, payload=payload, payload_mime=mime, meta=meta)
        return
    total = (len(payload) + CHUNK - 1) // CHUNK
    for j in range(total):
        yield pb.InferRequest(
            correlation_id=cid, task=task, payload=payload[j * CHUNK:(j + 1) * CHUNK],
            payload_mime=mime, meta=meta if j == 0 else {}, seq=j, total=total, offset=j * CHUNK,
        )


def percentile(values: list[float], q: float) -> float | None:
    """Nearest-rank percentile of all values; None when there are none."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(0, min(len(ordered) - 1, int(-(-q * len(ordered) // 100)) - 1))
    return float(ordered[rank])


def median(values: list[float]) -> float | None:
    return float(statistics.median(values)) if values else None
