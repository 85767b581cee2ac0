"""Tokens the decode steps emitted over the decode steps run: the client's
streamed tokens less each request's first (prefill makes that one), over
blocks run times the block length."""

from benchmark.readers.common import decode_rows


def read(ctx, spec):
    return decode_rows(ctx)
