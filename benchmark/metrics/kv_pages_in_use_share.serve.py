"""Per-layer metric ``kv_pages_in_use_share.serve``: pages in use (live slots and retained prefixes) over the pool, at the window close."""

from benchmark.harness import readers


def read(ctx):
    return readers.kv_pages_in_use_share(ctx)
