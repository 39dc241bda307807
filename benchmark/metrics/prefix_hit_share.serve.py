"""Per-layer metric ``prefix_hit_share.serve``: prefix-cache hits over lookups inside the window (engine counters)."""

from benchmark.harness import readers


def read(ctx):
    return readers.prefix_hit_share(ctx)
