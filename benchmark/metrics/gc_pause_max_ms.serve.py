"""The longest garbage collection of the window: every thread of the
process, batcher and load generator alike, stood still for it."""

from benchmark.harness import readers


def read(ctx):
    return readers.largest(ctx, "gc_pause_ms", "serve")
