"""Per-layer metric ``setup_trace_lower_s.serve``: seconds of set-up inside the union of the compile lane's `compile.trace` and `compile.lower` spans: the part of every compile that no persistent cache saves (program spans)."""

from benchmark.harness import setup_readers


def read(ctx):
    return setup_readers.trace_lower_s(ctx, "serve")
