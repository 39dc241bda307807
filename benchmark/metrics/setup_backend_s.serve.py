"""Per-layer metric ``setup_backend_s.serve``: seconds of set-up inside `compile.backend` spans and not inside `compile.trace` / `compile.lower`: XLA compiles and persistent-cache loads (program spans)."""

from benchmark.harness import setup_readers


def read(ctx):
    return setup_readers.backend_s(ctx, "serve")
