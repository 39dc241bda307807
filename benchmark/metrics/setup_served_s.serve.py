"""Per-layer metric ``setup_served_s.serve``: seconds of set-up in which at least one of the engine's `request` spans was open, less what the compile lane counted: the warm-up at every shape and the deployment's past (program spans)."""

from benchmark.harness import setup_readers


def read(ctx):
    return setup_readers.served_s(ctx)
