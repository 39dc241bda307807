"""Per-layer metric ``peak_hbm_share.serve``: peak bytes in use over the device bytes limit."""

from benchmark.harness import readers


def read(ctx):
    return readers.peak_hbm_share(ctx, "serve")
