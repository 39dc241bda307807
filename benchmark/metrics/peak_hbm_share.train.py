"""Per-layer metric ``peak_hbm_share.train``: peak bytes in use over the device bytes limit, fullest chip."""

from benchmark.harness import readers


def read(ctx):
    return readers.peak_hbm_share(ctx, "train")
