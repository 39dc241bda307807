"""Per-layer metric ``data_wait_share.train``: goodput's data_wait seconds over the window (program span)."""

from benchmark.harness import readers


def read(ctx):
    return readers.share_of_window(ctx, "data_wait_s", "train")
