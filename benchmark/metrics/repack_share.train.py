"""Per-layer metric ``repack_share.train``: seconds inside the per-epoch repack closure over the window (benchmark's own span around the call into the packer)."""

from benchmark.harness import readers


def read(ctx):
    return readers.share_of_window(ctx, "repack_s", "train")
