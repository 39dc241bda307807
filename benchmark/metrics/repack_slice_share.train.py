"""Per-layer metric ``repack_slice_share.train``: seconds of the traced slice inside the loop's `train.repack` spans, over the slice's seconds (program spans)."""

from benchmark.harness import setup_readers


def read(ctx):
    return setup_readers.repack_slice_share(ctx)
