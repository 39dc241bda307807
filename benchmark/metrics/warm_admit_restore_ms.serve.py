"""Per-layer metric ``warm_admit_restore_ms.serve``: median of the batcher lane's `admit.restore_state` spans inside the window: a warm admit's bind of its state snapshot."""

from benchmark.harness import readers


def read(ctx):
    return readers.span_percentile(ctx, "admit.restore_state", 50)
