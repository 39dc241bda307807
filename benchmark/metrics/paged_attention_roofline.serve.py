"""Per-layer metric ``paged_attention_roofline.serve``: least time for the K/V the live slots read over the kernel device time, from the trace."""

from benchmark.harness import readers


def read(ctx):
    return readers.paged_attention_roofline(ctx)
