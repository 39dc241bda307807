"""Per-layer metric ``device_idle_share.serve``: 1 - union of device-op intervals over the traced window."""

from benchmark.harness import readers


def read(ctx):
    return readers.device_idle_share(ctx, "serve")
