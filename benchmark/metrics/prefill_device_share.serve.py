"""Per-layer metric ``prefill_device_share.serve``: share of the traced window in which a prefill executable (`XLA Modules` name holding `_prefill_b`) ran on the device."""

from benchmark.harness import phase_readers


def read(ctx):
    return phase_readers.module_device_share(ctx, "_prefill_b")
