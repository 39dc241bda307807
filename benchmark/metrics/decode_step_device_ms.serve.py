"""Per-layer metric ``decode_step_device_ms.serve``: device time of one decode executable launch, median over the traced launches."""

from benchmark.harness import readers


def read(ctx):
    return readers.decode_step_device_ms(ctx)
