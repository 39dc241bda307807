"""Per-layer metric ``step_host_tail_ms.train``: median per step of `train.data_wait` + `train.dispatch` + `train.host_tail`: host time of a step not spent waiting for the device (program spans)."""

from benchmark.harness import phase_readers


def read(ctx):
    return phase_readers.step_host_tail_ms(ctx)
