"""Per-layer metric ``host_gap_per_decode_step_ms.serve``: device idle while a request was in flight, per decode step, from the trace."""

from benchmark.harness import readers


def read(ctx):
    return readers.host_gap_per_decode_step_ms(ctx)
