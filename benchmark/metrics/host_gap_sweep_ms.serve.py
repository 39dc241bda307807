"""Per-layer metric ``host_gap_sweep_ms.serve``: the host gap per decode step that falls inside `decode.sweep` spans of the batcher's lane (trace + program spans)."""

from benchmark.harness import phase_readers


def read(ctx):
    return phase_readers.host_gap_phase_ms(ctx, "sweep")
