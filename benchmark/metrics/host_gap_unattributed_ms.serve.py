"""Per-layer metric ``host_gap_unattributed_ms.serve``: the host gap per decode step that no phase of the batcher's lane covers (trace + program spans)."""

from benchmark.harness import phase_readers


def read(ctx):
    return phase_readers.host_gap_unattributed_ms(ctx)
