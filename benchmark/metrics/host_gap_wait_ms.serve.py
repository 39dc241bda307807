"""Per-layer metric ``host_gap_wait_ms.serve``: the host gap per decode step that falls inside `batcher.idle_wait` spans: requests queued under the coalescing deadline while the device stands (trace + program spans)."""

from benchmark.harness import phase_readers


def read(ctx):
    return phase_readers.host_gap_phase_ms(ctx, "wait")
