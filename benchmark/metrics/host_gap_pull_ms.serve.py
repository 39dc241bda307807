"""Per-layer metric ``host_gap_pull_ms.serve``: the host gap per decode step that falls inside `decode.pull` spans: the pull after the device went idle (trace + program spans)."""

from benchmark.harness import phase_readers


def read(ctx):
    return phase_readers.host_gap_phase_ms(ctx, "pull")
