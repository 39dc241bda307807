"""Per-layer metric ``serve_latency_p95_ms.serve``: 95th percentile of due-to-done latency over every request of the window; recorded, never judged."""

from benchmark.harness import readers


def read(ctx):
    return readers.latency_percentile(ctx, "lat_ms", 95)
