"""Per-layer metric ``serve_latency_p99_ms.serve``: 99th percentile of due-to-done latency; recorded, never judged."""

from benchmark.harness import readers


def read(ctx):
    return readers.latency_percentile(ctx, "lat_ms", 99)
