"""Per-layer metric ``queue_wait_p95_ms.serve``: 95th percentile of the engine queue_wait spans."""

from benchmark.harness import readers


def read(ctx):
    return readers.span_percentile(ctx, "queue_wait", 95)
