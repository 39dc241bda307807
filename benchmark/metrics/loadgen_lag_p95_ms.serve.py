"""Per-layer metric ``loadgen_lag_p95_ms.serve``: how late the generator sent: sent - due, 95th percentile."""

from benchmark.harness import readers


def read(ctx):
    return readers.latency_percentile(ctx, "lag_ms", 95)
