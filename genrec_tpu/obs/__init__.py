"""Unified observability layer: spans, goodput, flight recorder, export.

The substrate the fleet-scale roadmap items (disaggregated multi-host
serving, streaming-training -> hot-serving) sit on:

- `spans`           — request/step-scoped tracer, Chrome-trace export,
                      jax.profiler bridging
- `goodput`         — training wall-time classified into buckets,
                      fleet-wide aggregation, and the compile tap: every
                      JAX trace, lowering, XLA compile and cache load,
                      registered when this package is imported
- `flight_recorder` — bounded structured-event ring dumped atomically on
                      SIGTERM / crash / chaos kill points
- `export`          — Prometheus-style text exposition of any snapshot
- `memory`          — device-memory ledger: operands + compiled
                      executables summed into an HBM budget model
- `slo`             — declared per-head SLO targets, sustained-breach
                      detection, load-shed/recover hysteresis

Layering: `obs` imports nothing from core/trainers/serving (jax only,
lazily), so every layer above may use it freely.
"""

from genrec_tpu.obs.export import prometheus_text, write_prometheus
from genrec_tpu.obs.flight_recorder import (
    FlightRecorder,
    get_flight_recorder,
    json_safe,
)
from genrec_tpu.obs.goodput import (
    BUCKETS,
    CompileEvents,
    GoodputMeter,
    fleet_goodput,
)
from genrec_tpu.obs.memory import (
    MemoryLedger,
    device_memory_stats,
    executable_memory_stats,
    tree_nbytes,
)
from genrec_tpu.obs.slo import SLOMonitor, SLOTarget
from genrec_tpu.obs.spans import NULL_TRACER, Span, SpanTracer, TraceContext

# From here on every compile of the process is in the tap's log, the
# adapters' and trainers' weight initialisation included; a listener costs
# nothing until JAX compiles.
CompileEvents.ensure()

__all__ = [
    "BUCKETS",
    "CompileEvents",
    "FlightRecorder",
    "GoodputMeter",
    "MemoryLedger",
    "NULL_TRACER",
    "SLOMonitor",
    "SLOTarget",
    "Span",
    "SpanTracer",
    "TraceContext",
    "device_memory_stats",
    "executable_memory_stats",
    "fleet_goodput",
    "get_flight_recorder",
    "json_safe",
    "prometheus_text",
    "tree_nbytes",
    "write_prometheus",
]
