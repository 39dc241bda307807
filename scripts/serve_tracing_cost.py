#!/usr/bin/env python
"""What span tracing costs a serving cell when it is ON, profiler off.

    python3 scripts/serve_tracing_cost.py [<workload> [<windows each side>]]

One engine is built, warmed and filled as a run of the cell does
(`benchmark.kinds.serve.build`); then 2 x N windows of the cell's own length
and rate follow on it, each another draw of arrivals, alternating between no
tracer and a `SpanTracer` attached live (`engine.set_tracer`), in the order
off on on off so that neither side has the warmer half. One process, because
a cell's p50 spreads by 2-3% from process to process, which would hide a cost
of a per cent. Prints one JSON line per window and a last line with each
side's median p50 and completed/s and the cost as a share of the off side.
Runs only on the chip (exits non-zero where the cell's chips are not there).
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv) -> int:
    workload = argv[0] if argv else "tiger_serve_steady"
    n = int(argv[1]) if len(argv) > 1 else 6
    from benchmark.harness import device as devmod
    from benchmark.harness import stats
    from benchmark.harness.spec import Spec
    from benchmark.harness.traffic import deployment_trace
    from genrec_tpu.obs.spans import SpanTracer

    devmod.enable_compile_cache()
    spec = Spec()
    cell = spec.cell(workload)
    devmod.require_chips(cell.chips)
    cfg, adapter, serve = cell.config, cell.adapter, cell.kind
    seconds = float(spec.doc["run_seconds"])
    seed = 2600000300
    t0 = time.monotonic()
    engine, head, _, catalog, _ = serve.build(cell, seed, seconds)
    print(json.dumps({"setup_s": time.monotonic() - t0}), flush=True)
    entries = cfg["assumed"]["serve"]["prefix_cache_entries"]
    sides: dict = {"off": [], "on": []}
    for i in range(2 * n):
        side = "on" if i % 4 in (1, 2) else "off"
        tracer = SpanTracer(capacity=2_000_000) if side == "on" else None
        engine.set_tracer(tracer)
        _, arrivals = deployment_trace(
            cell.traffic, seconds, cfg["max_items"], len(catalog), seed,
            cache_entries=entries, window=i + 1)
        stats0 = engine.stats()
        records, t_open = serve.drive(engine, adapter.make_request, head.name,
                                      arrivals)
        stats1 = engine.stats()
        t_close = t_open + seconds
        done = [r for r in records if r.response is not None]
        lat = [(r.done - r.due) * 1e3 for r in done]
        line = {
            "window": i + 1, "tracer": side, "attempted": len(records),
            "failed": len(records) - len(done),
            "p50_ms": stats.percentile(lat, 50),
            "p95_ms": stats.percentile(lat, 95),
            "completed_per_s": sum(r.done <= t_close for r in done) / seconds,
            "decode_steps": stats1["decode_steps"] - stats0["decode_steps"],
            "steps_by_slots": {
                k: v - stats0["decode_steps_by_slots"].get(k, 0)
                for k, v in stats1["decode_steps_by_slots"].items()},
            "spans": tracer.stats()["spans_recorded"] if tracer else 0,
            "recompilations": stats1["recompilations"],
        }
        if tracer is not None:
            # Host time of each phase of the batcher's lane (whether or not
            # the device stood still meanwhile), mean ms per decode step.
            total: dict = {}
            for s in tracer.spans(f"batcher/{head.name}"):
                total[s.name] = total.get(s.name, 0.0) + (s.t1 - s.t0)
            line["phase_ms_per_step"] = {
                k: round(v * 1e3 / max(line["decode_steps"], 1), 3)
                for k, v in sorted(total.items())}
        sides[side].append(line)
        print(json.dumps(line), flush=True)
    engine.set_tracer(None)
    engine.stop()
    med = {s: {k: statistics.median(w[k] for w in ws)
               for k in ("p50_ms", "completed_per_s", "decode_steps")}
           for s, ws in sides.items()}
    print(json.dumps({
        "workload": workload, "windows_each_side": n, "median": med,
        "p50_cost_pct": 100.0 * (med["on"]["p50_ms"] / med["off"]["p50_ms"] - 1.0),
        "completed_cost_pct": 100.0 * (
            1.0 - med["on"]["completed_per_s"] / med["off"]["completed_per_s"]),
        "spans_per_window": statistics.median(w["spans"] for w in sides["on"]),
    }), flush=True)
    return 0


if __name__ == "__main__":
    code = main(sys.argv[1:])
    sys.stdout.flush()
    os._exit(code)  # daemon threads of the program must not hold the exit
