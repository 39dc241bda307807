"""Find a serving cell's knee once: one engine, one window per offered rate.

    python3 -m benchmark.tools.knee_sweep <workload> <seconds> <point> [<point> ...]

A point is ``<rate>`` or ``<rate>:<seconds>``, the second form with a window
length of its own. The engine is built, warmed and filled once, as a run of
the cell does; every point then opens a further window of the same
deployment, each with another draw of arrivals (gaps, user ranks, repeats,
lengths). Points at one rate therefore also read the spread over arrival
draws, and at two lengths that spread against the window's length. Prints one
JSON line per point: completed per second inside the window, the latency
percentiles, how late the generator ran, and the backlog at the close. The
cell's fixed rate (a share of the highest rate the engine sustains) is then
written by hand into its traffic file, with these points in PERF.md.
"""

from __future__ import annotations

import json
import sys
import time


def main(argv) -> int:
    workload, seconds = argv[0], float(argv[1])
    points = []
    for word in argv[2:]:
        rate, _, secs = word.partition(":")
        points.append((float(rate), float(secs or seconds)))
    from benchmark.harness import device as devmod
    from benchmark.harness import stats
    from benchmark.harness.spec import Spec
    from benchmark.harness.traffic import deployment_trace

    devmod.enable_compile_cache()
    cell = Spec().cell(workload)
    devmod.require_chips(cell.chips)
    cfg, adapter, serve = cell.config, cell.adapter, cell.kind
    seed = 424242
    t0 = time.monotonic()
    engine, head, _, catalog, _ = serve.build(cell, seed, seconds)
    print(json.dumps({"setup_s": time.monotonic() - t0,
                      "kv_pool": engine.stats()["kv_pool"][head.name]}),
          flush=True)
    entries = cfg["assumed"]["serve"]["prefix_cache_entries"]
    for i, (rate, seconds) in enumerate(points, 1):
        _, arrivals = deployment_trace(
            dict(cell.traffic, rate_per_s=rate), seconds, cfg["max_items"],
            len(catalog), seed, cache_entries=entries, window=i)
        records, t_open = serve.drive(engine, adapter.make_request, head.name,
                                      arrivals)
        t_close = t_open + seconds
        done = [r for r in records if r.response is not None]
        lat = [(r.done - r.due) * 1e3 for r in done]
        lag = [(r.sent - r.due) * 1e3 for r in records if r.sent is not None]
        print(json.dumps({
            "rate": rate, "seconds": seconds, "window": i,
            "attempted": len(records), "failed": len(records) - len(done),
            "completed_per_s": sum(r.done <= t_close for r in done) / seconds,
            "backlog_at_close": sum(r.done > t_close for r in done),
            "p50_ms": stats.percentile(lat, 50), "p95_ms": stats.percentile(lat, 95),
            "p99_ms": stats.percentile(lat, 99),
            "lag_p95_ms": stats.percentile(lag, 95),
            "drain_s": max(r.done for r in done) - t_close,
            "kv_pool": engine.stats()["kv_pool"][head.name],
        }), flush=True)
    engine.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
