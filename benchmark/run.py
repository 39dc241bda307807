"""Run one cell of the benchmark once and print the result line.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Knows no cell, configuration, traffic mix or metric by name: each is found
from its own file through ``benchmark/harness/spec.py``. Exits non-zero and
prints no result where JAX finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

_T_BEGIN = time.monotonic()  # as near to process start as this file gets

import argparse
import json
import logging
import math
import os
import sys


def parse(argv):
    ap = argparse.ArgumentParser(prog="benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--root", default=None, help="directory of BENCHMARK.json")
    return ap.parse_args(argv)


def per_layer(cell, ctx: dict) -> dict:
    """Every per-layer metric of the cell through its own reader. A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        value = cell.metric_reader(m["name"])(ctx)
        if value is None:
            continue
        value = float(value)
        if not math.isfinite(value):
            raise ValueError(f"metric {m['name']} read {value}")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def verdict(checks: dict) -> bool:
    """Every number compared is finite and within its limit."""
    return bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())


def result_line(cell, result: dict, device: dict, trace: bool) -> dict:
    from benchmark.harness import trace_reduce

    checks = result["checks"]
    correct = verdict(checks)
    ctx = result["ctx"]
    device = dict(device, memory_peak_bytes=int(ctx["memory_peak_bytes"]))
    line = {"correct": correct, "attempted": int(result["attempted"]),
            "failed": int(result["failed"])}
    if trace:
        red = ctx["trace"].reduce()
        ctx["reduced"] = red
        ctx["device_kind"] = device["kind"]
        line["metrics"] = per_layer(cell, ctx)
        if red is not None:
            device["busy_s"] = trace_reduce.busy_seconds(red)
            device["window_s"] = red.window_s
            idle = []
            for d in red.devices[:1]:
                idle = trace_reduce.gaps(trace_reduce.busy(d, red.t0, red.t1),
                                         red.t0, red.t1)
            off = ctx["trace"].offset
            host = [(s.name, s.t0 + off, s.t1 + off) for s in ctx["spans"]
                    if s.t1 + off > red.t0 and s.t0 + off < red.t1]
            order = {"request": 0, "queue_wait": 1}
            host.sort(key=lambda h: order.get(h[0], 2))
            host += [("repack", a + off, b + off)
                     for a, b in ctx.get("repack_spans", [])]
            line["breakdown"] = {
                "device_ops": trace_reduce.top_ops(red, 10),
                "idle_gaps": trace_reduce.label_gaps(idle, host, 10),
            }
    else:
        wanted = {m["name"]: m for m in cell.end_to_end}
        line["metrics"] = {
            k: {"value": float(v), "unit": wanted[k]["unit"]}
            for k, v in result["e2e"].items() if k in wanted}
        missing = set(wanted) - set(line["metrics"])
        if missing:
            line["correct"] = False
            checks = dict(checks, missing_metrics={"value": float(len(missing)),
                                                   "limit": 0.0})
    line["device"] = device
    if "control_checks" in result:  # only where a test or a reading asks
        line["control_correct"] = verdict(result["control_checks"])
        line["control_checks"] = result["control_checks"]
    for key in ("checked_requests", "gc_pause_max_ms", "loadgen_lag_max_ms"):  # for a run's reader
        if key in result:
            line[key] = result[key]
    line["checks"] = checks
    return line


def run_cell(cell, seed: int, seconds: float, trace: bool, device: dict,
             t_begin: float, control: bool = False) -> dict:
    """Everything of a run after the look for a chip (the tests drive this
    on the CPU). ``control`` also reads the lower-precision control in the
    program's place: the tests and the readings behind the limits ask for
    it, a benchmark run never does."""
    result = cell.kind.run(cell, seed, seconds, trace, t_begin, control=control)
    return result_line(cell, result, device, trace)


def main(argv=None) -> int:
    args = parse(sys.argv[1:] if argv is None else argv)
    from benchmark.harness.spec import Spec, SpecError

    try:
        spec = Spec(args.root)
        cell = spec.cell(args.workload)
        cell.check_files()
    except SpecError as e:
        sys.stderr.write(f"benchmark: {e}\n")
        return 2
    seconds = float(args.seconds if args.seconds is not None
                    else spec.doc["run_seconds"])
    logging.basicConfig(level=logging.WARNING)
    from benchmark.harness import device as devmod

    devmod.enable_compile_cache()
    device = devmod.require_chips(cell.chips)
    line = run_cell(cell, args.seed, seconds, bool(args.trace), device,
                    _T_BEGIN)
    for name, c in line["checks"].items():
        sys.stderr.write(f"check {name}: {c['value']!r} limit {c['limit']!r}\n")
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # daemon threads of the program must not hold the exit
