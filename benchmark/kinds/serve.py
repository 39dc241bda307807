"""A serving cell: an open loop at the mix's fixed rate against a started,
warmed ``ServingEngine``; latency from the instant each request was DUE.

One generator thread in the benchmark's own process (a chip belongs to one
process) sleeps until each request is due and calls ``engine.submit``. The
answers are judged once the window has closed, from what they say and never
from how the batches formed.
"""

from __future__ import annotations

import gc
import sys
import threading
import time

import numpy as np

from benchmark.harness import device as devmod
from benchmark.harness import stats, tracing
from benchmark.harness.traffic import deployment_trace

WAIT_PAST_CLOSE_S = 60.0


class Record:
    __slots__ = ("arrival", "due", "sent", "done", "response", "error")

    def __init__(self, arrival):
        self.arrival = arrival
        self.due = self.sent = self.done = None
        self.response = self.error = None


def drive(engine, make_request, head_name, arrivals, tracer=None) -> tuple:
    """Submit every arrival when it is due; return (records, t_open)."""
    records = [Record(a) for a in arrivals]
    requests = [make_request(head_name, a.user_id, a.history) for a in arrivals]
    left = threading.Semaphore(0)

    def on_done(rec):
        def cb(fut):
            rec.done = time.monotonic()
            try:
                rec.response = fut.result()
            except BaseException as e:  # noqa: BLE001 — recorded, judged later
                rec.error = e
            left.release()
        return cb

    t_open = time.monotonic() + 0.05
    for rec in records:
        rec.due = t_open + rec.arrival.due_s

    def generate():
        for rec, req in zip(records, requests):
            while True:
                now = time.monotonic()
                if tracer is not None:
                    tracer.poll()
                if now >= rec.due:
                    break
                time.sleep(min(rec.due - now, 0.002))
            rec.sent = time.monotonic()
            try:
                engine.submit(req).add_done_callback(on_done(rec))
            except BaseException as e:  # noqa: BLE001 — refused at submit
                rec.done = time.monotonic()
                rec.error = e
                left.release()

    gen = threading.Thread(target=generate, name="benchmark-loadgen")
    gen.start()
    gen.join()
    deadline = time.monotonic() + WAIT_PAST_CLOSE_S
    for _ in records:
        if not left.acquire(timeout=max(deadline - time.monotonic(), 0.0)):
            break
    return records, t_open


def warm(engine, make_request, head_name, cfg, n_items: int, seed: int) -> None:
    """Run every shape once before the window: one request alone and a
    full micro-batch at each history bucket (user ids outside the mix's)."""
    rng = np.random.default_rng([seed, 41])
    a = cfg["assumed"]["serve"]
    futs = []
    uid = 10**9
    for L in a["history_buckets"]:
        for n in a["batch_buckets"]:
            for _ in range(n):
                uid += 1
                futs.append(engine.submit(make_request(
                    head_name, uid, rng.integers(0, n_items, L))))
            for f in futs:
                f.result(300)
            futs = []


class GcPauses:
    """How long each of the interpreter's garbage collections held every
    thread of the process, in milliseconds (the program's Python runs in
    the benchmark's process, so its garbage is collected here)."""

    def __init__(self):
        self.ms: list[float] = []
        self._t0 = None

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.monotonic()
        elif self._t0 is not None:
            self.ms.append((time.monotonic() - self._t0) * 1e3)

    def __enter__(self):
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)


def fill(engine, make_request, head_name, arrivals, in_flight: int = 256) -> None:
    """Send the deployment's past (``deployment_trace``'s fill) once, in
    order, as fast as the engine takes it: when the last has answered, the
    prefix cache and the page pool hold what they would after a long run."""
    room = threading.Semaphore(in_flight)
    errors = []

    def cb(fut):
        if fut.exception() is not None:
            errors.append(fut.exception())
        room.release()

    for a in arrivals:
        room.acquire()
        engine.submit(make_request(head_name, a.user_id, a.history)
                      ).add_done_callback(cb)
    for _ in range(in_flight):
        room.acquire()
    if errors:
        raise RuntimeError(f"{len(errors)} fill requests failed: {errors[0]!r}")


def build(cell, seed: int, seconds: float, tracer=None):
    """A started engine, warmed in every shape and filled with the
    deployment's past, and the window's arrivals."""
    cfg, adapter = cell.config, cell.adapter
    engine, head, params, catalog = adapter.build_serve(cfg, cell.traffic, seed,
                                                        tracer=tracer)
    past, arrivals = deployment_trace(
        cell.traffic, seconds, cfg["max_items"], len(catalog), seed,
        cache_entries=cfg["assumed"]["serve"]["prefix_cache_entries"])
    warm(engine, adapter.make_request, head.name, cfg, len(catalog), seed)
    fill(engine, adapter.make_request, head.name, past)
    del past
    # A full collection now: with the prefix cache at its cap the program's
    # radix index alone is some hundred thousand objects, a full collection
    # is the longest pause the interpreter can cause, and the next is due
    # only when a quarter as many objects again have come to stay. Whether
    # one fell into a 20 s window would otherwise be chance.
    t0 = time.monotonic()
    gc.collect()
    sys.stderr.write(f"benchmark: full collection before the window took "
                     f"{time.monotonic() - t0:.3f} s\n")
    return engine, head, params, catalog, arrivals


def run(cell, seed: int, seconds: float, trace: bool, t_begin: float,
        control: bool = False) -> dict:
    adapter = cell.adapter
    span_tracer = None
    if trace:
        from genrec_tpu.obs.spans import SpanTracer

        span_tracer = SpanTracer(capacity=2_000_000, enabled=True)
    engine, head, params, catalog, arrivals = build(cell, seed, seconds,
                                                    tracer=span_tracer)
    stats0 = engine.stats()
    tr = tracing.Tracer(cell, enabled=trace)
    setup_s = time.monotonic() - t_begin
    tr.maybe_start(time.monotonic() + 0.05, seconds)
    with GcPauses() as pauses:
        records, t_open = drive(engine, adapter.make_request, head.name,
                                arrivals, tracer=tr if trace else None)
    tr.stop()
    t_close = t_open + seconds
    stats1 = engine.stats()
    peak = devmod.memory_peak_bytes()
    limit = devmod.bytes_limit()
    spans = list(span_tracer.spans()) if span_tracer is not None else []
    engine.stop()

    done = [r for r in records if r.response is not None]
    failed = len(records) - len(done)
    lat_ms = [(r.done - r.due) * 1e3 for r in done]
    lag_ms = [(r.sent - r.due) * 1e3 for r in records if r.sent is not None]
    in_window = sum(1 for r in done if r.done <= t_close)
    e2e = {"setup_s": setup_s}
    if lat_ms:
        e2e["serve_latency_p50_ms"] = stats.percentile(lat_ms, 50)
        e2e["serve_completed_per_s"] = in_window / seconds
    ctx = {
        "cell": cell, "kind": "serve", "window_s": seconds, "chips": cell.chips,
        "records": records, "done": done, "lat_ms": lat_ms, "lag_ms": lag_ms,
        "t_open": t_open, "t_close": t_close,
        "stats0": stats0, "stats1": stats1, "head": head.name,
        "gc_pause_ms": pauses.ms,
        "spans": spans, "trace": tr,
        "memory_peak_bytes": peak, "bytes_limit": limit,
    }
    # Free the program's state before the reference runs.
    del engine, head
    checker = cell._config_module("check")
    checks, extra = checker.judge_served(
        cell, params, catalog, done, seed, control=control)
    for r in records:
        if r.response is None:
            checks["unanswered"] = {"value": float(failed), "limit": 0.0}
            break
    result = {"attempted": len(records), "failed": failed, "checks": checks,
              "e2e": e2e, "ctx": ctx}
    result.update(extra)
    # Where a tail reads far off, these two say whether the host stood still.
    if pauses.ms:
        result["gc_pause_max_ms"] = max(pauses.ms)
    if lag_ms:
        result["loadgen_lag_max_ms"] = max(lag_ms)
    return result
