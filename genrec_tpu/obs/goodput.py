"""Training goodput accounting: classify every wall-second of a run.

"Goodput" here is the fraction of wall time the accelerator spends doing
useful training compute — the number a fleet operator watches, because
everything else (compiles, checkpoint saves, restores, host data stalls,
skipped non-finite steps, preemption drains) is overhead that checkpoints,
chaos events, and input pipelines silently eat.

`GoodputMeter` splits an epoch's wall time into the buckets below. The
measured buckets come from explicit ``measure()`` scopes in
`trainers.packed_loop.PackedTrainLoop`; the derived ones come out of the
step-section time:

- ``data_wait``      — blocked in the input iterator (host pipeline stall)
- ``checkpoint_save``— inside `loop.save` / `ckpt.wait`
- ``restore``        — inside `loop.resume` (integrity ladder + device put)
- ``preemption_drain``— inside the preemption save + monitor flush
- ``compile``        — XLA compile seconds observed DURING step dispatch
                       (`CompileEvents`, a process-wide jax.monitoring tap;
                       a persistent-cache load is not a compile)
- ``nonfinite_skipped``— the step time attributed to steps the jitted
                       guard skipped (streak steps * mean step time — the
                       flag read is deferred one step, so per-step
                       attribution would stall dispatch)
- ``compute``        — step-section time minus compile minus skipped
- ``other``          — the residual (logging, eval between epochs, hooks)

Buckets sum to the epoch wall time EXACTLY (``other`` is the residual;
tests pin the arithmetic), and ``goodput_pct = compute / wall``.

Fleet-wide view: `fleet_goodput` allgathers every host's bucket
microseconds through an INJECTED allgather callable (the packed loop
passes `parallel.mesh.allgather_host_ints`) and reports the fleet sums —
one number for "the job is 7% checkpoint-bound", even when only host 3
has the slow disk. Collective: every host must call it at the same point
(the packed loop calls it in the epoch epilogue, which runs in
lockstep). The callable is injected rather than imported: obs is the
cross-cutting leaf layer — every layer feeds it, it imports none of them
(docs/architecture.md; machine-enforced by graftlint's layering rule).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import threading
import time
import weakref
from typing import Mapping

#: Reporting order. compute/other are derived; the rest are measured.
BUCKETS = (
    "compute",
    "compile",
    "checkpoint_save",
    "restore",
    "data_wait",
    "nonfinite_skipped",
    "preemption_drain",
    "other",
)

_MEASURED = ("checkpoint_save", "restore", "data_wait", "preemption_drain")


#: JAX's compile-pipeline events (`jax._src.dispatch`), by the work each
#: times: tracing a function to a jaxpr, lowering it to an MLIR module, and
#: the backend step, which is an XLA compile or a persistent-cache load.
COMPILE_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}

#: The persistent cache's verdicts, fired on the compiling thread inside
#: the backend event they belong to (a miss only where an entry is written,
#: which `parallel.mesh.enable_compile_cache`'s zero thresholds make every
#: miss).
_CACHE_VERDICTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}

#: Trace id of the compile lane (`spans.LANE_PREFIXES`).
COMPILE_LANE = "compile"


@dataclasses.dataclass(frozen=True)
class CompileEvent:
    seq: int
    kind: str  # "trace" | "lower" | "backend"
    fun: str
    t0: float  # time.monotonic(), like every span of the ring
    t1: float
    cache: str = ""  # backend only: "hit" | "miss" | "off"
    thread: int = 0

    def record(self, tracer) -> None:
        attrs = {"fun": self.fun, "seq": self.seq}
        if self.cache:
            attrs["cache"] = self.cache
        tracer.record_span(f"compile.{self.kind}", COMPILE_LANE, self.t0,
                           self.t1, **attrs)


class CompileEvents:
    """Process-wide tap on JAX's compile pipeline: every jaxpr trace, MLIR
    lowering and backend step (XLA compile or persistent-cache load).

    One pair of listeners, registered once per process when `genrec_tpu.obs`
    is imported; scoped consumers take snapshot deltas or attach a tracer
    instead of registering listeners of their own. JAX times each
    stage on the wall clock and reports it twice on exit, as a duration and
    as a time span; the time-span listener takes both (its end is read as
    `time.monotonic()` at the callback and the duration subtracted). A
    backend event is a LOAD when the cache's hit fired on its thread inside
    it: ``cache="hit"``, ``"miss"`` where an entry was written, ``"off"``
    where neither (no cache in use).

    ``snapshot()`` counts XLA compiles only, ``load_snapshot()`` the loads:
    the packed loop diffs the first around step dispatch to catch an
    unexpected mid-run recompile the moment it happens. Every event also
    goes to a bounded log (`LOG_CAPACITY`, ``dropped`` counts what fell
    off), and `attach` replays that log onto an enabled `SpanTracer` as the
    lane `compile` and sends it each later event: set-up's compiles then
    sit on the span clock beside everything else. With no tracer attached
    an event costs one deque append.

    The log keeps the OUTERMOST trace of a nest: a jitted function traced
    inside another's trace reports first, and leaves the log when the
    enclosing trace on its thread reports (it adds nothing to the lane's
    union, and a model's init or step nests thousands: 6,600 to 12,400 a
    process on the chip, which would push set-up's early events off a log
    of this size). A tracer attached by then got it live all the same.
    """

    LOG_CAPACITY = 4096

    _instance: "CompileEvents | None" = None
    _instance_lock = threading.Lock()

    def __init__(self):
        self._lock = threading.Lock()
        self._local = threading.local()
        self.count = 0  # XLA compiles: backend events the cache did not serve
        self.seconds = 0.0
        self.loads = 0  # persistent-cache loads
        self.load_seconds = 0.0
        self.dropped = 0
        self._log: collections.deque[CompileEvent] = collections.deque(
            maxlen=self.LOG_CAPACITY)
        self._seq = itertools.count(1)
        self._tracers: "weakref.WeakSet" = weakref.WeakSet()

    def _on_event(self, event: str, **kwargs) -> None:
        verdict = _CACHE_VERDICTS.get(event)
        if verdict is not None:
            self._local.cache = verdict  # the next backend event's

    def _on_span(self, event: str, start_time: float, end_time: float,
                 **kwargs) -> None:
        kind = COMPILE_KINDS.get(event)
        if kind is None:
            return
        t1 = time.monotonic()
        seconds = max(float(end_time) - float(start_time), 0.0)
        cache = ""
        if kind == "backend":
            cache = getattr(self._local, "cache", None) or "off"
            self._local.cache = None
        with self._lock:
            ev = CompileEvent(next(self._seq), kind,
                              str(kwargs.get("fun_name", "")), t1 - seconds,
                              t1, cache, threading.get_ident())
            log = self._log
            while (kind == "trace" and log and log[-1].kind == "trace"
                   and log[-1].thread == ev.thread and log[-1].t0 >= ev.t0):
                log.pop()
            if len(self._log) == self.LOG_CAPACITY:
                self.dropped += 1
            self._log.append(ev)
            if cache == "hit":
                self.loads += 1
                self.load_seconds += seconds
            elif cache:
                self.count += 1
                self.seconds += seconds
            tracers = list(self._tracers) if self._tracers else ()
        for tracer in tracers:
            ev.record(tracer)

    def snapshot(self) -> tuple[int, float]:
        """(count, seconds) of XLA compiles so far; loads are not compiles."""
        with self._lock:
            return self.count, self.seconds

    def load_snapshot(self) -> tuple[int, float]:
        """(count, seconds) of persistent-cache loads so far."""
        with self._lock:
            return self.loads, self.load_seconds

    def events(self) -> list[CompileEvent]:
        with self._lock:
            return list(self._log)

    def attach(self, tracer) -> None:
        """Replay the log onto an enabled tracer as compile spans, then send
        it every later event. Once per tracer; a disabled one is ignored,
        and a tracer is held weakly."""
        if tracer is None or not tracer.enabled:
            return
        with self._lock:
            if tracer in self._tracers:
                return
            for ev in self._log:
                ev.record(tracer)
            self._tracers.add(tracer)

    @classmethod
    def ensure(cls) -> "CompileEvents":
        with cls._instance_lock:
            if cls._instance is None:
                inst = cls()
                import jax.monitoring

                jax.monitoring.register_event_listener(inst._on_event)
                jax.monitoring.register_event_time_span_listener(inst._on_span)
                cls._instance = inst
            return cls._instance


class GoodputMeter:
    """Wall-time bucket accounting for one training run.

    The epoch window is "since the last ``end_epoch``" (or construction),
    so between-epoch work — eval, periodic saves, the next epoch's repack
    — is charged to the NEXT report's wall and lands in its measured
    buckets or ``other``. Thread-compatible, not thread-safe: one loop
    owns one meter (the packed loop's single-writer discipline).
    """

    def __init__(self):
        self._buckets: dict[str, float] = {b: 0.0 for b in _MEASURED}
        self._step_time = 0.0
        self._compile_time = 0.0
        self._steps = 0
        self._skipped = 0
        self._t_last = time.perf_counter()
        self._run_totals: dict[str, float] = {b: 0.0 for b in BUCKETS}
        self._run_wall = 0.0

    # -- recording -----------------------------------------------------------

    def add(self, bucket: str, seconds: float) -> None:
        if bucket not in self._buckets:
            raise KeyError(f"unknown goodput bucket {bucket!r}; have {_MEASURED}")
        self._buckets[bucket] += max(float(seconds), 0.0)

    @contextlib.contextmanager
    def measure(self, bucket: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add(bucket, time.perf_counter() - t0)

    def note_step(self, seconds: float, compile_seconds: float = 0.0,
                  skipped: bool = False) -> None:
        """One optimizer-step section: its wall time, the XLA compile
        seconds observed inside it, and (deferred) whether the jitted
        guard skipped it."""
        self._step_time += max(float(seconds), 0.0)
        self._compile_time += max(float(compile_seconds), 0.0)
        self._steps += 1
        if skipped:
            self._skipped += 1

    def note_skipped(self, n: int = 1) -> None:
        """Deferred non-finite attribution (the monitor learns about step
        N while step N+1 runs)."""
        self._skipped += int(n)

    # -- reporting -----------------------------------------------------------

    def end_epoch(self) -> dict:
        """Close the window: derive compute/nonfinite/other, reset the
        epoch accumulators, fold into the run totals. Returns
        ``{"wall_s", "goodput_pct", "steps", "buckets": {...}}``."""
        now = time.perf_counter()
        wall = max(now - self._t_last, 1e-9)
        self._t_last = now

        compile_t = min(self._compile_time, self._step_time)
        # The guard's skip flag is read one step late, so skipped time is
        # attributed at the mean step rate rather than per offending step.
        post_compile = max(self._step_time - compile_t, 0.0)
        skipped_t = (
            post_compile * min(self._skipped, self._steps) / self._steps
            if self._steps else 0.0
        )
        compute = max(post_compile - skipped_t, 0.0)
        buckets = {
            "compute": compute,
            "compile": compile_t,
            "nonfinite_skipped": skipped_t,
            **{b: self._buckets[b] for b in _MEASURED},
        }
        accounted = sum(buckets.values())
        buckets["other"] = max(wall - accounted, 0.0)
        # Exactness contract: buckets sum to wall. Over-accounting (timer
        # overlap) is squeezed out of `other` first, then proportionally.
        overflow = accounted + buckets["other"] - wall
        if overflow > 0 and accounted > 0:
            scale = wall / accounted
            buckets = {k: v * scale for k, v in buckets.items()}
        report = {
            "wall_s": wall,
            "steps": self._steps,
            "goodput_pct": 100.0 * buckets["compute"] / wall,
            "buckets": {b: buckets[b] for b in BUCKETS},
        }
        for b in BUCKETS:
            self._run_totals[b] += buckets[b]
        self._run_wall += wall
        self._buckets = {b: 0.0 for b in _MEASURED}
        self._step_time = self._compile_time = 0.0
        self._steps = self._skipped = 0
        return report

    def run_report(self) -> dict:
        """Cumulative over every closed epoch window."""
        wall = max(self._run_wall, 1e-9)
        return {
            "wall_s": self._run_wall,
            "goodput_pct": 100.0 * self._run_totals["compute"] / wall,
            "buckets": dict(self._run_totals),
        }


def fleet_goodput(report: Mapping, allgather=None) -> dict:
    """Aggregate one epoch report fleet-wide (sums over hosts).

    ``allgather`` takes a list of ints and returns an (n_hosts, n_ints)
    array — the caller injects `parallel.mesh.allgather_host_ints` (obs
    imports nothing upward). COLLECTIVE on multi-host: call at the same
    loop point on every host. Single-process returns the local report
    unchanged without touching ``allgather``."""
    import jax

    if jax.process_count() == 1:
        return dict(report)
    if allgather is None:
        raise ValueError(
            "fleet_goodput on a multi-process run needs an allgather "
            "callable (pass parallel.mesh.allgather_host_ints); obs does "
            "not import the runtime layer itself"
        )

    keys = list(BUCKETS)
    local_us = [int(report["buckets"][b] * 1e6) for b in keys]
    local_us.append(int(report["wall_s"] * 1e6))
    gathered = allgather(local_us)  # (n_hosts, len(keys)+1)
    sums = gathered.sum(axis=0)
    buckets = {b: float(sums[i]) / 1e6 for i, b in enumerate(keys)}
    wall = max(float(sums[-1]) / 1e6, 1e-9)
    return {
        "wall_s": wall,
        "n_hosts": int(gathered.shape[0]),
        "goodput_pct": 100.0 * buckets["compute"] / wall,
        "buckets": buckets,
    }
