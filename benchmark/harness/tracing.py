"""The profiler around a short part of the window, in the traced run only.

The trace covers ``trace_seconds`` (from the traffic file), either the last
of the window (``trace_at: "end"``, so that stopping the profiler stalls
nothing that is measured) or from ``trace_at`` seconds in. An anchor annotation entered at a known ``time.monotonic()``
reading puts the program's host spans on the trace's clock.
"""

from __future__ import annotations

import os
import shutil
import time

from benchmark.harness import device as devmod
from benchmark.harness import trace_reduce


class Tracer:
    def __init__(self, cell, enabled: bool):
        self.enabled = bool(enabled)
        self.cell = cell
        self.dir = os.path.join(devmod.checkout_root(), ".bench_trace", cell.name)
        self.state = "off"
        self.anchor_monotonic = None
        self.t_start = self.t_stop = None
        self.reduced = None
        self._begin = self._end = None

    def maybe_start(self, t_open: float, seconds: float) -> None:
        """Plan the traced slice; start now if it starts at once."""
        if not self.enabled:
            return
        want = min(float(self.cell.traffic.get("trace_seconds", 3.0)), seconds)
        at = self.cell.traffic.get("trace_at", "end")
        lead = max(seconds - want, 0.0) if at == "end" else min(
            float(at), max(seconds - want, 0.0))
        self._begin = t_open + lead
        self._end = self._begin + want
        self.state = "planned"
        self.poll()

    def poll(self) -> None:
        """Start or stop the profiler when its time has come. Called from
        the thread that drives the window."""
        if self.state == "planned" and time.monotonic() >= self._begin:
            self._start()
        elif self.state == "on" and time.monotonic() >= self._end:
            self.stop()

    def _start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.anchor_monotonic = time.monotonic()
        with jax.profiler.TraceAnnotation(trace_reduce.ANCHOR):
            time.sleep(0.0005)
        self.t_start = time.monotonic()
        self.state = "on"

    def stop(self) -> None:
        if self.state != "on":
            if self.state == "planned":
                self.state = "off"
            return
        import jax

        self.t_stop = time.monotonic()
        jax.profiler.stop_trace()
        self.state = "done"

    def reduce(self):
        """Read the trace once; None when nothing was traced."""
        if self.state != "done":
            return None
        if self.reduced is None:
            path = trace_reduce.find_xplane(self.dir)
            keep = os.environ.get("BENCHMARK_KEEP_TRACE")  # for a look by hand
            if keep:
                os.makedirs(keep, exist_ok=True)
                shutil.copy(path, os.path.join(keep, self.cell.name + ".xplane.pb"))
            red = trace_reduce.load(path)
            self.offset = trace_reduce.to_trace_clock(red, self.anchor_monotonic)
            # The traced window, on the trace's clock.
            red.t0 = self.t_start + self.offset
            red.t1 = self.t_stop + self.offset
            self.reduced = red
            shutil.rmtree(self.dir, ignore_errors=True)
        return self.reduced
