"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

Read with ``jax.profiler.ProfileData`` and nothing else. On a TPU the trace
holds one plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` has one
event per device operation and whose line ``XLA Modules`` has one event per
launch of a compiled executable; the host's threads are lines of
``/host:CPU``, where ``jax.profiler.TraceAnnotation`` spans land. All events
share one clock (nanoseconds).

Everything here is interval arithmetic on plain lists, so the test checks it
on a small recorded trace without a chip.
"""

from __future__ import annotations

import dataclasses
import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
ANCHOR = "benchmark_anchor"


@dataclasses.dataclass
class DevicePlane:
    name: str
    ops: list      # (name, start_s, end_s) sorted by start
    modules: list  # (name, start_s, end_s) sorted by start


@dataclasses.dataclass
class Reduced:
    devices: list            # DevicePlane per chip that ran anything
    host: list               # (name, start_s, end_s) host annotations
    t0: float                # traced window on the trace's clock, seconds
    t1: float

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _events(line):
    """(name, start_s, end_s) of a line's events. A custom call (a Pallas
    kernel) carries nothing of its source in its HLO text, so the framework's
    op name from its stats (the named scopes and the calling function) is
    appended to the name, for readers that match a kernel by name."""
    out = []
    for e in line.events:
        s = e.start_ns * 1e-9
        name = e.name
        if "custom-call" in name:
            for key, value in e.stats:
                if key in ("tf_op", "long_name", "name") and isinstance(value, str):
                    name += " | " + value
        out.append((name, s, s + e.duration_ns * 1e-9))
    out.sort(key=lambda x: x[1])
    return out


def load(path: str, host_names=(ANCHOR,)) -> Reduced:
    """Read a trace. ``host_names``: prefixes of host annotations to keep."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = _events(line)
                elif line.name == MODULES_LINE:
                    modules = _events(line)
            if ops:
                devices.append(DevicePlane(plane.name, ops, modules))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(tuple(host_names)):
                        s = e.start_ns * 1e-9
                        host.append((e.name, s, s + e.duration_ns * 1e-9))
    host.sort(key=lambda x: x[1])
    starts = [d.ops[0][1] for d in devices]
    ends = [max(e for _, _, e in d.ops) for d in devices]
    t0 = min(starts) if starts else 0.0
    t1 = max(ends) if ends else 0.0
    return Reduced(devices, host, t0, t1)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals) -> list:
    """Merge (start, end) intervals; returns sorted disjoint intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, t0: float, t1: float) -> list:
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if min(e, t1) > max(s, t0)]


def gaps(merged, t0: float, t1: float) -> list:
    """The complement of disjoint sorted intervals inside [t0, t1]."""
    out, cur = [], t0
    for s, e in merged:
        if s > cur:
            out.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        out.append((cur, t1))
    return [(s, e) for s, e in out if e > s]


def intersect(a, b) -> list:
    """Intersection of two lists of disjoint sorted intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


# ---------------------------------------------------------------------------
# what the metrics read
# ---------------------------------------------------------------------------


def busy(plane: DevicePlane, t0: float, t1: float) -> list:
    """Disjoint intervals in [t0, t1] in which an operation ran on the chip."""
    return clip(union((s, e) for _, s, e in plane.ops), t0, t1)


def busy_seconds(red: Reduced, t0=None, t1=None) -> float:
    """Seconds an operation ran, averaged over the chips in the trace."""
    t0 = red.t0 if t0 is None else t0
    t1 = red.t1 if t1 is None else t1
    if not red.devices:
        return 0.0
    return sum(total(busy(d, t0, t1)) for d in red.devices) / len(red.devices)


def op_totals(red: Reduced, t0=None, t1=None, match=None) -> dict:
    """Summed device seconds per operation name, averaged over chips;
    ``match``: substrings that must all be in the name. Nested events (a
    fusion inside a while) would double count, so only events not covered
    by an earlier, longer event of the same line count."""
    t0 = red.t0 if t0 is None else t0
    t1 = red.t1 if t1 is None else t1
    out: dict = {}
    for d in red.devices:
        outer_end = -1.0
        for name, s, e in d.ops:
            if s < outer_end and e <= outer_end:
                continue  # nested inside the previous outer event
            outer_end = max(outer_end, e)
            if e <= t0 or s >= t1:
                continue
            if match is not None and not all(m in name for m in match):
                continue
            out[name] = out.get(name, 0.0) + (min(e, t1) - max(s, t0))
    n = max(len(red.devices), 1)
    return {k: v / n for k, v in out.items()}


def top_ops(red: Reduced, k: int = 10, t0=None, t1=None) -> list:
    tot = op_totals(red, t0, t1)
    return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def to_trace_clock(red: Reduced, anchor_monotonic_s: float) -> float:
    """Offset to add to a host ``time.monotonic()`` reading to get the
    trace's clock: the anchor annotation was entered at that reading."""
    for name, s, _ in red.host:
        if name.startswith(ANCHOR):
            return s - anchor_monotonic_s
    raise ValueError("trace has no anchor annotation")


def label_gaps(idle, spans, k: int = 10) -> list:
    """The ``k`` longest idle gaps, each named by what the host was doing.
    ``spans``: (name, start_s, end_s) on the trace's clock, general ones
    first and specific ones last: a gap takes the name of the LAST span that
    covers at least half of it, or else of the span that overlaps it most."""
    out = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:k]:
        best, best_ov, covering = "no_span", 0.0, None
        for name, a, b in spans:
            ov = min(e, b) - max(s, a)
            if ov <= 0:
                continue
            if ov >= 0.5 * (e - s):
                covering = name
            if ov > best_ov:
                best, best_ov = name, ov
        out.append([covering or best, e - s])
    return out


def modules_in(plane: DevicePlane, t0: float, t1: float) -> list:
    return [(n, s, e) for n, s, e in plane.modules if s >= t0 and e <= t1]
