"""Per-layer metrics read from what the program records where the work
happens: the batcher's lane of step-level spans (`batcher/<head>`), the train
loop's host phases, `ServingMetrics`' slot and prefill counters and the
executables' names on the trace's `XLA Modules` line.

The seven `host_gap_*_ms.serve` split `host_gap_per_decode_step_ms.serve`
by cause: the same idle intervals (device idle while a request is in flight)
over the same step count, cut by the lane's phases. The batcher is one
thread, so the phases do not overlap and the seven sum to the whole, the
last being what no phase covers. A program without the lane, the counters
or the names (an earlier commit) gives None everywhere.
"""

from __future__ import annotations

import statistics

from benchmark.harness import readers, trace_reduce

ADMIT_PHASES = ("admit.pop", "prefill.stage", "prefill.launch",
                "prefill.pull", "prefill.retain")
GAP_PHASES = {
    "stage": ("decode.stage",),
    "launch": ("decode.launch",),
    "pull": ("decode.pull",),
    "sweep": ("decode.sweep",),
    "admit": ADMIT_PHASES,
    "wait": ("batcher.idle_wait",),
}
LANE_PHASES = frozenset(n for names in GAP_PHASES.values() for n in names)


def _waited(ctx):
    """(idle-while-in-flight intervals on the trace's clock, decode steps,
    clock offset), exactly as `readers.host_gap_per_decode_step_ms` takes
    them; None where that reader has nothing to read or the program records
    no batcher lane."""
    red = ctx.get("reduced")
    if ctx["kind"] != "serve" or red is None or not red.devices:
        return None
    if not any(s.name in LANE_PHASES for s in ctx["spans"]):
        return None
    steps = len(readers._decode_intervals(ctx))
    if not steps:
        return None
    off = ctx["trace"].offset
    in_flight = trace_reduce.clip(trace_reduce.union(
        (s.t0 + off, s.t1 + off) for s in ctx["spans"] if s.name == "request"),
        red.t0, red.t1)
    idle = trace_reduce.gaps(
        trace_reduce.busy(red.devices[0], red.t0, red.t1), red.t0, red.t1)
    return trace_reduce.intersect(idle, in_flight), steps, off


def _covered(ctx, waited, off, names) -> float:
    """Seconds of ``waited`` inside the lane's spans named ``names``."""
    spans = trace_reduce.union(
        (s.t0 + off, s.t1 + off) for s in ctx["spans"] if s.name in names)
    return trace_reduce.total(trace_reduce.intersect(waited, spans))


def host_gap_phase_ms(ctx, phase: str):
    """The part of the host gap per decode step that fell inside one group
    of the batcher's phases (0 where the lane is there and that phase held
    none of the gap)."""
    w = _waited(ctx)
    if w is None:
        return None
    waited, steps, off = w
    return _covered(ctx, waited, off, GAP_PHASES[phase]) / steps * 1e3


def host_gap_unattributed_ms(ctx):
    """The host gap per decode step less what every phase covers: what
    still has no name (the per-request span bookkeeping between pull and
    sweep, the batch loop between iterations)."""
    w = _waited(ctx)
    if w is None:
        return None
    waited, steps, off = w
    named = _covered(ctx, waited, off, LANE_PHASES)
    return (trace_reduce.total(waited) - named) / steps * 1e3


def counter_ratio(ctx, useful: str, attempted: str):
    """100 x the window's growth of one engine counter over another's."""
    if ctx["kind"] != "serve":
        return None
    a, b = ctx["stats0"], ctx["stats1"]
    if attempted not in a or useful not in a or attempted not in b:
        return None
    den = b[attempted] - a[attempted]
    return 100.0 * (b[useful] - a[useful]) / den if den > 0 else None


def module_device_share(ctx, match: str):
    """Share of the traced window in which an executable whose name holds
    ``match`` ran on the device (`XLA Modules` line of the first chip)."""
    red = ctx.get("reduced")
    if ctx["kind"] != "serve" or red is None or not red.devices:
        return None
    ran = [(s, e) for n, s, e in red.devices[0].modules if match in n]
    if not ran:
        return None
    ran = trace_reduce.clip(trace_reduce.union(ran), red.t0, red.t1)
    return 100.0 * trace_reduce.total(ran) / red.window_s


def step_host_tail_ms(ctx):
    """Median over the window's steps of the host time a train step spends
    NOT waiting for the device: `train.data_wait` + `train.dispatch` +
    `train.host_tail` (the loop's spans, grouped by their `step`)."""
    if ctx["kind"] != "train":
        return None
    parts = ("train.data_wait", "train.dispatch", "train.host_tail")
    spans = [s for s in ctx["spans"] if s.name in parts]
    if not spans:
        return None
    # The ring also holds set-up's steps (the first one compiles): keep
    # those of the window, which ends with the last span recorded.
    t_open = max(s.t1 for s in spans) - ctx["window_s"]
    by_step: dict = {}
    for s in spans:
        if s.t0 >= t_open:
            by_step.setdefault(s.attrs.get("step"), {})[s.name] = s.t1 - s.t0
    whole = [sum(d.values()) * 1e3 for d in by_step.values()
             if len(d) == len(parts)]
    return statistics.median(whole) if whole else None
