"""The arithmetic behind the per-layer metrics. Each metric has a small file
of its own under ``benchmark/metrics/`` that names one of these with its
arguments; a reader that finds nothing to read returns None and the metric is
left out of the line (never 0 for a share of a roofline or a peak).

``ctx`` is what the run hands over: the window's counts, the program's spans
and counters as read at the window's edges, and the reduced trace.
"""

from __future__ import annotations

import statistics

from benchmark.harness import stats, trace_reduce
from benchmark.harness.peaks import device_peaks


def _kind(ctx, kind):
    return ctx["kind"] == kind


# -- host clock and counters -------------------------------------------------


def share_of_window(ctx, key: str, kind: str):
    if not _kind(ctx, kind) or ctx.get(key) is None:
        return None
    return 100.0 * ctx[key] / ctx["window_s"]


def pack_occupancy(ctx):
    if not _kind(ctx, "train") or not ctx.get("slot_tokens"):
        return None
    return 100.0 * ctx["tokens"] / ctx["slot_tokens"]


def peak_hbm_share(ctx, kind: str):
    if not _kind(ctx, kind) or not ctx.get("bytes_limit"):
        return None
    return 100.0 * ctx["memory_peak_bytes"] / ctx["bytes_limit"]


def latency_percentile(ctx, key: str, q: float):
    if not _kind(ctx, "serve") or not ctx.get(key):
        return None
    return stats.percentile(ctx[key], q)


def largest(ctx, key: str, kind: str):
    if not _kind(ctx, kind) or not ctx.get(key):
        return None
    return max(ctx[key])


def span_percentile(ctx, name: str, q: float):
    if not _kind(ctx, "serve"):
        return None
    durs = [(s.t1 - s.t0) * 1e3 for s in ctx["spans"]
            if s.name == name and s.t0 >= ctx["t_open"]]
    return stats.percentile(durs, q) if durs else None


def prefix_hit_share(ctx):
    if not _kind(ctx, "serve"):
        return None
    a = ctx["stats0"].get("prefix_cache", {}).get(ctx["head"])
    b = ctx["stats1"].get("prefix_cache", {}).get(ctx["head"])
    if not a or not b or b["lookups"] == a["lookups"]:
        return None
    return 100.0 * (b["hits"] - a["hits"]) / (b["lookups"] - a["lookups"])


def kv_pages_in_use_share(ctx):
    if not _kind(ctx, "serve"):
        return None
    g = ctx["stats1"].get("kv_pool", {}).get(ctx["head"])
    if not g:
        return None
    total = g["pages_in_use"] + g["pages_free"]
    return 100.0 * g["pages_in_use"] / total if total else None


# -- model FLOPs over the chip's peak -----------------------------------------


def model_step_mfu_train(ctx):
    if not _kind(ctx, "train") or not ctx.get("tokens"):
        return None
    cell = ctx["cell"]
    cfg = cell.config
    # Tokens of an example: its encoder stream plus its target codes.
    per_example = ctx["enc_tokens_per_example"] + cfg["sem_id_dim"]
    examples = ctx["tokens"] / per_example
    flops = examples * cell.flops.train_example(cfg, ctx["enc_tokens_per_example"])
    peak = device_peaks(ctx["device_kind"])["bf16_flops"]
    return 100.0 * flops / (ctx["window_s"] * ctx["chips"] * peak)


def model_step_mfu_serve(ctx):
    if not _kind(ctx, "serve") or not ctx.get("done"):
        return None
    cell = ctx["cell"]
    cfg = cell.config
    D = cfg["sem_id_dim"]
    beams = cfg["assumed"]["beam"]
    flops = 0.0
    for r in ctx["done"]:
        if r.done > ctx["t_close"]:
            continue
        n = 1 + min(len(r.arrival.history), cfg["max_items"]) * D
        flops += cell.flops.serve_decode(cfg, n, beams)
        if not r.arrival.repeat:  # a repeat is served from retained pages
            flops += cell.flops.serve_prefill(cfg, n)
    peak = device_peaks(ctx["device_kind"])["bf16_flops"]
    return 100.0 * flops / (ctx["window_s"] * ctx["chips"] * peak)


# -- the device trace ---------------------------------------------------------


def device_idle_share(ctx, kind: str):
    red = ctx.get("reduced")
    if not _kind(ctx, kind) or red is None or not red.devices:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_seconds(red) / red.window_s)


def _decode_intervals(ctx):
    """Distinct decode-step intervals inside the traced window, on the
    trace's clock, with the spans (one per resident request) of each."""
    red, off = ctx["reduced"], ctx["trace"].offset
    by = {}
    for s in ctx["spans"]:
        if s.name != "decode_step":
            continue
        a, b = s.t0 + off, s.t1 + off
        if a >= red.t0 and b <= red.t1:
            by.setdefault((a, b), []).append(s)
    return by


def decode_step_device_ms(ctx):
    red = ctx.get("reduced")
    if not _kind(ctx, "serve") or red is None or not red.devices:
        return None
    plane = red.devices[0]
    durs = []
    for (a, b) in _decode_intervals(ctx):
        mods = trace_reduce.modules_in(plane, a, b)
        if mods:
            _, s, e = mods[-1]  # the launch the step's pull waited for
            durs.append((e - s) * 1e3)
    return statistics.median(durs) if durs else None


def host_gap_per_decode_step_ms(ctx):
    """Device idle while at least one request was in flight, summed over
    the traced window, over the decode steps taken in it."""
    red = ctx.get("reduced")
    if not _kind(ctx, "serve") or red is None or not red.devices:
        return None
    steps = len(_decode_intervals(ctx))
    if not steps:
        return None
    off = ctx["trace"].offset
    in_flight = trace_reduce.clip(trace_reduce.union(
        (s.t0 + off, s.t1 + off) for s in ctx["spans"] if s.name == "request"),
        red.t0, red.t1)
    plane = red.devices[0]
    idle = trace_reduce.gaps(trace_reduce.busy(plane, red.t0, red.t1),
                             red.t0, red.t1)
    waited = trace_reduce.total(trace_reduce.intersect(idle, in_flight))
    return waited / steps * 1e3


def paged_attention_roofline(ctx, match=("custom-call", "paged")):
    """The least time the chip could take for the K/V the live slots had to
    read in the traced decode steps (the larger of FLOPs over peak and bytes
    over bandwidth), over the summed device time of the kernel's events. The
    kernel's ``pallas_call`` has no name of its own: in the trace it is a
    custom call named after the module method that dispatches it
    (``cross_attn.decode_cross_paged``, ``self_attn.decode_paged``)."""
    red = ctx.get("reduced")
    if not _kind(ctx, "serve") or red is None or not red.devices:
        return None
    kernel_s = sum(trace_reduce.op_totals(red, match=match).values())
    if kernel_s <= 0:
        return None
    cell = ctx["cell"]
    cfg = cell.config
    beams = cfg["assumed"]["beam"]
    layers = cell.flops.paged_layers(cfg)
    by_id = {r.response.request_id: r for r in ctx["done"]
             if r.response.request_id is not None}
    flops = bytes_ = 0.0
    for spans in _decode_intervals(ctx).values():
        for s in spans:
            r = by_id.get(s.trace_id)
            if r is None:
                continue
            kv = cell.flops.kv_tokens(cfg, len(r.arrival.history))
            f, b = cell.flops.paged_attention_call(cfg, kv, beams)
            flops += layers * f
            bytes_ += layers * b
    if flops <= 0:
        return None
    peaks = device_peaks(ctx["device_kind"])
    least = max(flops / peaks["bf16_flops"], bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
