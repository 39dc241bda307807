"""Per-layer metrics of set-up, read from the program's compile lane: the
spans `compile.trace`, `compile.lower` and `compile.backend` (attribute
``cache``: ``hit``, ``miss`` or ``off``) that `genrec_tpu.obs.CompileEvents`
replays onto the run's tracer under trace id ``compile``, beside the engine's
``request`` spans.

Set-up is every span that ENDS before the window opens: ``ctx["t_open"]`` in
a serving cell; in a training cell the first span on lane ``train-e1``, the
window's first epoch (set-up runs epoch 0, and the window begins each epoch
it runs with the next number). The readers take unions and subtract what an
earlier row counted, so the seconds-metrics of one run never add up to more
than the set-up they cut. A program with no compile lane (an earlier commit)
gives None everywhere, and the metric is left out of the line.

Also here, since it reads a span this PR's program adds too:
`repack_slice_share`, the traced slice's seconds inside `train.repack`.
"""

from __future__ import annotations

from benchmark.harness import trace_reduce

LANE = "compile"
TRACE_LOWER = ("compile.trace", "compile.lower")


def _setup_end(ctx):
    if ctx["kind"] == "serve":
        return ctx.get("t_open")
    starts = [s.t0 for s in ctx["spans"] if s.trace_id == "train-e1"]
    return min(starts) if starts else None


def _setup(ctx, kind: str):
    """(compile-lane spans that end in set-up, set-up's end), or None where
    the cell is of the other kind or the program records no lane."""
    if ctx["kind"] != kind:
        return None
    lane = [s for s in ctx["spans"] if s.trace_id == LANE]
    t_end = _setup_end(ctx)
    if not lane or t_end is None:
        return None
    return [s for s in lane if s.t1 <= t_end], t_end


def _seconds(spans, names=None) -> float:
    return trace_reduce.total(trace_reduce.union(
        (s.t0, s.t1) for s in spans if names is None or s.name in names))


def trace_lower_s(ctx, kind: str):
    """Seconds of set-up inside the union of `compile.trace` and
    `compile.lower`: the part of every compile that no persistent cache
    saves."""
    got = _setup(ctx, kind)
    if got is None:
        return None
    return _seconds(got[0], TRACE_LOWER)


def backend_s(ctx, kind: str):
    """Seconds of set-up inside `compile.backend` and not inside the row
    above: XLA compiles and persistent-cache loads."""
    got = _setup(ctx, kind)
    if got is None:
        return None
    spans = got[0]
    return _seconds(spans) - _seconds(spans, TRACE_LOWER)


def cache_hit_share(ctx, kind: str):
    """100 x set-up's `compile.backend` spans with ``cache="hit"`` over
    those the cache answered at all (``hit`` or ``miss``)."""
    got = _setup(ctx, kind)
    if got is None:
        return None
    verdicts = [s.attrs.get("cache") for s in got[0]
                if s.name == "compile.backend"]
    hits, misses = verdicts.count("hit"), verdicts.count("miss")
    return 100.0 * hits / (hits + misses) if hits + misses else None


def served_s(ctx):
    """Seconds of a serving cell's set-up in which at least one of the
    engine's ``request`` spans was open, less what the compile lane already
    counted: the warm-up at every shape and the deployment's past."""
    got = _setup(ctx, "serve")
    if got is None:
        return None
    spans, t_end = got
    served = trace_reduce.clip(trace_reduce.union(
        (s.t0, s.t1) for s in ctx["spans"] if s.name == "request"),
        float("-inf"), t_end)
    compiles = trace_reduce.union((s.t0, s.t1) for s in spans)
    return (trace_reduce.total(trace_reduce.union(served + compiles))
            - trace_reduce.total(compiles))


def repack_slice_share(ctx):
    """100 x seconds of the traced slice inside the loop's `train.repack`
    spans, over the slice's seconds."""
    red = ctx.get("reduced")
    if ctx["kind"] != "train" or red is None:
        return None
    off = ctx["trace"].offset
    repack = [(s.t0 + off, s.t1 + off) for s in ctx["spans"]
              if s.name == "train.repack"]
    if not repack:
        return None
    inside = trace_reduce.clip(trace_reduce.union(repack), red.t0, red.t1)
    return 100.0 * trace_reduce.total(inside) / red.window_s
