"""The set-up metrics that read the program's compile lane
(``benchmark/harness/setup_readers.py``) and `repack_slice_share.train`: on
hand-made span lists whose overlaps are known, and on one the program
records itself."""

import json
import os
import time
import types

import pytest

from bench_tiny import REPO
from benchmark.harness import setup_readers, trace_reduce as tr
from benchmark.harness.spec import Spec

TRAIN = ["tiger_train_packed", "keye_sft_long_history",
         "kimi_linear_sft_lifelong"]
SERVE = ["tiger_serve_steady", "solar_open2_serve_lifelong"]
NEW = {
    "setup_trace_lower_s.train": TRAIN, "setup_backend_s.train": TRAIN,
    "compile_cache_hit_share.train": TRAIN,
    "setup_trace_lower_s.serve": SERVE, "setup_backend_s.serve": SERVE,
    "compile_cache_hit_share.serve": SERVE, "setup_served_s.serve": SERVE,
    "repack_slice_share.train": ["tiger_train_packed"],
}
OFFSET = 100.0


def span(name, t0, t1, trace_id="compile", **attrs):
    return types.SimpleNamespace(name=name, t0=t0, t1=t1, trace_id=trace_id,
                                 attrs=attrs)


def compile_lane():
    """Set-up's compiles on [0, 10): a trace holding a nested trace, its
    lowering, a backend step that overlaps the lowering by 0.5 s; two loads
    and one compile the cache missed; one event with no cache at all."""
    return [
        span("compile.trace", 1.0, 3.0, fun="f", seq=1),
        span("compile.trace", 1.5, 2.0, fun="g", seq=2),  # inside f's
        span("compile.lower", 3.0, 4.0, fun="jit(f)", seq=3),
        span("compile.backend", 3.5, 6.0, fun="jit(f)", seq=4, cache="miss"),
        span("compile.backend", 7.0, 7.5, fun="jit(h)", seq=5, cache="hit"),
        span("compile.backend", 8.0, 8.5, fun="jit(k)", seq=6, cache="hit"),
        span("compile.backend", 9.0, 9.25, fun="jit(m)", seq=7, cache="off"),
    ]


def serve_ctx(lane=True):
    spans = compile_lane() if lane else []
    # Warm-up and fill: requests open over [5, 12) in two overlapping runs,
    # the first under the backend step; one after the window opened.
    spans += [span("request", 5.0, 9.0, trace_id="req-1"),
              span("request", 8.0, 12.0, trace_id="req-2"),
              span("queue_wait", 5.0, 5.5, trace_id="req-1"),
              span("request", 12.5, 13.0, trace_id="req-3")]
    # A compile inside the window is not set-up's.
    spans.append(span("compile.backend", 12.6, 12.9, cache="miss"))
    return {"kind": "serve", "spans": spans, "t_open": 12.2}


def train_ctx(lane=True):
    spans = compile_lane() if lane else []
    spans += [span("train_step", 10.0, 11.0, trace_id="train-e0"),
              span("train.repack", 12.0, 12.4, trace_id="train-e1"),
              span("train_step", 12.4, 12.5, trace_id="train-e1"),
              span("compile.lower", 12.1, 12.3, fun="late")]
    return {"kind": "train", "spans": spans}


def test_serving_set_up_split_counts_each_second_once():
    ctx = serve_ctx()
    tl = setup_readers.trace_lower_s(ctx, "serve")
    be = setup_readers.backend_s(ctx, "serve")
    sv = setup_readers.served_s(ctx)
    assert tl == pytest.approx(3.0)  # [1, 4): the nested trace once
    # [4, 6) of the missed compile, the two loads, the uncached one.
    assert be == pytest.approx(2.0 + 0.5 + 0.5 + 0.25)
    # requests open over [5, 12): less the compile lane's [5, 6), [7, 7.5),
    # [8, 8.5), [9, 9.25).
    assert sv == pytest.approx(7.0 - 1.0 - 0.5 - 0.5 - 0.25)
    # The three never exceed set-up, which ends at the window's open.
    assert tl + be + sv <= ctx["t_open"]
    every = tr.union([(s.t0, s.t1) for s in ctx["spans"]
                      if s.t1 <= ctx["t_open"] and s.name != "queue_wait"])
    assert tl + be + sv == pytest.approx(tr.total(every))
    assert setup_readers.cache_hit_share(ctx, "serve") == pytest.approx(
        100.0 * 2 / 3)


def test_training_set_up_ends_at_the_first_epoch_of_the_window():
    ctx = train_ctx()
    assert setup_readers.trace_lower_s(ctx, "train") == pytest.approx(3.0)
    assert setup_readers.backend_s(ctx, "train") == pytest.approx(3.25)
    assert setup_readers.cache_hit_share(ctx, "train") == pytest.approx(
        100.0 * 2 / 3)
    # No serving reader reads a training cell, and the other way about.
    assert setup_readers.served_s(ctx) is None
    assert setup_readers.trace_lower_s(ctx, "serve") is None
    assert setup_readers.backend_s(serve_ctx(), "train") is None
    # Without the window's first epoch, set-up has no end to read.
    no_e1 = [s for s in ctx["spans"] if s.trace_id != "train-e1"]
    assert setup_readers.trace_lower_s(dict(ctx, spans=no_e1), "train") is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_every_new_metric_reads_none_on_a_program_without_its_spans(name):
    """The parent: no compile lane and no `train.repack`; every reader
    gives None and the metric is left out of the line."""
    cell = Spec(REPO).cell(NEW[name][0])
    read = cell.metric_reader(name)
    kind = name.rsplit(".", 1)[1]
    bare = serve_ctx(lane=False) if kind == "serve" else train_ctx(lane=False)
    bare["spans"] = [s for s in bare["spans"]
                     if s.name != "train.repack" and s.trace_id != "compile"]
    bare.update(reduced=tr.Reduced([], [], 0.0, 20.0),
                trace=types.SimpleNamespace(offset=0.0))
    assert read(bare) is None
    full = serve_ctx() if kind == "serve" else train_ctx()
    full.update(reduced=tr.Reduced([], [], 11.0 + OFFSET, 13.0 + OFFSET),
                trace=types.SimpleNamespace(offset=OFFSET))
    assert read(full) is not None


def test_cache_hit_share_none_where_no_cache_answered():
    ctx = serve_ctx()
    ctx["spans"] = [s for s in ctx["spans"]
                    if s.attrs.get("cache") not in ("hit", "miss")]
    assert setup_readers.cache_hit_share(ctx, "serve") is None
    ctx = serve_ctx()
    for s in ctx["spans"]:
        if s.attrs.get("cache") == "miss":
            s.attrs["cache"] = "hit"
    assert setup_readers.cache_hit_share(ctx, "serve") == 100.0


def test_repack_slice_share_reads_the_traced_slice_only():
    spans = [span("train.repack", 1.0, 1.5, trace_id="train-e1", epoch=1),
             span("train.repack", 3.0, 3.4, trace_id="train-e2", epoch=2),
             span("train.repack", 9.0, 9.5, trace_id="train-e5", epoch=5)]
    red = tr.Reduced([], [], 2.9 + OFFSET, 4.9 + OFFSET)  # a 2 s slice
    ctx = {"kind": "train", "spans": spans, "reduced": red,
           "trace": types.SimpleNamespace(offset=OFFSET)}
    assert setup_readers.repack_slice_share(ctx) == pytest.approx(20.0)
    assert setup_readers.repack_slice_share(dict(ctx, reduced=None)) is None
    assert setup_readers.repack_slice_share(dict(ctx, kind="serve")) is None


def test_on_spans_the_program_records():
    """The tap's own lane: a function traced, lowered and compiled after a
    tracer is attached reads back with every second counted once."""
    import jax
    import jax.numpy as jnp

    from genrec_tpu.obs import CompileEvents, SpanTracer

    tracer = SpanTracer()
    CompileEvents.ensure().attach(tracer)
    t_start = time.monotonic()
    c = float(time.time_ns() % 1_000_003) / 13.0

    def inner(x):
        return jnp.cos(x) + c

    jax.jit(lambda x: jax.jit(inner)(x) * 2.0)(jnp.ones(4)).block_until_ready()
    t_open = time.monotonic()
    spans = [s for s in tracer.spans() if s.t0 >= t_start]
    ctx = {"kind": "serve", "spans": spans, "t_open": t_open}
    tl = setup_readers.trace_lower_s(ctx, "serve")
    be = setup_readers.backend_s(ctx, "serve")
    assert tl > 0 and be > 0
    assert tl + be <= t_open - t_start
    assert tl + be == pytest.approx(tr.total(tr.union(
        (s.t0, s.t1) for s in spans)))
    # Nested traces overlap: summing them would count some seconds twice.
    traces = [s for s in spans if s.name == "compile.trace"]
    assert len(traces) >= 2


def test_entries_name_files_layer_and_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    entries = {m["name"]: m for m in doc["per_layer"]}
    names = [m["name"] for m in doc["per_layer"]]
    assert names[-len(NEW):] == list(NEW)  # appended, at the end
    for name, cells in NEW.items():
        m = entries[name]
        assert os.path.exists(os.path.join(REPO, "benchmark", "metrics",
                                           name + ".py"))
        assert m["workloads"] == cells
        if name.startswith("repack"):
            assert (m["layer"], m["moves"]) == ("train loop",
                                                "train_tokens_per_s_per_chip")
            assert m["source"] == "program_span"
        else:
            assert (m["layer"], m["moves"]) == ("set-up / compile", "setup_s")
            assert m["source"] == ("program_counter" if "share" in name
                                   else "program_span")
