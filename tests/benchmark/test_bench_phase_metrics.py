"""The metrics that read the program's step-level spans, slot counters and
executable names (``benchmark/harness/phase_readers.py``), on a hand-made
``ctx``: a device plane, the batcher's lane and the per-request spans of three
decode steps, the engine's counters at the window's edges."""

import json
import os
import types

import pytest

from bench_tiny import REPO
from benchmark.harness import phase_readers, readers, trace_reduce as tr
from benchmark.harness.spec import Spec

GAP_METRICS = [f"host_gap_{p}_ms.serve" for p in
               ("stage", "launch", "pull", "sweep", "admit", "wait",
                "unattributed")]
SERVE_METRICS = GAP_METRICS + ["decode_slot_occupancy.serve",
                               "prefill_occupancy.serve",
                               "prefill_device_share.serve"]
OFFSET = 100.0  # the trace's clock is the host's plus this


def span(name, t0, t1, trace_id="batcher/tiger", **attrs):
    return types.SimpleNamespace(name=name, t0=t0, t1=t1, trace_id=trace_id,
                                 attrs=attrs)


def serve_ctx(lane=True, names=True, counters=True):
    """Three iterations of 10 ms on the host's clock from t = 1.0: stage
    1 ms, launch 1 ms, pull 6 ms (the device runs for the first 4 of them),
    1 ms of bookkeeping nothing names, sweep 1 ms; the second iteration
    starts with 2 ms of admission (pop, then a prefill whose launch runs
    1 ms on the device), and a 3 ms idle wait follows the third."""
    spans, ops, mods = [], [], []
    t = 1.0
    for i in range(3):
        if i == 1:
            spans.append(span("admit.pop", t, t + 0.0005, seq=i))
            spans.append(span("prefill.stage", t + 0.0005, t + 0.001, seq=i))
            spans.append(span("prefill.launch", t + 0.001, t + 0.002, seq=i))
            ops.append(("fusion.9", t + 0.001 + OFFSET, t + 0.002 + OFFSET))
            mods.append(("jit_tiger_prefill_b4_l20(1)" if names else "jit_fn(9)",
                         t + 0.001 + OFFSET, t + 0.002 + OFFSET))
            t += 0.002
        stage = (t, t + 0.001)
        launch = (t + 0.001, t + 0.002)
        pull = (t + 0.002, t + 0.008)
        sweep = (t + 0.009, t + 0.010)
        for r in range(2):  # one decode_step span per resident request
            spans.append(span("decode_step", launch[0], pull[1],
                              trace_id=f"req-{r}"))
        spans += [span("decode.stage", *stage, seq=i),
                  span("decode.launch", *launch, seq=i),
                  span("decode.pull", *pull, seq=i),
                  span("decode.sweep", *sweep, seq=i)]
        ops.append(("while.20", launch[1] + OFFSET, launch[1] + 0.004 + OFFSET))
        mods.append(("jit_tiger_decode_s32(2)" if names else "jit_fn(2)",
                     launch[1] + OFFSET, launch[1] + 0.004 + OFFSET))
        t += 0.010
    spans.append(span("batcher.idle_wait", t, t + 0.003, seq=3))
    t_end = t + 0.003
    for r in range(2):
        spans.append(span("request", 0.5, t_end + 1.0, trace_id=f"req-{r}"))
    if not lane:
        spans = [s for s in spans if s.trace_id != "batcher/tiger"]
    red = tr.Reduced([tr.DevicePlane("/device:TPU:0", sorted(ops, key=lambda o: o[1]),
                                     sorted(mods, key=lambda o: o[1]))],
                     [], 1.0 + OFFSET, t_end + OFFSET)
    stats0 = {"decode_steps": 10}
    stats1 = {"decode_steps": 13}
    if counters:
        stats0.update(decode_slot_steps=320, decode_live_slot_steps=100,
                      prefill_tokens=50, prefill_token_slots=80)
        stats1.update(decode_slot_steps=416, decode_live_slot_steps=124,
                      prefill_tokens=60, prefill_token_slots=160)
    return {"kind": "serve", "spans": spans, "reduced": red,
            "trace": types.SimpleNamespace(offset=OFFSET),
            "stats0": stats0, "stats1": stats1, "window_s": 20.0}


@pytest.fixture(scope="module")
def cell():
    return Spec(REPO).cell("tiger_serve_steady")


def read(cell, name, ctx):
    return cell.metric_reader(name)(ctx)


def test_host_gap_split_sums_to_the_whole(cell):
    ctx = serve_ctx()
    whole = readers.host_gap_per_decode_step_ms(ctx)
    # Idle per iteration: stage 1 + launch 1 + the last 2 ms of the pull +
    # 1 unnamed + sweep 1; admission once: 1 ms before the prefill runs;
    # the wait: 3 ms. Over three steps.
    assert whole == pytest.approx((3 * 6 + 1 + 3) / 3)
    got = {n: read(cell, n, ctx) for n in GAP_METRICS}
    assert got["host_gap_stage_ms.serve"] == pytest.approx(1.0)
    assert got["host_gap_launch_ms.serve"] == pytest.approx(1.0)
    assert got["host_gap_pull_ms.serve"] == pytest.approx(2.0)
    assert got["host_gap_sweep_ms.serve"] == pytest.approx(1.0)
    assert got["host_gap_admit_ms.serve"] == pytest.approx(1.0 / 3)
    assert got["host_gap_wait_ms.serve"] == pytest.approx(1.0)
    assert got["host_gap_unattributed_ms.serve"] == pytest.approx(1.0)
    assert sum(got.values()) == pytest.approx(whole, rel=1e-9)


def test_a_phase_that_held_none_of_the_gap_reads_zero_and_the_sum_holds(cell):
    ctx = serve_ctx()
    ctx["spans"] = [s for s in ctx["spans"] if s.name != "batcher.idle_wait"]
    got = {n: read(cell, n, ctx) for n in GAP_METRICS}
    assert got["host_gap_wait_ms.serve"] == 0.0
    assert got["host_gap_unattributed_ms.serve"] == pytest.approx(2.0)
    assert sum(got.values()) == pytest.approx(
        readers.host_gap_per_decode_step_ms(ctx), rel=1e-9)


def test_counters_and_module_names(cell):
    ctx = serve_ctx()
    assert read(cell, "decode_slot_occupancy.serve", ctx) == pytest.approx(25.0)
    assert read(cell, "prefill_occupancy.serve", ctx) == pytest.approx(12.5)
    # One 1 ms prefill launch in a traced window of 37 ms.
    assert read(cell, "prefill_device_share.serve", ctx) == pytest.approx(
        100.0 * 0.001 / ctx["reduced"].window_s)
    same = dict(ctx, stats1=dict(ctx["stats0"]))
    assert read(cell, "decode_slot_occupancy.serve", same) is None


@pytest.mark.parametrize("name", SERVE_METRICS)
def test_none_where_the_program_has_nothing_to_read(cell, name):
    """An earlier commit: no lane, executables all `jit_fn`, no new
    counters; an untraced run; a training cell."""
    assert read(cell, name, serve_ctx(lane=False, names=False,
                                      counters=False)) is None
    if name not in ("decode_slot_occupancy.serve", "prefill_occupancy.serve"):
        assert read(cell, name, dict(serve_ctx(), reduced=None)) is None
    assert read(cell, name, dict(serve_ctx(), kind="train")) is None
    # The whole gap still reads there: only its split needs the lane.
    assert readers.host_gap_per_decode_step_ms(serve_ctx(lane=False)) > 0


def train_ctx(with_phases=True):
    spans, t = [], 5.0
    for step in range(1, 8):
        compile_s = 30.0 if step == 1 else 0.0  # set-up's first step
        tail = 0.004 if step != 5 else 0.050
        parts = [("train.data_wait", 0.001), ("train.dispatch", 0.002 + compile_s),
                 ("train.sync", 1.5), ("train.host_tail", tail)]
        for name, dur in parts:
            if name == "train.dispatch":
                spans.append(span("train_step", t, t + dur + 1.5,
                                  trace_id="train-e0", step=step))
            if with_phases or name == "train.sync":
                spans.append(span(name, t, t + dur, trace_id="train-e0",
                                  step=step))
            t += dur
    # The window: the last five steps (set-up ran the first two).
    return {"kind": "train", "spans": spans, "window_s": 5 * 1.507 + 0.046}


def test_step_host_tail(cell):
    train = Spec(REPO).cell("tiger_train_packed")
    read_it = train.metric_reader("step_host_tail_ms.train")
    assert read_it(train_ctx()) == pytest.approx(7.0)  # median of 7,7,53,7,7
    assert read_it(train_ctx(with_phases=False)) is None
    assert read_it(dict(train_ctx(), kind="serve")) is None
    assert phase_readers.step_host_tail_ms(dict(train_ctx(), spans=[])) is None


def test_new_entries_name_files_layers_and_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    entries = {m["name"]: m for m in doc["per_layer"]}
    layers = {m["layer"] for m in doc["per_layer"][:19]}
    for name in SERVE_METRICS + ["step_host_tail_ms.train"]:
        m = entries[name]
        assert os.path.exists(os.path.join(REPO, "benchmark", "metrics",
                                           name + ".py"))
        assert m["layer"] in layers
        train = name.endswith(".train")
        assert m["workloads"] == ["tiger_train_packed" if train
                                  else "tiger_serve_steady"]
        assert m["moves"] == ("train_tokens_per_s_per_chip" if train
                              else "serve_latency_p50_ms")
    # Additions only, at the end: the first nineteen are the accepted ones.
    assert [m["name"] for m in doc["per_layer"][19:]] == (
        SERVE_METRICS + ["step_host_tail_ms.train"])
