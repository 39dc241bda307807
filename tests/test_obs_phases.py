"""Step-level spans and counters where the work happens.

The batcher's lane (`batcher/<head>`: each host phase of an iteration once),
the always-on slot and prefill counters of `ServingMetrics`, the names of
the compiled executables, the train loop's host phases and the clock anchor
that joins the ring to a device profile. One tiny paged TIGER engine serves
every engine test (tracing is flipped live with `set_tracer`).
"""

import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from genrec_tpu.obs import SpanTracer, prometheus_text
from genrec_tpu.obs.spans import ANCHOR, is_lane

DECODE_PHASES = ("decode.stage", "decode.launch", "decode.pull", "decode.sweep")
PREFILL_PHASES = ("prefill.stage", "prefill.launch", "prefill.pull",
                  "prefill.retain")
COUNTERS = ("decode_steps", "decode_slot_steps", "decode_live_slot_steps",
            "decode_kv_tokens", "prefill_rows", "prefill_row_slots",
            "prefill_tokens", "prefill_token_slots")


def _tiny_tiger():
    """(model, params, valid sem-ids) of a toy TIGER."""
    from genrec_tpu.models.tiger import Tiger

    rng = np.random.default_rng(7)
    valid = np.unique(rng.integers(0, 8, (20, 3)), axis=0)
    tiger = Tiger(embedding_dim=16, attn_dim=32, dropout=0.0, num_heads=4,
                  n_layers=2, num_item_embeddings=8, num_user_embeddings=20,
                  sem_id_dim=3, max_pos=64)
    params = tiger.init(
        jax.random.key(0), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2, 6), jnp.int32), jnp.zeros((2, 6), jnp.int32),
        jnp.zeros((2, 3), jnp.int32), jnp.zeros((2, 3), jnp.int32),
        jnp.ones((2, 6), jnp.int32),
    )["params"]
    return tiger, params, valid


@pytest.fixture(scope="module")
def engine():
    """Paged TIGER engine with two slot rungs (2, 4) and two prefill
    buckets: four executables, started once for the module."""
    from genrec_tpu.serving import (
        BucketLadder, PagedConfig, ServingEngine, TigerGenerativeHead,
    )

    tiger, params, valid = _tiny_tiger()
    head = TigerGenerativeHead(tiger, valid, top_k=4, name="tiger")
    eng = ServingEngine(
        [head], params, ladder=BucketLadder((1, 2), (8,)), max_batch=2,
        max_wait_ms=1.0, handle_signals=False,
        paged_config=PagedConfig(max_slots=4, page_size=8, pages_per_slot=4),
    ).start()
    eng.n_items = len(valid)
    yield eng
    eng.stop()


def _serve(eng, n: int, seed: int):
    from genrec_tpu.serving import Request

    rng = np.random.default_rng(seed)
    futs = [eng.submit(Request(head="tiger", user_id=int(rng.integers(0, 20)),
                               history=rng.integers(0, eng.n_items,
                                                    int(rng.integers(2, 7)))))
            for _ in range(n)]
    return [f.result(120) for f in futs]


def _traced(eng, tracer, n: int, seed: int):
    """Serve ``n`` requests with ``tracer`` on; turn it off only once the
    batcher has committed the last step's phases (a step's futures resolve
    in its sweep, before the iteration's phases are flushed)."""
    eng.set_tracer(tracer)
    before = _counters(eng)
    resps = _serve(eng, n, seed)
    after = _counters(eng)
    want = after["decode_steps"] - before["decode_steps"]
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if sum(s.name == "decode.sweep" for s in tracer.spans()) == want:
            break
        time.sleep(0.002)
    eng.set_tracer(None)
    return resps, before, after


def _counters(eng) -> dict:
    st = eng.stats()
    out = {k: st[k] for k in COUNTERS}
    out["by_slots"] = dict(st["decode_steps_by_slots"])
    return out


def test_batcher_lane_one_span_per_phase_per_step(engine):
    tracer = SpanTracer(capacity=100_000)
    resps, before, after = _traced(engine, tracer, 9, seed=1)
    assert all(r.request_id for r in resps)

    ring = tracer.spans()
    order = {id(s): i for i, s in enumerate(ring)}  # commit order
    lane = [s for s in ring if s.trace_id == "batcher/tiger"]
    assert lane and all(is_lane(s.trace_id) for s in lane)
    assert all(s.parent_id is None and "seq" in s.attrs for s in lane)
    assert all(s.t1 >= s.t0 for s in lane)

    # The lane is one thread's: committed in order of time, no two phases
    # overlap.
    for a, b in zip(lane, lane[1:]):
        assert a.t1 <= b.t0 + 1e-9, (a.name, b.name)

    by_seq: dict = {}
    for s in lane:
        by_seq.setdefault(s.attrs["seq"], []).append(s)
    steps = [spans for spans in by_seq.values()
             if any(s.name == "decode.stage" for s in spans)]
    assert len(steps) == after["decode_steps"] - before["decode_steps"] > 0

    per_request = [s for s in ring if s.name == "decode_step"]
    for spans in steps:
        decode = [s for s in spans if s.name.startswith("decode.")]
        # Exactly one of each, in this order, disjoint.
        assert tuple(s.name for s in decode) == DECODE_PHASES
        stage, launch, pull, sweep = decode
        assert stage.t1 == launch.t0 and launch.t1 == pull.t0
        assert pull.t1 <= sweep.t0
        assert 0 < stage.attrs["live"] <= stage.attrs["slots"]
        assert launch.attrs["slots"] == stage.attrs["slots"]
        assert stage.attrs["kv_tokens"] >= stage.attrs["live"]
        assert pull.attrs["leaves"] >= 1 and sweep.attrs["finished"] >= 0
        # The per-request spans of the step cover launch and pull exactly,
        # one per live slot, and were committed BEFORE the step's phases.
        mine = [s for s in per_request
                if s.t0 == launch.t0 and s.t1 == pull.t1]
        assert len(mine) == stage.attrs["live"]
        assert max(order[id(s)] for s in mine) < order[id(stage)]
        # Whatever else the iteration did (admission, prefill) came first.
        others = [s for s in spans if not s.name.startswith("decode.")]
        assert all(o.t1 <= stage.t0 for o in others)

    # Counters over the run equal the sums of the spans' attributes.
    stages = [s for s in lane if s.name == "decode.stage"]
    delta = {k: after[k] - before[k] for k in COUNTERS}
    assert delta["decode_slot_steps"] == sum(s.attrs["slots"] for s in stages)
    assert delta["decode_live_slot_steps"] == sum(s.attrs["live"] for s in stages)
    assert delta["decode_kv_tokens"] == sum(s.attrs["kv_tokens"] for s in stages)
    for rung in {s.attrs["slots"] for s in stages}:
        assert (after["by_slots"].get(f"s{rung}", 0)
                - before["by_slots"].get(f"s{rung}", 0)
                == sum(1 for s in stages if s.attrs["slots"] == rung))
    pre = [s for s in lane if s.name == "prefill.stage"]
    assert pre and delta["prefill_rows"] == sum(s.attrs["rows"] for s in pre)
    assert delta["prefill_row_slots"] == sum(s.attrs["bucket_b"] for s in pre)
    assert delta["prefill_tokens"] == sum(s.attrs["tokens"] for s in pre)
    assert delta["prefill_token_slots"] == sum(
        s.attrs["bucket_b"] * s.attrs["bucket_l"] for s in pre)
    assert delta["prefill_tokens"] <= delta["prefill_token_slots"]
    assert delta["prefill_rows"] <= delta["prefill_row_slots"]


def test_admission_and_prefill_phases(engine):
    tracer = SpanTracer(capacity=100_000)
    _traced(engine, tracer, 5, seed=2)
    lane = tracer.spans("batcher/tiger")
    pops = [s for s in lane if s.name == "admit.pop"]
    assert pops
    for p in pops:
        assert {"seq", "warm", "cold", "deferred"} <= set(p.attrs)
        assert p.attrs["warm"] + p.attrs["cold"] + p.attrs["deferred"] > 0
    prefills = [s for s in lane if s.name.startswith("prefill.")]
    assert len(prefills) % 4 == 0 and prefills
    for i in range(0, len(prefills), 4):
        group = prefills[i:i + 4]
        assert tuple(s.name for s in group) == PREFILL_PHASES
        assert len({s.attrs["seq"] for s in group}) == 1
        for a, b in zip(group, group[1:]):
            assert a.t1 == b.t0
        stage, retain = group[0], group[3]
        assert 0 < stage.attrs["rows"] <= stage.attrs["bucket_b"]
        assert (stage.attrs["rows"] <= stage.attrs["tokens"]
                <= stage.attrs["rows"] * stage.attrs["bucket_l"])
        assert 0 <= retain.attrs["inserted"] <= stage.attrs["rows"]
        # The pop that fed this prefill ends where the prefill begins.
        assert any(p.attrs["seq"] == stage.attrs["seq"] and p.t1 <= stage.t0
                   for p in pops)


def test_idle_wait_is_recorded_only_with_requests_queued(engine):
    from genrec_tpu.serving import Request

    tracer = SpanTracer(capacity=100_000)
    engine.set_tracer(tracer)
    time.sleep(0.12)  # an idle engine waits, and records nothing for it
    assert not [s for s in tracer.spans() if s.name == "batcher.idle_wait"]
    # One request alone waits out the coalescing deadline before admission.
    engine.submit(Request(head="tiger", user_id=1,
                          history=np.array([0, 1, 2]))).result(120)
    engine.set_tracer(None)
    waits = [s for s in tracer.spans("batcher/tiger")
             if s.name == "batcher.idle_wait"]
    assert waits
    for w in waits:
        assert w.attrs["queued"] >= 1 and w.attrs["live"] == 0
        assert w.t1 > w.t0


def test_empty_engine_is_one_span_a_period(engine):
    """Between two requests the engine holds nothing: the batcher records
    ONE `batcher.empty` span on its lane for the whole gap, however many
    timed-out waits it took, and none while a request holds a slot."""
    from genrec_tpu.serving import Request

    def one(uid):
        engine.submit(Request(head="tiger", user_id=uid,
                              history=np.array([0, 1, 2]))).result(120)

    tracer = SpanTracer(capacity=100_000)
    engine.set_tracer(tracer)
    time.sleep(0.12)
    one(1)
    t_done = time.monotonic()
    time.sleep(0.3)  # six of the batcher's 50 ms idle waits
    t_sent = time.monotonic()
    one(2)
    engine.set_tracer(None)
    empty = [s for s in tracer.spans("batcher/tiger")
             if s.name == "batcher.empty"]
    between = [s for s in empty if s.t1 > t_done]
    assert len(between) == 1
    assert between[0].t1 - between[0].t0 == pytest.approx(0.3, abs=0.06)
    assert between[0].t1 >= t_sent and "seq" in between[0].attrs
    # None while a request was admitted: from its first span past the
    # queue to its end.
    by_trace: dict = {}
    for s in tracer.spans():
        if not is_lane(s.trace_id):
            by_trace.setdefault(s.trace_id, []).append(s)
    assert len(by_trace) == 2
    for spans in by_trace.values():
        held = (min(s.t0 for s in spans
                    if s.name not in ("request", "queue_wait")),
                max(s.t1 for s in spans))
        for e in empty:
            assert e.t1 <= held[0] or e.t0 >= held[1]


def test_tracer_off_ring_stays_empty_and_counters_count(engine):
    tracer = engine.tracer
    assert not tracer.enabled
    before = _counters(engine)
    recorded = tracer.stats()["spans_recorded"]
    resps = _serve(engine, 4, seed=3)
    after = _counters(engine)
    assert all(r.request_id is None for r in resps)
    assert tracer.stats()["spans_recorded"] == recorded
    assert not tracer.spans()
    assert not next(iter(engine._runners.values()))._phases
    assert after["decode_steps"] > before["decode_steps"]
    assert after["decode_slot_steps"] > before["decode_slot_steps"]
    assert after["decode_live_slot_steps"] > before["decode_live_slot_steps"]
    assert after["decode_kv_tokens"] > before["decode_kv_tokens"]
    assert after["prefill_tokens"] > before["prefill_tokens"]
    assert (after["decode_live_slot_steps"] - before["decode_live_slot_steps"]
            <= after["decode_slot_steps"] - before["decode_slot_steps"])


def test_new_counters_are_prometheus_counters(engine):
    text = prometheus_text(engine.stats())
    for name in COUNTERS:
        assert f"# TYPE genrec_{name} counter" in text
    rungs = re.findall(r"# TYPE genrec_decode_steps_by_slots_s(\d+) (\w+)", text)
    assert rungs and all(kind == "counter" for _, kind in rungs)


def _module_name(compiled) -> str:
    return re.search(r"HloModule\s+([\w.\-]+)", compiled.as_text()).group(1)


def test_every_executable_has_a_name_of_its_own(engine):
    """What a device profile's `XLA Modules` line and every op's scope path
    show: prefill told from decode, one slot rung from another; and no name
    holds `paged`, the word by which the kernel's custom calls are found."""
    from genrec_tpu.models.sasrec import SASRec
    from genrec_tpu.serving import BucketLadder, RetrievalHead, ServingEngine

    runner = engine._runners["tiger"]
    names = {f"decode_s{S}": _module_name(c) for S, c in runner.slots.executables.items()}
    names.update({f"prefill_b{B}_l{L}": _module_name(c)
                  for (B, L), c in runner._prefill.items()})
    assert len(names) == 4
    for key, name in names.items():
        assert name == f"jit_tiger_{key}"

    model = SASRec(num_items=30, max_seq_len=8, embed_dim=16, num_heads=2,
                   num_blocks=1, ffn_dim=32, dropout=0.0)
    params = model.init(jax.random.key(0), jnp.zeros((2, 8), jnp.int32))["params"]
    dense = ServingEngine(
        [RetrievalHead("sasrec", model, top_k=5)], params,
        ladder=BucketLadder((1, 2), (8,)), max_batch=2, handle_signals=False,
    ).start()
    try:
        for (head, B, L), c in dense._exec.items():
            names[(head, B, L)] = _module_name(c)
            assert names[(head, B, L)] == f"jit_{head}_generate_b{B}_l{L}"
    finally:
        dense.stop()
    assert len(set(names.values())) == len(names) == 6
    assert not [n for n in names.values() if "paged" in n]


def test_train_step_carries_its_name():
    import optax

    from genrec_tpu.core.harness import jit_train_step, make_train_step

    def loss_fn(params, batch, rng):
        return jnp.sum(params["w"] * batch), {}

    opt = optax.sgd(0.1)
    assert make_train_step(loss_fn, opt).__name__ == "train_step"
    step = jit_train_step(make_train_step(loss_fn, opt,
                                          name="tiger_train_step_packed"))
    assert step.__name__ == "tiger_train_step_packed"


def test_speculative_engine_leaves_the_same_lane():
    """The tree-verify step is staged, launched, pulled and swept like the
    plain one, under its own executable name."""
    from genrec_tpu.serving import (
        BucketLadder, PagedConfig, Request, ServingEngine,
        TigerGenerativeHead,
    )

    tiger, params, valid = _tiny_tiger()
    tracer = SpanTracer()
    eng = ServingEngine(
        [TigerGenerativeHead(tiger, valid, top_k=4, name="tiger")], params,
        ladder=BucketLadder((1,), (8,)), max_batch=1, max_wait_ms=1.0,
        handle_signals=False, spec_decode=True, spec_fanout=2, tracer=tracer,
        paged_config=PagedConfig(max_slots=1, page_size=8, pages_per_slot=4),
    ).start()
    try:
        eng.submit(Request(head="tiger", user_id=3,
                           history=np.array([0, 1, 2]))).result(120)
        runner = eng._runners["tiger"]
        assert runner.spec_topology is not None
        assert {_module_name(c) for c in runner.slots.executables.values()} == {
            "jit_tiger_spec_s1"}
        st = eng.stats()
        assert st["decode_slot_steps"] == st["decode_steps"] > 0
    finally:
        eng.stop()
    lane = tracer.spans("batcher/tiger")
    decode = [s.name for s in lane if s.name.startswith("decode.")]
    assert decode and len(decode) % 4 == 0
    assert tuple(decode[:4]) == DECODE_PHASES


# ---------------------------------------------------------------------------
# the train loop's host phases
# ---------------------------------------------------------------------------


def _toy_loop(tmp_path, tracer):
    import optax

    from genrec_tpu.core.harness import make_train_step
    from genrec_tpu.core.logging import Tracker, setup_logger
    from genrec_tpu.core.profiling import ProfileWindow
    from genrec_tpu.core.state import TrainState
    from genrec_tpu.parallel import get_mesh, replicate
    from genrec_tpu.trainers.packed_loop import PackedTrainLoop

    def loss_fn(params, batch, rng):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2), {}

    params = {"w": jax.random.normal(jax.random.key(0), (4, 2))}
    opt = optax.adam(1e-2)
    mesh = get_mesh()
    state = replicate(mesh, TrainState.create(params, opt, jax.random.key(1)))
    step_fn = jax.jit(make_train_step(loss_fn, opt, clip_norm=1.0))
    rng = np.random.default_rng(0)
    arrays = {"x": rng.standard_normal((64, 4)).astype(np.float32),
              "y": rng.standard_normal((64, 2)).astype(np.float32)}
    loop = PackedTrainLoop(
        logger=setup_logger(None), tracker=Tracker(save_dir=str(tmp_path)),
        prof=ProfileWindow("", 0), mesh=mesh, guard=None, ckpt=None,
        rows_per_step=8, row_len=1, seed=0, pack_sequences=False,
        train_arrays=arrays, wandb_log_interval=1, tracer=tracer,
    )
    return loop, state, step_fn


def test_train_loop_host_phases(tmp_path):
    tracer = SpanTracer()
    loop, state, step_fn = _toy_loop(tmp_path, tracer)
    t_before = time.monotonic()
    res = loop.run_epoch(state, step_fn, epoch=0, global_step=0)
    res = loop.run_epoch(res.state, step_fn, epoch=1, global_step=res.global_step)
    t_after = time.monotonic()
    assert res.global_step == 16
    assert not tracer.spans("train-e2")
    for epoch in (0, 1):
        ring = tracer.spans(f"train-e{epoch}")
        assert all(is_lane(s.trace_id) for s in ring)
        # One clock for every span of the program: time.monotonic().
        assert all(t_before <= s.t0 <= s.t1 <= t_after for s in ring)
        order = {id(s): i for i, s in enumerate(ring)}
        by_step: dict = {}
        for s in ring:
            by_step.setdefault(s.attrs["step"], {}).setdefault(s.name, []).append(s)
        assert sorted(by_step) == list(range(8 * epoch + 1, 8 * epoch + 9))
        for step, spans in by_step.items():
            want = {"train_step", "train.data_wait", "train.dispatch",
                    "train.sync", "train.host_tail"}
            if step == 1:
                want.add("train.compile")  # the run's first step compiles
            assert set(spans) == want, (step, sorted(spans))
            assert all(len(v) == 1 for v in spans.values())
            whole = spans["train_step"][0]
            wait, dispatch, sync, tail = (
                spans[n][0] for n in ("train.data_wait", "train.dispatch",
                                      "train.sync", "train.host_tail"))
            # In order, without overlap; dispatch and sync make up the step.
            assert wait.t1 <= dispatch.t0 < dispatch.t1 <= sync.t0
            assert sync.t1 <= tail.t0 <= tail.t1
            assert whole.t0 == dispatch.t0 and whole.t1 == sync.t1
            # Committed after the step's `train_step`.
            for s in (wait, dispatch, sync, tail):
                assert order[id(s)] > order[id(whole)]
        compile_span = by_step[8 * epoch + 1].get("train.compile")
        if compile_span:
            # An XLA compile, or a load where the persistent cache holds
            # the step already: either way the dispatch built it.
            a = compile_span[0].attrs
            assert a["n"] + a["loads"] >= 1
            assert (a["seconds"] > 0) == (a["n"] > 0)
        # A step's tail ends where the next wait for data begins.
        steps = sorted(by_step)
        for a, b in zip(steps, steps[1:]):
            assert (by_step[a]["train.host_tail"][0].t1
                    == by_step[b]["train.data_wait"][0].t0)


def test_train_loop_tracer_off_records_nothing(tmp_path):
    from genrec_tpu.obs.spans import NULL_TRACER

    loop, state, step_fn = _toy_loop(tmp_path, None)
    assert loop.tracer is NULL_TRACER
    res = loop.run_epoch(state, step_fn, epoch=0, global_step=0)
    assert res.n_batches == 8
    assert not NULL_TRACER.spans()
    report = loop.goodput.run_report()
    assert report["buckets"]["data_wait"] >= 0 and report["wall_s"] > 0


# ---------------------------------------------------------------------------
# one clock with a device profile; lanes in the report
# ---------------------------------------------------------------------------


def test_profile_anchor_marks_the_ring_and_the_profile(tmp_path):
    tracer = SpanTracer()
    assert SpanTracer(enabled=False).profile_anchor() is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        t0 = tracer.profile_anchor()
        jnp.ones((8, 8)).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (span,) = [s for s in tracer.spans() if s.name == ANCHOR]
    assert span.t0 == t0 and span.t1 > span.t0 and is_lane(span.trace_id)

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmark.harness import trace_reduce

    red = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)),
                            host_names=(ANCHOR,))
    marks = [(n, a, b) for n, a, b in red.host if n.startswith(ANCHOR)]
    assert len(marks) == 1
    # The annotation's start less the span's t0 is the ring's offset: the
    # anchor span, moved by it, lies on the annotation.
    offset = marks[0][1] - span.t0
    assert marks[0][1] <= span.t1 + offset <= marks[0][2] + 5e-3


def test_profile_window_anchors_when_a_tracer_is_on(tmp_path, monkeypatch):
    from genrec_tpu.core.profiling import ProfileWindow

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: calls.append("stop"))
    tracer = SpanTracer()
    win = ProfileWindow(str(tmp_path), n_steps=2, start=1)
    win.tick(1, tracer=tracer)
    win.tick(2, tracer=tracer)
    win.tick(3, tracer=tracer)
    assert calls == ["start", "stop"]
    assert [s.name for s in tracer.spans()] == [ANCHOR]
    # Without a tracer, and with one that is off, the window still works.
    win = ProfileWindow(str(tmp_path), n_steps=1, start=1)
    win.tick(1)
    win.tick(2, tracer=SpanTracer(enabled=False))
    assert calls == ["start", "stop", "start", "stop"]


def test_trace_report_counts_lanes_apart_from_requests(tmp_path, capsys):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))
    import trace_report

    from genrec_tpu.obs import spans

    assert trace_report.LANE_PREFIXES == spans.LANE_PREFIXES  # kept twice
    t = SpanTracer()
    for i in range(3):
        root = t.allocate_span_id()
        t.record_span("decode_step", f"req-{i}", 0.001, 0.004, parent_id=root)
        t.record_span("request", f"req-{i}", 0.0, 0.005, span_id=root)
        t.record_span("decode.pull", "batcher/tiger", 0.002, 0.004, seq=i)
        t.record_span("train_step", "train-e0", 0.0, 0.004, step=i)
    t.record_span(ANCHOR, t.new_trace("profile"), 0.0, 0.001)
    path = t.dump(str(tmp_path / "trace.json"))
    data = trace_report.load_trace(path)
    rep = trace_report.summarize(data)
    assert rep["n_traces"] == 3 and rep["n_lanes"] == 3
    assert rep["phases"]["decode.pull"]["count"] == 3
    cp = trace_report.critical_path_report(data)
    assert cp["n_requests"] == 3 and cp["unrooted_traces"] == 0
    assert trace_report.main([path]) == 0
    assert "traces: 3 (+ 3 lanes)" in capsys.readouterr().out
