"""Observability layer: spans, goodput, flight recorder, export, wiring.

Covers the ISSUE-7 satellites explicitly: span-tracer concurrency
(parallel submitters -> well-nested, non-interleaved spans per trace
ID), flight-recorder dump-on-SIGTERM through the REAL chaos hooks, and
goodput-bucket arithmetic (buckets sum to wall time). Plus the
regression pins: strict-JSON metrics.jsonl under NaN metrics, the
engine-totals serving log line, Prometheus exposition, trace_report CLI,
and an end-to-end served-request span tree.
"""

import json
import math
import os
import signal
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from genrec_tpu.core import chaos
from genrec_tpu.core.harness import make_train_step
from genrec_tpu.core.logging import Tracker, log_serving_stats, setup_logger
from genrec_tpu.core.preemption import PreemptionGuard
from genrec_tpu.core.profiling import ProfileWindow
from genrec_tpu.core.state import TrainState
from genrec_tpu.obs import (
    BUCKETS,
    CompileEvents,
    FlightRecorder,
    GoodputMeter,
    MemoryLedger,
    SLOMonitor,
    SLOTarget,
    SpanTracer,
    device_memory_stats,
    get_flight_recorder,
    prometheus_text,
    tree_nbytes,
)
from genrec_tpu.obs.spans import NULL_TRACER, is_lane
from genrec_tpu.parallel import get_mesh, replicate
from genrec_tpu.trainers.packed_loop import PackedTrainLoop


def _strict_loads(line: str):
    """json.loads that REJECTS the bare NaN/Infinity tokens json.dumps
    emits by default — the parser a log pipeline actually uses."""
    def _reject(tok):
        raise ValueError(f"non-strict JSON constant {tok!r}")

    return json.loads(line, parse_constant=_reject)


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------


def test_span_nesting_and_parenting():
    t = SpanTracer()
    with t.span("outer", trace_id="req-a", kind="root"):
        with t.span("mid"):
            with t.span("inner"):
                pass
    spans = {s.name: s for s in t.spans("req-a")}
    assert set(spans) == {"outer", "mid", "inner"}
    assert spans["outer"].parent_id is None
    assert spans["mid"].parent_id == spans["outer"].span_id
    assert spans["inner"].parent_id == spans["mid"].span_id
    # Children inherit the explicit trace id; intervals nest.
    assert spans["inner"].t0 >= spans["mid"].t0
    assert spans["inner"].t1 <= spans["mid"].t1 <= spans["outer"].t1
    assert spans["outer"].attrs == {"kind": "root"}


def test_span_concurrent_traces_well_nested():
    """ISSUE satellite: parallel submitters produce well-nested,
    non-interleaved span trees per trace ID — no cross-trace parenting,
    every child interval inside its parent's."""
    t = SpanTracer(capacity=4096)
    n_threads, depth, reps = 8, 4, 10
    errs = []

    def worker(i: int) -> None:
        try:
            for r in range(reps):
                tid = f"req-{i}-{r}"
                with t.span("l0", trace_id=tid):
                    for d in range(1, depth):
                        with t.span(f"l{d}"):
                            time.sleep(0.0002)
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errs
    all_spans = t.spans()
    assert len(all_spans) == n_threads * reps * depth
    by_trace = {}
    for s in all_spans:
        by_trace.setdefault(s.trace_id, []).append(s)
    assert len(by_trace) == n_threads * reps
    for tid, spans in by_trace.items():
        ids = {s.span_id: s for s in spans}
        roots = [s for s in spans if s.parent_id is None]
        assert len(roots) == 1 and roots[0].name == "l0"
        for s in spans:
            if s.parent_id is None:
                continue
            # Parent is in the SAME trace (no interleaving across
            # threads) and the child's interval nests inside it.
            assert s.parent_id in ids, f"{tid}: foreign parent"
            p = ids[s.parent_id]
            assert p.t0 <= s.t0 and s.t1 <= p.t1


def test_disabled_tracer_records_nothing():
    t = SpanTracer(enabled=False)
    with t.span("x") as s:
        assert s is None
    assert t.record_span("y", "tr", 0.0, 1.0) is None
    assert t.spans() == []
    assert NULL_TRACER.spans() == []


def test_record_span_preallocated_root_and_exemplars():
    t = SpanTracer(max_exemplars=2)
    root = t.allocate_span_id()
    t.record_span("child", "req-1", 1.0, 2.0, parent_id=root)
    t.record_span("request", "req-1", 0.5, 2.5, span_id=root)
    spans = t.spans("req-1")
    assert {s.name for s in spans} == {"child", "request"}
    req = next(s for s in spans if s.name == "request")
    assert req.span_id == root
    assert next(s for s in spans if s.name == "child").parent_id == root

    t.mark_exemplar("req-1", reason="p99 outlier")
    for i in range(2, 5):  # exemplar store is bounded, oldest evicted
        t.record_span("request", f"req-{i}", 0.0, 1.0)
        t.mark_exemplar(f"req-{i}", reason="r")
    ex = t.exemplars()
    assert len(ex) == 2 and "req-1" not in ex
    # ring capacity: completed spans are bounded too
    small = SpanTracer(capacity=4)
    for i in range(10):
        small.record_span("s", "tr", i, i + 1)
    assert len(small.spans()) == 4


def test_chrome_trace_export_and_dump(tmp_path):
    t = SpanTracer()
    with t.span("phase", trace_id="req-1", step=3):
        pass
    t.mark_exemplar("req-1", reason="kept")
    path = t.dump(str(tmp_path / "trace.json"), metadata={"run": "test"})
    data = json.load(open(path))
    assert data["displayTimeUnit"] == "ms"
    ev = data["traceEvents"][0]
    for key in ("name", "cat", "ph", "ts", "dur", "pid", "tid", "args"):
        assert key in ev
    assert ev["ph"] == "X" and ev["args"]["trace_id"] == "req-1"
    assert ev["args"]["step"] == 3
    assert data["otherData"]["exemplars"] == {"req-1": "kept"}
    assert data["otherData"]["run"] == "test"


# ---------------------------------------------------------------------------
# goodput
# ---------------------------------------------------------------------------


def test_goodput_buckets_sum_to_wall():
    """ISSUE satellite: bucket arithmetic — measured + derived + residual
    buckets sum to the epoch wall time."""
    m = GoodputMeter()
    with m.measure("data_wait"):
        time.sleep(0.02)
    with m.measure("checkpoint_save"):
        time.sleep(0.01)
    t0 = time.perf_counter()
    time.sleep(0.03)
    m.note_step(time.perf_counter() - t0)
    time.sleep(0.01)  # unattributed -> other
    r = m.end_epoch()
    assert set(r["buckets"]) == set(BUCKETS)
    total = sum(r["buckets"].values())
    assert math.isclose(total, r["wall_s"], rel_tol=1e-6, abs_tol=1e-6)
    assert r["buckets"]["data_wait"] >= 0.015
    assert r["buckets"]["checkpoint_save"] >= 0.005
    assert r["buckets"]["compute"] >= 0.02
    assert r["buckets"]["other"] >= 0.005
    assert 0.0 < r["goodput_pct"] < 100.0
    # run totals accumulate across epochs
    with m.measure("restore"):
        time.sleep(0.005)
    m.note_step(0.0)
    r2 = m.end_epoch()
    assert math.isclose(sum(r2["buckets"].values()), r2["wall_s"],
                        rel_tol=1e-6, abs_tol=1e-6)
    run = m.run_report()
    assert run["wall_s"] >= r["wall_s"] + r2["wall_s"] - 1e-6
    assert run["buckets"]["restore"] >= 0.004


def test_goodput_compile_and_skipped_attribution():
    m = GoodputMeter()
    for _ in range(4):
        t0 = time.perf_counter()
        time.sleep(0.01)
        m.note_step(time.perf_counter() - t0)
    t0 = time.perf_counter()
    time.sleep(0.06)
    # 0.05s of this step's wall was XLA compile (synthetic attribution).
    m.note_step(time.perf_counter() - t0, compile_seconds=0.05)
    m.note_skipped(1)  # one of the 5 steps was guard-skipped
    r = m.end_epoch()
    b = r["buckets"]
    assert b["compile"] == pytest.approx(0.05, rel=0.2)
    # skipped share = post-compile step time / steps (~0.05/5)
    assert b["nonfinite_skipped"] == pytest.approx(0.01, rel=0.5)
    assert b["compute"] == pytest.approx(0.04, rel=0.5)
    assert math.isclose(sum(b.values()), r["wall_s"], rel_tol=1e-6,
                        abs_tol=1e-6)


def test_compile_events_tap_counts_fresh_jits():
    tap = CompileEvents.ensure()
    assert tap is CompileEvents.ensure()  # singleton
    n0, s0 = tap.snapshot()
    # A constant of this run's own: no persistent cache holds the program,
    # so it is an XLA compile and not a load.
    c = float(time.time_ns() % 1_000_003) / 7.0
    jax.jit(lambda x: x * 2.0 + c)(jnp.ones(5))  # fresh shape+expr
    n1, s1 = tap.snapshot()
    assert n1 > n0 and s1 > s0


def test_compile_tap_persistent_cache_load_is_not_a_compile(tmp_path):
    """A fresh persistent cache: the first compile of a function is a
    `compile.backend` with ``cache="miss"``; after `jax.clear_caches()` the
    same function comes back from the cache, a `compile.backend` with
    ``cache="hit"`` that adds a load and nothing to the compile count, nor
    to the goodput meter's compile bucket (which the loop feeds from the
    count's delta)."""
    from jax._src import compilation_cache

    tap = CompileEvents.ensure()
    tracer = SpanTracer()
    tap.attach(tracer)
    t_start = time.monotonic()
    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    compilation_cache.reset_cache()
    try:
        def f(x):
            return jnp.sin(x) * 3.0 + 0.5
        x = jnp.ones(11)
        jax.jit(f)(x).block_until_ready()
        jax.clear_caches()
        (n0, s0), (l0, _) = tap.snapshot(), tap.load_snapshot()
        meter = GoodputMeter()
        t0 = time.perf_counter()
        jax.jit(f)(x).block_until_ready()
        (n1, s1), (l1, ls1) = tap.snapshot(), tap.load_snapshot()
        meter.note_step(time.perf_counter() - t0, compile_seconds=s1 - s0)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()
    assert (n1, s1) == (n0, s0) and l1 == l0 + 1 and ls1 > 0
    assert meter.end_epoch()["buckets"]["compile"] == 0.0
    ours = [s for s in tracer.spans("compile") if s.t0 >= t_start]
    backend = [s for s in ours
               if s.name == "compile.backend" and s.attrs["fun"] == "jit(f)"]
    assert [s.attrs["cache"] for s in backend] == ["miss", "hit"]
    # The pipeline's other stages ran both times: no cache saves them.
    for stage, fun in (("compile.trace", "f"), ("compile.lower", "jit(f)")):
        assert sum(s.name == stage and s.attrs["fun"] == fun
                   for s in ours) == 2


def test_compile_lane_replayed_then_live():
    """An enabled tracer attached to the tap gets the process's past
    compiles (the log) and then each new one as it happens, once each, on
    the lane `compile` and on `time.monotonic()`; a disabled one gets
    nothing."""
    tap = CompileEvents.ensure()
    c = float(time.time_ns() % 1_000_003) / 11.0
    t_before = time.monotonic()
    jax.jit(lambda x: x - c)(jnp.ones(3))
    past = [e for e in tap.events() if e.t0 >= t_before]
    assert {e.kind for e in past} >= {"trace", "lower", "backend"}
    assert all(t_before <= e.t0 <= e.t1 <= time.monotonic() for e in past)

    tracer, off = SpanTracer(), SpanTracer(enabled=False)
    tap.attach(tracer)
    tap.attach(tracer)  # once per tracer
    tap.attach(off)
    ring = tracer.spans()
    assert all(s.trace_id == "compile" and is_lane(s.trace_id) for s in ring)
    got = {s.attrs["seq"] for s in ring}
    assert {e.seq for e in past} <= got
    assert len(got) == len(ring)  # no event twice
    n = len(ring)
    jax.jit(lambda x: x * c)(jnp.ones(3))  # after the attach: live
    live = tracer.spans()[n:]
    assert {s.name for s in live} >= {"compile.trace", "compile.lower",
                                      "compile.backend"}
    assert all("cache" in s.attrs for s in live
               if s.name == "compile.backend")
    assert not off.spans()


def test_compile_log_keeps_the_outermost_trace():
    """A jitted function traced inside another's trace leaves the log when
    the enclosing trace reports; a tracer attached before got both."""
    tap = CompileEvents.ensure()
    tracer = SpanTracer()
    tap.attach(tracer)
    c = float(time.time_ns() % 1_000_003) / 13.0
    t_before = time.monotonic()

    @jax.jit
    def inner(x):
        return x * c

    def outer(x):
        return inner(x) + 1.0

    jax.jit(outer)(jnp.ones(3))
    logged = {e.fun for e in tap.events()
              if e.kind == "trace" and e.t0 >= t_before}
    assert "outer" in logged and "inner" not in logged
    live = {s.attrs["fun"] for s in tracer.spans("compile")
            if s.name == "compile.trace" and s.t0 >= t_before}
    assert {"outer", "inner"} <= live


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flight_recorder_ring_bound_and_atomic_dump(tmp_path):
    fr = FlightRecorder(capacity=8)
    for i in range(20):
        fr.record("step", step=i, loss=float("nan") if i == 5 else 1.0)
    events = fr.events()
    assert len(events) == 8 and events[-1]["step"] == 19
    assert events[0]["step"] == 12  # oldest evicted
    # no destination configured -> no-op, never raises
    assert fr.dump(reason="nowhere") is None
    path = fr.configure(str(tmp_path / "fr.json"), install_excepthook=False,
                        run="test")
    got = fr.dump(reason="unit")
    assert got == path
    payload = _strict_loads(open(path).read())  # NaN field became null
    assert payload["reason"] == "unit" and payload["meta"]["run"] == "test"
    assert [e["kind"] for e in payload["events"]] == ["step"] * 8
    assert payload["events"][-1]["seq"] == 20
    assert not [f for f in os.listdir(tmp_path) if ".tmp." in f]


def test_flight_recorder_dump_on_sigterm_via_chaos(tmp_path):
    """ISSUE satellite: the REAL chaos hook delivers a real SIGTERM; the
    PreemptionGuard latches it and the flight recorder leaves a dump
    whose last events explain the shutdown (chaos_kill -> signal)."""
    fr = get_flight_recorder()
    fr.clear()
    path = fr.configure(str(tmp_path / "flight_recorder.json"),
                        install_excepthook=False)
    logger = setup_logger(None)
    guard = PreemptionGuard(logger)
    try:
        fr.record("step", step=1)
        fr.record("step", step=2)
        with chaos.inject(chaos.ChaosPlan(kill_at_step=3)):
            chaos.maybe_kill(step=2)  # not yet
            assert not guard.fired
            chaos.maybe_kill(step=3)  # fires SIGTERM at this process
        assert guard.fired
        dump = _strict_loads(open(path).read())
        kinds = [e["kind"] for e in dump["events"]]
        # Injection recorded before delivery, receipt after — the last
        # events ARE the post-mortem narrative.
        assert kinds[-3:] == ["step", "chaos_kill", "signal"] or \
            kinds[-2:] == ["chaos_kill", "signal"], kinds
        assert dump["reason"].startswith("signal:SIGTERM")
        assert dump["events"][-1]["name"] == "SIGTERM"
    finally:
        guard.close()


def test_flight_recorder_excepthook_chains(tmp_path):
    import sys

    fr = FlightRecorder()
    fr.configure(str(tmp_path / "crash.json"), install_excepthook=False)
    seen = []
    prev, sys.excepthook = sys.excepthook, lambda *a: seen.append(a)
    try:
        fr.install_excepthook()
        try:
            raise RuntimeError("boom")
        except RuntimeError:
            sys.excepthook(*sys.exc_info())
        assert len(seen) == 1  # chained to the previous hook
        dump = json.load(open(tmp_path / "crash.json"))
        assert dump["reason"] == "crash:RuntimeError"
        assert dump["events"][-1]["kind"] == "unhandled_exception"
        assert "boom" in dump["events"][-1]["error"]
    finally:
        fr.uninstall_excepthook()
        sys.excepthook = prev


# ---------------------------------------------------------------------------
# memory ledger (ISSUE 10 tentpole)
# ---------------------------------------------------------------------------


def test_tree_nbytes_counts_leaves():
    tree = {"a": np.zeros((4, 8), np.float32),
            "b": (np.zeros(16, np.int32), jnp.zeros((2, 2), jnp.float32)),
            "c": "not an array"}
    assert tree_nbytes(tree) == 4 * 8 * 4 + 16 * 4 + 2 * 2 * 4


def test_memory_ledger_budget_model():
    led = MemoryLedger()
    led.record_operand("tiger", "params", 1000)
    led.record_operand("tiger", "kv_page_pool", 4000)
    led.record_executable("tiger", "decode/S8",
                          stats={"temp": 300, "output": 200, "argument": 5000,
                                 "alias": 0, "code": 50})
    led.record_executable("tiger", "prefill/B2/L8",
                          stats={"temp": 100, "output": 100, "argument": 5000,
                                 "alias": 0, "code": 40})
    led.record_executable("tiger", "broken", stats=None)  # still counted
    h = led.group_summary("tiger")
    assert h["operand_bytes"] == 5000
    assert h["n_executables"] == 3 and h["n_executables_analyzed"] == 2
    # transient peak = worst single executable's temp+output
    assert h["transient_peak_bytes"] == 500
    assert h["transient_peak_executable"] == "decode/S8"
    assert h["total_bytes"] == 5500  # operands + transient peak

    s = led.summary(budget_bytes=10_000)
    assert s["total_bytes"] == 5500 and not s["over_budget"]
    assert s["headroom_pct"] == pytest.approx(45.0)
    s = led.summary(budget_bytes=5000)
    assert s["over_budget"]

    # Engine total across groups: ALL operands resident together, but
    # only the single largest transient (one executable runs at a time)
    # — summing per-group peaks would refuse configs that fit.
    led.record_operand("cobra", "params", 2000)
    led.record_executable("cobra", "decode/S4",
                          stats={"temp": 100, "output": 50, "argument": 0,
                                 "alias": 0, "code": 0})
    s = led.summary()
    assert s["heads"]["cobra"]["total_bytes"] == 2150
    assert s["total_bytes"] == (5000 + 2000) + max(500, 150)
    led.reset_group("cobra")
    text = led.breakdown_text(budget_bytes=5000)
    # actionable: every component named with its bytes
    assert "kv_page_pool" in text and "decode/S8" in text
    assert "budget" in text

    led.reset_group("tiger")
    assert led.summary()["total_bytes"] == 0


def test_device_memory_stats_graceful_without_allocator_stats():
    """CPU exposes no allocator counters: the helper returns {} and the
    packed loop's peak-bytes fold stays a no-op instead of crashing."""
    stats = device_memory_stats()
    assert isinstance(stats, dict)
    for v in stats.values():
        assert isinstance(v, int)


def _tiny_tiger_engine(**kwargs):
    """Paged TIGER engine with a deliberately SMALL compile surface
    (one-bucket ladder, max_slots == max_batch): 2 prefill + 1 decode
    executables, so the ledger tests stay inside the tier-1 budget."""
    from genrec_tpu.models.tiger import Tiger
    from genrec_tpu.serving import (
        BucketLadder, PagedConfig, ServingEngine, TigerGenerativeHead,
    )

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
    head = TigerGenerativeHead(tiger, valid, top_k=4, name="tiger")
    eng = ServingEngine(
        [head], params, ladder=BucketLadder((1, 2), (8,)), max_batch=2,
        max_wait_ms=1.0, handle_signals=False,
        paged_config=PagedConfig(max_slots=2, page_size=8, pages_per_slot=4),
        **kwargs,
    )
    return eng, valid


def test_engine_ledger_accounts_refuses_over_budget_and_exports(rng, tmp_path):
    """ISSUE-10 acceptance + the Prometheus satellite, on ONE warmed
    engine: a synthetic over-budget config is refused at warmup with an
    actionable per-component breakdown; within budget, every warmed
    executable + runtime operand is accounted with consistent sums; and
    the pool/catalog/ledger gauges survive engine snapshot ->
    write_prometheus -> parse-back."""
    from genrec_tpu.obs import write_prometheus
    from genrec_tpu.serving import HBMBudgetError, Request

    # Over-budget: REFUSED at warmup (predict the OOM, don't serve into
    # it), with every component named in the breakdown.
    eng, _ = _tiny_tiger_engine(hbm_budget_bytes=10_000)
    with pytest.raises(HBMBudgetError) as exc:
        eng.start()
    msg = str(exc.value)
    for component in ("params", "kv_page_pool", "paged_slot_state",
                      "catalog_operands", "budget"):
        assert component in msg, (component, msg)

    # Within budget: accounted, consistent, exported.
    eng, valid = _tiny_tiger_engine(hbm_budget_bytes=10**10)
    eng.start()
    try:
        for _ in range(3):
            eng.serve(Request(head="tiger",
                              history=rng.integers(0, len(valid), 5)),
                      timeout=120)
        st = eng.stats()
        h = st["hbm"]["heads"]["tiger"]
        assert h["n_executables"] == st["warmup_compiles"]
        assert set(h["operands"]) == {"params", "catalog_operands",
                                      "kv_page_pool", "paged_slot_state"}
        assert all(v > 0 for v in h["operands"].values())
        assert h["total_bytes"] == h["operand_bytes"] + h["transient_peak_bytes"]
        assert st["hbm"]["budget_bytes"] == 10**10
        assert not st["hbm"]["over_budget"]
        path = write_prometheus(str(tmp_path / "metrics.prom"), st)
    finally:
        eng.stop()
    lines = open(path).read().splitlines()
    # parse back: alternating "# TYPE name kind" / "name value" pairs
    metrics, kinds = {}, {}
    for i in range(0, len(lines), 2):
        assert lines[i].startswith("# TYPE ")
        _, _, name, kind = lines[i].split()
        val_name, val = lines[i + 1].split()
        assert val_name == name
        metrics[name] = float(val)
        kinds[name] = kind
    # pool gauges
    assert "genrec_kv_pool_tiger_pages_in_use" in metrics
    assert kinds["genrec_kv_pool_tiger_pages_in_use"] == "gauge"
    # catalog counters
    assert metrics["genrec_catalog_swaps"] == 0
    assert kinds["genrec_catalog_swaps"] == "counter"
    # ledger gauges
    assert metrics["genrec_hbm_heads_tiger_total_bytes"] > 0
    assert metrics["genrec_hbm_heads_tiger_operands_kv_page_pool"] > 0
    assert kinds["genrec_hbm_heads_tiger_total_bytes"] == "gauge"
    assert metrics["genrec_hbm_total_bytes"] == \
        metrics["genrec_hbm_heads_tiger_total_bytes"]
    # request counters really counted
    assert metrics["genrec_completed"] == 3
    assert kinds["genrec_completed"] == "counter"


# ---------------------------------------------------------------------------
# SLO monitor (ISSUE 10 tentpole)
# ---------------------------------------------------------------------------


def test_slo_monitor_breach_hysteresis_and_recovery():
    fr = FlightRecorder()
    target = SLOTarget(p99_ms=50.0, max_queue_depth=4, window_s=10.0,
                       breach_s=1.0, recover_s=2.0)
    mon = SLOMonitor({"tiger": target}, flight=fr)
    t = 100.0
    # healthy: no shed
    assert mon.observe("tiger", p99_ms=20.0, queue_depth=1, now=t) is False
    # breach starts but has not been sustained for breach_s yet
    assert mon.observe("tiger", p99_ms=80.0, queue_depth=1, now=t + 0.1) is False
    # a blip back to OK resets the breach clock
    assert mon.observe("tiger", p99_ms=20.0, queue_depth=0, now=t + 0.5) is False
    assert mon.observe("tiger", p99_ms=80.0, queue_depth=1, now=t + 1.0) is False
    # sustained past breach_s -> shed + flight event
    assert mon.observe("tiger", p99_ms=80.0, queue_depth=1, now=t + 2.1) is True
    assert mon.is_shedding("tiger")
    assert "p99_ms" in mon.shed_reason("tiger")
    assert [e["head"] for e in fr.events("slo_breach")] == ["tiger"]
    # recovery needs recover_s of sustained OK (hysteresis): a brief OK
    # window does NOT un-shed
    assert mon.observe("tiger", p99_ms=10.0, queue_depth=0, now=t + 3.0) is True
    assert mon.observe("tiger", p99_ms=10.0, queue_depth=0, now=t + 4.0) is True
    # ...and a breach inside the recovery window resets it
    assert mon.observe("tiger", p99_ms=90.0, queue_depth=0, now=t + 4.5) is True
    assert mon.observe("tiger", p99_ms=10.0, queue_depth=0, now=t + 5.0) is True
    assert mon.observe("tiger", p99_ms=10.0, queue_depth=0, now=t + 7.1) is False
    assert not mon.is_shedding("tiger")
    assert len(fr.events("slo_recovered")) == 1
    snap = mon.snapshot()
    assert snap["heads"]["tiger"]["breaches"] == 1
    assert not snap["shedding"]
    # None p99 (not enough samples) skips the dimension, not a breach
    assert mon.observe("tiger", p99_ms=None, queue_depth=0, now=t + 8.0) is False


def test_slo_monitor_deferral_rate_window():
    mon = SLOMonitor({"h": SLOTarget(max_deferral_rate=0.25, window_s=5.0,
                                     breach_s=0.0, recover_s=0.0)})
    t = 10.0
    mon.observe("h", oom_deferred_total=0, submitted_total=0, now=t)
    # 10 submits, 1 deferral in-window: rate 0.1 -> fine
    assert mon.observe("h", oom_deferred_total=1, submitted_total=10,
                       now=t + 1) is False
    # 10 more submits, 9 more deferrals: windowed rate ~0.5 -> shed
    assert mon.observe("h", oom_deferred_total=10, submitted_total=20,
                       now=t + 2) is True
    assert mon.snapshot()["heads"]["h"]["deferral_rate"] > 0.25
    # window slides past the burst; idle (no new submits) must recover,
    # not pin the stale rate forever
    assert mon.observe("h", oom_deferred_total=10, submitted_total=20,
                       now=t + 20) is False


def test_recent_p99_is_per_head_windowed():
    """One slow co-hosted head must not read as a latency breach on a
    healthy head: the sliding-window p99 attributes per head."""
    from genrec_tpu.serving import ServingMetrics

    m = ServingMetrics()
    for _ in range(30):
        m.record_response(0.0, 0.0, 0.001, head="fast")
        m.record_response(0.0, 0.0, 0.5, head="slow")
    assert m.recent_p99_ms(60.0, head="fast") < 10.0
    assert m.recent_p99_ms(60.0, head="slow") > 400.0
    assert m.recent_p99_ms(60.0) > 400.0  # engine-wide view still pools
    assert m.recent_p99_ms(60.0, head="absent") is None  # below min_count


def test_slo_target_validation():
    with pytest.raises(ValueError):
        SLOTarget()  # no objective declared
    with pytest.raises(ValueError):
        SLOTarget(p99_ms=10.0, window_s=0.0)
    with pytest.raises(ValueError):
        SLOMonitor({})


def test_engine_sheds_under_synthetic_overload_and_recovers(rng):
    """ISSUE-10 acceptance: sustained queue breach -> OverloadError for
    new submissions while every ACCEPTED request completes; hysteresis
    un-sheds after the queue drains; zero steady-state recompiles."""
    from genrec_tpu.models.sasrec import SASRec
    from genrec_tpu.serving import (
        BucketLadder, OverloadError, Request, RetrievalHead, ServingEngine,
        SLOTarget as ServingSLOTarget,
    )

    model = SASRec(num_items=30, max_seq_len=8, embed_dim=16, num_heads=2,
                   num_blocks=1, ffn_dim=32, dropout=0.0)
    params = model.init(jax.random.key(0),
                        jnp.zeros((2, 8), jnp.int32))["params"]
    eng = ServingEngine(
        [RetrievalHead("sasrec", model, top_k=5)], params,
        ladder=BucketLadder((1, 2), (8,)), max_batch=2, max_wait_ms=1.0,
        handle_signals=False,
        slo_targets=ServingSLOTarget(max_queue_depth=2, window_s=1.0,
                                     breach_s=0.0, recover_s=0.05),
        slo_poll_secs=0.005,
    ).start()
    try:
        accepted, shed = [], False
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                accepted.append(eng.submit(
                    Request(head="sasrec", history=rng.integers(1, 31, 5))))
            except OverloadError as e:
                shed = True
                assert "sasrec" in str(e) and "queue_depth" in str(e)
                break
        assert shed, "synthetic overload never shed"
        # in-flight and queued work completes while shedding (the drain
        # discipline, recoverable)
        resps = [f.result(120) for f in accepted]
        assert len(resps) == len(accepted)
        # hysteresis un-sheds once the targets hold again
        recovered = False
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                eng.submit(Request(head="sasrec",
                                   history=rng.integers(1, 31, 5))).result(60)
                recovered = True
                break
            except OverloadError:
                time.sleep(0.01)
        assert recovered, "shed never recovered"
        st = eng.stats()
        assert st["overload_rejected"] >= 1
        assert st["overload_by_head"].get("sasrec", 0) >= 1
        assert st["recompilations"] == 0
        assert st["slo"]["heads"]["sasrec"]["breaches"] >= 1
        # overload rejections are NOT drain rejections
        assert st["rejected"] == 0
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# tracker / logging satellites
# ---------------------------------------------------------------------------


def test_tracker_nonfinite_metrics_stay_strict_json(tmp_path):
    """Satellite regression: a NaN/Inf metric must not poison
    metrics.jsonl — every line round-trips through a strict parser."""
    tr = Tracker(save_dir=str(tmp_path))
    tr.log({"train/loss": float("nan"), "train/gnorm": float("inf"),
            "train/neg": float("-inf"), "train/ok": 1.5,
            "nested": {"bad": float("nan")}, "listy": [1.0, float("inf")]})
    tr.log({"train/loss": 2.0})
    tr.finish()
    lines = (tmp_path / "metrics.jsonl").read_text().splitlines()
    assert len(lines) == 2
    first = _strict_loads(lines[0])
    assert first["train/loss"] is None and first["train/gnorm"] is None
    assert first["train/neg"] is None and first["train/ok"] == 1.5
    assert first["nested"]["bad"] is None and first["listy"] == [1.0, None]
    assert _strict_loads(lines[1])["train/loss"] == 2.0


def test_log_serving_stats_engine_totals_not_per_head():
    """Satellite: admit/evict/OOM counters are ENGINE totals — printed
    once on their own line, never inside a head's kv-pool line."""
    import logging

    class _Capture(logging.Handler):
        def __init__(self):
            super().__init__()
            self.records = []

        def emit(self, record):
            self.records.append(record)

    logger = setup_logger(None)  # propagate=False: attach our own handler
    cap = _Capture()
    logger.addHandler(cap)
    stats = {
        "qps": 1.0, "completed": 2, "total_ms": {"p50": 1.0},
        "admits": 10, "evictions": 9, "oom_deferred_admits": 3,
        "decode_steps": 17,
        "kv_pool": {
            "tiger": {"pages_in_use": 1, "pages_free": 7,
                      "slots_active": 1, "slots_total": 4,
                      "kv_tokens_resident": 16},
            "cobra": {"pages_in_use": 2, "pages_free": 6,
                      "slots_active": 2, "slots_total": 4,
                      "kv_tokens_resident": 32},
        },
    }
    try:
        log_serving_stats(logger, Tracker(), stats)
    finally:
        logger.removeHandler(cap)
    messages = [r.getMessage() for r in cap.records]
    totals = [m for m in messages if "engine totals" in m]
    assert len(totals) == 1
    assert "admits=10" in totals[0] and "oom_deferred=3" in totals[0]
    pool_lines = [m for m in messages if "kv-pool[" in m]
    assert len(pool_lines) == 2
    for line in pool_lines:
        assert "admits=" not in line and "oom_deferred" not in line


# ---------------------------------------------------------------------------
# prometheus export + trace report CLI
# ---------------------------------------------------------------------------


def test_prometheus_text_exposition():
    text = prometheus_text({
        "completed": 12, "qps": 3.25,
        "total_ms": {"p99": 8.5, "count": 12},
        "kv_pool": {"tiger": {"pages_in_use": 3}},
        "skip_nan": float("nan"),
        "draining": False,
        # Disaggregated-serving aggregation (genrec_tpu/disagg/): the
        # handoff/transfer lifetime totals are counters; pending
        # backlog, transfer percentiles, and per-role headroom are
        # gauges — typing pinned here beside the engine leaves.
        "disagg": {
            "handoffs_sent": 9, "handoffs_admitted": 9,
            "handoffs_refused": 0, "handoffs_resubmitted": 1,
            "transfer_bytes": 43684, "pending_handoffs": 2,
            "transfer_ms": {"p50": 0.4},
            "roles": {"tiger": {"prefill": {"headroom": 0.9},
                                "decode": {"headroom": 0.5}}},
        },
        # Guarded rollout (serving/rollout.RolloutController.stats(),
        # exported under "rollout") + the engine's checkpoint-watcher
        # error counter: decision totals and failed poll passes are
        # counters; the step gauges and freshness are gauges.
        "watcher_errors": 2,
        "rollout": {
            "staged": 4, "promotions": 3, "vetoes": 1, "rollbacks": 0,
            "watcher_errors": 1, "last_good_step": 120, "canary_step": -1,
            "quarantined_steps": 1, "freshness_s": 0.42,
        },
        # Multi-tenant front (genrec_tpu/tenancy/, TenantFront.stats()):
        # per-tenant admission/shed/mirror and per-arm routing totals
        # are counters; inflight depth, windowed p99, shed state, and
        # the experiment split are gauges.
        "tenancy": {
            "acme": {"submitted": 31, "shed": 2, "shadow_mirrored": 29,
                     "exp_arm_a": 14, "exp_arm_b": 15, "inflight": 1,
                     "p99_ms": 7.5, "shedding": False},
        },
        "experiments": {
            "ranker-v2": {"split": 0.5, "routed_a": 14, "routed_b": 15,
                          "shadow_errors": 0, "shadow_mismatches": 3},
        },
    })
    lines = text.splitlines()
    assert "# TYPE genrec_completed counter" in lines
    assert "genrec_completed 12" in lines
    assert "# TYPE genrec_qps gauge" in lines
    assert "genrec_qps 3.25" in lines
    assert "genrec_total_ms_p99 8.5" in lines
    assert "# TYPE genrec_total_ms_count counter" in lines
    assert "genrec_kv_pool_tiger_pages_in_use 3" in lines
    assert "genrec_draining 0" in lines
    assert not any("nan" in ln.lower() for ln in lines if "genrec_skip" in ln)
    assert "# TYPE genrec_disagg_handoffs_sent counter" in lines
    assert "# TYPE genrec_disagg_handoffs_refused counter" in lines
    assert "# TYPE genrec_disagg_transfer_bytes counter" in lines
    assert "# TYPE genrec_disagg_pending_handoffs gauge" in lines
    assert "# TYPE genrec_disagg_transfer_ms_p50 gauge" in lines
    assert "# TYPE genrec_disagg_roles_tiger_prefill_headroom gauge" in lines
    assert "# TYPE genrec_watcher_errors counter" in lines
    assert "# TYPE genrec_rollout_watcher_errors counter" in lines
    assert "# TYPE genrec_rollout_staged counter" in lines
    assert "# TYPE genrec_rollout_promotions counter" in lines
    assert "# TYPE genrec_rollout_vetoes counter" in lines
    assert "# TYPE genrec_rollout_rollbacks counter" in lines
    assert "# TYPE genrec_rollout_last_good_step gauge" in lines
    assert "# TYPE genrec_rollout_canary_step gauge" in lines
    assert "# TYPE genrec_rollout_quarantined_steps gauge" in lines
    assert "# TYPE genrec_rollout_freshness_s gauge" in lines
    assert "# TYPE genrec_tenancy_acme_submitted counter" in lines
    assert "# TYPE genrec_tenancy_acme_shed counter" in lines
    assert "# TYPE genrec_tenancy_acme_shadow_mirrored counter" in lines
    assert "# TYPE genrec_tenancy_acme_exp_arm_a counter" in lines
    assert "# TYPE genrec_tenancy_acme_exp_arm_b counter" in lines
    assert "# TYPE genrec_tenancy_acme_inflight gauge" in lines
    assert "# TYPE genrec_tenancy_acme_p99_ms gauge" in lines
    assert "# TYPE genrec_tenancy_acme_shedding gauge" in lines
    assert "# TYPE genrec_experiments_ranker_v2_routed_a counter" in lines
    assert "# TYPE genrec_experiments_ranker_v2_routed_b counter" in lines
    assert "# TYPE genrec_experiments_ranker_v2_shadow_errors counter" in lines
    assert "# TYPE genrec_experiments_ranker_v2_shadow_mismatches counter" in lines
    assert "# TYPE genrec_experiments_ranker_v2_split gauge" in lines


def test_trace_report_cli_summarizes(tmp_path, capsys):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))
    import trace_report

    t = SpanTracer()
    for i in range(5):
        t.record_span("decode_step", f"req-{i}", 0.0, 0.001 * (i + 1), step=i)
        t.record_span("request", f"req-{i}", 0.0, 0.002 * (i + 1))
    path = t.dump(str(tmp_path / "trace.json"),
                  metadata={"goodput": {"goodput_pct": 80.0, "wall_s": 10.0,
                                        "buckets": {"compute": 8.0,
                                                    "other": 2.0}}})
    assert trace_report.main([path]) == 0
    out = capsys.readouterr().out
    assert "decode_step" in out and "request" in out
    assert "traces: 5" in out
    assert "goodput: 80.0%" in out
    rep = trace_report.summarize(trace_report.load_trace(path))
    assert rep["phases"]["decode_step"]["count"] == 5
    assert rep["phases"]["request"]["max_ms"] == pytest.approx(10.0, rel=0.01)
    # invalid file -> rc 1
    bad = tmp_path / "bad.json"
    bad.write_text("{}")
    assert trace_report.main([str(bad)]) == 1


def test_trace_report_compare_two_traces(tmp_path, capsys):
    """Satellite: --compare A.json B.json prints per-phase p50/p95/p99
    deltas — a serving perf diff in one command."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))
    import trace_report

    a, b = SpanTracer(), SpanTracer()
    for i in range(10):
        a.record_span("decode_step", f"req-{i}", 0.0, 0.010)
        b.record_span("decode_step", f"req-{i}", 0.0, 0.015)  # 50% slower
        a.record_span("prefill", f"req-{i}", 0.0, 0.020)
        b.record_span("prefill", f"req-{i}", 0.0, 0.010)      # 50% faster
    a.record_span("only_a", "req-0", 0.0, 0.001)
    pa = a.dump(str(tmp_path / "a.json"))
    pb = b.dump(str(tmp_path / "b.json"))
    assert trace_report.main(["--compare", pa, pb]) == 0
    out = capsys.readouterr().out
    assert "decode_step" in out and "+50.0" in out
    assert "prefill" in out and "-50.0" in out
    assert "only in A: only_a" in out
    cmp = trace_report.compare_reports(
        trace_report.summarize(trace_report.load_trace(pa)),
        trace_report.summarize(trace_report.load_trace(pb)),
    )
    d = cmp["phases"]["decode_step"]
    assert d["p50_ms_a"] == pytest.approx(10.0)
    assert d["p50_ms_b"] == pytest.approx(15.0)
    assert d["p50_ms_delta_pct"] == pytest.approx(50.0)
    assert d["p99_ms_delta_pct"] == pytest.approx(50.0)
    assert cmp["only_in_a"] == ["only_a"]
    # one trace and --compare together is a usage error; neither too
    with pytest.raises(SystemExit):
        trace_report.main([pa, "--compare", pa, pb])
    with pytest.raises(SystemExit):
        trace_report.main([])


def test_trace_context_header_roundtrip_and_child():
    """Request lineage: the TraceContext survives the wire-header
    round-trip (the KVHandoff v2 contract) and re-parents via child()."""
    from genrec_tpu.obs import TraceContext

    ctx = TraceContext("req-5", 7, "fleet_router")
    assert TraceContext.from_header(ctx.to_header()) == ctx
    child = ctx.child(11)
    assert child.trace_id == "req-5" and child.parent_span_id == 11
    assert child.origin == "fleet_router"
    assert TraceContext.from_header(None) is None
    assert TraceContext.from_header({"trace_id": None}) is None
    # A root context (no parent yet) keeps parent None through the wire.
    root = TraceContext("req-6", None, "disagg_front")
    assert TraceContext.from_header(root.to_header()) == root


def test_scoped_flight_recorder_stamps_identity():
    """Satellite: every flight event carries its owner — component plus
    replica/worker identity, with callables evaluated at RECORD time
    (a replica learns its id after construction)."""
    fr = get_flight_recorder()
    rid = {"v": None}
    scoped = fr.scoped("engine", replica_id=lambda: rid["v"])
    scoped.record("lineage_test_event", foo=1)
    rid["v"] = "r9"
    worker = scoped.scoped("decode_worker", worker_id="tiger:d0")
    worker.record("lineage_test_event", foo=2)
    evs = fr.events("lineage_test_event")[-2:]
    assert evs[0]["component"] == "engine" and evs[0]["replica_id"] is None
    assert evs[1]["component"] == "decode_worker"
    assert evs[1]["replica_id"] == "r9"
    assert evs[1]["worker_id"] == "tiger:d0"
    # Explicit fields win over the scope's.
    worker.record("lineage_test_event", component="override")
    assert fr.events("lineage_test_event")[-1]["component"] == "override"


def test_tracer_stats_and_component_lanes():
    """Tracer self-metering counters + per-(trace, component) export
    lanes: a lineage trace fans into one Perfetto track per component."""
    tracer = SpanTracer(capacity=64)
    tid = tracer.new_trace()
    root = tracer.allocate_span_id()
    tracer.record_span("route", tid, 0.0, 1.0, parent_id=root,
                       component="fleet_router")
    tracer.record_span("prefill", tid, 1.0, 2.0, parent_id=root,
                       component="prefill_worker")
    tracer.record_span("request", tid, 0.0, 3.0, span_id=root,
                       component="fleet_router")
    s = tracer.stats()
    assert s["enabled"] and s["spans_recorded"] == 3
    assert s["traces_started"] == 1 and s["ring_spans"] == 3
    assert s["ring_capacity"] == 64
    lanes = {
        (e["args"]["trace_id"], e["args"].get("component")): e["tid"]
        for e in tracer.to_chrome_trace()["traceEvents"]
    }
    assert len(set(lanes.values())) == 2  # two component lanes, one trace


def test_critical_path_segments_sum_to_root(tmp_path):
    """The deepest-cover partition attributes every instant of the root
    span to exactly one segment, so segments sum to the root duration —
    including nested containers (slot_residency) and untraced gaps."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))
    import trace_report

    tracer = SpanTracer(capacity=128)
    tid = tracer.new_trace()
    root = tracer.allocate_span_id()
    sid = tracer.allocate_span_id()
    t = 100.0
    tracer.record_span("queue_wait", tid, t, t + 0.010, parent_id=root,
                       component="prefill_worker")
    tracer.record_span("prefill", tid, t + 0.010, t + 0.030,
                       parent_id=root, component="prefill_worker")
    tracer.record_span("decode_step", tid, t + 0.032, t + 0.040,
                       parent_id=sid, component="decode_worker")
    tracer.record_span("slot_residency", tid, t + 0.030, t + 0.045,
                       span_id=sid, parent_id=root,
                       component="decode_worker")
    tracer.record_span("request", tid, t, t + 0.050, span_id=root,
                       component="fleet_router")
    path = tracer.dump(str(tmp_path / "lineage.json"))
    rep = trace_report.critical_path_report(trace_report.load_trace(path))
    assert rep["n_requests"] == 1 and rep["unrooted_traces"] == 0
    segs = {k: v["total_ms"] for k, v in rep["segments"].items()}
    assert segs["queue_wait"] == pytest.approx(10.0, abs=1e-3)
    assert segs["prefill"] == pytest.approx(20.0, abs=1e-3)
    assert segs["decode"] == pytest.approx(8.0, abs=1e-3)
    # residency minus its decode child = the scheduler gap
    assert segs["slot_gap"] == pytest.approx(7.0, abs=1e-3)
    # root time no child covers
    assert segs["untraced"] == pytest.approx(5.0, abs=1e-3)
    assert sum(segs.values()) == pytest.approx(50.0, abs=1e-3)
    assert rep["max_segment_sum_error_ms"] <= 1e-3
    assert rep["segments"]["decode"]["components"] == ["decode_worker"]
    # tail blame ranks the dominant segment first
    assert rep["tail"]["blame"][0]["segment"] == "prefill"
    # --compare --critical-path: identical files diff to zero
    cmp = trace_report.compare_critical_paths(rep, rep)
    assert cmp["segments"]["prefill"]["p50_ms_delta"] == 0.0


def test_critical_path_tenant_filter(tmp_path, capsys):
    """--critical-path --tenant <t>: root spans stamped with the
    tenancy front's ``tenant=`` attribution slice the report to one
    tenant's requests; everything else is counted, not mixed in."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))
    import trace_report

    tracer = SpanTracer(capacity=128)
    for tenant, dur in (("acme", 0.050), ("acme", 0.030), ("globex", 0.200)):
        tid = tracer.new_trace()
        root = tracer.allocate_span_id()
        tracer.record_span("queue_wait", tid, 0.0, dur / 2, parent_id=root,
                           component="serving_engine")
        tracer.record_span("request", tid, 0.0, dur, span_id=root,
                           component="tenant_front", tenant=tenant)
    # One untenanted trace rides along (plain engine traffic).
    tid = tracer.new_trace()
    root = tracer.allocate_span_id()
    tracer.record_span("request", tid, 0.0, 0.005, span_id=root,
                       component="serving_engine")
    path = tracer.dump(str(tmp_path / "tenants.json"))
    data = trace_report.load_trace(path)
    rep_all = trace_report.critical_path_report(data)
    assert rep_all["n_requests"] == 4
    rep = trace_report.critical_path_report(data, tenant="acme")
    assert rep["n_requests"] == 2 and rep["other_tenant_requests"] == 2
    assert rep["tenant"] == "acme"
    # globex's 200ms request is OUT of acme's percentiles.
    assert rep["root_ms"]["p99"] == pytest.approx(50.0, abs=1e-3)
    # CLI: the flag wires through; --tenant without --critical-path errors.
    assert trace_report.main([path, "--critical-path", "--tenant", "acme"]) == 0
    out = capsys.readouterr().out
    assert "2 rooted for tenant 'acme'" in out
    with pytest.raises(SystemExit):
        trace_report.main([path, "--tenant", "acme"])
    capsys.readouterr()


def test_log_serving_stats_hbm_line_per_head():
    """Satellite: one HBM line per head (ledger total vs budget,
    headroom %) beside the pool gauges."""
    import logging

    class _Capture(logging.Handler):
        def __init__(self):
            super().__init__()
            self.records = []

        def emit(self, record):
            self.records.append(record)

    logger = setup_logger(None)
    cap = _Capture()
    logger.addHandler(cap)
    stats = {
        "qps": 1.0, "completed": 2, "total_ms": {"p50": 1.0},
        "hbm": {
            "heads": {
                "tiger": {"operands": {"params": 2 * 2**20},
                          "operand_bytes": 2 * 2**20,
                          "transient_peak_bytes": 2**20,
                          "n_executables": 5,
                          "total_bytes": 3 * 2**20},
            },
            "total_bytes": 3 * 2**20,
            "budget_bytes": 6 * 2**20,
            "headroom_pct": 50.0,
            "over_budget": False,
        },
    }
    try:
        log_serving_stats(logger, Tracker(), stats)
    finally:
        logger.removeHandler(cap)
    messages = [r.getMessage() for r in cap.records]
    hbm_lines = [m for m in messages if "hbm[tiger]" in m]
    assert len(hbm_lines) == 1
    line = hbm_lines[0]
    assert "3.00 MB" in line         # ledger total
    assert "budget 6.0 MB" in line   # vs budget
    assert "headroom 50.0%" in line  # headroom %
    assert "5 executables" in line


# ---------------------------------------------------------------------------
# packed-loop wiring: goodput report + flight events end to end
# ---------------------------------------------------------------------------


def _toy_loop(tmp_path, tracer=None):
    # A constant of this run's own, so that no persistent cache holds the
    # step: its first dispatch is an XLA compile, never a load.
    fresh = (time.time_ns() % 1_000_003) * 1e-12

    def loss_fn(params, batch, rng):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2) + fresh, {}

    params = {"w": jax.random.normal(jax.random.key(0), (4, 2))}
    opt = optax.adam(1e-2)
    mesh = get_mesh()
    state = replicate(mesh, TrainState.create(params, opt, jax.random.key(1)))
    step_fn = jax.jit(make_train_step(loss_fn, opt, clip_norm=1.0))
    rng = np.random.default_rng(0)
    arrays = {"x": rng.standard_normal((64, 4)).astype(np.float32),
              "y": rng.standard_normal((64, 2)).astype(np.float32)}
    tracker = Tracker(save_dir=str(tmp_path))
    loop = PackedTrainLoop(
        logger=setup_logger(None), tracker=tracker, prof=ProfileWindow("", 0),
        mesh=mesh, guard=None, ckpt=None, rows_per_step=8, row_len=1, seed=0,
        pack_sequences=False, train_arrays=arrays, wandb_log_interval=1000,
        save_dir_root=str(tmp_path), tracer=tracer,
    )
    return loop, state, step_fn, tracker


def test_packed_loop_reports_goodput_and_flight_events(tmp_path):
    fr = get_flight_recorder()
    fr.clear()
    tracer = SpanTracer()
    loop, state, step_fn, tracker = _toy_loop(tmp_path, tracer=tracer)
    res = loop.run_epoch(state, step_fn, epoch=0, global_step=0)
    assert res.n_batches == 8 and not res.preempted
    tracker.finish()

    # goodput/* metrics emitted, buckets sum to wall, first-step compile
    # attributed to the compile bucket.
    lines = [_strict_loads(ln)
             for ln in (tmp_path / "metrics.jsonl").read_text().splitlines()]
    g = next(ln for ln in lines if "goodput/pct" in ln)
    wall = g["goodput/wall_s"]
    bucket_sum = sum(v for k, v in g.items()
                     if k.endswith("_s") and k != "goodput/wall_s")
    assert bucket_sum == pytest.approx(wall, rel=0.02, abs=1e-3)
    assert g["goodput/compile_s"] > 0  # the first step's jit compile
    assert loop.recompiles == 0  # steady state: no mid-run recompiles

    # flight recorder: run directory configured, narrative events present
    assert fr.path == str(tmp_path / "flight_recorder.json")
    kinds = [e["kind"] for e in fr.events()]
    assert kinds[0] == "epoch_start"
    assert kinds.count("step") == 8
    assert "epoch_end" in kinds

    # tracer: one train_step span per step under the epoch trace (its
    # host phases beside it: tests/test_obs_phases.py)
    steps = [s for s in tracer.spans("train-e0") if s.name == "train_step"]
    assert len(steps) == 8
    assert [s.attrs["step"] for s in steps] == list(range(1, 9))


def test_packed_loop_goodput_counts_skipped_steps(tmp_path):
    fr = get_flight_recorder()
    fr.clear()
    loop, state, step_fn, tracker = _toy_loop(tmp_path)
    with chaos.inject(chaos.ChaosPlan(nan_at_steps=frozenset({3}))):
        res = loop.run_epoch(state, step_fn, epoch=0, global_step=0)
    assert res.n_batches == 8
    tracker.finish()
    lines = [_strict_loads(ln)
             for ln in (tmp_path / "metrics.jsonl").read_text().splitlines()]
    g = next(ln for ln in lines if "goodput/pct" in ln)
    assert g["goodput/nonfinite_skipped_s"] > 0
    assert any(e["kind"] == "nonfinite_step" for e in fr.events())


# ---------------------------------------------------------------------------
# served request span tree (dense path; the paged tree is pinned by
# scripts/check_obs.py to keep tier-1 wall time lean)
# ---------------------------------------------------------------------------


def test_served_request_yields_complete_span_tree(rng):
    from genrec_tpu.models.sasrec import SASRec
    from genrec_tpu.serving import (
        BucketLadder, Request, RetrievalHead, ServingEngine,
    )

    model = SASRec(num_items=30, max_seq_len=8, embed_dim=16, num_heads=2,
                   num_blocks=1, ffn_dim=32, dropout=0.0)
    params = model.init(jax.random.key(0), jnp.zeros((2, 8), jnp.int32))["params"]
    tracer = SpanTracer()
    eng = ServingEngine(
        [RetrievalHead("sasrec", model, top_k=5)], params,
        ladder=BucketLadder((1, 2), (8,)), max_batch=2, max_wait_ms=1.0,
        handle_signals=False, tracer=tracer,
    ).start()
    try:
        futs = [eng.submit(Request(head="sasrec",
                                   history=rng.integers(1, 31, 5)))
                for _ in range(3)]
        resps = [f.result(60) for f in futs]
        ids = [r.request_id for r in resps]
        assert all(ids) and len(set(ids)) == 3  # unique ids, all minted
        for r in resps:
            spans = tracer.spans(r.request_id)
            by_name = {s.name: s for s in spans}
            assert set(by_name) == {"request", "queue_wait", "compute",
                                    "finalize"}
            root = by_name["request"]
            assert root.parent_id is None
            assert root.attrs["head"] == "sasrec"
            for name in ("queue_wait", "compute", "finalize"):
                child = by_name[name]
                assert child.parent_id == root.span_id
                assert child.t0 >= root.t0 - 1e-6
                assert child.t1 <= root.t1 + 1e-6
            # span durations agree with the Response's own latency split
            assert by_name["queue_wait"].duration == pytest.approx(
                r.queue_wait_s, abs=5e-3)
            assert by_name["compute"].duration == pytest.approx(
                r.compute_s, abs=5e-3)
        # tracing off by default: a fresh engine mints no request ids
        eng.set_tracer(None)
        r = eng.serve(Request(head="sasrec", history=rng.integers(1, 31, 4)),
                      timeout=60)
        assert r.request_id is None
    finally:
        eng.stop()
