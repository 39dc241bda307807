#!/usr/bin/env python
"""Obs smoke: traced serve + train loops, schema checks, overhead bound.

Built on the shared graftlint harness (genrec_tpu/analysis/ir.py) for the
CLI and one-verdict-JSON conventions; CLI, verdict schema and rc are
unchanged.

What it proves (the ISSUE-7 acceptance plus the ISSUE-10 device-memory
ledger and SLO guard, CI-sized):

1. A single served request through the PAGED generative path yields a
   COMPLETE span tree — request -> queue_wait / admission / prefill /
   decode_step(s) / finalize — exportable to Chrome-trace JSON that
   passes a schema check and summarizes through scripts/trace_report.py.
2. A short traced train loop reports per-epoch goodput whose buckets sum
   to the epoch wall time, and every metrics.jsonl line (including one
   with a NaN metric) round-trips through a STRICT JSON parser.
3. The tracing-OFF hot path stays under the 2% overhead budget: the
   per-request instrumentation cost with a disabled tracer (measured by
   microbenchmark x the per-request call count, plus what the batcher
   lane's step-level sites and the always-on slot counters cost per
   decode step, printed) must be <2% of the measured per-request
   latency. bench.py's serve.obs section carries the complementary
   tracing-ON closed-loop sweep.
4. The memory ledger (obs/memory.py) accounts EVERY warmed executable
   of the engine in (1) plus its runtime operands, its per-head sums are
   internally consistent (total == operands + transient peak), and the
   ledger gauges survive Prometheus exposition.
5. The SLO monitor (obs/slo.py) sheds under a synthetic overload —
   sustained queue breach -> typed OverloadError for new submissions
   while every accepted request completes — recovery un-sheds, and the
   steady state never recompiles. GENREC_CI_SKIP_SLO=1 skips this
   section (same contract as the other GENREC_CI_SKIP_* knobs) for
   callers whose pytest pass already runs the SLO tests directly.

Exit codes: 0 ok, 1 check failed. Stdout is one verdict JSON
(ci_checks.sh convention); human detail goes to stderr.

Usage: python scripts/check_obs.py [--small] [--platform cpu]
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from genrec_tpu.analysis import ir  # noqa: E402


def log(msg: str) -> None:
    print(f"check_obs: {msg}", file=sys.stderr)


def _strict_loads(line: str):
    def _reject(tok):
        raise ValueError(f"non-strict JSON constant {tok!r}")

    return json.loads(line, parse_constant=_reject)


def check_span_tree(spans) -> list:
    """A paged request's span tree is complete in either decode shape:

    - plain:       request -> queue_wait / admission / prefill(|warm_admit)
                   / decode_step+ / finalize
    - speculative: the per-code ``decode_step`` spans are replaced by
                   ``draft`` -> ``tree_verify`` -> ``accept`` per spec
                   iteration (docs/OBSERVABILITY.md)

    Everything must parent onto ONE request root, and the decode phase
    must actually be present (>= 2 plain steps at sem_id_dim=3, or >= 1
    complete draft/verify/accept triple)."""
    names = sorted({s.name for s in spans})
    base = {"request", "queue_wait", "admission", "finalize"}
    missing = base - set(names)
    if missing:
        raise AssertionError(f"span tree incomplete: missing {missing} "
                             f"(got {names})")
    if not ({"prefill", "warm_admit"} & set(names)):
        raise AssertionError(f"span tree has neither prefill nor warm_admit "
                             f"(got {names})")
    root = [s for s in spans if s.name == "request"]
    if len(root) != 1:
        raise AssertionError(f"expected ONE root request span, got {len(root)}")
    for s in spans:
        if s is not root[0] and s.parent_id != root[0].span_id:
            raise AssertionError(f"span {s.name} not parented to the request root")
    n_plain = sum(1 for s in spans if s.name == "decode_step")
    spec_names = {"draft", "tree_verify", "accept"}
    have_spec = spec_names & set(names)
    if have_spec and have_spec != spec_names:
        raise AssertionError(
            f"partial speculative span triple: {sorted(have_spec)}")
    if not have_spec and n_plain < 2:  # sem_id_dim=3, code 0 at prefill
        raise AssertionError(f"expected >=2 decode_step spans, got {n_plain}")
    return names


def check_batcher_lane(lane, stats: dict) -> None:
    """The batcher's own lane holds every phase of admission, prefill and
    decode (flat spans, no root, grouped by `seq`), and the always-on slot
    counters count (tests/test_obs_phases.py holds them equal to the
    spans' attributes)."""
    names = {s.name for s in lane}
    need = {"admit.pop", "prefill.stage", "prefill.launch", "prefill.pull",
            "prefill.retain", "decode.stage", "decode.launch", "decode.pull",
            "decode.sweep"}
    if not need <= names:
        raise AssertionError(f"batcher lane lacks {sorted(need - names)}")
    if any(s.parent_id is not None or "seq" not in s.attrs for s in lane):
        raise AssertionError("batcher lane spans must be flat and carry seq")
    if not 0 < stats["decode_live_slot_steps"] <= stats["decode_slot_steps"]:
        raise AssertionError(
            f"slot counters off: live {stats['decode_live_slot_steps']} of "
            f"{stats['decode_slot_steps']} slot-steps")
    log(f"batcher lane OK: {len(lane)} phase spans; "
        f"{stats['decode_live_slot_steps']}/{stats['decode_slot_steps']} "
        f"live/compiled slot-steps")


def check_serve_trace(tmp: str) -> dict:
    """Paged TIGER engine with tracing on: full span tree + trace schema."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from genrec_tpu.models.tiger import Tiger
    from genrec_tpu.obs import SpanTracer
    from genrec_tpu.serving import (
        BucketLadder, Request, ServingEngine, TigerGenerativeHead,
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
    tracer = SpanTracer()
    eng = ServingEngine(
        [head], params, ladder=BucketLadder((1, 2), (4, 8)), max_batch=2,
        max_wait_ms=1.0, handle_signals=False, tracer=tracer,
    ).start()
    lat_s = []
    try:
        futs = [
            eng.submit(Request(head="tiger",
                               history=rng.integers(0, len(valid), 5)))
            for _ in range(4)
        ]
        resps = [f.result(300) for f in futs]
        lat_s = [r.total_s for r in resps]
        r0 = resps[0]
        if r0.request_id is None:
            raise AssertionError("tracer enabled but request_id is None")
        spans = tracer.spans(r0.request_id)
        names = check_span_tree(spans)
        n_decode = sum(1 for s in spans
                       if s.name in ("decode_step", "tree_verify"))
        log(f"span tree OK: {names}, {n_decode} decode steps")
        check_batcher_lane(tracer.spans("batcher/tiger"), eng.stats())
        memory = check_memory_ledger(eng)
    finally:
        eng.stop()

    path = os.path.join(tmp, "trace.json")
    tracer.dump(path)
    data = json.load(open(path))
    if "traceEvents" not in data or not data["traceEvents"]:
        raise AssertionError("trace dump has no traceEvents")
    for ev in data["traceEvents"]:
        for key in ("name", "ph", "ts", "dur", "pid", "tid"):
            if key not in ev:
                raise AssertionError(f"trace event missing {key!r}: {ev}")
        if ev["ph"] != "X" or not isinstance(ev["ts"], (int, float)):
            raise AssertionError(f"bad trace event {ev}")
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import trace_report

    summary = trace_report.summarize(trace_report.load_trace(path))
    if "decode_step" not in summary["phases"]:
        raise AssertionError("trace_report lost the decode_step phase")
    log(f"trace schema + report OK ({len(data['traceEvents'])} events)")
    return {
        "n_trace_events": len(data["traceEvents"]),
        "p50_request_ms": summary["phases"]["request"]["p50_ms"],
        "mean_latency_s": sum(lat_s) / len(lat_s),
        "memory": memory,
    }


def check_memory_ledger(eng) -> dict:
    """ISSUE-10 acceptance, CI-sized: the ledger holds an entry for
    EVERY warmed executable, every runtime operand class the paged head
    carries is accounted, the per-head sums are consistent, and the
    gauges survive Prometheus exposition."""
    from genrec_tpu.obs import prometheus_text

    st = eng.stats()
    head = st["hbm"]["heads"].get("tiger")
    if head is None:
        raise AssertionError("memory ledger has no entry for the tiger head")
    if head["n_executables"] != st["warmup_compiles"]:
        raise AssertionError(
            f"ledger holds {head['n_executables']} executables but warmup "
            f"compiled {st['warmup_compiles']} — a warmed executable is "
            "missing from the ledger"
        )
    want_ops = {"params", "catalog_operands", "kv_page_pool",
                "paged_slot_state"}
    missing = want_ops - set(head["operands"])
    if missing:
        raise AssertionError(f"ledger missing runtime operands: {missing}")
    if any(v <= 0 for v in head["operands"].values()):
        raise AssertionError(f"zero-byte operand entries: {head['operands']}")
    if head["total_bytes"] != head["operand_bytes"] + head["transient_peak_bytes"]:
        raise AssertionError(
            f"ledger sums inconsistent: total {head['total_bytes']} != "
            f"operands {head['operand_bytes']} + transient peak "
            f"{head['transient_peak_bytes']}"
        )
    if st["hbm"]["total_bytes"] < head["total_bytes"]:
        raise AssertionError("engine total smaller than its one head")
    text = prometheus_text(st)
    for needle in ("genrec_hbm_heads_tiger_total_bytes",
                   "genrec_hbm_heads_tiger_operand_bytes",
                   "genrec_hbm_total_bytes"):
        if needle not in text:
            raise AssertionError(f"ledger gauge {needle} missing from "
                                 "Prometheus exposition")
    log(f"memory ledger OK: {head['n_executables']} executables, "
        f"{head['operand_bytes']} operand bytes, "
        f"total {head['total_bytes']} bytes")
    return {
        "n_executables": head["n_executables"],
        "operand_bytes": head["operand_bytes"],
        "total_bytes": head["total_bytes"],
        "sums_consistent": True,
        "ledger_complete": True,
    }


def check_slo_shed() -> dict:
    """Synthetic overload: an aggressive queue-depth target sheds under
    a submit flood (typed OverloadError), every ACCEPTED request still
    completes, hysteresis un-sheds once the queue drains, and the whole
    episode never recompiles."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from genrec_tpu.models.sasrec import SASRec
    from genrec_tpu.obs import get_flight_recorder
    from genrec_tpu.serving import (
        BucketLadder, OverloadError, Request, RetrievalHead, SLOTarget,
        ServingEngine,
    )

    model = SASRec(num_items=30, max_seq_len=8, embed_dim=16, num_heads=2,
                   num_blocks=1, ffn_dim=32, dropout=0.0)
    params = model.init(jax.random.key(0),
                        jnp.zeros((2, 8), jnp.int32))["params"]
    rng = np.random.default_rng(3)
    eng = ServingEngine(
        [RetrievalHead("sasrec", model, top_k=5)], params,
        ladder=BucketLadder((1, 2), (8,)), max_batch=2, max_wait_ms=1.0,
        handle_signals=False,
        slo_targets=SLOTarget(max_queue_depth=2, window_s=1.0,
                              breach_s=0.0, recover_s=0.05),
        slo_poll_secs=0.005,
    ).start()
    try:
        accepted, shed = [], False
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                accepted.append(eng.submit(
                    Request(head="sasrec", history=rng.integers(1, 31, 5))
                ))
            except OverloadError:
                shed = True
                break
        if not shed:
            raise AssertionError("synthetic overload never shed")
        resps = [f.result(120) for f in accepted]
        if len(resps) != len(accepted):
            raise AssertionError("accepted requests dropped during shed")
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
        if not recovered:
            raise AssertionError("shed never recovered after the queue drained")
        st = eng.stats()
        if st["overload_rejected"] < 1:
            raise AssertionError("no overload rejection counted")
        if st["recompilations"] != 0:
            raise AssertionError(
                f"SLO shedding recompiled: {st['recompilations']}")
        breaches = st["slo"]["heads"]["sasrec"]["breaches"]
        flight = [e for e in get_flight_recorder().events("slo_breach")]
        if not flight:
            raise AssertionError("no slo_breach flight event recorded")
    finally:
        eng.stop()
    log(f"slo OK: shed after {len(accepted)} accepted, all completed, "
        f"recovered; {st['overload_rejected']} overload rejections, "
        f"{breaches} breach(es)")
    return {
        "shed": True,
        "accepted_completed": len(resps),
        "recovered": True,
        "overload_rejected": st["overload_rejected"],
        "breaches": breaches,
        "recompilations": st["recompilations"],
    }


def check_train_goodput(tmp: str) -> dict:
    """Toy packed-loop epoch: goodput buckets sum to wall; metrics.jsonl
    (with a NaN metric logged) stays strictly parseable."""
    import logging

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from genrec_tpu.core.harness import make_train_step
    from genrec_tpu.core.logging import Tracker
    from genrec_tpu.core.profiling import ProfileWindow
    from genrec_tpu.core.state import TrainState
    from genrec_tpu.parallel import get_mesh, replicate
    from genrec_tpu.trainers.packed_loop import PackedTrainLoop

    # Stderr-only logger: stdout must stay ONE verdict JSON for
    # ci_checks.sh (setup_logger would attach a stdout handler).
    train_log = logging.getLogger("genrec_tpu.check_obs")
    train_log.propagate = False
    if not train_log.handlers:
        train_log.addHandler(logging.StreamHandler(sys.stderr))
        train_log.setLevel(logging.INFO)

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
    tracker = Tracker(save_dir=tmp)
    loop = PackedTrainLoop(
        logger=train_log, tracker=tracker, prof=ProfileWindow("", 0),
        mesh=mesh, guard=None, ckpt=None, rows_per_step=8, row_len=1, seed=0,
        pack_sequences=False, train_arrays=arrays, wandb_log_interval=4,
        save_dir_root=tmp,
    )
    res = loop.run_epoch(state, step_fn, epoch=0, global_step=0)
    if res.n_batches != 8:
        raise AssertionError(f"expected 8 batches, ran {res.n_batches}")
    tracker.log({"train/poison": float("nan"), "train/inf": float("inf")})
    tracker.finish()

    lines = open(os.path.join(tmp, "metrics.jsonl")).read().splitlines()
    goodput_lines = []
    for line in lines:
        parsed = _strict_loads(line)  # raises on bare NaN/Infinity
        if "goodput/pct" in parsed:
            goodput_lines.append(parsed)
    if not goodput_lines:
        raise AssertionError("no goodput report in metrics.jsonl")
    g = goodput_lines[-1]
    wall = g["goodput/wall_s"]
    bucket_sum = sum(v for k, v in g.items()
                     if k.startswith("goodput/") and k.endswith("_s")
                     and k != "goodput/wall_s")
    if abs(bucket_sum - wall) > 0.02 * wall + 1e-3:
        raise AssertionError(
            f"goodput buckets sum {bucket_sum:.4f}s != wall {wall:.4f}s")
    log(f"goodput OK: {g['goodput/pct']:.1f}% of {wall:.2f}s, "
        f"{len(lines)} strict-JSON metric lines")
    return {"goodput_pct": g["goodput/pct"], "metric_lines": len(lines)}


def check_disabled_overhead(mean_latency_s: float) -> dict:
    """Tracing-off budget: per-request instrumentation cost (disabled
    tracer) must stay <2% of the measured per-request latency."""
    from genrec_tpu.obs.spans import NULL_TRACER

    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        if NULL_TRACER.enabled:  # the engine's per-site guard
            NULL_TRACER.record_span("x", "t", 0.0, 0.0)
    per_call = (time.perf_counter() - t0) / n
    # Upper bound on tracer touchpoints for one paged request: submit
    # mint + queue/admission/prefill + decode steps + finalize + root +
    # exemplar check, with margin.
    calls_per_request = 32
    # The batcher lane's sites in `_PagedRunner.step` with the tracer off
    # (serving/engine.py): two clock readings more than before (launch
    # returned, sweep begins), the live-slot count and KV-token sum the
    # always-on counters take, the `tracing` check and the batch loop's
    # look at the empty phase buffer. A prefill adds as much again.
    import numpy as np

    seq_lens = np.arange(64, dtype=np.int32)
    active_idx = np.arange(0, 64, 2)
    phases: list = []
    n_steps = 100_000
    t0 = time.perf_counter()
    for _ in range(n_steps):
        time.monotonic()
        time.monotonic()
        live, kv_tokens = len(active_idx), int(seq_lens[active_idx].sum())
        if NULL_TRACER.enabled:
            phases.append((live, kv_tokens))
        if phases:
            phases.clear()
    per_step = (time.perf_counter() - t0) / n_steps
    # A request is charged every step it rides as if it rode alone:
    # sem_id_dim decode steps and one prefill.
    steps_per_request = 4
    cost = per_call * calls_per_request + per_step * steps_per_request
    pct = 100.0 * cost / max(mean_latency_s, 1e-9)
    log(f"disabled-tracer cost: {per_call * 1e9:.0f}ns/site x "
        f"{calls_per_request} sites + {per_step * 1e6:.2f}us/decode step x "
        f"{steps_per_request} steps = {cost * 1e6:.1f}us/request "
        f"({pct:.3f}% of {mean_latency_s * 1e3:.1f}ms mean latency)")
    if pct >= 2.0:
        raise AssertionError(
            f"tracing-off overhead {pct:.2f}% >= 2% budget")
    return {"disabled_ns_per_site": per_call * 1e9,
            "disabled_us_per_decode_step": per_step * 1e6,
            "overhead_pct_of_request": pct}


def main(argv=None) -> int:
    args = ir.check_args(
        argv,
        small_help="CI shapes (this check is already small)",
        note_help="accepted for ci_checks.sh symmetry (no-op)",
    )
    # Env-var pin (not mesh.pin_platform): this check spawns engine and
    # train-loop threads that must all see the platform choice.
    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform

    verdict = {"check": "obs", "ok": False}
    try:
        with tempfile.TemporaryDirectory() as tmp:
            serve = check_serve_trace(tmp)
            train = check_train_goodput(os.path.join(tmp, "train"))
            overhead = check_disabled_overhead(serve["mean_latency_s"])
            # GENREC_CI_SKIP_SLO=1 skips the synthetic-overload section
            # for callers whose pytest pass already runs the SLO tests
            # (tests/test_obs.py) directly — same contract as the
            # GENREC_CI_SKIP_* knobs in ci_checks.sh.
            if os.environ.get("GENREC_CI_SKIP_SLO"):
                slo = {"skipped": True}
                log("slo section skipped (GENREC_CI_SKIP_SLO)")
            else:
                slo = check_slo_shed()
        memory = serve.pop("memory")
        verdict.update(ok=True, serve=serve, train=train, overhead=overhead,
                       memory=memory, slo=slo)
    except AssertionError as e:
        verdict["error"] = str(e)
        log(f"FAILED: {e}")
    ir.emit_verdict(verdict)
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
