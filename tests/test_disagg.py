"""Disaggregated serving (genrec_tpu/disagg/): prefill/decode split with
typed KV-page handoff — the PR-13 tentpole pins.

Acceptance bars, each pinned here:

- disagg == co-located parity for the TIGER and COBRA paged heads under
  mixed warm/cold churn: sem_ids bit-identical, scores <= 1e-5 (the
  repo's paged==dense bar — prefill co-batch shapes differ between the
  two serving paths), and STRICT bit-for-bit when the prefill batch
  shape matches (solo vs solo);
- both transports: in-process zero-copy (shared page bank, 0 transfer
  bytes) and serializing host-roundtrip (pinned wire format, measured
  bytes);
- receipt validation is a typed refusal (`HandoffRefusedError`) on
  params/catalog/head/layout skew — never silent mixing;
- a decode worker killed mid-handoff loses nothing: typed at-most-once
  re-submit through the survivors, flight-recorder narrative, and the
  second loss fails `WorkerLostError`;
- the decode worker's OWN `MemoryLedger` budget refuses at warmup;
- role pools scale independently through the existing fleet.Autoscaler,
  and a whole DisaggFront rides behind fleet.FleetRouter unchanged;
- zero steady-state recompiles and clean pools on BOTH sides after
  drain, throughout.

Engine fixtures keep the compile surface tiny (one history bucket,
max_slots == max_batch) — warmup compiles are the tier-1 wall-clock
hogs."""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from genrec_tpu.disagg import (
    DisaggFront,
    HandoffRefusedError,
    KVHandoff,
    WorkerLostError,
    pack_handoff,
    unpack_handoff,
)
from genrec_tpu.models.cobra import Cobra
from genrec_tpu.models.tiger import Tiger
from genrec_tpu.obs import prometheus_text
from genrec_tpu.obs.flight_recorder import get_flight_recorder
from genrec_tpu.serving import (
    BucketLadder,
    HBMBudgetError,
    OverloadError,
    PagedConfig,
    Request,
    ServingEngine,
)
from genrec_tpu.serving.heads import CobraGenerativeHead, TigerGenerativeHead

K_CB = 8


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(7)
    valid = np.unique(rng.integers(0, K_CB, (20, 3)), axis=0)
    item_text = rng.integers(1, 50, (len(valid), 5)).astype(np.int32)
    return valid, item_text


@pytest.fixture(scope="module")
def tiger_setup(corpus):
    valid, _ = corpus
    model = Tiger(embedding_dim=16, attn_dim=32, dropout=0.0, num_heads=4,
                  n_layers=2, num_item_embeddings=K_CB, num_user_embeddings=20,
                  sem_id_dim=3, max_pos=64)
    params = model.init(
        jax.random.key(0), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2, 6), jnp.int32), jnp.zeros((2, 6), jnp.int32),
        jnp.zeros((2, 3), jnp.int32), jnp.zeros((2, 3), jnp.int32),
        jnp.ones((2, 6), jnp.int32),
    )["params"]
    return model, params


LADDER = ((1, 2), (8,))
CFG = dict(max_slots=2, page_size=8, pages_per_slot=4)


def _tiger_front(model, valid, params, **kw):
    kw.setdefault("ladder", BucketLadder(*LADDER))
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_wait_ms", 1.0)
    kw.setdefault("paged_config", PagedConfig(**CFG))
    kw.setdefault("params_step", 1)
    head = TigerGenerativeHead(model, valid, top_k=4, name="tiger")
    return DisaggFront([head], params, **kw)


def _tiger_engine(model, valid, params):
    head = TigerGenerativeHead(model, valid, top_k=4, name="tiger")
    return ServingEngine(
        [head], params, ladder=BucketLadder(*LADDER), max_batch=2,
        max_wait_ms=1.0, handle_signals=False,
        paged_config=PagedConfig(**CFG), params_step=1,
    )


def _req(rng, valid, n=None):
    n = n if n is not None else int(rng.integers(1, 9))
    return Request(head="tiger", history=rng.integers(0, len(valid), n),
                   user_id=int(rng.integers(0, 20)))


# ---- the wire format (jax-free) ---------------------------------------------


def test_handoff_wire_roundtrip_and_version_refusal():
    init = {"base_pos": np.asarray(12, np.int32),
            "beam": np.arange(8, dtype=np.float32).reshape(2, 4)}
    h = KVHandoff(
        head="tiger", n_tokens=17, bucket=(2, 8),
        layout=(1, 4, 8, "float32"), init=init, params_step=5,
        catalog_version="abc123", prefill_worker_id="tiger:p0", warm=True,
    )
    # Page content in the pool's own shape: (pages, page, heads * head_dim).
    k = (np.arange(3 * 8 * 4 * 8, dtype=np.float32).reshape(3, 8, 4 * 8),)
    v = (np.ones((3, 8, 4 * 8), np.float32),)
    data = pack_handoff(h, k, v)
    assert isinstance(data, bytes) and len(data) > 0
    back, k2, v2 = unpack_handoff(data)
    assert back.head == "tiger" and back.n_tokens == 17
    assert back.bucket == (2, 8) and back.layout == (1, 4, 8, "float32")
    assert back.params_step == 5 and back.catalog_version == "abc123"
    assert back.prefill_worker_id == "tiger:p0" and back.warm
    assert back.trace is None  # untraced requests stay untraced
    np.testing.assert_array_equal(k2[0], k[0])
    np.testing.assert_array_equal(v2[0], v[0])
    np.testing.assert_array_equal(back.init["base_pos"], init["base_pos"])
    np.testing.assert_array_equal(back.init["beam"], init["beam"])
    # v2: the header carries the request lineage (TraceContext) — the
    # cross-host decode side re-attaches spans to the SAME trace.
    from genrec_tpu.obs import TraceContext

    ctx = TraceContext("req-41", 77, "fleet_router")
    h.trace = ctx
    traced, _k3, _v3 = unpack_handoff(pack_handoff(h, k, v))
    assert traced.trace == ctx
    # Version skew must be REFUSED typed, not misread — a FUTURE layout,
    # the pre-lineage v1 layout, and v3, whose page arrays were 4-D.
    import io
    import json

    for bad_version in (99, 1, 3):
        bad_header = json.dumps({"wire_version": bad_version}).encode()
        buf = io.BytesIO()
        np.savez(buf, __header__=np.frombuffer(bad_header, np.uint8))
        with pytest.raises(HandoffRefusedError, match="wire version"):
            unpack_handoff(buf.getvalue())


# ---- parity: disagg == co-located, mixed warm/cold churn --------------------


@pytest.mark.serving_smoke
def test_tiger_disagg_parity_mixed_churn_inprocess(tiger_setup, corpus, rng):
    """1-prefill/2-decode TIGER front on the zero-copy shared-bank
    transport: mixed replays (warm handoffs off the prefill worker's
    prefix cache) and fresh cold traffic, every answer matching the
    co-located paged engine, full worker provenance, zero steady-state
    recompiles, and clean pools after drain."""
    model, params = tiger_setup
    valid, _ = corpus
    front = _tiger_front(model, valid, params, n_prefill=1, n_decode=2,
                         transport="inprocess").start()
    eng = _tiger_engine(model, valid, params).start()
    try:
        fixed = [_req(rng, valid) for _ in range(3)]
        # Even slots cycle the fixed requests twice over (first pass
        # cold, second pass warm replays); odd slots are fresh cold
        # traffic racing them through the same slots.
        churn = [fixed[(i // 2) % 3] if i % 2 == 0 else _req(rng, valid)
                 for i in range(12)]
        futs = [front.submit(r) for r in churn]
        resps = [f.result(120) for f in futs]
        for r, resp in zip(churn, resps):
            ref = eng.serve(r, timeout=120)
            # The repo's paged==dense bar: items/sem_ids bit-identical,
            # scores <= 1e-5 (prefill co-batch shapes differ between a
            # churned front and a solo engine serve).
            np.testing.assert_array_equal(resp.sem_ids, ref.sem_ids)
            np.testing.assert_array_equal(resp.items, ref.items)
            np.testing.assert_allclose(resp.scores, ref.scores, atol=1e-5)
            # Provenance: disagg stamps both worker ids; the co-located
            # engine stamps None at both finalize sites.
            assert resp.prefill_worker_id == "tiger:p0"
            assert resp.decode_worker_id in ("tiger:d0", "tiger:d1")
            assert resp.replica_id is None and resp.params_step == 1
            assert ref.prefill_worker_id is None
            assert ref.decode_worker_id is None
        # Solo-vs-solo: same prefill batch shape on both sides -> the
        # handoff pipeline is STRICTLY bit-identical, scores included.
        solo = _req(rng, valid, n=7)
        a = front.serve(solo, timeout=120)
        b = eng.serve(solo, timeout=120)
        np.testing.assert_array_equal(a.sem_ids, b.sem_ids)
        np.testing.assert_array_equal(a.scores, b.scores)
        st = front.stats()
        assert st["recompilations"] == 0
        d = st["disagg"]
        assert d["transport"] == "inprocess"
        assert d["handoffs_sent"] == d["handoffs_admitted"] == 13
        assert d["handoffs_refused"] == 0
        assert d["transfer_bytes"] == 0  # zero-copy: pages move by ref
        assert st["prefix_cache"]["tiger"]["hits"] >= 3  # replays warm
        assert d["transfer_ms"]["count"] == 13
    finally:
        final = front.stop()
        eng.stop()
    # Drain released everything on both sides: the shared bank accounts
    # clean (prefix retention cleared) and every decode slot is free.
    pool = final["kv_pool"]["tiger"]
    assert pool["pages_in_use"] == 0 and pool["slots_active"] == 0
    assert final["prefix_cache"]["tiger"]["entries"] == 0


@pytest.mark.serving_smoke
def test_cobra_disagg_parity_serializing_wire(corpus, rng):
    """COBRA through the host-roundtrip transport: every handoff's KV
    and beam state cross the pinned wire format (separate prefill and
    decode pools — transfer bytes measured), answers match the
    co-located engine, warm replays land off the prefix cache."""
    valid, item_text = corpus
    # One decoder layer: the wire carries per-layer KV either way, and
    # a single layer keeps the two warmups (front + reference engine)
    # inside the tier-1 wall-time budget.
    model = Cobra(encoder_n_layers=1, encoder_hidden_dim=16,
                  encoder_num_heads=2, encoder_vocab_size=50,
                  id_vocab_size=K_CB, n_codebooks=3, d_model=16, max_len=64,
                  temperature=0.2, decoder_n_layers=1, decoder_num_heads=2,
                  decoder_dropout=0.0)
    params = model.init(
        jax.random.key(0), jnp.zeros((2, 12), jnp.int32),
        jnp.ones((2, 4, 5), jnp.int32),
    )["params"]

    def mkhead():
        return CobraGenerativeHead(model, valid, item_text_tokens=item_text,
                                   top_k=4, name="cobra")

    cfg = PagedConfig(max_slots=2, page_size=8, pages_per_slot=4)
    front = DisaggFront(
        [mkhead()], params, ladder=BucketLadder((1, 2), (8,)), max_batch=2,
        max_wait_ms=1.0, n_prefill=1, n_decode=1, transport="serializing",
        paged_config=cfg, params_step=1,
    ).start()
    eng = ServingEngine(
        [mkhead()], params, ladder=BucketLadder((1, 2), (8,)), max_batch=2,
        max_wait_ms=1.0, handle_signals=False, paged_config=cfg,
        params_step=1,
    ).start()
    try:
        fixed = Request(head="cobra", history=np.arange(5) % len(valid))
        churn = [fixed if i % 2 == 0 else
                 Request(head="cobra",
                         history=rng.integers(0, len(valid),
                                              int(rng.integers(1, 9))))
                 for i in range(6)]
        futs = [front.submit(r) for r in churn]
        resps = [f.result(300) for f in futs]
        for r, resp in zip(churn, resps):
            ref = eng.serve(r, timeout=300)
            np.testing.assert_array_equal(resp.sem_ids, ref.sem_ids)
            np.testing.assert_allclose(resp.scores, ref.scores, atol=1e-5)
            assert resp.prefill_worker_id == "cobra:p0"
            assert resp.decode_worker_id == "cobra:d0"
        st = front.stats()
        assert st["recompilations"] == 0
        d = st["disagg"]
        assert d["transport"] == "serializing"
        assert d["handoffs_admitted"] == 6 and d["handoffs_refused"] == 0
        assert d["transfer_bytes"] > 0  # the wire genuinely carried KV
        assert st["prefix_cache"]["cobra"]["hits"] >= 2
    finally:
        final = front.stop()
        eng.stop()
    # BOTH pools clean: prefill staging pool + decode worker pool.
    pool = final["kv_pool"]["cobra"]
    assert pool["pages_in_use"] == 0 and pool["slots_active"] == 0


# ---- typed refusal on provenance skew ---------------------------------------


@pytest.mark.serving_smoke
def test_spec_disagg_parity_and_request_lineage(tiger_setup, corpus, rng):
    """The disagg decode pool speculates (`DisaggFront(spec_decode=)`):
    answers stay pinned to a PLAIN front on the same solo sequence
    (sem_ids/items bit-identical, scores <= 1e-5 — the repo's
    spec==plain bar) at strictly fewer target invocations, and with a
    tracer attached every response's spans form ONE rooted tree crossing
    front / prefill worker / decode worker, the spec
    draft->tree_verify->accept triple parented under the slot-residency
    umbrella. Pools AND the scratch reservation account clean after
    drain."""
    from genrec_tpu.obs import SpanTracer

    model, params = tiger_setup
    valid, _ = corpus
    reqs = [_req(rng, valid) for _ in range(6)]
    tracer = SpanTracer(capacity=16384)
    front = _tiger_front(model, valid, params, spec_decode=True,
                         spec_fanout=8, tracer=tracer).start()
    try:
        spec_resps = [front.serve(r, 120) for r in reqs]
    finally:
        spec_stats = front.stop()
    plain = _tiger_front(model, valid, params).start()
    try:
        plain_resps = [plain.serve(r, 120) for r in reqs]
    finally:
        plain_stats = plain.stop()

    for a, b in zip(spec_resps, plain_resps):
        np.testing.assert_array_equal(a.sem_ids, b.sem_ids)
        np.testing.assert_array_equal(a.items, b.items)
        np.testing.assert_allclose(a.scores, b.scores, atol=1e-5, rtol=0)
    assert spec_stats["recompilations"] == 0
    assert plain_stats["recompilations"] == 0
    assert spec_stats["decode_steps"] < plain_stats["decode_steps"]
    spec_sec = spec_stats["spec"]["tiger"]
    assert spec_sec["codes_per_invocation"] > 1.0
    pool = spec_stats["kv_pool"]["tiger"]
    assert pool["pages_in_use"] == 0 and pool["slots_active"] == 0
    # Scratch reservation released at drain (per decode worker).
    roles = spec_stats["disagg"]["roles"]["tiger"]["decode"]["per_worker"]
    assert all(w["scratch_pages"] == 0 for w in roles.values())
    assert spec_stats["tracing"]["spans_recorded"] > 0

    for r in spec_resps:
        assert r.request_id is not None
        spans = tracer.spans(r.request_id)
        ids = {s.span_id for s in spans}
        roots = [s for s in spans
                 if s.name == "request"
                 and (s.parent_id is None or s.parent_id not in ids)]
        assert len(roots) == 1
        assert roots[0].attrs["component"] == "disagg_front"
        assert roots[0].attrs["origin"] == "disagg_front"
        comps = {s.attrs.get("component") for s in spans} - {None}
        assert {"disagg_front", "prefill_worker", "decode_worker"} <= comps
        names = {s.name for s in spans}
        assert {"queue_wait", "handoff_wire", "decode_slot_wait",
                "slot_residency", "draft", "tree_verify", "accept",
                "finalize"} <= names
        assert "decode_step" not in names  # spec replaces the plain step
        sid = [s for s in spans if s.name == "slot_residency"][0].span_id
        assert all(s.parent_id == sid for s in spans
                   if s.name in ("draft", "tree_verify", "accept",
                                 "finalize"))


@pytest.mark.serving_smoke
def test_handoff_refused_on_version_skew_never_silently_mixed(
        tiger_setup, corpus, rng):
    """A decode worker serving params step N refuses a handoff prefilled
    at step M (same for catalog skew): the request fails TYPED, the
    refusal is counted and narrated, and the front keeps serving."""
    model, params = tiger_setup
    valid, _ = corpus
    fr = get_flight_recorder()
    front = _tiger_front(model, valid, params, n_prefill=1, n_decode=1,
                         transport="inprocess").start(run_loop=False)
    try:
        dw = front._groups["tiger"].decode[0]
        # Unit surface: every skew dimension is a typed refusal.
        from genrec_tpu.disagg.handoff import layout_of

        base = dict(head="tiger", n_tokens=16, bucket=(1, 8),
                    layout=layout_of(dw.head), init=None,
                    params_step=1,
                    catalog_version=dw.head.catalog_version,
                    prefill_worker_id="tiger:p0")
        for skew, match in (
            ({"params_step": 2}, "params step"),
            ({"catalog_version": "deadbeef"}, "catalog"),
            ({"head": "cobra"}, "routed"),
            ({"layout": (9, 9, 9, "float64")}, "layout"),
        ):
            with pytest.raises(HandoffRefusedError, match=match):
                dw.validate(KVHandoff(**{**base, **skew}))
        # End to end: skew the worker's own step -> the submitted
        # request fails typed through the pipeline, counted + narrated.
        refused_before = len(fr.events("handoff_refused"))
        dw.params_step = 2
        fut = front.submit(_req(rng, valid))
        for _ in range(200):
            front.pump_once()
            if fut.done():
                break
            time.sleep(0.002)  # let the coalescing deadline expire
        with pytest.raises(HandoffRefusedError, match="params step"):
            fut.result(1)
        st = front.stats()
        assert st["disagg"]["handoffs_refused"] == 1
        assert len(fr.events("handoff_refused")) == refused_before + 1
        # The front survives: fix the skew, serve normally.
        dw.params_step = 1
        fut2 = front.submit(_req(rng, valid))
        for _ in range(200):
            front.pump_once()
            if fut2.done():
                break
            time.sleep(0.002)
        assert fut2.result(1).decode_worker_id == "tiger:d0"
    finally:
        final = front.stop()
    pool = final["kv_pool"]["tiger"]
    assert pool["pages_in_use"] == 0 and pool["slots_active"] == 0


# ---- decode-worker death: typed at-most-once re-submit ----------------------


@pytest.mark.serving_smoke
def test_kill_decode_worker_mid_handoff_loses_nothing(
        tiger_setup, corpus, rng):
    """SIGKILL a decode worker while it holds admitted handoffs
    mid-decode: every stranded flight is re-submitted (typed, at most
    once) back through the prefill path onto the survivor — nothing is
    lost, the flight recorder narrates, pools stay clean. Then the
    at-most-once bound: flights that lose their SECOND worker fail
    `WorkerLostError`, never hang."""
    model, params = tiger_setup
    valid, _ = corpus
    fr = get_flight_recorder()
    # max_slots=1 per decode worker: placement is deterministic (one
    # flight per worker), and the kill is guaranteed mid-decode because
    # TIGER needs sem_id_dim=3 steps per request.
    front = _tiger_front(
        model, valid, params, n_prefill=1, n_decode=2,
        transport="inprocess",
        paged_config=PagedConfig(max_slots=1, page_size=8, pages_per_slot=4),
    ).start(run_loop=False)
    try:
        futs = [front.submit(_req(rng, valid)) for _ in range(2)]
        front.pump_once()  # prefill both, admit one per worker, 1 step
        assert all(not f.done() for f in futs)  # mid-decode on both
        deaths_before = len(fr.events("disagg_worker_dead"))
        stranded = front.kill_decode_worker("tiger:d1")
        assert stranded == 1
        # Pump to completion: the survivor decodes its own flight AND
        # the re-submitted one (re-prefilled warm off the prefix cache).
        for _ in range(300):
            front.pump_once()
            if all(f.done() for f in futs):
                break
        resps = [f.result(1) for f in futs]
        assert all(r.decode_worker_id == "tiger:d0" for r in resps)
        st = front.stats()
        assert st["disagg"]["handoffs_resubmitted"] == 1
        assert st["disagg"]["decode_worker_deaths"] == 1
        assert st["recompilations"] == 0
        deaths = fr.events("disagg_worker_dead")[deaths_before:]
        assert any(e["worker"] == "tiger:d1" and e["stranded"] == 1
                   for e in deaths)
        assert fr.events("handoff_resubmitted")
        # -- at-most-once: lose the survivor too ---------------------------
        futs2 = [front.submit(_req(rng, valid)) for _ in range(2)]
        for _ in range(50):
            front.pump_once()
            dw = front._groups["tiger"].decode[0]
            if dw.pool.active_slot_count == 1:
                break
        assert front.kill_decode_worker("tiger:d0") >= 1
        # No decode capacity survives: every in-flight future fails
        # TYPED (first loss with zero survivors, or second loss after
        # the spent retry) — never silently hangs.
        for _ in range(100):
            front.pump_once()
            if all(f.done() for f in futs2):
                break
        for f in futs2:
            with pytest.raises(WorkerLostError):
                f.result(1)
        # Zero live PREFILL workers: submit raises the RECOVERABLE
        # error (FleetRouter fails over on OverloadError; a leaked
        # WorkerLostError would propagate through the router as a
        # caller bug and skip the surviving replicas).
        front.kill_prefill_worker("tiger:p0")
        with pytest.raises(OverloadError):
            front.submit(_req(rng, valid))
    finally:
        final = front.stop()
    pool = final["kv_pool"]["tiger"]
    assert pool["pages_in_use"] == 0 and pool["slots_active"] == 0


# ---- per-worker HBM budget --------------------------------------------------


@pytest.mark.serving_smoke
def test_decode_worker_hbm_budget_refuses_at_warmup(
        tiger_setup, corpus, rng):
    """The decode worker owns its OWN MemoryLedger budget (PR 10's
    per-worker next step): an impossible decode-side budget refuses at
    warmup with the typed error; a sane one starts, and the prefill
    worker's retained prefix pages stay visible as ITS reclaimable
    component."""
    model, params = tiger_setup
    valid, _ = corpus
    with pytest.raises(HBMBudgetError, match="decode worker"):
        _tiger_front(model, valid, params,
                     decode_hbm_budget_bytes=1024).start(run_loop=False)
    with pytest.raises(HBMBudgetError, match="prefill worker"):
        _tiger_front(model, valid, params,
                     prefill_hbm_budget_bytes=1024).start(run_loop=False)
    front = _tiger_front(
        model, valid, params,
        decode_hbm_budget_bytes=1 << 30,
        prefill_hbm_budget_bytes=1 << 30,
    ).start(run_loop=False)
    try:
        fut = front.submit(_req(rng, valid, n=8))
        for _ in range(200):
            front.pump_once()
            if fut.done():
                break
            # A lone request (1 < max_batch) waits out the 1 ms coalescing
            # deadline; 200 idle pumps of a warm process take less.
            time.sleep(0.002)
        fut.result(1)
        st = front.stats()
        roles = st["disagg"]["roles"]["tiger"]
        pw = roles["prefill"]["per_worker"]["tiger:p0"]
        # Retained prefix pages ride the PREFILL worker's ledger as its
        # reclaimable component (budget math sees cached bytes as
        # releasable), and the decode worker's model carries its own
        # pool + slot state + executables under its own budget.
        assert pw["hbm"]["heads"]["tiger:p0"]["reclaimable"][
            "prefix_cache_pages"] > 0
        dw = roles["decode"]["per_worker"]["tiger:d0"]
        assert dw["hbm"]["total_bytes"] > 0
        assert dw["hbm"]["over_budget"] is False
    finally:
        front.stop()


# ---- role pools scale independently through the fleet Autoscaler ------------


def test_role_pools_autoscale_with_fleet_autoscaler(tiger_setup, corpus, rng):
    """The decode pool saturates on slot occupancy; the existing
    fleet.Autoscaler drives `role_pool("tiger", "decode")` unchanged:
    sustained all-worker shed scales OUT one decode worker (a measured
    warmup), sustained headroom drains one back IN. Prefill pool
    untouched — the roles scale independently."""
    from genrec_tpu.fleet import Autoscaler, AutoscalerConfig

    model, params = tiger_setup
    valid, _ = corpus
    front = _tiger_front(
        model, valid, params, n_prefill=1, n_decode=1,
        transport="inprocess",
        paged_config=PagedConfig(max_slots=1, page_size=8, pages_per_slot=4),
    ).start(run_loop=False)
    try:
        pool = front.role_pool("tiger", "decode")
        asc = Autoscaler(pool, AutoscalerConfig(
            min_replicas=1, max_replicas=2, scale_out_after_s=1.0,
            scale_in_after_s=1.0, scale_in_headroom=0.5, cooldown_s=0.5,
        ))
        # Saturate: 1 slot total, several waiting handoffs.
        futs = [front.submit(_req(rng, valid)) for _ in range(4)]
        for _ in range(10):
            front.pump_once()
            sig = pool.scale_signal()
            if all(r["shedding"] for r in sig["replicas"].values()) \
                    and sig["alive"] == 1:
                break
        assert all(r["shedding"] for r in pool.scale_signal()
                   ["replicas"].values())
        t = 100.0
        assert asc.tick(t) is None          # breach clock starts
        assert asc.tick(t + 1.1) == "scale_out"
        assert len(front._groups["tiger"].decode) == 2
        assert front.stats()["disagg"]["roles"]["tiger"]["decode"][
            "workers"] == 2
        # The scaled-out worker participates: drain the backlog.
        for _ in range(400):
            front.pump_once()
            if all(f.done() for f in futs):
                break
        assert all(f.result(1).head == "tiger" for f in futs)
        # Idle now: sustained headroom scales back IN (graceful drain).
        t2 = t + 10.0
        assert asc.tick(t2) is None         # idle clock starts
        assert asc.tick(t2 + 1.1) == "scale_in"
        assert len(front._groups["tiger"].decode) == 1
        assert front.stats()["recompilations"] == 0
    finally:
        front.stop()


# ---- a DisaggFront is a fleet replica ---------------------------------------


def test_fleet_router_routes_over_disagg_fronts(tiger_setup, corpus, rng):
    """The front duck-types the engine surface, so FleetRouter fronts N
    disaggregated replicas exactly as it fronts N engines — replica
    provenance stamped beside the worker ids."""
    from genrec_tpu.fleet import FleetRouter

    model, params = tiger_setup
    valid, _ = corpus

    def make_replica(rid):
        return _tiger_front(model, valid, params, n_prefill=1, n_decode=1,
                            transport="inprocess", replica_id=rid)

    router = FleetRouter(make_replica, initial_replicas=2).start()
    try:
        futs = [router.submit(_req(rng, valid)) for _ in range(6)]
        resps = [f.result(120) for f in futs]
        assert all(r.replica_id in ("r0", "r1") for r in resps)
        assert all(r.prefill_worker_id == "tiger:p0" for r in resps)
        assert all(r.decode_worker_id == "tiger:d0" for r in resps)
        st = router.stats()
        assert st["routed"] == 6 and st["completed"] == 6
        assert st["recompilations"] == 0
    finally:
        router.stop()


# ---- observability typing (jax-free) ----------------------------------------


def test_disagg_counters_typed_in_prometheus():
    snap = {
        "disagg": {
            "transport": "inprocess",
            "handoffs_sent": 11, "handoffs_admitted": 11,
            "handoffs_refused": 1, "handoffs_resubmitted": 2,
            "transfer_bytes": 43684, "decode_worker_deaths": 1,
            "prefill_worker_deaths": 0, "pending_handoffs": 0,
            "transfer_ms": {"p50": 0.4, "p99": 1.2, "count": 11},
            "roles": {
                "tiger": {
                    "prefill": {"workers": 1, "queue_depth": 0,
                                "headroom": 1.0, "deferred": 0},
                    "decode": {"workers": 2, "slots_active": 1,
                               "slots_total": 4, "headroom": 0.75,
                               "pending_handoffs": 0},
                },
            },
        },
    }
    text = prometheus_text(snap)
    kinds = {}
    for line in text.splitlines():
        if line.startswith("# TYPE"):
            _, _, name, kind = line.split()
            kinds[name] = kind
    assert kinds["genrec_disagg_handoffs_sent"] == "counter"
    assert kinds["genrec_disagg_handoffs_admitted"] == "counter"
    assert kinds["genrec_disagg_handoffs_refused"] == "counter"
    assert kinds["genrec_disagg_handoffs_resubmitted"] == "counter"
    assert kinds["genrec_disagg_transfer_bytes"] == "counter"
    assert kinds["genrec_disagg_decode_worker_deaths"] == "counter"
    assert kinds["genrec_disagg_pending_handoffs"] == "gauge"
    assert kinds["genrec_disagg_transfer_ms_p50"] == "gauge"
    assert kinds["genrec_disagg_roles_tiger_prefill_headroom"] == "gauge"
    assert kinds["genrec_disagg_roles_tiger_decode_slots_active"] == "gauge"
    assert kinds["genrec_disagg_roles_tiger_prefill_deferred"] == "counter"
