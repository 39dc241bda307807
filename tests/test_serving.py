"""Online serving engine: micro-batching, bucket ladder, hot reload, drain.

The `serving_smoke` marker is the subset scripts/ci_checks.sh runs as the
CPU serving smoke; the heavy all-four-heads test is additionally `slow`
(ci_checks selects by serving_smoke, the tier-1 fast pass skips it).
"""

import os
import signal
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from genrec_tpu.core import chaos
from genrec_tpu.core.checkpoint import CheckpointManager
from genrec_tpu.core.logging import Tracker, log_serving_stats, setup_logger
from genrec_tpu.models.cobra import Cobra
from genrec_tpu.models.hstu import HSTU
from genrec_tpu.models.sasrec import SASRec
from genrec_tpu.models.tiger import Tiger
from genrec_tpu.parallel.shardings import item_topk
from genrec_tpu.serving import (
    BucketLadder,
    CobraGenerativeHead,
    DrainingError,
    LatencyHistogram,
    PagedConfig,
    Request,
    RetrievalHead,
    ServingEngine,
    TigerGenerativeHead,
    UnknownHeadError,
    default_ladder,
)

K_CB = 8
N_ITEMS = 30  # retrieval vocab (ids 1..30; 0 = pad)


# ---- units ------------------------------------------------------------------


def test_bucket_ladder_rounding():
    lad = BucketLadder((1, 4, 16), (8, 32))
    assert lad.batch_bucket(1) == 1 and lad.batch_bucket(2) == 4
    assert lad.batch_bucket(16) == 16
    with pytest.raises(ValueError):
        lad.batch_bucket(17)
    assert lad.history_bucket(3) == 8 and lad.history_bucket(9) == 32
    assert lad.history_bucket(100) == 32  # truncate-to-newest contract
    assert len(list(lad.combos())) == 6
    with pytest.raises(ValueError):
        BucketLadder((4, 2), (8,))  # not increasing


def test_default_ladder_caps():
    lad = default_ladder(max_batch=16, max_history=64)
    assert lad.max_batch == 16
    assert lad.history_buckets[-1] == 64


def test_latency_histogram_percentiles():
    h = LatencyHistogram()
    for ms in [1, 1, 1, 1, 1, 1, 1, 1, 1, 100]:
        h.record(ms / 1e3)
    s = h.summary()
    assert s["count"] == 10
    assert s["p50"] <= s["p95"] <= s["p99"] <= s["max"] * 1.26
    assert s["p50"] < 2.0  # ~1ms bucket edge
    assert s["p99"] > 50.0  # the 100ms outlier
    assert LatencyHistogram().summary()["p99"] == 0.0


def test_item_topk_sharded_matches_plain(rng):
    V, d, k = 24, 8, 5
    h = jnp.asarray(rng.normal(size=(3, d)), jnp.float32)
    emb = jnp.asarray(rng.normal(size=(V, d)), jnp.float32)
    s_plain, i_plain = item_topk(h, emb, k, mesh=None)
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("model",))
    s_sh, i_sh = item_topk(h, emb, k, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(i_plain), np.asarray(i_sh))
    np.testing.assert_allclose(np.asarray(s_plain), np.asarray(s_sh), atol=1e-6)
    assert not (np.asarray(i_plain) == 0).any()  # pad row excluded


def test_log_serving_stats_smoke(tmp_path):
    logger = setup_logger()
    tracker = Tracker(save_dir=str(tmp_path))
    stats = {
        "qps": 12.5, "completed": 10, "rejected": 0, "recompilations": 0,
        "params_step": 3, "total_ms": {"p50": 5.0, "p95": 9.0, "p99": 12.0},
        "bucket_hits": {"tiger/B1/L8": 10},
        "admits": 10, "evictions": 10, "oom_deferred_admits": 1,
        "kv_pool": {"tiger": {"pages_in_use": 3, "pages_free": 5,
                              "slots_active": 2, "slots_total": 8,
                              "kv_tokens_resident": 40}},
        "prefix_cache": {"tiger": {"lookups": 10, "hits": 6,
                                   "partial_hits": 0, "misses": 4,
                                   "warm_tokens": 96, "insertions": 4,
                                   "evictions": 1, "invalidations": 0,
                                   "entries": 3, "retained_pages": 5,
                                   "retained_bytes": 10240}},
    }
    log_serving_stats(logger, tracker, stats)
    tracker.finish()
    text = (tmp_path / "metrics.jsonl").read_text()
    assert "serve/qps" in text and "serve/total_ms/p95" in text
    # Pool + prefix-cache gauges flatten into the tracker namespace too.
    assert "serve/kv_pool/tiger/pages_in_use" in text
    assert "serve/prefix_cache/tiger/hits" in text
    assert "serve/prefix_cache/tiger/retained_pages" in text


# ---- tiny model zoo ---------------------------------------------------------


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(7)
    valid = np.unique(rng.integers(0, K_CB, (20, 3)), axis=0)
    item_text = rng.integers(1, 50, (len(valid), 5)).astype(np.int32)
    return valid, item_text


@pytest.fixture(scope="module")
def sasrec_setup():
    model = SASRec(num_items=N_ITEMS, max_seq_len=8, embed_dim=16, num_heads=2,
                   num_blocks=1, ffn_dim=32, dropout=0.0)
    params = model.init(jax.random.key(0), jnp.zeros((2, 8), jnp.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def zoo(corpus, sasrec_setup):
    valid, item_text = corpus
    tiger = Tiger(embedding_dim=16, attn_dim=32, dropout=0.0, num_heads=4,
                  n_layers=2, num_item_embeddings=K_CB, num_user_embeddings=20,
                  sem_id_dim=3, max_pos=64)
    tparams = tiger.init(
        jax.random.key(0), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2, 6), jnp.int32), jnp.zeros((2, 6), jnp.int32),
        jnp.zeros((2, 3), jnp.int32), jnp.zeros((2, 3), jnp.int32),
        jnp.ones((2, 6), jnp.int32),
    )["params"]
    cobra = Cobra(encoder_n_layers=1, encoder_hidden_dim=16, encoder_num_heads=2,
                  encoder_vocab_size=50, id_vocab_size=K_CB, n_codebooks=3,
                  d_model=16, max_len=64, temperature=0.2, decoder_n_layers=2,
                  decoder_num_heads=2, decoder_dropout=0.0)
    cparams = cobra.init(
        jax.random.key(0), jnp.zeros((2, 12), jnp.int32),
        jnp.ones((2, 4, 5), jnp.int32),
    )["params"]
    hstu = HSTU(num_items=N_ITEMS, max_seq_len=8, embed_dim=16, num_heads=2,
                num_blocks=1, dropout=0.0)
    hparams = hstu.init(jax.random.key(0), jnp.zeros((2, 8), jnp.int32))["params"]
    sas, sparams = sasrec_setup
    models = dict(tiger=tiger, cobra=cobra, sasrec=sas, hstu=hstu)
    params = dict(tiger=tparams, cobra=cparams, sasrec=sparams, hstu=hparams)
    return models, params


def _req(head, rng, n, corpus_size):
    if head in ("tiger", "cobra"):
        hist = rng.integers(0, corpus_size, n)
    else:
        hist = rng.integers(1, N_ITEMS + 1, n)
    return Request(head=head, history=hist, user_id=int(rng.integers(0, 20)))


# ---- the four-head smoke + SIGTERM drain (ci_checks serving smoke) ----------


@pytest.mark.slow
@pytest.mark.serving_smoke
def test_engine_four_heads_smoke_and_drain(zoo, corpus, rng):
    models, params = zoo
    valid, item_text = corpus
    heads = [
        TigerGenerativeHead(models["tiger"], valid, top_k=4, name="tiger"),
        CobraGenerativeHead(models["cobra"], valid, item_text_tokens=item_text,
                            top_k=4, name="cobra"),
        RetrievalHead("sasrec", models["sasrec"], top_k=5),
        RetrievalHead("hstu", models["hstu"], top_k=5),
    ]
    prev_term = signal.getsignal(signal.SIGTERM)
    # Small-ladder discipline: one history bucket and max_slots ==
    # max_batch (shared by both paged heads: TIGER needs 25 KV tokens at
    # L=8, COBRA 32 — both fit 4 pages of 8) keeps warmup at one decode
    # shape per head instead of the default 4x ladder.
    eng = ServingEngine(
        heads, params, ladder=BucketLadder((1, 2), (8,)), max_batch=2,
        max_wait_ms=2.0,
        paged_config=PagedConfig(max_slots=2, page_size=8, pages_per_slot=4),
    ).start()
    try:
        futs = [
            eng.submit(_req(h, rng, int(rng.integers(1, 9)), len(valid)))
            for h in ("tiger", "cobra", "sasrec", "hstu")
            for _ in range(4)
        ]
        resps = [f.result(120) for f in futs]
        for r in resps:
            assert len(r.items) in (4, 5)
            assert r.total_s >= r.compute_s >= 0
            if r.head in ("tiger", "cobra"):
                # Constrained decode: every answer is a REAL corpus item.
                assert (r.items >= 0).all() and (r.items < len(valid)).all()
                assert r.sem_ids.shape[-1] == 3
            else:
                assert (r.items >= 1).all() and (r.items <= N_ITEMS).all()
        # Steady state after warmup: zero new XLA compilations.
        assert eng.metrics.recompilations == 0
        st = eng.stats()
        assert st["completed"] == len(futs)
        assert st["total_ms"]["p50"] > 0
        assert len(st["bucket_hits"]) >= 4  # every head hit a bucket

        # SIGTERM -> graceful drain: typed rejection, clean join, and the
        # one-shot guard restored the previous handler (second signal
        # escalates).
        os.kill(os.getpid(), signal.SIGTERM)
        assert eng.join(60), "engine did not drain after SIGTERM"
        with pytest.raises(DrainingError):
            eng.submit(_req("tiger", rng, 3, len(valid)))
        assert signal.getsignal(signal.SIGTERM) == prev_term
    finally:
        eng.stop()
    assert signal.getsignal(signal.SIGTERM) == prev_term


# ---- graceful-drain chaos: SIGTERM mid-load ---------------------------------


@pytest.mark.serving_smoke
def test_drain_chaos_sigterm_midload(sasrec_setup, rng):
    """core/chaos delivers a real SIGTERM after the 2nd micro-batch while
    requests are still queued: every already-accepted request must
    complete, late submissions get the typed error, and the one-shot
    guard restores the previous handlers (escalation contract)."""
    model, params = sasrec_setup
    prev_term = signal.getsignal(signal.SIGTERM)
    prev_int = signal.getsignal(signal.SIGINT)
    eng = ServingEngine(
        [RetrievalHead("sasrec", model, top_k=5)], params,
        ladder=BucketLadder((1, 4), (8,)), max_batch=4, max_wait_ms=1.0,
    )
    try:
        with chaos.inject(chaos.ChaosPlan(kill_at_step=2)):
            # Enqueue BEFORE the batcher starts: all 12 are accepted, and
            # the chaos SIGTERM (after micro-batch 2 of 3) is guaranteed
            # to land mid-load with a batch still queued — no race between
            # this thread's submits and the drain flip.
            futs = [
                eng.submit(_req("sasrec", rng, int(rng.integers(1, 9)), 0))
                for _ in range(12)
            ]
            eng.start()
            resps = [f.result(60) for f in futs]
        assert len(resps) == 12  # nothing dropped
        assert eng.join(30), "engine did not finish draining"
        assert eng.draining
        with pytest.raises(DrainingError):
            eng.submit(_req("sasrec", rng, 3, 0))
        assert eng.stats()["rejected"] == 1
        # One-shot escalation: handlers are back to the pre-engine ones.
        assert signal.getsignal(signal.SIGTERM) == prev_term
        assert signal.getsignal(signal.SIGINT) == prev_int
        assert eng._guard._prev == {}
    finally:
        eng.stop()


# ---- paged decode: slot-level continuous batching ---------------------------


@pytest.mark.serving_smoke
def test_paged_continuous_batching_churn_under_pool_pressure(zoo, corpus, rng):
    """TIGER through the paged decode path with a pool SMALLER than the
    offered load: requests churn through slots (admit-on-free,
    evict-on-finish), over-budget admissions defer (never drop, never
    over-allocate), every answer is a real corpus item matching the
    dense path bit-for-bit, and the steady state never recompiles."""
    models, params = zoo
    valid, _ = corpus
    head = TigerGenerativeHead(models["tiger"], valid, top_k=4, name="tiger")
    # 4 slots / 9 pages: at most 2 max-history requests resident at once.
    # prefix_cache=False: this test pins the COLD pool-pressure deferral
    # machinery and exact page accounting (the cache would reclaim
    # retained pages before deferring and keep pages_in_use warm between
    # requests — tests/test_prefix_cache.py covers that behavior).
    cfg = PagedConfig(max_slots=4, page_size=8, pages_per_slot=4, num_pages=9)
    eng = ServingEngine(
        [head], params["tiger"], ladder=BucketLadder((1, 2), (8,)),
        max_batch=2, max_wait_ms=1.0, handle_signals=False, paged_config=cfg,
        prefix_cache=False,
    ).start()
    try:
        futs = [
            eng.submit(_req("tiger", rng, int(rng.integers(1, 9)), len(valid)))
            for _ in range(12)
        ]
        resps = [f.result(120) for f in futs]
        for r in resps:
            assert (r.items >= 0).all() and (r.items < len(valid)).all()
            assert r.sem_ids.shape == (4, 3)
        st = eng.stats()
        assert st["completed"] == 12
        assert st["recompilations"] == 0
        assert st["admits"] == 12 and st["evictions"] == 12
        # The pool genuinely ran under pressure and deferred admissions.
        assert st["oom_deferred_admits"] > 0
        # Decode really interleaved generations: strictly fewer decode
        # steps than 12 sequential 3-step generations would need.
        assert 3 <= st["decode_steps"] < 36
        pool = st["kv_pool"]["tiger"]
        assert pool["pages_in_use"] == 0 and pool["slots_active"] == 0

        # Paged engine answers == the dense whole-batch path, bit-for-bit.
        fixed = Request(head="tiger", history=np.arange(5) % len(valid))
        r = eng.serve(fixed, timeout=60)
        dense = ServingEngine(
            [TigerGenerativeHead(models["tiger"], valid, top_k=4, name="tiger")],
            params["tiger"], ladder=BucketLadder((1, 2), (8,)),
            max_batch=2, max_wait_ms=1.0, handle_signals=False, paged=False,
        ).start()
        try:
            r_dense = dense.serve(fixed, timeout=60)
        finally:
            dense.stop()
        np.testing.assert_array_equal(r.sem_ids, r_dense.sem_ids)
        np.testing.assert_allclose(r.scores, r_dense.scores, atol=1e-5)
    finally:
        eng.stop()


@pytest.mark.parametrize("name,drafting", [
    ("tiger", False), ("tiger", True), ("cobra", False)])
def test_paged_heads_name_the_leaves_prefill_writes_and_finalize_reads(
        zoo, corpus, name, drafting):
    """The slot table stages the head's `paged_init_leaves` and fetches its
    `paged_result_leaves` and nothing else, so the two must be what the
    prefill's ``init`` carries and what `paged_finalize` reads."""
    from genrec_tpu.serving.kv_pool import KVPagePool

    models, params = zoo
    valid, item_text = corpus
    head = (TigerGenerativeHead(models[name], valid, top_k=4, name=name)
            if name == "tiger" else
            CobraGenerativeHead(models[name], valid, item_text_tokens=item_text,
                                top_k=4, name=name))
    if drafting:
        head.enable_spec_drafting()
    head.on_params(params[name])  # as the engine does before it compiles
    pool = KVPagePool(PagedConfig(max_slots=2, page_size=8, pages_per_slot=4),
                      *head.paged_layout())
    _, _, init = jax.eval_shape(
        head.make_prefill_paged_fn(1, 8), params[name],
        *head.runtime_operands(),
        *head.make_batch([head.dummy_request()], 1, 8),
        np.zeros((1, 4), np.int32), pool.k_pools, pool.v_pools)
    assert set(init) == set(head.paged_init_leaves)
    state = head.paged_state_zeros(2)
    assert set(init) | set(head.paged_result_leaves) <= set(state)
    row = {k: np.asarray(state[k][0]) for k in head.paged_result_leaves}
    assert head.paged_finalize(row, head.dummy_request())["items"].shape == (4,)


@pytest.mark.serving_smoke
@pytest.mark.parametrize("name", ["tiger", "cobra"])
def test_paged_answers_are_the_dense_path_s_under_cold_warm_and_reused_slots(
        zoo, corpus, name):
    """A fixed churn through the device-resident slot table: six cold
    requests through four slots (so slots and both rungs are reused),
    then every one again (warm admits racing two more cold ones). Each
    answer, whichever slot and admission path it took, is the dense
    whole-batch path's answer to the same request served alone."""
    models, params = zoo
    valid, item_text = corpus

    def head():
        if name == "tiger":
            return TigerGenerativeHead(models[name], valid, top_k=4, name=name)
        return CobraGenerativeHead(models[name], valid, item_text_tokens=item_text,
                                   top_k=4, name=name)

    draw = np.random.default_rng(11)
    reqs = [Request(head=name, history=draw.integers(0, len(valid), n),
                    user_id=3 + i)
            for i, n in enumerate((5, 2, 8, 3, 7, 4, 6, 1))]
    ladder = dict(ladder=BucketLadder((1, 2), (8,)), max_batch=2,
                  max_wait_ms=1.0, handle_signals=False)
    dense = ServingEngine([head()], params[name], paged=False, **ladder).start()
    try:
        want = [dense.serve(r, timeout=120) for r in reqs]
    finally:
        dense.stop()
    eng = ServingEngine(
        [head()], params[name], **ladder,
        paged_config=PagedConfig(max_slots=4, page_size=8, pages_per_slot=4,
                                 num_pages=60),
    ).start()
    try:
        cold = [f.result(120) for f in [eng.submit(r) for r in reqs[:6]]]
        again = [f.result(120) for f in
                 [eng.submit(r) for r in (*reqs[:3], reqs[6], *reqs[3:6], reqs[7])]]
        got = dict(zip((0, 1, 2, 6, 3, 4, 5, 7), again))
        for i, r in enumerate(cold):
            got.setdefault(("cold", i), r)
        for key, r in got.items():
            ref = want[key[1] if isinstance(key, tuple) else key]
            np.testing.assert_array_equal(r.sem_ids, ref.sem_ids, err_msg=str(key))
            np.testing.assert_array_equal(r.items, ref.items, err_msg=str(key))
            np.testing.assert_allclose(r.scores, ref.scores, atol=1e-5,
                                       err_msg=str(key))
        st = eng.stats()
        assert st["recompilations"] == 0
        assert st["admits"] == 14 and st["evictions"] == 14  # 4 slots: reused
        assert st["prefix_cache"][name]["hits"] == 6  # the replays landed warm
    finally:
        final = eng.stop()
    pool = final["kv_pool"][name]
    assert pool["pages_in_use"] == 0 and pool["slots_active"] == 0


@pytest.mark.serving_smoke
def test_paged_drain_chaos_sigterm_midchurn(zoo, corpus, rng):
    """SIGTERM lands mid decode-churn (chaos fires after the 2nd decode
    step): every accepted request still completes through the continuous
    loop, late submissions get the typed error ATTRIBUTED PER HEAD in the
    drain stats, and the one-shot guard restores the previous handlers —
    the second-signal escalation contract, now pinned for the paged loop."""
    models, params = zoo
    valid, _ = corpus
    prev_term = signal.getsignal(signal.SIGTERM)
    prev_int = signal.getsignal(signal.SIGINT)
    head = TigerGenerativeHead(models["tiger"], valid, top_k=4, name="tiger")
    eng = ServingEngine(
        [head], params["tiger"], ladder=BucketLadder((1, 2), (8,)),
        max_batch=2, max_wait_ms=1.0,
        paged_config=PagedConfig(max_slots=2, page_size=8, pages_per_slot=4),
    )
    try:
        with chaos.inject(chaos.ChaosPlan(kill_at_step=2)):
            futs = [
                eng.submit(_req("tiger", rng, int(rng.integers(1, 9)), len(valid)))
                for _ in range(8)
            ]
            eng.start()
            resps = [f.result(120) for f in futs]
        assert len(resps) == 8  # nothing dropped mid-churn
        assert eng.join(60), "paged engine did not finish draining"
        assert eng.draining
        with pytest.raises(DrainingError):
            eng.submit(_req("tiger", rng, 3, len(valid)))
        st = eng.stats()
        assert st["rejected"] == 1
        assert st["rejected_by_head"] == {"tiger": 1}
        pool = st["kv_pool"]["tiger"]
        assert pool["slots_active"] == 0 and pool["pages_in_use"] == 0
        # One-shot escalation: previous handlers restored on first signal.
        assert signal.getsignal(signal.SIGTERM) == prev_term
        assert signal.getsignal(signal.SIGINT) == prev_int
    finally:
        eng.stop()


# ---- checkpoint watcher: hot reload + quarantine ----------------------------


@pytest.mark.serving_smoke
def test_checkpoint_watcher_hot_reload_and_quarantine(sasrec_setup, rng):
    model, p1 = sasrec_setup
    p2 = jax.tree_util.tree_map(lambda x: x * 1.5, p1)
    with tempfile.TemporaryDirectory() as tmp:
        mgr = CheckpointManager(tmp, max_to_keep=5)
        mgr.save(1, p1)
        mgr.wait()
        eng = ServingEngine(
            [RetrievalHead("sasrec", model, top_k=5)], p1,
            ladder=BucketLadder((1,), (8,)), max_batch=1, max_wait_ms=0.5,
            ckpt_dir=tmp, ckpt_poll_secs=0.05, params_step=1,
            handle_signals=False,
        ).start()
        try:
            req = lambda: _req("sasrec", rng, 5, 0)
            fixed = Request(head="sasrec", history=np.arange(1, 6))
            r1 = eng.serve(req(), timeout=30)
            assert r1.params_step == 1
            s1 = eng.serve(fixed, timeout=30).scores

            # A newer valid step swaps in between micro-batches.
            mgr.save(2, p2)
            mgr.wait()
            deadline = time.monotonic() + 30
            while eng.params_step != 2 and time.monotonic() < deadline:
                eng.serve(req(), timeout=30)
                time.sleep(0.02)
            assert eng.params_step == 2
            assert eng.metrics.params_swaps == 1
            # The 1.5x-scaled params genuinely change the answers.
            s2 = eng.serve(fixed, timeout=30).scores
            assert not np.allclose(s1, s2)

            # A garbled newest step is quarantined; the engine keeps
            # serving step 2 and no request errors out.
            mgr.save(3, p2)
            mgr.wait()
            chaos.garble_checkpoint(tmp, 3)
            qdir = os.path.join(tmp, "quarantine", "p0", "3")
            deadline = time.monotonic() + 30
            while not os.path.exists(qdir) and time.monotonic() < deadline:
                r = eng.serve(req(), timeout=30)
                assert r.params_step == 2
                time.sleep(0.02)
            assert os.path.exists(qdir), "garbled step was not quarantined"
            assert eng.serve(req(), timeout=30).params_step == 2
        finally:
            eng.stop()
            mgr.close()


# ---- engine-surface errors --------------------------------------------------


def test_submit_unknown_head_and_params_validation(sasrec_setup):
    model, params = sasrec_setup
    head = RetrievalHead("sasrec", model, top_k=5)
    eng = ServingEngine([head], params, ladder=BucketLadder((1,), (8,)),
                        max_batch=1, handle_signals=False)
    with pytest.raises(UnknownHeadError):
        eng.submit(Request(head="nope", history=np.arange(3)))
    # Malformed histories raise to THEIR caller at submit time — negative
    # ids would wrap, too-large ids would be clamped by the OOB gather —
    # and never reach (and fail) a shared micro-batch.
    with pytest.raises(ValueError):
        eng.submit(Request(head="sasrec", history=np.asarray([3, -1])))
    with pytest.raises(ValueError):
        eng.submit(Request(head="sasrec", history=np.asarray([N_ITEMS + 1])))
    # Multi-head engines demand the combined {head: subtree} params dict.
    with pytest.raises(ValueError):
        ServingEngine(
            [head, RetrievalHead("hstu2", model, top_k=5)], params,
            ladder=BucketLadder((1,), (8,)), max_batch=1, handle_signals=False,
        )
    with pytest.raises(ValueError):
        ServingEngine([head], params, ladder=BucketLadder((1, 2), (8,)),
                      max_batch=4, handle_signals=False)


def test_retrieval_head_clamps_history_bucket_to_max_seq_len(sasrec_setup, rng):
    """A ladder bucket past the model's max_seq_len must not crash the
    warmup trace (position table is (max_seq_len, d)): the head clamps
    and serves the newest max_seq_len items."""
    model, params = sasrec_setup  # max_seq_len = 8
    eng = ServingEngine(
        [RetrievalHead("sasrec", model, top_k=5)], params,
        ladder=BucketLadder((1,), (32,)), max_batch=1, max_wait_ms=0.5,
        handle_signals=False,
    ).start()
    try:
        r = eng.serve(Request(head="sasrec", history=rng.integers(1, N_ITEMS + 1, 20)),
                      timeout=30)
        assert (r.items >= 1).all()
        assert r.bucket == (1, 32)  # ladder key; shapes clamp inside the head
    finally:
        eng.stop()


@pytest.mark.slow  # compiles an engine
def test_failed_prefill_that_consumed_donated_pools_resets_them(zoo, corpus):
    """On a TPU the prefill donates the page pools, so a prefill that
    fails after launch takes them with it. The engine must fail what was
    resident, reset the pools, and keep serving — not raise "Array has
    been deleted" on every later call. CPU does not donate, so the lost
    buffers are simulated by deleting them as the failing call would."""
    models, params = zoo
    valid, _ = corpus
    head = TigerGenerativeHead(models["tiger"], valid, top_k=4, name="tiger")
    engine = ServingEngine(
        [head], params["tiger"], ladder=BucketLadder((1, 2), (2,)),
        max_batch=2, max_wait_ms=1.0, handle_signals=False,
        paged_config=PagedConfig(max_slots=2, page_size=8, pages_per_slot=1),
    ).start()
    try:
        runner = engine._runners["tiger"]
        real_prefill = runner._run_prefill

        def failing_prefill(*a, **k):
            for leaf in jax.tree_util.tree_leaves(
                (runner.pool.k_pools, runner.pool.v_pools)
            ):
                leaf.delete()
            raise RuntimeError("device fault mid-prefill")

        rng = np.random.default_rng(0)
        runner._run_prefill = failing_prefill
        with pytest.raises(RuntimeError, match="device fault"):
            engine.serve(_req("tiger", rng, 2, len(valid)), timeout=60)
        runner._run_prefill = real_prefill
        assert not runner.pool.device_pools_consumed()
        resp = engine.serve(_req("tiger", rng, 2, len(valid)), timeout=60)
        assert len(resp.items) == 4
    finally:
        stats = engine.stop()
    assert stats["failed"] == 1 and stats["completed"] == 1
    pool = stats["kv_pool"]["tiger"]
    assert pool["pages_in_use"] == 0 and pool["slots_active"] == 0
