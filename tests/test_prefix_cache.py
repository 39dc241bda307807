"""Cross-request KV prefix cache: warm-path parity, eviction governance,
invalidation (PR-11 tentpole pins).

The acceptance bars, each pinned here:

- a repeat-user request served through the prefix cache returns sem_ids
  bit-identical (scores <= 1e-5) to a cold serving of the same request,
  for the TIGER and COBRA paged heads, under mixed warm/cold churn with
  zero steady-state recompiles;
- retained prefix pages are a distinct MemoryLedger component
  (reclaimable, inside the pool operand) and are reclaimed under pool
  pressure BEFORE any admission is deferred;
- a params or catalog hot swap EMPTIES the index — a cached prefix from
  old params/catalog must never serve the new version;
- drain releases every retained page.

Engine fixtures keep the compile surface tiny (one or two history
buckets, max_slots == max_batch so the decode ladder is ONE shape) —
warmup compiles are the tier-1 wall-clock hogs.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from genrec_tpu.models.cobra import Cobra
from genrec_tpu.models.tiger import Tiger
from genrec_tpu.serving import (
    BucketLadder,
    CobraGenerativeHead,
    PagedConfig,
    Request,
    ServingEngine,
    TigerGenerativeHead,
)

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


def _tiger_head(model, valid):
    return TigerGenerativeHead(model, valid, top_k=4, name="tiger")


def _stage_params(eng, tree, step):
    """Stage a params swap exactly like the checkpoint watcher does and
    wait for the batcher to apply it."""
    with eng._lock:
        eng._pending_params = (tree, step)
    t0 = time.monotonic()
    while eng.params_step != step and time.monotonic() - t0 < 30.0:
        time.sleep(0.01)
    assert eng.params_step == step


# ---- warm-path parity under mixed warm/cold churn ---------------------------


@pytest.mark.serving_smoke
def test_tiger_warm_hits_are_bit_identical_under_mixed_churn(
        tiger_setup, corpus, rng):
    """Replays of already-served (user, history) pairs land WARM (pages
    shared, prefill skipped) interleaved with fresh cold traffic, and
    every warm answer is bit-identical to the cold first serving of the
    same request — with zero steady-state recompiles throughout."""
    model, params = tiger_setup
    valid, _ = corpus
    # num_pages well above the slot budget: retention must not hit LRU
    # pressure here (the reclaim test below runs the pressure path).
    eng = ServingEngine(
        [_tiger_head(model, valid)], params,
        ladder=BucketLadder((2,), (8,)), max_batch=2, max_wait_ms=1.0,
        handle_signals=False,
        paged_config=PagedConfig(max_slots=2, page_size=8, pages_per_slot=4,
                                 num_pages=25),
    ).start()
    try:
        fixed = [
            Request(head="tiger", history=np.arange(5) % len(valid), user_id=3),
            Request(head="tiger", history=np.asarray([2, 2, 7]), user_id=9),
        ]
        ref = [eng.serve(r, timeout=120) for r in fixed]  # cold firsts
        # Mixed churn: replays racing fresh cold requests through the
        # same slot set (short histories keep retention small).
        futs = []
        for i in range(8):
            futs.append(eng.submit(fixed[i % 2]))
            futs.append(eng.submit(Request(
                head="tiger", history=rng.integers(0, len(valid), 2),
                user_id=int(rng.integers(0, 20)),
            )))
        resps = [f.result(120) for f in futs]
        replays = resps[0::2]
        for i, r in enumerate(replays):
            np.testing.assert_array_equal(r.sem_ids, ref[i % 2].sem_ids)
            np.testing.assert_allclose(r.scores, ref[i % 2].scores, atol=1e-5)
        st = eng.stats()
        assert st["recompilations"] == 0
        pc = st["prefix_cache"]["tiger"]
        assert pc["hits"] >= 8  # every replay genuinely landed warm
        assert pc["warm_tokens"] > 0
        assert pc["insertions"] >= 2
        # Retained pages are visible as the ledger's reclaimable
        # component, inside the pool operand (not double-counted).
        hbm = st["hbm"]["heads"]["tiger"]
        assert hbm["reclaimable"]["prefix_cache_pages"] > 0
        assert hbm["reclaimable"]["prefix_cache_pages"] <= hbm["operands"]["kv_page_pool"]
        assert st["hbm"]["reclaimable_bytes"] >= hbm["reclaimable"]["prefix_cache_pages"]
    finally:
        final = eng.stop()
    # Drain released every page, INCLUDING retained prefix pages.
    pool = final["kv_pool"]["tiger"]
    assert pool["pages_in_use"] == 0 and pool["slots_active"] == 0
    assert final["prefix_cache"]["tiger"]["entries"] == 0


@pytest.mark.serving_smoke
def test_cobra_warm_hit_matches_cold_serving_including_full_bucket_edge(
        corpus):
    """COBRA warm parity on one engine, including the bucket edge that
    makes it interesting: a history that exactly fills its own bucket
    (4 items at bucket 4), donated from a prefill CO-BATCHED at a larger
    bucket (L=8). The donor entry's `full` flag is bucket-dependent —
    paged_warm_state recomputes it at admission — so the warm answer
    must equal the SOLO cold serving, not the donor's group answer.

    Cold references are the engine's own solo first serves; a staged
    params swap (same tree, new step) then empties the index — pinning
    COBRA-side invalidation — before the co-batched donor pass, so the
    replays are warm FROM THE GROUP DONOR."""
    valid, item_text = corpus
    model = Cobra(encoder_n_layers=1, encoder_hidden_dim=16, encoder_num_heads=2,
                  encoder_vocab_size=50, id_vocab_size=K_CB, n_codebooks=3,
                  d_model=16, max_len=64, temperature=0.2, decoder_n_layers=2,
                  decoder_num_heads=2, decoder_dropout=0.0)
    params = model.init(
        jax.random.key(0), jnp.zeros((2, 12), jnp.int32),
        jnp.ones((2, 4, 5), jnp.int32),
    )["params"]
    head = CobraGenerativeHead(model, valid, item_text_tokens=item_text,
                               top_k=4, name="cobra")
    # 8 items x (C+1) = 32 KV tokens -> 4 pages of 8.
    from genrec_tpu.obs.spans import SpanTracer

    tracer = SpanTracer(capacity=20_000, enabled=True)
    eng = ServingEngine(
        [head], params, ladder=BucketLadder((2,), (4, 8)), max_batch=2,
        max_wait_ms=4.0, handle_signals=False, params_step=1, tracer=tracer,
        paged_config=PagedConfig(max_slots=2, page_size=8, pages_per_slot=4,
                                 num_pages=25),
    ).start()
    try:
        h4 = np.arange(4) % len(valid)  # exactly fills its own bucket (4)
        h8 = np.arange(8) % len(valid)
        # Cold SOLO references (each at its own bucket).
        ref4 = eng.serve(Request(head="cobra", history=h4), timeout=300)
        ref8 = eng.serve(Request(head="cobra", history=h8), timeout=300)
        # Empty the index via a staged params swap (same tree, new
        # step): COBRA invalidation-on-reload, pinned.
        _stage_params(eng, params, 2)
        pc = eng.stats()["prefix_cache"]["cobra"]
        assert pc["entries"] == 0 and pc["invalidations"] >= 2
        # Donor pass: h4 and h8 co-batched -> h4 prefilled at L=8. The
        # deadline coalescer makes a joint pop overwhelmingly likely;
        # retry (after re-clearing the index) if a scheduling hiccup
        # split the group, so the edge ALWAYS genuinely happens.
        for attempt in range(2, 6):
            futs = [eng.submit(Request(head="cobra", history=h))
                    for h in (h4, h8)]
            donor4 = futs[0].result(300)
            futs[1].result(300)
            if donor4.bucket == (2, 8):
                break
            _stage_params(eng, params, attempt + 1)
        assert donor4.bucket == (2, 8)  # the edge genuinely happened
        # Replays arrive solo -> warm from the co-batched donor entries.
        warm4 = eng.serve(Request(head="cobra", history=h4), timeout=300)
        warm8 = eng.serve(Request(head="cobra", history=h8), timeout=300)
        for warm, ref in ((warm4, ref4), (warm8, ref8)):
            np.testing.assert_array_equal(warm.sem_ids, ref.sem_ids)
            np.testing.assert_allclose(warm.scores, ref.scores, atol=1e-5)
        st = eng.stats()
        assert st["prefix_cache"]["cobra"]["hits"] == 2
        assert st["recompilations"] == 0
        # a snapshot of a beam's few numbers rides the next step's row
        # write: only a head with recurrent leaves restores at the admit
        assert eng._runners["cobra"].slots.recurrent_nbytes == 0
        names = {s.name for s in tracer.spans()}
        assert "warm_admit" in names and "admit.restore_state" not in names
    finally:
        eng.stop()


# ---- eviction governance: reclaim before any deferral -----------------------


@pytest.mark.serving_smoke
def test_retained_pages_reclaimed_before_admission_defers(
        tiger_setup, corpus):
    """A pool whose free pages are exhausted BY RETAINED ENTRIES must
    reclaim them (LRU first) and admit — never defer: deferral is for
    pages pinned by live slots, not by the cache."""
    model, params = tiger_setup
    valid, _ = corpus
    # 8 allocatable pages; an 8-item history needs 4 -> two retained
    # runs fill the pool.
    cfg = PagedConfig(max_slots=2, page_size=8, pages_per_slot=4, num_pages=9)
    eng = ServingEngine(
        [_tiger_head(model, valid)], params,
        ladder=BucketLadder((2,), (8,)), max_batch=2, max_wait_ms=1.0,
        handle_signals=False, paged_config=cfg,
    ).start()
    try:
        hists = [np.full(8, i, np.int64) % len(valid) for i in range(3)]
        eng.serve(Request(head="tiger", history=hists[0]), timeout=120)
        eng.serve(Request(head="tiger", history=hists[1]), timeout=120)
        pc = eng.stats()["prefix_cache"]["tiger"]
        assert pc["retained_pages"] == 8  # the whole pool is warm
        # Third distinct history: needs 4 fresh pages -> reclaims the
        # LRU entry (hists[0]) instead of deferring.
        eng.serve(Request(head="tiger", history=hists[2]), timeout=120)
        st = eng.stats()
        assert st["oom_deferred_admits"] == 0
        pc = st["prefix_cache"]["tiger"]
        assert pc["evictions"] >= 1
        # hists[1] survived (LRU evicts oldest first) -> replay is warm.
        eng.serve(Request(head="tiger", history=hists[1]), timeout=120)
        assert eng.stats()["prefix_cache"]["tiger"]["hits"] == 1
    finally:
        eng.stop()


# ---- invalidation: params and catalog hot swaps empty the index -------------


@pytest.mark.serving_smoke
def test_params_and_catalog_hot_swaps_empty_prefix_index(tiger_setup, corpus):
    """A cached prefix was prefilled by the OLD params / OLD catalog:
    after either hot swap the index must be empty and replays must
    re-prefill under the new version — one engine, both swap paths."""
    from genrec_tpu.catalog import CatalogSnapshot

    model, params = tiger_setup
    valid, _ = corpus
    snap_a = CatalogSnapshot.build(valid, K_CB)
    valid_b = valid[: len(valid) - 2]
    snap_b = CatalogSnapshot.build(valid_b, K_CB,
                                   capacity=snap_a.trie().capacity)
    head = TigerGenerativeHead(model, catalog=snap_a, top_k=4, name="tiger")
    eng = ServingEngine(
        [head], params, ladder=BucketLadder((2,), (8,)), max_batch=2,
        max_wait_ms=1.0, handle_signals=False, params_step=1,
        paged_config=PagedConfig(max_slots=2, page_size=8, pages_per_slot=4),
    ).start()
    try:
        fixed = Request(head="tiger", history=np.arange(5) % len(valid_b))
        r1 = eng.serve(fixed, timeout=120)
        assert eng.stats()["prefix_cache"]["tiger"]["entries"] == 1

        # -- params hot swap (staged exactly like the watcher) --------------
        bumped = jax.tree_util.tree_map(lambda x: x * 1.01, params)
        _stage_params(eng, bumped, 2)
        pc = eng.stats()["prefix_cache"]["tiger"]
        assert pc["entries"] == 0 and pc["retained_pages"] == 0
        assert pc["invalidations"] >= 1
        # The replay is a MISS (re-prefilled under new params), and the
        # new-params answer is genuinely recomputed, not served stale.
        r2 = eng.serve(fixed, timeout=120)
        assert r2.params_step == 2
        pc = eng.stats()["prefix_cache"]["tiger"]
        assert pc["hits"] == 0 and pc["misses"] == 2 and pc["entries"] == 1
        assert not np.allclose(r1.scores, r2.scores)

        # -- same-rung catalog hot swap -------------------------------------
        assert eng.stage_catalog("tiger", snap_b)
        t0 = time.monotonic()
        while (eng.catalog_version("tiger") != snap_b.version
               and time.monotonic() - t0 < 30.0):
            time.sleep(0.01)
        assert eng.catalog_version("tiger") == snap_b.version
        pc = eng.stats()["prefix_cache"]["tiger"]
        assert pc["entries"] == 0 and pc["invalidations"] >= 2
        r3 = eng.serve(fixed, timeout=120)
        assert r3.catalog_version == snap_b.version
        st = eng.stats()
        assert st["prefix_cache"]["tiger"]["hits"] == 0
        assert st["recompilations"] == 0  # same rung: operand swap only
    finally:
        eng.stop()


def test_cobra_warm_state_full_flag_uses_effective_length():
    """The warm-admit `full` recompute must compare the donor's
    pad-masked effective length (init's base_pos), not the
    natural-length-derived token count: a history carrying dead ids
    (dropped by make_batch after a shrinking catalog swap) has
    n_tokens == L*(C+1) while prefill saw fewer valid positions — warm
    and cold must agree on full=False there."""
    from types import SimpleNamespace

    head = CobraGenerativeHead.__new__(CobraGenerativeHead)
    head.model = SimpleNamespace(n_codebooks=3)
    init = {"base_pos": np.asarray(12, np.int32)}  # 3 valid items of 4
    # 4 natural items at bucket 4 (16 tokens), one of them dead.
    patched = head.paged_warm_state(init, n_tokens=16, L_bucket=4)
    assert patched["full"] == False  # noqa: E712 — numpy bool
    # A genuinely full row still reads full at its own bucket.
    full = head.paged_warm_state({"base_pos": np.asarray(16, np.int32)},
                                 n_tokens=16, L_bucket=4)
    assert full["full"] == True  # noqa: E712


# ---- observability plumbing (jax-light) -------------------------------------


def test_prefix_gauges_flow_to_prometheus():
    from genrec_tpu.obs.export import prometheus_text

    snap = {
        "prefix_cache": {
            "tiger": {
                "lookups": 10, "hits": 6, "partial_hits": 1, "misses": 3,
                "warm_tokens": 96, "insertions": 4, "evictions": 1,
                "invalidations": 2, "entries": 3, "retained_pages": 5,
                "retained_bytes": 10240,
            }
        }
    }
    text = prometheus_text(snap)
    kinds = {}
    lines = text.splitlines()
    for line in lines:
        if line.startswith("# TYPE"):
            _, _, name, kind = line.split()
            kinds[name] = kind
    assert kinds["genrec_prefix_cache_tiger_hits"] == "counter"
    assert kinds["genrec_prefix_cache_tiger_warm_tokens"] == "counter"
    assert kinds["genrec_prefix_cache_tiger_invalidations"] == "counter"
    assert kinds["genrec_prefix_cache_tiger_entries"] == "gauge"
    assert kinds["genrec_prefix_cache_tiger_retained_bytes"] == "gauge"
    assert "genrec_prefix_cache_tiger_hits 6" in lines


def test_zipfian_repeat_user_trace_is_deterministic_and_warm_heavy():
    """The bench's trace generator (canonical home since PR 12:
    genrec_tpu/fleet/traffic.py, re-exported by bench): seeded
    determinism (thread-safe by construction — fully materialized before
    any driver thread runs) and a genuinely repeat-heavy shape (verbatim
    repeats dominate)."""
    from genrec_tpu.fleet.traffic import zipfian_repeat_user_trace

    t1 = zipfian_repeat_user_trace(200, 32, 20, 100,
                                   np.random.default_rng(5))
    t2 = zipfian_repeat_user_trace(200, 32, 20, 100,
                                   np.random.default_rng(5))
    assert len(t1) == 200
    for (u1, h1), (u2, h2) in zip(t1, t2):
        assert u1 == u2
        np.testing.assert_array_equal(h1, h2)
    seen, repeats = {}, 0
    for user, hist in t1:
        key = (user, hist.tobytes())
        repeats += key in seen
        seen[key] = True
        assert len(hist) <= 20
    assert repeats / len(t1) > 0.5  # verbatim repeats dominate arrivals
