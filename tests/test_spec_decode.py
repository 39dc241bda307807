"""Speculative tree decode: spec == plain, rollback purity, termination.

The speculative contract (docs/SERVING.md "Speculative decoding"): the
tree-verify step replays the PLAIN beam-update definition on verified
logits, so speculation may only change how many target invocations a
tuple costs — never what is decoded. Pinned here:

- model-level spec-vs-plain parity for TIGER (two catalogs: depth 3 and
  the depth-4 disambiguation regime) and COBRA (trie-constrained and
  free decode): sem-ids/prefixes BIT-exact, scores to float association
  (<= 1e-5 — the same pin as paged == dense; the spec pass is a
  different XLA program, so cross-program fusion may differ in the last
  ulp even though every per-element op matches);
- engine-level bit-identical responses under mixed spec/plain churn on
  ONE engine (spec TIGER + spec COBRA + a plain retrieval head),
  against an all-plain engine, with zero steady-state recompiles and
  clean pools/scratch after drain;
- rollback purity: a FULLY-REJECTED tree (adversarial draft_override)
  leaves pool refcounts, prefix-cache retained pages and slot state
  byte-identical to the plain step's — speculation shares no pages with
  slot state and commits nothing it did not verify;
- the drafter-disagrees worst case commits exactly one code per call
  (the exact root level) and terminates in <= D steps.

Small-ladder discipline throughout (one history bucket, max_slots ==
max_batch) to protect tier-1 wall time.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from genrec_tpu.catalog.tensor_trie import TensorTrie
from genrec_tpu.models.cobra import (
    Cobra,
    cobra_paged_decode_step,
    cobra_prefill_paged,
    cobra_spec_tree_step,
    init_cobra_paged_state,
)
from genrec_tpu.models.tiger import (
    Tiger,
    init_tiger_paged_state,
    tiger_paged_decode_step,
    tiger_prefill_paged,
    tiger_spec_tree_step,
)
from genrec_tpu.ops.paged import zero_pool
from genrec_tpu.ops.spec_tree import TreeTopology
from genrec_tpu.ops.trie import legal_topk_ragged, tuples_are_valid

K_CB = 8
BEAMS = 4


@functools.lru_cache(maxsize=None)  # three tests share the D=3 build
def _tiger_setup(D: int):
    model = Tiger(embedding_dim=16, attn_dim=32, dropout=0.0, num_heads=4,
                  n_layers=4, num_item_embeddings=K_CB, num_user_embeddings=20,
                  sem_id_dim=D, max_pos=64)
    rng = np.random.default_rng(D)
    valid = np.unique(rng.integers(0, K_CB, (30, D)), axis=0)
    trie = TensorTrie.build(valid, K_CB).device()
    B, L = 3, 4 * D
    mask = np.zeros((B, L), np.int32)
    for i, n in enumerate((L, 2 * D, 3 * D)):
        mask[i, :n] = 1
    user = jnp.asarray(rng.integers(0, 20, (B,)), jnp.int32)
    items = jnp.asarray(rng.integers(0, K_CB, (B, L)), jnp.int32)
    types = jnp.asarray(np.tile(np.arange(D), (B, L // D)), jnp.int32)
    maskj = jnp.asarray(mask)
    params = model.init(
        jax.random.key(0), user, items, types, jnp.zeros((B, D), jnp.int32),
        jnp.zeros((B, D), jnp.int32), maskj,
    )["params"]
    nl, H = model.n_layers // 2, model.num_heads
    hd = model.attn_dim // H
    page = 8
    pps = -(-(L + 1) // page)
    bt = jnp.asarray(1 + jnp.arange(B * pps).reshape(B, pps), jnp.int32)
    zeros = lambda: tuple(
        zero_pool(1 + B * pps, page, H, hd, model.dtype) for _ in range(nl)
    )
    k_pools, v_pools, seq_lens, _ = tiger_prefill_paged(
        model, params, user, items, types, maskj, bt, zeros(), zeros(),
    )
    return model, params, trie, bt, seq_lens, k_pools, v_pools, B


def _tiger_plain(model, params, trie, bt, seq_lens, k_pools, v_pools, B):
    D = model.sem_id_dim
    state = init_tiger_paged_state(model, B, BEAMS)
    for step in range(D):
        state = tiger_paged_decode_step(
            model, params, trie, state, jnp.full((B,), step, jnp.int32),
            bt, seq_lens, k_pools, v_pools, rng=None,
        )
    return state


def _assert_state_match(plain, spec, int_keys, float_keys):
    for k in int_keys:
        np.testing.assert_array_equal(
            np.asarray(plain[k]), np.asarray(spec[k]), err_msg=k
        )
    for k in float_keys:
        # Beam log-probabilities are float32 sums of magnitude 5-17, where
        # one ulp is already 1e-6 to 2e-6: the two paths add the same terms
        # in another order, which an absolute 1e-5 alone cannot admit.
        np.testing.assert_allclose(
            np.asarray(plain[k]), np.asarray(spec[k]), atol=1e-5, rtol=1e-5,
            err_msg=k,
        )


@pytest.mark.parametrize("D", [3, 4])
def test_tiger_spec_matches_plain(D):
    model, params, trie, bt, seq_lens, k_pools, v_pools, B = _tiger_setup(D)
    plain = _tiger_plain(model, params, trie, bt, seq_lens, k_pools, v_pools, B)
    spec = init_tiger_paged_state(model, B, BEAMS)
    steps = jnp.zeros((B,), jnp.int32)
    calls = 0
    while int(np.asarray(steps).min()) < D:
        spec, acc = tiger_spec_tree_step(
            model, params, trie, spec, steps, bt, seq_lens, k_pools, v_pools,
            fanout=K_CB,
        )
        assert int(np.asarray(acc).min()) >= 1  # the root level is exact
        steps = steps + acc
        calls += 1
    assert calls <= D  # worst case degenerates to plain, never worse
    _assert_state_match(
        plain, spec, ("beam_seqs", "prefix_idx"),
        ("beam_logps", "cache_k", "cache_v"),
    )
    assert bool(np.asarray(tuples_are_valid(trie, spec["beam_seqs"])).all())


@functools.lru_cache(maxsize=None)
def _cobra_setup(with_trie: bool):
    C = 3
    model = Cobra(encoder_n_layers=1, encoder_hidden_dim=16,
                  encoder_num_heads=2, encoder_vocab_size=50,
                  id_vocab_size=K_CB, n_codebooks=C, d_model=16, max_len=64,
                  temperature=0.2, decoder_n_layers=2, decoder_num_heads=2,
                  decoder_dropout=0.0)
    rng = np.random.default_rng(5)
    valid = np.unique(rng.integers(0, K_CB, (25, C)), axis=0)
    trie = TensorTrie.build(valid, K_CB).device() if with_trie else None
    B, T, Ltxt = 3, 4, 5
    ids = rng.integers(0, K_CB, (B, T * C)).astype(np.int32)
    ids[1, 2 * C:] = model.pad_id  # partial rows: prefill-tail path
    txt = rng.integers(1, 50, (B, T, Ltxt)).astype(np.int32)
    params = model.init(
        jax.random.key(0), jnp.asarray(ids), jnp.asarray(txt)
    )["params"]
    vecs = model.apply({"params": params}, jnp.asarray(txt),
                       method=Cobra.encode_items)
    nl, H = model.decoder_n_layers, model.decoder_num_heads
    hd = model.d_model // H
    page = 8
    pps = -(-(T * (C + 1)) // page)
    bt = jnp.asarray(1 + jnp.arange(B * pps).reshape(B, pps), jnp.int32)
    zeros = lambda: tuple(
        zero_pool(1 + B * pps, page, H, hd, model.dtype) for _ in range(nl)
    )
    k_pools, v_pools, init = cobra_prefill_paged(
        model, params, jnp.asarray(ids), vecs, bt, zeros(), zeros(),
        trie, BEAMS, 1.0,
    )
    state = init_cobra_paged_state(model, B, BEAMS)
    state.update(init)
    return model, params, trie, bt, init["base_pos"], k_pools, v_pools, state, B


@pytest.mark.parametrize("with_trie", [True, False], ids=["trie", "free"])
def test_cobra_spec_matches_plain(with_trie):
    (model, params, trie, bt, seq_lens, k_pools, v_pools,
     state0, B) = _cobra_setup(with_trie)
    C = model.n_codebooks
    plain = dict(state0)
    for c in range(1, C):
        plain = cobra_paged_decode_step(
            model, params, trie, plain, jnp.full((B,), c, jnp.int32),
            bt, seq_lens, k_pools, v_pools,
        )
    spec = dict(state0)
    steps = jnp.ones((B,), jnp.int32)
    calls = 0
    while int(np.asarray(steps).min()) < C:
        spec, acc = cobra_spec_tree_step(
            model, params, trie, spec, steps, bt, seq_lens, k_pools, v_pools,
            fanout=K_CB,
        )
        assert int(np.asarray(acc).min()) >= 1
        steps = steps + acc
        calls += 1
    assert calls <= C - 1
    if with_trie:
        # Trie-legal drafting at full fanout covers every child: the
        # whole suffix commits in ONE target invocation.
        assert calls == 1
    _assert_state_match(
        plain, spec, ("beam_tokens", "prefix_idx"),
        ("beam_scores", "cache_k", "cache_v", "h_last"),
    )
    if with_trie:
        assert bool(np.asarray(tuples_are_valid(trie, spec["beam_tokens"])).all())


# ---- rollback purity + worst-case termination -------------------------------


def _reject_all_drafts(B, fanout, depth):
    """Adversarial draft: every speculated candidate is an illegal code,
    so no selection can ever match — the fully-rejected tree."""
    return [
        np.full((B, BEAMS * fanout**l, fanout), K_CB + 3, np.int32)
        for l in range(depth)
    ]


def test_fully_rejected_tree_rolls_back_clean():
    """A fully-rejected tree must leave pool refcounts, prefix-cache
    retained pages and slot state byte-identical to the plain step's:
    speculation is pure w.r.t. the pool (tree K/V never land in slot
    pages) and commits exactly the one exact root level."""
    from genrec_tpu.serving.kv_pool import PagedConfig, KVPagePool, PrefixIndex

    model, params, trie, bt, seq_lens, k_pools, v_pools, B = _tiger_setup(3)
    D = model.sem_id_dim
    # A real pool with live slots + a retained prefix entry + a scratch
    # reservation — the full accounting surface the rollback must not
    # disturb.
    cfg = PagedConfig(max_slots=B, page_size=8, pages_per_slot=4)
    # Tiny geometry: only the HOST-side accounting matters here.
    pool = KVPagePool(cfg, 1, 2, 4, jnp.float32)
    slots = [pool.admit(9) for _ in range(B)]
    index = PrefixIndex(pool.allocator)
    index.insert((1, 2, 3), n_tokens=9, pages=pool.slot_pages(slots[0]))
    pool.reserve_scratch(2)
    refs_before = np.array(pool.allocator._refs)
    tables_before = pool.block_tables.copy()
    retained_before = index.retained_pages

    state = init_tiger_paged_state(model, B, BEAMS)
    steps = jnp.zeros((B,), jnp.int32)
    plain = tiger_paged_decode_step(
        model, params, trie, dict(state), steps, bt, seq_lens,
        k_pools, v_pools, rng=None,
    )
    spec, acc = tiger_spec_tree_step(
        model, params, trie, dict(state), steps, bt, seq_lens,
        k_pools, v_pools, fanout=4,
        draft_override=_reject_all_drafts(B, 4, D - 1),
    )
    np.testing.assert_array_equal(np.asarray(acc), np.ones(B, np.int32))
    # The committed result IS the plain step (the exact root level)...
    _assert_state_match(
        plain, spec, ("beam_seqs", "prefix_idx"),
        ("beam_logps", "cache_k", "cache_v"),
    )
    # ...and the pool-side world is byte-identical: refcounts, block
    # tables, retained prefix pages, scratch.
    np.testing.assert_array_equal(refs_before, pool.allocator._refs)
    np.testing.assert_array_equal(tables_before, pool.block_tables)
    assert index.retained_pages == retained_before
    assert pool.scratch_page_count == 2
    pool.check_invariants()


def test_drafter_disagrees_terminates_in_D_steps():
    model, params, trie, bt, seq_lens, k_pools, v_pools, B = _tiger_setup(3)
    D = model.sem_id_dim
    state = init_tiger_paged_state(model, B, BEAMS)
    steps = jnp.zeros((B,), jnp.int32)
    calls = 0
    while int(np.asarray(steps).min()) < D:
        state, acc = tiger_spec_tree_step(
            model, params, trie, state, steps, bt, seq_lens, k_pools, v_pools,
            fanout=4, draft_override=_reject_all_drafts(B, 4, D - 1),
        )
        np.testing.assert_array_equal(np.asarray(acc), np.ones(B, np.int32))
        steps = steps + acc
        calls += 1
        assert calls <= D, "worst case must terminate in <= D steps"
    assert calls == D
    plain = _tiger_plain(model, params, trie, bt, seq_lens, k_pools, v_pools, B)
    _assert_state_match(
        plain, state, ("beam_seqs", "prefix_idx"), ("beam_logps",)
    )


# ---- drafting primitives ----------------------------------------------------


def test_legal_topk_ragged_ranks_by_weight_then_code():
    valid = np.array([[0, 1], [0, 3], [0, 3], [2, 5], [2, 5], [2, 5]])
    # Leaf WEIGHTS count duplicate tuples: under root 0 the children are
    # {1 (w=1), 3 (w=2)}; both roots carry weight 3 (tie).
    full = TensorTrie.build(valid[:, :1], K_CB).device()
    tok, legal = legal_topk_ragged(
        full, jnp.zeros((1, 1), jnp.int32), jnp.zeros((1,), jnp.int32), 3
    )
    # Root children {0 (w=3), 2 (w=3)}: tie -> ascending code order.
    assert tok[0, 0, 0] == 0 and tok[0, 0, 1] == 2
    assert bool(legal[0, 0, 0]) and bool(legal[0, 0, 1]) and not bool(legal[0, 0, 2])
    # Weighted ranking: child 3 (two leaves) outranks child 1 (one leaf).
    w = TensorTrie.build(valid, K_CB).device()
    tok2, _ = legal_topk_ragged(
        w, jnp.zeros((1, 1), jnp.int32), jnp.ones((1,), jnp.int32), 2
    )
    assert tok2[0, 0, 0] == 3 and tok2[0, 0, 1] == 1


def test_tree_topology_tables():
    topo = TreeTopology(beams=2, fanout=3, depth=2)
    assert topo.n_nodes == 2 + 6 + 18
    assert list(topo.level_offsets) == [0, 2, 8, 26]
    # Node 8 + 5 = level-2 node 5: parent = level-1 node 1, root beam 0.
    n = 8 + 5
    assert topo.level[n] == 2
    assert topo.parent[n] == 2 + 1
    assert topo.root_beam[n] == 0
    assert list(topo.anc[n]) == [0, 3, 13]


# ---- engine: mixed spec/plain churn, bit-identical to a plain engine --------


@pytest.mark.slow
@pytest.mark.serving_smoke
def test_spec_engine_matches_plain_engine_under_churn(rng):
    """One engine serving spec TIGER + spec COBRA + a plain retrieval
    head (mixed spec/plain churn), staggered submits so slots sit at
    mixed steps: every response bit-identical (items/sem_ids; scores to
    float association) to an all-plain engine's, zero steady-state
    recompiles, fewer target invocations, pools + scratch clean after
    drain."""
    from genrec_tpu.models.sasrec import SASRec
    from genrec_tpu.serving import (
        BucketLadder, CobraGenerativeHead, PagedConfig, Request,
        RetrievalHead, ServingEngine, TigerGenerativeHead,
    )

    tiger = Tiger(embedding_dim=16, attn_dim=32, dropout=0.0, num_heads=4,
                  n_layers=2, num_item_embeddings=K_CB, num_user_embeddings=20,
                  sem_id_dim=3, max_pos=64)
    tparams = tiger.init(
        jax.random.key(0), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2, 6), jnp.int32), jnp.zeros((2, 6), jnp.int32),
        jnp.zeros((2, 3), jnp.int32), jnp.zeros((2, 3), jnp.int32),
        jnp.ones((2, 6), jnp.int32),
    )["params"]
    cobra = Cobra(encoder_n_layers=1, encoder_hidden_dim=16,
                  encoder_num_heads=2, encoder_vocab_size=50,
                  id_vocab_size=K_CB, n_codebooks=3, d_model=16, max_len=64,
                  temperature=0.2, decoder_n_layers=2, decoder_num_heads=2,
                  decoder_dropout=0.0)
    cparams = cobra.init(
        jax.random.key(0), jnp.zeros((2, 12), jnp.int32),
        jnp.ones((2, 4, 5), jnp.int32),
    )["params"]
    sas = SASRec(num_items=30, max_seq_len=8, embed_dim=16, num_heads=2,
                 num_blocks=1, ffn_dim=32, dropout=0.0)
    sparams = sas.init(jax.random.key(0), jnp.zeros((2, 8), jnp.int32))["params"]
    valid = np.unique(np.random.default_rng(7).integers(0, K_CB, (20, 3)), axis=0)
    item_text = np.random.default_rng(7).integers(1, 50, (len(valid), 5)).astype(np.int32)
    params = dict(tiger=tparams, cobra=cparams, sasrec=sparams)

    reqs = []
    for i in range(18):
        head = ("tiger", "cobra", "sasrec")[i % 3]
        hist = (rng.integers(0, len(valid), int(rng.integers(1, 9)))
                if head != "sasrec" else rng.integers(1, 31, 5))
        reqs.append(Request(head=head, history=hist,
                            user_id=int(rng.integers(0, 20))))

    def run(spec_decode):
        heads = [
            TigerGenerativeHead(tiger, valid, top_k=BEAMS, name="tiger"),
            CobraGenerativeHead(cobra, valid, item_text_tokens=item_text,
                                top_k=BEAMS, name="cobra"),
            RetrievalHead("sasrec", sas, top_k=5),
        ]
        eng = ServingEngine(
            heads, params, ladder=BucketLadder((1, 2), (8,)), max_batch=2,
            max_wait_ms=1.0, handle_signals=False,
            paged_config=PagedConfig(max_slots=2, page_size=8, pages_per_slot=4),
            spec_decode=spec_decode, spec_fanout=K_CB,
        ).start()
        try:
            # Staggered: interleave submits with partial result waits so
            # slots churn at mixed steps while spec iterations run.
            futs, resps = [], []
            for i, r in enumerate(reqs):
                futs.append(eng.submit(r))
                if i % 5 == 4:
                    resps.extend(f.result(300) for f in futs)
                    futs = []
            resps.extend(f.result(300) for f in futs)
        finally:
            stats = eng.stop()
        return resps, stats

    spec_resps, spec_stats = run({"tiger", "cobra"})
    plain_resps, plain_stats = run(False)

    for a, b in zip(spec_resps, plain_resps):
        np.testing.assert_array_equal(a.items, b.items)
        if a.sem_ids is not None:
            np.testing.assert_array_equal(a.sem_ids, b.sem_ids)
        np.testing.assert_allclose(a.scores, b.scores, atol=1e-5, rtol=0)

    assert spec_stats["recompilations"] == 0
    assert plain_stats["recompilations"] == 0
    # Fewer target invocations for the SAME codes (the whole point), and
    # honest accounting: decode_steps still counts invocations while the
    # spec section carries the multi-token story.
    assert spec_stats["decode_steps"] < plain_stats["decode_steps"]
    for head in ("tiger", "cobra"):
        s = spec_stats["spec"][head]
        assert s["accepted"] >= s["slot_steps"] >= 1
        assert s["codes_per_invocation"] >= 1.0
        assert sum(s["accept_len_hist"].values()) == s["slot_steps"]
    assert spec_stats["spec"]["tiger"]["codes_per_invocation"] > 1.5
    # Pools clean after drain: no leaked slot pages, prefix retention or
    # scratch reservation.
    for head in ("tiger", "cobra"):
        pool = spec_stats["kv_pool"][head]
        assert pool["pages_in_use"] == 0
        assert pool["slots_active"] == 0
        assert pool["scratch_pages"] == 0
