"""Live catalog subsystem: TensorTrie parity, snapshot format, hot swap.

Pins the tentpole contracts of genrec_tpu/catalog/ + the serving swap
path (ISSUE 9):

- TensorTrie (the runtime-operand encoding) is mask- and advance-
  equivalent to DenseTrie/PackedTrie along every path, batch AND ragged,
  on randomized catalogs — and rank-identical to PackedTrie, whose
  representation it shares;
- constrained decode through a TensorTrie threaded as a jit ARGUMENT is
  bit-identical to the baked-trie reference (the acceptance criterion);
- CatalogSnapshot round-trips atomically, detects garbling by content
  hash, and the watcher quarantines bad files while serving continues;
- one warmed engine serves two catalog snapshots with ZERO steady-state
  recompiles (same capacity rung), beams stay valid items under
  mid-churn swap, and NO request ever mixes catalog versions (disjoint
  corpora make a mix detectable: every answer must be valid under the
  version its response reports);
- COBRA's item tower re-encodes only when the catalog version changes —
  never on a params-only hot reload (the PR-5 debt this PR retires).
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from genrec_tpu.catalog import (
    CatalogIntegrityError,
    CatalogSnapshot,
    TensorTrie,
    capacity_for,
)
from genrec_tpu.ops.trie import (
    DenseTrie,
    PackedTrie,
    advance_ragged,
    legal_mask_ragged,
    tuples_are_valid,
)

K_CB = 8


# ---- TensorTrie unit parity -------------------------------------------------


def _random_corpus(rng, n, depth, k=K_CB):
    return np.unique(rng.integers(0, k, (n, depth)), axis=0)


@pytest.mark.parametrize("seed,n,depth", [(0, 30, 3), (1, 100, 3), (2, 60, 4)])
def test_tensor_trie_masks_match_references_on_random_catalogs(seed, n, depth):
    """Walking random probe paths (valid tuples AND random garbage), the
    TensorTrie legal mask equals DenseTrie's and PackedTrie's at every
    step, and its ranks track PackedTrie's exactly (live prefixes)."""
    rng = np.random.default_rng(seed)
    valid = _random_corpus(rng, n, depth)
    tt = TensorTrie.build(valid, K_CB).device()
    refs = [PackedTrie.build(valid, K_CB)]
    if K_CB**depth <= 2**28:
        refs.append(DenseTrie.build(valid, K_CB))
    probes = np.concatenate([valid, rng.integers(0, K_CB, (40, depth))])
    toks = jnp.asarray(probes)
    for ref in refs:
        p_t = jnp.zeros(len(probes), jnp.int32)
        p_r = jnp.zeros(len(probes), jnp.int32)
        for t in range(depth):
            np.testing.assert_array_equal(
                np.asarray(tt.legal_mask(p_t, t)),
                np.asarray(ref.legal_mask(p_r, t)),
                err_msg=f"step {t} vs {type(ref).__name__}",
            )
            p_t = tt.advance(p_t, toks[:, t], t)
            p_r = ref.advance(p_r, toks[:, t], t)
            if isinstance(ref, PackedTrie):
                # Shared rank representation: live prefixes agree exactly
                # (dead ones differ only in the sentinel value).
                live = np.asarray(p_r) < ref.step_keys[t].shape[0]
                np.testing.assert_array_equal(
                    np.asarray(p_t)[live], np.asarray(p_r)[live]
                )


def test_tensor_trie_ragged_matches_batch_and_dispatches(rng):
    """The ragged variants (per-row step operand) equal the per-step
    batch calls row by row — through the trie's OWN methods and through
    the ops/trie dispatch helpers the decode paths call."""
    valid = _random_corpus(rng, 40, 3)
    tt = TensorTrie.build(valid, K_CB).device()
    S = 7
    steps = jnp.asarray(rng.integers(0, 3, (S,)), jnp.int32)
    prefix = jnp.asarray(rng.integers(0, tt.capacity, (S, 4)), jnp.int32)
    tok = jnp.asarray(rng.integers(0, K_CB, (S, 4)), jnp.int32)
    got_m = legal_mask_ragged(tt, prefix, steps)  # dispatches to TensorTrie
    got_a = advance_ragged(tt, prefix, tok, steps)
    assert got_m.shape == (S, 4, K_CB)
    for s in range(S):
        t = int(steps[s])
        np.testing.assert_array_equal(
            np.asarray(got_m[s]), np.asarray(tt.legal_mask(prefix[s], t))
        )
        np.testing.assert_array_equal(
            np.asarray(got_a[s]), np.asarray(tt.advance(prefix[s], tok[s], t))
        )


def _corpus_weights(valid, item_w, k):
    """An independent numpy walk of the corpus: per step, the (n_prefixes,
    K) table of summed item weights below each (prefix, code). A prefix's
    rank is its place among the sorted unique prefixes of its length."""
    tables = []
    for t in range(valid.shape[1]):
        prefixes, rank = np.unique(valid[:, :t], axis=0, return_inverse=True)
        table = np.zeros((len(prefixes), k), np.float64)
        np.add.at(table, (rank.reshape(-1), valid[:, t]), item_w)
        tables.append(table)
    return tables


@pytest.mark.parametrize(
    "k,n,depth,fill",
    [
        (256, 12_101, 3, False),  # the serving cell's sizes: capacity 16,384
        (8, 120, 3, True),   # capacity pinned to the widest step: the last
                             # node's K-wide read would run off the row
        (8, 300, 4, False),
        (256, 30, 3, False),  # capacity 64 < K: every K-wide read is clipped
    ],
)
def test_children_read_through_offsets_is_exact_at_every_rank(k, n, depth, fill):
    """For EVERY rank in [0, C] at every step — the live nodes, the padded
    range, the dead sentinel — the legal mask is PackedTrie's on live
    ranks and all-False elsewhere; the ragged form with mixed steps
    equals the static calls row by row; the draft weights equal the
    corpus's own sums."""
    rng = np.random.default_rng(k * 1000 + n)
    valid = _random_corpus(rng, n, depth, k)
    item_w = rng.integers(1, 5, len(valid)).astype(np.float64)
    cap = None
    if fill:
        cap = max(TensorTrie.build(valid, k).n_nodes())
        assert cap >= k  # the widest row is full and longer than one read
    tt = TensorTrie.build(valid, k, capacity=cap, item_weights=item_w).device()
    ref = PackedTrie.build(valid, k)
    want_w = _corpus_weights(valid, item_w, k)
    C = tt.capacity
    assert C == (cap or capacity_for(max(tt.n_nodes())))
    # Every rank, then the sentinel again to fill whole chunks of `rows`
    # rows of B: the CPU backend writes the (nodes, K, K) compare out, so
    # a call holds at most 2**26 of its elements.
    B = 32
    rows = max(1, min(-(-(C + 1) // B), 2**26 // (B * k * k)))
    ranks = np.full(-(-(C + 1) // (B * rows)) * B * rows, C, np.int32)
    ranks[: C + 1] = np.arange(C + 1)
    chunks = jnp.asarray(ranks.reshape(-1, rows, B))
    live = [len(w) for w in want_w]  # prefixes of length t: step t-1's nodes

    shape = (len(chunks), rows, B, k)
    static, want = [], []
    for t in range(depth):
        mask_t = jax.jit(lambda p, t=t: tt.legal_mask(p, t))
        got = np.stack([np.asarray(mask_t(c)) for c in chunks]).reshape(-1, k)
        np.testing.assert_array_equal(
            got[: live[t]],
            np.asarray(ref.legal_mask(jnp.arange(live[t], dtype=jnp.int32), t)),
        )
        np.testing.assert_array_equal(got[: live[t]], want_w[t] > 0)
        assert not got[live[t]:].any(), f"step {t}: a rank with no node has children"
        static.append(got.reshape(shape))
        want.append(np.zeros((len(ranks), k), np.float32))
        want[t][: live[t]] = want_w[t]
        want[t] = want[t].reshape(shape)

    # Mixed steps in one call; over the `depth` shifts every row meets
    # every step.
    mask_r = jax.jit(tt.legal_mask_ragged)
    weights_r = jax.jit(tt.child_weights_ragged)
    for shift in range(depth):
        steps = (np.arange(rows) + shift) % depth
        got_m = np.stack([np.asarray(mask_r(c, jnp.asarray(steps))) for c in chunks])
        got_w = np.stack([np.asarray(weights_r(c, jnp.asarray(steps))) for c in chunks])
        assert got_m.shape == got_w.shape == shape
        for t in range(depth):
            at = steps == t
            np.testing.assert_array_equal(got_m[:, at], static[t][:, at])
            np.testing.assert_array_equal(got_w[:, at], want[t][:, at])


@pytest.fixture(scope="module")
def one_chip():
    """A v5e chip that is described and not attached: the TPU compiler
    compiles for it here. Only inside this fixture (never while a module
    is imported): one process at a time may load the TPU's library."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here, or it is taken
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_legal_mask_compiles_to_one_segment_gather_on_the_chip(one_chip):
    """What the chip's compiler makes of the serving cell's mask (16 slots,
    beam 10, codebook 256, capacity 16,384): no loop — a `searchsorted`
    per candidate code was a 15-round `while` of 40,960-value gathers,
    three quarters of the cell's device time — and one gather of the
    S x beam x K child keys."""
    from jax.experimental.compilation_cache import compilation_cache

    from genrec_tpu.analysis.ir import hlo_ops_of_size

    S, beam, k, C, depth = 16, 10, 256, 16_384, 3

    def described(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    trie = TensorTrie(
        described((depth, C), jnp.int32), described((depth, C + 1), jnp.int32),
        k, described((depth, C), jnp.float32),
    )
    # What is compiled for a described chip cannot be read back from the
    # persistent cache without one: keep it out.
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        hlo = (
            jax.jit(lambda t, p, s: t.legal_mask_ragged(p, s))
            .lower(trie, described((S, beam), jnp.int32), described((S,), jnp.int32))
            .compile().as_text()
        )
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    loops = [line.strip()[:200] for line in hlo.splitlines() if " while(" in line]
    assert not loops, loops
    gathers = [line for op, line in hlo_ops_of_size(hlo, S * beam * k)
               if op == "gather"]
    assert len(gathers) <= 1, gathers


def test_tensor_trie_tuples_are_valid_and_capacity_ladder(rng):
    valid = _random_corpus(rng, 25, 3)
    tt = TensorTrie.build(valid, K_CB).device()
    probe = np.concatenate([valid, rng.integers(0, K_CB, (50, 3))])
    got = np.asarray(tuples_are_valid(tt, jnp.asarray(probe)))
    want = np.asarray([tuple(t) in {tuple(r) for r in valid} for t in probe])
    np.testing.assert_array_equal(got, want)
    # The ladder is geometric and monotone; same-rung corpora share avals.
    assert capacity_for(1) == capacity_for(64) == 64
    assert capacity_for(65) == 256 and capacity_for(257) == 1024
    a = CatalogSnapshot.build(valid, K_CB)
    b = CatalogSnapshot.build(valid[:-2], K_CB)
    assert a.trie().aval_signature() == b.trie().aval_signature()
    big = CatalogSnapshot.build(valid, K_CB, capacity=256)
    assert big.trie().aval_signature() != a.trie().aval_signature()


def test_tensor_trie_is_a_runtime_operand_not_a_constant(rng):
    """The acceptance mechanics: passed through a jit boundary, the trie
    tensors are program ARGUMENTS — the optimized HLO holds no trie-sized
    literal, and the same executable answers for a different same-rung
    catalog without retracing."""
    from genrec_tpu.analysis.ir import hlo_constants

    valid_a = _random_corpus(rng, 30, 3)
    valid_b = _random_corpus(np.random.default_rng(99), 33, 3)
    tt_a = TensorTrie.build(valid_a, K_CB).device()
    tt_b = TensorTrie.build(valid_b, K_CB).device()
    assert tt_a.aval_signature() == tt_b.aval_signature()

    traces = []

    @jax.jit
    def walk(trie, seqs):
        traces.append(1)
        return tuples_are_valid(trie, seqs)

    probe = jnp.asarray(rng.integers(0, K_CB, (20, 3)), jnp.int32)
    ok_a = np.asarray(walk(tt_a, probe))
    ok_b = np.asarray(walk(tt_b, probe))
    assert len(traces) == 1, "same-rung catalog swap must not retrace"
    set_a = {tuple(r) for r in valid_a}
    set_b = {tuple(r) for r in valid_b}
    np.testing.assert_array_equal(
        ok_a, [tuple(t) in set_a for t in np.asarray(probe)]
    )
    np.testing.assert_array_equal(
        ok_b, [tuple(t) in set_b for t in np.asarray(probe)]
    )
    hlo = jax.jit(walk).lower(tt_a, probe).compile().as_text()
    trie_bytes = 4 * tt_a.keys.size
    big = [c for c in hlo_constants(hlo) if c["bytes"] >= min(trie_bytes, 512)]
    assert not big, f"trie-sized literals baked into the executable: {big}"


# ---- TensorTrie == baked trie through the generate paths --------------------


@pytest.fixture(scope="module")
def tiger_setup():
    from genrec_tpu.models.tiger import Tiger

    model = Tiger(embedding_dim=16, attn_dim=32, dropout=0.0, num_heads=4,
                  n_layers=2, num_item_embeddings=K_CB, num_user_embeddings=20,
                  sem_id_dim=3, max_pos=64)
    rng = np.random.default_rng(0)
    B, L = 3, 12
    batch = dict(
        user=jnp.asarray(rng.integers(0, 20, (B,)), jnp.int32),
        items=jnp.asarray(rng.integers(0, K_CB, (B, L)), jnp.int32),
        types=jnp.asarray(np.tile(np.arange(3), (B, L // 3)), jnp.int32),
        mask=jnp.asarray((rng.random((B, L)) < 0.8), jnp.int32),
    )
    params = model.init(
        jax.random.key(0), batch["user"], batch["items"], batch["types"],
        jnp.zeros((B, 3), jnp.int32), jnp.zeros((B, 3), jnp.int32),
        batch["mask"],
    )["params"]
    return model, params, batch


def test_tiger_generate_tensor_trie_bit_identical_to_baked(tiger_setup, rng):
    """`tiger_generate` with the trie THREADED as a jit argument emits
    bit-identical sem_ids (and log-probs <= 1e-5) vs the baked DenseTrie
    reference on the shared catalog — the acceptance criterion."""
    from genrec_tpu.models.tiger import tiger_generate

    model, params, b = tiger_setup
    valid = _random_corpus(np.random.default_rng(7), 30, 3)

    def gen(p, trie):
        return tiger_generate(
            model, p, trie, b["user"], b["items"], b["types"], b["mask"],
            jax.random.key(3), n_top_k_candidates=5, deterministic=True,
        )

    baked = jax.jit(lambda p: gen(p, DenseTrie.build(valid, K_CB)))(params)
    tt = TensorTrie.build(valid, K_CB).device()
    operand = jax.jit(gen)(params, tt)
    np.testing.assert_array_equal(
        np.asarray(operand.sem_ids), np.asarray(baked.sem_ids)
    )
    np.testing.assert_allclose(
        np.asarray(operand.log_probas), np.asarray(baked.log_probas), atol=1e-5
    )
    assert bool(np.asarray(tuples_are_valid(tt, operand.sem_ids)).all())


# ---- snapshot format --------------------------------------------------------


def test_snapshot_roundtrip_content_hash_and_garble(tmp_path, rng):
    valid = _random_corpus(rng, 20, 3)
    vecs = rng.normal(size=(len(valid), 6)).astype(np.float32)
    snap = CatalogSnapshot.build(valid, K_CB, item_vecs=vecs)
    path = snap.save(str(tmp_path))
    assert os.path.basename(path) == f"catalog-{snap.version}.npz"
    # No stray tmp files: the write is tmp + os.replace.
    assert [f for f in os.listdir(tmp_path) if f.endswith(".tmp")] == []
    back = CatalogSnapshot.load(path)
    assert back.version == snap.version and back.capacity == snap.capacity
    np.testing.assert_array_equal(back.item_sem_ids, valid)
    np.testing.assert_array_equal(back.item_vecs, vecs)
    # Same content => same version (the hash is CONTENT, not identity);
    # different content => different version.
    assert CatalogSnapshot.build(valid, K_CB, item_vecs=vecs).version == snap.version
    assert CatalogSnapshot.build(valid[:-1], K_CB).version != snap.version
    # Garbling any byte breaks the content hash (or the archive).
    raw = bytearray(open(path, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    open(path, "wb").write(bytes(raw))
    with pytest.raises(CatalogIntegrityError):
        CatalogSnapshot.load(path)


# ---- serving: hot catalog swap ----------------------------------------------


def _tiger_head_and_params(valid, name="tiger"):
    from genrec_tpu.models.tiger import Tiger
    from genrec_tpu.serving import TigerGenerativeHead

    model = Tiger(embedding_dim=16, attn_dim=32, dropout=0.0, num_heads=4,
                  n_layers=2, num_item_embeddings=K_CB, num_user_embeddings=20,
                  sem_id_dim=3, max_pos=64)
    params = model.init(
        jax.random.key(0), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2, 6), jnp.int32), jnp.zeros((2, 6), jnp.int32),
        jnp.zeros((2, 3), jnp.int32), jnp.zeros((2, 3), jnp.int32),
        jnp.ones((2, 6), jnp.int32),
    )["params"]
    return TigerGenerativeHead(model, valid, top_k=4, name=name), params


def _disjoint_corpora(rng, n=24, depth=3):
    """Two corpora with NO shared tuple: first-code 0..3 vs 4..7, so a
    beam that mixed trie versions would be valid in NEITHER corpus."""
    a = np.unique(
        np.concatenate(
            [rng.integers(0, K_CB // 2, (n, 1)),
             rng.integers(0, K_CB, (n, depth - 1))], axis=1
        ), axis=0,
    )
    b = np.unique(
        np.concatenate(
            [rng.integers(K_CB // 2, K_CB, (n, 1)),
             rng.integers(0, K_CB, (n, depth - 1))], axis=1
        ), axis=0,
    )
    return a, b


@pytest.mark.slow
@pytest.mark.serving_smoke
def test_catalog_swap_mid_churn_zero_recompiles_no_version_mixing(rng):
    """The tentpole, end to end: a warmed PAGED engine serves constrained
    decode against catalog A, catalog B is staged MID-CHURN (requests in
    flight), and

    - every response's beams are valid items of the catalog version the
      response REPORTS (disjoint corpora: a version mix would be invalid
      everywhere) — the no-mixing property;
    - both versions actually served requests;
    - zero steady-state recompilations (same capacity rung: the swap is
      a pure operand change);
    - the final answers equal a fresh engine built directly on B
      (bit-identical sem_ids).
    """
    from genrec_tpu.serving import BucketLadder, Request, ServingEngine

    valid_a, valid_b = _disjoint_corpora(rng)
    snap_a = CatalogSnapshot.build(valid_a, K_CB)
    snap_b = CatalogSnapshot.build(valid_b, K_CB)
    assert snap_a.trie().aval_signature() == snap_b.trie().aval_signature()
    sets = {
        snap_a.version: {tuple(r) for r in valid_a},
        snap_b.version: {tuple(r) for r in valid_b},
    }
    head, params = _tiger_head_and_params(valid_a)
    # Small-ladder discipline (tier-1 wall time): one history bucket and
    # max_slots == max_batch collapse warmup to 2 prefill + 1 decode
    # executables; the swap barrier/no-mixing property is bucket-count
    # independent.
    from genrec_tpu.serving import PagedConfig

    eng = ServingEngine(
        [head], params, ladder=BucketLadder((1, 2), (8,)), max_batch=2,
        max_wait_ms=1.0, handle_signals=False,
        paged_config=PagedConfig(max_slots=2, page_size=8, pages_per_slot=4),
    ).start()
    try:
        n_corpus = min(len(valid_a), len(valid_b))

        def req():
            return Request(
                head="tiger",
                history=rng.integers(0, n_corpus, int(rng.integers(1, 9))),
            )

        futs = [eng.submit(req()) for _ in range(6)]
        assert eng.stage_catalog("tiger", snap_b) is True
        futs += [eng.submit(req()) for _ in range(6)]
        # Wait until the swap has applied, then serve a few more under B.
        deadline = time.monotonic() + 60
        while eng.catalog_version("tiger") != snap_b.version:
            assert time.monotonic() < deadline, "catalog swap never applied"
            futs.append(eng.submit(req()))
            time.sleep(0.01)
        futs += [eng.submit(req()) for _ in range(4)]
        resps = [f.result(120) for f in futs]

        versions = {r.catalog_version for r in resps}
        assert versions <= {snap_a.version, snap_b.version}
        assert snap_b.version in versions, "no request served by the new catalog"
        for r in resps:
            corpus = sets[r.catalog_version]
            for t in np.asarray(r.sem_ids).reshape(-1, 3):
                assert tuple(t) in corpus, (
                    f"beam {tuple(t)} invalid under reported catalog "
                    f"{r.catalog_version} — versions mixed within a request"
                )
        st = eng.stats()
        assert st["recompilations"] == 0
        assert st["catalog_compiles"] == 0  # same rung: operand-only swap
        assert st["catalog_swaps"] == 1

        # Bit-identical to a fresh engine built directly on catalog B.
        fixed = Request(head="tiger", history=np.arange(5) % n_corpus)
        r_swapped = eng.serve(fixed, timeout=60)
        assert r_swapped.catalog_version == snap_b.version
        head_b, params_b = _tiger_head_and_params(valid_b)
        ref = ServingEngine(
            [head_b], params, ladder=BucketLadder((1, 2), (8,)), max_batch=2,
            max_wait_ms=1.0, handle_signals=False,
            paged_config=PagedConfig(max_slots=2, page_size=8,
                                     pages_per_slot=4),
        ).start()
        try:
            r_ref = ref.serve(fixed, timeout=60)
        finally:
            ref.stop()
        np.testing.assert_array_equal(r_swapped.sem_ids, r_ref.sem_ids)
    finally:
        eng.stop()


@pytest.mark.slow
@pytest.mark.serving_smoke
def test_catalog_rung_growth_precompiles_off_hot_path(rng):
    """A snapshot past the capacity rung changes the trie aval: staging
    precompiles replacement executables (counted as catalog_compiles,
    NEVER as steady-state recompilations) and the swap still serves
    valid items of the big catalog."""
    from genrec_tpu.serving import BucketLadder, Request, ServingEngine

    valid_a, _ = _disjoint_corpora(rng)
    big = np.unique(rng.integers(0, K_CB, (120, 3)), axis=0)
    snap_a = CatalogSnapshot.build(valid_a, K_CB)
    snap_big = CatalogSnapshot.build(big, K_CB)
    assert snap_big.capacity > snap_a.capacity  # rung genuinely grew
    head, params = _tiger_head_and_params(valid_a)
    eng = ServingEngine(
        [head], params, ladder=BucketLadder((1, 2), (4,)), max_batch=2,
        max_wait_ms=1.0, handle_signals=False,
    ).start()
    try:
        eng.stage_catalog("tiger", snap_big)
        deadline = time.monotonic() + 120
        while eng.catalog_version("tiger") != snap_big.version:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        r = eng.serve(
            Request(head="tiger", history=rng.integers(0, len(big), 4)),
            timeout=120,
        )
        assert r.catalog_version == snap_big.version
        corpus = {tuple(row) for row in big}
        for t in np.asarray(r.sem_ids).reshape(-1, 3):
            assert tuple(t) in corpus
        st = eng.stats()
        assert st["catalog_compiles"] > 0  # the AOT staging compiles
        assert st["recompilations"] == 0  # the hot path never compiled
    finally:
        eng.stop()


@pytest.mark.slow
@pytest.mark.serving_smoke
def test_catalog_watcher_stages_new_snapshot_and_quarantines_garbled(
    tmp_path, rng
):
    """Disk path end to end: the watcher picks up an atomically published
    snapshot within a poll, serves it, and a garbled file is quarantined
    to <dir>/quarantine/ while serving continues on the old catalog."""
    from genrec_tpu.serving import BucketLadder, Request, ServingEngine

    valid_a, valid_b = _disjoint_corpora(rng)
    snap_a = CatalogSnapshot.build(valid_a, K_CB)
    snap_b = CatalogSnapshot.build(valid_b, K_CB)
    head, params = _tiger_head_and_params(valid_a)
    cat_dir = str(tmp_path / "catalogs")
    snap_a.save(cat_dir)
    eng = ServingEngine(
        [head], params, ladder=BucketLadder((1, 2), (4,)), max_batch=2,
        max_wait_ms=1.0, handle_signals=False,
        catalog_dirs={"tiger": cat_dir}, catalog_poll_secs=0.05,
    ).start()
    try:
        n = min(len(valid_a), len(valid_b))
        req = lambda: Request(head="tiger", history=rng.integers(0, n, 4))
        assert eng.serve(req(), timeout=60).catalog_version == snap_a.version

        path_b = snap_b.save(cat_dir)
        deadline = time.monotonic() + 60
        while eng.catalog_version("tiger") != snap_b.version:
            assert time.monotonic() < deadline, "watcher never staged snapshot B"
            time.sleep(0.02)
        assert eng.serve(req(), timeout=60).catalog_version == snap_b.version

        # Publish a garbled "newer" file: quarantined, serving continues.
        snap_c = CatalogSnapshot.build(valid_a[:-1], K_CB)
        path_c = snap_c.save(cat_dir)
        raw = bytearray(open(path_c, "rb").read())
        raw[len(raw) // 2] ^= 0xFF
        open(path_c, "wb").write(bytes(raw))
        os.utime(path_c, None)  # newest mtime: the watcher must pick it
        qpath = os.path.join(cat_dir, "quarantine", os.path.basename(path_c))
        deadline = time.monotonic() + 60
        while not os.path.exists(qpath):
            assert time.monotonic() < deadline, "garbled snapshot not quarantined"
            time.sleep(0.02)
        assert eng.serve(req(), timeout=60).catalog_version == snap_b.version
        assert os.path.exists(path_b)  # good snapshots stay in place
    finally:
        eng.stop()


# ---- COBRA: tower encodes once per catalog version --------------------------


@pytest.mark.serving_smoke
def test_cobra_tower_reencodes_only_on_catalog_change(rng):
    """PR-5 debt retired: a params-only hot reload REUSES the item tower
    (encoded from item text once per catalog version); only a catalog
    swap with new text triggers a re-encode, and snapshot-held vecs never
    encode at all."""
    from genrec_tpu.models.cobra import Cobra
    from genrec_tpu.serving import CobraGenerativeHead

    model = Cobra(encoder_n_layers=1, encoder_hidden_dim=16,
                  encoder_num_heads=2, encoder_vocab_size=50,
                  id_vocab_size=K_CB, n_codebooks=3, d_model=16, max_len=64,
                  temperature=0.2, decoder_n_layers=2, decoder_num_heads=2,
                  decoder_dropout=0.0)
    valid = _random_corpus(rng, 20, 3)
    text = rng.integers(1, 50, (len(valid), 5)).astype(np.int32)
    params = model.init(
        jax.random.key(0), jnp.zeros((2, 12), jnp.int32),
        jnp.ones((2, 4, 5), jnp.int32),
    )["params"]

    head = CobraGenerativeHead(model, valid, item_text_tokens=text, top_k=4)
    head.on_params(params)
    assert head.tower_encodes == 1
    vecs_v1 = np.array(head.item_vecs)

    # Params-only reloads: tower reused, no re-encode.
    p2 = jax.tree_util.tree_map(lambda x: x * 1.5, params)
    head.on_params(p2)
    head.on_params(p2)
    assert head.tower_encodes == 1
    np.testing.assert_array_equal(head.item_vecs, vecs_v1)

    # Catalog change (new text): exactly one re-encode, under the LAST
    # delivered params.
    valid2 = _random_corpus(np.random.default_rng(5), 22, 3)
    text2 = rng.integers(1, 50, (len(valid2), 5)).astype(np.int32)
    head.set_catalog(CatalogSnapshot.build(valid2, K_CB, item_text_tokens=text2))
    assert head.tower_encodes == 2
    head.on_params(p2)
    assert head.tower_encodes == 2

    # Snapshot-held vecs: adopted directly, never encoded.
    vecs3 = rng.normal(size=(len(valid), 16)).astype(np.float32)
    head.set_catalog(CatalogSnapshot.build(valid, K_CB, item_vecs=vecs3))
    head.on_params(params)
    assert head.tower_encodes == 2
    np.testing.assert_array_equal(head.item_vecs, vecs3)
