"""Quantized serving: int8 KV pages + int8 param tables, parity-pinned.

Quantization must pay for itself without changing ANSWERS: the paged
int8 decode path is pinned against the paged fp32 path (sem-ids exact at
serving beams, scores within a pinned tolerance), the quantized
retrieval scoring path is pinned by a recall floor, and the allocator /
handoff machinery is re-run under ``kv_dtype="int8"`` — pages carry
their scales through COW shares and the serializing wire, and a
prefill/decode dtype skew is a typed refusal, never silent garbage.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from genrec_tpu.ops.quant import (
    QuantizedKVPool,
    QuantizedTable,
    quantize_symmetric,
)
from genrec_tpu.serving.kv_pool import KVPagePool, PagedConfig, PoolExhausted

K_CB = 8


# ---- the quant primitives ---------------------------------------------------


def test_quantize_symmetric_roundtrip_and_zeros(rng):
    x = jnp.asarray(rng.normal(size=(3, 8, 2 * 4)), jnp.float32)
    data, scale = quantize_symmetric(x, (-1,))
    assert data.dtype == jnp.int8 and scale.dtype == jnp.float32
    assert data.shape == x.shape and scale.shape == (3, 8)
    # Max representable error is scale/2 per element.
    back = np.asarray(data, np.float32) * np.asarray(scale)[..., None]
    np.testing.assert_allclose(
        back, np.asarray(x), atol=float(np.asarray(scale).max()) * 0.51
    )
    # All-zero rows quantize to zero (the eps clamp, not a div-by-zero).
    d0, s0 = quantize_symmetric(jnp.zeros((2, 4)), (-1,))
    assert (np.asarray(d0) == 0).all() and (np.asarray(s0) > 0).all()


def test_quantized_containers_are_pytrees(rng):
    pool = QuantizedKVPool.zeros((5, 8, 2 * 4))
    leaves = jax.tree_util.tree_leaves(pool)
    assert len(leaves) == 2  # data + scale, no aux arrays
    assert pool.nbytes == 5 * 8 * 2 * 4 * 1 + 5 * 8 * 4
    # tree_map over SDS leaves must NOT validate (the engine's _sds path).
    sds = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), pool
    )
    assert isinstance(sds, QuantizedKVPool)
    table = QuantizedTable.from_array(
        jnp.asarray(rng.normal(size=(10, 4)), jnp.float32)
    )
    assert len(jax.tree_util.tree_leaves(table)) == 2
    assert table.data.dtype == jnp.int8 and table.scale.shape == (10,)


# ---- paged decode: int8 == fp32 at serving beams ----------------------------


@pytest.fixture(scope="module")
def tiger_setup():
    from genrec_tpu.models.tiger import Tiger

    model = Tiger(embedding_dim=16, attn_dim=32, dropout=0.0, num_heads=4,
                  n_layers=4, num_item_embeddings=K_CB, num_user_embeddings=20,
                  sem_id_dim=3, max_pos=64)
    rng = np.random.default_rng(0)
    valid = np.unique(rng.integers(0, K_CB, (30, 3)), axis=0)
    B, L = 3, 12
    mask = np.zeros((B, L), np.int32)
    for i, n in enumerate((12, 6, 9)):
        mask[i, :n] = 1
    batch = dict(
        user=jnp.asarray(rng.integers(0, 20, (B,)), jnp.int32),
        items=jnp.asarray(rng.integers(0, K_CB, (B, L)), jnp.int32),
        types=jnp.asarray(np.tile(np.arange(3), (B, L // 3)), jnp.int32),
        mask=jnp.asarray(mask),
    )
    params = model.init(
        jax.random.key(0), batch["user"], batch["items"], batch["types"],
        jnp.zeros((B, 3), jnp.int32), jnp.zeros((B, 3), jnp.int32),
        batch["mask"],
    )["params"]
    return model, params, valid, batch


def test_tiger_paged_int8_matches_fp32(tiger_setup):
    """The acceptance pin: paged-int8 sem-ids BIT-IDENTICAL to paged-fp32
    for TIGER at serving beams, scores within the pinned tolerance."""
    from genrec_tpu.models.tiger import tiger_generate_paged
    from genrec_tpu.ops.trie import DenseTrie, tuples_are_valid

    model, params, valid, b = tiger_setup
    trie = DenseTrie.build(valid, K_CB)
    kw = dict(n_top_k_candidates=5, deterministic=True)
    out = {
        dt: tiger_generate_paged(
            model, params, trie, b["user"], b["items"], b["types"], b["mask"],
            jax.random.key(7), kv_dtype=dt, **kw,
        )
        for dt in ("float32", "int8")
    }
    np.testing.assert_array_equal(
        np.asarray(out["float32"].sem_ids), np.asarray(out["int8"].sem_ids)
    )
    np.testing.assert_allclose(
        np.asarray(out["float32"].log_probas),
        np.asarray(out["int8"].log_probas), atol=0.25,
    )
    assert bool(np.asarray(tuples_are_valid(trie, out["int8"].sem_ids)).all())


def test_cobra_paged_int8_matches_fp32():
    from genrec_tpu.models.cobra import Cobra, cobra_generate_paged
    from genrec_tpu.ops.trie import DenseTrie

    model = Cobra(encoder_n_layers=1, encoder_hidden_dim=16,
                  encoder_num_heads=2, encoder_vocab_size=50,
                  id_vocab_size=K_CB, n_codebooks=3, d_model=16, max_len=64,
                  temperature=0.2, decoder_n_layers=2, decoder_num_heads=2,
                  decoder_dropout=0.0)
    rng = np.random.default_rng(0)
    B, T, C = 3, 4, 3
    ids = rng.integers(0, K_CB, (B, T * C)).astype(np.int32)
    ids[1, 2 * C:] = model.pad_id
    ids[2, 3 * C:] = model.pad_id
    txt = rng.integers(1, 50, (B, T, 5)).astype(np.int32)
    valid = np.unique(rng.integers(0, K_CB, (30, 3)), axis=0)
    params = model.init(
        jax.random.key(0), jnp.asarray(ids), jnp.asarray(txt)
    )["params"]
    trie = DenseTrie.build(valid, K_CB)
    out = {
        dt: cobra_generate_paged(
            model, params, jnp.asarray(ids), jnp.asarray(txt), n_candidates=4,
            temperature=1.0, trie=trie, kv_dtype=dt,
        )
        for dt in ("float32", "int8")
    }
    np.testing.assert_array_equal(
        np.asarray(out["float32"].sem_ids), np.asarray(out["int8"].sem_ids)
    )
    np.testing.assert_allclose(
        np.asarray(out["float32"].scores), np.asarray(out["int8"].scores),
        atol=0.02,
    )
    np.testing.assert_allclose(
        np.asarray(out["float32"].dense_vecs),
        np.asarray(out["int8"].dense_vecs), atol=0.01,
    )


# ---- the quantized Pallas kernel vs the dequant-gather fallback -------------


def test_paged_attention_quantized_kernel_matches_fallback(rng):
    """Dequant-in-kernel Pallas path (interpret mode on CPU) == the
    pure-JAX gather-dequant fallback <= 1e-5 — the same pin discipline as
    the fp32 twin, including a fully-masked slot and null-page padding."""
    from genrec_tpu.kernels.paged_attention import (
        paged_attention_stats_pallas_quantized,
    )
    from genrec_tpu.ops.paged import paged_attention_stats

    S, K, H, hd, page, P = 4, 5, 3, 8, 8, 12
    q = jnp.asarray(rng.normal(size=(S, K, H, hd)), jnp.float32)
    kd, ks = quantize_symmetric(
        jnp.asarray(rng.normal(size=(P, page, H * hd)), jnp.float32), (-1,)
    )
    vd, vs = quantize_symmetric(
        jnp.asarray(rng.normal(size=(P, page, H * hd)), jnp.float32), (-1,)
    )
    kp = QuantizedKVPool(kd, ks)
    vp = QuantizedKVPool(vd, vs)
    bt = jnp.asarray([[1, 2, 3], [4, 0, 0], [5, 6, 0], [7, 8, 9]], jnp.int32)
    sl = jnp.asarray([24, 3, 0, 17], jnp.int32)

    ref = paged_attention_stats(q, kp, vp, bt, sl, use_kernel=False)
    out = paged_attention_stats_pallas_quantized(q, kp, vp, bt, sl)
    for a, b, name in zip(ref, out, ("acc", "m", "l")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, err_msg=name
        )


# ---- quantized retrieval scoring: recall floor ------------------------------


def test_item_topk_quantized_recall_floor(rng):
    """int8 dequant-at-score top-k vs the fp32 table: recall@10 >= 0.9
    over a realistic table size — the pinned floor for the retrieval
    heads' quantized scoring operand."""
    from genrec_tpu.parallel.shardings import item_topk

    V, d, B, k = 200, 32, 16, 10
    table = jnp.asarray(rng.normal(size=(V, d)), jnp.float32)
    h = jnp.asarray(rng.normal(size=(B, d)), jnp.float32)
    _, ids_fp = item_topk(h, table, k)
    _, ids_q8 = item_topk(h, QuantizedTable.from_array(table), k)
    recall = np.mean([
        len(set(np.asarray(ids_fp[b]).tolist())
            & set(np.asarray(ids_q8[b]).tolist())) / k
        for b in range(B)
    ])
    assert recall >= 0.9, f"quantized recall@{k} {recall:.3f} below floor"


@pytest.mark.serving_smoke
@pytest.mark.slow
def test_engine_quantized_retrieval_heads(rng):
    """SASRec + HSTU served with ``quantized=True``: the int8 table rides
    as a runtime operand (on_params once per params version, zero
    steady-state recompiles) and per-request recall@5 against the fp32
    engine stays above the pinned floor. Slow-marked (two engine
    warmups, ~8s): tier-1 keeps the scoring-path pin via the
    item_topk recall floor above."""
    from genrec_tpu.models.hstu import HSTU
    from genrec_tpu.models.sasrec import SASRec
    from genrec_tpu.serving import BucketLadder, Request, ServingEngine
    from genrec_tpu.serving.heads import RetrievalHead

    n_items = 40
    sas = SASRec(num_items=n_items, max_seq_len=8, embed_dim=16, num_heads=2,
                 num_blocks=1, ffn_dim=32, dropout=0.0)
    sparams = sas.init(jax.random.key(0), jnp.zeros((2, 8), jnp.int32))["params"]
    hstu = HSTU(num_items=n_items, max_seq_len=8, embed_dim=16, num_heads=2,
                num_blocks=1, dropout=0.0)
    hparams = hstu.init(jax.random.key(1), jnp.zeros((2, 8), jnp.int32))["params"]
    params = dict(sasrec=sparams, hstu=hparams)
    reqs = [
        dict(head=h, history=rng.integers(1, n_items + 1, int(rng.integers(1, 9))),
             user_id=int(rng.integers(0, 20)))
        for h in ("sasrec", "hstu") for _ in range(4)
    ]

    def serve(quantized):
        eng = ServingEngine(
            [RetrievalHead("sasrec", sas, top_k=5, quantized=quantized),
             RetrievalHead("hstu", hstu, top_k=5, quantized=quantized)],
            params, ladder=BucketLadder((1, 2), (8,)), max_batch=2,
            max_wait_ms=1.0, handle_signals=False,
        ).start()
        try:
            futs = [eng.submit(Request(**r)) for r in reqs]
            out = [np.asarray(f.result(120).items) for f in futs]
            assert eng.metrics.recompilations == 0
        finally:
            eng.stop()
        return out

    fp32, int8 = serve(False), serve(True)
    for a, b in zip(fp32, int8):
        assert len(set(a.tolist()) & set(b.tolist())) / len(a) >= 0.8


# ---- allocator churn at kv_dtype=int8 ---------------------------------------


def test_allocator_random_churn_int8_never_leaks_or_aliases(rng):
    """The 600-op churn property test re-run over an int8 pool: identical
    allocator invariants (pages are pages regardless of storage dtype),
    with the pool arrays stored as QuantizedKVPool pairs throughout."""
    cfg = PagedConfig(max_slots=6, page_size=8, pages_per_slot=3,
                      num_pages=12, kv_dtype="int8")
    pool = KVPagePool(cfg, n_layers=1, n_heads=2, head_dim=4)
    assert isinstance(pool.k_pools[0], QuantizedKVPool)
    assert pool.stats()["kv_dtype"] == "int8"
    live: list[int] = []
    admitted = evicted = deferred = shared = 0
    for _ in range(600):
        op = rng.random()
        try:
            if op < 0.45:
                live.append(
                    pool.admit(int(rng.integers(0, cfg.max_kv_tokens + 1)))
                )
                admitted += 1
            elif op < 0.55 and live:
                src = live[int(rng.integers(len(live)))]
                tokens = int(rng.integers(0, int(pool.seq_lens[src]) + 1))
                live.append(pool.share_into(src, tokens))
                shared += 1
            elif live:
                slot = live.pop(int(rng.integers(len(live))))
                pool.evict(slot)
                evicted += 1
        except PoolExhausted:
            deferred += 1
        pool.check_invariants()
        assert pool.active_slot_count == len(live)
    assert admitted > 100 and evicted > 100 and deferred > 10 and shared > 5
    for slot in list(live):
        pool.evict(slot)
    pool.check_invariants()
    assert pool.allocator.pages_in_use == 0
    assert pool.allocator.pages_free == cfg.num_pages - 1


def test_scales_travel_with_cow_shares(rng):
    """A COW share reads back the DONOR's values: page scales live in the
    pool arrays beside the int8 rows, so a shared block table dequantizes
    identically with no per-slot scale state to copy."""
    from genrec_tpu.ops.paged import gather_pages, write_pages

    cfg = PagedConfig(max_slots=4, page_size=8, pages_per_slot=2,
                      kv_dtype="int8")
    pool = KVPagePool(cfg, n_layers=1, n_heads=2, head_dim=4)
    src = pool.admit(16)
    bt_src = jnp.asarray(pool.block_tables[src : src + 1], jnp.int32)
    kv = jnp.asarray(rng.normal(size=(1, 2, 16, 4)), jnp.float32)  # (B,H,L,hd)
    pool.k_pools = (write_pages(pool.k_pools[0], bt_src, kv),)
    dst = pool.share_into(src, 16)
    bt_dst = jnp.asarray(pool.block_tables[dst : dst + 1], jnp.int32)
    got_src = np.asarray(gather_pages(pool.k_pools[0], bt_src))
    got_dst = np.asarray(gather_pages(pool.k_pools[0], bt_dst))
    np.testing.assert_array_equal(got_src, got_dst)
    # And both dequantize back to the written content (quant error only).
    scale = np.asarray(pool.k_pools[0].scale).max()
    np.testing.assert_allclose(
        got_dst[:, :16], np.moveaxis(np.asarray(kv), 1, 2).reshape(1, 16, 8),
        atol=scale * 0.51,
    )


# ---- handoff: dtype skew is a typed refusal, wire carries scales ------------


def _handoff(kv_dtype, layout=(1, 2, 4, "float32")):
    from genrec_tpu.disagg.handoff import KVHandoff

    return KVHandoff(
        head="sasrec", n_tokens=12, bucket=(1, 8), layout=layout, init=None,
        params_step=1, catalog_version=None, prefill_worker_id="sasrec:p0",
        kv_dtype=kv_dtype,
    )


def test_serializing_transport_int8_roundtrip_and_skew_refusal(rng):
    """Gather -> wire (int8 rows + scale planes) -> scatter restores
    page CONTENT across distinct pools; admitting into a pool of the
    other storage dtype is a typed refusal before any bytes land."""
    from genrec_tpu.disagg.handoff import HandoffRefusedError
    from genrec_tpu.disagg.transport import SerializingTransport
    from genrec_tpu.ops.paged import gather_pages, write_pages

    cfg = PagedConfig(max_slots=2, page_size=8, pages_per_slot=2,
                      kv_dtype="int8")
    src = KVPagePool(cfg, n_layers=1, n_heads=2, head_dim=4)
    dst = KVPagePool(cfg, n_layers=1, n_heads=2, head_dim=4)
    tr = SerializingTransport()
    n_compiles = []
    tr.prepare_send(src, n_compiles.append)
    tr.prepare_admit(dst, n_compiles.append)
    assert len(n_compiles) == 2

    slot = src.admit(12)
    bt = jnp.asarray(src.block_tables[slot : slot + 1], jnp.int32)
    kv = jnp.asarray(rng.normal(size=(1, 2, 16, 4)), jnp.float32)  # (B,H,L,hd)
    src.k_pools = (write_pages(src.k_pools[0], bt, kv),)
    src.v_pools = (write_pages(src.v_pools[0], bt, -kv),)

    h = _handoff("int8")
    tr.send(src, src.slot_pages(slot), h)
    assert h.wire is not None and h.transfer_bytes == len(h.wire)
    got = tr.admit(h, dst)
    bt2 = jnp.asarray(dst.block_tables[got : got + 1], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(gather_pages(dst.k_pools[0], bt2))[:, :12],
        np.asarray(gather_pages(src.k_pools[0], bt))[:, :12],
    )
    np.testing.assert_array_equal(
        np.asarray(gather_pages(dst.v_pools[0], bt2))[:, :12],
        np.asarray(gather_pages(src.v_pools[0], bt))[:, :12],
    )

    # Backstop refusal: the same wire into an fp32 pool.
    fp_pool = KVPagePool(
        PagedConfig(max_slots=2, page_size=8, pages_per_slot=2),
        n_layers=1, n_heads=2, head_dim=4,
    )
    tr.prepare_admit(fp_pool, n_compiles.append)
    h2 = _handoff("int8")
    tr.send(src, src.slot_pages(slot), h2)
    with pytest.raises(HandoffRefusedError, match="kv_dtype"):
        tr.admit(h2, fp_pool)


@pytest.mark.slow
def test_decode_worker_refuses_kv_dtype_skew(rng):
    """DecodeWorker.validate refuses a handoff whose pages were encoded
    under the other storage dtype — before params/catalog checks can
    pass it through to a garbage scatter. Slow-marked (full DisaggFront
    warmup, ~9s): tier-1 keeps the transport-level skew refusal via the
    SerializingTransport admit backstop test above."""
    from genrec_tpu.disagg.front import DisaggFront
    from genrec_tpu.disagg.handoff import (
        HandoffRefusedError,
        KVHandoff,
        layout_of,
    )
    from genrec_tpu.models.tiger import Tiger
    from genrec_tpu.serving import BucketLadder, PagedConfig
    from genrec_tpu.serving.heads import TigerGenerativeHead

    model = Tiger(embedding_dim=16, attn_dim=32, dropout=0.0, num_heads=4,
                  n_layers=2, num_item_embeddings=K_CB, num_user_embeddings=20,
                  sem_id_dim=3, max_pos=64)
    params = model.init(
        jax.random.key(0), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2, 6), jnp.int32), jnp.zeros((2, 6), jnp.int32),
        jnp.zeros((2, 3), jnp.int32), jnp.zeros((2, 3), jnp.int32),
        jnp.ones((2, 6), jnp.int32),
    )["params"]
    valid = np.unique(rng.integers(0, K_CB, (20, 3)), axis=0)
    head = TigerGenerativeHead(model, valid, top_k=4, name="tiger")
    front = DisaggFront(
        [head], params, ladder=BucketLadder((1, 2), (8,)), max_batch=2,
        max_wait_ms=1.0, params_step=1,
        paged_config=PagedConfig(max_slots=2, page_size=8, pages_per_slot=4),
        n_prefill=1, n_decode=1, transport="inprocess",
    ).start(run_loop=False)
    try:
        dw = front._groups["tiger"].decode[0]
        assert dw.pool.cfg.kv_dtype == "float32"
        base = dict(head="tiger", n_tokens=16, bucket=(1, 8),
                    layout=layout_of(dw.head), init=None, params_step=1,
                    catalog_version=dw.head.catalog_version,
                    prefill_worker_id="tiger:p0")
        with pytest.raises(HandoffRefusedError, match="storage dtypes"):
            dw.validate(KVHandoff(**base, kv_dtype="int8"))
        # The matching dtype still validates clean.
        dw.validate(KVHandoff(**base, kv_dtype="float32"))
    finally:
        front.stop()


# ---- config plumbing --------------------------------------------------------


def test_paged_config_kv_dtype_validation_and_bytes():
    with pytest.raises(ValueError, match="kv_dtype"):
        PagedConfig(max_slots=2, page_size=8, pages_per_slot=2,
                    kv_dtype="bf16")
    fp = PagedConfig(max_slots=4, page_size=16, pages_per_slot=3)
    q8 = PagedConfig(max_slots=4, page_size=16, pages_per_slot=3,
                     kv_dtype="int8")
    rows = 2 * 2 * 13 * 16  # K+V x layers x pages x page_size
    assert fp.hbm_bytes(n_layers=2, n_heads=4, head_dim=8) == rows * 4 * 8 * 4
    # int8: one byte per element + one fp32 scale per (page, position).
    assert q8.hbm_bytes(n_layers=2, n_heads=4, head_dim=8) == (
        rows * (4 * 8 * 1 + 4)
    )
    # The ledger sees the same bytes the arrays actually occupy.
    pool = KVPagePool(q8, n_layers=2, n_heads=4, head_dim=8)
    from genrec_tpu.obs.memory import tree_nbytes

    assert tree_nbytes((pool.k_pools, pool.v_pools)) == q8.hbm_bytes(
        n_layers=2, n_heads=4, head_dim=8
    )


def test_engine_kv_dtype_conflict_refused():
    """An explicit paged_config wins; a DISAGREEING engine-level kv_dtype
    is a construction-time error, not a silent override."""
    from genrec_tpu.models.sasrec import SASRec
    from genrec_tpu.serving import BucketLadder, ServingEngine
    from genrec_tpu.serving.heads import RetrievalHead

    model = SASRec(num_items=20, max_seq_len=8, embed_dim=16, num_heads=2,
                   num_blocks=1, ffn_dim=32, dropout=0.0)
    params = model.init(jax.random.key(0), jnp.zeros((2, 8), jnp.int32))["params"]
    with pytest.raises(ValueError, match="kv_dtype"):
        ServingEngine(
            [RetrievalHead("sasrec", model, top_k=5)], params,
            ladder=BucketLadder((1, 2), (8,)), max_batch=2,
            handle_signals=False, kv_dtype="int8",
            paged_config=PagedConfig(max_slots=2, page_size=8,
                                     pages_per_slot=2),
        )
