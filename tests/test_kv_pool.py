"""Paged KV pool: allocator safety under churn + kernel/fallback parity.

The page allocator is the one piece of the paged decode path with
NON-compiled mutable state, so it gets property tests: random
admit/evict/share(beam-reorder-style COW) sequences must never leak a
page, never double-free, and never alias a page across live slots
without a ref. The paged-attention kernel is pinned against the pure-JAX
fallback the same way the HSTU kernel is pinned against its XLA
reference.
"""

import numpy as np
import pytest

from genrec_tpu.serving.kv_pool import (
    KVPagePool,
    PageAllocator,
    PagedConfig,
    PoolExhausted,
)


# ---- PagedConfig ------------------------------------------------------------


def test_paged_config_defaults_and_validation():
    cfg = PagedConfig(max_slots=4, page_size=16, pages_per_slot=3)
    assert cfg.num_pages == 1 + 4 * 3  # full budget + null page
    assert cfg.max_kv_tokens == 48
    assert cfg.pages_for(1) == 1 and cfg.pages_for(16) == 1
    assert cfg.pages_for(17) == 2 and cfg.pages_for(48) == 3
    assert cfg.pages_for(0) == 1  # empty history still binds one page
    with pytest.raises(ValueError):
        cfg.pages_for(49)
    with pytest.raises(ValueError):
        PagedConfig(page_size=12)  # not a sublane multiple
    with pytest.raises(ValueError):
        PagedConfig(max_slots=0)
    with pytest.raises(ValueError):
        # A pool that can't hold ONE max-size slot would let a max-history
        # request defer forever (head-of-line block) — refused at config.
        PagedConfig(max_slots=4, page_size=16, pages_per_slot=3, num_pages=3)
    assert cfg.hbm_bytes(n_layers=2, n_heads=4, head_dim=8) == (
        2 * 2 * 13 * 16 * 4 * 8 * 4
    )


# ---- allocator unit behavior ------------------------------------------------


def test_allocator_alloc_free_refcounts():
    a = PageAllocator(6)  # pages 1..5 allocatable
    p1 = a.alloc(2)
    p2 = a.alloc(3)
    assert a.pages_free == 0 and a.pages_in_use == 5
    with pytest.raises(PoolExhausted):
        a.alloc(1)
    # Exhausted alloc left state intact (all-or-nothing).
    a.check_invariants()
    a.addref(p1)  # COW share
    a.free(p1)  # one holder drops; pages stay live
    assert a.pages_free == 0
    a.free(p1)  # last ref -> back on the free list
    assert a.pages_free == 2
    with pytest.raises(ValueError):
        a.free(p1)  # double free refuses
    with pytest.raises(ValueError):
        a.addref(p1)  # dead pages cannot be shared
    with pytest.raises(ValueError):
        a.free([0])  # the null page is never allocatable
    a.free(p2)
    assert a.pages_free == 5 and a.pages_in_use == 0
    a.check_invariants()


def test_pool_admit_evict_binds_block_tables():
    cfg = PagedConfig(max_slots=3, page_size=8, pages_per_slot=2)
    pool = KVPagePool(cfg, n_layers=1, n_heads=2, head_dim=4)
    s0 = pool.admit(13)  # 2 pages
    s1 = pool.admit(3)  # 1 page
    assert pool.seq_lens[s0] == 13 and pool.seq_lens[s1] == 3
    assert (pool.block_tables[s0] > 0).sum() == 2
    assert (pool.block_tables[s1] > 0).sum() == 1
    # No page appears in two live rows.
    live = np.concatenate([pool.block_tables[s] for s in (s0, s1)])
    live = live[live > 0]
    assert len(set(live)) == len(live)
    pool.check_invariants()
    pool.evict(s0)
    assert pool.seq_lens[s0] == 0 and (pool.block_tables[s0] == 0).all()
    with pytest.raises(ValueError):
        pool.evict(s0)  # double evict refuses
    pool.check_invariants()


def test_pool_reset_after_a_donating_call_consumed_the_pages():
    """What a prefill that DONATES the pools leaves behind when it fails
    after launch: deleted page arrays. The pool can tell, and can start
    over from zeros without touching its slot bookkeeping (the runner
    evicts what read the lost pages first)."""
    cfg = PagedConfig(max_slots=2, page_size=8, pages_per_slot=2)
    pool = KVPagePool(cfg, n_layers=2, n_heads=2, head_dim=4)
    slot = pool.admit(9)
    assert not pool.device_pools_consumed()
    pool.k_pools[1].delete()  # the failed call's donation
    assert pool.device_pools_consumed()
    pool.reset_device_pools()
    assert not pool.device_pools_consumed()
    assert len(pool.k_pools) == len(pool.v_pools) == 2
    assert pool.k_pools[1].shape == (cfg.num_pages, 8, 2 * 4)
    assert not np.asarray(pool.k_pools[1]).any()
    assert pool.seq_lens[slot] == 9  # bookkeeping untouched
    pool.evict(slot)
    pool.check_invariants()
    # A slot view resets (and sees) its bank's arrays.
    view = KVPagePool(cfg, 2, 2, 4, bank=pool)
    pool.v_pools[0].delete()
    assert view.device_pools_consumed()
    view.reset_device_pools()
    assert not pool.device_pools_consumed()


@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_pool_leaves_have_the_one_shape(kv_dtype):
    """Every pool leaf is (pages, page, heads * head_dim): the shape the
    kernel's block and a page write share (ops.paged.zero_pool). Owner
    pools, bank views and a reset hand out the same; an int8 pool's
    scale plane is (pages, page)."""
    import jax

    from genrec_tpu.ops.quant import QuantizedKVPool

    cfg = PagedConfig(max_slots=2, page_size=8, pages_per_slot=2,
                      kv_dtype=kv_dtype)
    bank = KVPagePool(cfg, n_layers=2, n_heads=3, head_dim=4)
    view = KVPagePool(cfg, 2, 3, 4, bank=bank)
    bank.reset_device_pools()
    want = (cfg.num_pages, 8, 3 * 4)
    for pool in (bank, view):
        for leaf in (*pool.k_pools, *pool.v_pools):
            assert leaf.shape == want
            data, *scale = jax.tree_util.tree_leaves(leaf)
            assert data.shape == want
            assert [s.shape for s in scale] == (
                [want[:2]] if kv_dtype == "int8" else [])
    assert view.k_pools[0] is bank.k_pools[0]
    q = QuantizedKVPool.zeros(want)
    assert q.data.shape == want and q.scale.shape == want[:2]
    assert q.dequantize().shape == want


def test_kv_pool_sharding_shards_the_merged_axis():
    """The bank splits over `model` along the head-major last axis (whole
    heads a shard under the divisibility it requires); an int8 pool's
    scale plane spans heads and replicates."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from genrec_tpu.parallel.shardings import kv_pool_sharding

    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    assert kv_pool_sharding(mesh, n_heads=3) is None  # 3 heads over 2
    place = kv_pool_sharding(mesh, n_heads=4)
    cfg = PagedConfig(max_slots=2, page_size=8, pages_per_slot=2,
                      kv_dtype="int8")
    pool = KVPagePool(cfg, n_layers=1, n_heads=4, head_dim=4)
    pool.place(place)
    leaf = pool.k_pools[0]
    assert leaf.data.sharding.spec == P(None, None, "model")
    assert leaf.data.addressable_shards[0].data.shape == (cfg.num_pages, 8, 8)
    assert leaf.scale.sharding.spec == P()


def test_pool_exhaustion_defers_cleanly():
    cfg = PagedConfig(max_slots=8, page_size=8, pages_per_slot=2, num_pages=4)
    pool = KVPagePool(cfg, n_layers=1, n_heads=2, head_dim=4)
    pool.admit(16)  # 2 pages
    pool.admit(8)  # 1 page -> 0 free
    before = pool.block_tables.copy()
    with pytest.raises(PoolExhausted):
        pool.admit(16)
    # Failed admission left nothing bound.
    np.testing.assert_array_equal(pool.block_tables, before)
    pool.check_invariants()


def test_pool_share_into_is_copy_on_write():
    cfg = PagedConfig(max_slots=4, page_size=8, pages_per_slot=2)
    pool = KVPagePool(cfg, n_layers=1, n_heads=2, head_dim=4)
    src = pool.admit(16)
    dst = pool.share_into(src, 8)  # shared view of the first page's tokens
    # Only the COVERING page is shared and reffed: a prefix view must not
    # pin the donor's tail pages for its whole lifetime (PR-11 fix).
    src_pages = pool.block_tables[src].copy()
    np.testing.assert_array_equal(pool.block_tables[dst], [src_pages[0], 0])
    pool.check_invariants()  # aliasing is ref-backed, not a leak
    pool.evict(src)  # shared page survives (dst ref); the TAIL frees now
    assert pool.allocator.pages_in_use == 1
    pool.evict(dst)
    assert pool.allocator.pages_in_use == 0
    pool.check_invariants()
    # A full-view share still pins (and shares) the whole run.
    src = pool.admit(16)
    dst = pool.share_into(src, 16)
    np.testing.assert_array_equal(pool.block_tables[src], pool.block_tables[dst])
    pool.evict(src)
    assert pool.allocator.pages_in_use == 2  # dst holds both pages
    pool.evict(dst)
    assert pool.allocator.pages_in_use == 0
    pool.check_invariants()


def test_pool_admit_shared_binds_retained_run():
    """admit_shared (the prefix cache's warm admit) binds a free slot to
    an already-live page run with one extra ref per covering page —
    exactly share_into without a source SLOT."""
    cfg = PagedConfig(max_slots=4, page_size=8, pages_per_slot=2)
    pool = KVPagePool(cfg, n_layers=1, n_heads=2, head_dim=4)
    donor = pool.admit(13)  # 2 pages
    run = pool.slot_pages(donor)
    pool.allocator.addref(run)  # the index's retained ref
    pool.evict(donor)  # donor gone; the run survives via the index ref
    assert pool.allocator.pages_in_use == 2
    warm = pool.admit_shared(run, 13)
    assert pool.seq_lens[warm] == 13
    np.testing.assert_array_equal(pool.block_tables[warm], run)
    pool.check_invariants()
    pool.evict(warm)
    assert pool.allocator.pages_in_use == 2  # index ref still holds
    pool.allocator.free(run)
    assert pool.allocator.pages_in_use == 0
    with pytest.raises(ValueError):
        pool.admit_shared([1, 2], 17)  # view exceeds the run


# ---- the churn property test ------------------------------------------------


def test_allocator_random_churn_never_leaks_or_aliases(rng):
    """Random admit/evict/share sequences: after EVERY op the pool must
    account for all pages (free + live == capacity), hold no page in two
    live slots without a matching ref, and reject over-budget admits
    without corrupting state."""
    cfg = PagedConfig(max_slots=6, page_size=8, pages_per_slot=3, num_pages=12)
    pool = KVPagePool(cfg, n_layers=1, n_heads=2, head_dim=4)
    live: list[int] = []
    admitted = evicted = deferred = shared = 0
    for _ in range(600):
        op = rng.random()
        try:
            if op < 0.45:
                live.append(pool.admit(int(rng.integers(0, cfg.max_kv_tokens + 1))))
                admitted += 1
            elif op < 0.55 and live:
                # Mix full-view and PARTIAL-PREFIX shares: the prefix
                # view must ref only its covering pages (no leak of the
                # donor's tail, no double-free on either eviction order).
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
    # The sequence genuinely exercised all paths.
    assert admitted > 100 and evicted > 100 and deferred > 10 and shared > 5
    for slot in list(live):
        pool.evict(slot)
    pool.check_invariants()
    assert pool.allocator.pages_in_use == 0
    assert pool.allocator.pages_free == cfg.num_pages - 1


# ---- PrefixIndex: the cross-request prefix cache over the allocator ---------


def test_prefix_index_insert_lookup_exact_and_partial():
    from genrec_tpu.serving.kv_pool import PrefixIndex

    a = PageAllocator(10)
    idx = PrefixIndex(a)
    run = a.alloc(2)
    e = idx.insert((7, 8, 9), n_tokens=10, pages=run, bucket=(1, 4))
    assert len(idx) == 1 and idx.retained_pages == 2
    assert a._refs[run[0]] == 2  # donor slot + index, COW style
    hit, depth = idx.lookup((7, 8, 9))
    assert hit is e and depth == 3 and hit.n_tokens == 10
    # A proper prefix of the retained key is NOT admissible (no entry at
    # that node) and no shorter entry exists -> depth 0.
    assert idx.lookup((7, 8)) == (None, 0)
    # An EXTENSION of the retained key: near-miss at the retained depth
    # (the "how warm would suffix reuse be" telemetry).
    assert idx.lookup((7, 8, 9, 11)) == (None, 3)
    assert idx.lookup((1, 2)) == (None, 0)
    a.free(run)  # donor evicts; the entry keeps the run alive
    assert a.pages_free == 10 - 1 - 2
    idx.remove((7, 8, 9))  # last ref -> pages return to the free list
    assert a.pages_free == 9 and idx.retained_pages == 0
    a.check_invariants()


def test_prefix_index_lru_reclaim_capacity_and_clear():
    from genrec_tpu.serving.kv_pool import PrefixIndex

    a = PageAllocator(8)  # 7 allocatable
    idx = PrefixIndex(a, max_entries=3)
    for i in range(3):
        run = a.alloc(2)
        idx.insert((i, i), n_tokens=16, pages=run)
        a.free(run)  # the index holds the ONLY ref now
    assert a.pages_free == 1 and idx.retained_pages == 6
    idx.touch((0, 0))  # LRU order becomes (1,1), (2,2), (0,0)
    assert idx.reclaim(3) == 1  # evicting (1,1) frees 2 -> 3 free, stop
    assert a.pages_free == 3
    assert idx.lookup((1, 1)) == (None, 0)
    assert idx.lookup((0, 0))[0] is not None
    # Capacity bound: the 4th entry evicts the LRU (2,2) first.
    for key in ((3,), (4,)):
        run = a.alloc(1)
        idx.insert(key, n_tokens=8, pages=run)
        a.free(run)
    assert len(idx) == 3
    assert idx.lookup((2, 2)) == (None, 0)
    # Same-key re-insert REPLACES: the superseded run's refs drop.
    free_before = a.pages_free
    run = a.alloc(1)
    idx.insert((3,), n_tokens=8, pages=run)
    a.free(run)
    assert len(idx) == 3 and a.pages_free == free_before
    # clear() releases everything (swap invalidation / drain).
    assert idx.clear() == 3
    assert idx.retained_pages == 0 and a.pages_free == 7
    a.check_invariants()


def test_prefix_index_reclaim_skips_slot_pinned_entries():
    """An entry whose pages are all still bound by a live slot frees
    NOTHING when evicted — reclaim must skip it (it stays warm) instead
    of wiping the index for zero relief, and still evict the entries
    that DO free pages."""
    from genrec_tpu.serving.kv_pool import PrefixIndex

    a = PageAllocator(8)  # 7 allocatable
    idx = PrefixIndex(a)
    pinned = a.alloc(3)  # donor slot still holds these (refcount stays 2)
    idx.insert((1,), n_tokens=24, pages=pinned)
    free_able = a.alloc(3)
    idx.insert((2,), n_tokens=24, pages=free_able)
    a.free(free_able)  # donor evicted: index holds the only ref
    assert a.pages_free == 1
    # Demand 4: evicting (2,) frees 3 -> 4; (1,) is pinned and — even
    # though it is the LRU entry — must survive untouched.
    assert idx.reclaim(4) == 1
    assert a.pages_free == 4
    assert idx.lookup((1,))[0] is not None
    assert idx.lookup((2,)) == (None, 0)
    # Unmeetable demand: nothing evictable remains, the loop stops
    # (no index wipe), state intact.
    assert idx.reclaim(7) == 0
    assert len(idx) == 1 and idx.retained_pages == 3
    a.check_invariants()


# ---- paged-attention kernel vs fallback parity ------------------------------


def test_paged_attention_kernel_matches_fallback(rng):
    """Pallas kernel (interpret mode on CPU) == pure-JAX gather fallback
    <= 1e-5, including a fully-masked slot and null-page padding — the
    same pin discipline as test_hstu_kernel."""
    import jax.numpy as jnp

    from genrec_tpu.kernels.paged_attention import paged_attention_stats_pallas
    from genrec_tpu.ops.paged import paged_attention_stats

    S, K, H, hd, page, P = 4, 5, 3, 8, 8, 12
    q = jnp.asarray(rng.normal(size=(S, K, H, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, page, H * hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, page, H * hd)), jnp.float32)
    bt = jnp.asarray([[1, 2, 3], [4, 0, 0], [5, 6, 0], [7, 8, 9]], jnp.int32)
    sl = jnp.asarray([24, 3, 0, 17], jnp.int32)  # incl. a fully-masked slot

    ref = paged_attention_stats(q, kp, vp, bt, sl, use_kernel=False)
    out = paged_attention_stats_pallas(q, kp, vp, bt, sl)
    for a, b, name in zip(ref, out, ("acc", "m", "l")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, err_msg=name
        )


def test_paged_attention_matches_dense_softmax(rng):
    """The normalized paged output equals plain masked softmax attention
    over the gathered keys — the bridge to the dense decode paths."""
    import jax.numpy as jnp

    from genrec_tpu.ops.paged import gather_pages, paged_attention

    S, K, H, hd, page, P, Pm = 2, 3, 2, 8, 8, 8, 2
    q = jnp.asarray(rng.normal(size=(S, K, H, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, page, H * hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, page, H * hd)), jnp.float32)
    bt = jnp.asarray([[1, 2], [3, 0]], jnp.int32)
    sl = jnp.asarray([11, 8], jnp.int32)

    out = np.asarray(paged_attention(q, kp, vp, bt, sl, use_kernel=False))
    # Head-major features in the merged axis: (..., H*hd) reads as (H, hd).
    k = np.asarray(gather_pages(kp, bt)).reshape(S, Pm * page, H, hd)
    v = np.asarray(gather_pages(vp, bt)).reshape(S, Pm * page, H, hd)
    s = np.einsum("skhd,smhd->skhm", np.asarray(q), k) * hd**-0.5
    tok = np.arange(Pm * page)
    s = np.where(tok[None, None, None, :] >= np.asarray(sl)[:, None, None, None],
                 -1e9, s)
    attn = np.exp(s - s.max(-1, keepdims=True))
    attn /= attn.sum(-1, keepdims=True)
    ref = np.einsum("skhm,smhd->skhd", attn, v)
    np.testing.assert_allclose(out, ref, atol=1e-5)
