"""Paged KV cache for the decode heads: fixed HBM budget, free-list pages.

PR 5's engine AOT-compiled a dense (batch x history) KV cache per bucket,
so decode memory scaled with the BUCKET a request landed in. Here the
history K/V of every in-flight request lives in ONE pool per decoder
layer, shaped (num_pages, page_size, heads * head_dim) — the one shape
its writer and its reader share (``ops.paged.zero_pool``) — and each decode
slot names its pages through a block-table row (Ragged Paged Attention,
arxiv 2604.15464): HBM is a fixed budget, occupancy tracks the tokens
actually resident, and admission is denied (never over-allocated) when
the pool is out of pages.

Four layers, separable for testing:

- ``PageAllocator``: host-side free list with per-page REFCOUNTS. Plain
  admits hold one ref per page; ``addref`` lets two holders share pages
  copy-on-write-style (the beam-sharing primitive: all K beams of a slot
  read the same history pages, and a hand-off — e.g. prefill worker to
  decode worker on the roadmap's disaggregated split — shares instead of
  copying). A page returns to the free list only when its last ref is
  dropped; freeing an unheld page raises.
- ``KVPagePool``: the device pools + per-slot block tables + seq_lens.
  ``admit(n_tokens)`` binds a free slot to freshly allocated pages,
  ``evict(slot)`` releases them. Block-table rows pad with page 0, the
  reserved NULL page — prefill's padded-tail writes land there and
  attention never reads it unmasked (ops/paged.py contract).
- ``PagedConfig``: the handful of static shapes the decode side compiles
  against — (max_slots, pages_per_slot) replaces the whole decode-side
  bucket ladder.
- ``PrefixIndex``: the cross-request prefix cache (vLLM/SGLang-style
  radix index, docs/SERVING.md "Prefix cache") — a token radix trie
  whose entries hold a COW ref on a finished request's page run, so a
  returning user's next request shares those pages instead of re-paying
  prefill. Entries are an LRU pool reclaimed FIRST under PoolExhausted
  pressure (the engine reclaims before it ever defers an admission).

Host-side bookkeeping is intentionally NOT thread-safe on its own: the
engine's batcher thread is the only caller (same discipline as the
executable cache).
"""

from __future__ import annotations

import collections
import dataclasses
import heapq

import jax.numpy as jnp
import numpy as np


class PoolExhausted(RuntimeError):
    """Not enough free pages (or free slots) to admit the request; the
    engine counts these and leaves the request queued instead of
    over-committing the budget."""


@dataclasses.dataclass(frozen=True)
class PagedConfig:
    """Static shape surface of the paged decode path.

    The decode executable is compiled ONCE at (max_slots, pages_per_slot);
    prefill stays on the (batch, history) bucket ladder but writes into
    pages. ``num_pages`` includes the reserved null page 0.
    """

    max_slots: int = 32
    page_size: int = 16
    pages_per_slot: int = 8
    num_pages: int = 0  # 0 = full budget: every slot can hold max pages
    # "float32" | "int8": int8 stores pages quantized (per-page-row
    # symmetric scales, ops/quant.QuantizedKVPool) — ~4x smaller page
    # bytes, dequantized at the attention read with fp32 accumulation.
    kv_dtype: str = "float32"

    def __post_init__(self):
        if self.max_slots <= 0 or self.page_size <= 0 or self.pages_per_slot <= 0:
            raise ValueError(f"invalid paged config {self}")
        from genrec_tpu.ops.quant import KV_DTYPES

        if self.kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"kv_dtype {self.kv_dtype!r} not supported; "
                f"one of {KV_DTYPES}"
            )
        if self.page_size % 8:
            raise ValueError(
                f"page_size {self.page_size} must be a multiple of 8 "
                "(TPU sublane tile of the paged-attention kernel)"
            )
        if self.num_pages == 0:
            object.__setattr__(
                self, "num_pages", 1 + self.max_slots * self.pages_per_slot
            )
        if self.num_pages < 1 + self.pages_per_slot:
            # A pool that cannot hold even ONE max-size slot would let an
            # admissible max-history request defer forever (PoolExhausted
            # on every retry) and head-of-line-block its queue.
            raise ValueError(
                f"num_pages {self.num_pages} cannot hold one full slot "
                f"({self.pages_per_slot} pages + the null page); the pool "
                "must fit at least one max-history request"
            )

    @property
    def max_kv_tokens(self) -> int:
        """Largest history (in KV tokens) one slot can hold."""
        return self.pages_per_slot * self.page_size

    def pages_for(self, n_tokens: int) -> int:
        n = -(-int(n_tokens) // self.page_size)
        if n > self.pages_per_slot:
            raise ValueError(
                f"{n_tokens} KV tokens need {n} pages > pages_per_slot "
                f"{self.pages_per_slot}; size the config off the largest "
                "history bucket"
            )
        return max(n, 1)

    def hbm_bytes(self, n_layers: int, n_heads: int, head_dim: int,
                  itemsize: int = 4) -> int:
        """Pool HBM footprint (K + V, all layers) — the fixed budget.

        ``kv_dtype="int8"`` prices real quantized bytes: one byte per
        element plus the fp32 per-page-row scale planes (matching
        ``obs.memory.tree_nbytes`` over the QuantizedKVPool leaves
        exactly, so the ledger and this planner never disagree).
        """
        rows = 2 * n_layers * self.num_pages * self.page_size
        if self.kv_dtype == "int8":
            return rows * (n_heads * head_dim * 1 + 4)
        return rows * n_heads * head_dim * itemsize


class PageAllocator:
    """Free-list page allocator with refcounts; page 0 is never handed out."""

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is the null page)")
        self.num_pages = int(num_pages)
        # LIFO free list: recently-freed pages are reused first (their
        # stale KV is overwritten by the next prefill before any read).
        self._free = list(range(self.num_pages - 1, 0, -1))
        self._refs = np.zeros(self.num_pages, np.int64)

    @property
    def pages_free(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - 1 - len(self._free)

    def alloc(self, n: int) -> list[int]:
        """n fresh pages at refcount 1 — all-or-nothing."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            raise PoolExhausted(
                f"need {n} pages, {len(self._free)} free of "
                f"{self.num_pages - 1} allocatable"
            )
        pages = [self._free.pop() for _ in range(n)]
        self._refs[pages] += 1
        return pages

    def addref(self, pages) -> None:
        """Share already-live pages (copy-on-write ref, beam/worker
        sharing). Refusing dead pages catches use-after-free at the
        source."""
        pages = list(pages)
        if not self.is_live(pages):
            raise ValueError("addref on a page that is not live")
        self._refs[pages] += 1

    def is_live(self, pages) -> bool:
        """Whether every page currently holds at least one ref — the
        public liveness probe (callers must not read ``_refs``)."""
        return all(self._refs[p] > 0 for p in pages)

    def free(self, pages) -> None:
        """Drop one ref per page; a page returns to the free list at zero.
        Double-frees raise instead of corrupting the free list."""
        for p in pages:
            if p <= 0 or p >= self.num_pages:
                raise ValueError(f"free of invalid page id {p}")
            if self._refs[p] <= 0:
                raise ValueError(f"double free of page {p}")
            self._refs[p] -= 1
            if self._refs[p] == 0:
                self._free.append(p)

    def check_invariants(self) -> None:
        """Accounting self-check (the property tests call this after every
        random op): free + live == capacity, no negative refs, free list
        has no duplicates and no live pages."""
        free = set(self._free)
        if len(free) != len(self._free):
            raise AssertionError("free list contains duplicates")
        if 0 in free:
            raise AssertionError("null page on the free list")
        if (self._refs < 0).any():
            raise AssertionError("negative refcount")
        live = {p for p in range(self.num_pages) if self._refs[p] > 0}
        if live & free:
            raise AssertionError("page both live and free")
        if len(live) + len(free) != self.num_pages - 1:
            raise AssertionError("pages leaked")


class KVPagePool:
    """Device page pools + slot bindings for ONE head's decode layers.

    ``bank=`` builds a pool that SHARES another pool's device page
    arrays and allocator but owns its own slot tables — the
    disaggregated-serving split (genrec_tpu/disagg/): a prefill worker
    writes KV into the bank's pages and a decode worker binds its own
    slots onto the same pages (`admit_shared`, the PR-11 COW machinery
    generalized across pools). The bank and every view must agree on
    page geometry; slot capacity (``max_slots``) is per-view.
    """

    def __init__(self, cfg: PagedConfig, n_layers: int, n_heads: int,
                 head_dim: int, dtype=jnp.float32, bank: "KVPagePool" = None):
        self.cfg = cfg
        self.n_layers = n_layers
        self._bank = bank
        self._placement = None  # sharding_of, once place() has run
        if bank is None:
            self._layout = (n_heads, head_dim, dtype)
            self._k_pools = self._zero_pools()
            self._v_pools = self._zero_pools()
            self.allocator = PageAllocator(cfg.num_pages)
        else:
            if (cfg.num_pages, cfg.page_size, cfg.kv_dtype) != (
                bank.cfg.num_pages, bank.cfg.page_size, bank.cfg.kv_dtype
            ) or n_layers != bank.n_layers:
                raise ValueError(
                    "slot view must match its bank's page geometry and "
                    f"kv_dtype: view {cfg} x {n_layers} layers vs bank "
                    f"{bank.cfg} x {bank.n_layers}"
                )
            self.allocator = bank.allocator
        self.block_tables = np.zeros((cfg.max_slots, cfg.pages_per_slot), np.int32)
        self.seq_lens = np.zeros((cfg.max_slots,), np.int32)
        self._slot_pages: list[list[int] | None] = [None] * cfg.max_slots
        # Pages pinned by reserve_scratch (speculative tree decode):
        # held OUTSIDE slot bookkeeping, never admitted against.
        self._scratch_pages: list[int] = []
        # Min-heap: slots fill LOWEST-INDEX-FIRST so the active set stays
        # quasi-compact and the decode step can run at the smallest slot
        # shape covering max(active index) (the collapsed decode ladder).
        self._free_slots = list(range(cfg.max_slots))
        heapq.heapify(self._free_slots)

    def _zero_pools(self) -> tuple:
        from genrec_tpu.ops.paged import zero_pool

        cfg = self.cfg
        return tuple(
            zero_pool(cfg.num_pages, cfg.page_size, *self._layout, cfg.kv_dtype)
            for _ in range(self.n_layers)
        )

    def device_pools_consumed(self) -> bool:
        """True when a device page array has been deleted under the pool
        — what a DONATING prefill leaves behind when it fails after
        launch. On backends without donation this never happens."""
        import jax

        return any(
            leaf.is_deleted()
            for leaf in jax.tree_util.tree_leaves((self.k_pools, self.v_pools))
        )

    def reset_device_pools(self) -> None:
        """Replace the device page arrays with fresh zeros at the same
        placement. Page CONTENTS are gone; slot and allocator bookkeeping
        is untouched, so the caller evicts whatever read those pages
        first (`_PagedRunner._recover_lost_pools`)."""
        if self._bank is not None:
            self._bank.reset_device_pools()
            return
        self._k_pools = self._zero_pools()
        self._v_pools = self._zero_pools()
        if self._placement is not None:
            self.place(self._placement)

    # Device pools live on the BANK when this pool is a slot view: a
    # prefill executable donates + replaces the bank's arrays, and every
    # view must read the replacement, not a stale reference.
    @property
    def k_pools(self):
        return self._bank.k_pools if self._bank is not None else self._k_pools

    @k_pools.setter
    def k_pools(self, value):
        if self._bank is not None:
            self._bank.k_pools = value
        else:
            self._k_pools = value

    @property
    def v_pools(self):
        return self._bank.v_pools if self._bank is not None else self._v_pools

    @v_pools.setter
    def v_pools(self, value):
        if self._bank is not None:
            self._bank.v_pools = value
        else:
            self._v_pools = value

    def place(self, sharding_of) -> None:
        """Commit the page pools through ``sharding_of(leaf) -> Sharding``
        (parallel.shardings.kv_pool_sharding: the head axis shards over
        the serving mesh, int8 scale planes replicate). Owner pools only
        — a slot view reads its bank's arrays, so the bank is what gets
        placed. Runs BEFORE warmup: aot.sds_tree carries the resulting
        NamedSharding into every prefill/decode/scatter lowering."""
        if self._bank is not None:
            self._bank.place(sharding_of)
            return
        import jax

        self._placement = sharding_of
        put = lambda x: jax.device_put(x, sharding_of(x))  # noqa: E731
        self._k_pools = jax.tree_util.tree_map(put, self._k_pools)
        self._v_pools = jax.tree_util.tree_map(put, self._v_pools)

    @property
    def free_slot_count(self) -> int:
        return len(self._free_slots)

    @property
    def active_slot_count(self) -> int:
        return self.cfg.max_slots - len(self._free_slots)

    def live_slots(self) -> list[int]:
        return [s for s, p in enumerate(self._slot_pages) if p is not None]

    def admit(self, n_tokens: int) -> int:
        """Bind a free slot to pages covering ``n_tokens`` of KV. Returns
        the slot id; raises PoolExhausted (state unchanged) when out of
        slots or pages."""
        if not self._free_slots:
            raise PoolExhausted("no free decode slots")
        pages = self.allocator.alloc(self.cfg.pages_for(n_tokens))  # may raise
        return self._bind_slot(pages, n_tokens)

    def _bind_slot(self, pages: list[int], n_tokens: int) -> int:
        """Pop a free slot and point it at ``pages``. The caller has
        already arranged one alloc ref per page for the slot to own
        (fresh alloc, addref'd share, or a transferred ref) and checked
        ``_free_slots`` — every entry point shares this body so slot
        bookkeeping changes in exactly one place."""
        slot = heapq.heappop(self._free_slots)
        self._slot_pages[slot] = pages
        row = np.zeros(self.cfg.pages_per_slot, np.int32)
        row[: len(pages)] = pages
        self.block_tables[slot] = row
        self.seq_lens[slot] = n_tokens
        return slot

    def evict(self, slot: int) -> None:
        """Release the slot's pages (their last ref, unless shared) and
        return the slot to the free list."""
        pages = self._slot_pages[slot]
        if pages is None:
            raise ValueError(f"evict of inactive slot {slot}")
        self.allocator.free(pages)
        self._slot_pages[slot] = None
        self.block_tables[slot] = 0
        self.seq_lens[slot] = 0
        heapq.heappush(self._free_slots, slot)

    def slot_pages(self, slot: int) -> list[int]:
        """The page run a live slot is bound to (copy — callers must not
        mutate pool bookkeeping)."""
        pages = self._slot_pages[slot]
        if pages is None:
            raise ValueError(f"pages of inactive slot {slot}")
        return list(pages)

    def share_into(self, src_slot: int, dst_slot_tokens: int) -> int:
        """Admit a NEW slot that shares the source slot's pages (COW ref,
        no copy) — the page-remapping hand-off primitive. The new slot
        sees the first ``dst_slot_tokens`` of the shared history, and
        shares (and refs) ONLY the pages that view covers: sharing the
        donor's whole run would pin its tail pages for the new slot's
        entire lifetime even though the view never reads them."""
        pages = self._slot_pages[src_slot]
        if pages is None:
            raise ValueError(f"share from inactive slot {src_slot}")
        if dst_slot_tokens > len(pages) * self.cfg.page_size:
            raise ValueError("shared view exceeds the source slot's pages")
        return self._bind_shared(pages, dst_slot_tokens)

    def admit_shared(self, pages, n_tokens: int) -> int:
        """Admit a NEW slot onto an already-live page run (the prefix
        cache's warm admit: the run is a PrefixIndex entry, not a slot).
        Refs only the pages the ``n_tokens`` view covers, exactly like
        share_into."""
        pages = list(pages)
        if n_tokens > len(pages) * self.cfg.page_size:
            raise ValueError("shared view exceeds the retained page run")
        return self._bind_shared(pages, n_tokens)

    def bind_pages(self, pages, n_tokens: int) -> int:
        """Bind a slot onto pages this caller ALREADY OWNS (their alloc
        ref transfers to the slot — no addref): the serializing-transport
        admit path, where a handoff's KV content was scattered into
        freshly allocated pages of the receiving pool. Evicting the slot
        drops the transferred ref like any admit. State unchanged on
        error (no free slot raises before ownership moves)."""
        pages = list(pages)
        if n_tokens > len(pages) * self.cfg.page_size:
            raise ValueError("bound view exceeds the page run")
        if not self.allocator.is_live(pages):
            raise ValueError("bind_pages on a page that is not live")
        if not self._free_slots:
            raise PoolExhausted("no free decode slots")
        return self._bind_slot(pages, n_tokens)

    def _bind_shared(self, pages: list[int], n_tokens: int) -> int:
        if not self._free_slots:
            raise PoolExhausted("no free decode slots")
        cover = pages[: self.cfg.pages_for(n_tokens)]
        self.allocator.addref(cover)  # may raise; slot state untouched
        return self._bind_slot(list(cover), n_tokens)

    def reserve_scratch(self, n_pages: int) -> np.ndarray:
        """Pin ``n_pages`` for speculative tree verification and return
        them as a block-table-shaped row set — the landing zone the TPU
        tree-verify kernel appends candidate-tree K/V into (the pure-JAX
        fallback carries tree K/V as in-call dense arrays and leaves the
        reserved pages untouched). The pages hold one allocator ref each
        (reflected in pages_in_use / the HBM ledger's pool bytes) and
        can never collide with an admission — which is what makes a
        rejected tree's rollback a no-op on the pool: speculation and
        slot state share no pages. Idempotence/stacking is the caller's
        job (the engine reserves once at warmup); ``release_scratch``
        undoes it (drain/stop, so pools account clean at shutdown)."""
        if n_pages <= 0:
            return np.zeros((0,), np.int32)
        pages = self.allocator.alloc(int(n_pages))  # may raise: size the
        self._scratch_pages.extend(pages)           # config to include it
        return np.asarray(pages, np.int32)

    def release_scratch(self) -> int:
        """Drop every scratch reservation (their last refs). Returns the
        number of pages released."""
        n = len(self._scratch_pages)
        if n:
            self.allocator.free(self._scratch_pages)
            self._scratch_pages = []
        return n

    @property
    def scratch_page_count(self) -> int:
        return len(self._scratch_pages)

    def check_invariants(self) -> None:
        """Property-test hook: allocator accounting holds AND no page is
        bound by two live slots unless deliberately shared (refcount >=
        the number of slots binding it)."""
        self.allocator.check_invariants()
        bound: dict[int, int] = {}
        for pages in self._slot_pages:
            for p in pages or ():
                bound[p] = bound.get(p, 0) + 1
        for p, n in bound.items():
            if self.allocator._refs[p] < n:
                raise AssertionError(
                    f"page {p} bound by {n} slots but holds "
                    f"{self.allocator._refs[p]} refs (aliasing without a ref)"
                )

    def stats(self) -> dict:
        """Operator gauges (serving/metrics.py forwards these)."""
        return {
            "pages_in_use": self.allocator.pages_in_use,
            "pages_free": self.allocator.pages_free,
            "scratch_pages": len(self._scratch_pages),
            "slots_active": self.active_slot_count,
            "slots_total": self.cfg.max_slots,
            "kv_tokens_resident": int(self.seq_lens.sum()),
            "kv_dtype": self.cfg.kv_dtype,
        }


# ---------------------------------------------------------------------------
# Cross-request prefix cache (the warm-prefix store over the COW pool)
# ---------------------------------------------------------------------------


class PrefixEntry:
    """One retained page run: the KV a finished request prefilled, kept
    alive by a COW ref so the SAME token-aligned history can be admitted
    again without paying prefill.

    ``init`` is the donor's post-prefill slot-state rows (host numpy) —
    what a warm admission restores instead of running the prefill
    executable; None for heads whose prefill leaves the state zeroed
    (TIGER). For a head with recurrent layers it is the SNAPSHOT a warm
    admit needs beside the pages: each such layer's end state and
    convolution tails, megabytes where a beam's numbers are bytes, so
    ``init_nbytes`` counts it. ``bucket`` records the donor's prefill
    (B, L) for the response's provenance field."""

    __slots__ = ("key", "n_tokens", "pages", "init", "init_nbytes", "bucket",
                 "hits")

    def __init__(self, key, n_tokens, pages, init=None, bucket=None):
        self.key = tuple(key)
        self.n_tokens = int(n_tokens)
        self.pages = list(pages)
        self.init = init
        self.init_nbytes = sum(
            int(np.asarray(v).nbytes) for v in (init or {}).values())
        self.bucket = bucket
        self.hits = 0


class _RadixNode:
    __slots__ = ("children", "entry")

    def __init__(self):
        self.children: dict = {}
        self.entry: PrefixEntry | None = None


class PrefixIndex:
    """Radix (token-trie) index of retained page runs, LRU-ordered.

    Keys are token-aligned history tuples (the head's
    ``prefix_key_tokens``); the trie rolls the key one token per level —
    the incremental-hash structure of the vLLM/SGLang radix caches — so
    ``lookup`` reports both the exact entry (admissible: full-history
    match, the only reuse tier that is numerically exact for BOTH
    serving head families — see docs/SERVING.md "Prefix cache") and the
    longest retained prefix depth (observability: how warm the traffic
    WOULD be at page-granularity suffix reuse).

    The index owns one allocator ref per retained page (taken at
    ``insert``, dropped at eviction), so a retained run survives its
    donor slot's eviction and is freed the moment the last holder lets
    go — the same COW discipline beams use. Retained entries are a
    reclaimable pool: ``reclaim`` drops LRU entries until the allocator
    can satisfy a demand, which the engine runs BEFORE deferring any
    admission. Single-threaded by contract (batcher thread), like the
    pool it fronts."""

    def __init__(self, allocator: PageAllocator, max_entries: int = 4096):
        if max_entries <= 0:
            raise ValueError(f"max_entries {max_entries} must be positive")
        self._alloc = allocator
        self._max_entries = int(max_entries)
        self._root = _RadixNode()
        # LRU: key -> entry, oldest first. Python's dict preserves
        # insertion order; move-to-end on touch keeps it an LRU list.
        self._lru: collections.OrderedDict[tuple, PrefixEntry] = (
            collections.OrderedDict()
        )
        self._retained_pages = 0
        self._snapshot_bytes = 0  # of the entries' ``init`` rows (host)

    def __len__(self) -> int:
        return len(self._lru)

    @property
    def retained_pages(self) -> int:
        """Page refs the index holds (entries never share pages with
        each other: each run came from one donor prefill)."""
        return self._retained_pages

    def entries(self) -> list[PrefixEntry]:
        """The retained entries, least recently used first."""
        return list(self._lru.values())

    def lookup(self, key) -> tuple[PrefixEntry | None, int]:
        """(exact entry or None, matched token depth). Only a FULL-key
        match returns an entry; a proper-prefix match reports its depth
        so hit-rate telemetry can show near-miss warmth."""
        key = tuple(key)
        node, path = self._root, [self._root]
        for tok in key:
            node = node.children.get(tok)
            if node is None:
                break
            path.append(node)
        if len(path) - 1 == len(key) and path[-1].entry is not None:
            return path[-1].entry, len(key)
        # Deepest RETAINED prefix at or above where the walk ended.
        for depth in range(len(path) - 1, 0, -1):
            if path[depth].entry is not None:
                return None, depth
        return None, 0

    def touch(self, key) -> None:
        """Refresh an entry's LRU position (called on every warm hit)."""
        self._lru.move_to_end(tuple(key))

    def insert(self, key, n_tokens: int, pages, *, init=None,
               bucket=None) -> PrefixEntry:
        """Retain a page run under ``key`` (one allocator ref per page —
        the pages must be live, i.e. still bound by the donor slot). An
        existing entry for the key is REPLACED (its refs dropped): the
        fresh run supersedes it. Over ``max_entries`` the LRU entry is
        evicted first, so host-side index memory stays bounded."""
        key = tuple(key)
        existing = self._lru.get(key)
        if existing is not None:
            self.remove(key)
        while len(self._lru) >= self._max_entries:
            self._evict_lru()
        entry = PrefixEntry(key, n_tokens, pages, init=init, bucket=bucket)
        self._alloc.addref(entry.pages)
        node = self._root
        for tok in key:
            node = node.children.setdefault(tok, _RadixNode())
        node.entry = entry
        self._lru[key] = entry
        self._retained_pages += len(entry.pages)
        self._snapshot_bytes += entry.init_nbytes
        return entry

    def remove(self, key) -> PrefixEntry | None:
        """Drop one entry (and its page refs); prunes emptied trie nodes."""
        key = tuple(key)
        entry = self._lru.pop(key, None)
        if entry is None:
            return None
        self._release(entry)
        path = [self._root]
        for tok in key:
            path.append(path[-1].children[tok])
        path[-1].entry = None
        for i in range(len(key), 0, -1):  # prune childless, entry-less tail
            node, parent = path[i], path[i - 1]
            if node.children or node.entry is not None:
                break
            del parent.children[key[i - 1]]
        return entry

    def _release(self, entry: PrefixEntry) -> None:
        self._alloc.free(entry.pages)
        self._retained_pages -= len(entry.pages)
        self._snapshot_bytes -= entry.init_nbytes

    def _evict_lru(self) -> PrefixEntry:
        key = next(iter(self._lru))
        return self.remove(key)

    def reclaim(self, pages_needed: int) -> int:
        """Evict entries (LRU-first) until the allocator has
        ``pages_needed`` free pages or nothing evictable remains.
        Returns entries evicted. Entries whose pages are ALL still bound
        elsewhere (a live decode slot holds another ref) are SKIPPED,
        not sacrificed: evicting them frees no pages now, so dropping
        them would wipe warm state for zero relief — they stay retained
        and become evictable once their donors finish."""
        evicted = 0
        while self._alloc.pages_free < pages_needed:
            victim = next(
                (key for key, e in self._lru.items()
                 if any(self._alloc._refs[p] == 1 for p in e.pages)),
                None,
            )
            if victim is None:
                break
            self.remove(victim)
            evicted += 1
        return evicted

    def clear(self) -> int:
        """Drop every entry (params/catalog swap invalidation, drain)."""
        n = len(self._lru)
        for entry in self._lru.values():
            self._release(entry)
        self._lru.clear()
        self._root = _RadixNode()
        return n

    def stats(self) -> dict:
        """Index gauges (the runner adds byte figures from pool geometry)."""
        return {
            "entries": len(self._lru),
            "retained_pages": self._retained_pages,
            # The entries' state snapshots: rows kept on the HOST (a warm
            # admit stages them), so none of these bytes are device bytes.
            "snapshot_bytes": self._snapshot_bytes,
            "snapshot_device_bytes": 0,
        }
