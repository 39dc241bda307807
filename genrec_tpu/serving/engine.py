"""In-process online inference engine: queue -> micro-batch -> executable.

The request path (ROADMAP north star: "serves heavy traffic"):

1. `submit(Request)` enqueues into the head's queue and returns a Future.
2. The batcher thread flushes a queue when it holds `max_batch` requests
   OR its oldest request has waited `max_wait_ms` (dynamic micro-batching:
   full batches under load, bounded latency when idle).
3. The micro-batch is padded UP to a (batch, history) bucket from the
   `BucketLadder` and dispatched to the executable AOT-compiled for that
   bucket at warmup — steady state never compiles (the engine counts
   compiles; scripts/check_serving_hlo.py asserts zero after warmup).
4. Outputs are split per-request, futures resolve, and queue-wait /
   compute / total latencies land in the metrics histograms.

Generative (paged) heads replace steps 3-4 with slot-level continuous
batching (`_PagedRunner`): requests are ADMITTED into free decode slots
(a bucketed prefill writes their history K/V into the fixed-budget page
pool of serving/kv_pool.py), every batcher iteration advances ALL active
slots one decode position through one fixed-shape executable with
per-slot step operands, and finished slots EVICT mid-decode — freeing
pages for the next admission without waiting for their co-admitted
batch. Decode-side compile surface: a handful of
(slot-count, pages_per_slot) shapes per head instead of the whole
bucket grid.

Hot checkpoint reload: a watcher thread polls a checkpoint directory of
params-only steps (published by the trainer or a sidecar) and restores
strictly NEWER steps through `CheckpointManager.restore_latest_valid` —
the PR-3 integrity ladder, so a half-written or garbled step is
quarantined and the engine keeps serving the previous valid params. The
restored tree is staged and swapped in by the batcher BETWEEN
micro-batches (never mid-batch), so every request is answered by exactly
one params version, reported as `Response.params_step`.

Hot CATALOG swap (the live-catalog subsystem, genrec_tpu/catalog/):
catalog heads take their legal-item trie as a RUNTIME OPERAND
(`head.runtime_operands()`, threaded between params and the batch in
every compiled call), so one executable serves any same-rung
`CatalogSnapshot`. `stage_catalog()` — or a `CatalogWatcher` polling a
snapshot directory (serving/catalog.py) — validates the snapshot (aval
check against the live trie; a garbled file is quarantined, mirroring
the params ladder) and stages it; the batcher applies it BETWEEN
micro-batches, after paged slots drain, so no request ever mixes two
catalog versions (`Response.catalog_version` beside `params_step`).
Growth past a capacity rung changes the trie aval: the staging path
precompiles replacement executables AOT on the staging thread (counted
as `catalog_compiles`, never as steady-state recompilations) and the
swap installs them atomically — the hot path never compiles.

Graceful drain: a one-shot `PreemptionGuard` latches SIGTERM/SIGINT.
On fire the engine finishes every in-flight and queued request, rejects
new submissions with the typed `DrainingError`, and stops; a second
signal falls through to the restored previous handlers (the PR-3
one-shot escalation contract).

Compiled executables are AOT (`jax.jit(fn).lower(...).compile()`), so a
shape drifting out of the bucket grid raises loudly instead of silently
recompiling; the params swap keeps avals identical (same tree, same
shapes/dtypes), which `_check_like` verifies before staging.

Observability (genrec_tpu/obs, docs/OBSERVABILITY.md): with a tracer
attached (``tracer=`` or ``set_tracer`` live) every request carries a
span tree — request -> queue_wait -> admission/prefill/per-decode_step
(paged) or compute (dense) -> finalize — keyed by the request ID minted
at submit() (`Response.request_id`), with p99-outlier exemplars
persisted past ring eviction — and the batcher thread records each host
phase of an iteration ONCE on a lane of its own, `batcher/<head>`
(`admit.pop`, `prefill.stage/launch/pull/retain`,
`decode.stage/launch/pull/sweep`, `batcher.idle_wait`), committed after
the iteration's per-request spans so that a device-idle gap reads by its
cause. Tracing is off by default (one attribute check per site; budget
pinned <2% by scripts/check_obs.py). The slot and prefill occupancy
counters of `ServingMetrics` count whether or not a tracer is on. The flight
recorder gets lifecycle/drain/hot-reload/OOM-deferral events regardless.

Device-memory ledger (obs/memory.py): warmup sums every compiled
executable's XLA memory analysis with the logical runtime operands
(params, KV page pools, catalog trie, paged slot state) into a per-head
HBM model. ``hbm_budget_bytes=`` makes it a gate — an over-budget
config is REFUSED at warmup with a per-component breakdown
(`HBMBudgetError`) instead of OOMing on hardware; the gauges ride every
stats() snapshot into Prometheus/operator lines.

Cross-request KV prefix cache (serving/kv_pool.PrefixIndex,
docs/SERVING.md "Prefix cache"): every cold prefill retains its page
run (COW ref) in a per-head radix index keyed by the token-aligned
history; a repeat request whose FULL key matches shares those pages and
restores the donor's post-prefill slot state — admission straight into
decode, no prefill executable call, zero compile-surface change.
Retained pages are an LRU pool reclaimed before any admission defers,
appear as the ledger's reclaimable component, and the index empties on
params swap, catalog swap, and drain (a cached prefix from an old
version must never serve the new one). ``prefix_cache=False`` restores
the always-cold PR-6 behavior.

SLO guard (obs/slo.py): ``slo_targets=`` declares per-head p99 /
queue-depth / OOM-deferral-rate objectives. The batcher polls the
monitor off the hot path; a SUSTAINED breach sheds load — new
submissions get the typed recoverable `OverloadError` while in-flight
and queued work completes (the drain discipline, reversible) — and
hysteresis un-sheds once the targets hold again. Zero effect on the
compiled surface: shedding is pure host-side admission control.
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import threading
import time
from concurrent.futures import Future
from typing import Optional, Sequence

import jax
import numpy as np

from genrec_tpu.core import chaos
from genrec_tpu.obs.flight_recorder import get_flight_recorder
from genrec_tpu.obs.goodput import CompileEvents
from genrec_tpu.obs.memory import MemoryLedger, tree_nbytes
from genrec_tpu.obs.slo import SLOMonitor, SLOTarget
from genrec_tpu.obs.spans import NULL_TRACER, SpanTracer
from genrec_tpu.serving.buckets import BucketLadder, default_ladder
from genrec_tpu.serving.kv_pool import (
    KVPagePool,
    PagedConfig,
    PoolExhausted,
    PrefixIndex,
)
from genrec_tpu.serving.metrics import ServingMetrics
from genrec_tpu.serving.types import (
    DrainingError,
    HBMBudgetError,
    OverloadError,
    Request,
    Response,
    UnknownHeadError,
    normalize_spec_config,
)


from genrec_tpu.serving.aot import donate_argnums as _donate_argnums
from genrec_tpu.serving.aot import named as _named
from genrec_tpu.serving.aot import sds_tree as _sds
from genrec_tpu.serving.slots import SlotTable, stage as _stage


def _operand_avals(operands) -> tuple:
    """Shape/dtype signature of a runtime-operand tuple — the facts that
    decide whether compiled executables accept it (stage_catalog's
    rung-change test, generalized from TensorTrie.aval_signature so
    non-trie catalog operands — NoteLLM's scoring bank — participate)."""
    return tuple(
        (tuple(int(s) for s in leaf.shape), str(leaf.dtype))
        for leaf in jax.tree_util.tree_leaves(operands)
    )


def is_transient_fs_error(e: BaseException) -> bool:
    """Classify a poll-loop failure as a transient filesystem condition
    (an NFS blip, a listing racing a writer's mid-rename window, a stale
    handle) vs a real bug. Shared by the engine's checkpoint watcher and
    the rollout controller's publish-dir poll (serving/rollout.py): a
    transient error is retried with backoff, never treated as "no new
    step"."""
    return isinstance(e, OSError)


class _PagedRunner:
    """Slot-level continuous batching for ONE paged generative head.

    The PR-5 engine decoded a whole micro-batch per executable call:
    requests admitted together finished together, and the KV cache was a
    dense (bucket-batch x bucket-history) tensor per executable. This
    runner replaces that for heads implementing the paged protocol
    (serving/heads.py): the head's history K/V lives in a fixed-budget
    page pool (serving/kv_pool.py), prefill stays on the (batch, history)
    bucket ladder but WRITES its K/V straight into pages, and decode is
    a fixed-shape step over the slot set that every batcher iteration
    advances by one position — requests are admitted into free slots and
    evicted on finish MID-decode, so the decode side's compile surface
    collapses from the whole bucket grid to a handful of
    (slot-count, pages_per_slot) shapes.

    All methods run on the batcher thread (same single-writer discipline
    as the executable cache); slot state (serving/slots.py) and pools
    stay device-resident between steps.
    """

    def __init__(self, engine: "ServingEngine", head, cfg: PagedConfig):
        max_kv = head.paged_kv_tokens(10**9, engine._ladder.history_buckets[-1])
        if cfg.max_kv_tokens < max_kv:
            raise ValueError(
                f"paged config holds {cfg.max_kv_tokens} KV tokens/slot but "
                f"head {head.name!r} needs {max_kv} at the largest history "
                "bucket; raise pages_per_slot or page_size"
            )
        self.engine = engine
        self.head = head
        # Speculative tree decode (docs/SERVING.md "Speculative
        # decoding"): opt-in per engine (or per head via a name set).
        # One static topology (beams x fanout x spec_depth) for the
        # whole runner — every slot-count rung compiles the same tree.
        spec_cfg = engine._spec_decode
        want_spec = (
            head.name in spec_cfg
            if isinstance(spec_cfg, (set, frozenset, list, tuple))
            else bool(spec_cfg)
        )
        # A head refuses, by name, what its paged path does not implement.
        head.paged_check_options(kv_dtype=cfg.kv_dtype, spec_decode=want_spec,
                                 mesh=engine._mesh)
        self.spec_topology = None
        if (want_spec and getattr(head, "supports_spec", False)
                and head.spec_depth >= 1):
            from genrec_tpu.ops.spec_tree import TreeTopology

            # BEFORE state/prefill construction: the head may extend its
            # slot state + prefill with drafter hints.
            head.enable_spec_drafting()
            self.spec_topology = TreeTopology(
                head.top_k, engine._spec_fanout, head.spec_depth
            )
            # Scratch-page reservation: the landing zone a TPU
            # tree-verify kernel appends candidate-tree K/V into, pinned
            # so speculation can never compete with admissions. The pool
            # budget is EXTENDED by the reservation (an explicit
            # paged_config keeps its admission capacity; the ledger sees
            # the real total).
            per_slot = -(-self.spec_topology.n_nodes // cfg.page_size)
            self._scratch_demand = cfg.max_slots * per_slot
            cfg = dataclasses.replace(
                cfg, num_pages=cfg.num_pages + self._scratch_demand
            )
        else:
            self._scratch_demand = 0
        self.cfg = cfg
        n_layers, n_heads, head_dim, dtype = head.paged_layout()
        self.pool = KVPagePool(cfg, n_layers, n_heads, head_dim, dtype)
        if engine._mesh is not None:
            from genrec_tpu.parallel.shardings import kv_pool_sharding

            # Shard the KV page BANK over the head axis: paged attention
            # is independent per head, so the pools (the biggest serving
            # operand after the item table) split n-fold with no
            # cross-device traffic inside the attention read. Placement
            # rides into the AOT lowering via aot.sds_tree; a mesh that
            # cannot shard n_heads keeps the pool replicated (and
            # kv_pool_sharding returns None rather than pretending).
            place = kv_pool_sharding(
                engine._mesh, n_heads, engine._model_axis
            )
            if place is not None:
                self.pool.place(place)
        self._scratch_tables = self.pool.reserve_scratch(self._scratch_demand)
        # The decode side of the slot set (serving/slots.py): state rows,
        # step counters, the rung ladder (max_slots halving down to
        # max_batch) with its executables, and the step itself.
        self.slots = SlotTable(
            head, self.pool, floor=engine._max_batch, mesh=engine._mesh,
            spec_topology=self.spec_topology, spec_fanout=engine._spec_fanout,
        )
        # (req, fut, t_enq, trace_ctx, t_admit); trace_ctx is the
        # (trace_id, request_span_id, upstream_parent_span_id) adopted/
        # minted at submit(), or None (tracing off, no incoming trace).
        self.entries: list = [None] * cfg.max_slots
        self.buckets: list = [None] * cfg.max_slots  # prefill (B, L) per slot
        self._prefill: dict[tuple[int, int], object] = {}
        # The batcher's own lane of the span ring (docs/OBSERVABILITY.md
        # "The batcher lane"): each host phase of an iteration ONCE, as
        # (name, t0, t1, attrs), buffered here by admit() and step() and
        # committed by flush_phases() when the iteration is over.
        self.lane = f"batcher/{head.name}"
        self._phases: list[tuple] = []
        # Futures already counted as OOM-deferred: the gauge counts
        # REQUESTS deferred, not per-batcher-iteration retries.
        self._oom_counted: set[int] = set()
        # Cross-request prefix cache (docs/SERVING.md "Prefix cache"):
        # finished requests retain their prefilled page runs (COW ref)
        # in a radix index keyed by the head's token-aligned history
        # key; a repeat request with a FULL-key match shares those pages
        # (admit_shared) and restores the donor's post-prefill state —
        # no prefill executable call, zero compile-surface change.
        # Retained pages are an LRU pool reclaimed before any admission
        # defers, and the index empties on params/catalog swap + drain.
        self.prefix: PrefixIndex | None = (
            PrefixIndex(self.pool.allocator,
                        max_entries=engine._prefix_cache_entries)
            if engine._prefix_cache else None
        )
        # Device bytes one page pins across layers and K+V pools — the
        # retained-bytes gauge + ledger reclaimable component.
        self._page_nbytes = (
            tree_nbytes((self.pool.k_pools, self.pool.v_pools))
            // cfg.num_pages
        )

    @property
    def idle(self) -> bool:
        return self.slots.idle

    # -- compilation ---------------------------------------------------------

    def warmup(self) -> None:
        """One decode executable per slot rung + the prefill bucket grid.
        Everything else the dense path compiled per bucket (the whole
        generate loop) is gone from the decode side. A speculative
        runner's rungs hold the tree-verify step INSTEAD of the plain
        step (accept >= 1 always, so no plain-step fallback executable
        is needed: the verified-rejection worst case IS the plain step)."""
        self.slots.executables = self._compile_rungs()
        self.slots.compile_writer()
        self.engine.metrics.record_compile()
        for B, L in self.engine._ladder.combos():
            self._prefill[(B, L)] = self._compile_prefill(B, L)

    def _compile_rungs(self, operands=None, catalog_compile=False) -> dict:
        eng = self.engine
        out = {}
        for S in self.slots.rungs:
            out[S] = self.slots.compile(
                S, eng._select(self.head, eng._params), operands
            )
            eng.metrics.record_compile(catalog=catalog_compile)
        return out

    def _compile_prefill(self, B: int, L: int, operands=None,
                         catalog_compile=False):
        eng = self.engine
        fn = _named(self.head.make_prefill_paged_fn(B, L),
                    f"{self.head.name}_prefill_b{B}_l{L}")
        ops = operands if operands is not None else self.head.runtime_operands()
        batch = self.head.make_batch([self.head.dummy_request()], B, L)
        n = 1 + len(ops) + len(batch)  # params + operands + batch
        args = (
            eng._select(self.head, eng._params),
            *(_sds(op) for op in ops),
            *(_sds(b) for b in batch),  # aval-only: never pins a device
            jax.ShapeDtypeStruct((B, self.cfg.pages_per_slot), np.int32),
            _sds(self.pool.k_pools),
            _sds(self.pool.v_pools),
        )
        compiled = jax.jit(
            fn, donate_argnums=_donate_argnums(n + 1, n + 2)  # k_pools, v_pools
        ).lower(*args).compile()
        eng.metrics.record_compile(catalog=catalog_compile)
        return compiled

    # -- admission (prefill into pages) --------------------------------------

    def admit(self) -> bool:
        """Drain the head's queue into free slots, one bucketed prefill
        micro-batch at a time. Each popped request is first looked up in
        the prefix index: a warm FULL-history hit shares the retained
        pages (admit_shared) and skips prefill entirely; the rest go
        through the bucketed prefill as before. Requests that don't fit
        (no free slot or no free pages even after reclaiming retained
        prefix pages) STAY QUEUED — they retry as evictions free pages —
        and the deferral is counted (metrics.oom_deferred_admits)."""
        eng = self.engine
        progressed = False
        while True:
            budget = min(self.pool.free_slot_count, eng._max_batch)
            if budget == 0:
                return progressed
            now = time.monotonic()
            with eng._lock:
                q = eng._queues[self.head.name]
                if not q:
                    return progressed
                # Coalesce trickling arrivals into bucket-sized prefills
                # (the dense batcher's deadline discipline): admitting
                # one-by-one would pay a prefill dispatch + a decode step
                # per request. Deadline, drain, or a full group flushes.
                if (
                    len(q) < budget
                    and now - q[0][2] < eng._max_wait_s
                    and not eng._draining
                ):
                    return progressed
                entries = [q.popleft() for _ in range(min(len(q), budget))]
            warm, cold, holdback = self._split_warm(entries)
            if holdback:
                # Duplicate-key holdback (in-flight prefix matching): an
                # identical request co-popped with its donor would miss
                # and prefill redundantly; requeued at the front, it
                # returns NEXT iteration — after the donor's prefill has
                # retained the run — and admits warm. Strictly less work
                # than prefilling, one batcher iteration of extra wait.
                with eng._lock:
                    eng._queues[self.head.name].extendleft(
                        reversed(holdback)
                    )
            for e, centry, own_L in warm:
                # Slot availability is guaranteed (popped <= budget <=
                # free slots) and a warm admit allocates NO pages.
                self._warm_admit(e, centry, own_L, t_pop=now)
                progressed = True
            if warm:
                self._publish_prefix_gauges()
                self._sweep_finished()  # init step == total finishes here
            slots, admitted = [], []
            L = eng._ladder.history_bucket(
                max(max((self.head.natural_len(e[0]) for e, _k, _n in cold),
                        default=1), 1)
            )
            for e, key, n_tok in cold:
                try:
                    slots.append(self._admit_pages(n_tok))
                    admitted.append((e, key))
                except PoolExhausted:
                    break
            leftover = [e for e, _k, _n in cold[len(admitted):]]
            if eng._tracer.enabled:
                self._phases.append((
                    "admit.pop", now, time.monotonic(),
                    {"warm": len(warm), "cold": len(admitted),
                     "deferred": len(leftover)},
                ))
            if leftover:  # out of pages: requeue at the FRONT (FIFO order)
                with eng._lock:
                    eng._queues[self.head.name].extendleft(reversed(leftover))
                fresh = [e for e in leftover if id(e[1]) not in self._oom_counted]
                if fresh:  # count each request's deferral ONCE, not per retry
                    self._oom_counted.update(id(e[1]) for e in fresh)
                    eng.metrics.record_oom_admit(len(fresh),
                                                 head=self.head.name)
                    eng._flight.record(
                        "pool_oom_deferred", head=self.head.name,
                        n=len(fresh), pages_free=self.pool.stats().get("pages_free"),
                    )
            if admitted:
                self._oom_counted.difference_update(
                    id(e[1]) for e, _k in admitted
                )
                try:
                    self._run_prefill(
                        [e for e, _k in admitted], slots, L, t_pop=now,
                        keys=[k for _e, k in admitted],
                    )
                except Exception as e:  # noqa: BLE001 — fail THESE futures only
                    eng._log.exception(
                        f"serving: paged prefill on head {self.head.name} failed"
                    )
                    for slot in slots:
                        self.pool.evict(slot)
                        # Undo any slot bookkeeping a partial prefill set,
                        # or step() would decode an entry-less slot.
                        self.slots.release(slot)
                        self.entries[slot] = None
                        self.buckets[slot] = None
                    if self.pool.device_pools_consumed():
                        self._recover_lost_pools(e)
                    # Futures last: a caller that sees the failure sees an
                    # engine already fit to take its retry.
                    for (_req, fut, _t, _tr), _k in admitted:
                        if not fut.done():
                            fut.set_exception(e)
                    eng.metrics.record_failure(len(admitted))
                progressed = True
            if leftover:
                return progressed

    def _recover_lost_pools(self, cause: BaseException) -> None:
        """The prefill donates the page pools, so one that fails AFTER
        launch has consumed them: `pool.k_pools` / `v_pools` name deleted
        buffers, and with them went the K/V of every resident slot and
        every retained prefix run. Left alone, every later prefill and
        decode call raises "Array has been deleted" while the engine
        keeps admitting. Fail the resident requests with the cause, drop
        the prefix cache, and carry on from fresh zero pools."""
        eng = self.engine
        lost = [int(s) for s in self.slots.active_slots()]
        for slot in lost:
            fut = self.entries[slot][1]
            self.pool.evict(slot)
            self.slots.release(slot)
            self.entries[slot] = None
            self.buckets[slot] = None
            if not fut.done():
                fut.set_exception(cause)
        eng.metrics.record_failure(len(lost))
        eng.metrics.record_evict(len(lost))
        self.clear_prefix_cache("kv_pools_lost")
        self.pool.reset_device_pools()
        eng.metrics.set_pool_gauges(self.head.name, self.pool_stats())
        eng._flight.record("kv_pools_lost", head=self.head.name,
                           slots_failed=len(lost))
        eng._log.error(
            f"serving: failed prefill on head {self.head.name} consumed the "
            f"donated KV page pools; failed {len(lost)} resident request(s) "
            "and reset the pools"
        )

    # -- cross-request prefix cache ------------------------------------------

    def _split_warm(self, entries):
        """Partition popped queue entries into warm full-history hits
        and cold admissions. Warm/cold membership is decided per request
        against the request's OWN history bucket (what a cold engine
        serving it solo would compile against), so a hit reproduces the
        solo cold answer bit-for-bit."""
        eng = self.engine
        head = self.head
        warm, cold, holdback = [], [], []
        group_cold_keys: set = set()
        max_hist = eng._ladder.history_buckets[-1]
        for e in entries:
            req = e[0]
            own_L = eng._ladder.history_bucket(max(head.natural_len(req), 1))
            n_tok = head.paged_kv_tokens(head.natural_len(req), own_L)
            key = (
                head.prefix_key_tokens(req, max_hist)
                if self.prefix is not None else None
            )
            if key is None:
                cold.append((e, None, n_tok))
                continue
            if key in group_cold_keys:
                # An identical request is already going COLD in this
                # group: hold this one back one iteration so it lands
                # warm on the donor's freshly retained run (no lookup
                # counted — it will be looked up for real next pass).
                holdback.append(e)
                continue
            t0 = time.monotonic()
            centry, matched = self.prefix.lookup(key)
            if centry is not None and centry.n_tokens != n_tok:
                # Same key but a different KV footprint (dead ids dropped
                # from the key while natural_len still counts them): the
                # retained run is not this request's prefill. Cold.
                centry = None
            outcome = (
                "hit" if centry is not None
                else ("partial" if matched else "miss")
            )
            # An OOM-deferred request is re-popped (and re-looked-up)
            # every batcher retry: record its lookup outcome ONCE, or a
            # pressure episode would spam misses into the warm-hit rate
            # the bench gate pins (hits from a retry stay silent too —
            # its one recorded outcome was the miss that deferred it).
            if id(e[1]) not in self._oom_counted:
                eng.metrics.record_prefix_lookup(
                    head.name, outcome,
                    tokens=centry.n_tokens if centry is not None else 0,
                )
                tr = e[3]
                if tr is not None:
                    eng._tracer.record_span(
                        "prefix_lookup", tr[0], t0, time.monotonic(),
                        parent_id=tr[1], outcome=outcome,
                        matched_tokens=int(matched), **eng._span_ident(),
                    )
            if centry is not None:
                warm.append((e, centry, own_L))
            else:
                group_cold_keys.add(key)
                cold.append((e, key, n_tok))
        return warm, cold, holdback

    def _warm_admit(self, e, centry, own_L: int, t_pop: float) -> None:
        """Admit one request onto a retained page run: COW-share the
        pages, restore the donor's post-prefill state rows, enter decode
        at the head's init step. The prefill executable never runs —
        that is the whole win."""
        eng = self.engine
        head = self.head
        # A previously deferred request can admit WARM once a donor's
        # run lands: clear its deferral marker or the stale id would
        # leak (and could suppress a later request's deferral count
        # after CPython reuses the id).
        self._oom_counted.discard(id(e[1]))
        t0 = time.monotonic()
        slot = self.pool.admit_shared(centry.pages, centry.n_tokens)
        self.prefix.touch(centry.key)
        centry.hits += 1
        t_bind = time.monotonic()
        self.slots.bind(
            slot,
            head.paged_warm_state(centry.init, centry.n_tokens, own_L)
            if centry.init is not None else None,
        )
        if self.slots.recurrent_nbytes and centry.init is not None:
            # A head with recurrent leaves: the snapshot is megabytes, not
            # a beam's few numbers, so its rows go to the device NOW, and
            # the lane shows what the restore cost (`admit.restore_state`).
            # Every other head's rows wait for the next step's flush.
            staged = self.slots.flush()
            if eng._tracer.enabled:
                self._phases.append((
                    "admit.restore_state", t_bind, time.monotonic(),
                    {"snapshot_bytes": centry.init_nbytes,
                     "staged_bytes": staged},
                ))
        t_admit = time.monotonic()
        self.entries[slot] = (*e, t_admit)
        self.buckets[slot] = centry.bucket
        tr = e[3]
        if tr is not None:
            # Same span tree as the cold path, with `warm_admit` where
            # `prefill` would be — trace_report shows warm-vs-cold
            # prefill phases side by side.
            tid, root = tr[0], tr[1]
            tracer = eng._tracer
            ident = eng._span_ident()
            tracer.record_span("queue_wait", tid, e[2], t_pop,
                               parent_id=root, **ident)
            tracer.record_span("admission", tid, t_pop, t0,
                               parent_id=root, slot=int(slot), **ident)
            tracer.record_span("warm_admit", tid, t0, t_admit,
                               parent_id=root,
                               warm_tokens=int(centry.n_tokens), **ident)
        eng.metrics.record_admit(1)

    def _admit_pages(self, n_tok: int) -> int:
        """pool.admit with the reclaim ladder: when the allocator cannot
        satisfy the demand, retained prefix pages are evicted LRU-first
        and the admit retried — an admission is DEFERRED only when even
        an empty cache could not fit it (pages pinned by live slots)."""
        try:
            return self.pool.admit(n_tok)
        except PoolExhausted:
            if self.prefix is None or not len(self.prefix):
                raise
            evicted = self.prefix.reclaim(self.cfg.pages_for(n_tok))
            if evicted:
                self.engine.metrics.record_prefix_evict(
                    self.head.name, evicted
                )
                self._publish_prefix_gauges()
            return self.pool.admit(n_tok)  # may still raise: defer

    def pool_stats(self) -> dict:
        """The pool's gauges and, beside them, the bytes of the slot
        table's recurrent leaves (state that is not KV: on the device for
        as long as the table is, whatever the slots hold)."""
        return {**self.pool.stats(),
                "recurrent_state_bytes": self.slots.recurrent_nbytes}

    def prefix_stats(self) -> dict:
        if self.prefix is None:
            return {}
        s = self.prefix.stats()
        s["retained_bytes"] = s["retained_pages"] * self._page_nbytes
        return s

    def _publish_prefix_gauges(self) -> None:
        if self.prefix is None:
            return
        s = self.prefix_stats()
        self.engine.metrics.set_prefix_gauges(self.head.name, s)
        # The retained pages live INSIDE the kv_page_pool operand the
        # ledger already counts — recorded as the reclaimable component,
        # so budget math sees cached bytes as releasable, not leaked.
        self.engine.memory.record_reclaimable(
            self.head.name, "prefix_cache_pages", s["retained_bytes"]
        )

    def clear_prefix_cache(self, reason: str) -> int:
        """Invalidate every retained entry (params/catalog hot swap,
        drain): a cached prefix from old params or an old catalog must
        never serve the new version."""
        if self.prefix is None:
            return 0
        n = self.prefix.clear()
        if n:
            eng = self.engine
            eng.metrics.record_prefix_evict(self.head.name, n,
                                            invalidation=True)
            eng._flight.record(
                "prefix_cache_invalidated", head=self.head.name,
                reason=reason, entries=n,
            )
            eng.metrics.set_pool_gauges(self.head.name, self.pool_stats())
        self._publish_prefix_gauges()
        return n

    def release_scratch(self, reason: str) -> int:
        """Drop the speculative scratch-page reservation (drain/stop) so
        the pool accounts clean at shutdown — the same discipline as the
        prefix cache's drain invalidation. Idempotent."""
        n = self.pool.release_scratch()
        if n:
            self.engine._flight.record(
                "spec_scratch_released", head=self.head.name,
                reason=reason, pages=n,
            )
            self.engine.metrics.set_pool_gauges(self.head.name,
                                                self.pool_stats())
        return n

    def _run_prefill(self, entries, slots, L: int,
                     t_pop: float | None = None, keys=None) -> None:
        eng = self.engine
        head = self.head
        t_admit = time.monotonic()
        reqs = [e[0] for e in entries]
        B = eng._ladder.batch_bucket(len(reqs))
        compiled = self._prefill.get((B, L))
        if compiled is None:  # off-grid (should not happen): counted
            compiled = self._prefill[(B, L)] = self._compile_prefill(B, L)
        args = _stage(head.make_batch(reqs, B, L), eng._mesh)
        bt = np.zeros((B, self.cfg.pages_per_slot), np.int32)
        bt[: len(slots)] = self.pool.block_tables[slots]
        bt = _stage(bt, eng._mesh)
        t_launch = time.monotonic()
        k_pools, v_pools, init = compiled(
            eng._select(head, eng._params), *head.runtime_operands(), *args,
            bt, self.pool.k_pools, self.pool.v_pools,
        )
        t_launched = time.monotonic()
        self.pool.k_pools, self.pool.v_pools = k_pools, v_pools
        n = len(slots)
        # The init rows come to the host (one fetch): the table stages
        # them with its next row write, and the prefix cache snapshots
        # them below.
        init = jax.device_get(init)
        # One number a launch, not rows (`Head.paged_prefill_counters`).
        counters = {k: init.pop(k) for k in head.paged_prefill_counters
                    if k in init}
        init = {k: v[:n] for k, v in init.items()}
        self.slots.bind(slots, init)
        t_prefilled = time.monotonic()
        inserted = 0
        if self.prefix is not None and keys is not None:
            # Retain every freshly prefilled run under its history key:
            # the entry addrefs the slot's pages (COW) and snapshots the
            # post-prefill state rows (only the keys prefill initialized
            # — the rest are zeroed again at warm admit), so the run
            # outlives its donor slot and a repeat request skips
            # prefill. Replacing a same-key entry drops the old refs.
            for i, (key, slot) in enumerate(zip(keys, slots)):
                if key is None:
                    continue
                snapshot = (
                    {k: np.array(v[i]) for k, v in init.items()}
                    if init else None
                )
                self.prefix.insert(
                    key, n_tokens=int(self.pool.seq_lens[slot]),
                    pages=self.pool.slot_pages(slot),
                    init=snapshot, bucket=(B, L),
                )
                eng.metrics.record_prefix_insert(head.name)
                inserted += 1
            self._publish_prefix_gauges()
        # Real history positions, in the ladder's own unit (what L counts),
        # and the prompt tokens they are (the KV tokens the slots hold).
        tokens = sum(min(max(head.natural_len(r), 1), L) for r in reqs)
        prompt_tokens = int(self.pool.seq_lens[slots].sum())
        if eng._tracer.enabled:
            self._phases += [
                ("prefill.stage", t_admit, t_launch,
                 {"bucket_b": B, "bucket_l": L, "rows": n, "tokens": tokens,
                  "prompt_tokens": prompt_tokens}),
                ("prefill.launch", t_launch, t_launched, {}),
                ("prefill.pull", t_launched, t_prefilled,
                 {k: float(v) for k, v in counters.items()}),
                ("prefill.retain", t_prefilled, time.monotonic(),
                 {"inserted": inserted}),
            ]
        for e, slot in zip(entries, slots):
            self.entries[slot] = (*e, t_admit)
            self.buckets[slot] = (B, L)
            tr = e[3]
            if tr is not None:
                # queue_wait: submit -> popped; admission: slot+page
                # grab; prefill: the compiled bucket call + state write.
                tid, root = tr[0], tr[1]
                tracer = eng._tracer
                ident = eng._span_ident()
                t0 = t_pop if t_pop is not None else t_admit
                tracer.record_span("queue_wait", tid, e[2], t0,
                                   parent_id=root, **ident)
                tracer.record_span("admission", tid, t0, t_admit,
                                   parent_id=root, slot=int(slot), **ident)
                tracer.record_span("prefill", tid, t_admit, t_prefilled,
                                   parent_id=root, bucket_b=B, bucket_l=L,
                                   **ident)
        eng.metrics.record_admit(n)
        eng.metrics.record_batch(head.name, (B, L), rows=n, tokens=tokens,
                                 prompt_tokens=prompt_tokens)
        self._sweep_finished()  # heads whose init step == total finish here

    # -- decode (one fixed-shape step over all slots) ------------------------

    def _trace_of(self, slot):
        return self.entries[slot][3]

    def step(self) -> bool:
        """Advance every active slot through the shared step
        (serving/slots.py). Finished slots resolve their futures and free
        their pages immediately, so the NEXT admit() can reuse them —
        eviction mid-decode, no batch barrier."""
        eng = self.engine
        res = self.slots.step(
            eng._select(self.head, eng._params), eng._tracer,
            eng._span_ident, self._trace_of,
        )
        if res is None:
            return False
        eng.metrics.record_decode_step(res.slots, res.live, res.kv_tokens)
        if res.accept is not None:
            eng.metrics.record_spec(
                self.head.name, drafted=res.drafted, accept_lens=res.accept
            )
        t_sweep = time.monotonic()
        finished = self._sweep_finished()
        if eng._tracer.enabled:
            self._phases += [
                ("decode.stage", res.t_stage, res.t0,
                 {"slots": res.slots, "live": res.live,
                  "kv_tokens": res.kv_tokens,
                  "staged_bytes": res.staged_bytes}),
                ("decode.launch", res.t0, res.t_launched,
                 {"slots": res.slots}),
                ("decode.pull", res.t_launched, res.t1,
                 {"leaves": res.leaves, "pulled_bytes": res.pulled_bytes}),
                ("decode.sweep", t_sweep, time.monotonic(),
                 {"finished": finished}),
            ]
        # Chaos hook: a real SIGTERM after the Nth decode step exercises
        # drain mid-churn for the continuous-batching loop.
        chaos.maybe_kill(step=eng.metrics.decode_steps)
        return True

    def flush_phases(self, seq: int) -> None:
        """Commit the iteration's buffered phases to the batcher's lane,
        in order of time and AFTER every per-request span of the
        iteration: a reader that names a device-idle gap by the last
        span committed over it (the benchmark's `breakdown.idle_gaps`)
        then reads the phase, not the `decode_step` that holds it."""
        tracer, ident = self.engine._tracer, self.engine._span_ident()
        for name, t0, t1, attrs in self._phases:
            tracer.record_span(name, self.lane, t0, t1, seq=seq,
                               **attrs, **ident)
        self._phases.clear()

    def _sweep_finished(self) -> int:
        eng = self.engine
        head = self.head
        done = self.slots.finished()
        step_id = eng._step
        # Stable while any slot is active: catalog swaps barrier on slot
        # drain, so every finished request decoded under THIS version.
        cat_version = head.catalog_version
        for slot in done:
            req, fut, t_enq, tr, t_admit = self.entries[slot]
            t_done = time.monotonic()
            try:
                payload = head.paged_finalize(self.slots.row(slot), req)
                now = time.monotonic()
                resp = Response(
                    head=head.name,
                    items=payload["items"],
                    scores=payload["scores"],
                    sem_ids=payload.get("sem_ids"),
                    params_step=step_id,
                    catalog_version=cat_version,
                    bucket=self.buckets[slot],
                    queue_wait_s=t_admit - t_enq,
                    compute_s=now - t_admit,
                    total_s=now - t_enq,
                    request_id=tr[0] if tr is not None else None,
                    replica_id=eng.replica_id,
                    # Co-located engine: prefill and decode ran in this
                    # process — no handoff, no worker attribution (the
                    # disagg front stamps real ids at ITS finalize).
                    prefill_worker_id=None,
                    decode_worker_id=None,
                )
            except Exception as e:  # noqa: BLE001 — one bad slot, not the loop
                eng._log.exception(
                    f"serving: paged finalize failed on head {head.name}"
                )
                if not fut.done():
                    fut.set_exception(e)
                eng.metrics.record_failure(1)
            else:
                eng.metrics.record_response(
                    resp.queue_wait_s, resp.compute_s, resp.total_s,
                    head=head.name,
                )
                if tr is not None:
                    tid, root = tr[0], tr[1]
                    ident = eng._span_ident()
                    eng._tracer.record_span(
                        "finalize", tid, t_done, now, parent_id=root,
                        **ident,
                    )
                    # This engine's request-level span: the trace ROOT
                    # when the request arrived untraced, a child of the
                    # upstream router/front span when a TraceContext
                    # came in (tr[2] — one rooted tree per request).
                    eng._tracer.record_span(
                        "request", tid, t_enq, now, span_id=root,
                        parent_id=tr[2], head=head.name, slot=int(slot),
                        params_step=step_id, **ident,
                    )
                    eng._maybe_exemplar(tid, resp)
                if not fut.done():
                    fut.set_result(resp)
            self.pool.evict(int(slot))
            self.slots.release(slot)
            self.entries[slot] = None
            self.buckets[slot] = None
            eng.metrics.record_evict(1)
        eng.metrics.set_pool_gauges(head.name, self.pool_stats())
        self._publish_prefix_gauges()
        return len(done)


class ServingEngine:
    def __init__(
        self,
        heads: Sequence,
        params,
        *,
        ladder: Optional[BucketLadder] = None,
        max_batch: int = 16,
        max_wait_ms: float = 4.0,
        ckpt_dir: Optional[str] = None,
        ckpt_poll_secs: float = 2.0,
        catalog_dirs: Optional[dict] = None,
        catalog_poll_secs: float = 2.0,
        params_step: Optional[int] = None,
        params_by_head: Optional[bool] = None,
        handle_signals: bool = True,
        guard=None,
        logger: Optional[logging.Logger] = None,
        paged: bool = True,
        paged_config: Optional[PagedConfig] = None,
        kv_dtype: str = "float32",
        prefix_cache: bool = True,
        prefix_cache_entries: int = 4096,
        spec_decode=False,
        spec_fanout: int = 8,
        tracer: Optional[SpanTracer] = None,
        hbm_budget_bytes: Optional[int] = None,
        slo_targets=None,
        slo_poll_secs: float = 0.05,
        replica_id: Optional[str] = None,
        mesh=None,
        model_axis: str = "model",
    ):
        # Replica identity (fleet deployments, genrec_tpu/fleet/): stamped
        # into every Response (`Response.replica_id` provenance) and the
        # lifecycle flight events. None for a standalone engine.
        self.replica_id = replica_id
        # Tensor-parallel serving operands (docs/SERVING.md "Cross-host
        # serving"): with a mesh, start() commits params through
        # parallel.shardings.serve_rules (retrieval item tables + the
        # TIGER vocab head row-sharded over ``model_axis``, everything
        # else replicated), each head places its runtime operands
        # (quantized table sharded, catalog trie replicated), and every
        # paged runner's KV page bank shards its HEAD axis. The AOT
        # lowering carries those placements (aot.sds_tree), so the
        # compile discipline is unchanged — same executable count, now
        # partitioned by GSPMD.
        self._mesh = mesh
        self._model_axis = str(model_axis)
        self._heads = {h.name: h for h in heads}
        if len(self._heads) != len(heads):
            raise ValueError("duplicate head names")
        self._params = params
        # Multi-head engines serve ONE combined tree {head_name: subtree}
        # so a hot reload swaps every head's params in the same atomic
        # step; a single-head engine may pass its raw tree.
        self._params_by_head = (
            params_by_head if params_by_head is not None else len(self._heads) > 1
        )
        if self._params_by_head:
            missing = [n for n in self._heads if n not in params]
            if missing:
                raise ValueError(f"params missing head subtrees: {missing}")
        self._step = params_step
        self._ladder = ladder or default_ladder(max_batch=max_batch)
        if max_batch > self._ladder.max_batch:
            raise ValueError(
                f"max_batch {max_batch} exceeds largest batch bucket "
                f"{self._ladder.max_batch}"
            )
        self._max_batch = max_batch
        self._max_wait_s = max_wait_ms / 1e3
        # Paged decode (default): heads implementing the paged protocol go
        # through slot-level continuous batching; paged=False keeps every
        # head on the dense whole-generate bucket executables (the parity
        # baseline bench.py measures against).
        self._paged = paged
        self._paged_config = paged_config
        # KV page dtype for the DEFAULT paged config ("float32" | "int8"
        # — docs/SERVING.md "Quantized serving"). An explicit
        # paged_config carries its own kv_dtype; passing both must agree
        # (a silent override would ledger different bytes than the pool
        # actually holds).
        self._kv_dtype = str(kv_dtype)
        if paged_config is not None and self._kv_dtype != "float32" \
                and paged_config.kv_dtype != self._kv_dtype:
            raise ValueError(
                f"kv_dtype={self._kv_dtype!r} conflicts with "
                f"paged_config.kv_dtype={paged_config.kv_dtype!r}; set it "
                "on the PagedConfig (or drop the engine kwarg)"
            )
        # Cross-request KV prefix cache over the COW page pool (paged
        # heads only): finished requests retain their prefilled pages in
        # a radix index; a repeat request with the same token-aligned
        # history admits straight into decode. prefix_cache=False is the
        # cold baseline bench.py measures against.
        self._prefix_cache = bool(prefix_cache)
        self._prefix_cache_entries = int(prefix_cache_entries)
        # Speculative tree decode (docs/SERVING.md "Speculative
        # decoding"): False (default — plain one-code steps), True (every
        # spec-capable paged head), or a set of head names (mixed
        # spec/plain heads on one engine). Off by default: speculation
        # trades redundant tree FLOPs for fewer sequential target
        # invocations — the right trade on dispatch/latency-bound
        # serving, measured (serve.spec in bench.py) rather than assumed.
        # spec_fanout: one int, or a per-level tuple (wide first
        # speculated level, narrow deep levels — TreeTopology
        # normalizes either form).
        self._spec_decode, self._spec_fanout = normalize_spec_config(
            spec_decode, spec_fanout, self._heads
        )
        self._runners: dict[str, _PagedRunner] = {}
        self._ckpt_dir = ckpt_dir
        self._ckpt_poll_secs = ckpt_poll_secs
        # Catalog watcher config: {head_name: snapshot_dir}. Watchers poll
        # for new CatalogSnapshot files and stage them through
        # stage_catalog (serving/catalog.py).
        self._catalog_dirs = dict(catalog_dirs or {})
        self._catalog_poll_secs = catalog_poll_secs
        for name in self._catalog_dirs:
            if name not in self._heads:
                raise ValueError(f"catalog_dirs names unknown head {name!r}")
            if not getattr(self._heads[name], "supports_catalog", False):
                raise ValueError(f"head {name!r} has no swappable catalog")
        self._catalog_watchers: list = []
        self._handle_signals = handle_signals
        self._guard = guard
        self._log = logger or logging.getLogger("genrec_tpu")
        # Request tracing is opt-in (pass an enabled SpanTracer); the
        # default NULL_TRACER keeps every hot-path check to one attribute
        # read. The flight recorder is always on (bounded ring).
        self._tracer = tracer if tracer is not None else NULL_TRACER
        CompileEvents.ensure().attach(tracer)
        # Every flight event this engine records is stamped with its
        # owner identity (component + replica_id, evaluated at record
        # time — the fleet router assigns replica_id AFTER construction),
        # so multi-replica rings stay attributable post-mortem.
        self._flight = get_flight_recorder().scoped(
            "engine", replica_id=lambda: self.replica_id
        )
        # Device-memory ledger (obs/memory.py): populated at warmup from
        # every compiled executable's XLA memory analysis + the logical
        # runtime operands; hbm_budget_bytes makes it a hard gate —
        # warmup REFUSES (HBMBudgetError, per-component breakdown) when
        # the model exceeds budget, and warns within 10% of it.
        self.memory = MemoryLedger()
        self._hbm_budget = (
            int(hbm_budget_bytes) if hbm_budget_bytes is not None else None
        )
        # SLO monitor (obs/slo.py): `slo_targets` is one SLOTarget for
        # every head or a {head: SLOTarget} dict. The batcher polls
        # observations off the hot path; a sustained breach sheds load
        # (typed OverloadError at submit, in-flight work completes) and
        # hysteresis un-sheds on recovery.
        if slo_targets is None:
            self._slo = None
        else:
            if isinstance(slo_targets, SLOTarget):
                targets = {name: slo_targets for name in self._heads}
            else:
                targets = dict(slo_targets)
                unknown = [n for n in targets if n not in self._heads]
                if unknown:
                    raise ValueError(f"slo_targets names unknown heads {unknown}")
            self._slo = SLOMonitor(targets, flight=self._flight)
        self._slo_poll_secs = float(slo_poll_secs)
        self._slo_next_poll = 0.0

        self.metrics = ServingMetrics()
        self._exec: dict[tuple[str, int, int], object] = {}
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._queues = {name: collections.deque() for name in self._heads}
        self._pending_params = None  # (tree, step) staged by the watcher
        # {head_name: (snapshot, dense_exec | None, runner_exec | None)}
        # staged by stage_catalog; applied by the batcher between batches.
        self._pending_catalog: dict[str, tuple] = {}
        # Serializes concurrent stage_catalog callers (watchers + manual
        # stagers); never taken by the batcher, so no ordering cycle with
        # _lock (which stage_catalog takes nested, briefly).
        self._stage_lock = threading.Lock()
        self._rr = 0  # round-robin head cursor (_next_batch)
        self._seq = 0  # batcher iterations: `seq` on the batcher lane's spans
        self._draining = False
        self._stop_watch = threading.Event()
        self._drained = threading.Event()
        self._batcher: Optional[threading.Thread] = None
        self._watcher: Optional[threading.Thread] = None
        self._ckpt_mgr = None
        self._started = False

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "ServingEngine":
        """Refresh head tables, compile every bucket, start the threads,
        install the signal guard. Returns self."""
        if self._started:
            raise RuntimeError("engine already started")
        if self._mesh is not None:
            from genrec_tpu.parallel.shardings import serve_rules, shard_params

            self._params = shard_params(
                self._mesh, self._params, serve_rules(self._model_axis),
                log_fn=self._log.info,
            )
            for head in self._heads.values():
                head.place_operands(self._mesh, self._model_axis)
        for head in self._heads.values():
            head.on_params(self._select(head, self._params))
        if self._paged:
            for head in self._heads.values():
                if getattr(head, "supports_paged", False):
                    self._runners[head.name] = _PagedRunner(
                        self, head, self._paged_config or self._default_paged_config(head)
                    )
        self.warmup()
        if self._guard is None and self._handle_signals:
            from genrec_tpu.core.preemption import PreemptionGuard

            self._guard = PreemptionGuard(self._log)
        if self._ckpt_dir is not None:
            from genrec_tpu.core.checkpoint import CheckpointManager

            self._ckpt_mgr = CheckpointManager(self._ckpt_dir)
            self._watcher = threading.Thread(
                target=self._watch_loop, name="serving-ckpt-watcher", daemon=True
            )
            self._watcher.start()
        if self._catalog_dirs:
            from genrec_tpu.serving.catalog import CatalogWatcher

            for name, directory in self._catalog_dirs.items():
                w = CatalogWatcher(
                    self, name, directory,
                    poll_secs=self._catalog_poll_secs, logger=self._log,
                )
                w.start()
                self._catalog_watchers.append(w)
        self._batcher = threading.Thread(
            target=self._batch_loop, name="serving-batcher", daemon=True
        )
        self._started = True
        self._flight.record(
            "serving_started", heads=sorted(self._heads),
            paged_heads=sorted(self._runners),
            warmup_compiles=self.metrics.warmup_compiles,
            replica_id=self.replica_id,
        )
        self._batcher.start()
        return self

    def _default_paged_config(self, head) -> PagedConfig:
        """Pool shapes sized off the ladder: pages_per_slot covers the
        largest history bucket, max_slots defaults to 4x the micro-batch
        (continuous batching's whole point is holding MORE concurrent
        decodes than one dense micro-batch), and the page budget covers
        every slot at max history (no OOM by default — shrink num_pages
        to run the pool under pressure)."""
        page_size = 16
        max_kv = head.paged_kv_tokens(10**9, self._ladder.history_buckets[-1])
        return PagedConfig(
            max_slots=4 * self._max_batch,
            page_size=page_size,
            pages_per_slot=-(-max_kv // page_size),
            kv_dtype=self._kv_dtype,
        )

    def warmup(self) -> None:
        """AOT-compile every (head, batch-bucket, history-bucket) combo so
        steady state is pure executable lookup. Paged heads compile the
        prefill bucket grid + ONE decode executable instead of a
        whole-generate executable per bucket."""
        t0 = time.monotonic()
        for head in self._heads.values():
            runner = self._runners.get(head.name)
            if runner is not None:
                runner.warmup()
            else:
                for B, L in self._ladder.combos():
                    self._compile(head, B, L)
        for head in self._heads.values():
            self._ledger_head(head)
        self._enforce_hbm_budget()
        self.metrics.mark_warm()
        self._log.info(
            f"serving warmup: {self.metrics.warmup_compiles} executables "
            f"({len(self._heads)} heads x {len(list(self._ladder.combos()))} "
            f"buckets; {len(self._runners)} paged decode heads) "
            f"in {time.monotonic() - t0:.1f}s"
        )

    # -- device-memory ledger ------------------------------------------------

    def _ledger_head(self, head) -> None:
        """(Re)account one head: resident runtime operands + every warmed
        executable's XLA memory analysis. Called at warmup and again
        after a catalog swap replaces operands/executables. Attribute
        reads + host sums only — nothing touches device buffers."""
        led = self.memory
        led.reset_group(head.name)
        led.record_operand(
            head.name, "params", tree_nbytes(self._select(head, self._params))
        )
        ops = head.runtime_operands()
        if ops:
            led.record_operand(head.name, "catalog_operands", tree_nbytes(ops))
        runner = self._runners.get(head.name)
        if runner is not None:
            led.record_operand(
                head.name, "kv_page_pool",
                tree_nbytes((runner.pool.k_pools, runner.pool.v_pools)),
            )
            # Retained prefix pages: a distinct, reclaimable component
            # INSIDE the pool bytes above (released under pool pressure
            # before any admission defers — never leaked growth).
            led.record_reclaimable(
                head.name, "prefix_cache_pages",
                runner.prefix_stats().get("retained_bytes", 0),
            )
            runner.slots.record_memory(led, head.name)
            for (B, L), ex in runner._prefill.items():
                led.record_executable(head.name, f"prefill/B{B}/L{L}", ex)
        else:
            for (name, B, L), ex in self._exec.items():
                if name == head.name:
                    led.record_executable(head.name, f"dense/B{B}/L{L}", ex)

    def _enforce_hbm_budget(self, during_swap: bool = False) -> None:
        """Warmup gate: refuse (typed, with an actionable per-component
        breakdown) when the ledger model exceeds the declared budget;
        warn inside the last 10% of headroom. A post-warmup re-check
        (catalog rung growth) can only WARN — failing the batcher thread
        mid-serve would be worse than running hot."""
        if self._hbm_budget is None:
            return
        summary = self.memory.summary(budget_bytes=self._hbm_budget)
        if summary["over_budget"]:
            breakdown = self.memory.breakdown_text(self._hbm_budget)
            self._flight.record(
                "hbm_budget_exceeded", total_bytes=summary["total_bytes"],
                budget_bytes=self._hbm_budget, during_swap=during_swap,
            )
            msg = (
                f"HBM budget model exceeds hbm_budget_bytes="
                f"{self._hbm_budget}: predicted "
                f"{summary['total_bytes']} bytes resident+transient. "
                "Shrink the bucket ladder / paged pool / catalog, or "
                f"raise the budget.\n{breakdown}"
            )
            if during_swap:
                self._log.warning(f"serving: {msg}")
                return
            raise HBMBudgetError(msg)
        if summary.get("headroom_pct", 100.0) < 10.0:
            self._flight.record(
                "hbm_budget_warning", total_bytes=summary["total_bytes"],
                budget_bytes=self._hbm_budget,
                headroom_pct=summary["headroom_pct"],
            )
            self._log.warning(
                "serving: HBM budget headroom is "
                f"{summary['headroom_pct']:.1f}% "
                f"({summary['total_bytes']} of {self._hbm_budget} bytes) — "
                "the next catalog rung or ladder growth will not fit"
            )

    def stop(self, timeout: float = 60.0) -> dict:
        """Drain (finish queued work, reject new) and join the threads.
        Returns the final metrics snapshot. Idempotent."""
        with self._lock:
            self._draining = True
            self._work.notify_all()
        self._flight.record("serving_stop", completed=self.metrics.completed)
        self._stop_watch.set()
        for w in self._catalog_watchers:
            w.stop(timeout)
        self._catalog_watchers = []
        if self._batcher is not None:
            self._batcher.join(timeout)
        for runner in self._runners.values():
            runner.release_scratch("stop")  # idempotent drain backstop
        if self._watcher is not None:
            self._watcher.join(timeout)
        if self._guard is not None:
            self._guard.close()
        if self._ckpt_mgr is not None:
            self._ckpt_mgr.close()
            self._ckpt_mgr = None
        return self.stats()

    def join(self, timeout: Optional[float] = None) -> bool:
        """Block until the engine has fully drained (e.g. after SIGTERM).
        True if drained within timeout."""
        return self._drained.wait(timeout)

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def params_step(self) -> Optional[int]:
        return self._step

    @property
    def tracer(self) -> SpanTracer:
        return self._tracer

    def set_tracer(self, tracer: Optional[SpanTracer]) -> None:
        """Swap the tracer LIVE (turn tracing on/off against a running
        engine — no recompile, no restart). Requests submitted before the
        swap keep the trace context minted at their submit; every record
        site guards on that per-entry context, so mixing is safe. An
        enabled tracer gets the process's compiles (`CompileEvents.attach`)."""
        self._tracer = tracer if tracer is not None else NULL_TRACER
        CompileEvents.ensure().attach(tracer)

    def _span_ident(self) -> dict:
        """Identity attrs stamped on every span this engine records:
        the component lane for the Perfetto export and the blame label
        for trace_report's critical path. Evaluated per record — the
        fleet router assigns replica_id after construction."""
        if self.replica_id is not None:
            return {"component": "engine", "replica": self.replica_id}
        return {"component": "engine"}

    def _maybe_exemplar(self, trace_id: str, resp: Response) -> None:
        """Slow-request exemplars: a p99-outlier request persists its full
        span tree past ring eviction, so the trace export always holds a
        worked example of 'why was the tail slow'."""
        thr = self.metrics.slow_threshold_s()
        if thr is not None and resp.total_s >= thr:
            self._tracer.mark_exemplar(
                trace_id,
                reason=f"p99 outlier: total {resp.total_s * 1e3:.1f}ms "
                       f">= {thr * 1e3:.1f}ms ({resp.head})",
            )

    def stats(self) -> dict:
        snap = self.metrics.snapshot()
        snap["params_step"] = self._step
        snap["draining"] = self._draining
        with self._lock:
            depths = {name: len(q) for name, q in self._queues.items()}
        snap["queue_depth"] = depths
        # Flat per-head headroom leaf: the ONE scalar a fleet router
        # (genrec_tpu/fleet/router.py) ranks replicas by — SLO margin
        # (tightest per-target margin, 1.0 with no monitor or no
        # observations yet) minus live queue pressure, normalized by the
        # replica's in-flight budget. Draining floors it at -1: a dying
        # replica never looks like capacity. Dict reads + one division
        # per head — no percentile math on this path.
        slo_room = self._slo.headroom() if self._slo is not None else {}
        norm = float(max(4 * self._max_batch, 1))
        snap["headroom"] = {
            name: round(
                min(slo_room.get(name, 1.0) - depths[name] / norm,
                    -1.0 if self._draining else 1.0),
                4,
            )
            for name in self._heads
        }
        # Device-memory ledger gauges (per-head operand/executable HBM
        # model + budget headroom) and the SLO shed state ride in every
        # snapshot, so log_serving_stats / write_prometheus expose them
        # with the pool gauges.
        snap["hbm"] = self.memory.summary(budget_bytes=self._hbm_budget)
        # Tracer self-metering (lineage liveness: spans/traces recorded,
        # ring occupancy) — typed counter/gauge by leaf name in
        # obs/export.py, so a scrape can tell "tracing on but ring too
        # shallow for the traffic" from "tracing off".
        snap["tracing"] = self._tracer.stats()
        if self._slo is not None:
            snap["slo"] = self._slo.snapshot()
        return snap

    # -- request path --------------------------------------------------------

    def submit(self, req: Request) -> Future:
        if req.head not in self._heads:
            raise UnknownHeadError(
                f"unknown head {req.head!r}; have {sorted(self._heads)}"
            )
        # Per-request validation BEFORE enqueueing: a malformed history
        # raises to its own caller here instead of failing the whole
        # micro-batch it would have been padded into.
        self._heads[req.head].validate(req)
        with self._lock:
            # Drain wins over shed: a dying replica must report the
            # TERMINAL DrainingError ("fail over"), never the
            # recoverable OverloadError ("retry") — a client backing
            # off and retrying a draining replica would just watch it
            # exit.
            if self._draining:
                self.metrics.record_reject(req.head)
                raise DrainingError(
                    "engine is draining (shutdown signal received); "
                    "request rejected — fail over to another replica"
                )
            # SLO load shed: while the monitor holds this head in
            # SHEDDING, new submissions bounce with the recoverable
            # typed error — queued and in-flight work keeps completing
            # (that completion is what drives recovery), exactly the
            # drain discipline but reversible via hysteresis. (Monitor
            # lock nests inside the engine lock; the monitor never
            # takes the engine lock, so the order is acyclic.)
            if self._slo is not None and self._slo.is_shedding(req.head):
                self.metrics.record_overload(req.head)
                raise OverloadError(
                    f"head {req.head!r} is load-shedding "
                    f"({self._slo.shed_reason(req.head)}); back off and "
                    "retry or fail over to another replica"
                )
            # Trace context AT submit: (trace id, pre-allocated span id
            # for this engine's request-level span — children recorded
            # before it completes can already parent onto it, and the
            # span id of the incoming parent). An incoming
            # Request.trace (a fleet router / disagg front upstream)
            # is ADOPTED: same trace id, our request span parented
            # under the upstream's — one rooted tree per request — and
            # the trace id rides Response.request_id even when this
            # engine's own tracer is off (lineage provenance survives a
            # partially instrumented fleet).
            ctx = req.trace
            if ctx is not None:
                tr = (
                    ctx.trace_id,
                    self._tracer.allocate_span_id()
                    if self._tracer.enabled else None,
                    ctx.parent_span_id,
                )
            elif self._tracer.enabled:
                tr = (self._tracer.new_trace(),
                      self._tracer.allocate_span_id(), None)
            else:
                tr = None
            entry = (req, Future(), time.monotonic(), tr)
            self._queues[req.head].append(entry)
            self._work.notify()
        self.metrics.record_submit(head=req.head)
        return entry[1]

    def serve(self, req: Request, timeout: Optional[float] = 60.0) -> Response:
        """Synchronous convenience wrapper around submit()."""
        return self.submit(req).result(timeout)

    # -- batcher -------------------------------------------------------------

    def _batch_loop(self) -> None:
        # (start, tracer) of the empty period in progress (nothing queued,
        # every runner idle), where a tracer is on. Only a submit ends it:
        # the iteration that finds a request queued ends it there and
        # records it once, as `batcher.empty`, after its admission and
        # before any phase of its own is committed.
        empty_period, empty_end = None, None
        try:
            while True:
                try:
                    if empty_period is not None and any(self._queues.values()):
                        empty_end = time.monotonic()
                    if (
                        self._guard is not None
                        and self._guard.fired
                        and not self._draining
                    ):
                        with self._lock:
                            self._draining = True
                        self._flight.record("serving_drain_started",
                                            cause="signal")
                        self._log.warning(
                            "serving: shutdown signal latched — draining "
                            "in-flight requests, rejecting new submissions"
                        )
                    self._seq += 1
                    swap_pending = self._apply_pending_params()
                    swap_pending |= self._apply_pending_catalog()
                    self._poll_slo()
                    # Slot-level continuous batching: admit queued requests
                    # into free slots (paused while a params OR catalog
                    # swap is staged, so every request decodes under ONE
                    # version of each), then advance every active slot
                    # one decode step.
                    progressed = False
                    for runner in self._runners.values():
                        if not swap_pending:
                            progressed |= runner.admit()
                        progressed |= runner.step()
                        if empty_end is not None:
                            self._record_empty(*empty_period, empty_end)
                            empty_period = empty_end = None
                        if runner._phases:
                            runner.flush_phases(self._seq)
                    batch = self._next_batch()
                    if batch is not None:
                        self._run_batch(*batch)
                        continue
                    if progressed:
                        continue
                    waited = None
                    with self._lock:
                        empty = all(not q for q in self._queues.values())
                        runners_idle = all(r.idle for r in self._runners.values())
                        done = self._draining and empty and runners_idle
                        if (empty_period is None and empty and runners_idle
                                and not done and self._runners
                                and self._tracer.enabled):
                            empty_period = (time.monotonic(), self._tracer)
                        if not done:
                            if self._tracer.enabled and not empty:
                                waited = {
                                    name: len(self._queues[name])
                                    for name in self._runners
                                    if self._queues[name]
                                }
                                t_wait = time.monotonic()
                            # Wake on submit/stop notify; when requests are
                            # queued, cap the wait so deadline flushes stay
                            # responsive — when idle, back off (guard/drain
                            # polls tolerate 50ms; a 1 kHz idle spin does not).
                            self._work.wait(
                                timeout=max(self._max_wait_s / 4, 1e-3)
                                if not (empty and runners_idle)
                                else 0.05
                            )
                    if waited is not None:
                        t_woke = time.monotonic()
                        # The batcher stood still with requests queued
                        # (under the coalescing deadline, or held by a
                        # staged swap). A wait with nothing queued is not
                        # recorded: an idle engine would fill the ring.
                        ident = self._span_ident()
                        for name, queued in waited.items():
                            runner = self._runners[name]
                            self._tracer.record_span(
                                "batcher.idle_wait", runner.lane, t_wait,
                                t_woke, seq=self._seq, queued=queued,
                                live=runner.slots.live, **ident,
                            )
                    if done:
                        if empty_period is not None:
                            self._record_empty(*empty_period, time.monotonic())
                        # Drained: release every retained prefix page —
                        # and any speculative scratch reservation — so
                        # the pool accounts clean at shutdown ("all pages
                        # released after drain", check_serving_hlo /
                        # check_spec_hlo).
                        for runner in self._runners.values():
                            runner.clear_prefix_cache("drain")
                            runner.release_scratch("drain")
                        break
                except Exception:  # noqa: BLE001 — the batcher must survive
                    # Anything escaping _run_batch's own guard (params
                    # refresh, metrics, future bookkeeping) would otherwise
                    # kill the thread while submit() keeps accepting.
                    self._log.exception("serving: batcher iteration failed")
        finally:
            self._drained.set()

    def _record_empty(self, t0: float, tracer: SpanTracer, t1: float) -> None:
        """One `batcher.empty` span on every runner's lane: the engine held
        no request from ``t0`` to ``t1``, so a device-idle gap there is the
        traffic's, not the host's. Dropped where the tracer was swapped
        since the period began."""
        if tracer is not self._tracer:
            return
        ident = self._span_ident()
        for runner in self._runners.values():
            tracer.record_span("batcher.empty", runner.lane, t0, t1,
                               seq=self._seq, **ident)

    def _poll_slo(self) -> None:
        """Feed the SLO monitor (batcher thread, rate-limited to
        ``slo_poll_secs``): windowed p99 from the metrics' recent-latency
        ring, live queue depths, and the cumulative deferral/submit
        counters the monitor differences over its window. The idle loop
        still iterates (condition-wait timeouts), so recovery keeps
        being evaluated when traffic stops."""
        if self._slo is None:
            return
        now = time.monotonic()
        if now < self._slo_next_poll:
            return
        self._slo_next_poll = now + self._slo_poll_secs
        with self._lock:
            depths = {name: len(q) for name, q in self._queues.items()}
        for head, target in self._slo.targets.items():
            # Every observation is PER HEAD (latency ring, queue, and
            # the deferral/submit counters): one head's pool pressure
            # or slow decode must never shed a healthy co-hosted head.
            self._slo.observe(
                head,
                p99_ms=self.metrics.recent_p99_ms(target.window_s, head=head),
                queue_depth=depths.get(head, 0),
                oom_deferred_total=self.metrics.oom_deferred_by_head[head],
                submitted_total=self.metrics.submitted_by_head[head],
                now=now,
            )

    def _next_batch(self):
        """Pop the next flush-ready head queue: full micro-batch, oldest
        entry past the wait deadline, or draining (flush ASAP). Heads are
        scanned round-robin from just past the last-flushed one, so a
        head under sustained full-batch load cannot starve the others."""
        now = time.monotonic()
        names = [n for n in self._queues if n not in self._runners]
        if not names:
            return None
        with self._lock:
            for i in range(len(names)):
                name = names[(self._rr + i) % len(names)]
                q = self._queues[name]
                if not q:
                    continue
                if (
                    len(q) >= self._max_batch
                    or self._draining
                    or now - q[0][2] >= self._max_wait_s
                ):
                    self._rr = (self._rr + i + 1) % len(names)
                    n = min(len(q), self._max_batch)
                    return self._heads[name], [q.popleft() for _ in range(n)]
        return None

    def _run_batch(self, head, entries) -> None:
        t_start = time.monotonic()
        reqs = [e[0] for e in entries]
        L_nat = max((head.natural_len(r) for r in reqs), default=1)
        L = self._ladder.history_bucket(max(L_nat, 1))
        B = self._ladder.batch_bucket(len(reqs))
        cat_version = head.catalog_version  # stable: swaps apply on this thread
        try:
            args = _stage(head.make_batch(reqs, B, L), self._mesh)
            compiled = self._get_executable(head, B, L)
            out = compiled(
                self._select(head, self._params), *head.runtime_operands(), *args
            )
            out = jax.tree_util.tree_map(np.asarray, out)  # host sync
            t_done = time.monotonic()
            payloads = head.finalize(out, reqs)
            t_final = time.monotonic()
        except Exception as e:  # noqa: BLE001 — a bad batch must not kill the loop
            self._log.exception(f"serving: micro-batch on head {head.name} failed")
            for _, fut, _t, _tr in entries:
                if not fut.done():
                    fut.set_exception(e)
            self.metrics.record_failure(len(entries))
            return
        self.metrics.record_batch(head.name, (B, L))
        # Chaos hook (no-op without an installed plan): deliver a real
        # shutdown signal after the Nth micro-batch — the drain chaos test
        # fires SIGTERM mid-load exactly like a preemption would.
        chaos.maybe_kill(step=self.metrics.batches)
        step = self._step
        for (req, fut, t_enq, tr), payload in zip(entries, payloads):
            now = time.monotonic()
            resp = Response(
                head=head.name,
                items=payload["items"],
                scores=payload["scores"],
                sem_ids=payload.get("sem_ids"),
                params_step=step,
                catalog_version=cat_version,
                bucket=(B, L),
                queue_wait_s=t_start - t_enq,
                compute_s=t_done - t_start,
                total_s=now - t_enq,
                request_id=tr[0] if tr is not None else None,
                replica_id=self.replica_id,
                prefill_worker_id=None,  # co-located: no handoff to
                decode_worker_id=None,   # attribute (see paged finalize)
            )
            self.metrics.record_response(
                resp.queue_wait_s, resp.compute_s, resp.total_s,
                head=head.name,
            )
            if tr is not None:
                # Dense whole-batch span tree: queue -> compute (the
                # shared executable call, host sync included) -> finalize.
                tid, root = tr[0], tr[1]
                ident = self._span_ident()
                self._tracer.record_span("queue_wait", tid, t_enq, t_start,
                                         parent_id=root, **ident)
                self._tracer.record_span("compute", tid, t_start, t_done,
                                         parent_id=root, bucket_b=B,
                                         bucket_l=L, **ident)
                self._tracer.record_span("finalize", tid, t_done, t_final,
                                         parent_id=root, **ident)
                self._tracer.record_span(
                    "request", tid, t_enq, now, span_id=root,
                    parent_id=tr[2], head=head.name, params_step=step,
                    **ident,
                )
                self._maybe_exemplar(tid, resp)
            if not fut.done():  # a cancelled Future must not kill the loop
                fut.set_result(resp)

    def _select(self, head, params):
        return params[head.name] if self._params_by_head else params

    def _get_executable(self, head, B: int, L: int):
        key = (head.name, B, L)
        compiled = self._exec.get(key)
        if compiled is None:
            # Off-ladder shape (should not happen: the ladder covers every
            # reachable bucket). Count it — check_serving_hlo pins zero.
            compiled = self._compile(head, B, L)
        return compiled

    def _compile(self, head, B: int, L: int, operands=None, install=True,
                 catalog_compile=False):
        """AOT-compile one (head, bucket) executable. Catalog operands
        (the trie) are lowered as runtime ARGUMENTS between params and
        the batch; ``operands`` overrides them for catalog-growth
        precompiles (install=False: the staged swap installs the result,
        the live table keeps serving the old catalog meanwhile)."""
        fn = _named(head.make_fn(B, L), f"{head.name}_generate_b{B}_l{L}")
        ops = operands if operands is not None else head.runtime_operands()
        args = head.make_batch([head.dummy_request()], B, L)
        compiled = jax.jit(fn).lower(
            self._select(head, self._params), *(_sds(op) for op in ops),
            *(_sds(a) for a in args),  # aval-only: never pins a device
        ).compile()
        if install:
            self._exec[(head.name, B, L)] = compiled
        self.metrics.record_compile(catalog=catalog_compile)
        return compiled

    # -- hot checkpoint reload -----------------------------------------------

    @property
    def params_step(self) -> Optional[int]:
        """The checkpoint step currently serving (Response.params_step
        provenance) — None until a versioned tree is installed."""
        return self._step

    def stage_params(self, tree, step: Optional[int], *,
                     source: str = "rollout") -> None:
        """Stage an externally-provided params tree for the atomic
        between-micro-batches swap — the rollout controller's entry
        point (serving/rollout.py), sharing the watcher's staging path
        (`_check_like` aval validation, `_apply_pending_params` swap
        barrier, prefix-cache invalidation). Unlike the watcher this is
        NOT monotonic: a rollback legitimately stages a step OLDER than
        the serving one. The swap applies at the next idle batcher pass;
        poll `params_step` to observe it."""
        self._check_like(tree)
        with self._lock:
            self._pending_params = (tree, step)
            self._work.notify()
        self._flight.record("hot_reload_staged", step=step, source=source)
        self._log.info(
            f"serving: staged params step {step} (source={source})"
        )

    def _watch_loop(self) -> None:
        # Transient filesystem errors (an NFS blip, a listing that races
        # a writer's rename) used to be indistinguishable from "no new
        # step": both silently skipped the poll. Classify them instead —
        # every failed pass counts in `watcher_errors` and leaves a
        # flight event, and transient ones back off exponentially
        # (bounded) so a flapping mount isn't hammered at poll rate.
        backoff = 0.0
        while not self._stop_watch.wait(self._ckpt_poll_secs + backoff):
            try:
                self._check_reload()
                backoff = 0.0
            except Exception as e:  # noqa: BLE001 — keep serving on watcher errors
                transient = is_transient_fs_error(e)
                self.metrics.record_watcher_error()
                self._flight.record(
                    "watcher_error", transient=transient,
                    error=f"{type(e).__name__}: {e}",
                )
                if transient:
                    backoff = min(
                        max(2 * backoff, self._ckpt_poll_secs), 30.0
                    )
                    self._log.warning(
                        "serving: transient checkpoint watcher error "
                        f"({type(e).__name__}: {e}); retrying in "
                        f"{self._ckpt_poll_secs + backoff:.1f}s"
                    )
                else:
                    backoff = 0.0
                    self._log.exception(
                        "serving: checkpoint watcher pass failed"
                    )

    def _check_reload(self) -> None:
        mgr = self._ckpt_mgr
        if mgr is None:
            return
        mgr.reload()  # pick up steps written by another process
        latest = mgr.latest_step()
        if latest is None or (self._step is not None and latest <= self._step):
            return
        # Integrity ladder: a garbled newest step is quarantined and the
        # previous valid one returned — which is the step already being
        # served, so the swap below is skipped and serving never pauses.
        restored, step = mgr.restore_latest_valid(self._params)
        if restored is None or (self._step is not None and step <= self._step):
            return
        self._check_like(restored)
        with self._lock:
            self._pending_params = (restored, step)
        self._flight.record("hot_reload_staged", step=step)
        self._log.info(f"serving: staged hot reload to checkpoint step {step}")

    def _check_like(self, restored) -> None:
        """The swapped tree must keep every aval identical, or the AOT
        executables would reject it mid-flight. Attribute reads only —
        no device-to-host copies of the weights."""
        cur = jax.tree_util.tree_leaves(self._params)
        new = jax.tree_util.tree_leaves(restored)
        if len(cur) != len(new) or any(
            np.shape(a) != np.shape(b) or np.result_type(a) != np.result_type(b)
            for a, b in zip(cur, new)
        ):
            raise RuntimeError("restored params tree does not match the serving tree")

    def _apply_pending_params(self) -> bool:
        """Atomic swap BETWEEN micro-batches (batcher thread only).

        With paged heads the swap additionally waits for every decode
        slot to drain (admission pauses, in-flight slots finish within
        sem_id_dim steps) so each request is answered by exactly ONE
        params version — the same guarantee the dense path gets for free
        from whole-batch executables. Returns True while a swap is still
        staged (callers pause admission on it)."""
        with self._lock:
            pending = self._pending_params
        if pending is None:
            return False
        if any(not r.idle for r in self._runners.values()):
            return True  # swap barrier: drain decode slots first
        with self._lock:
            pending, self._pending_params = self._pending_params, None
        if pending is None:
            return False
        restored, step = pending
        self._params = restored
        self._step = step
        self.metrics.record_swap()
        self._flight.record("hot_reload_swapped", step=step)
        for head in self._heads.values():
            head.on_params(self._select(head, restored))
        # A retained prefix was prefilled by the OLD params: serving it
        # under the new step would silently mix versions. Empty every
        # head's index (pinned by tests/test_prefix_cache.py).
        for runner in self._runners.values():
            runner.clear_prefix_cache("params_swap")
        self._log.info(f"serving: now serving checkpoint step {step}")
        return False

    # -- hot catalog swap ----------------------------------------------------

    def catalog_version(self, head_name: str) -> Optional[str]:
        return self._heads[head_name].catalog_version

    def staged_catalog_version(self, head_name: str) -> Optional[str]:
        with self._lock:
            staged = self._pending_catalog.get(head_name)
        return staged[0].version if staged is not None else None

    def stage_catalog(self, head_name: str, snapshot) -> bool:
        """Validate + stage a CatalogSnapshot for ``head_name``; the
        batcher swaps it in between micro-batches (paged slots drain
        first). Returns False when the snapshot is already live/staged.

        Runs on the CALLER'S thread (a CatalogWatcher or a test), which
        is the point: if the snapshot's trie sits on a different capacity
        rung than the installed executables (aval change), replacement
        executables are precompiled HERE, off the hot path, and installed
        atomically with the swap; head-side staging work (COBRA's tower
        encode for text-only snapshots) runs here too. Same-rung
        snapshots stage with zero compiles.

        Concurrent stagers are serialized by ``_stage_lock``, and the
        rung comparison is made against the EFFECTIVE aval — the staged
        pending snapshot when one exists, else the live trie — so a
        snapshot staged while a rung-changing swap is still pending can
        never be applied against mismatched executables.
        """
        head = self._heads.get(head_name)
        if head is None:
            raise UnknownHeadError(f"unknown head {head_name!r}")
        if not getattr(head, "supports_catalog", False):
            raise ValueError(f"head {head_name!r} has no swappable catalog")
        head.validate_snapshot(snapshot)
        with self._stage_lock:
            if snapshot.version == head.catalog_version:
                return False
            with self._lock:
                staged = self._pending_catalog.get(head_name)
            if staged is not None and staged[0].version == snapshot.version:
                return False
            # Expensive head-side derivations (e.g. COBRA's item-tower
            # encode from snapshot text) happen on THIS thread, so the
            # batcher's set_catalog is a pure pointer swap.
            prepare = getattr(head, "prepare_snapshot", None)
            if prepare is not None:
                prepare(snapshot)
            # The operand tuple this snapshot would install (the trie for
            # trie-operand heads, NoteLLM's scoring bank, ...) — the aval
            # source for rung-change detection and the AOT precompile.
            new_ops = head.snapshot_operands(snapshot)
            # Effective aval: what the executables will expect AT APPLY
            # time. While a swap is pending, that is the pending
            # snapshot's operands — and replacing the pending entry must
            # INHERIT its precompiled executables (it may be a
            # rung-change whose executables are not installed yet; the
            # dict holds one entry per head, so dropping them would swap
            # new-rung operands against old-rung executables).
            if staged is not None:
                base_ops = head.snapshot_operands(staged[0])
                dense_exec, runner_exec = staged[1], staged[2]
            else:
                base_ops = head.runtime_operands()
                dense_exec = runner_exec = None
            same_rung = _operand_avals(new_ops) == _operand_avals(base_ops)
            if not same_rung:
                dense_exec, runner_exec = self._precompile_catalog(head, new_ops)
            with self._lock:
                self._pending_catalog[head_name] = (
                    snapshot, dense_exec, runner_exec
                )
                self._work.notify()
        self._flight.record(
            "catalog_staged", head=head_name, version=snapshot.version,
            n_items=snapshot.n_items, capacity=snapshot.capacity,
            recompiled=not same_rung,
        )
        self._log.info(
            f"serving: staged catalog {snapshot.version} for head "
            f"{head_name} ({snapshot.n_items} items, capacity "
            f"{snapshot.capacity}{'' if same_rung else ', rung grew: executables precompiled'})"
        )
        return True

    def _precompile_catalog(self, head, operands):
        """Capacity-rung growth: AOT-compile every executable the head
        owns against the NEW operand avals (staging thread; the live
        tables keep serving the old catalog until the swap installs
        these)."""
        runner = self._runners.get(head.name)
        if runner is not None:
            decode = runner._compile_rungs(operands=operands,
                                           catalog_compile=True)
            prefill = {
                (B, L): runner._compile_prefill(B, L, operands=operands,
                                                catalog_compile=True)
                for B, L in self._ladder.combos()
            }
            return None, (decode, prefill)
        dense = {
            (head.name, B, L): self._compile(
                head, B, L, operands=operands, install=False,
                catalog_compile=True,
            )
            for B, L in self._ladder.combos()
        }
        return dense, None

    def _apply_pending_catalog(self) -> bool:
        """Atomic catalog swap BETWEEN micro-batches (batcher thread),
        after every paged decode slot drains — so one request never
        mixes catalog versions, the property tests/test_catalog.py pins.
        Returns True while a swap is still staged (admission pauses)."""
        with self._lock:
            if not self._pending_catalog:
                return False
        if any(not r.idle for r in self._runners.values()):
            return True  # swap barrier: drain decode slots first
        with self._lock:
            pending, self._pending_catalog = self._pending_catalog, {}
        for name, (snapshot, dense_exec, runner_exec) in pending.items():
            head = self._heads[name]
            runner_pre = self._runners.get(name)
            if runner_pre is not None:
                # Invalidate BEFORE the head swaps: retained runs (and
                # their state snapshots — COBRA's codebook-0 beam was
                # trie-masked, its dense vecs tower-encoded) belong to
                # the outgoing catalog version.
                runner_pre.clear_prefix_cache("catalog_swap")
            head.set_catalog(snapshot)
            if dense_exec is not None:
                self._exec.update(dense_exec)
            runner = self._runners.get(name)
            if runner is not None and runner_exec is not None:
                runner.slots.executables, runner._prefill = runner_exec
            self.metrics.record_catalog_swap()
            # Re-ledger the swapped head: the trie operand changed size
            # and a rung growth installed new executables. Post-warmup
            # the budget check can only warn (never fail the batcher).
            self._ledger_head(head)
            self._flight.record(
                "catalog_swapped", head=name, version=snapshot.version
            )
            self._log.info(
                f"serving: head {name} now serving catalog {snapshot.version}"
            )
        self._enforce_hbm_budget(during_swap=True)
        return False
