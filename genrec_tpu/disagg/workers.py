"""Role-specialized serving workers: prefill (history encode) and decode
(suffix generation), joined by typed `KVHandoff`s.

COBRA's history prefill and its suffix-step decode have completely
different arithmetic-intensity profiles (TPLA, arxiv 2508.15881): the
prefill is a bucketed batch encode that saturates on queue depth, the
decode is a slot-resident continuous loop that saturates on slot
occupancy. Splitting them into role pools lets each scale on its own
signal; the transfer unit is the refcounted page run + post-prefill
state snapshot the PR-11 prefix cache already retains.

- `PrefillWorker` owns admission: the deadline-coalesced bucket-sized
  groups of serving/engine.py, the SAME AOT prefill bucket grid, and a
  per-worker `PrefixIndex` — a warm full-history hit hands off the
  retained run without touching the prefill executable. Every completed
  prefill (warm or cold) becomes a `KVHandoff` through the configured
  `KVTransport`.
- `DecodeWorker` owns slot-level continuous batching over decode-only
  executables (the engine's collapsed slot-shape ladder) and its OWN
  `MemoryLedger` budget: ``hbm_budget_bytes`` is enforced at warmup
  (typed `HBMBudgetError` refusal) against the decode-side model —
  params + page pool + slot state + decode executables — with the
  prefill worker budgeted separately (PR 10's "per-worker budget" next
  step). Handoffs are VALIDATED on receipt: head/layout/params_step/
  catalog_version skew is a typed `HandoffRefusedError`, never silent
  mixing.

Every handoff admission uses the warm-admission semantics pinned by
tests/test_prefix_cache.py: state rows are patched against the request's
OWN history bucket (`head.paged_warm_state`), so a disagg answer equals
the co-located engine's solo serving of the same request bit-for-bit —
the parity bar scripts/check_disagg.py holds.

Threading: all worker methods run on the front's single runtime thread
(the engine's single-writer pool discipline, kept across the split);
submit threads only touch the queue under the front's lock.
"""

from __future__ import annotations

import collections
import logging
import time
from concurrent.futures import Future
from typing import Optional

import numpy as np

from genrec_tpu.disagg.handoff import (
    HandoffRefusedError,
    KVHandoff,
    layout_of,
)
from genrec_tpu.obs.memory import MemoryLedger, tree_nbytes
from genrec_tpu.obs.spans import NULL_TRACER
from genrec_tpu.serving.aot import donate_argnums as _donate, sds_tree as _sds
from genrec_tpu.serving.kv_pool import (
    KVPagePool,
    PoolExhausted,
    PrefixIndex,
)
from genrec_tpu.serving.slots import SlotTable, stage as _stage
from genrec_tpu.serving.types import HBMBudgetError, Response


def _place_worker(worker, mesh, model_axis: str) -> None:
    """The DecodeWorker/PrefillWorker ``mesh=`` knob: shard params by
    ``serve_rules`` (row-sharded retrieval item table incl. the int8
    QuantizedTable operand, vocab-sharded TIGER output head), commit the
    head's runtime operands, and — when this worker OWNS its pool
    (serializing/socket tiers) — shard the KV page bank over the head
    axis. A shared in-process bank is the front's to place, not one
    view's. Runs before warmup so aot.sds_tree carries every
    NamedSharding into the lowerings."""
    from genrec_tpu.parallel.shardings import (
        kv_pool_sharding,
        serve_rules,
        shard_params,
    )

    worker.params = shard_params(
        mesh, worker.params, serve_rules(model_axis), log_fn=worker._log.info
    )
    worker.head.place_operands(mesh, model_axis)
    if worker.owns_pool:
        n_heads = layout_of(worker.head)[1]
        place = kv_pool_sharding(mesh, n_heads, model_axis)
        if place is not None:
            worker.pool.place(place)


class Flight:
    """One accepted request moving through the role pipeline."""

    __slots__ = ("req", "fut", "t_enq", "retried", "trace")

    def __init__(self, req, fut: Optional[Future] = None,
                 t_enq: Optional[float] = None, retried: bool = False):
        self.req = req
        self.fut = fut if fut is not None else Future()
        self.t_enq = t_enq if t_enq is not None else time.monotonic()
        self.retried = retried  # at-most-once worker-loss re-submit spent
        # Request lineage (obs.TraceContext), parented under the front's
        # per-request span — set by DisaggFront.submit; every worker
        # span for this flight attaches here. Survives re-submit after
        # a worker death, so the retry stays in the ORIGINAL trace.
        self.trace = None


class PrefillWorker:
    """Admission + bucket-ladder prefill; emits typed `KVHandoff`s.

    ``pool`` is either a slot view over the shared in-process page bank
    (zero-copy transport) or this worker's own staging pool (serializing
    transport; ``owns_pool=True`` budgets its bytes here). The worker
    never binds slots — prefill writes through raw page runs, and the
    run's ownership moves to the handoff (and, when the prefix cache
    retains it, to the index) the moment the executable returns.
    """

    role = "prefill"

    def __init__(self, worker_id: str, head, params, *, ladder, transport,
                 pool: KVPagePool, owns_pool: bool, max_batch: int,
                 max_wait_s: float, metrics, flight_recorder,
                 params_step: Optional[int] = None, prefix_cache: bool = True,
                 prefix_cache_entries: int = 4096,
                 hbm_budget_bytes: Optional[int] = None,
                 tracer=None,
                 mesh=None, model_axis: str = "model",
                 logger: Optional[logging.Logger] = None):
        self.worker_id = worker_id
        self.head = head
        self.params = params
        self.ladder = ladder
        self.transport = transport
        self.pool = pool
        self.owns_pool = owns_pool
        self.max_batch = max_batch
        self.max_wait_s = max_wait_s
        self.metrics = metrics
        self._flight = flight_recorder
        self.params_step = params_step
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self._log = logger or logging.getLogger("genrec_tpu")
        self._mesh = mesh
        self._model_axis = str(model_axis)
        if mesh is not None:
            _place_worker(self, mesh, self._model_axis)
        # Guarded by the FRONT's lock: submit threads append, the front
        # runtime thread pops.
        self.queue: collections.deque = collections.deque()
        # Flights already counted as deferred / prefix-looked-up: a
        # page-starved request is re-popped every pass and must count its
        # deferral (and its lookup outcome) ONCE, not per retry — the
        # engine's _oom_counted discipline.
        self._oom_counted: set[int] = set()
        self.prefix: PrefixIndex | None = (
            PrefixIndex(pool.allocator, max_entries=prefix_cache_entries)
            if prefix_cache else None
        )
        self._prefill: dict[tuple[int, int], object] = {}
        self._transport_execs: list = []
        self.warmup_compiles = 0
        self.recompilations = 0
        self._warm = False
        self.prefills = 0
        self.deferred = 0
        self.dead = False
        self.draining = False
        self.memory = MemoryLedger()
        self._hbm_budget = (
            int(hbm_budget_bytes) if hbm_budget_bytes is not None else None
        )
        self._page_nbytes = (
            tree_nbytes((pool.k_pools, pool.v_pools)) // pool.cfg.num_pages
        )

    # -- warmup --------------------------------------------------------------

    def _count_compile(self, _compiled=None) -> None:
        if self._warm:
            self.recompilations += 1
        else:
            self.warmup_compiles += 1

    def _count_transport_compile(self, compiled=None) -> None:
        # Transport executables (serializing gather/scatter) belong in
        # THIS worker's HBM model beside its own grid — omitting them
        # would let a budget pass warmup and OOM live.
        self._count_compile(compiled)
        if compiled is not None:
            self._transport_execs.append(compiled)

    def _compile_prefill(self, B: int, L: int):
        import jax
        import jax.numpy as jnp  # noqa: F401 — jax must be up

        fn = self.head.make_prefill_paged_fn(B, L)
        ops = self.head.runtime_operands()
        batch = self.head.make_batch([self.head.dummy_request()], B, L)
        args = (
            self.params,
            *(_sds(op) for op in ops),
            *(_sds(b) for b in batch),  # aval-only: never pins a device
            jax.ShapeDtypeStruct((B, self.pool.cfg.pages_per_slot), np.int32),
            _sds(self.pool.k_pools),
            _sds(self.pool.v_pools),
        )
        n = 1 + len(ops) + len(batch)
        compiled = jax.jit(
            fn, donate_argnums=_donate(n + 1, n + 2)  # k_pools, v_pools
        ).lower(*args).compile()
        self._count_compile()
        return compiled

    def warmup(self) -> None:
        # Operands-first budget check: params/catalog/pool bytes are
        # known before any executable exists, and the ledger total only
        # grows from here — refusing NOW spends zero compile time on a
        # worker that can never fit.
        self._ledger(operands_only=True)
        for B, L in self.ladder.combos():
            self._prefill[(B, L)] = self._compile_prefill(B, L)
        self.transport.prepare_send(self.pool, self._count_transport_compile)
        self._ledger()
        self._warm = True

    def _ledger(self, operands_only: bool = False) -> None:
        led = self.memory
        led.reset_group(self.worker_id)
        led.record_operand(self.worker_id, "params", tree_nbytes(self.params))
        ops = self.head.runtime_operands()
        if ops:
            led.record_operand(self.worker_id, "catalog_operands",
                               tree_nbytes(ops))
        if self.owns_pool:
            led.record_operand(
                self.worker_id, "kv_page_pool",
                tree_nbytes((self.pool.k_pools, self.pool.v_pools)),
            )
        else:
            # In-process tier: the shared page bank is not this worker's
            # to own, but it IS resident on the device this worker's
            # budget models — omit it and an impossible budget passes
            # warmup only to OOM live. (Aggregating per-worker ledgers
            # across a group double-counts the bank by design: the
            # per-worker budget is the gate, and on the cross-host tier
            # every worker really does hold its own pool.)
            led.record_operand(
                self.worker_id, "kv_page_bank_shared",
                tree_nbytes((self.pool.k_pools, self.pool.v_pools)),
            )
        led.record_reclaimable(
            self.worker_id, "prefix_cache_pages",
            (self.prefix.retained_pages if self.prefix is not None else 0)
            * self._page_nbytes,
        )
        for (B, L), ex in self._prefill.items():
            led.record_executable(self.worker_id, f"prefill/B{B}/L{L}", ex)
        for i, ex in enumerate(self._transport_execs):
            led.record_executable(self.worker_id, f"transport/{i}", ex)
        if self._hbm_budget is not None:
            summary = led.summary(budget_bytes=self._hbm_budget)
            if summary["over_budget"]:
                raise HBMBudgetError(
                    f"prefill worker {self.worker_id}: HBM model exceeds "
                    f"hbm_budget_bytes={self._hbm_budget} (predicted "
                    f"{summary['total_bytes']} bytes"
                    + (" on operands alone, before any executable"
                       if operands_only else "") + ")\n"
                    + led.breakdown_text(self._hbm_budget)
                )

    # -- the prefill pass ----------------------------------------------------

    @property
    def queue_depth(self) -> int:
        return len(self.queue)

    def headroom(self) -> float:
        if self.dead or self.draining:
            return -1.0
        return round(1.0 - len(self.queue) / float(4 * self.max_batch), 4)

    def _alloc_run(self, n_pages: int):
        """allocator.alloc with the prefix-reclaim ladder: retained runs
        are released LRU-first before any admission defers (the engine's
        _admit_pages discipline, per worker)."""
        try:
            return self.pool.allocator.alloc(n_pages)
        except PoolExhausted:
            if self.prefix is None or not len(self.prefix):
                raise
            evicted = self.prefix.reclaim(n_pages)
            if evicted:
                self.metrics.record_prefix_evict(self.head.name, evicted)
            return self.pool.allocator.alloc(n_pages)

    def pump(self, lock, draining: bool) -> list[tuple[Flight, KVHandoff]]:
        """One admission pass (front runtime thread): pop one deadline-
        coalesced group, serve warm hits off the prefix index, run ONE
        bucketed prefill for the cold rest, and return the handoffs for
        the front to route. Requests that can't get pages stay queued
        (deferral counted once per request is the front's concern — here
        each pass counts at most one deferral episode)."""
        now = time.monotonic()
        with lock:
            if not self.queue:
                return []
            if (
                len(self.queue) < self.max_batch
                and now - self.queue[0].t_enq < self.max_wait_s
                and not (draining or self.draining)
            ):
                return []
            group = [self.queue.popleft()
                     for _ in range(min(len(self.queue), self.max_batch))]
        t_pop = time.monotonic()
        head = self.head
        max_hist = self.ladder.history_buckets[-1]
        out: list[tuple[Flight, KVHandoff]] = []
        warm, cold = [], []
        for fl in group:
            own_L = self.ladder.history_bucket(
                max(head.natural_len(fl.req), 1))
            n_tok = head.paged_kv_tokens(head.natural_len(fl.req), own_L)
            key = (head.prefix_key_tokens(fl.req, max_hist)
                   if self.prefix is not None else None)
            entry = None
            if key is not None:
                t0 = time.monotonic()
                entry, matched = self.prefix.lookup(key)
                if entry is not None and entry.n_tokens != n_tok:
                    entry = None  # same key, different KV footprint: cold
                outcome = ("hit" if entry is not None
                           else ("partial" if matched else "miss"))
                if id(fl) not in self._oom_counted:
                    self.metrics.record_prefix_lookup(
                        head.name, outcome,
                        tokens=entry.n_tokens if entry is not None else 0,
                    )
                    if fl.trace is not None:
                        self.tracer.record_span(
                            "prefix_lookup", fl.trace.trace_id, t0,
                            time.monotonic(),
                            parent_id=fl.trace.parent_span_id,
                            outcome=outcome, matched_tokens=int(matched),
                            **self._span_ident(),
                        )
            if entry is not None:
                warm.append((fl, entry))
            else:
                cold.append((fl, key, n_tok))
        for fl, entry in warm:
            self._oom_counted.discard(id(fl))
            t0 = time.monotonic()
            handoff = self._make_handoff(
                entry.n_tokens, entry.bucket, entry.init, warm=True,
                trace=fl.trace)
            try:
                tw0 = time.monotonic()
                self.transport.send(self.pool, entry.pages, handoff)
                tw1 = time.monotonic()
            except Exception as e:  # noqa: BLE001 — fail THIS flight only
                # The flight is already popped from the queue: anything
                # escaping pump() would strand its future unresolved
                # (the retained prefix entry itself is untouched).
                self._log.exception(
                    f"disagg: warm handoff send failed on worker "
                    f"{self.worker_id}"
                )
                if not fl.fut.done():
                    fl.fut.set_exception(e)
                self.metrics.record_failure(1)
                continue
            self.prefix.touch(entry.key)
            entry.hits += 1
            if fl.trace is not None:
                self._record_handoff_spans(
                    fl, t_pop, warm_t0=t0,
                    wire=(tw0, tw1, handoff.transfer_bytes))
            out.append((fl, handoff))
        if cold:
            out.extend(self._prefill_cold(cold, lock, t_pop))
        self._publish_reclaimable()
        return out

    def _span_ident(self) -> dict:
        return {"component": "prefill_worker", "worker": self.worker_id}

    def _record_handoff_spans(self, fl: Flight, t_pop: float, *,
                              warm_t0: float | None = None,
                              admission=None, prefill=None,
                              wire=None) -> None:
        """One flight's prefill-side span set, attached under the
        front's per-request span (fl.trace.parent_span_id):
        queue_wait, then warm_admit OR admission+prefill, then the
        send side of handoff_wire."""
        tr = fl.trace
        ident = self._span_ident()
        rs = self.tracer.record_span
        rs("queue_wait", tr.trace_id, fl.t_enq, t_pop,
           parent_id=tr.parent_span_id, **ident)
        if warm_t0 is not None:
            rs("warm_admit", tr.trace_id, warm_t0, time.monotonic(),
               parent_id=tr.parent_span_id, **ident)
        if admission is not None:
            rs("admission", tr.trace_id, admission[0], admission[1],
               parent_id=tr.parent_span_id, **ident)
        if prefill is not None:
            t0, t1, B, L = prefill
            rs("prefill", tr.trace_id, t0, t1,
               parent_id=tr.parent_span_id, bucket_b=B, bucket_l=L,
               **ident)
        if wire is not None:
            tw0, tw1, nbytes = wire
            rs("handoff_wire", tr.trace_id, tw0, tw1,
               parent_id=tr.parent_span_id, side="send",
               transport=self.transport.name, transfer_bytes=int(nbytes),
               **ident)

    def _make_handoff(self, n_tokens: int, bucket, init, warm: bool,
                      trace=None):
        return KVHandoff(
            head=self.head.name, n_tokens=int(n_tokens), bucket=bucket,
            layout=layout_of(self.head), init=init,
            params_step=self.params_step,
            catalog_version=self.head.catalog_version,
            prefill_worker_id=self.worker_id, warm=warm, trace=trace,
            kv_dtype=self.pool.cfg.kv_dtype,
        )

    def _prefill_cold(self, cold, lock,
                      t_pop: float) -> list[tuple[Flight, KVHandoff]]:
        head = self.head
        t_alloc0 = time.monotonic()
        runs, admitted = [], []
        for fl, key, n_tok in cold:
            try:
                runs.append(self._alloc_run(self.pool.cfg.pages_for(n_tok)))
                admitted.append((fl, key, n_tok))
            except PoolExhausted:
                break
        leftover = [fl for fl, _k, _n in cold[len(admitted):]]
        if leftover:  # out of pages: requeue at the FRONT (FIFO order)
            with lock:
                self.queue.extendleft(reversed(leftover))
            fresh = [fl for fl in leftover
                     if id(fl) not in self._oom_counted]
            if fresh:  # one deferral per request, not per retry
                self._oom_counted.update(id(fl) for fl in fresh)
                self.deferred += len(fresh)
                self.metrics.record_oom_admit(len(fresh), head=head.name)
        if not admitted:
            return []
        self._oom_counted.difference_update(
            id(fl) for fl, _k, _n in admitted)
        reqs = [fl.req for fl, _k, _n in admitted]
        L = self.ladder.history_bucket(
            max(max((head.natural_len(r) for r in reqs), default=1), 1))
        B = self.ladder.batch_bucket(len(reqs))
        compiled = self._prefill.get((B, L))
        if compiled is None:  # off-grid (should not happen): counted
            compiled = self._prefill[(B, L)] = self._compile_prefill(B, L)
        bt = np.zeros((B, self.pool.cfg.pages_per_slot), np.int32)
        for i, run in enumerate(runs):
            bt[i, : len(run)] = run
        t_run0 = time.monotonic()
        try:
            args = _stage(head.make_batch(reqs, B, L), self._mesh)
            k_pools, v_pools, init = compiled(
                self.params, *head.runtime_operands(), *args,
                _stage(bt, self._mesh), self.pool.k_pools, self.pool.v_pools,
            )
            self.pool.k_pools, self.pool.v_pools = k_pools, v_pools
        except Exception as e:  # noqa: BLE001 — fail THESE futures only
            self._log.exception(
                f"disagg: prefill on worker {self.worker_id} failed"
            )
            for run, (fl, _k, _n) in zip(runs, admitted):
                self.pool.allocator.free(run)
                if not fl.fut.done():
                    fl.fut.set_exception(e)
            self.metrics.record_failure(len(admitted))
            return []
        t_run1 = time.monotonic()
        self.prefills += len(admitted)
        self.metrics.record_batch(head.name, (B, L))
        out = []
        for i, (run, (fl, key, n_tok)) in enumerate(zip(runs, admitted)):
            snapshot = (
                {k: np.array(np.asarray(v)[i]) for k, v in init.items()}
                if init else None
            )
            if self.prefix is not None and key is not None:
                self.prefix.insert(key, n_tokens=n_tok, pages=run,
                                   init=snapshot, bucket=(B, L))
                self.metrics.record_prefix_insert(head.name)
            handoff = self._make_handoff(n_tok, (B, L), snapshot, warm=False,
                                         trace=fl.trace)
            try:
                tw0 = time.monotonic()
                self.transport.send(self.pool, run, handoff)
                tw1 = time.monotonic()
            except Exception as e:  # noqa: BLE001 — fail THIS flight only
                # Same guarantee as the warm loop: the temp alloc ref
                # still drops (no page leak in the staging pool) and the
                # popped flight fails typed instead of hanging; the
                # remaining handoffs in the group still go out.
                self._log.exception(
                    f"disagg: handoff send failed on worker "
                    f"{self.worker_id}"
                )
                self.pool.allocator.free(run)
                if not fl.fut.done():
                    fl.fut.set_exception(e)
                self.metrics.record_failure(1)
                continue
            self.pool.allocator.free(run)  # drop the temp alloc ref
            if fl.trace is not None:
                self._record_handoff_spans(
                    fl, t_pop, admission=(t_alloc0, t_run0),
                    prefill=(t_run0, t_run1, B, L),
                    wire=(tw0, tw1, handoff.transfer_bytes))
            out.append((fl, handoff))
        return out

    def _publish_reclaimable(self) -> None:
        if self.prefix is None:
            return
        s = self.prefix.stats()
        s["retained_bytes"] = s["retained_pages"] * self._page_nbytes
        self.metrics.set_prefix_gauges(self.head.name, s)
        self.memory.record_reclaimable(
            self.worker_id, "prefix_cache_pages", s["retained_bytes"]
        )

    def clear_prefix_cache(self, reason: str) -> int:
        if self.prefix is None:
            return 0
        n = self.prefix.clear()
        if n:
            self.metrics.record_prefix_evict(self.head.name, n,
                                             invalidation=True)
            self._flight.record(
                "prefix_cache_invalidated", head=self.head.name,
                worker=self.worker_id, reason=reason, entries=n,
            )
        self._publish_reclaimable()
        return n

    def stats(self) -> dict:
        out = {
            "queue_depth": len(self.queue),
            "prefills": self.prefills,
            "deferred": self.deferred,
            "warmup_compiles": self.warmup_compiles,
            "recompilations": self.recompilations,
            "headroom": self.headroom(),
            "hbm": self.memory.summary(budget_bytes=self._hbm_budget),
        }
        if self.prefix is not None:
            s = self.prefix.stats()
            s["retained_bytes"] = s["retained_pages"] * self._page_nbytes
            out["prefix_cache"] = s
        return out


class DecodeWorker:
    """Slot-level continuous batching over decode-only executables.

    With ``spec_topology`` set (the front computes one `TreeTopology`
    per spec-enabled head group), the worker compiles the tree-verify
    step INSTEAD of the plain decode step at every slot rung — the
    engine's speculative path, per worker — and reserves the scratch
    pages the tree's candidate K/V lands in out of its pool, so
    speculation never competes with handoff admissions."""

    role = "decode"

    def __init__(self, worker_id: str, head, params, *, transport,
                 pool: KVPagePool, owns_pool: bool, ladder, metrics,
                 flight_recorder, slot_floor: int = 1,
                 params_step: Optional[int] = None,
                 replica_id: Optional[str] = None,
                 hbm_budget_bytes: Optional[int] = None,
                 spec_topology=None, spec_fanout=8,
                 tracer=None,
                 mesh=None, model_axis: str = "model",
                 logger: Optional[logging.Logger] = None):
        self.worker_id = worker_id
        self.head = head
        self.params = params
        self.transport = transport
        self.pool = pool
        self.owns_pool = owns_pool
        self.ladder = ladder
        self.metrics = metrics
        self._flight = flight_recorder
        self.params_step = params_step
        self.replica_id = replica_id
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self._log = logger or logging.getLogger("genrec_tpu")
        self._mesh = mesh
        self._model_axis = str(model_axis)
        if mesh is not None:
            _place_worker(self, mesh, self._model_axis)
        cfg = pool.cfg
        if spec_topology is not None:
            # Scratch-page reservation (the engine's discipline, per
            # worker): the pool/bank the front built at CONSTRUCTION
            # already includes the demand, so reserving here never eats
            # admission capacity for the initial workers.
            per_slot = -(-spec_topology.n_nodes // cfg.page_size)
            self._scratch_demand = cfg.max_slots * per_slot
        else:
            self._scratch_demand = 0
        try:
            self._scratch_tables = pool.reserve_scratch(self._scratch_demand)
        except PoolExhausted:
            # A bank-backed worker added PAST the group's initial sizing
            # (decode role scale-out on the in-process tier): the shared
            # bank was provisioned for the construction-time worker
            # count, so this reservation may not fit. Degrade narrated
            # instead of failing the scale-out mid-construction — the
            # pure-JAX tree-verify fallback never touches the scratch
            # pages (they are the TPU kernel's landing zone), so serving
            # on this tier is unaffected; a real TPU deployment
            # re-provisions the bank instead. (On the serializing tier
            # the worker owns its pool, sized to include the demand, so
            # this path cannot fire there.)
            wanted, self._scratch_demand = self._scratch_demand, 0
            self._scratch_tables = pool.reserve_scratch(0)
            self._flight.record("spec_scratch_unreserved",
                                worker_id=worker_id, pages_wanted=wanted)
            self._log.warning(
                f"disagg: decode worker {worker_id} joined a shared bank "
                "with no room for its speculative scratch reservation — "
                "proceeding unreserved (CPU fallback unaffected)"
            )
        # The decode side of the slot set, shared with the co-located
        # engine (serving/slots.py): state rows, step counters, the rung
        # ladder (max_slots halving down to slot_floor) with its
        # executables, and the step itself.
        self.slots = SlotTable(
            head, pool, floor=slot_floor, mesh=mesh,
            spec_topology=spec_topology, spec_fanout=spec_fanout,
        )
        # (flight, handoff, t_admit, span_ctx) per slot; span_ctx is
        # (trace_id, slot_residency_span_id, parent_span_id) or None.
        self.entries: list = [None] * cfg.max_slots
        self._transport_execs: list = []
        self.warmup_compiles = 0
        self.recompilations = 0
        self._warm = False
        self.decode_steps = 0
        self.admitted = 0
        self.dead = False
        self.draining = False
        self.memory = MemoryLedger()
        self._hbm_budget = (
            int(hbm_budget_bytes) if hbm_budget_bytes is not None else None
        )

    # -- warmup --------------------------------------------------------------

    def _count_compile(self, _compiled=None) -> None:
        if self._warm:
            self.recompilations += 1
        else:
            self.warmup_compiles += 1

    def _count_transport_compile(self, compiled=None) -> None:
        # See PrefillWorker._count_transport_compile: the scatter
        # executable belongs in this worker's HBM model.
        self._count_compile(compiled)
        if compiled is not None:
            self._transport_execs.append(compiled)

    def warmup(self) -> None:
        # Operands-first (see PrefillWorker.warmup): an impossible
        # decode-side budget refuses before any compile is paid.
        self._ledger(operands_only=True)
        for S in self.slots.rungs:
            self.slots.executables[S] = self.slots.compile(S, self.params)
            self._count_compile()
        self.slots.compile_writer()
        self._count_compile()
        self.transport.prepare_admit(self.pool, self._count_transport_compile)
        self._ledger()
        self._warm = True

    def _ledger(self, operands_only: bool = False) -> None:
        led = self.memory
        led.reset_group(self.worker_id)
        led.record_operand(self.worker_id, "params", tree_nbytes(self.params))
        ops = self.head.runtime_operands()
        if ops:
            led.record_operand(self.worker_id, "catalog_operands",
                               tree_nbytes(ops))
        if self.owns_pool:
            led.record_operand(
                self.worker_id, "kv_page_pool",
                tree_nbytes((self.pool.k_pools, self.pool.v_pools)),
            )
        else:
            # Shared-bank slot view: see PrefillWorker._ledger — the
            # bank's bytes belong in this worker's budget model even
            # though the group owns the arrays.
            led.record_operand(
                self.worker_id, "kv_page_bank_shared",
                tree_nbytes((self.pool.k_pools, self.pool.v_pools)),
            )
        self.slots.record_memory(led, self.worker_id)
        for i, ex in enumerate(self._transport_execs):
            led.record_executable(self.worker_id, f"transport/{i}", ex)
        if self._hbm_budget is not None:
            summary = led.summary(budget_bytes=self._hbm_budget)
            if summary["over_budget"]:
                raise HBMBudgetError(
                    f"decode worker {self.worker_id}: HBM model exceeds "
                    f"hbm_budget_bytes={self._hbm_budget} (predicted "
                    f"{summary['total_bytes']} bytes — decode-side only: "
                    "params + page pool + slot state + decode "
                    "executables"
                    + (", refused on operands alone before any "
                       "executable" if operands_only else "") + ")\n"
                    + led.breakdown_text(self._hbm_budget)
                )

    # -- handoff receipt -----------------------------------------------------

    @property
    def idle(self) -> bool:
        return self.slots.idle

    @property
    def free_slots(self) -> int:
        return self.pool.free_slot_count

    def occupancy(self) -> float:
        total = self.pool.cfg.max_slots
        return round((total - self.pool.free_slot_count) / total, 4)

    def headroom(self) -> float:
        if self.dead or self.draining:
            return -1.0
        return round(self.pool.free_slot_count / self.pool.cfg.max_slots, 4)

    def validate(self, handoff: KVHandoff) -> None:
        """Receipt validation — every mismatch is a typed refusal. The
        handoff is self-describing precisely so this check needs nothing
        but the artifact and this worker's own identity."""
        if handoff.head != self.head.name:
            raise HandoffRefusedError(
                f"handoff for head {handoff.head!r} routed to a "
                f"{self.head.name!r} decode worker"
            )
        if tuple(handoff.layout) != layout_of(self.head):
            raise HandoffRefusedError(
                f"handoff KV layout {tuple(handoff.layout)} != this "
                f"worker's {layout_of(self.head)}"
            )
        if handoff.kv_dtype != self.pool.cfg.kv_dtype:
            raise HandoffRefusedError(
                f"handoff KV pages are {handoff.kv_dtype} but this "
                f"worker's pool stores {self.pool.cfg.kv_dtype} — "
                "refusing to mix page storage dtypes across the split "
                "(prefill and decode pools must share one kv_dtype)"
            )
        if handoff.params_step != self.params_step:
            raise HandoffRefusedError(
                f"handoff prefilled at params step {handoff.params_step} "
                f"but this worker serves step {self.params_step} — "
                "refusing to mix params versions across the split"
            )
        if handoff.catalog_version != self.head.catalog_version:
            raise HandoffRefusedError(
                f"handoff catalog {handoff.catalog_version} != this "
                f"worker's {self.head.catalog_version} — refusing to "
                "decode against a different corpus"
            )

    def admit(self, flight: Flight, handoff: KVHandoff) -> bool:
        """Bind one validated handoff into a free slot; False when the
        pool has no room NOW (the handoff stays pending at the front).
        State restore is the warm-admission semantics: rows zeroed, the
        donor snapshot written, bucket-dependent fields re-judged against
        the request's OWN bucket (head.paged_warm_state)."""
        if self.pool.free_slot_count == 0:
            return False
        try:
            slot = self.transport.admit(handoff, self.pool)
        except PoolExhausted:
            return False
        try:
            patched = None
            if handoff.init:
                own_L = self.ladder.history_bucket(
                    max(self.head.natural_len(flight.req), 1))
                patched = self.head.paged_warm_state(
                    dict(handoff.init), handoff.n_tokens, own_L)
            self.slots.bind(slot, patched)
        except Exception as e:  # noqa: BLE001 — unbind, then refuse typed
            # The transport already bound the slot: a state snapshot
            # that does not fit this head (skewed peer) must not leak
            # it — evict drops the binding ref, then the typed refusal
            # rides the front's normal refusal path.
            self.pool.evict(slot)
            raise HandoffRefusedError(
                f"handoff state snapshot does not fit this worker's "
                f"slot state: {e!r}"
            ) from e
        # Slot-residency span: pre-allocate its id so the decode/spec
        # step spans recorded BEFORE the slot finishes can parent onto
        # it (the engine's allocate-before-record discipline). The
        # lineage comes off the HANDOFF — on a cross-host hop the wire
        # header is the only carrier — falling back to the flight's.
        ctx = handoff.trace if handoff.trace is not None else flight.trace
        span_ctx = None
        if ctx is not None and self.tracer.enabled:
            span_ctx = (ctx.trace_id, self.tracer.allocate_span_id(),
                        ctx.parent_span_id)
        elif ctx is not None:
            span_ctx = (ctx.trace_id, None, ctx.parent_span_id)
        self.entries[slot] = (flight, handoff, time.monotonic(), span_ctx)
        self.transport.release(handoff)
        self.admitted += 1
        self.metrics.record_admit(1)
        return True

    # -- decode --------------------------------------------------------------

    def _decode_span_ident(self) -> dict:
        return {"component": "decode_worker", "worker": self.worker_id}

    def _trace_of(self, slot):
        return self.entries[slot][3]

    def step(self) -> bool:
        """Advance every active slot through the shared step
        (serving/slots.py), the engine's fixed-shape step per worker."""
        res = self.slots.step(self.params, self.tracer,
                              self._decode_span_ident, self._trace_of)
        if res is None:
            return False
        self.metrics.record_decode_step(res.slots, res.live, res.kv_tokens)
        if res.accept is not None:
            self.metrics.record_spec(
                self.head.name, drafted=res.drafted, accept_lens=res.accept
            )
        self.decode_steps += 1
        self.sweep_finished()
        return True

    def sweep_finished(self) -> None:
        head = self.head
        for slot in self.slots.finished():
            flight, handoff, t_admit, span_ctx = self.entries[slot]
            now = time.monotonic()
            try:
                payload = head.paged_finalize(self.slots.row(slot),
                                              flight.req)
                resp = Response(
                    head=head.name,
                    items=payload["items"],
                    scores=payload["scores"],
                    sem_ids=payload.get("sem_ids"),
                    params_step=self.params_step,
                    catalog_version=head.catalog_version,
                    bucket=handoff.bucket,
                    queue_wait_s=t_admit - flight.t_enq,
                    compute_s=now - t_admit,
                    total_s=now - flight.t_enq,
                    request_id=span_ctx[0] if span_ctx is not None else None,
                    replica_id=self.replica_id,
                    prefill_worker_id=handoff.prefill_worker_id,
                    decode_worker_id=self.worker_id,
                )
            except Exception as e:  # noqa: BLE001 — one bad slot, not the loop
                self._log.exception(
                    f"disagg: finalize failed on worker {self.worker_id}"
                )
                if not flight.fut.done():
                    flight.fut.set_exception(e)
                self.metrics.record_failure(1)
            else:
                self.metrics.record_response(
                    resp.queue_wait_s, resp.compute_s, resp.total_s,
                    head=head.name,
                )
                if span_ctx is not None:
                    tid, sid, parent = span_ctx
                    t_final = time.monotonic()
                    ident = self._decode_span_ident()
                    self.tracer.record_span(
                        "finalize", tid, now, t_final, parent_id=sid,
                        **ident,
                    )
                    # The residency umbrella: admit -> evict, parenting
                    # every decode/spec step span this slot recorded.
                    self.tracer.record_span(
                        "slot_residency", tid, t_admit, t_final,
                        span_id=sid, parent_id=parent, slot=int(slot),
                        **ident,
                    )
                if not flight.fut.done():
                    flight.fut.set_result(resp)
            self.pool.evict(int(slot))
            self.slots.release(slot)
            self.entries[slot] = None
            self.metrics.record_evict(1)

    # -- failure / teardown --------------------------------------------------

    def kill(self) -> list[Flight]:
        """SIGKILL-style death: mark dead, return the flights whose KV
        died with this worker (active slots), and release the emulated
        device resources so the shared bank accounts clean — on a real
        remote host the pages die with the process; here the allocator
        is shared and must not leak the casualty's refs."""
        self.dead = True
        stranded = []
        for slot in self.slots.active_slots():
            flight, _handoff, t_admit, span_ctx = self.entries[slot]
            if not flight.fut.done():
                stranded.append(flight)
            if span_ctx is not None:
                # Close the residency span typed: the trace shows WHERE
                # the request was when its worker died, and the reroute
                # span the front records next stays in the same tree.
                tid, sid, parent = span_ctx
                self.tracer.record_span(
                    "slot_residency", tid, t_admit, time.monotonic(),
                    span_id=sid, parent_id=parent, slot=int(slot),
                    outcome="worker_killed", **self._decode_span_ident(),
                )
            self.pool.evict(int(slot))
            self.slots.release(slot)
            self.entries[slot] = None
        # The emulated device dies with the worker: drop the scratch
        # reservation's refs too, or the shared bank would leak the
        # casualty's pinned pages forever.
        self.pool.release_scratch()
        return stranded

    def stats(self) -> dict:
        return {
            "slots_active": self.pool.active_slot_count,
            "slots_total": self.pool.cfg.max_slots,
            "occupancy": self.occupancy(),
            "headroom": self.headroom(),
            "admitted": self.admitted,
            "decode_steps": self.decode_steps,
            "scratch_pages": self.pool.scratch_page_count,
            "warmup_compiles": self.warmup_compiles,
            "recompilations": self.recompilations,
            "hbm": self.memory.summary(budget_bytes=self._hbm_budget),
        }
