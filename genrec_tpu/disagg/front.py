"""DisaggFront: the engine's `submit() -> Future` surface over
role-specialized prefill/decode worker pools.

Request path: `submit` routes to the least-loaded *prefill worker* of
the request's head (prefill saturates on queue depth); the completed
prefill emits a typed `KVHandoff` which the front routes to the decode
worker with the most free slots (decode saturates on slot occupancy);
the decode worker's continuous-batching loop resolves the caller's
future with full provenance (`Response.prefill_worker_id` /
`decode_worker_id` beside replica/params/catalog).

The co-located `ServingEngine` stays the default; disagg is opt-in per
head — a `DisaggFront` serves paged-capable heads only, and a deployment
mixes fronts and engines per head. The front duck-types the engine
surface (`start/stop/submit/stats()["headroom"]/metrics.warmup_compiles`
/`replica_id`), so a `fleet.FleetRouter` can route over N disagg fronts
exactly as it routes over N engines, while `fleet.Autoscaler` instances
scale the two roles INDEPENDENTLY through `role_pool(head, role)` —
each role pool speaks the router protocol the autoscaler drives
(`scale_signal`/`add_replica`/`remove_replica`).

Failure discipline (the fleet front's, one level down): a decode
worker's SIGKILL-style death strands the flights whose KV died with it —
each is re-submitted typed and AT MOST ONCE back through a surviving
prefill/decode pair (the KV must be re-encoded; a surviving prefill
worker's prefix cache usually makes that re-encode warm), and a second
loss fails `WorkerLostError`, never silence. Handoff validation failures
are typed `HandoffRefusedError` refusals. Drain completes in-flight
handoffs: queued requests prefill, pending handoffs land, decode slots
finish, and the pools on BOTH sides account clean.

Execution model: one runtime thread cooperatively schedules every
worker (prefill pass -> handoff delivery -> one decode step per worker)
— the engine's single-writer pool discipline held across the split, so
the in-process front is a CONTROL-PLANE of the disaggregated system;
compute overlap between roles arrives with the cross-host transport,
which slots in behind `KVTransport` without touching this file.
``start(run_loop=False)`` + `pump_once()` exposes the same scheduling
deterministically for tests and the chaos harness.
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import threading
import time
from concurrent.futures import Future
from typing import Optional, Sequence

from genrec_tpu.disagg.handoff import (
    HandoffRefusedError,
    WorkerLostError,
)
from genrec_tpu.disagg.transport import (
    InProcessTransport,
    KVTransport,
    SerializingTransport,
)
from genrec_tpu.disagg.workers import DecodeWorker, Flight, PrefillWorker
from genrec_tpu.obs.flight_recorder import get_flight_recorder
from genrec_tpu.obs.slo import SLOMonitor, SLOTarget
from genrec_tpu.obs.spans import NULL_TRACER, SpanTracer, TraceContext
from genrec_tpu.serving.buckets import BucketLadder, default_ladder
from genrec_tpu.serving.kv_pool import KVPagePool, PagedConfig
from genrec_tpu.serving.metrics import LatencyHistogram, ServingMetrics
from genrec_tpu.serving.types import (
    DrainingError,
    OverloadError,
    Request,
    UnknownHeadError,
    normalize_spec_config,
)


class _HeadGroup:
    """One head's role pools + in-flight handoffs."""

    __slots__ = ("head", "bank", "transport", "prefill", "decode",
                 "pending", "seq", "spec_topology")

    def __init__(self, head, bank, transport, spec_topology=None):
        self.head = head
        self.bank: Optional[KVPagePool] = bank
        self.transport: KVTransport = transport
        self.prefill: list[PrefillWorker] = []
        self.decode: list[DecodeWorker] = []
        # (flight, handoff, t_sent): sent but not yet admitted — routed
        # to a concrete decode worker only when one has a free slot, so
        # a kill in between strands nothing that is still re-routable.
        self.pending: collections.deque = collections.deque()
        self.seq = {"prefill": 0, "decode": 0}
        # ops.spec_tree.TreeTopology when this head speculates: shared
        # by every decode worker in the group (one topology per rung —
        # the check_spec_hlo pin, held across the split).
        self.spec_topology = spec_topology


class _RolePool:
    """`fleet.Autoscaler`-compatible view of one (head, role) pool:
    scale_signal/add_replica/remove_replica over WORKERS instead of
    engine replicas — the two roles scale independently, each on its own
    saturation signal."""

    def __init__(self, front: "DisaggFront", head: str, role: str):
        self._front = front
        self.head = head
        self.role = role

    def scale_signal(self) -> dict:
        return self._front._role_signal(self.head, self.role)

    def add_replica(self) -> str:
        return self._front._add_worker(self.head, self.role)

    def remove_replica(self, worker_id: str, timeout: float = 60.0) -> dict:
        return self._front._remove_worker(self.head, self.role, worker_id,
                                          timeout)


class DisaggFront:
    def __init__(
        self,
        heads: Sequence,
        params,
        *,
        ladder: Optional[BucketLadder] = None,
        max_batch: int = 8,
        max_wait_ms: float = 4.0,
        n_prefill: int = 1,
        n_decode: int = 1,
        transport: str = "inprocess",
        workers: Optional[Sequence[str]] = None,
        standby_workers: Optional[Sequence[str]] = None,
        remote_net: Optional[dict] = None,
        paged_config: Optional[PagedConfig] = None,
        bank_num_pages: Optional[int] = None,
        prefix_cache: bool = True,
        prefix_cache_entries: int = 4096,
        prefill_hbm_budget_bytes: Optional[int] = None,
        decode_hbm_budget_bytes: Optional[int] = None,
        slo_targets: Optional[dict] = None,
        slo_poll_secs: float = 0.05,
        params_step: Optional[int] = None,
        params_by_head: Optional[bool] = None,
        replica_id: Optional[str] = None,
        spec_decode=False,
        spec_fanout=8,
        mesh=None,
        model_axis: str = "model",
        tracer: Optional[SpanTracer] = None,
        handle_signals: bool = False,
        guard=None,
        logger: Optional[logging.Logger] = None,
    ):
        self._heads = {h.name: h for h in heads}
        if len(self._heads) != len(heads):
            raise ValueError("duplicate head names")
        for h in heads:
            if not getattr(h, "supports_paged", False):
                raise ValueError(
                    f"head {h.name!r} has no paged decode protocol — "
                    "disagg is opt-in per head; serve it on the "
                    "co-located ServingEngine instead"
                )
            h.paged_check_options(handoff=True)
        self._params = params
        self._params_by_head = (
            params_by_head if params_by_head is not None
            else len(self._heads) > 1
        )
        if self._params_by_head:
            missing = [n for n in self._heads if n not in params]
            if missing:
                raise ValueError(f"params missing head subtrees: {missing}")
        self._step = params_step
        self._ladder = ladder or default_ladder(max_batch=max_batch)
        self._max_batch = max_batch
        self._max_wait_s = max_wait_ms / 1e3
        if n_prefill < 1 or n_decode < 1:
            raise ValueError("need at least one worker per role")
        self._n_prefill = n_prefill
        self._n_decode = n_decode
        if transport not in ("inprocess", "serializing", "socket"):
            raise ValueError(
                f"unknown transport {transport!r}: "
                "'inprocess' (zero-copy shared page bank), "
                "'serializing' (host-roundtrip wire) or "
                "'socket' (cross-process decode hosts)"
            )
        if transport == "socket":
            if not workers:
                raise ValueError(
                    "transport='socket' needs workers=[\"host:port\", ...] "
                    "— the decode-host processes this front serves "
                    "through (spawn_decode_host returns the address)"
                )
        elif workers:
            raise ValueError(
                f"workers= is the socket tier's knob; the {transport!r} "
                "transport builds its decode workers in-process "
                "(n_decode=)"
            )
        self._transport_kind = transport
        self._remote_addrs = list(workers or ())
        # Unconnected decode-host addresses scale-out may consume
        # (_add_worker on the socket tier attaches one per call).
        self._standby_addrs = list(standby_workers or ())
        # Socket-tier resilience knobs forwarded verbatim to every
        # RemoteDecodeWorker this front builds (liveness_timeout,
        # reconnect_max, reconnect_base, reconnect_cap, reconnect_seed).
        if remote_net and transport != "socket":
            raise ValueError("remote_net= is the socket tier's knob")
        self._remote_net = dict(remote_net or ())
        self._paged_config = paged_config
        self._bank_num_pages = bank_num_pages
        self._prefix_cache = bool(prefix_cache)
        self._prefix_cache_entries = int(prefix_cache_entries)
        self._prefill_budget = prefill_hbm_budget_bytes
        self._decode_budget = decode_hbm_budget_bytes
        self.replica_id = replica_id
        # Speculative decode on the decode POOL (the engine's exact
        # opt-in surface, per front): True/False, or a set of head
        # names. The decode workers compile the tree-verify rung
        # ladder; prefill workers are untouched beyond the drafter-hint
        # state enable_spec_drafting() adds to the head.
        self._spec_decode, self._spec_fanout = normalize_spec_config(
            spec_decode, spec_fanout, self._heads
        )
        # Tensor-parallel serving operands (the engine's mesh= knob, per
        # front): params shard by serve_rules, owned pools/banks shard
        # their page banks over the head axis. Socket-tier decode HOSTS
        # place their own mesh (factory mesh_shape) — this knob covers
        # the front's prefill side and the in-process tiers.
        self._mesh = mesh
        self._model_axis = str(model_axis)
        self._handle_signals = handle_signals
        self._guard = guard
        self._log = logger or logging.getLogger("genrec_tpu")
        self._flight = get_flight_recorder().scoped(
            "disagg_front", replica_id=lambda: self.replica_id
        )
        # Request lineage: adopt an incoming Request.trace (a fleet
        # router upstream) or mint one here — either way every worker
        # span parents under this front's per-request span. Workers
        # share THIS tracer (one span-id space per process).
        self._tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = ServingMetrics()
        # Role-level SLO guard: {"prefill": SLOTarget, "decode":
        # SLOTarget} applied per head; the monitor keys on
        # "<head>/<role>" and submit sheds when EITHER role of the
        # request's head is shedding (a saturated decode pool must push
        # back at admission, not queue unboundedly at prefill).
        if slo_targets is None:
            self._slo = None
        else:
            unknown = [r for r in slo_targets if r not in ("prefill",
                                                           "decode")]
            if unknown:
                raise ValueError(
                    f"slo_targets keys must be roles, got {unknown}")
            targets = {
                f"{name}/{role}": t
                for name in self._heads
                for role, t in slo_targets.items()
                if isinstance(t, SLOTarget)
            }
            self._slo = SLOMonitor(targets, flight=self._flight)
        self._slo_poll_secs = float(slo_poll_secs)
        self._slo_next_poll = 0.0
        self._groups: dict[str, _HeadGroup] = {}
        # Queue lock + wake condition (submit threads <-> runtime
        # thread) and the coarse runtime lock serializing pump
        # iterations with operator verbs (kill/add/remove).
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._runtime = threading.RLock()
        self._counters = {
            "handoffs_sent": 0,
            "handoffs_admitted": 0,
            "handoffs_refused": 0,
            "handoffs_resubmitted": 0,
            "transfer_bytes": 0,
            "decode_worker_deaths": 0,
            "prefill_worker_deaths": 0,
            "degraded_entered": 0,
            "degraded_exited": 0,
        }
        # Heads whose decode pool currently has ZERO live capacity
        # (socket tier: every remote peer unreachable). While a head is
        # degraded, submit sheds with the recoverable OverloadError
        # instead of queueing work that can only hang; pump_once exits
        # the head the moment a worker (reconnected or promoted
        # standby) is live again.
        self._degraded: set[str] = set()
        self.transfer = LatencyHistogram()
        self._draining = False
        self._drained = threading.Event()
        self._batcher: Optional[threading.Thread] = None
        self._started = False

    # -- construction helpers ------------------------------------------------

    def _select(self, head, params):
        return params[head.name] if self._params_by_head else params

    def _default_config(self, head) -> PagedConfig:
        page_size = 16
        max_kv = head.paged_kv_tokens(10**9, self._ladder.history_buckets[-1])
        return PagedConfig(
            max_slots=4 * self._max_batch,
            page_size=page_size,
            pages_per_slot=-(-max_kv // page_size),
        )

    def _spec_topology_for(self, head, cfg: PagedConfig):
        """One TreeTopology per spec-enabled head group (every decode
        worker's rungs compile the same tree). Calling
        ``enable_spec_drafting()`` HERE — before any worker builds
        state or compiles prefill — lets the head extend its slot
        state/prefill with drafter hints, exactly the engine's
        construction order."""
        want = (
            head.name in self._spec_decode
            if isinstance(self._spec_decode, frozenset)
            else bool(self._spec_decode)
        )
        if not (want and getattr(head, "supports_spec", False)
                and head.spec_depth >= 1):
            return None
        from genrec_tpu.ops.spec_tree import TreeTopology

        head.enable_spec_drafting()
        return TreeTopology(head.top_k, self._spec_fanout, head.spec_depth)

    @staticmethod
    def _scratch_pages_per_worker(topo, cfg: PagedConfig) -> int:
        if topo is None:
            return 0
        return cfg.max_slots * (-(-topo.n_nodes // cfg.page_size))

    def _build_group(self, head) -> _HeadGroup:
        cfg = self._paged_config or self._default_config(head)
        max_kv = head.paged_kv_tokens(10**9, self._ladder.history_buckets[-1])
        if cfg.max_kv_tokens < max_kv:
            raise ValueError(
                f"paged config holds {cfg.max_kv_tokens} KV tokens/slot "
                f"but head {head.name!r} needs {max_kv} at the largest "
                "history bucket"
            )
        topo = self._spec_topology_for(head, cfg)
        n_layers, n_heads, head_dim, dtype = head.paged_layout()
        if self._transport_kind == "inprocess":
            # One shared page bank per head: decode workers are slot
            # VIEWS over it, prefill writes raw runs into it — the
            # zero-copy handoff substrate. Sized for every decode slot
            # plus in-flight prefill staging (retained prefix pages ride
            # inside and reclaim under pressure), EXTENDED by each
            # speculative decode worker's scratch reservation so
            # speculation never eats admission capacity.
            bank_pages = (self._bank_num_pages or (
                1 + cfg.pages_per_slot
                * (self._n_decode * cfg.max_slots + 2 * self._max_batch)
            )) + self._n_decode * self._scratch_pages_per_worker(topo, cfg)
            bank_cfg = PagedConfig(
                max_slots=1, page_size=cfg.page_size,
                pages_per_slot=cfg.pages_per_slot, num_pages=bank_pages,
                kv_dtype=cfg.kv_dtype,
            )
            bank = KVPagePool(bank_cfg, n_layers, n_heads, head_dim, dtype)
            if self._mesh is not None:
                from genrec_tpu.parallel.shardings import kv_pool_sharding

                place = kv_pool_sharding(self._mesh, n_heads,
                                         self._model_axis)
                if place is not None:
                    bank.place(place)
            return _HeadGroup(head, bank, InProcessTransport(bank),
                              spec_topology=topo)
        if self._transport_kind == "socket":
            from genrec_tpu.disagg.net import SocketTransport

            return _HeadGroup(head, None, SocketTransport(),
                              spec_topology=topo)
        return _HeadGroup(head, None, SerializingTransport(),
                          spec_topology=topo)

    def _make_prefill(self, group: _HeadGroup) -> PrefillWorker:
        head = group.head
        wid = f"{head.name}:p{group.seq['prefill']}"
        group.seq["prefill"] += 1
        cfg = self._paged_config or self._default_config(head)
        if group.bank is not None:
            pool, owns = group.bank, False
        else:
            n_layers, n_heads, head_dim, dtype = head.paged_layout()
            staging_cfg = PagedConfig(
                max_slots=1, page_size=cfg.page_size,
                pages_per_slot=cfg.pages_per_slot,
                num_pages=1 + cfg.pages_per_slot * 3 * self._max_batch,
                kv_dtype=cfg.kv_dtype,
            )
            pool = KVPagePool(staging_cfg, n_layers, n_heads, head_dim, dtype)
            owns = True
        return PrefillWorker(
            wid, head, self._select(head, self._params),
            ladder=self._ladder, transport=group.transport, pool=pool,
            owns_pool=owns, max_batch=self._max_batch,
            max_wait_s=self._max_wait_s, metrics=self.metrics,
            flight_recorder=self._flight.scoped("prefill_worker",
                                                worker_id=wid),
            params_step=self._step,
            prefix_cache=self._prefix_cache,
            prefix_cache_entries=self._prefix_cache_entries,
            hbm_budget_bytes=self._prefill_budget,
            tracer=self._tracer,
            mesh=self._mesh, model_axis=self._model_axis,
            logger=self._log,
        )

    def _make_decode(self, group: _HeadGroup) -> DecodeWorker:
        head = group.head
        wid = f"{head.name}:d{group.seq['decode']}"
        group.seq["decode"] += 1
        cfg = self._paged_config or self._default_config(head)
        scratch = self._scratch_pages_per_worker(group.spec_topology, cfg)
        n_layers, n_heads, head_dim, dtype = head.paged_layout()
        if group.bank is not None:
            view_cfg = PagedConfig(
                max_slots=cfg.max_slots, page_size=cfg.page_size,
                pages_per_slot=cfg.pages_per_slot,
                num_pages=group.bank.cfg.num_pages,
                kv_dtype=cfg.kv_dtype,
            )
            pool = KVPagePool(view_cfg, n_layers, n_heads, head_dim, dtype,
                              bank=group.bank)
            owns = False
        else:
            # Serializing tier: each decode worker owns its pool —
            # extend it by the scratch reservation (an explicit
            # paged_config keeps its admission capacity; the ledger
            # sees the real total — the engine's discipline).
            if scratch:
                cfg = dataclasses.replace(
                    cfg, num_pages=cfg.num_pages + scratch
                )
            pool = KVPagePool(cfg, n_layers, n_heads, head_dim, dtype)
            owns = True
        return DecodeWorker(
            wid, head, self._select(head, self._params),
            transport=group.transport, pool=pool, owns_pool=owns,
            ladder=self._ladder, metrics=self.metrics,
            flight_recorder=self._flight.scoped("decode_worker",
                                                worker_id=wid),
            slot_floor=min(self._max_batch, cfg.max_slots),
            params_step=self._step, replica_id=self.replica_id,
            hbm_budget_bytes=self._decode_budget,
            spec_topology=group.spec_topology,
            spec_fanout=self._spec_fanout,
            tracer=self._tracer,
            mesh=self._mesh, model_axis=self._model_axis,
            logger=self._log,
        )

    def _make_remote_decode(self, addr: str):
        """One connected `RemoteDecodeWorker` proxy for a decode-host
        process (socket tier). The host accepts exactly ONE connection,
        so the proxy connects once and is then routed to its head's
        group by the identity it announced in its HELLO — a dead
        address or an unknown head refuses at attach time, typed, never
        at delivery time."""
        from genrec_tpu.disagg.net import RemoteDecodeWorker

        # The group's transport carries the tier's wire counters; until
        # the HELLO names the head, connect through a throwaway one and
        # swap after routing (warmup only touches connect counters).
        w = RemoteDecodeWorker(
            addr, transport=next(
                g.transport for g in self._groups.values()
            ), metrics=self.metrics, counters=self._counters,
            flight_recorder=self._flight.scoped("decode_worker",
                                                worker_id=addr),
            replica_id=self.replica_id, tracer=self._tracer,
            logger=self._log, **self._remote_net,
        )
        w.warmup()
        head_name = w.identity["head"]
        group = self._groups.get(head_name)
        if group is None:
            w.kill()
            raise UnknownHeadError(
                f"decode host {addr} serves head {head_name!r} but "
                f"this front only has {sorted(self._groups)}"
            )
        w.worker_id = f"{head_name}:d{group.seq['decode']}"
        group.seq["decode"] += 1
        w.transport = group.transport
        w._flight = self._flight.scoped("decode_worker",
                                        worker_id=w.worker_id)
        group.decode.append(w)
        return w

    def _connect_remote_decodes(self) -> None:
        """Socket tier: attach every configured decode-host address."""
        for addr in self._remote_addrs:
            self._make_remote_decode(addr)

    # -- lifecycle -----------------------------------------------------------

    def start(self, run_loop: bool = True) -> "DisaggFront":
        if self._started:
            raise RuntimeError("front already started")
        for head in self._heads.values():
            head.on_params(self._select(head, self._params))
        t0 = time.monotonic()
        for head in self._heads.values():
            group = self._build_group(head)
            for _ in range(self._n_prefill):
                group.prefill.append(self._make_prefill(group))
            if self._transport_kind != "socket":
                for _ in range(self._n_decode):
                    group.decode.append(self._make_decode(group))
            self._groups[head.name] = group
        if self._transport_kind == "socket":
            # Decode pools live in their own processes: attach one
            # proxy per configured host (connect + HELLO; the host
            # warmed its grid before accepting).
            self._connect_remote_decodes()
            for name, g in self._groups.items():
                if not g.decode:
                    raise WorkerLostError(
                        f"no decode host connected for head {name!r} — "
                        "every head needs at least one workers= address"
                    )
        workers = [w for g in self._groups.values()
                   for w in g.prefill + g.decode]
        for w in workers:
            # Operands-only budget pass over EVERY worker first: an
            # impossible budget on any role refuses before the front
            # pays a single compile (prefill warms before decode below,
            # so warmup()'s own early check alone would not cover a
            # decode-side refusal).
            w._ledger(operands_only=True)
        for w in workers:
            w.warmup()  # HBMBudgetError refusal propagates
        self.metrics.warmup_compiles = sum(
            w.warmup_compiles
            for g in self._groups.values() for w in g.prefill + g.decode
        )
        self.metrics.mark_warm()
        if self._guard is None and self._handle_signals:
            from genrec_tpu.core.preemption import PreemptionGuard

            self._guard = PreemptionGuard(self._log)
        self._started = True
        self._flight.record(
            "disagg_started", heads=sorted(self._heads),
            transport=self._transport_kind,
            prefill_workers=sum(len(g.prefill)
                                for g in self._groups.values()),
            decode_workers=sum(len(g.decode)
                               for g in self._groups.values()),
            warmup_compiles=self.metrics.warmup_compiles,
            replica_id=self.replica_id,
        )
        self._log.info(
            f"disagg: front up ({self._transport_kind} transport, "
            f"{self._n_prefill} prefill + {self._n_decode} decode "
            f"workers/head, {self.metrics.warmup_compiles} warmup "
            f"executables in {time.monotonic() - t0:.1f}s)"
        )
        if run_loop:
            self._batcher = threading.Thread(
                target=self._run_loop, name="disagg-runtime", daemon=True
            )
            self._batcher.start()
        return self

    def stop(self, timeout: float = 60.0) -> dict:
        """Drain: queued requests prefill, in-flight handoffs land,
        decode slots finish; new submissions get the typed error.
        Idempotent; returns the final stats snapshot."""
        with self._lock:
            self._draining = True
            self._work.notify_all()
        if self._batcher is not None:
            self._batcher.join(timeout)
        else:
            # Loop-less front (run_loop=False): pump the drain here.
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                progressed = self.pump_once()
                if self._check_drained():
                    break
                if not progressed:
                    time.sleep(1e-3)
            if not self._drained.is_set():
                self._finish_drain()
        if self._guard is not None:
            self._guard.close()
        return self.stats()

    def join(self, timeout: Optional[float] = None) -> bool:
        return self._drained.wait(timeout)

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def params_step(self) -> Optional[int]:
        return self._step

    def set_tracer(self, tracer: Optional[SpanTracer]) -> None:
        """Swap lineage tracing live, front-wide: the front's own spans
        and every worker's. The workers read their ``tracer`` attribute
        per call, so this is a plain reference swap (the engine's
        set_tracer contract, one level down)."""
        self._tracer = tracer if tracer is not None else NULL_TRACER
        with self._runtime:
            for group in self._groups.values():
                for w in group.prefill + group.decode:
                    w.tracer = self._tracer

    # -- request path --------------------------------------------------------

    def submit(self, req: Request) -> Future:
        head = self._heads.get(req.head)
        if head is None:
            raise UnknownHeadError(
                f"unknown head {req.head!r}; have {sorted(self._heads)}"
            )
        head.validate(req)
        flight = Flight(req)
        with self._lock:
            if self._draining:
                self.metrics.record_reject(req.head)
                raise DrainingError(
                    "disagg front is draining; request rejected — fail "
                    "over to another replica"
                )
            if self._slo is not None and (
                self._slo.is_shedding(f"{req.head}/prefill")
                or self._slo.is_shedding(f"{req.head}/decode")
            ):
                self.metrics.record_overload(req.head)
                raise OverloadError(
                    f"head {req.head!r} disagg pools are load-shedding; "
                    "back off and retry or fail over"
                )
            if req.head in self._degraded:
                # Degraded mode: every remote decode peer is currently
                # unreachable. Shed at admission with the recoverable
                # error rather than accept work that can only pile up
                # behind reconnect — the caller (or FleetRouter) backs
                # off / fails over, and the head exits degraded the
                # moment a peer is live again.
                self.metrics.record_overload(req.head)
                raise OverloadError(
                    f"head {req.head!r} is in degraded mode (no "
                    "reachable decode peers); back off and retry or "
                    "fail over"
                )
            self._attach_trace(flight)
            try:
                self._enqueue_locked(flight)
            except WorkerLostError as e:
                # Zero live prefill workers: to a FLEET caller this
                # replica is saturated-unusable, not broken — raise the
                # recoverable error FleetRouter fails over on
                # (WorkerLostError would propagate through the router as
                # a caller bug and skip the surviving replicas).
                self.metrics.record_overload(req.head)
                raise OverloadError(
                    f"head {req.head!r} has no live prefill workers on "
                    f"this front; fail over ({e})"
                ) from e
            self._work.notify()
        self.metrics.record_submit(head=req.head)
        return flight.fut

    def _attach_trace(self, flight: Flight) -> None:
        """Adopt the request's incoming lineage (a fleet router above
        us) or mint it here, and pre-allocate this front's per-request
        span: the prefill worker's admission/prefill spans, the
        handoff's wire spans and the decode worker's residency span all
        parent onto it, and it is recorded — submit to future-resolve,
        reroutes included — when the caller's future settles."""
        req = flight.req
        ctx_in = req.trace
        tracer = self._tracer
        if not tracer.enabled:
            if ctx_in is not None:
                # Tracing off on this front but the request IS traced:
                # carry the id (Response.request_id provenance); span
                # recording no-ops downstream.
                flight.trace = ctx_in
            return
        tid = ctx_in.trace_id if ctx_in is not None else tracer.new_trace()
        parent = ctx_in.parent_span_id if ctx_in is not None else None
        origin = ctx_in.origin if ctx_in is not None else "disagg_front"
        fspan = tracer.allocate_span_id()
        flight.trace = TraceContext(tid, fspan, origin)
        t_sub = flight.t_enq
        ident = {"component": "disagg_front"}
        if self.replica_id is not None:
            ident["replica"] = self.replica_id

        def _record_request(f, tid=tid, fspan=fspan, parent=parent,
                            t_sub=t_sub, head_name=req.head,
                            origin=origin, ident=ident):
            try:
                outcome = "error" if f.exception() else "ok"
            except Exception:  # noqa: BLE001 — cancelled future
                outcome = "cancelled"
            tracer.record_span(
                "request", tid, t_sub, time.monotonic(), span_id=fspan,
                parent_id=parent, head=head_name, origin=origin,
                outcome=outcome, **ident,
            )

        flight.fut.add_done_callback(_record_request)

    def serve(self, req: Request, timeout: Optional[float] = 60.0):
        return self.submit(req).result(timeout)

    def _enqueue_locked(self, flight: Flight) -> None:
        """Route to the prefill worker with the shallowest queue (the
        prefill pool's saturation signal IS queue depth). Caller holds
        the queue lock."""
        group = self._groups[flight.req.head]
        live = [w for w in group.prefill if not w.dead and not w.draining]
        if not live:
            raise WorkerLostError(
                f"no live prefill workers for head {flight.req.head!r}"
            )
        min(live, key=lambda w: (len(w.queue), w.worker_id)).queue.append(
            flight
        )

    # -- the runtime loop ----------------------------------------------------

    def _run_loop(self) -> None:
        try:
            while True:
                try:
                    if (
                        self._guard is not None
                        and self._guard.fired
                        and not self._draining
                    ):
                        with self._lock:
                            self._draining = True
                        self._flight.record("disagg_drain_started",
                                            cause="signal")
                    progressed = self.pump_once()
                    if self._draining and self._check_drained():
                        break
                    if progressed:
                        continue
                    with self._lock:
                        busy = any(
                            w.queue for g in self._groups.values()
                            for w in g.prefill
                        ) or any(g.pending for g in self._groups.values())
                        self._work.wait(
                            timeout=max(self._max_wait_s / 4, 1e-3)
                            if busy else 0.05
                        )
                except Exception:  # noqa: BLE001 — the loop must survive
                    self._log.exception("disagg: runtime iteration failed")
        finally:
            self._finish_drain()

    def pump_once(self) -> bool:
        """One cooperative scheduling pass over every worker: prefill
        admission -> handoff delivery -> one decode step per worker.
        Deterministically callable when started with run_loop=False (the
        chaos tests single-step the pipeline through it)."""
        progressed = False
        with self._runtime:
            for group in self._groups.values():
                for pw in list(group.prefill):
                    if pw.dead:
                        continue
                    for fl, handoff in pw.pump(self._lock, self._draining):
                        self._counters["handoffs_sent"] += 1
                        self._flight.record(
                            "handoff_sent", head=group.head.name,
                            prefill_worker=handoff.prefill_worker_id,
                            n_tokens=handoff.n_tokens, warm=handoff.warm,
                            transfer_bytes=handoff.transfer_bytes,
                        )
                        group.pending.append((fl, handoff, time.monotonic()))
                        progressed = True
                progressed |= self._deliver(group)
                for dw in list(group.decode):
                    if dw.dead:
                        # A remote proxy marks ITSELF dead when its peer
                        # process drops (kill -9 included) — the pump
                        # reaps it here exactly like kill_decode_worker:
                        # re-submit every resident flight, typed and
                        # at-most-once. In-process workers only die via
                        # the kill verb, which already removed them.
                        if dw in group.decode:
                            self._reap_dead_decode(group, dw)
                            progressed = True
                        continue
                    progressed |= dw.step()
                    # A reconnect stranded this worker's pre-reconnect
                    # flights (the host orphaned them): re-submit each
                    # through prefill, at-most-once, exactly like the
                    # death path — but the worker itself stays live.
                    take = getattr(dw, "take_stranded", None)
                    if take is not None:
                        for fl in take():
                            self._resubmit(group, fl,
                                           from_worker=dw.worker_id)
                            progressed = True
                self._update_degraded(group)
            self._poll_slo()
        return progressed

    def _update_degraded(self, group: _HeadGroup) -> None:
        """Enter/exit the head's degraded mode on the socket tier: zero
        reachable decode peers in, first live peer out. Flight-evented
        both ways and visible in stats()["disagg"]["degraded_heads"]."""
        if self._transport_kind != "socket":
            return
        name = group.head.name
        live = any(
            not w.dead and not w.draining
            and not getattr(w, "reconnecting", False)
            for w in group.decode
        )
        if not live and name not in self._degraded:
            self._degraded.add(name)
            self._counters["degraded_entered"] += 1
            self._flight.record(
                "degraded_mode_entered", head=name,
                decode_workers=len(group.decode),
            )
            self._log.warning(
                f"disagg: head {name!r} entered degraded mode — no "
                "reachable decode peers; shedding at admission"
            )
        elif live and name in self._degraded:
            self._degraded.discard(name)
            self._counters["degraded_exited"] += 1
            self._flight.record("degraded_mode_exited", head=name)
            self._log.info(
                f"disagg: head {name!r} exited degraded mode — decode "
                "capacity restored"
            )

    def _reap_dead_decode(self, group: _HeadGroup, worker) -> None:
        """kill_decode_worker's body for a worker that died on its own
        (a lost decode-host peer): remove, strand, re-submit typed."""
        group.decode.remove(worker)
        stranded = worker.kill()
        group.transport.forget(worker.pool)
        self._counters["decode_worker_deaths"] += 1
        self._flight.record(
            "disagg_worker_dead", worker=worker.worker_id, role="decode",
            head=group.head.name, stranded=len(stranded),
            survivors=len(group.decode),
            peer=getattr(worker, "peer_addr", None),
        )
        self._log.warning(
            f"disagg: decode worker {worker.worker_id} "
            f"({getattr(worker, 'peer_addr', 'in-process')}) lost with "
            f"{len(stranded)} requests resident — re-submitting through "
            f"{len(group.decode)} survivors"
        )
        for fl in stranded:
            self._resubmit(group, fl, from_worker=worker.worker_id)

    def _deliver(self, group: _HeadGroup) -> bool:
        """Route pending handoffs onto decode workers with free slots
        (most-free-first — the decode pool's saturation signal is slot
        occupancy). A handoff with no admissible worker NOW stays
        pending; zero live decode workers is a typed failure."""
        progressed = False
        while group.pending:
            live = [w for w in group.decode
                    if not w.dead and not w.draining]
            if not live:
                fl, handoff, _t = group.pending.popleft()
                group.transport.release(handoff)
                if not fl.fut.done():
                    if self._transport_kind == "socket":
                        # Socket tier: dead peers are a NETWORK outcome
                        # (partition, crash) the fleet fails over on —
                        # shed recoverable, and enter degraded mode so
                        # subsequent submits shed at admission instead
                        # of burning a prefill first.
                        self._update_degraded(group)
                        self.metrics.record_overload(group.head.name)
                        fl.fut.set_exception(OverloadError(
                            f"head {group.head.name!r} has no reachable "
                            "decode peers (degraded mode); back off and "
                            "retry or fail over"
                        ))
                    else:
                        fl.fut.set_exception(WorkerLostError(
                            f"no live decode workers for head "
                            f"{group.head.name!r}; handoff dropped typed"
                        ))
                    self.metrics.record_failure(1)
                progressed = True
                continue
            target = max(live, key=lambda w: (w.free_slots, w.worker_id))
            if target.free_slots == 0:
                break  # every live worker full: deliver after evictions
            fl, handoff, t_sent = group.pending.popleft()
            if fl.fut.done():  # caller cancelled while in flight
                group.transport.release(handoff)
                continue
            tb = handoff.transfer_bytes
            t_adm0 = time.monotonic()
            try:
                target.validate(handoff)
                admitted = target.admit(fl, handoff)
            except Exception as e:  # noqa: BLE001 — any admit failure
                # Typed refusals AND unexpected admit errors take the
                # same exit: the flight was already popped from pending,
                # so anything escaping here would strand its future
                # unresolved (the caller hangs to its own timeout).
                if not isinstance(e, HandoffRefusedError):
                    self._log.exception(
                        f"disagg: handoff admit failed on "
                        f"{target.worker_id}"
                    )
                group.transport.release(handoff)
                self._counters["handoffs_refused"] += 1
                self._flight.record(
                    "handoff_refused", head=group.head.name,
                    prefill_worker=handoff.prefill_worker_id,
                    decode_worker=target.worker_id, reason=str(e),
                )
                if not fl.fut.done():
                    fl.fut.set_exception(e)
                self.metrics.record_failure(1)
                progressed = True
                continue
            if not admitted:
                group.pending.appendleft((fl, handoff, t_sent))
                break
            if fl.trace is not None and self._tracer.enabled:
                tr = fl.trace
                # The tail's two disagg-specific segments: time the
                # handoff sat waiting for a free decode slot, and the
                # receive side of the wire (unpack + scatter + bind).
                self._tracer.record_span(
                    "decode_slot_wait", tr.trace_id, t_sent, t_adm0,
                    parent_id=tr.parent_span_id, component="disagg_front",
                    worker=target.worker_id,
                )
                self._tracer.record_span(
                    "handoff_wire", tr.trace_id, t_adm0, time.monotonic(),
                    parent_id=tr.parent_span_id, side="admit",
                    transport=group.transport.name, transfer_bytes=tb,
                    component="decode_worker", worker=target.worker_id,
                    peer=getattr(target, "peer_addr", None),
                )
            self._counters["handoffs_admitted"] += 1
            self._counters["transfer_bytes"] += tb
            self.transfer.record(time.monotonic() - t_sent)
            self._flight.record(
                "handoff_admitted", head=group.head.name,
                prefill_worker=handoff.prefill_worker_id,
                decode_worker=target.worker_id,
                n_tokens=handoff.n_tokens, warm=handoff.warm,
                transfer_bytes=tb,
            )
            progressed = True
        return progressed

    def _check_drained(self) -> bool:
        with self._lock:
            queues_empty = all(
                not w.queue for g in self._groups.values()
                for w in g.prefill
            )
        return (
            queues_empty
            and all(not g.pending for g in self._groups.values())
            and all(dw.idle for g in self._groups.values()
                    for dw in g.decode if not dw.dead)
        )

    def _finish_drain(self) -> None:
        # Release every retained prefix page — and every speculative
        # scratch reservation — so the banks/pools account clean at
        # shutdown (pages released after drain — the check_disagg bar,
        # both sides; scratch_pages == 0 is the check_spec bar).
        with self._runtime:
            for group in self._groups.values():
                for pw in group.prefill:
                    pw.clear_prefix_cache("drain")
                for dw in group.decode:
                    n = dw.pool.release_scratch()
                    if n:
                        self._flight.record(
                            "spec_scratch_released", head=group.head.name,
                            worker_id=dw.worker_id, reason="drain", pages=n,
                        )
                    if hasattr(dw, "close"):
                        # Remote proxy: SHUTDOWN handshake drains the
                        # host process and closes both sockets clean.
                        dw.close()
        self._flight.record("disagg_stopped",
                            completed=self.metrics.completed)
        self._drained.set()

    # -- SLO guard -----------------------------------------------------------

    def _poll_slo(self) -> None:
        if self._slo is None:
            return
        now = time.monotonic()
        if now < self._slo_next_poll:
            return
        self._slo_next_poll = now + self._slo_poll_secs
        for name, group in self._groups.items():
            with self._lock:
                qdepth = sum(len(w.queue) for w in group.prefill)
            for role, depth, p99, deferred in (
                # Deferral is an ADMISSION-side phenomenon: feed the
                # per-head oom/submit counters to the prefill target so
                # SLOTarget.max_deferral_rate sheds a page-thrashing
                # pool (the engine's _poll_slo wiring, per role).
                ("prefill", qdepth, None,
                 self.metrics.oom_deferred_by_head[name]),
                ("decode", len(group.pending),
                 self.metrics.recent_p99_ms(
                     self._slo.targets.get(
                         f"{name}/decode",
                         SLOTarget(max_queue_depth=1)).window_s,
                     head=name)
                 if f"{name}/decode" in self._slo.targets else None, None),
            ):
                key = f"{name}/{role}"
                if key in self._slo.targets:
                    self._slo.observe(
                        key, p99_ms=p99, queue_depth=depth,
                        oom_deferred_total=deferred,
                        submitted_total=(
                            self.metrics.submitted_by_head[name]
                            if deferred is not None else None),
                        now=now)

    # -- failure injection / role scaling ------------------------------------

    def kill_decode_worker(self, worker_id: str) -> int:
        """SIGKILL-style decode-worker death: its resident KV is gone,
        every flight it held is re-submitted typed + at-most-once back
        through the prefill path on the survivors. Returns the stranded
        count."""
        with self._runtime:
            group, worker = self._find(worker_id, "decode")
            group.decode.remove(worker)
            stranded = worker.kill()
            group.transport.forget(worker.pool)
            self._counters["decode_worker_deaths"] += 1
            self._flight.record(
                "disagg_worker_dead", worker=worker_id, role="decode",
                head=group.head.name, stranded=len(stranded),
                survivors=len(group.decode),
            )
            self._log.warning(
                f"disagg: decode worker {worker_id} died with "
                f"{len(stranded)} requests resident — re-submitting "
                f"through {len(group.decode)} survivors"
            )
            for fl in stranded:
                self._resubmit(group, fl, from_worker=worker_id)
        with self._lock:
            self._work.notify()
        return len(stranded)

    def kill_prefill_worker(self, worker_id: str) -> int:
        """Prefill-worker death: nothing decoded is lost (its queue
        holds un-prefilled requests), but its retained prefix pages and
        queue die with it — queued flights re-route to surviving prefill
        workers (no retry spent: no accepted work was lost)."""
        with self._runtime:
            group, worker = self._find(worker_id, "prefill")
            group.prefill.remove(worker)
            worker.dead = True
            worker.clear_prefix_cache("worker_killed")
            group.transport.forget(worker.pool)
            with self._lock:
                stranded = list(worker.queue)
                worker.queue.clear()
            self._counters["prefill_worker_deaths"] += 1
            self._flight.record(
                "disagg_worker_dead", worker=worker_id, role="prefill",
                head=group.head.name, stranded=len(stranded),
                survivors=len(group.prefill),
            )
            for fl in stranded:
                try:
                    with self._lock:
                        self._enqueue_locked(fl)
                except WorkerLostError as e:
                    if not fl.fut.done():
                        fl.fut.set_exception(e)
                        self.metrics.record_failure(1)
        with self._lock:
            self._work.notify()
        return len(stranded)

    def _resubmit(self, group: _HeadGroup, flight: Flight,
                  from_worker: str) -> None:
        if flight.fut.done():
            return
        if flight.retried:
            flight.fut.set_exception(WorkerLostError(
                f"request lost decode worker {from_worker} after already "
                "being re-submitted once (at-most-once retry exhausted)"
            ))
            self.metrics.record_failure(1)
            return
        live_decode = [w for w in group.decode if not w.dead]
        if not live_decode:
            flight.fut.set_exception(WorkerLostError(
                f"decode worker {from_worker} died and no decode "
                "capacity survives for the re-submit"
            ))
            self.metrics.record_failure(1)
            return
        flight.retried = True
        try:
            with self._lock:
                self._enqueue_locked(flight)
        except WorkerLostError as e:
            flight.fut.set_exception(e)
            self.metrics.record_failure(1)
            return
        self._counters["handoffs_resubmitted"] += 1
        self._flight.record(
            "handoff_resubmitted", head=group.head.name,
            worker_from=from_worker,
            trace_id=flight.trace.trace_id
            if flight.trace is not None else None,
        )

    def _find(self, worker_id: str, role: str):
        for group in self._groups.values():
            pool = group.decode if role == "decode" else group.prefill
            for w in pool:
                if w.worker_id == worker_id:
                    return group, w
        raise KeyError(f"no live {role} worker {worker_id!r}")

    def role_pool(self, head: str, role: str) -> _RolePool:
        if head not in self._heads or role not in ("prefill", "decode"):
            raise KeyError(f"no role pool ({head!r}, {role!r})")
        return _RolePool(self, head, role)

    def _role_signal(self, head: str, role: str) -> dict:
        group = self._groups[head]
        workers = group.prefill if role == "prefill" else group.decode
        per = {}
        with self._lock:
            pending = len(group.pending)
            for w in workers:
                if w.dead or w.draining:
                    continue
                hr = w.headroom()
                if role == "prefill":
                    shedding = len(w.queue) >= 4 * self._max_batch
                else:
                    shedding = w.free_slots == 0 and pending > 0
                per[w.worker_id] = {"headroom": hr, "shedding": shedding}
        return {"replicas": per, "alive": len(per)}

    def _add_worker(self, head: str, role: str) -> str:
        with self._runtime:
            if self._draining:
                raise DrainingError("front is draining; refusing scale-out")
            group = self._groups[head]
            if role == "prefill":
                w = self._make_prefill(group)
                w.warmup()
                group.prefill.append(w)
            elif self._transport_kind == "socket":
                # Scale-out attaches the next standby decode host; the
                # socket tier never builds decode workers in-process.
                if not self._standby_addrs:
                    raise WorkerLostError(
                        "socket-tier decode scale-out needs a standby "
                        "decode host (standby_workers=) — none left"
                    )
                w = self._make_remote_decode(self._standby_addrs.pop(0))
            else:
                w = self._make_decode(group)
                w.warmup()
                group.decode.append(w)
            self._flight.record(
                "disagg_worker_added", worker=w.worker_id, role=role,
                head=head, warmup_compiles=w.warmup_compiles,
            )
        with self._lock:
            self._work.notify()
        return w.worker_id

    def _remove_worker(self, head: str, role: str, worker_id: str,
                       timeout: float) -> dict:
        group, worker = self._find(worker_id, role)
        worker.draining = True
        if role == "prefill":
            # Re-route its queued flights; nothing prefilled is lost.
            # Removing the LAST live prefill worker fails its queue
            # typed — a raise here would strand the flights with their
            # futures never set (callers hang to their own timeouts).
            with self._runtime:
                with self._lock:
                    queued = list(worker.queue)
                    worker.queue.clear()
                group.prefill.remove(worker)
                for fl in queued:
                    try:
                        with self._lock:
                            self._enqueue_locked(fl)
                    except WorkerLostError as e:
                        if not fl.fut.done():
                            fl.fut.set_exception(e)
                            self.metrics.record_failure(1)
                worker.clear_prefix_cache("scale_in")
        else:
            # Graceful: stop routing handoffs to it, let resident slots
            # finish (the loop keeps stepping it), then drop the handle.
            deadline = time.monotonic() + timeout
            while not worker.idle and time.monotonic() < deadline:
                if self._batcher is None:
                    self.pump_once()
                else:
                    time.sleep(0.005)
            if not worker.idle:
                raise TimeoutError(
                    f"decode worker {worker_id} did not drain in "
                    f"{timeout}s"
                )
            with self._runtime:
                group.decode.remove(worker)
                # A removed worker's scratch reservation leaves with it
                # (its refs would pin shared-bank pages forever).
                worker.pool.release_scratch()
                if hasattr(worker, "close"):
                    worker.close()
        group.transport.forget(worker.pool)
        final = worker.stats()
        self._flight.record(
            "disagg_worker_removed", worker=worker_id, role=role,
            head=head,
        )
        return final

    # -- observability -------------------------------------------------------

    def stats(self) -> dict:
        snap = self.metrics.snapshot()
        snap["params_step"] = self._step
        snap["draining"] = self._draining
        workers = [w for g in self._groups.values()
                   for w in g.prefill + g.decode]
        snap["warmup_compiles"] = sum(w.warmup_compiles for w in workers)
        snap["recompilations"] = sum(w.recompilations for w in workers)
        with self._lock:
            depths = {
                name: sum(len(w.queue) for w in g.prefill)
                for name, g in self._groups.items()
            }
        snap["queue_depth"] = depths
        headroom, kv_pool, roles_by_head = {}, {}, {}
        for name, g in self._groups.items():
            pre_live = [w for w in g.prefill if not w.dead and not w.draining]
            dec_live = [w for w in g.decode if not w.dead and not w.draining]
            pre_hr = max((w.headroom() for w in pre_live), default=-1.0)
            dec_hr = max((w.headroom() for w in dec_live), default=-1.0)
            headroom[name] = round(
                min(pre_hr, dec_hr, -1.0 if self._draining else 1.0), 4
            )
            pools = []
            if g.bank is not None:
                pools.append(g.bank)
            else:
                pools.extend(w.pool for w in g.prefill if w.owns_pool)
                pools.extend(w.pool for w in g.decode if w.owns_pool)
            kv_pool[name] = {
                "pages_in_use": sum(p.allocator.pages_in_use for p in pools),
                "pages_free": sum(p.allocator.pages_free for p in pools),
                "slots_active": sum(w.pool.active_slot_count
                                    for w in g.decode),
                "slots_total": sum(w.pool.cfg.max_slots for w in g.decode),
                "kv_tokens_resident": int(sum(
                    w.pool.seq_lens.sum() for w in g.decode
                )),
            }
            roles_by_head[name] = {
                "prefill": {
                    "workers": len(pre_live),
                    "queue_depth": depths[name],
                    "headroom": round(pre_hr, 4),
                    "deferred": sum(w.deferred for w in g.prefill),
                    "per_worker": {w.worker_id: w.stats()
                                   for w in g.prefill},
                },
                "decode": {
                    "workers": len(dec_live),
                    "pending_handoffs": len(g.pending),
                    "slots_active": kv_pool[name]["slots_active"],
                    "slots_total": kv_pool[name]["slots_total"],
                    "headroom": round(dec_hr, 4),
                    "per_worker": {w.worker_id: w.stats()
                                   for w in g.decode},
                },
            }
        snap["headroom"] = headroom
        snap["kv_pool"] = kv_pool
        snap["tracing"] = self._tracer.stats()
        snap["disagg"] = {
            "transport": self._transport_kind,
            **dict(self._counters),
            "pending_handoffs": sum(len(g.pending)
                                    for g in self._groups.values()),
            "degraded_heads": sorted(self._degraded),
            "transfer_ms": self.transfer.summary(),
            "roles": roles_by_head,
        }
        transports = {}
        for g in self._groups.values():
            tstats = g.transport.stats()
            if tstats:
                transports[g.transport.name] = tstats
        if transports:
            snap["disagg"]["transports"] = transports
        if self._slo is not None:
            snap["slo"] = self._slo.snapshot()
        return snap
