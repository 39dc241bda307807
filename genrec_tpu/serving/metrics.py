"""Serving instrumentation: latency histograms, QPS, bucket/compile counters.

Day-one observability for the engine (the ISSUE's explicit requirement):
per-request queue-wait / compute / total latency histograms with
p50/p95/p99, lifetime QPS, per-(head, batch, history)
bucket-hit counts, and the recompilation counter that
scripts/check_serving_hlo.py asserts stays ZERO in steady state.

Histograms are fixed log-spaced buckets (Prometheus-style) so recording
is O(log n_buckets) with no per-request allocation; percentiles report
the upper edge of the containing bucket (<= 25% relative error at the
chosen growth factor, plenty for alerting-grade latency numbers).
"""

from __future__ import annotations

import bisect
import collections
import threading
import time


class LatencyHistogram:
    """Log-spaced latency histogram over [100us, ~15min]."""

    def __init__(self, base: float = 1e-4, factor: float = 1.25, n: int = 64):
        self.bounds = [base * factor**i for i in range(n)]  # upper edges
        self.counts = [0] * (n + 1)  # last bucket = overflow
        self.count = 0
        self.total = 0.0
        self.max = 0.0

    def record(self, seconds: float) -> None:
        seconds = max(float(seconds), 0.0)
        self.counts[bisect.bisect_left(self.bounds, seconds)] += 1
        self.count += 1
        self.total += seconds
        self.max = max(self.max, seconds)

    def percentile(self, q: float) -> float:
        """Upper edge of the bucket holding the q-quantile (0 < q <= 1)."""
        if self.count == 0:
            return 0.0
        target = q * self.count
        seen = 0
        for i, c in enumerate(self.counts):
            seen += c
            if seen >= target:
                return self.bounds[i] if i < len(self.bounds) else self.max
        return self.max

    def summary(self, scale: float = 1e3) -> dict:
        """p50/p95/p99/mean/max, scaled (default: seconds -> ms)."""
        mean = self.total / self.count if self.count else 0.0
        return {
            "p50": round(self.percentile(0.50) * scale, 3),
            "p95": round(self.percentile(0.95) * scale, 3),
            "p99": round(self.percentile(0.99) * scale, 3),
            "mean": round(mean * scale, 3),
            "max": round(self.max * scale, 3),
            "count": self.count,
        }


class ServingMetrics:
    """Thread-safe counters + histograms for one engine instance."""

    def __init__(self, recent_window: int = 2048):
        self._lock = threading.Lock()
        self.queue_wait = LatencyHistogram()
        self.compute = LatencyHistogram()
        self.total = LatencyHistogram()
        self.submitted = 0
        self.completed = 0
        self.rejected = 0
        self.failed = 0
        self.batches = 0
        self.bucket_hits: collections.Counter = collections.Counter()
        self.warmup_compiles = 0
        self.recompilations = 0  # post-warmup compiles: steady state => 0
        self.params_swaps = 0
        # Checkpoint-watcher poll failures (transient FS errors included)
        # — a silently skipped poll must still be visible (docs/
        # OBSERVABILITY.md; the flight event carries the classification).
        self.watcher_errors = 0
        # Live-catalog subsystem: swaps applied, and AOT compiles done by
        # the catalog STAGING path on capacity-rung growth — intentional
        # off-hot-path work, counted apart from steady-state
        # recompilations (which check_serving_hlo pins at zero).
        self.catalog_swaps = 0
        self.catalog_compiles = 0
        # Paged decode (slot-level continuous batching): admit/evict churn,
        # deferred-for-OOM admits, decode-step count, and per-head KV-pool
        # gauges so pool pressure is visible in the operator line.
        self.admits = 0
        self.evictions = 0
        self.oom_deferred_admits = 0
        self.decode_steps = 0
        # Useful over attempted, counted where the work happens: slots a
        # decode step was compiled for (sum of S) against slots that held
        # a request (sum of live), the KV tokens those live slots attended
        # over, and the steps taken at each slot rung; real prefill rows
        # and history positions against the (B, L) bucket they ran in.
        self.decode_slot_steps = 0
        self.decode_live_slot_steps = 0
        self.decode_kv_tokens = 0
        self.decode_steps_by_slots: collections.Counter = collections.Counter()
        self.prefill_rows = 0
        self.prefill_row_slots = 0
        self.prefill_tokens = 0
        self.prefill_token_slots = 0
        self.prefill_prompt_tokens = 0  # KV tokens the prefills wrote
        self.rejected_by_head: collections.Counter = collections.Counter()
        # Per-head submit/deferral attribution (the SLO monitor's rate
        # denominators/numerators — engine totals would let one head's
        # pool pressure read as every head's breach).
        self.submitted_by_head: collections.Counter = collections.Counter()
        self.oom_deferred_by_head: collections.Counter = collections.Counter()
        self.pool_gauges: dict[str, dict] = {}
        # Cross-request prefix cache (serving/kv_pool.PrefixIndex via the
        # paged runner): lookup outcomes, KV tokens served warm (the
        # prefill FLOPs NOT paid), index churn, and per-head gauges
        # (entries / retained pages / retained bytes). partial_hits are
        # near-misses — a shorter retained prefix matched, admitted COLD
        # (only full-history reuse is numerically exact for both head
        # families; docs/SERVING.md "Prefix cache").
        self.prefix_lookups: collections.Counter = collections.Counter()
        self.prefix_hits: collections.Counter = collections.Counter()
        self.prefix_partial_hits: collections.Counter = collections.Counter()
        self.prefix_misses: collections.Counter = collections.Counter()
        self.prefix_warm_tokens: collections.Counter = collections.Counter()
        self.prefix_insertions: collections.Counter = collections.Counter()
        self.prefix_evictions: collections.Counter = collections.Counter()
        self.prefix_invalidations: collections.Counter = collections.Counter()
        self.prefix_gauges: dict[str, dict] = {}
        # Speculative tree decode (docs/SERVING.md "Speculative
        # decoding"). Metrics honesty for multi-token steps:
        # ``decode_steps`` above KEEPS meaning target executable
        # invocations (a spec call is ONE invocation however many codes
        # it commits); these counters carry the multi-token story —
        # drafted speculated tokens, codes committed, slot-steps (one
        # per active slot per invocation; accepted/slot_steps is the
        # mean accept length, 1.0 == plain decode's rate), and the
        # per-step accept-length histogram.
        self.spec_steps: collections.Counter = collections.Counter()
        self.spec_drafted: collections.Counter = collections.Counter()
        self.spec_accepted: collections.Counter = collections.Counter()
        self.spec_slot_steps: collections.Counter = collections.Counter()
        self.spec_accept_hist: dict[str, collections.Counter] = {}
        # SLO load shedding (obs/slo.py via the engine): submissions
        # rejected with the typed OverloadError while a head sheds.
        # Separate from `rejected` — that one means draining (terminal);
        # overload is recoverable and per-head attributed.
        self.overload_rejected = 0
        self.overload_by_head: collections.Counter = collections.Counter()
        # PER-HEAD rings of (t, total_s) samples for SLIDING-WINDOW
        # percentiles — the SLO monitor evaluates p99 over its window,
        # not over the lifetime histogram (which can never recover from
        # an old bad minute). One bounded ring per head: a high-QPS
        # head can neither read as a breach on a healthy co-hosted head
        # nor evict a quiet head's samples out of evaluation.
        self._recent_window = recent_window
        self._recent_lat: dict = {}
        # PER-TENANT rings parallel to the per-head ones: the tenancy
        # front (genrec_tpu/tenancy) attributes each completed response
        # to the SUBMITTING tenant, so its SLO monitor evaluates tenant
        # p99 over tenant traffic only — a head shared by two tenants
        # (or renamed bindings) can never smear one tenant's tail onto
        # another's shed decision. Head rings stay untouched.
        self._recent_lat_tenant: dict = {}
        self._started = time.monotonic()
        self._warm = False

    def mark_warm(self) -> None:
        """Warmup done: compiles from here on count as recompilations."""
        with self._lock:
            self._warm = True
            self._started = time.monotonic()

    def record_compile(self, catalog: bool = False) -> None:
        with self._lock:
            if catalog:
                self.catalog_compiles += 1
            elif self._warm:
                self.recompilations += 1
            else:
                self.warmup_compiles += 1

    def record_submit(self, head: str | None = None) -> None:
        with self._lock:
            self.submitted += 1
            if head is not None:
                self.submitted_by_head[head] += 1

    def record_reject(self, head: str | None = None) -> None:
        """Draining rejection; per-head attribution feeds the drain report
        (rejections only ever happen while draining, so the per-head
        counter IS "rejected during drain" for each head)."""
        with self._lock:
            self.rejected += 1
            if head is not None:
                self.rejected_by_head[head] += 1

    def record_overload(self, head: str) -> None:
        """SLO load-shed rejection (typed OverloadError at submit)."""
        with self._lock:
            self.overload_rejected += 1
            self.overload_by_head[head] += 1

    def record_admit(self, n: int = 1) -> None:
        with self._lock:
            self.admits += n

    def record_evict(self, n: int = 1) -> None:
        with self._lock:
            self.evictions += n

    def record_oom_admit(self, n: int = 1, head: str | None = None) -> None:
        """Admissions DEFERRED because the KV pool had no pages/slots —
        the request stays queued and retries as evictions free pages, so
        a nonzero rate means the pool budget, not the arrival rate, is
        the bottleneck. Per-head attribution feeds the SLO monitor: one
        head's pool pressure must not shed a healthy co-hosted head."""
        with self._lock:
            self.oom_deferred_admits += n
            if head is not None:
                self.oom_deferred_by_head[head] += n

    def record_decode_step(self, slots: int = 0, live: int = 0,
                           kv_tokens: int = 0) -> None:
        """One decode executable invocation at slot rung ``slots`` with
        ``live`` of them resident, attending over ``kv_tokens`` KV tokens
        in all."""
        with self._lock:
            self.decode_steps += 1
            self.decode_slot_steps += slots
            self.decode_live_slot_steps += live
            self.decode_kv_tokens += kv_tokens
            if slots:
                self.decode_steps_by_slots[slots] += 1

    def record_spec(self, head: str, drafted: int, accept_lens) -> None:
        """One speculative tree-verify invocation: ``drafted`` speculated
        tokens proposed across the active slots, ``accept_lens`` the
        per-active-slot codes committed (>= 1 each: the root level is
        exact). The caller records the invocation itself through
        `record_decode_step` — decode_steps stays "target executable
        invocations" whether or not speculation is on."""
        lens = [int(x) for x in accept_lens]
        with self._lock:
            self.spec_steps[head] += 1
            self.spec_drafted[head] += int(drafted)
            self.spec_slot_steps[head] += len(lens)
            self.spec_accepted[head] += sum(lens)
            hist = self.spec_accept_hist.setdefault(head, collections.Counter())
            hist.update(lens)

    def record_prefix_lookup(self, head: str, outcome: str,
                             tokens: int = 0) -> None:
        """One prefix-cache lookup: outcome in {"hit", "partial", "miss"}.
        ``tokens`` is the KV tokens the matched run covers — for a hit,
        the prefill work NOT paid (warm tokens)."""
        with self._lock:
            self.prefix_lookups[head] += 1
            if outcome == "hit":
                self.prefix_hits[head] += 1
                self.prefix_warm_tokens[head] += int(tokens)
            elif outcome == "partial":
                self.prefix_partial_hits[head] += 1
            else:
                self.prefix_misses[head] += 1

    def record_prefix_insert(self, head: str, n: int = 1) -> None:
        with self._lock:
            self.prefix_insertions[head] += n

    def record_prefix_evict(self, head: str, n: int = 1,
                            invalidation: bool = False) -> None:
        """Entries dropped: LRU/pressure reclaims vs wholesale
        invalidations (params/catalog swap, drain) — separate counters,
        a swap storm must not read as memory pressure."""
        with self._lock:
            if invalidation:
                self.prefix_invalidations[head] += n
            else:
                self.prefix_evictions[head] += n

    def set_prefix_gauges(self, head: str, gauges: dict) -> None:
        with self._lock:
            self.prefix_gauges[head] = dict(gauges)

    def set_pool_gauges(self, head: str, gauges: dict) -> None:
        with self._lock:
            self.pool_gauges[head] = dict(gauges)

    def record_failure(self, n: int = 1) -> None:
        with self._lock:
            self.failed += n

    def record_swap(self) -> None:
        with self._lock:
            self.params_swaps += 1

    def record_watcher_error(self) -> None:
        with self._lock:
            self.watcher_errors += 1

    def record_catalog_swap(self) -> None:
        with self._lock:
            self.catalog_swaps += 1

    def record_batch(self, head: str, bucket: tuple[int, int],
                     rows: int | None = None, tokens: int = 0,
                     prompt_tokens: int = 0) -> None:
        """One bucketed executable call. A paged prefill also gives its
        real ``rows`` and ``tokens`` (history positions in the ladder's
        own unit, `head.natural_len`), counted against the bucket's
        B and B x L, and the ``prompt_tokens`` it wrote to pages."""
        with self._lock:
            self.batches += 1
            self.bucket_hits[(head, *bucket)] += 1
            if rows is not None:
                self.prefill_rows += rows
                self.prefill_row_slots += bucket[0]
                self.prefill_tokens += tokens
                self.prefill_token_slots += bucket[0] * bucket[1]
                self.prefill_prompt_tokens += prompt_tokens

    def record_response(self, queue_wait: float, compute: float, total: float,
                        head: str | None = None) -> None:
        now = time.monotonic()
        with self._lock:
            self.queue_wait.record(queue_wait)
            self.compute.record(compute)
            self.total.record(total)
            self.completed += 1
            ring = self._recent_lat.get(head)
            if ring is None:
                ring = self._recent_lat[head] = collections.deque(
                    maxlen=self._recent_window
                )
            ring.append((now, float(total)))

    def record_tenant_response(self, tenant: str, total: float) -> None:
        """Attribute one completed response's total latency to a TENANT
        ring (the tenancy front's done-callback; head-side recording
        already happened via record_response — tenant rings are a
        parallel index, not a second count)."""
        now = time.monotonic()
        with self._lock:
            ring = self._recent_lat_tenant.get(tenant)
            if ring is None:
                ring = self._recent_lat_tenant[tenant] = collections.deque(
                    maxlen=self._recent_window
                )
            ring.append((now, float(total)))

    def recent_p99_ms(self, window_s: float, head: str | None = None,
                      q: float = 0.99, min_count: int = 20,
                      tenant: str | None = None) -> float | None:
        """Total-latency quantile over responses completed within the
        last ``window_s`` seconds — one head's ring when given, one
        TENANT's ring when ``tenant=`` is given (fed by
        record_tenant_response), pooled over every head otherwise — or
        None below ``min_count`` samples (an empty window must not read
        as 'SLO met at 0ms' — the SLO monitor skips the latency
        dimension instead). Only the ring copy happens under the lock;
        filter + sort run outside it, off the response hot path."""
        cut = time.monotonic() - window_s
        with self._lock:
            if tenant is not None:
                ring = self._recent_lat_tenant.get(tenant)
                samples = list(ring) if ring else []
            elif head is None:
                samples = [s for ring in self._recent_lat.values()
                           for s in ring]
            else:
                ring = self._recent_lat.get(head)
                samples = list(ring) if ring else []
        vals = sorted(v for t, v in samples if t >= cut)
        if len(vals) < min_count:
            return None
        return vals[min(len(vals) - 1, int(q * len(vals)))] * 1e3

    def slow_threshold_s(self, q: float = 0.99, min_count: int = 64) -> float | None:
        """Latency above which a request counts as a slow outlier (the
        total-latency q-quantile), or None until ``min_count`` responses
        have been recorded — an empty histogram's p99 is 0, which would
        flag EVERY early request as an exemplar."""
        with self._lock:
            if self.total.count < min_count:
                return None
            return self.total.percentile(q)

    def qps(self) -> float:
        """Lifetime QPS since warmup finished."""
        with self._lock:
            dt = time.monotonic() - self._started
            return self.completed / dt if dt > 0 else 0.0

    def snapshot(self) -> dict:
        with self._lock:
            bucket_hits = {
                f"{h}/B{b}/L{l}": n for (h, b, l), n in sorted(self.bucket_hits.items())
            }
            counts = dict(
                submitted=self.submitted,
                completed=self.completed,
                rejected=self.rejected,
                failed=self.failed,
                batches=self.batches,
                warmup_compiles=self.warmup_compiles,
                recompilations=self.recompilations,
                params_swaps=self.params_swaps,
                watcher_errors=self.watcher_errors,
                catalog_swaps=self.catalog_swaps,
                catalog_compiles=self.catalog_compiles,
                admits=self.admits,
                evictions=self.evictions,
                oom_deferred_admits=self.oom_deferred_admits,
                decode_steps=self.decode_steps,
                decode_slot_steps=self.decode_slot_steps,
                decode_live_slot_steps=self.decode_live_slot_steps,
                decode_kv_tokens=self.decode_kv_tokens,
                prefill_rows=self.prefill_rows,
                prefill_row_slots=self.prefill_row_slots,
                prefill_tokens=self.prefill_tokens,
                prefill_token_slots=self.prefill_token_slots,
                prefill_prompt_tokens=self.prefill_prompt_tokens,
                overload_rejected=self.overload_rejected,
            )
            decode_steps_by_slots = {
                f"s{s}": n for s, n in sorted(self.decode_steps_by_slots.items())
            }
            rejected_by_head = dict(sorted(self.rejected_by_head.items()))
            submitted_by_head = dict(sorted(self.submitted_by_head.items()))
            overload_by_head = dict(sorted(self.overload_by_head.items()))
            oom_deferred_by_head = dict(sorted(self.oom_deferred_by_head.items()))
            kv_pool = {h: dict(g) for h, g in sorted(self.pool_gauges.items())}
            prefix_heads = sorted(
                set(self.prefix_lookups) | set(self.prefix_gauges)
            )
            prefix_cache = {
                h: {
                    "lookups": self.prefix_lookups[h],
                    "hits": self.prefix_hits[h],
                    "partial_hits": self.prefix_partial_hits[h],
                    "misses": self.prefix_misses[h],
                    "warm_tokens": self.prefix_warm_tokens[h],
                    "insertions": self.prefix_insertions[h],
                    "evictions": self.prefix_evictions[h],
                    "invalidations": self.prefix_invalidations[h],
                    **self.prefix_gauges.get(h, {}),
                }
                for h in prefix_heads
            }
            spec = {}
            for h in sorted(self.spec_steps):
                slot_steps = self.spec_slot_steps[h]
                spec[h] = {
                    "spec_steps": self.spec_steps[h],
                    "drafted": self.spec_drafted[h],
                    "accepted": self.spec_accepted[h],
                    "slot_steps": slot_steps,
                    # Mean accept length == accepted codes per target
                    # invocation per stream (plain decode == 1.0) — the
                    # bench-gated headline of speculative decode.
                    "codes_per_invocation": round(
                        self.spec_accepted[h] / slot_steps, 4
                    ) if slot_steps else 0.0,
                    "accept_len_hist": {
                        f"accept_len_{l}": n
                        for l, n in sorted(self.spec_accept_hist[h].items())
                    },
                }
        return {
            **counts,
            "qps": round(self.qps(), 3),
            "queue_wait_ms": self.queue_wait.summary(),
            "compute_ms": self.compute.summary(),
            "total_ms": self.total.summary(),
            "bucket_hits": bucket_hits,
            "decode_steps_by_slots": decode_steps_by_slots,
            "rejected_by_head": rejected_by_head,
            "submitted_by_head": submitted_by_head,
            "overload_by_head": overload_by_head,
            "oom_deferred_by_head": oom_deferred_by_head,
            "kv_pool": kv_pool,
            "prefix_cache": prefix_cache,
            "spec": spec,
        }
