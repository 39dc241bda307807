#!/usr/bin/env python
"""Perf regression gate: compare a bench run against a committed baseline.

The perf trajectory (BENCH_r*.json) had no committed gate: a PR could
silently lose the 4.6x decode or 1.93x packing wins and CI stayed green.
This script — in the graftlint mold: one JSON verdict line on stdout,
rc 0/1 (2 = ran fine but inconclusive), human detail on stderr — makes
every future perf claim measured instead of asserted:

- The committed baseline (results/bench_baseline.json) pins a VALUE, a
  DIRECTION (higher/lower is better) and a per-metric NOISE TOLERANCE
  (pct) for each gated metric.
- A run is a bench.py output line (or a BENCH_r*.json driver file whose
  "parsed" field holds one). Runs carry the stable "meta" section
  bench.py stamps (git sha, backend, jax version, shape config);
  backend-mismatched comparisons are SKIPPED (rc 2), never flagged —
  a CPU fallback line must not read as a TPU regression.
- Direction-aware, noise-band tolerant: a higher-is-better metric fails
  only when it drops more than its tolerance below baseline; moves
  inside the band are noise; moves past it the GOOD way are reported as
  improvements (candidates for --update-baseline).
- ``--update-baseline`` rewrites the baseline from the run — and REFUSES
  a partial run (any metric the existing baseline gates that the run
  does not carry), so a truncated bench can never silently shrink the
  gate.
- A built-in self-test (fixture baseline + identical / regressed /
  improved runs) runs before every comparison — the regex_bites
  discipline: the gate proves it still bites before it certifies
  anything. ``--self-test`` runs only that (CI smoke mode).

Usage:
    python scripts/bench_gate.py                      # self-test + newest BENCH_r*.json
    python scripts/bench_gate.py RUN.json             # self-test + gate RUN.json
    python scripts/bench_gate.py --self-test          # fixtures only
    python scripts/bench_gate.py RUN.json --update-baseline
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import time
from typing import Any, Mapping, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE = os.path.join(REPO, "results", "bench_baseline.json")
BASELINE_SCHEMA = 1

#: The gate's metric catalog: path -> (direction, default tolerance %).
#: Direction says which way is GOOD; tolerance is the noise band a
#: same-config rerun may wander within. Ratios (same-backend by
#: construction) get tight bands; absolute qps/latency numbers get wide
#: ones (shared-host CPU measurements breathe).
GATED_METRICS: dict[str, tuple[str, float]] = {
    "value": ("higher", 10.0),                       # headline seq/s/chip
    "step_ms": ("lower", 10.0),
    "mfu": ("higher", 10.0),
    "tiger_train_tokens_per_sec_per_chip": ("higher", 15.0),
    "packed_vs_padded": ("higher", 10.0),
    "pack_occupancy": ("higher", 5.0),
    "tiger_decode_seq_per_sec_per_chip": ("higher", 15.0),
    "decode_vs_uncached": ("higher", 10.0),
    "serve/batched_vs_sequential": ("higher", 20.0),
    "serve/closed_loop_qps_per_chip": ("higher", 25.0),
    "serve/p99_ms": ("lower", 30.0),
    "serve/paged_vs_dense": ("higher", 20.0),
    "serve/max_concurrent_decode_streams_per_chip": ("higher", 10.0),
    "serve/catalog_swap/swap_to_visible_ms_p50": ("lower", 30.0),
    "serve/obs/tracing_on_overhead_pct": ("lower", 50.0),
    # Fleet-path lineage overhead (request lineage PR): closed-loop qps
    # through a 2-replica router, tracing-off vs tracing-on (router
    # route/reroute spans + full per-replica request trees). Same
    # budget intent as the engine-level line above — lineage must not
    # silently tax the hot path; the tracing-OFF fast path keeps its
    # deterministic <2% pin in scripts/check_obs.py.
    "serve/obs/fleet_tracing_on_overhead_pct": ("lower", 50.0),
    # Cross-request prefix cache (PR 11): hit rate and the warm-vs-cold
    # prefill ratio are same-backend and tight-ish; absolute latency and
    # the fixed-HBM stream ratio breathe more on shared CPU hosts.
    "serve/prefix_cache/warm_hit_rate": ("higher", 15.0),
    "serve/prefix_cache/warm_prefill_p50_ms": ("lower", 50.0),
    "serve/prefix_cache/warm_vs_cold_prefill_p50": ("higher", 40.0),
    "serve/prefix_cache/streams_at_fixed_hbm_warm_vs_cold": ("higher", 30.0),
    # Fleet front (PR 12): p99 of burst-window arrivals through the
    # 2-replica router on the deterministic trace, and the fleet-level
    # shed rate over the whole trace. The SCHEDULE is bit-identical
    # across runs (seeded trace), but both metrics measure a saturated
    # serving stack on a shared CPU host, so the bands are wide; a
    # zero-measured shed_rate baseline would gate in absolute units
    # (the zero-baseline rule above).
    "serve/fleet/p99_under_burst_ms": ("lower", 50.0),
    "serve/fleet/shed_rate": ("lower", 100.0),
    # Multi-tenant serving plane (PR 20): the victim tenant's p99 with
    # an admission-capped aggressor surging vs serving its share alone
    # (both sides saturated-CPU walls: wide band), the A/B arm split's
    # absolute error vs the pure bucket_arm hash (deterministic routing
    # -> 0.0 baseline, banded in ABSOLUTE units by the zero-baseline
    # rule — any drift means the router stopped honoring the hash), and
    # the shadow mirror's closed-loop qps tax at identical arms (the
    # mirror machinery alone; shadow compute runs on its own engine).
    "serve/tenancy/victim_p99_with_aggressor_vs_alone": ("lower", 80.0),
    "serve/tenancy/ab_split_abs_err": ("lower", 0.02),
    "serve/tenancy/shadow_overhead_pct": ("lower", 100.0),
    # Disaggregated serving (PR 13): the serializing handoff's
    # send->admit p50 (latency on a shared CPU host: wide band), the
    # mean wire bytes per handoff (measured packed payloads on the
    # seeded trace: tight band — catches wire-format growth), and the
    # in-process front's qps against the co-located engine at parity
    # traffic (same-backend ratio; the split's control-plane overhead).
    "serve/disagg/handoff_p50_ms": ("lower", 60.0),
    "serve/disagg/wire_bytes_per_handoff": ("lower", 15.0),
    "serve/disagg/qps_vs_colocated": ("higher", 40.0),
    # Cross-host serving (PR 17): the socket tier's send->admit p50 with
    # the decode pool in another OS process (loopback kernel socket + a
    # second Python runtime on a shared CPU host: wide band), and the
    # socket front's qps against the co-located engine at parity traffic
    # (same-backend ratio — what the process/socket hop costs on one
    # machine, the number that must hold when the peer is a real host).
    "serve/crosshost/handoff_p50_ms": ("lower", 60.0),
    "serve/crosshost/qps_vs_colocated": ("higher", 40.0),
    # Chaos-hardened cross-host serving (PR 18): the same socket tier
    # through a seeded network-fault schedule. qps_under_faults_vs_clean
    # is a same-run same-backend ratio (the throughput tax of the
    # self-healing machinery actually firing: CRC trip -> reconnect ->
    # re-submit mid-trace, plus latency jitter) — but both numerator and
    # denominator are saturated-CPU walls, so the band is wide.
    # recovery_time_ms is submit-to-answer across a yanked decode
    # connection (detection + backoff + handshake + re-admit + decode);
    # scheduler noise on a shared host dominates the backoff constants,
    # so the band is the widest in the serve section.
    "serve/chaos/qps_under_faults_vs_clean": ("higher", 40.0),
    "serve/chaos/recovery_time_ms": ("lower", 100.0),
    # Speculative tree decode (PR 14): codes committed per target-model
    # invocation is structural (drafter acceptance on the seeded trace —
    # tight band; the >2x acceptance bar lives in the committed
    # baseline value), while the spec-vs-plain closed-loop qps ratios
    # are saturated-CPU measurements (wide bands; on CPU the tree's
    # redundant FLOPs make the ratio < 1 — the gate defends it against
    # further regression, it is not a speedup claim).
    "serve/spec/codes_per_target_invocation": ("higher", 15.0),
    "serve/spec/qps_vs_plain_at_16": ("higher", 60.0),
    "serve/spec/qps_vs_plain_at_32": ("higher", 60.0),
    # Quantized serving (PR 16): resident decode streams at the fixed
    # fp32-provisioning HBM budget, int8 vs fp32 — ledger byte math on
    # fixed engine geometry, so the band is tight and the >=2x bar
    # lives in the committed baseline value. The int8-vs-fp32 qps ratio
    # is a saturated-CPU measurement (wide band): it defends the
    # dequant-at-read decode path against regression, not a speedup
    # claim on a compute-bound host.
    "serve/quant/streams_improvement": ("higher", 10.0),
    "serve/quant/int8_vs_fp32_qps": ("higher", 40.0),
    # Guarded continuous rollout (PR 19): checkpoint-commit -> first
    # response served by the promoted step on a non-canary replica,
    # through the FULL guard (vet on the pinned batch, canary window,
    # fleet promote). The floor is the configured poll/canary windows;
    # the rest is scheduler noise on a shared CPU host, so the bands
    # are wide. qps_with_rollouts_vs_none is a same-run same-backend
    # ratio (closed-loop qps with a 1s publish cadence live vs none) —
    # it defends the hot path against the guard machinery growing a
    # throughput tax, with both sides saturated-CPU walls (wide band).
    "serve/pipeline/freshness_p50_ms": ("lower", 100.0),
    "serve/pipeline/freshness_p99_ms": ("lower", 100.0),
    "serve/pipeline/qps_with_rollouts_vs_none": ("higher", 40.0),
}


def log(msg: str) -> None:
    print(f"bench_gate: {msg}", file=sys.stderr)


def flatten(tree: Mapping, prefix: str = "") -> dict[str, float]:
    """Numeric leaves of a nested dict as {"a/b/c": value} (the same
    path convention core.logging/obs.export use)."""
    out: dict[str, float] = {}
    for k, v in tree.items():
        key = f"{prefix}/{k}" if prefix else str(k)
        if isinstance(v, Mapping):
            out.update(flatten(v, key))
        elif isinstance(v, bool):
            continue
        elif isinstance(v, (int, float)):
            out[key] = float(v)
    return out


def load_run(path: str) -> dict:
    """A bench.py output line, or a BENCH_r*.json driver file whose
    "parsed" field holds one."""
    with open(path) as fh:
        data = json.load(fh)
    if not isinstance(data, dict):
        raise ValueError(f"{path}: not a JSON object")
    if "parsed" in data and isinstance(data["parsed"], dict):
        data = data["parsed"]
    if "metric" not in data and "value" not in data:
        raise ValueError(f"{path}: not a bench output line (no metric/value)")
    return data


def newest_committed_run() -> Optional[str]:
    def round_no(path: str) -> int:
        # Numeric, not lexicographic: "r100" must sort after "r99".
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        return int(m.group(1)) if m else -1

    runs = sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json")), key=round_no)
    for path in reversed(runs):
        try:
            load_run(path)
            return path
        except (OSError, ValueError):
            continue
    return None


def metric_backend(run: Mapping, name: str) -> Optional[str]:
    """The backend a specific metric was MEASURED on. Run files from
    before PR 21 can carry CPU supplements grafted onto TPU-evidence
    lines (serve.source / packed_source stamp the provenance); the gate
    must compare each such metric against its own backend, not the
    line's headline one. bench.py no longer writes either label: a line
    it prints was measured whole on the chip."""
    backend = run.get("backend") or (run.get("meta") or {}).get("backend")
    if name.startswith("serve/"):
        src = (run.get("serve") or {}).get("source")
        if src:
            backend = src
    if name in ("packed_vs_padded", "pack_occupancy",
                "tiger_train_tokens_per_sec_per_chip"):
        src = run.get("packed_source")
        if src:
            backend = src
    return backend


def compare(baseline: Mapping, run: Mapping,
            ignore_backend: bool = False) -> dict:
    """Direction-aware, tolerance-banded comparison. Returns the verdict
    fields (regressions / improvements / within-band / missing /
    backend-skipped). A zero baseline value makes a relative band
    meaningless, so ``tolerance_pct`` is applied in ABSOLUTE units there
    (a lower-is-better metric at baseline 0 still gates)."""
    flat = flatten(run)
    base_backend = (baseline.get("meta") or {}).get("backend")
    regressions, improvements, within, missing, backend_skipped = \
        [], [], [], [], []
    for name, spec in sorted(baseline.get("metrics", {}).items()):
        base = float(spec["value"])
        direction = spec.get("direction", "higher")
        tol = float(spec.get("tolerance_pct", 10.0))
        got = flat.get(name)
        if got is None:
            missing.append(name)
            continue
        mb = metric_backend(run, name)
        if not ignore_backend and base_backend and mb and mb != base_backend:
            # e.g. a CPU serve supplement riding a TPU-evidence line:
            # never compared against TPU baselines, never seeds them.
            backend_skipped.append(name)
            continue
        entry = {
            "metric": name, "baseline": base, "run": got,
            "direction": direction, "tolerance_pct": tol,
        }
        if base:
            delta_pct = 100.0 * (got - base) / abs(base)
            good = delta_pct if direction == "higher" else -delta_pct
            entry["delta_pct"] = round(delta_pct, 2)
        else:
            # Zero baseline: band in absolute units, pct undefined.
            delta = got - base
            good = delta if direction == "higher" else -delta
            entry["delta_pct"] = None
            entry["delta_abs"] = round(delta, 4)
        if good < -tol:
            regressions.append(entry)
        elif good > tol:
            improvements.append(entry)
        else:
            within.append(name)
    return {
        "compared": (len(baseline.get("metrics", {})) - len(missing)
                     - len(backend_skipped)),
        "regressions": regressions,
        "improvements": improvements,
        "within_band": within,
        "missing": missing,
        "backend_skipped": backend_skipped,
    }


def build_baseline(run: Mapping, existing: Optional[Mapping]) -> dict:
    """A fresh baseline from ``run``: existing gated metrics keep their
    direction/tolerance config; new GATED_METRICS present in the run are
    added with catalog defaults. REFUSES a partial run (ValueError) —
    a metric the existing baseline gates must be present."""
    flat = flatten(run)
    run_backend = run.get("backend") or (run.get("meta") or {}).get("backend")
    old_metrics = dict((existing or {}).get("metrics", {}))
    absent = [n for n in old_metrics if n not in flat]
    if absent:
        raise ValueError(
            f"refusing --update-baseline from a partial run: the current "
            f"baseline gates {sorted(absent)} but the run does not carry "
            "them (a truncated bench must not shrink the gate)"
        )

    def foreign(name: str) -> bool:
        # A grafted supplement (cpu serve section on a tpu line) must
        # not seed values into this line's-backend baseline.
        mb = metric_backend(run, name)
        return bool(run_backend and mb and mb != run_backend)

    metrics: dict[str, dict] = {}
    for name, spec in old_metrics.items():
        if foreign(name):
            log(f"update: keeping prior {name} (run value is "
                f"{metric_backend(run, name)}-measured, baseline is "
                f"{run_backend})")
            metrics[name] = dict(spec)
            continue
        metrics[name] = {**spec, "value": flat[name]}
    for name, (direction, tol) in GATED_METRICS.items():
        if name in metrics or name not in flat or foreign(name):
            continue
        metrics[name] = {
            "value": flat[name], "direction": direction, "tolerance_pct": tol,
        }
    if not metrics:
        raise ValueError("run carries no gateable metrics")
    meta = dict(run.get("meta") or {})
    return {
        "schema": BASELINE_SCHEMA,
        "meta": {
            "backend": run.get("backend") or meta.get("backend"),
            "source": run.get("source"),
            "git_sha": meta.get("git_sha"),
            "updated_t": round(time.time(), 1),
        },
        "metrics": metrics,
    }


# ---------------------------------------------------------------------------
# self-test fixtures (the gate proves it bites before certifying anything)
# ---------------------------------------------------------------------------

def self_test() -> dict:
    base_run = {
        "metric": "tiger_train_seq_per_sec_per_chip", "value": 1000.0,
        "step_ms": 10.0, "backend": "tpu", "packed_vs_padded": 1.9,
        "serve": {"p99_ms": 20.0},
        "meta": {"schema": 1, "backend": "tpu"},
    }
    baseline = build_baseline(base_run, None)
    checks: dict[str, bool] = {}

    identical = compare(baseline, base_run)
    checks["identical_run_passes"] = not identical["regressions"] and \
        identical["compared"] == len(baseline["metrics"])

    # ~11-12% worse: past the 10% band (a boundary-exact -10% is noise).
    regressed = dict(base_run, value=885.0, step_ms=11.2)
    res = compare(baseline, regressed)
    flagged = {e["metric"] for e in res["regressions"]}
    checks["ten_pct_regression_flagged"] = flagged == {"step_ms", "value"}

    noisy = dict(base_run, value=1000.0 * 0.95)  # inside the 10% band
    checks["noise_band_tolerated"] = not compare(baseline, noisy)["regressions"]

    improved = dict(base_run, value=1200.0, serve={"p99_ms": 12.0})
    res = compare(baseline, improved)
    better = {e["metric"] for e in res["improvements"]}
    checks["improvement_reported_not_flagged"] = (
        not res["regressions"] and better == {"serve/p99_ms", "value"}
    )

    partial = {k: v for k, v in base_run.items() if k != "step_ms"}
    try:
        build_baseline(partial, baseline)
        checks["partial_update_refused"] = False
    except ValueError:
        checks["partial_update_refused"] = True

    missing_run = {k: v for k, v in base_run.items() if k != "serve"}
    checks["missing_metric_reported"] = (
        compare(baseline, missing_run)["missing"] == ["serve/p99_ms"]
    )

    # Zero baseline: the band applies in ABSOLUTE units (a relative pct
    # of 0 would make the metric permanently ungateable).
    zero_base = {
        "schema": BASELINE_SCHEMA, "meta": {"backend": "tpu"},
        "metrics": {"serve/obs/tracing_on_overhead_pct": {
            "value": 0.0, "direction": "lower", "tolerance_pct": 5.0}},
    }
    blown = dict(base_run, serve={"obs": {"tracing_on_overhead_pct": 45.0}})
    res = compare(zero_base, blown)
    fine = dict(base_run, serve={"obs": {"tracing_on_overhead_pct": 2.0}})
    checks["zero_baseline_still_gates"] = (
        len(res["regressions"]) == 1
        and not compare(zero_base, fine)["regressions"]
    )

    # A CPU supplement grafted onto a TPU-evidence line is skipped, not
    # compared against TPU baselines (and never seeds them on update).
    grafted = dict(base_run, serve={"p99_ms": 500.0, "source": "cpu"})
    res = compare(baseline, grafted)
    seeded = build_baseline(dict(grafted, step_ms=base_run["step_ms"]),
                            baseline)
    checks["cpu_supplement_skipped_not_flagged"] = (
        res["backend_skipped"] == ["serve/p99_ms"]
        and not any(e["metric"] == "serve/p99_ms" for e in res["regressions"])
        and seeded["metrics"]["serve/p99_ms"]["value"] == 20.0  # prior kept
    )

    ok = all(checks.values())
    for name, passed in checks.items():
        log(f"self-test {name}: {'ok' if passed else 'FAILED'}")
    return {"ok": ok, **checks}


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("run", nargs="?", default=None,
                    help="bench output line or BENCH_r*.json (default: "
                         "newest committed BENCH_r*.json)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="committed per-metric baseline JSON")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline from the run (refuses "
                         "partial runs)")
    ap.add_argument("--self-test", action="store_true",
                    help="run only the fixture self-test (CI smoke)")
    ap.add_argument("--ignore-backend", action="store_true",
                    help="compare even when run/baseline backends differ")
    args = ap.parse_args(argv)

    verdict: dict[str, Any] = {
        "check": "bench_gate", "ok": False, "self_test": None,
        "compared": 0, "regressions": [], "improvements": [],
        "within_band": [], "missing": [], "backend_skipped": [],
        "skipped": None,
        "baseline": args.baseline, "run": args.run, "updated": False,
    }

    st = self_test()
    verdict["self_test"] = st
    if not st["ok"]:
        print(json.dumps(verdict))
        log("FAILED: the gate's own fixtures no longer bite")
        return 1
    if args.self_test:
        verdict["ok"] = True
        verdict["skipped"] = "self-test only"
        print(json.dumps(verdict))
        return 0

    run_path = args.run or newest_committed_run()
    if run_path is None:
        verdict["ok"] = True
        verdict["skipped"] = "no run file found (no BENCH_r*.json yet)"
        print(json.dumps(verdict))
        log(verdict["skipped"])
        return 2
    verdict["run"] = run_path
    try:
        run = load_run(run_path)
    except (OSError, ValueError) as e:
        verdict["skipped"] = f"unreadable run: {e}"
        print(json.dumps(verdict))
        log(verdict["skipped"])
        return 1

    baseline = None
    if os.path.exists(args.baseline):
        with open(args.baseline) as fh:
            baseline = json.load(fh)

    run_backend = run.get("backend") or (run.get("meta") or {}).get("backend")
    base_backend = ((baseline or {}).get("meta") or {}).get("backend")
    backend_mismatch = (
        not args.ignore_backend and run_backend and base_backend
        and run_backend != base_backend
    )

    if args.update_baseline:
        if backend_mismatch:
            # A CPU-fallback line silently rewriting the committed TPU
            # baseline would rc-2-skip every later hardware comparison —
            # the gate would permanently stop gating.
            verdict["skipped"] = (
                f"refusing --update-baseline across backends: run="
                f"{run_backend} baseline={base_backend} "
                "(--ignore-backend overrides)"
            )
            print(json.dumps(verdict))
            log(f"FAILED: {verdict['skipped']}")
            return 1
        try:
            fresh = build_baseline(run, baseline)
        except ValueError as e:
            verdict["skipped"] = str(e)
            print(json.dumps(verdict))
            log(f"FAILED: {e}")
            return 1
        os.makedirs(os.path.dirname(os.path.abspath(args.baseline)),
                    exist_ok=True)
        tmp = f"{args.baseline}.tmp.{os.getpid()}"
        with open(tmp, "w") as fh:
            json.dump(fresh, fh, indent=1, sort_keys=True)
            fh.write("\n")
        os.replace(tmp, args.baseline)
        verdict.update(ok=True, updated=True,
                       compared=len(fresh["metrics"]))
        print(json.dumps(verdict))
        log(f"baseline updated from {run_path}: "
            f"{len(fresh['metrics'])} gated metrics")
        return 0

    if baseline is None:
        verdict["ok"] = True
        verdict["skipped"] = (
            f"no baseline at {args.baseline} (seed one with "
            "--update-baseline)"
        )
        print(json.dumps(verdict))
        log(verdict["skipped"])
        return 2

    if backend_mismatch:
        verdict["ok"] = True
        verdict["skipped"] = (
            f"backend mismatch: run={run_backend} baseline={base_backend} "
            "(a fallback line must not read as a hardware regression; "
            "--ignore-backend overrides)"
        )
        print(json.dumps(verdict))
        log(verdict["skipped"])
        return 2

    res = compare(baseline, run, ignore_backend=args.ignore_backend)
    verdict.update(res)
    verdict["ok"] = not res["regressions"]
    print(json.dumps(verdict))
    def delta_str(e: dict) -> str:
        # Zero-baseline entries carry delta_abs (absolute band), not pct.
        if e.get("delta_pct") is not None:
            return f"{e['delta_pct']:+.1f}%"
        return f"{e.get('delta_abs', 0.0):+g} abs"

    for e in res["regressions"]:
        log(f"REGRESSION {e['metric']}: {e['run']} vs baseline "
            f"{e['baseline']} ({delta_str(e)}, tolerance "
            f"{e['tolerance_pct']}, {e['direction']} is better)")
    for e in res["improvements"]:
        log(f"improvement {e['metric']}: {e['run']} vs {e['baseline']} "
            f"({delta_str(e)}) — consider --update-baseline")
    if res["missing"]:
        log(f"missing from run (reported, not failed): {res['missing']}")
    log(f"{'PASS' if verdict['ok'] else 'FAIL'}: {res['compared']} compared, "
        f"{len(res['regressions'])} regressions, "
        f"{len(res['improvements'])} improvements")
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
