#!/usr/bin/env python
"""Summarize a Chrome-trace JSON file written by the obs span tracer.

Usage:
    python scripts/trace_report.py out/serve/trace.json
    python scripts/trace_report.py trace.json --json      # machine-readable
    python scripts/trace_report.py trace.json --phase decode_step
    python scripts/trace_report.py trace.json --critical-path
    python scripts/trace_report.py trace.json --critical-path --tenant acme
    python scripts/trace_report.py --compare A.json B.json
    python scripts/trace_report.py --compare A.json B.json --critical-path

Per-phase (span-name) latency summary — count, total, p50/p95/p99/max —
plus the number of distinct traces (requests / epochs), the slow-request
exemplars the tracer persisted, and, when the file's ``otherData``
carries a goodput section (scripts/check_obs.py and the packed loop's
dumps embed one), the goodput breakdown. The same file opens in Perfetto
(https://ui.perfetto.dev) or chrome://tracing for the visual view; this
CLI is the grep-speed alternative.

``--critical-path`` walks each request's span TREE (the lineage traces
of docs/OBSERVABILITY.md "Request lineage": one rooted tree per routed/
disaggregated request) and decomposes the root span's duration into
EXCLUSIVE-time segments: every instant of the request's life is
attributed to exactly one span — the deepest one covering it — so the
segments (queue_wait / route / prefill / handoff_wire /
decode_slot_wait / decode / tree_verify / finalize / ... plus
``untraced`` for uninstrumented root time) sum to the root duration by
construction. Per-segment p50/p95/p99 across requests rank where the
time goes, and the tail table re-ranks the same segments over the
slowest requests only — "which segment ate the p99" is one command.

``--compare A.json B.json`` diffs two trace files per phase — p50/p95/
p99 deltas (ms and %) from A to B — so "what did this change do to
serving latency" is one command against two span dumps instead of
eyeballing two Perfetto tabs. With ``--critical-path`` the diff is
segment-by-segment instead: a bench regression names its phase.

Flight events embedded in ``otherData.flight_events`` (check scripts
dump them beside the spans) are rendered as a per-component table —
every event carries component/replica_id/worker_id stamps since the
lineage PR, so a multi-replica ring reads attributably.

Lanes (`batcher/<head>`, `train-e<n>`, `profile-<n>`, `compile`: flat
phase spans of one thread's loop or of the process's compiles,
docs/OBSERVABILITY.md "The batcher lane", "The compile lane") show in the phase table like any span, but are not requests: they
are counted apart from the traces and never enter the critical path, so
they cannot read as unrooted.

Exit codes: 0 ok, 1 unreadable/invalid trace file.
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict


#: Trace-id prefixes of lanes (genrec_tpu/obs/spans.LANE_PREFIXES; kept
#: here too so this CLI needs nothing but the trace file).
LANE_PREFIXES = ("batcher/", "train-e", "profile-", "compile")


def is_lane(trace_id) -> bool:
    return isinstance(trace_id, str) and trace_id.startswith(LANE_PREFIXES)


def load_trace(path: str) -> dict:
    with open(path) as fh:
        data = json.load(fh)
    if not isinstance(data, dict) or "traceEvents" not in data:
        raise ValueError(f"{path}: not a Chrome-trace JSON object "
                         "(missing 'traceEvents')")
    return data


def percentile(sorted_vals: list[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(int(q * len(sorted_vals)), len(sorted_vals) - 1)
    return sorted_vals[idx]


def summarize(data: dict, phase: str | None = None) -> dict:
    by_name: dict[str, list[float]] = defaultdict(list)
    traces, lanes = set(), set()
    accept_lens: list[int] = []
    for ev in data["traceEvents"]:
        if ev.get("ph") != "X":
            continue
        name = ev.get("name", "?")
        if phase is not None and name != phase:
            continue
        by_name[name].append(float(ev.get("dur", 0.0)) / 1e3)  # us -> ms
        args = ev.get("args") or {}
        tid = args.get("trace_id")
        if tid is not None:
            (lanes if is_lane(tid) else traces).add(tid)
        # Speculative decode: `accept` spans carry the per-slot accept
        # length (codes committed by that tree-verify invocation), so
        # the report shows the multi-token story beside the phase p99s.
        if name == "accept" and args.get("accept_len") is not None:
            accept_lens.append(int(args["accept_len"]))
    phases = {}
    for name, durs in sorted(by_name.items()):
        durs.sort()
        phases[name] = {
            "count": len(durs),
            "total_ms": round(sum(durs), 3),
            "p50_ms": round(percentile(durs, 0.50), 3),
            "p95_ms": round(percentile(durs, 0.95), 3),
            "p99_ms": round(percentile(durs, 0.99), 3),
            "max_ms": round(durs[-1], 3),
        }
    other = data.get("otherData") or {}
    accept = None
    if accept_lens:
        hist: dict[str, int] = defaultdict(int)
        for l in accept_lens:
            hist[str(l)] += 1
        accept = {
            "count": len(accept_lens),
            "mean": round(sum(accept_lens) / len(accept_lens), 3),
            "max": max(accept_lens),
            "hist": dict(sorted(hist.items())),
        }
    return {
        "n_traces": len(traces),
        "n_lanes": len(lanes),
        "phases": phases,
        "exemplars": other.get("exemplars") or {},
        "goodput": other.get("goodput"),
        "accept_len": accept,
    }


def print_report(report: dict) -> None:
    print(f"traces: {report['n_traces']}"
          + (f" (+ {report['n_lanes']} lanes)" if report.get("n_lanes") else ""))
    if report["phases"]:
        w = max(len(n) for n in report["phases"])
        print(f"{'phase':<{w}}  {'count':>7} {'total':>10} {'p50':>8} "
              f"{'p95':>8} {'p99':>8} {'max':>8}  (ms)")
        for name, s in report["phases"].items():
            print(f"{name:<{w}}  {s['count']:>7} {s['total_ms']:>10.1f} "
                  f"{s['p50_ms']:>8.2f} {s['p95_ms']:>8.2f} "
                  f"{s['p99_ms']:>8.2f} {s['max_ms']:>8.2f}")
    else:
        print("no complete ('X') events found")
    acc = report.get("accept_len")
    if acc:
        hist = ", ".join(f"{k}:{v}" for k, v in acc["hist"].items())
        print(f"speculative accept length: mean {acc['mean']} over "
              f"{acc['count']} slot-steps (max {acc['max']}; hist {hist})")
    if report["exemplars"]:
        print("slow-request exemplars:")
        for tid, reason in report["exemplars"].items():
            print(f"  {tid}: {reason}")
    g = report.get("goodput")
    if g:
        wall = max(float(g.get("wall_s", 0.0)), 1e-9)
        print(f"goodput: {g.get('goodput_pct', 0.0):.1f}% of {wall:.1f}s wall")
        for k, v in (g.get("buckets") or {}).items():
            if v > 0:
                print(f"  {k:<18} {v:>9.3f}s  {100 * v / wall:>5.1f}%")


# -- critical path ------------------------------------------------------------

#: span name -> attributed segment. Spans not named here attribute to
#: their own name; the two CONTAINER spans get dedicated buckets for
#: their exclusive (not-covered-by-children) time.
SEGMENT_OF = {
    "reroute": "route",
    "prefix_lookup": "admission",
    "warm_admit": "prefill",
    "decode_step": "decode",
    "handoff_network": "network",  # socket-tier send, peer-attributed
    "request": "untraced",       # root/container exclusive time
    "slot_residency": "slot_gap",  # resident but not stepping (scheduler)
}


def _trace_forest(data: dict) -> dict:
    """traceEvents -> {trace_id: [span dicts]} with t0/t1 in ms."""
    by_trace: dict[str, list[dict]] = defaultdict(list)
    for ev in data["traceEvents"]:
        if ev.get("ph") != "X":
            continue
        args = ev.get("args") or {}
        tid = args.get("trace_id")
        if tid is None or args.get("span_id") is None or is_lane(tid):
            continue
        t0 = float(ev["ts"]) / 1e3
        by_trace[tid].append({
            "id": args["span_id"],
            "parent": args.get("parent_id"),
            "name": ev.get("name", "?"),
            "component": args.get("component", ""),
            # Tenant attribution (tenancy/front.py stamps the root
            # request span): lets --tenant slice the critical path.
            "tenant": args.get("tenant"),
            "t0": t0,
            "t1": t0 + float(ev.get("dur", 0.0)) / 1e3,
        })
    return by_trace


def _request_segments(spans: list[dict]) -> "tuple[dict, dict] | None":
    """Decompose ONE request's root span into exclusive-time segments.

    Every instant in [root.t0, root.t1] is attributed to exactly ONE
    span — the deepest span covering it (ties to the latest-starting) —
    so the returned segment times sum to the root duration by
    construction. Returns (segments_ms, meta) or None when the trace
    has no single root request span."""
    ids = {s["id"] for s in spans}
    roots = [s for s in spans
             if s["name"] == "request"
             and (s["parent"] is None or s["parent"] not in ids)]
    if len(roots) != 1:
        return None
    root = roots[0]
    by_id = {s["id"]: s for s in spans}
    depth_memo: dict = {root["id"]: 0}

    def depth(s) -> int:
        d = depth_memo.get(s["id"])
        if d is not None:
            return d
        parent = by_id.get(s["parent"]) if s["parent"] is not None else None
        # Orphans (parent outside the ring) hang off the root.
        d = 1 if parent is None else depth(parent) + 1
        depth_memo[s["id"]] = d
        return d

    clipped = []
    for s in spans:
        t0 = max(s["t0"], root["t0"])
        t1 = min(s["t1"], root["t1"])
        if t1 > t0 or s is root:
            clipped.append((t0, t1, depth(s), s))
    bounds = sorted({t for t0, t1, _d, _s in clipped for t in (t0, t1)})
    segments: dict[str, float] = defaultdict(float)
    components: dict[str, set] = defaultdict(set)
    for a, b in zip(bounds, bounds[1:]):
        if b <= a:
            continue
        cover = [(d, t0, s) for t0, t1, d, s in clipped
                 if t0 <= a and t1 >= b]
        d, _t0, s = max(cover, key=lambda c: (c[0], c[1]))
        seg = SEGMENT_OF.get(s["name"], s["name"])
        segments[seg] += b - a
        if s["component"]:
            components[seg].add(s["component"])
    meta = {
        "root_ms": root["t1"] - root["t0"],
        "tenant": root.get("tenant"),
        "components": {seg: sorted(c) for seg, c in components.items()},
        "span_components": sorted({s["component"] for s in spans
                                   if s["component"]}),
    }
    return dict(segments), meta


def critical_path_report(data: dict, tail_q: float = 0.95,
                         tenant: "str | None" = None) -> dict:
    """Per-segment latency attribution across every rooted request
    trace in the file: p50/p95/p99/total of each segment's exclusive
    time, each request's segments summing to its root span, and the
    same segments re-ranked over the TAIL (requests whose root duration
    sits at/above the ``tail_q`` quantile) — the p99's blame list.
    ``tenant`` keeps only requests whose root span carries that
    ``tenant=`` attribution (tenancy front traffic): "whose p99" is
    one flag."""
    forest = _trace_forest(data)
    per_request: list[tuple[float, dict]] = []
    seg_components: dict[str, set] = defaultdict(set)
    unrooted = 0
    other_tenant = 0
    max_sum_err = 0.0
    for tid, spans in forest.items():
        out = _request_segments(spans)
        if out is None:
            unrooted += 1
            continue
        segments, meta = out
        if tenant is not None and meta["tenant"] != tenant:
            other_tenant += 1
            continue
        max_sum_err = max(
            max_sum_err, abs(sum(segments.values()) - meta["root_ms"])
        )
        for seg, comps in meta["components"].items():
            seg_components[seg].update(comps)
        per_request.append((meta["root_ms"], segments))
    report: dict = {
        "n_requests": len(per_request),
        "unrooted_traces": unrooted,
        "tenant": tenant,
        "other_tenant_requests": other_tenant,
        "max_segment_sum_error_ms": round(max_sum_err, 6),
        "segments": {},
        "tail": {},
    }
    if not per_request:
        return report
    names = sorted({seg for _r, segs in per_request for seg in segs})
    roots = sorted(r for r, _s in per_request)
    report["root_ms"] = {
        "p50": round(percentile(roots, 0.50), 3),
        "p95": round(percentile(roots, 0.95), 3),
        "p99": round(percentile(roots, 0.99), 3),
    }
    total_all = sum(roots)
    for seg in names:
        vals = sorted(segs.get(seg, 0.0) for _r, segs in per_request)
        total = sum(vals)
        report["segments"][seg] = {
            "count": sum(1 for v in vals if v > 0),
            "total_ms": round(total, 3),
            "share_pct": round(100.0 * total / total_all, 2)
            if total_all else 0.0,
            "p50_ms": round(percentile(vals, 0.50), 3),
            "p95_ms": round(percentile(vals, 0.95), 3),
            "p99_ms": round(percentile(vals, 0.99), 3),
            "components": sorted(seg_components.get(seg, ())),
        }
    # Tail blame: among the slowest requests, where does the extra time
    # sit? Rank segments by their MEAN ms inside the tail.
    cut = percentile(roots, tail_q)
    tail = [(r, segs) for r, segs in per_request if r >= cut] or per_request
    tail_total = sum(r for r, _s in tail)
    blame = []
    for seg in names:
        ms = sum(segs.get(seg, 0.0) for _r, segs in tail) / len(tail)
        blame.append((seg, ms))
    blame.sort(key=lambda x: -x[1])
    report["tail"] = {
        "quantile": tail_q,
        "n_requests": len(tail),
        "cut_ms": round(cut, 3),
        "blame": [
            {"segment": seg, "mean_ms": round(ms, 3),
             "share_pct": round(100.0 * ms * len(tail) / tail_total, 2)
             if tail_total else 0.0}
            for seg, ms in blame
        ],
    }
    return report


def print_critical_path(report: dict) -> None:
    tenant = report.get("tenant")
    scope = f" for tenant {tenant!r}" if tenant is not None else ""
    skipped = []
    if report["unrooted_traces"]:
        skipped.append(f"{report['unrooted_traces']} unrooted")
    if report.get("other_tenant_requests"):
        skipped.append(f"{report['other_tenant_requests']} other-tenant")
    print(f"requests: {report['n_requests']} rooted{scope}"
          + (f" ({', '.join(skipped)} skipped)" if skipped else ""))
    if not report["segments"]:
        print("no rooted request span trees found")
        return
    r = report.get("root_ms") or {}
    print(f"root (request) ms: p50 {r.get('p50')}  p95 {r.get('p95')}  "
          f"p99 {r.get('p99')}; per-request segment sums match the root "
          f"within {report['max_segment_sum_error_ms']}ms")
    w = max(len(n) for n in report["segments"])
    print(f"{'segment':<{w}}  {'count':>6} {'total':>10} {'share':>7} "
          f"{'p50':>8} {'p95':>8} {'p99':>8}  components")
    for name, s in sorted(report["segments"].items(),
                          key=lambda kv: -kv[1]["total_ms"]):
        print(f"{name:<{w}}  {s['count']:>6} {s['total_ms']:>10.1f} "
              f"{s['share_pct']:>6.1f}% {s['p50_ms']:>8.2f} "
              f"{s['p95_ms']:>8.2f} {s['p99_ms']:>8.2f}  "
              f"{','.join(s['components'])}")
    tail = report["tail"]
    print(f"tail blame (root >= {tail['cut_ms']}ms, "
          f"{tail['n_requests']} requests):")
    for row in tail["blame"]:
        if row["mean_ms"] <= 0:
            continue
        print(f"  {row['segment']:<{w}}  mean {row['mean_ms']:>8.2f}ms  "
              f"{row['share_pct']:>5.1f}% of tail time")


def compare_critical_paths(rep_a: dict, rep_b: dict) -> dict:
    """Segment-by-segment p50/p95/p99 deltas A -> B (the --compare
    shape, over critical-path segments instead of raw phases): a bench
    regression names its phase."""
    segs_a, segs_b = rep_a["segments"], rep_b["segments"]
    out: dict = {"segments": {}, "only_in_a": [], "only_in_b": []}
    for name in sorted(set(segs_a) | set(segs_b)):
        a, b = segs_a.get(name), segs_b.get(name)
        if a is None:
            out["only_in_b"].append(name)
            continue
        if b is None:
            out["only_in_a"].append(name)
            continue
        row = {"count_a": a["count"], "count_b": b["count"]}
        for q in ("p50_ms", "p95_ms", "p99_ms"):
            row[f"{q}_a"] = a[q]
            row[f"{q}_b"] = b[q]
            row[f"{q}_delta"] = round(b[q] - a[q], 3)
            row[f"{q}_delta_pct"] = (
                round(100.0 * (b[q] - a[q]) / a[q], 1) if a[q] else None
            )
        out["segments"][name] = row
    return out


def print_flight_events(events: list) -> None:
    """Per-owner flight-event table: every event is stamped with
    component (+ replica_id / worker_id where the owner has one)."""
    if not events:
        return
    counts: dict[tuple, int] = defaultdict(int)
    for e in events:
        owner = e.get("component", "?")
        for key in ("replica_id", "worker_id", "worker", "replica"):
            if e.get(key) is not None:
                owner = f"{owner}[{e[key]}]"
                break
        counts[(owner, e.get("kind", "?"))] += 1
    print("flight events:")
    w = max(len(o) for o, _k in counts)
    for (owner, kind), n in sorted(counts.items()):
        print(f"  {owner:<{w}}  {kind:<28} {n:>5}")


def compare_reports(rep_a: dict, rep_b: dict) -> dict:
    """Per-phase p50/p95/p99 deltas from A to B (positive = B slower)."""
    phases_a, phases_b = rep_a["phases"], rep_b["phases"]
    out: dict = {"phases": {}, "only_in_a": [], "only_in_b": []}
    for name in sorted(set(phases_a) | set(phases_b)):
        a, b = phases_a.get(name), phases_b.get(name)
        if a is None:
            out["only_in_b"].append(name)
            continue
        if b is None:
            out["only_in_a"].append(name)
            continue
        row = {"count_a": a["count"], "count_b": b["count"]}
        for q in ("p50_ms", "p95_ms", "p99_ms"):
            row[f"{q}_a"] = a[q]
            row[f"{q}_b"] = b[q]
            row[f"{q}_delta"] = round(b[q] - a[q], 3)
            row[f"{q}_delta_pct"] = (
                round(100.0 * (b[q] - a[q]) / a[q], 1) if a[q] else None
            )
        out["phases"][name] = row
    return out


def print_compare(cmp: dict, path_a: str, path_b: str) -> None:
    print(f"A = {path_a}\nB = {path_b}")
    if cmp["phases"]:
        w = max(len(n) for n in cmp["phases"])
        print(f"{'phase':<{w}}  {'p50 A':>8} {'p50 B':>8} {'Δ%':>7}  "
              f"{'p95 A':>8} {'p95 B':>8} {'Δ%':>7}  "
              f"{'p99 A':>8} {'p99 B':>8} {'Δ%':>7}  (ms; +Δ = B slower)")
        for name, r in cmp["phases"].items():
            cells = []
            for q in ("p50_ms", "p95_ms", "p99_ms"):
                pct = r[f"{q}_delta_pct"]
                cells.append(f"{r[f'{q}_a']:>8.2f} {r[f'{q}_b']:>8.2f} "
                             f"{(f'{pct:+.1f}' if pct is not None else 'n/a'):>7}")
            print(f"{name:<{w}}  " + "  ".join(cells))
    else:
        print("no phases present in both traces")
    if cmp["only_in_a"]:
        print(f"phases only in A: {', '.join(cmp['only_in_a'])}")
    if cmp["only_in_b"]:
        print(f"phases only in B: {', '.join(cmp['only_in_b'])}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", nargs="?", default=None,
                    help="Chrome-trace JSON file (obs span dump)")
    ap.add_argument("--json", action="store_true", help="print JSON report")
    ap.add_argument("--phase", default=None,
                    help="restrict the summary to one span name")
    ap.add_argument("--critical-path", action="store_true",
                    help="decompose each rooted request trace into "
                         "exclusive-time segments (sum == root span) and "
                         "rank the tail's blame per segment")
    ap.add_argument("--tenant", default=None,
                    help="with --critical-path: keep only requests whose "
                         "root span carries this tenant= attribution "
                         "(tenancy front traffic)")
    ap.add_argument("--compare", nargs=2, metavar=("A", "B"), default=None,
                    help="diff two trace files per phase (p50/p95/p99 "
                         "deltas A -> B); with --critical-path, per "
                         "segment instead")
    args = ap.parse_args(argv)
    if (args.trace is None) == (args.compare is None):
        ap.error("pass one trace file, or --compare A.json B.json")
    if args.tenant is not None and not args.critical_path:
        ap.error("--tenant requires --critical-path")
    try:
        if args.compare is not None:
            path_a, path_b = args.compare
            data_a, data_b = load_trace(path_a), load_trace(path_b)
            if args.critical_path:
                cmp = compare_critical_paths(
                    critical_path_report(data_a, tenant=args.tenant),
                    critical_path_report(data_b, tenant=args.tenant),
                )
                cmp = {"phases": cmp["segments"],
                       "only_in_a": cmp["only_in_a"],
                       "only_in_b": cmp["only_in_b"]}
            else:
                cmp = compare_reports(
                    summarize(data_a, phase=args.phase),
                    summarize(data_b, phase=args.phase),
                )
            if args.json:
                json.dump(cmp, sys.stdout, indent=2)
                print()
            else:
                print_compare(cmp, path_a, path_b)
            return 0
        data = load_trace(args.trace)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"trace_report: {e}", file=sys.stderr)
        return 1
    if args.critical_path:
        report = critical_path_report(data, tenant=args.tenant)
        if args.json:
            json.dump(report, sys.stdout, indent=2)
            print()
        else:
            print_critical_path(report)
            print_flight_events(
                (data.get("otherData") or {}).get("flight_events") or [])
        return 0
    report = summarize(data, phase=args.phase)
    if args.json:
        json.dump(report, sys.stdout, indent=2)
        print()
    else:
        print_report(report)
        print_flight_events(
            (data.get("otherData") or {}).get("flight_events") or [])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
