"""scripts/ci_checks.sh — the single entrypoint for the standalone static
checks — plus fast in-process runs of the packed/fused HLO checks and
verdict-schema parity pins for the check_* scripts' PR-8 migration onto
the shared analysis/ir.py harness.

The full smoke invocation (all checks through the shell entrypoint)
is exercised once; check_decode_hlo additionally has its own in-process
CI wrapper (tests/test_check_decode_hlo.py), and graftlint has
tests/test_analysis.py."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")

# Bit-compat pins for the ISSUE-8 refactor: the migrated scripts must
# emit EXACTLY the verdict keys their consumers grep/parse.
DECODE_KEYS = {"backend", "shapes", "cached_broadcast_hits",
               "uncached_broadcast_hits", "compiled_one_program",
               "regex_bites", "ok"}
PACKED_KEYS = {"backend", "shapes", "scatter_ops_in_step",
               "repad_scatter_hits", "compiled_one_program",
               "regex_bites", "ok"}
FUSED_KEYS = {"backend", "devices", "conclusive", "mosaic_custom_calls",
              "collectives_in_module", "all_gather_feeding_custom_call",
              "global_sized_custom_call_operands", "ok"}
SERVING_KEYS = {"backend", "dense", "paged", "recompilations", "ok"}
FLEET_KEYS = {"backend", "replicas_started", "submitted", "completed",
              "shed", "failed", "lost", "rerouted", "replica_deaths",
              "kill_narrated", "reroutes_narrated", "recompilations",
              "pages_in_use_final", "slots_active_final",
              "constrained_items_valid", "p99_under_burst_ms", "ok"}
DISAGG_KEYS = {"backend", "submitted", "completed", "failed", "replays",
               "warm_hits", "handoffs_sent", "handoffs_admitted",
               "handoffs_refused", "transfer_bytes", "recompilations",
               "prefill_pages_final", "decode_pages_final",
               "slots_active_final", "parity_ok", "ok"}
CROSSHOST_KEYS = {"backend", "submitted", "completed", "failed", "replays",
                  "warm_hits", "handoffs_sent", "handoffs_admitted",
                  "handoffs_refused", "receipts", "peer_losses",
                  "wire_bytes", "recompilations_front",
                  "recompilations_peer", "prefill_pages_final",
                  "peer_pages_final", "peer_slots_final", "sockets_closed",
                  "child_rc", "parity_ok", "ok"}
CHAOSNET_KEYS = {"backend", "submitted", "completed", "failed", "lost",
                 "typed_only", "reconnects", "heartbeat_misses",
                 "incarnation_discards", "decode_worker_deaths",
                 "degraded_entered", "scale_outs", "recovery_ms",
                 "recompilations_front", "recompilations_peers",
                 "prefill_pages_final", "peer_pages_final",
                 "peer_slots_final", "parity_ok", "child_rcs", "ok"}
SPEC_KEYS = {"backend", "submitted", "completed", "recompilations", "rungs",
             "topology", "topologies_per_rung", "spec_steps",
             "plain_decode_steps", "spec_decode_steps",
             "codes_per_invocation", "accept_hist",
             "scratch_pages_reserved", "parity_ok", "spans_ok",
             "pages_in_use_final", "scratch_pages_final",
             "slots_active_final", "ok"}
LINEAGE_KEYS = {"backend", "submitted", "completed", "traces_checked",
                "rooted_ok", "components_ok", "min_components",
                "spec_spans_ok", "wire_spans_ok", "segment_sum_ok",
                "max_segment_sum_error_ms", "segments", "wire_trace_ok",
                "recompilations", "trace_path", "ok"}
QUANT_KEYS = {"backend", "churn", "pool_hlo", "recompilations", "ok"}
TENANCY_KEYS = {"backend", "submitted", "completed", "shed", "failed",
                "lost", "recompilations", "version_mixing",
                "shadow_surfaced", "wrong_arm", "shadow_mirrored",
                "shadow_errors", "exp_records", "ledger_identity",
                "tenants", "ok"}
PIPELINE_KEYS = {"backend", "records_appended", "records_lost",
                 "records_duplicated", "sigkills", "steps_trained",
                 "published_steps", "loss_parity_max_err",
                 "param_parity_max_err", "resume_exact", "promotions",
                 "vetoes", "rollbacks", "quarantined_steps",
                 "last_good_step", "responses_served", "unvetted_serves",
                 "garbage_served", "freshness_s", "first_serve_s",
                 "pages_in_use_final", "slots_active_final", "ok"}
# bench_gate is the new perf regression gate (one verdict line,
# graftlint mold); check_obs's grown verdict (memory + slo sections) is
# exercised by its own full run in ci_checks, not re-run here.
BENCH_GATE_KEYS = {"check", "ok", "self_test", "compared", "regressions",
                   "improvements", "within_band", "missing",
                   "backend_skipped", "skipped", "baseline", "run",
                   "updated"}


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "scripts", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_packed_hlo_check_small(capsys):
    mod = _load("check_packed_hlo")
    rc = mod.main(["--small"])
    verdict = json.loads(capsys.readouterr().out)
    assert verdict["regex_bites"], (
        "self-test failed: the explicit unpack no longer shows the re-pad "
        "scatter, so the check is vacuous"
    )
    assert verdict["repad_scatter_hits"] == 0, verdict
    assert verdict["compiled_one_program"]
    assert set(verdict) == PACKED_KEYS  # harness migration parity
    assert rc == 0


def test_fused_ce_hlo_check_small_is_inconclusive_not_failed(capsys):
    """On the CPU backend Mosaic can never appear (interpret mode): the
    check must report conclusive=false with rc=2, not a failure."""
    mod = _load("check_fused_ce_hlo")
    rc = mod.main(["--small"])
    verdict = json.loads(capsys.readouterr().out)
    assert verdict["conclusive"] is False
    assert set(verdict) == FUSED_KEYS  # harness migration parity
    assert rc == 2


def test_check_scripts_keep_their_cli():
    """The shared harness must preserve every script's flag surface
    (ci_checks.sh passes these exact flags)."""
    for script in ("check_decode_hlo", "check_packed_hlo",
                   "check_fused_ce_hlo", "check_serving_hlo",
                   "check_catalog_hlo", "check_fleet", "check_disagg",
                   "check_crosshost", "check_chaosnet", "check_spec_hlo",
                   "check_lineage", "check_obs", "check_quant_hlo",
                   "check_pipeline", "check_tenancy"):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", f"{script}.py"),
             "--help"],
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, (script, proc.stderr[-500:])
        for flag in ("--write-note", "--small", "--platform"):
            assert flag in proc.stdout, (script, flag)


def test_ci_checks_smoke_entrypoint():
    """The consolidated entrypoint runs every smoke check and exits 0
    (rc=2 inconclusives tolerated, real failures propagated)."""
    # The chaos-unit, obs, graftlint, catalog, quant, chaosnet,
    # pipeline and tenancy subsets are skipped here: this test runs
    # INSIDE the suite that already executes
    # tests/test_fault_tolerance.py, tests/test_obs.py,
    # tests/test_analysis.py, tests/test_catalog.py,
    # tests/test_quantized.py, tests/test_chaosnet.py,
    # tests/test_pipeline.py and tests/test_tenancy.py directly, and
    # nesting them would double-pay their cold-start (~30s-4min each)
    # for no coverage (check_quant_hlo's, check_chaosnet's,
    # check_pipeline's and check_tenancy's verdict schemas are pinned
    # by the slow-marked tests below). The (jax-free, sub-second)
    # bench_gate self-test stays.
    proc = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "ci_checks.sh"), "--smoke"],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "GENREC_CI_SKIP_CHAOS": "1", "GENREC_CI_SKIP_OBS": "1",
             "GENREC_CI_SKIP_LINT": "1", "GENREC_CI_SKIP_CATALOG": "1",
             "GENREC_CI_SKIP_QUANT": "1",
             "GENREC_CI_SKIP_CHAOSNET": "1",
             "GENREC_CI_SKIP_PIPELINE": "1",
             "GENREC_CI_SKIP_TENANCY": "1"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    # One verdict JSON per check on stdout (decode, fused-ce, packed,
    # serving, fleet, disagg, crosshost, spec, lineage, bench-gate
    # self-test; the quant, chaosnet, pipeline and tenancy checks are
    # env-skipped above, so the unfiltered smoke emits four more).
    verdicts = [json.loads(l) for l in proc.stdout.splitlines() if l.strip()]
    assert len(verdicts) == 10
    lineage = [v for v in verdicts if "segment_sum_ok" in v]
    assert len(lineage) == 1 and set(lineage[0]) == LINEAGE_KEYS
    assert lineage[0]["rooted_ok"] and lineage[0]["components_ok"]
    assert lineage[0]["min_components"] >= 3
    assert lineage[0]["segment_sum_ok"] and lineage[0]["wire_trace_ok"]
    assert lineage[0]["recompilations"] == 0
    spec = [v for v in verdicts if "codes_per_invocation" in v]
    assert len(spec) == 1 and set(spec[0]) == SPEC_KEYS
    assert spec[0]["recompilations"] == 0 and spec[0]["parity_ok"]
    assert spec[0]["topologies_per_rung"] == 1
    assert spec[0]["codes_per_invocation"] > 1.0
    assert spec[0]["scratch_pages_final"] == 0
    serving = [v for v in verdicts if "dense" in v]
    assert len(serving) == 1 and serving[0]["recompilations"] == 0
    assert set(serving[0]) == SERVING_KEYS  # harness migration parity
    fleet = [v for v in verdicts if "rerouted" in v]
    assert len(fleet) == 1 and set(fleet[0]) == FLEET_KEYS
    assert fleet[0]["recompilations"] == 0 and fleet[0]["lost"] == 0
    disagg = [v for v in verdicts if "decode_pages_final" in v]
    assert len(disagg) == 1 and set(disagg[0]) == DISAGG_KEYS
    assert disagg[0]["recompilations"] == 0 and disagg[0]["parity_ok"]
    assert disagg[0]["prefill_pages_final"] == 0
    assert disagg[0]["decode_pages_final"] == 0
    xhost = [v for v in verdicts if "recompilations_peer" in v]
    assert len(xhost) == 1 and set(xhost[0]) == CROSSHOST_KEYS
    assert xhost[0]["recompilations_front"] == 0
    assert xhost[0]["recompilations_peer"] == 0
    assert xhost[0]["parity_ok"] and xhost[0]["peer_losses"] == 0
    assert xhost[0]["receipts"] == xhost[0]["handoffs_sent"]
    assert xhost[0]["peer_pages_final"] == 0 and xhost[0]["child_rc"] == 0
    decode = [v for v in verdicts if "cached_broadcast_hits" in v]
    assert len(decode) == 1 and set(decode[0]) == DECODE_KEYS
    gate = [v for v in verdicts if v.get("check") == "bench_gate"]
    assert len(gate) == 1 and set(gate[0]) == BENCH_GATE_KEYS
    assert gate[0]["self_test"]["ok"] and gate[0]["ok"]


@pytest.mark.slow
def test_chaosnet_check_small():
    """check_chaosnet's verdict schema + the self-healing pins (slow:
    it spawns two decode-host children and runs a seeded partition +
    corrupt-frame + SIGKILL + recovery schedule, ~3-4min — the tier-1
    suite covers the same machinery via tests/test_chaosnet.py; this
    pins the SMOKE CHECK's contract for the shell entrypoint, which
    runs it unless GENREC_CI_SKIP_CHAOSNET is set)."""
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "scripts", "check_chaosnet.py"),
         "--small", "--platform", "cpu"],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    verdict = json.loads(lines[-1])
    assert set(verdict) == CHAOSNET_KEYS
    assert verdict["lost"] == 0 and verdict["typed_only"]
    assert verdict["reconnects"] >= 2
    assert verdict["decode_worker_deaths"] == 1
    assert verdict["scale_outs"] == 1 and verdict["parity_ok"]
    assert verdict["recompilations_front"] == 0
    assert verdict["recompilations_peers"] == 0
    assert verdict["child_rcs"] == [0, 0]


@pytest.mark.slow
def test_pipeline_check_small():
    """check_pipeline's verdict schema + the closed-loop pins (slow: it
    streams a seeded log through append -> train -> publish -> canary ->
    promote with two subprocess SIGKILLs and two warmed engines, ~2min —
    the tier-1 suite covers the same machinery via tests/test_pipeline.py
    and tests/test_stream_log.py; this pins the SMOKE CHECK's contract
    for the shell entrypoint, which runs it unless
    GENREC_CI_SKIP_PIPELINE is set)."""
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "scripts", "check_pipeline.py"),
         "--small", "--platform", "cpu"],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    verdict = json.loads(lines[-1])
    assert set(verdict) == PIPELINE_KEYS
    assert verdict["records_lost"] == 0
    assert verdict["records_duplicated"] == 0
    assert verdict["sigkills"] == 2 and verdict["resume_exact"]
    assert verdict["loss_parity_max_err"] <= 1e-5
    assert verdict["promotions"] == 2 and verdict["vetoes"] == 1
    assert verdict["unvetted_serves"] == 0
    assert verdict["garbage_served"] == 0
    assert verdict["pages_in_use_final"] == 0
    assert verdict["slots_active_final"] == 0
    assert 0.0 < verdict["freshness_s"] < 120.0


@pytest.mark.slow
def test_tenancy_check_small():
    """check_tenancy's verdict schema + the isolation/experiment pins
    (slow: it warms three engines — primary, arm-b, shadow — and
    replays a multi-tenant burst trace with mid-trace catalog churn,
    ~30s — the tier-1 suite covers the same machinery via
    tests/test_tenancy.py; this pins the SMOKE CHECK's contract for
    the shell entrypoint, which runs it unless GENREC_CI_SKIP_TENANCY
    is set)."""
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "scripts", "check_tenancy.py"),
         "--small", "--platform", "cpu"],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    verdict = json.loads(lines[-1])
    assert set(verdict) == TENANCY_KEYS
    assert verdict["lost"] == 0 and verdict["failed"] == 0
    assert verdict["recompilations"] == 0
    assert verdict["version_mixing"] == 0
    assert verdict["shadow_surfaced"] == 0
    assert verdict["wrong_arm"] == 0
    assert verdict["shadow_mirrored"] > 0
    assert verdict["shadow_errors"] == 0
    assert verdict["exp_records"] > 0
    assert verdict["ledger_identity"]
    assert set(verdict["tenants"]) == {"acme", "globex"}


@pytest.mark.slow
def test_quant_hlo_check_small(capsys):
    """check_quant_hlo's verdict schema + the int8-serving pins (slow:
    it warms a mixed-dtype two-head engine, ~60s — the tier-1 suite
    already covers the same surfaces via tests/test_quantized.py; this
    pins the SMOKE CHECK's contract for the shell entrypoint)."""
    mod = _load("check_quant_hlo")
    rc = mod.main(["--small"])
    verdict = json.loads(capsys.readouterr().out)
    assert set(verdict) == QUANT_KEYS
    assert rc == 0
    assert verdict["recompilations"] == 0
    assert verdict["churn"]["kv_dtype"] == "int8"
    assert verdict["churn"]["ledger_kv_page_pool_bytes"] == \
        verdict["churn"]["expected_kv_page_pool_bytes"]
    assert verdict["churn"]["ledger_quant_table_bytes"] == \
        verdict["churn"]["expected_quant_table_bytes"]
    assert verdict["pool_hlo"]["pool_param_s8"]
    assert not verdict["pool_hlo"]["full_pool_f32_upcast"]


# ---------------------------------------------------------------------------
# bench_gate fixtures (jax-free: direction, tolerance, partial refusal)
# ---------------------------------------------------------------------------


def _fixture_run(**overrides):
    run = {
        "metric": "tiger_train_seq_per_sec_per_chip", "value": 1000.0,
        "step_ms": 10.0, "backend": "tpu", "packed_vs_padded": 1.9,
        "serve": {"p99_ms": 20.0}, "meta": {"schema": 1, "backend": "tpu"},
    }
    run.update(overrides)
    return run


def test_bench_gate_flags_injected_regression(tmp_path, capsys):
    """ISSUE-10 acceptance: an injected ~10%+ regression on a fixture
    baseline is flagged (rc 1), an identical run passes (rc 0), and an
    improvement is reported without failing."""
    gate = _load("bench_gate")
    base = tmp_path / "baseline.json"
    run = tmp_path / "run.json"
    run.write_text(json.dumps(_fixture_run()))
    assert gate.main([str(run), "--baseline", str(base),
                      "--update-baseline"]) == 0
    capsys.readouterr()

    # identical run passes
    assert gate.main([str(run), "--baseline", str(base)]) == 0
    v = json.loads(capsys.readouterr().out)
    assert set(v) == BENCH_GATE_KEYS
    assert v["ok"] and not v["regressions"] and v["compared"] >= 3

    # ~12% headline drop (10% band) + ~35% p99 rise (30% band) -> rc 1
    run.write_text(json.dumps(_fixture_run(
        value=880.0, serve={"p99_ms": 27.0})))
    assert gate.main([str(run), "--baseline", str(base)]) == 1
    v = json.loads(capsys.readouterr().out)
    flagged = {e["metric"] for e in v["regressions"]}
    assert flagged == {"value", "serve/p99_ms"}, v["regressions"]

    # an improvement passes and is reported as such
    run.write_text(json.dumps(_fixture_run(value=1300.0)))
    assert gate.main([str(run), "--baseline", str(base)]) == 0
    v = json.loads(capsys.readouterr().out)
    assert {e["metric"] for e in v["improvements"]} == {"value"}


def test_bench_gate_refuses_partial_update_and_skips_backend_mismatch(
        tmp_path, capsys):
    gate = _load("bench_gate")
    base = tmp_path / "baseline.json"
    run = tmp_path / "run.json"
    run.write_text(json.dumps(_fixture_run()))
    assert gate.main([str(run), "--baseline", str(base),
                      "--update-baseline"]) == 0
    capsys.readouterr()
    # partial run (headline metric gone) must refuse the update
    partial = {k: v for k, v in _fixture_run().items() if k != "value"}
    run.write_text(json.dumps(partial))
    assert gate.main([str(run), "--baseline", str(base),
                      "--update-baseline"]) == 1
    v = json.loads(capsys.readouterr().out)
    assert not v["updated"] and "partial" in v["skipped"]
    # a cpu-fallback line against a tpu baseline is SKIPPED (rc 2), not
    # flagged as a hardware regression
    run.write_text(json.dumps(_fixture_run(
        value=500.0, backend="cpu", meta={"schema": 1, "backend": "cpu"})))
    assert gate.main([str(run), "--baseline", str(base)]) == 2
    v = json.loads(capsys.readouterr().out)
    assert v["ok"] and "backend mismatch" in v["skipped"]
    assert not v["regressions"]
    # ...and it must not be able to REWRITE the tpu baseline either, or
    # every later hardware comparison would rc-2-skip forever
    assert gate.main([str(run), "--baseline", str(base),
                      "--update-baseline"]) == 1
    v = json.loads(capsys.readouterr().out)
    assert not v["updated"] and "across backends" in v["skipped"]
    assert json.loads(base.read_text())["meta"]["backend"] == "tpu"


def test_bench_gate_committed_baseline_is_loadable():
    """The seeded results/bench_baseline.json stays schema-valid and
    gates at least the headline metric with a direction."""
    path = os.path.join(REPO, "results", "bench_baseline.json")
    with open(path) as fh:
        base = json.load(fh)
    assert base["schema"] == 1
    assert "value" in base["metrics"]
    for spec in base["metrics"].values():
        assert spec["direction"] in ("higher", "lower")
        assert spec["tolerance_pct"] > 0
        assert isinstance(spec["value"], (int, float))
