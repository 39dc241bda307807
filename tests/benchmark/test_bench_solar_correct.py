"""The Solar-Open2 serving cell's ``correct`` at a tiny size on the CPU: the
rest of a run after the look for a chip is sound; each control (matmul
operands one precision lower, the write strength not doubled, the output gate
dropped, the recurrent state carried in bfloat16) fails at least one limit;
an answer altered where it is produced fails, and so does a retained end
state altered where it is kept; and the new per-layer metrics read the
spans and counters a served window leaves (and nothing, without raising,
where the program has none)."""

import copy

import numpy as np
import pytest

from solar_tiny import CELL, FAKE_DEVICE, tiny_root

NEW_METRICS = ("recurrent_state_hbm_share.serve", "state_snapshot_bytes.serve",
               "warm_admit_restore_ms.serve", "prefill_tokens_per_s.serve",
               "expert_pairs_per_held_expert.serve")


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    from benchmark.harness.spec import Spec

    return Spec(tiny_root(tmp_path_factory.mktemp("solar"))).cell(CELL)


@pytest.fixture(scope="module")
def window(cell):
    """One traced-by-spans window of the tiny cell: the engine built as a
    run builds it (warmed, filled), driven once."""
    from genrec_tpu.kernels.policy import interpret_mode
    from genrec_tpu.obs.spans import SpanTracer

    tracer = SpanTracer(capacity=200_000, enabled=True)
    seed = 2**31 + 21
    with interpret_mode():
        engine, head, params, catalog, arrivals = cell.kind.build(
            cell, seed, 1.5, tracer=tracer)
        try:
            stats0 = engine.stats()
            records, t_open = cell.kind.drive(
                engine, cell.adapter.make_request, head.name, arrivals)
            stats1 = engine.stats()
        finally:
            engine.stop()
    done = [r for r in records if r.response is not None]
    assert len(done) == len(records) > 8
    ctx = {"cell": cell, "kind": "serve", "head": head.name, "t_open": t_open,
           "stats0": stats0, "stats1": stats1, "spans": list(tracer.spans()),
           "bytes_limit": 16 * 2**30, "done": done}
    return cell, params, catalog, seed, done, ctx


def _verdict(checks):
    return all(c["value"] <= c["limit"] for c in checks.values())


def test_sound_window_is_correct(window):
    cell, params, catalog, seed, done, _ = window
    check = cell._config_module("check")
    checks, extra = check.judge_served(cell, params, catalog, done, seed)
    assert _verdict(checks), checks
    assert set(checks) == {"score_gap", "beam_gap", "bad_items", "state_gap",
                           "state_bf16_share"}
    assert extra["checked_requests"] == cell.traffic["check_requests"]
    # the engine was stopped (its index drained) before the judging, as in a
    # run: the adapter noted the retained end states at the stop
    assert extra["checked_states"] == extra["checked_requests"]
    # warm and cold admits are both in the sample, and histories of both
    # buckets' lengths, the longest among them (which bucket a request was
    # prefilled in is the batch's, not the request's: never asserted)
    assert 0 < extra["checked_warm"] < extra["checked_requests"]
    picked = check.sample(done, cell.traffic["check_requests"], seed)
    lengths = [len(r.arrival.history) for r in picked]
    small = min(cell.config["assumed"]["serve"]["history_buckets"])
    assert min(lengths) <= small < max(lengths)
    assert max(lengths) == max(len(r.arrival.history) for r in done)


@pytest.fixture(scope="module")
def controls(window):
    cell, params, catalog, seed, done, _ = window
    check = cell._config_module("check")
    _, extra = check.judge_served(cell, params, catalog, done, seed, control=True)
    return check, extra


@pytest.mark.parametrize("name", ["fp8", "beta_single", "no_gate", "state_bf16"])
def test_each_control_fails_a_limit(controls, name):
    """Each control answers the prompts itself (its own beam search, its own
    end states) and passes through the numbers a served answer does."""
    check, extra = controls
    assert name in check.CONTROLS
    got = extra["controls"][name]
    assert set(got) == {"score_gap", "beam_gap", "bad_items", "state_gap",
                        "state_bf16_share"}
    assert not _verdict(got), got
    assert max(c["value"] / c["limit"] for c in got.values()
               if c["limit"] > 0) > 3, got
    # only a state carried in bfloat16 is made of bfloat16 numbers: the one
    # limit that still holds it at the real widths, where its score and
    # state gaps read inside a sound run's
    exact = got["state_bf16_share"]["value"]
    assert (exact == 1.0) if name == "state_bf16" else (exact < 0.01), got


def test_control_checks_are_the_control_that_came_closest(controls):
    _, extra = controls
    assert not _verdict(extra["control_checks"])
    worst = lambda c: max(v["value"] / max(v["limit"], 1e-12) for v in c.values())
    check, _ = controls
    assert worst(extra["control_checks"]) == min(
        worst(extra["controls"][name]) for name in check.CONTROLS)


def test_altered_answer_fails(window):
    cell, params, catalog, seed, done, _ = window
    check = cell._config_module("check")
    done = copy.deepcopy(done)
    r = check.sample(done, cell.traffic["check_requests"], seed)[0].response
    r.scores = np.array(r.scores)
    r.scores[0] += 0.05
    checks, _ = check.judge_served(cell, params, catalog, done, seed)
    assert not _verdict(checks)


@pytest.mark.parametrize("how, failing", [
    ("scaled", "state_gap"), ("rounded", "state_bf16_share"),
    ("dropped", "state_gap")])
def test_altered_retained_state_fails(window, monkeypatch, how, failing):
    """The snapshot a warm admit binds is held to the reference's state after
    the prompt, and to the float32 the configuration states."""
    import jax.numpy as jnp

    cell, params, catalog, seed, done, _ = window
    check = cell._config_module("check")
    kept = cell.adapter.retained_states
    alter = {
        "scaled": lambda s: s * 1.01,
        "rounded": lambda s: np.asarray(jnp.asarray(s).astype(jnp.bfloat16)
                                        .astype(jnp.float32)),
        "dropped": lambda s: None,
    }[how]
    monkeypatch.setattr(cell.adapter, "retained_states",
                        lambda cfg, history: alter(kept(cfg, history)))
    checks, _ = check.judge_served(cell, params, catalog, done, seed)
    failed = {k for k, c in checks.items() if c["value"] > c["limit"]}
    assert failed == {failing}, checks


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_metrics_read_the_window(window, metric):
    cell, *_, ctx = window
    value = cell.metric_reader(metric)(ctx)
    assert value is not None and np.isfinite(value) and value > 0, (metric, value)
    if metric == "recurrent_state_hbm_share.serve":
        assert value < 100.0
    if metric == "state_snapshot_bytes.serve":
        entries = ctx["stats1"]["prefix_cache"][ctx["head"]]["entries"]
        assert value % entries == 0  # whole snapshots, one an entry


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_metrics_read_nothing_from_a_program_without_them(window, metric):
    """The parent commit has neither the counters nor the spans: a reader
    returns None there and does not raise."""
    cell, *_, ctx = window
    strip = lambda s: {
        **{k: v for k, v in s.items() if k != "prefill_prompt_tokens"},
        "kv_pool": {h: {k: v for k, v in g.items() if k != "recurrent_state_bytes"}
                    for h, g in s["kv_pool"].items()},
        "prefix_cache": {h: {k: v for k, v in g.items() if "snapshot" not in k}
                         for h, g in s["prefix_cache"].items()}}
    import types

    spans = [types.SimpleNamespace(name=s.name, t0=s.t0, t1=s.t1, trace_id=s.trace_id,
                                   attrs={}) for s in ctx["spans"]
             if s.name != "admit.restore_state"]
    old = dict(ctx, stats0=strip(ctx["stats0"]), stats1=strip(ctx["stats1"]),
               spans=spans)
    assert cell.metric_reader(metric)(old) is None


def test_the_rest_of_a_run_is_correct_and_reports_its_metrics(cell):
    from genrec_tpu.kernels.policy import interpret_mode

    from benchmark import run as brun

    with interpret_mode():
        line = brun.run_cell(cell, 2**31 + 22, 1.0, False, FAKE_DEVICE, 0.0)
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"serve_latency_p50_ms", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0


def test_flops_and_the_cut_add_up(cell):
    """The configuration's arithmetic: the parameter count the file states
    is the tree's at the real sizes, and a prompt's FLOPs grow with it."""
    import json
    import os

    import jax

    from solar_tiny import CONFIG_DIR

    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        real = json.load(f)
    shapes = cell.adapter.param_shapes(real)
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert n == real["parameters"]
    fl = cell.flops
    assert fl.paged_layers(real) == 1 and fl.kv_tokens(real, 2000) == 5120
    assert fl.serve_prefill(real, 1 + 5120) > 4 * fl.serve_prefill(real, 1 + 1024)
    flops, bytes_ = fl.paged_attention_call(real, 5120, 10)
    assert bytes_ == 2 * 5120 * 1024 * 2 + 2 * 10 * 8192 * 2
    assert flops == 2 * 2 * 10 * 64 * 5120 * 128
