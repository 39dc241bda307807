"""The Kimi-Linear cell's ``correct`` at a tiny size on the CPU: the rest of a
run after the look for a chip, sound; the lower-precision control; a program
whose KDA forget gate is one scalar a head (the mean over channels) in the
per-channel gate's place; and the counters the new per-layer metrics read."""

import pytest

from kimi_tiny import FAKE_DEVICE, tiny_root

CELL = "kimi_linear_sft_lifelong"


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    from benchmark.harness.spec import Spec

    return Spec(tiny_root(tmp_path_factory.mktemp("kimi"))).cell(CELL)


def _run(cell, control=False):
    from benchmark import run as brun

    return brun.run_cell(cell, 2**31 + 13, 0.2, False, FAKE_DEVICE, 0.0,
                         control=control)


def test_sound_run_is_correct_and_control_is_not(cell):
    line = _run(cell, control=True)
    assert line["correct"] is True, line["checks"]
    assert set(line["checks"]) == {"loss_gap_step1", "loss_gap_step2",
                                   "loss_gap_step3", "grad_gap", "change_gap",
                                   "decay_gap"}
    assert line["metrics"]["train_tokens_per_s_per_chip"]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    assert line["attempted"] > 0 and line["failed"] == 0
    ctl = line["control_checks"]
    assert line["control_correct"] is False
    assert any(c["value"] > c["limit"] for c in ctl.values()), ctl


def test_a_scalar_gate_in_the_channel_gates_place_fails(cell, monkeypatch):
    """One decay a head (the mean over its channels) costs the same scan and
    keeps the same share of the state on average: only the comparison with
    the reference sees it."""
    import jax.numpy as jnp

    from genrec_tpu.models.backbones import kda

    real = kda.forget_gate

    def scalar(f, a_log, dt_bias):
        g = real(f, a_log, dt_bias)
        return jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)

    monkeypatch.setattr(kda, "forget_gate", scalar)
    line = _run(cell)
    assert line["correct"] is False
    over = {k: c["value"] / c["limit"] for k, c in line["checks"].items()}
    assert max(over.values()) > 10, over


def test_the_reference_can_play_the_scalar_gate_too(cell, monkeypatch):
    """The reference's own ``scalar_gate`` switch (a test's control, never a
    judge) gives the loss the patched program gives: the two agree on WHAT
    the scalar gate computes, so the test above fails for the gate and not
    for an accident of the patch."""
    import jax.numpy as jnp

    from genrec_tpu.models.backbones import kda
    from genrec_tpu.models.lcrec import sft_loss

    cfg, ad, ref = cell.config, cell.adapter, cell.reference
    params = ad.make_params(cfg, 3)
    rows = ad.make_rows(cfg, cell.traffic, 3)
    batch = {k: rows[k][:2] for k in ("input_ids", "attention_mask", "labels")}
    sound = float(ref.batch_loss(params, cfg, batch))
    scalar = float(ref.batch_loss(params, cfg, batch, scalar_gate=True))
    real = kda.forget_gate
    monkeypatch.setattr(kda, "forget_gate", lambda *a: jnp.broadcast_to(
        real(*a).mean(-1, keepdims=True), real(*a).shape))
    patched = float(sft_loss(ad._model(cfg), params, batch["input_ids"],
                             batch["attention_mask"], batch["labels"]))
    assert patched == pytest.approx(scalar, rel=1e-5)
    assert abs(scalar - sound) / sound > 1e-4  # the cell's loss limit at this size


def test_step_counters_reach_the_span_and_the_readers(cell):
    from genrec_tpu.obs.spans import SpanTracer

    tracer = SpanTracer(capacity=1000, enabled=True)
    entry = cell.adapter.build_train(cell.config, cell.traffic, 5, 1, tracer=tracer)
    entry.run_epoch(max_steps=2)
    steps = [s for s in tracer.spans() if s.name == "train_step"]
    assert len(steps) == 2
    ctx = {"kind": "train", "spans": list(tracer.spans())}
    got = {m: cell.metric_reader(m + ".train")(ctx)
           for m in ("expert_load_max_over_mean", "expert_picks_here_share",
                     "expert_pairs_per_held_expert", "kda_state_keep_share")}
    assert got["expert_picks_here_share"] == pytest.approx(100.0)  # all 16 held
    assert got["expert_load_max_over_mean"] >= 1.0
    # one row a step, 4 picks a real token, 16 experts held
    tokens = entry._row_tokens
    assert tokens.min() * 4 / 16 <= got["expert_pairs_per_held_expert"] <= tokens.max() * 4 / 16
    assert 50.0 < got["kda_state_keep_share"] < 100.0
    # a program without the counters (the parent): nothing to read, no error
    bare = {"kind": "train", "spans": [s for s in tracer.spans()
                                       if s.name != "train_step"]}
    for m in got:
        assert cell.metric_reader(m + ".train")(bare) is None


def test_cell_is_one_row_a_step_and_eight_steps_an_epoch(cell):
    import json
    import os

    from kimi_tiny import REPO

    with open(os.path.join(REPO, "benchmark", "traffic", "sft_lifelong_8k.json")) as f:
        t = json.load(f)
    assert t["rows_per_step_per_chip"] * 8 == t["corpus_rows"]
    assert t["reference_block_rows"] >= t["rows_per_step_per_chip"]
    assert t["row_len"] == 8192 and t["history_tokens"]["max"] == 8192
