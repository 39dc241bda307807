"""The training cell's ``correct`` at a tiny size on the CPU: the rest of a
run after the look for a chip, sound and with the timed path broken
underneath, and the lower-precision control."""

import pytest

from bench_tiny import FAKE_DEVICE, tiny_root


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    from benchmark.harness.spec import Spec

    return Spec(tiny_root(tmp_path_factory.mktemp("train"))).cell("tiger_train_packed")


def _run(cell, fault=None, control=False):
    from benchmark import run as brun
    from benchmark.harness import faults

    undo = faults.plant(cell.adapter, faults.TRAIN_FAULTS[fault]) if fault else None
    try:
        return brun.run_cell(cell, 2**31 + 9, 0.2, False, FAKE_DEVICE, 0.0,
                             control=control)
    finally:
        if undo:
            undo()


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
    assert ctl["loss_gap_step1"]["value"] > 3 * ctl["loss_gap_step1"]["limit"]


def test_state_left_unchanged_fails(cell):
    line = _run(cell, "state_unchanged")
    assert line["correct"] is False
    assert line["checks"]["change_gap"]["value"] == pytest.approx(1.0, abs=1e-3)


def test_weight_decay_left_out_fails(cell):
    """The three checked steps lie inside the warm-up, where the decay is a
    few thousandths of a step: only ``decay_gap`` sees it go."""
    from benchmark.harness import faults

    undo = faults.plant_no_weight_decay(cell.adapter)
    try:
        line = _run(cell)
    finally:
        undo()
    assert line["correct"] is False
    assert line["checks"]["decay_gap"]["value"] == pytest.approx(1.0, abs=0.1)
    assert line["checks"]["change_gap"]["value"] <= line["checks"]["change_gap"]["limit"]


def test_half_the_batch_left_out_fails(cell):
    line = _run(cell, "half_batch")
    assert line["correct"] is False
    assert line["checks"]["loss_gap_step1"]["value"] > 10 * 1e-4
