"""The Keye cell's ``correct`` at a tiny size on the CPU: the rest of a run
after the look for a chip, sound; the lower-precision control; a program
whose selection is a window (the newest top-k keys) in the indexer's place;
and the counters the new per-layer metrics read."""

import pytest

from keye_tiny import FAKE_DEVICE, tiny_root

CELL = "keye_sft_long_history"


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    from benchmark.harness.spec import Spec

    return Spec(tiny_root(tmp_path_factory.mktemp("keye"))).cell(CELL)


def _run(cell, control=False):
    from benchmark import run as brun

    return brun.run_cell(cell, 2**31 + 11, 0.2, False, FAKE_DEVICE, 0.0,
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


def test_a_window_in_the_indexers_place_fails(cell, monkeypatch):
    """Keeping the NEWEST top-k keys needs no indexer and reads the same
    number of keys: only the comparison with the reference sees it."""
    import jax.numpy as jnp

    from genrec_tpu.models.backbones import qwen

    def window(scores, allowed, k):
        newest = jnp.cumsum(allowed[..., ::-1], axis=-1)[..., ::-1]
        return allowed & (newest <= k)

    monkeypatch.setattr(qwen, "select_topk", window)
    line = _run(cell)
    assert line["correct"] is False
    assert line["checks"]["loss_gap_step1"]["value"] > 10 * 1e-4


def test_rows_are_seqrec_histories_with_a_fixed_draw_of_lengths(cell):
    import numpy as np

    cfg, traffic = cell.config, cell.traffic
    a = cell.adapter.make_rows(cfg, traffic, 1)
    b = cell.adapter.make_rows(cfg, traffic, 2)
    np.testing.assert_array_equal(a["attention_mask"], b["attention_mask"])
    assert (a["input_ids"] != b["input_ids"]).any()  # the seed moves content only
    n_instr, D = cfg["instruction_tokens"], cfg["sem_id_dim"]
    for r in range(len(a["input_ids"])):
        real = np.flatnonzero(a["attention_mask"][r])
        assert real[-1] == traffic["row_len"] - 1  # padded at the left
        assert (len(real) - n_instr) % D == 0
        row = a["input_ids"][r, real]
        assert (row[:n_instr] < cfg["base_vocab"]).all()
        codes = row[n_instr:].reshape(-1, D) - cfg["base_vocab"]
        np.testing.assert_array_equal(codes // cfg["codebook_size"],
                                      np.tile(np.arange(D), (len(codes), 1)))
        lab = a["labels"][r, real]
        assert (lab[:n_instr + D] == -100).all()  # instruction and first item
        np.testing.assert_array_equal(lab[n_instr + D:], row[n_instr + D:])


def test_step_counters_reach_the_span_and_the_readers(cell):
    from genrec_tpu.obs.spans import SpanTracer

    tracer = SpanTracer(capacity=1000, enabled=True)
    entry = cell.adapter.build_train(cell.config, cell.traffic, 5, 1, tracer=tracer)
    entry.run_epoch(max_steps=2)
    steps = [s for s in tracer.spans() if s.name == "train_step"]
    assert len(steps) == 2
    ctx = {"kind": "train", "spans": list(tracer.spans())}
    got = {m: cell.spec.cell(CELL).metric_reader(m + ".train")(ctx)
           for m in ("expert_load_max_over_mean", "expert_picks_here_share",
                     "sparse_keys_kept_share")}
    assert got["expert_picks_here_share"] == pytest.approx(100.0)  # all 16 held
    assert got["expert_load_max_over_mean"] >= 1.0
    assert 0 < got["sparse_keys_kept_share"] < 100.0  # the selection bites
    # a program without the counters (the parent): nothing to read, no error
    bare = {"kind": "train", "spans": [s for s in tracer.spans()
                                       if s.name != "train_step"]}
    for m in got:
        assert cell.metric_reader(m + ".train")(bare) is None
