"""Chaos suite for the step-granular fault-tolerance layer.

The trainer-level chaos tests (SIGTERM a real sasrec/hstu/tiger/rqvae/
cobra run, resume, assert parity) are @slow: scripts/ci_checks.sh runs
the FULL suite (smoke mode runs the @chaos_unit subset); the tier-1
'not slow' pass keeps the unit layer + the real-loop NaN path.

Covers, end to end on the CPU virtual mesh:

- exact mid-epoch resume: SIGTERM injected at an arbitrary step of ANY
  of the seven trainers (packed sasrec/hstu/tiger AND the converted
  cobra/lcrec/notellm/rqvae), then resume — per-step losses and final
  params match an uninterrupted run (no replayed or skipped batches).
  cobra/lcrec killed DURING THEIR FINAL EPOCH resume exactly too — the
  old epoch-granular path saved nothing there (a hole this file used to
  pin as documented; now pinned as CLOSED);
- the checkpoint integrity ladder: truncated/garbled/uncommitted/NaN
  checkpoint dirs are quarantined and restore falls back to the previous
  retained step, both at the manager level and through a real trainer;
- the jitted non-finite step guard + host NonFiniteMonitor: NaN batches
  skip the optimizer update without corrupting params/opt_state, dump
  the offending batch, and abort after N consecutive bad steps;
- the epoch-keyed `maybe_resume` arithmetic, kept ONLY for restoring
  pre-PR4 bare-TrainState records (no trainer calls it anymore —
  scripts/ci_checks.sh enforces the no-import rule).

The multi-host halves of this layer (consensus restore, coordinated
commit, per-host fault injection) live in tests/test_multihost.py — they
need real jax.distributed processes.
"""

import json
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from genrec_tpu.core import chaos
from genrec_tpu.core.checkpoint import (
    CheckpointCorruptError,
    CheckpointManager,
    CheckpointMismatchError,
    maybe_resume,
)
from genrec_tpu.core.fault_tolerance import (
    NonFiniteLossError,
    NonFiniteMonitor,
    restore_for_eval,
    resume_exact,
    save_resume_point,
)
from genrec_tpu.core.harness import make_train_step
from genrec_tpu.core.state import TrainState


# ---------------------------------------------------------------------------
# toy model: float batches so NaN injection can reach the loss
# ---------------------------------------------------------------------------


def _toy_setup(seed=0, lr=1e-2):
    def loss_fn(params, batch, rng):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2), {}

    params = {"w": jax.random.normal(jax.random.key(seed), (4, 2))}
    opt = optax.adam(lr)
    state = TrainState.create(params, opt, jax.random.key(seed + 1))
    return loss_fn, opt, state


def _toy_batch(rng, n=8):
    x = rng.standard_normal((n, 4)).astype(np.float32)
    y = rng.standard_normal((n, 2)).astype(np.float32)
    return {"x": x, "y": y}


def _tree_equal(a, b):
    jax.tree_util.tree_map(
        lambda u, v: np.testing.assert_array_equal(np.asarray(u), np.asarray(v)),
        a, b,
    )


# ---------------------------------------------------------------------------
# jitted non-finite guard (core.harness)
# ---------------------------------------------------------------------------


@pytest.mark.chaos_unit
def test_nonfinite_guard_skips_update_and_counts():
    loss_fn, opt, state = _toy_setup()
    step = jax.jit(make_train_step(loss_fn, opt, clip_norm=1.0))
    rng = np.random.default_rng(0)
    good = _toy_batch(rng)
    bad = {k: np.full_like(v, np.nan) for k, v in good.items()}

    state1, m1 = step(state, good)
    assert float(m1["nonfinite"]) == 0.0 and int(state1.step) == 1
    assert int(state1.nonfinite_count) == 0

    # NaN batch: params/opt_state/step pass through UNCHANGED.
    state2, m2 = step(state1, bad)
    assert float(m2["nonfinite"]) == 1.0
    assert int(state2.step) == 1
    assert int(state2.nonfinite_count) == 1
    _tree_equal(state2.params, state1.params)
    _tree_equal(state2.opt_state, state1.opt_state)

    # Streak grows on consecutive bad steps, resets on a finite one.
    state3, m3 = step(state2, bad)
    assert int(state3.nonfinite_count) == 2
    state4, m4 = step(state3, good)
    assert int(state4.nonfinite_count) == 0 and int(state4.step) == 2
    assert np.all(np.isfinite(np.asarray(state4.params["w"])))


@pytest.mark.chaos_unit
def test_nonfinite_guard_finite_path_is_identity():
    """With finite batches, guard on == guard off, bit for bit."""
    loss_fn, opt, state = _toy_setup()
    on = jax.jit(make_train_step(loss_fn, opt, clip_norm=1.0, skip_nonfinite=True))
    off = jax.jit(make_train_step(loss_fn, opt, clip_norm=1.0, skip_nonfinite=False))
    rng = np.random.default_rng(1)
    sa, sb = state, state
    for _ in range(3):
        b = _toy_batch(rng)
        sa, ma = on(sa, b)
        sb, mb = off(sb, b)
    _tree_equal(sa.params, sb.params)
    assert float(ma["loss"]) == float(mb["loss"])


@pytest.mark.chaos_unit
def test_nonfinite_monitor_dumps_and_aborts(tmp_path):
    mon = NonFiniteMonitor(str(tmp_path / "dumps"), max_consecutive=2)
    batch = {"x": np.ones((2, 2), np.float32)}

    def metrics(flag, streak):
        return {
            "loss": np.float32("nan") if flag else np.float32(1.0),
            "grad_norm": np.float32(1.0),
            "nonfinite": np.float32(flag),
            "nonfinite_count": np.float32(streak),
        }

    mon.observe(1, 0, metrics(0, 0), batch)
    mon.observe(2, 0, metrics(1, 1), batch)  # checks step 1: fine
    # Checking step 2 (deferred): dump, streak 1 < 2 -> no abort.
    mon.observe(3, 0, metrics(1, 2), batch)
    assert len(mon.dumped) == 1
    dump = np.load(mon.dumped[0])
    assert int(dump["global_step"]) == 2
    assert dump["batch/x"].shape == (2, 2)
    # Step 3 hits the threshold.
    with pytest.raises(NonFiniteLossError):
        mon.flush()


def test_packed_loop_nan_injection_skips_and_aborts(tmp_path):
    """NaN batches through the REAL loop helper: chaos poisons the host
    batch, the jitted guard skips, the monitor dumps and finally aborts."""
    from genrec_tpu.core.logging import Tracker, setup_logger
    from genrec_tpu.core.profiling import ProfileWindow
    from genrec_tpu.parallel import get_mesh, replicate
    from genrec_tpu.trainers.packed_loop import PackedTrainLoop

    loss_fn, opt, state = _toy_setup()
    mesh = get_mesh()
    state = replicate(mesh, state)
    step_fn = jax.jit(make_train_step(loss_fn, opt, clip_norm=1.0))
    rng = np.random.default_rng(0)
    arrays = {
        "x": rng.standard_normal((64, 4)).astype(np.float32),
        "y": rng.standard_normal((64, 2)).astype(np.float32),
    }
    logger = setup_logger(None)

    def make_loop():
        return PackedTrainLoop(
            logger=logger, tracker=Tracker(), prof=ProfileWindow("", 0),
            mesh=mesh, guard=None, ckpt=None,
            rows_per_step=8, row_len=1, seed=0, pack_sequences=False,
            train_arrays=arrays, wandb_log_interval=1000,
            save_dir_root=str(tmp_path),
            max_consecutive_nonfinite=3,
        )

    # One poisoned step: skipped + dumped, the epoch completes, and the
    # final params are FINITE (the NaN never touched them).
    loop = make_loop()
    with chaos.inject(chaos.ChaosPlan(nan_at_steps=frozenset({3}))):
        res = loop.run_epoch(state, step_fn, epoch=0, global_step=0)
    assert not res.preempted and res.n_batches == 8
    assert np.all(np.isfinite(np.asarray(res.state.params["w"])))
    assert int(res.state.step) == 7  # 8 batches, 1 skipped
    assert len(loop.monitor.dumped) == 1
    assert "batch/x" in np.load(loop.monitor.dumped[0])

    # Three consecutive poisoned steps: abort.
    loop = make_loop()
    with chaos.inject(chaos.ChaosPlan(nan_at_steps=frozenset({2, 3, 4}))):
        with pytest.raises(NonFiniteLossError):
            loop.run_epoch(state, step_fn, epoch=0, global_step=0)


# ---------------------------------------------------------------------------
# checkpoint integrity ladder (core.checkpoint)
# ---------------------------------------------------------------------------


def _dict_state(v: float):
    return {"w": np.full((8, 8), v, np.float32),
            "step": np.asarray(int(v), np.int32)}


@pytest.mark.chaos_unit
@pytest.mark.parametrize("damage", ["truncate", "garble", "marker"])
def test_integrity_ladder_falls_back(tmp_path, damage):
    d = str(tmp_path / "ck")
    mgr = CheckpointManager(d, max_to_keep=3)
    for s in (1, 2, 3):
        mgr.save(s, _dict_state(float(s)))
    mgr.wait()
    {
        "truncate": chaos.truncate_checkpoint,
        "garble": chaos.garble_checkpoint,
        "marker": lambda dd, ss: chaos.drop_commit_marker(dd, ss),
    }[damage](d, 3)
    restored, step = mgr.restore_latest_valid(_dict_state(0.0))
    assert step == 2
    assert float(restored["w"][0, 0]) == 2.0
    # The damaged step is quarantined, not retried forever.
    assert os.path.isdir(os.path.join(d, "quarantine"))
    assert 3 not in mgr.all_steps()
    mgr.close()


def _fake_fleet(monkeypatch, peer_votes):
    """Stand in for a 2-process fleet: this process is p0, the peer's
    answers to the consensus pass's two allgathers (newest-valid step,
    then ok flag) are scripted. The real 2-process collectives are
    covered by tests/test_multihost.py; the DECISIONS are pinned here."""
    from genrec_tpu.core import checkpoint
    from genrec_tpu.parallel import mesh

    class TwoProcessJax:
        """`jax` as core.checkpoint sees it on a 2-process fleet — only
        that module's view: orbax keeps reading the real one."""

        process_count = staticmethod(lambda: 2)

        def __getattr__(self, name):
            return getattr(jax, name)

    votes = iter(peer_votes)
    monkeypatch.setattr(checkpoint, "jax", TwoProcessJax())
    monkeypatch.setattr(
        mesh, "allgather_host_ints",
        lambda mine: np.asarray([list(mine), [next(votes)]], np.int64),
    )
    monkeypatch.setattr(mesh, "barrier", lambda name="barrier": None)


@pytest.mark.chaos_unit
def test_consensus_restore_falls_back_to_the_fleet_minimum(tmp_path, monkeypatch):
    """The peer's newest step is damaged, so its newest-valid is 1 while
    ours is 2: both must restore step 1 — never a forked restore — and our
    locally-valid step 2, abandoned by that decision, leaves discovery
    (retained, orbax would drop every later save keyed below it)."""
    d = str(tmp_path / "ck")
    mgr = CheckpointManager(d, max_to_keep=3)
    for s in (1, 2):
        mgr.save(s, _dict_state(float(s)))
    mgr.wait()
    _fake_fleet(monkeypatch, peer_votes=[1, 1])  # peer: step 1, then ok
    restored, step = mgr.restore_latest_valid_consensus(_dict_state(0.0))
    assert step == 1 and float(restored["w"][0, 0]) == 1.0
    assert mgr.all_steps() == [1]
    assert os.path.isdir(os.path.join(d, "quarantine", "p0", "2"))
    mgr.close()


@pytest.mark.chaos_unit
def test_consensus_restore_refuses_a_valid_vs_nothing_fleet(tmp_path, monkeypatch):
    d = str(tmp_path / "ck")
    mgr = CheckpointManager(d, max_to_keep=3)
    mgr.save(1, _dict_state(1.0))
    mgr.wait()
    _fake_fleet(monkeypatch, peer_votes=[-1])  # peer has nothing valid
    with pytest.raises(RuntimeError, match="p0=1, p1=none"):
        mgr.restore_latest_valid_consensus(_dict_state(0.0))
    mgr.close()


@pytest.mark.chaos_unit
def test_integrity_ladder_rejects_nonfinite_and_mismatch(tmp_path):
    d = str(tmp_path / "ck")
    mgr = CheckpointManager(d, max_to_keep=4)
    mgr.save(1, _dict_state(1.0))
    bad = _dict_state(2.0)
    bad["w"][3, 3] = np.nan
    mgr.save(2, bad)
    mgr.wait()
    with pytest.raises(CheckpointCorruptError, match="non-finite"):
        mgr.validate_and_restore(_dict_state(0.0), 2)
    restored, step = mgr.restore_latest_valid(_dict_state(0.0))
    assert step == 1

    # Structure mismatch (a READABLE record from another layout) fails
    # the rung too, but is skipped in place rather than quarantined —
    # a rollback could still use it.
    mgr.save(5, {"other": np.zeros((2,), np.float32)})
    mgr.wait()
    with pytest.raises(CheckpointMismatchError):
        mgr.validate_and_restore(_dict_state(0.0), 5)
    restored, step = mgr.restore_latest_valid(_dict_state(0.0))
    assert step == 1  # fell through the mismatched step 5 and bad step 2
    assert 5 in mgr.all_steps()  # mismatched record left on disk
    assert not os.path.exists(
        os.path.join(d, "quarantine", "5")
    )
    mgr.close()


@pytest.mark.chaos_unit
def test_ladder_nothing_valid(tmp_path):
    d = str(tmp_path / "ck")
    mgr = CheckpointManager(d, max_to_keep=2)
    mgr.save(1, _dict_state(1.0))
    mgr.wait()
    chaos.garble_checkpoint(d, 1)
    restored, step = mgr.restore_latest_valid(_dict_state(0.0))
    assert restored is None and step is None
    mgr.close()


@pytest.mark.chaos_unit
def test_resume_exact_roundtrip_and_seed_check(tmp_path):
    _, opt, state = _toy_setup()
    mgr = CheckpointManager(str(tmp_path / "ck"))
    save_resume_point(mgr, state, epoch=2, next_batch=5, global_step=17,
                      data_seed=7, wait=True)
    point = resume_exact(mgr, state, data_seed=7)
    assert (point.epoch, point.next_batch, point.global_step) == (2, 5, 17)
    _tree_equal(point.state.params, state.params)
    # A different data seed would silently break exactness: refuse it.
    with pytest.raises(ValueError, match="data seed"):
        resume_exact(mgr, state, data_seed=8)
    mgr.close()


@pytest.mark.chaos_unit
def test_restore_for_eval_skips_exactness_preconditions(tmp_path):
    """A pure evaluation consumes no training data, so the exact-resume
    preconditions must not refuse it: a resume point written with a
    DIFFERENT data seed restores fine, a stale foreign record above the
    restore point is ignored, and a pre-PR4 bare TrainState record (no
    cursor) still evaluates via the legacy-layout fallback."""
    from genrec_tpu.core import fault_tolerance as ft

    _, opt, state = _toy_setup()

    # Seed mismatch + foreign record above: both refuse resume_exact but
    # must not refuse evaluation.
    mgr = CheckpointManager(str(tmp_path / "ck"), max_to_keep=4)
    save_resume_point(mgr, state, epoch=2, next_batch=5, global_step=17,
                      data_seed=7, wait=True)
    mgr.save(20, {
        "state": state,
        "cursor": dict(ft._cursor_arrays(3, 0, 20, 7, 0),
                       format=np.asarray(99, np.int32)),
    })
    mgr.wait()
    with pytest.raises(RuntimeError, match="Refusing to resume below"):
        resume_exact(mgr, state, data_seed=8)
    got, step = restore_for_eval(mgr, state)
    assert step == 17
    _tree_equal(got.params, state.params)
    mgr.close()

    # Pre-PR4 bare TrainState record: the composite ladder mismatches
    # everything, the bare fallback restores it.
    mgr = CheckpointManager(str(tmp_path / "bare"))
    mgr.save(3, state)
    mgr.wait()
    got, step = restore_for_eval(mgr, state)
    assert step == 3
    _tree_equal(got.params, state.params)
    mgr.close()

    # Nothing on disk: the initial state comes back with step None.
    mgr = CheckpointManager(str(tmp_path / "empty"))
    got, step = restore_for_eval(mgr, state)
    assert step is None and got is state
    mgr.close()


@pytest.mark.chaos_unit
def test_resume_with_foreign_records(tmp_path):
    """Foreign-format records BELOW the restore point are harmlessly left
    on disk; foreign records ABOVE it refuse the resume loudly — orbax
    silently drops saves keyed below its retained latest, so continuing
    would checkpoint nothing."""
    from genrec_tpu.core import fault_tolerance as ft

    def foreign_record(state, global_step):
        return {
            "state": state,
            "cursor": dict(
                ft._cursor_arrays(3, 0, global_step, 0, 0),
                format=np.asarray(99, np.int32),
            ),
        }

    _, opt, state = _toy_setup()
    # Foreign BELOW the valid resume point: harmless, resume proceeds.
    mgr = CheckpointManager(str(tmp_path / "below"), max_to_keep=4)
    mgr.save(2, foreign_record(state, 2))
    mgr.wait()
    save_resume_point(mgr, state, epoch=1, next_batch=2, global_step=5,
                      data_seed=0, wait=True)
    point = resume_exact(mgr, state, data_seed=0)
    assert (point.epoch, point.next_batch, point.global_step) == (1, 2, 5)
    assert 2 in mgr.all_steps()  # foreign record left on disk
    mgr.save(6, {"state": point.state, "cursor": ft._cursor_arrays(1, 3, 6, 0, 0)})
    mgr.close()

    # Foreign ABOVE the valid resume point: loud refusal.
    mgr = CheckpointManager(str(tmp_path / "above"), max_to_keep=4)
    save_resume_point(mgr, state, epoch=1, next_batch=2, global_step=5,
                      data_seed=0, wait=True)
    mgr.save(9, foreign_record(state, 9))
    mgr.wait()
    with pytest.raises(RuntimeError, match="Refusing to resume below"):
        resume_exact(mgr, state, data_seed=0)
    mgr.close()


@pytest.mark.chaos_unit
def test_fresh_start_over_stale_records_is_refused(tmp_path):
    """Nothing restorable but readable foreign records retained: orbax
    silently refuses saves keyed below the stale latest step, so a fresh
    start here would checkpoint NOTHING — both resume paths must fail
    loudly instead, and a refused save must raise, not silently no-op."""
    _, opt, state = _toy_setup()
    d = str(tmp_path / "ck")
    mgr = CheckpointManager(d)
    mgr.save(5000, {"other_layout": np.zeros((2,), np.float32)})
    mgr.wait()
    with pytest.raises(RuntimeError, match="Refusing to start fresh"):
        resume_exact(mgr, state, data_seed=0)
    with pytest.raises(RuntimeError, match="Refusing to start fresh"):
        maybe_resume(mgr, state)
    # The last line of defense: a save orbax refuses (key below the
    # stale latest) raises instead of silently dropping the checkpoint.
    with pytest.raises(RuntimeError, match="refused to save"):
        mgr.save(7, {"other_layout": np.zeros((2,), np.float32)})
    mgr.close()


def test_best_tracker_corrupt_sidecar_recovers(tmp_path):
    from genrec_tpu.core.checkpoint import BestTracker

    p = {"w": np.ones((2, 2), np.float32)}
    t = BestTracker(str(tmp_path))
    assert t.update(0.5, p)
    # Crash mid-write (pre-atomic format): truncated json on disk.
    with open(t.meta, "w") as f:
        f.write('{"metric": "Recall@10", "va')
    t2 = BestTracker(str(tmp_path))  # must not raise
    assert t2.value == -1.0
    # Valid JSON of the wrong shape (list / null value) must recover too.
    for garbage in ('[1]', '{"value": null}'):
        with open(t.meta, "w") as f:
            f.write(garbage)
        assert BestTracker(str(tmp_path)).value == -1.0
    t2 = BestTracker(str(tmp_path))
    assert t2.update(0.3, p)  # tracking restarts and re-saves
    assert json.load(open(t2.meta))["value"] == 0.3


# ---------------------------------------------------------------------------
# PreemptionGuard satellites
# ---------------------------------------------------------------------------


@pytest.mark.chaos_unit
def test_guard_latches_sigterm_and_sigint_and_restores_handlers():
    from genrec_tpu.core.preemption import PreemptionGuard

    prev_term = signal.getsignal(signal.SIGTERM)
    prev_int = signal.getsignal(signal.SIGINT)
    for sig in (signal.SIGTERM, signal.SIGINT):
        guard = PreemptionGuard()
        assert not guard.fired
        os.kill(os.getpid(), sig)
        assert guard.fired
        # One-shot latch: the FIRST signal already restored the previous
        # handlers, so a second ^C/SIGTERM can always escalate (no
        # SIGKILL-only hangs, no permanently swallowed ^C after aborts).
        assert signal.getsignal(signal.SIGTERM) is prev_term
        assert signal.getsignal(signal.SIGINT) is prev_int
        guard.close()  # idempotent after the fire
    assert signal.getsignal(signal.SIGTERM) is prev_term
    assert signal.getsignal(signal.SIGINT) is prev_int


# ---------------------------------------------------------------------------
# chaos primitives
# ---------------------------------------------------------------------------


@pytest.mark.chaos_unit
def test_poison_batches_targets_float_leaves_only():
    batches = [({"ids": np.arange(4), "x": np.ones(4, np.float32)},
                np.ones(4, bool)) for _ in range(3)]
    with chaos.inject(chaos.ChaosPlan(nan_at_steps=frozenset({2}))):
        out = list(chaos.poison_batches(iter(batches), start_step=0))
    assert np.all(np.isfinite(out[0][0]["x"]))
    assert np.all(np.isnan(out[1][0]["x"]))  # global step 2
    np.testing.assert_array_equal(out[1][0]["ids"], np.arange(4))  # ints untouched
    assert np.all(np.isfinite(out[2][0]["x"]))


# ---------------------------------------------------------------------------
# exact mid-epoch resume parity through the real trainers
# ---------------------------------------------------------------------------


def _losses_by_step(save_dir, loss_key="train/loss"):
    """metrics.jsonl loss entries keyed by global step (the resumed
    run APPENDS to the same file; a step may appear at most once).
    ``loss_key`` follows the trainer's step_log payload (rqvae logs
    ``total_loss``)."""
    out = {}
    with open(os.path.join(save_dir, "metrics.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            if loss_key in rec and "global_step" in rec:
                step = int(rec["global_step"])
                assert step not in out, f"step {step} logged twice (replayed batch)"
                out[step] = rec[loss_key]
    return out


def _load_final_resume_point(save_dir):
    import orbax.checkpoint as ocp

    ckdir = os.path.join(save_dir, "checkpoints")
    steps = [int(s) for s in os.listdir(ckdir) if s.isdigit()]
    step = max(steps)
    raw = ocp.StandardCheckpointer().restore(
        os.path.join(ckdir, str(step), "default")
    )
    return step, raw


def _assert_parity(dir_a, dir_b, loss_key="train/loss"):
    """Same per-step losses (no replay/skip) and identical final params."""
    la = _losses_by_step(dir_a, loss_key)
    lb = _losses_by_step(dir_b, loss_key)
    assert sorted(la) == sorted(lb), "replayed or skipped batches"
    for s in la:
        assert la[s] == pytest.approx(lb[s], abs=1e-5), f"loss diverged at step {s}"
    step_a, fin_a = _load_final_resume_point(dir_a)
    step_b, fin_b = _load_final_resume_point(dir_b)
    assert step_a == step_b
    jax.tree_util.tree_map(
        lambda u, v: np.testing.assert_allclose(
            np.asarray(u, np.float64), np.asarray(v, np.float64), atol=1e-5
        ),
        fin_a["state"]["params"], fin_b["state"]["params"],
    )


_SASREC_CFG = dict(
    epochs=2, batch_size=32, max_seq_len=32, embed_dim=16, num_heads=2,
    num_blocks=1, ffn_dim=32, dropout=0.1, dataset="synthetic",
    do_eval=False, save_every_epoch=1, wandb_log_interval=1,
    amp=False, use_fused_ce=False, pack_sequences=True, seed=0,
)


def _run_interrupted_and_resume(train, cfg, tmp_path, kill_at_step,
                                preempt_rv=({}, {})):
    """(uninterrupted_dir, interrupted+resumed_dir) for _assert_parity.
    ``preempt_rv`` is the trainer's preempted-exit return value (None to
    skip the check for trainers whose return holds arrays)."""
    dir_a = str(tmp_path / "uninterrupted")
    train(**cfg, save_dir_root=dir_a)

    dir_b = str(tmp_path / "interrupted")
    with chaos.inject(chaos.ChaosPlan(kill_at_step=kill_at_step)):
        out = train(**cfg, save_dir_root=dir_b)
    if preempt_rv is not None:
        assert out == preempt_rv  # preempted exit
    # The mid-epoch resume point exists and sits at the kill step.
    ckdir = os.path.join(dir_b, "checkpoints")
    assert kill_at_step in [int(s) for s in os.listdir(ckdir) if s.isdigit()]
    train(**cfg, save_dir_root=dir_b, resume_from_checkpoint=True)
    return dir_a, dir_b


@pytest.mark.slow
def test_sasrec_exact_resume_after_midepoch_sigterm(tmp_path):
    from genrec_tpu.trainers.sasrec_trainer import train

    # 7 steps/epoch at this scale: step 3 is mid-epoch 0 — the regime the
    # old epoch-granular guard lost entirely.
    dir_a, dir_b = _run_interrupted_and_resume(train, _SASREC_CFG, tmp_path, 3)
    _assert_parity(dir_a, dir_b)


@pytest.mark.slow
def test_hstu_exact_resume_after_midepoch_sigterm(tmp_path):
    from genrec_tpu.trainers.hstu_trainer import train

    cfg = dict(
        epochs=2, batch_size=32, max_seq_len=32, embed_dim=16, num_heads=2,
        num_blocks=1, dropout=0.1, dataset="synthetic", do_eval=False,
        save_every_epoch=1, wandb_log_interval=1, amp=False,
        use_pallas=False, use_fused_ce=False, pack_sequences=True, seed=0,
    )
    # Kill inside epoch 1 so the resume also crosses a repack boundary.
    dir_a, dir_b = _run_interrupted_and_resume(train, cfg, tmp_path, 9)
    _assert_parity(dir_a, dir_b)


@pytest.mark.slow
def test_tiger_exact_resume_after_midepoch_sigterm(tmp_path):
    from genrec_tpu.trainers.tiger_trainer import train

    cfg = dict(
        epochs=2, batch_size=16, learning_rate=1e-3, num_warmup_steps=5,
        embedding_dim=16, attn_dim=32, num_heads=4, n_layers=2,
        sem_id_dim=2, codebook_size=16, max_items=4, num_users=40,
        num_user_embeddings=64, dataset="synthetic", do_eval=False,
        save_every_epoch=1, wandb_log_interval=1, amp=False,
        pack_sequences=True, seed=0,
    )
    dir_a, dir_b = _run_interrupted_and_resume(train, cfg, tmp_path, 4)
    _assert_parity(dir_a, dir_b)


@pytest.mark.slow
def test_sasrec_resume_survives_corrupt_latest(tmp_path):
    """Trainer-level ladder: garble the newest resume point — resume
    falls back to an older retained step and still completes."""
    from genrec_tpu.trainers.sasrec_trainer import train

    d = str(tmp_path / "run")
    with chaos.inject(chaos.ChaosPlan(kill_at_step=10)):
        train(**_SASREC_CFG, save_dir_root=d)
    ckdir = os.path.join(d, "checkpoints")
    steps = sorted(int(s) for s in os.listdir(ckdir) if s.isdigit())
    assert len(steps) >= 2  # epoch-0 boundary save + the preempt save
    chaos.garble_checkpoint(ckdir, steps[-1])
    vm, tm = train(**_SASREC_CFG, save_dir_root=d, resume_from_checkpoint=True)
    assert steps[-1] not in [
        int(s) for s in os.listdir(ckdir) if s.isdigit()
    ]
    assert os.path.isdir(os.path.join(ckdir, "quarantine"))
    _, fin = _load_final_resume_point(d)
    leaves = jax.tree_util.tree_leaves(fin["state"]["params"])
    assert all(np.all(np.isfinite(np.asarray(l))) for l in leaves)


# ---------------------------------------------------------------------------
# legacy epoch-keyed maybe_resume arithmetic (pre-PR4 records only)
# ---------------------------------------------------------------------------


@pytest.mark.chaos_unit
def test_maybe_resume_epoch_arithmetic(tmp_path):
    _, opt, state = _toy_setup()
    mgr = CheckpointManager(str(tmp_path / "ck"))
    # Nothing saved: fresh start.
    assert maybe_resume(mgr, state)[1:] == (0, 0)
    # Epoch-keyed save(e) resumes at start_epoch e+1.
    stepped = state.replace(step=jnp.asarray(42, jnp.int32))
    mgr.save(4, stepped)
    mgr.wait()
    restored, start_epoch, global_step = maybe_resume(mgr, state)
    assert (start_epoch, global_step) == (5, 42)
    # Ladder inside maybe_resume: corrupt latest falls back.
    mgr.save(7, stepped.replace(step=jnp.asarray(99, jnp.int32)))
    mgr.wait()
    chaos.garble_checkpoint(str(tmp_path / "ck"), 7)
    restored, start_epoch, global_step = maybe_resume(mgr, state)
    assert (start_epoch, global_step) == (5, 42)
    mgr.close()


# ---------------------------------------------------------------------------
# exact resume for the converted epoch-trainers (cobra/lcrec/notellm/rqvae)
# ---------------------------------------------------------------------------


_RQVAE_CFG = dict(
    epochs=3, batch_size=64, learning_rate=1e-3,
    vae_input_dim=16, vae_hidden_dims=(16,), vae_embed_dim=4,
    vae_codebook_size=8, vae_n_layers=2, kmeans_warmup_rows=64,
    dataset="synthetic", do_eval=False, eval_every=100,
    wandb_log_interval=1, seed=0,
)


@pytest.mark.slow
def test_rqvae_exact_resume_after_midepoch_sigterm(tmp_path):
    """rqvae through the shared step-granular loop: SIGTERM mid-epoch 1
    writes a resume point at the exact kill step; the resumed run matches
    an uninterrupted one per-step (rqvae logs ``total_loss``)."""
    from genrec_tpu.trainers.rqvae_trainer import train

    # ~28 steps/epoch at this scale: step 40 is mid-epoch 1.
    dir_a, dir_b = _run_interrupted_and_resume(
        train, _RQVAE_CFG, tmp_path, 40, preempt_rv=None
    )
    _assert_parity(dir_a, dir_b, loss_key="total_loss")


def _tiny_cobra_cfg():
    from genrec_tpu.data.cobra_seq import CobraSeqData
    from genrec_tpu.data.sem_ids import random_unique_sem_ids

    rng = np.random.default_rng(0)
    n_items, C, K = 24, 3, 8
    sem_ids = random_unique_sem_ids(n_items, K, C, rng)
    texts = np.zeros((n_items, 6), np.int32)
    texts[:, :4] = rng.integers(2, 64, (n_items, 4))
    seqs = [
        np.asarray(rng.integers(1, n_items + 1, rng.integers(5, 9)), np.int64)
        for _ in range(48)
    ]
    return dict(
        dataset=lambda: CobraSeqData(
            seqs, sem_ids, texts, id_vocab_size=K, max_items=6
        ),
        epochs=1, batch_size=8, learning_rate=1e-3, num_warmup_steps=2,
        encoder_n_layers=1, encoder_hidden_dim=16, encoder_num_heads=2,
        encoder_vocab_size=64, d_model=16, decoder_n_layers=1,
        decoder_num_heads=2, max_items=6, n_beam=4, do_eval=False,
        save_every_epoch=50, test_on_best=False, wandb_log_interval=1,
        seed=0,
    )


@pytest.mark.slow
def test_cobra_final_epoch_sigterm_resumes_exactly(tmp_path):
    """The pinned hole, CLOSED: the old epoch-granular cobra wrote NO
    checkpoint when signalled during the final epoch with a
    save_every_epoch cadence that never fires (this file used to pin
    `latest_step() is None` for exactly this setup). Through the shared
    step-granular loop, the same kill leaves a mid-final-epoch resume
    point and the resumed run matches the uninterrupted one exactly."""
    from genrec_tpu.trainers.cobra_trainer import train

    # epochs=1: every step is inside the final epoch; 6 steps/epoch.
    dir_a, dir_b = _run_interrupted_and_resume(
        train, _tiny_cobra_cfg(), tmp_path, 3
    )
    _assert_parity(dir_a, dir_b)


@pytest.mark.slow
def test_lcrec_final_epoch_sigterm_resumes_exactly(tmp_path):
    """lcrec killed DURING ITS FINAL epoch (the other half of the pinned
    cobra/lcrec hole) resumes step-exactly."""
    from genrec_tpu.trainers.lcrec_trainer import train

    cfg = dict(
        epochs=2, batch_size=16, eval_every_epoch=10, do_eval=False,
        eval_batch_size=16, hidden_size=32, intermediate_size=64,
        n_layers=1, num_heads=2, num_kv_heads=2, max_text_len=64,
        eval_item_tasks=False, save_every_epoch=1, wandb_log_interval=1,
        seed=0,
    )
    dir_a = str(tmp_path / "uninterrupted")
    train(**cfg, save_dir_root=dir_a)
    # Pick a kill step inside the FINAL epoch from the uninterrupted
    # run's step count (synthetic task mix size is a data detail).
    n = max(_losses_by_step(dir_a))
    kill = n // 2 + max(1, n // 4)
    dir_b = str(tmp_path / "interrupted")
    with chaos.inject(chaos.ChaosPlan(kill_at_step=kill)):
        out = train(**cfg, save_dir_root=dir_b)
    assert out == ({}, {})
    ckdir = os.path.join(dir_b, "checkpoints")
    assert kill in [int(s) for s in os.listdir(ckdir) if s.isdigit()]
    train(**cfg, save_dir_root=dir_b, resume_from_checkpoint=True)
    _assert_parity(dir_a, dir_b)


@pytest.mark.slow
def test_notellm_exact_resume_after_midepoch_sigterm(tmp_path):
    from genrec_tpu.trainers.notellm_trainer import train

    cfg = dict(
        epochs=2, batch_pairs=16, do_eval=False, eval_every_epoch=10,
        num_topics=32, eval_topics=16, pairs_per_topic=4,
        hidden_size=32, intermediate_size=64, n_layers=1,
        num_heads=2, num_kv_heads=1, save_every_epoch=1,
        wandb_log_interval=1, seed=0,
    )
    # 8 steps/epoch: step 5 is mid-epoch 0.
    dir_a, dir_b = _run_interrupted_and_resume(
        train, cfg, tmp_path, 5, preempt_rv={}
    )
    _assert_parity(dir_a, dir_b)


@pytest.mark.slow
def test_sasrec_between_epoch_sigterm_resumes_exactly(tmp_path):
    """kill_at_epoch fires in the eval/checkpoint window AFTER an epoch
    (the loop's top-of-epoch preemption branch): the next run_epoch call
    writes a (next epoch, batch 0) resume point without running a step,
    and the resumed run still matches exactly."""
    from genrec_tpu.trainers.sasrec_trainer import train

    dir_a = str(tmp_path / "uninterrupted")
    train(**_SASREC_CFG, save_dir_root=dir_a)
    dir_b = str(tmp_path / "interrupted")
    with chaos.inject(chaos.ChaosPlan(kill_at_epoch=0)):
        out = train(**_SASREC_CFG, save_dir_root=dir_b)
    assert out == ({}, {})
    train(**_SASREC_CFG, save_dir_root=dir_b, resume_from_checkpoint=True)
    _assert_parity(dir_a, dir_b)
