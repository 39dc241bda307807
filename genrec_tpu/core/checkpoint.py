"""Checkpointing via orbax: ONE format for every model.

Replaces the reference's three coexisting ad-hoc formats (torch.save dicts,
bare state_dicts, HF save_pretrained dirs — SURVEY.md §5.4) with orbax
PyTree checkpoints. Semantic-id artifacts (the RQ-VAE -> downstream-dataset
interface, amazon.py:296-313) are a separate portable .npz — see
genrec_tpu.data.sem_ids.
"""

from __future__ import annotations

import json
import logging
import os
import shutil
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import orbax.checkpoint as ocp

from genrec_tpu.core import chaos

logger = logging.getLogger("genrec_tpu")


def _flight():
    """Process flight recorder (obs layer): checkpoint saves, ladder
    verdicts and quarantines are exactly the events a post-mortem needs
    in order."""
    from genrec_tpu.obs.flight_recorder import get_flight_recorder

    return get_flight_recorder()


class CheckpointCorruptError(RuntimeError):
    """A checkpoint step failed integrity validation (missing commit
    marker, unreadable/garbled arrays, or non-finite leaves)."""


class CheckpointMismatchError(RuntimeError):
    """A checkpoint step is READABLE but its tree structure does not
    match the live state — e.g. a record written by an older code
    version. The ladder skips these (they are not damaged; a rollback
    could still use them) instead of quarantining."""


def _abs(path: str) -> str:
    return os.path.abspath(path)


def _is_prng_key(x) -> bool:
    return isinstance(x, jax.Array) and jnp.issubdtype(x.dtype, jax.dtypes.prng_key)


def to_savable(tree: Any) -> Any:
    """Checkpoint-ready copy of a pytree.

    Typed PRNG keys become their uint32 data. Fully-addressable arrays are
    materialized as host numpy; arrays sharded across NON-addressable
    devices (multi-host tensor parallelism) are passed through as
    jax.Arrays — orbax writes distributed arrays natively, where
    np.asarray would raise. Restore goes through the trainer's
    place_state, which re-applies the target sharding.
    """

    def conv(x):
        if _is_prng_key(x):
            x = jax.random.key_data(x)
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            return x
        return np.asarray(x)

    return jax.tree_util.tree_map(conv, tree)


def from_savable(saved: Any, like: Any) -> Any:
    """Re-wrap leaves that were PRNG keys in ``like``, preserving the
    like-key's generator (TPU states carry 'rbg' step keys — see
    core.state.fast_step_rng — whose key data is wider than threefry's)."""

    def conv(s, l):
        if _is_prng_key(l):
            return jax.random.wrap_key_data(
                jnp.asarray(s), impl=jax.random.key_impl(l)
            )
        return s

    return jax.tree_util.tree_map(conv, saved, like)


# Shared async checkpointer: StandardCheckpointer subclasses
# AsyncCheckpointer, so save() returns once arrays are snapshotted to host
# and the directory write proceeds on a background thread (a new save
# first waits for the previous one). SURVEY.md §5.3: async checkpointing
# is the explicit exceeds-parity goal here.
_ASYNC_CKPTR: ocp.StandardCheckpointer | None = None


def _async_ckptr() -> ocp.StandardCheckpointer:
    global _ASYNC_CKPTR
    if _ASYNC_CKPTR is None:
        _ASYNC_CKPTR = ocp.StandardCheckpointer()
    return _ASYNC_CKPTR


def wait_for_saves() -> None:
    """Block until every async `save_params(..., wait=False)` has landed."""
    if _ASYNC_CKPTR is not None:
        _ASYNC_CKPTR.wait_until_finished()


def save_params(path: str, params: Any, wait: bool = True) -> None:
    """Save a params pytree. ``wait=False`` returns as soon as the arrays
    are snapshotted (training continues while the write is in flight);
    call `wait_for_saves()` (or save again, or read back) to join."""
    ckptr = _async_ckptr()
    ckptr.save(_abs(path), to_savable(params), force=True)
    if wait:
        ckptr.wait_until_finished()


def load_params(path: str, like: Any | None = None) -> Any:
    ckptr = ocp.StandardCheckpointer()
    if like is not None:
        restored = ckptr.restore(_abs(path), to_savable(like))
        return from_savable(restored, like)
    return ckptr.restore(_abs(path))


def _refuse_resume_below_stale_steps(
    ckpt: "CheckpointManager", resumed_step: int | None
) -> None:
    """Readable foreign records retained ABOVE the restore point (or with
    nothing restorable at all) are a trap: orbax silently refuses saves
    at steps <= the stale latest (`should_save`), so the run would
    checkpoint NOTHING while logging success — every relaunch restores
    the same old step and the work loops forever. Fail loudly instead.

    Foreign records BELOW the restore point are harmless (future saves
    key above them) and stay on disk for rollbacks."""
    stale = [
        s for s in ckpt.all_steps()
        if resumed_step is None or s > resumed_step
    ]
    if stale:
        at = (
            "start fresh on top of them"
            if resumed_step is None
            else f"resume below them (at step {resumed_step})"
        )
        raise RuntimeError(stale_refusal_message(
            ckpt.directory,
            f"steps {stale}: written by a different code version or trainer",
            at,
        ))


def stale_refusal_message(directory: str, what: str, at: str) -> str:
    """The one stale-record refusal narrative, shared by the single-host
    refusal above and the collective multi-host refusal in
    `core.fault_tolerance.resume_exact` — remediation guidance edited in
    only one copy would drift."""
    return (
        f"checkpoint directory {directory} holds records this run "
        f"cannot resume ({what}). Refusing to {at} — orbax would silently "
        "drop every save keyed below the stale latest step. Move or "
        "delete those step dirs (the records are intact; pre-PR4 "
        "epoch-keyed records can still be restored from a script via "
        "genrec_tpu.core.checkpoint.maybe_resume) and relaunch."
    )


def maybe_resume(ckpt: "CheckpointManager | None", state, replicate_fn=None):
    """LEGACY epoch-keyed resume. No trainer uses this anymore — every
    trainer resumes step-exactly through `core.fault_tolerance.resume_exact`
    (scripts/ci_checks.sh enforces the no-import rule). Kept as a
    library-level migration helper for pre-PR4 epoch-keyed checkpoints
    (bare TrainState records): call it from a script to pull the state
    out of an old directory — the trainers themselves refuse such
    directories loudly (see `_refuse_resume_below_stale_steps`).

    Checkpoints are keyed by EPOCH. Returns
    ``(state, start_epoch, global_step)`` — fresh-start values when there
    is nothing (valid) to restore. ``replicate_fn`` re-places the
    restored host arrays on the mesh.

    Restores go through the integrity ladder
    (`CheckpointManager.restore_latest_valid`): a truncated/garbled
    latest step is quarantined with a warning and the previous retained
    step is used instead of crashing the resume.
    """
    if ckpt is None or ckpt.latest_step() is None:
        return state, 0, 0
    restored, step = ckpt.restore_latest_valid(state)
    _refuse_resume_below_stale_steps(ckpt, step)
    if restored is None:
        return state, 0, 0
    if replicate_fn is not None:
        restored = replicate_fn(restored)
    start_epoch = step + 1
    return restored, start_epoch, int(restored.step)


class BestTracker:
    """Best-metric model snapshotting that SURVIVES resume.

    The best params are written to ``<dir>/best_model`` the moment a new
    best appears (not only at exit), with the metric value in a sidecar
    json — so an interrupted run never loses an earlier, better model and
    a resumed run competes against the true best-so-far.
    """

    def __init__(self, save_dir: str | None, metric: str = "Recall@10"):
        self.dir = os.path.join(save_dir, "best_model") if save_dir else None
        self.meta = self.dir + ".json" if self.dir else None
        self.metric = metric
        self.value = -1.0
        if self.meta and os.path.exists(self.meta):
            try:
                with open(self.meta) as f:
                    self.value = float(json.load(f)["value"])
            except (ValueError, KeyError, TypeError, OSError) as e:
                # A sidecar truncated by a crash mid-write (pre-atomic
                # format) must not break resume: forget the best-so-far
                # value — the next improvement re-saves model + sidecar —
                # instead of crashing every future run.
                logger.warning(
                    f"corrupt best-model sidecar {self.meta} ({e}): "
                    "resetting best-metric tracking"
                )
                self.value = -1.0

    def update(self, value: float, params) -> bool:
        if value <= self.value:
            return False
        self.value = value
        if self.dir:
            # Synchronous on purpose: the sidecar must only ever describe
            # a DURABLE best_model dir. An async write here would let a
            # crash leave value=X on disk with no params — a resumed run
            # would then never re-save anything below X and the best model
            # is lost for good. Best-improvements are rare; the epoch-level
            # CheckpointManager saves are the async path.
            save_params(self.dir, params)
            if jax.process_index() == 0:
                # Process-0-only: on a shared filesystem every host sees
                # the same best_model dir; concurrent sidecar writers
                # would race each other's tmp/replace. The orbax save
                # above is still collective (all hosts contribute
                # shards); only the tiny json is single-writer.
                # Atomic replace: a crash mid-write must never leave a
                # truncated json that breaks the next resume's float(...).
                tmp = self.meta + ".tmp"
                with open(tmp, "w") as f:
                    json.dump({"metric": self.metric, "value": value}, f)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, self.meta)
        return True

    def best_params(self, like):
        """Best params seen across ALL runs (disk), or None if none saved."""
        wait_for_saves()
        if self.dir and os.path.exists(self.dir) and self.value > -1.0:
            return load_params(self.dir, like=like)
        return None


# Orbax finalizes a step by renaming its tmp dir and then writing this
# marker. A step dir without it was interrupted mid-commit.
_COMMIT_MARKER = "_CHECKPOINT_METADATA"


class CheckpointManager:
    """Step-numbered training checkpoints with auto-resume.

    Covers (and exceeds — the reference has no auto-resume discovery) the
    `resume_from_checkpoint` flow of tiger_trainer.py:248-256. Restores
    can run through an INTEGRITY LADDER (`restore_latest_valid`): newest
    retained step first, validated as (1) orbax commit marker present,
    (2) arrays readable + tree structure matches the live state, (3) every
    float leaf finite — a step failing any rung is quarantined to
    ``<dir>/quarantine/p<process>/`` (kept for post-mortem, excluded from
    discovery) and the ladder falls through to the previous retained step.

    Multi-host semantics — **coordinated commit** over one shared
    directory: orbax writes every host's shards into the step's tmp dir
    and process 0 finalizes (rename + commit marker) only after an
    ALL-HOST barrier through the distributed coordination service — a
    host dying mid-save can never yield a step that is commit-markered
    for some hosts and absent for others. The barrier is bounded by
    ``commit_timeout_secs`` so a lost host surfaces as an error on the
    survivors instead of a silent hang. Restores on a fleet go through
    `restore_latest_valid_consensus`, which makes every host restore the
    SAME step (or aborts loudly with a per-host validity report).
    """

    def __init__(self, directory: str, max_to_keep: int = 3, *,
                 commit_timeout_secs: int = 300):
        self.directory = _abs(directory)
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                async_options=ocp.options.AsyncOptions(
                    timeout_secs=commit_timeout_secs
                ),
            ),
        )

    def save(self, step: int, state: Any) -> None:
        _flight().record("checkpoint_save", step=step,
                         directory=self.directory)
        saved = self._mgr.save(
            step, args=ocp.args.StandardSave(to_savable(state))
        )
        # Chaos hook: a host lost MID-SAVE (SIGKILL with the directory
        # write still in flight on the background thread). The
        # coordinated-commit guarantee under test: the marker is written
        # by process 0 only after the all-host barrier, so this step must
        # never become restorable anywhere.
        chaos.maybe_die_in_save(step)
        # orbax's should_save REFUSES saves keyed <= the retained latest
        # step, returning False with no error. Re-saving the exact latest
        # key is benign (identical record, e.g. a preemption landing on a
        # just-written epoch boundary); anything else silently dropping a
        # checkpoint is the worst failure mode this layer exists to
        # prevent — surface it.
        if not saved and step != self._mgr.latest_step():
            raise RuntimeError(
                f"orbax refused to save checkpoint step {step} (latest "
                f"retained step is {self._mgr.latest_step()}): stale "
                "higher-numbered records in the directory? The save did "
                "NOT happen."
            )

    def latest_step(self) -> int | None:
        return self._mgr.latest_step()

    def all_steps(self) -> list[int]:
        return sorted(self._mgr.all_steps())

    def wait(self) -> None:
        """Join any in-flight async save (durability barrier)."""
        self._mgr.wait_until_finished()

    def reload(self) -> None:
        """Re-read the step listing from disk. Needed when another host
        sharing the directory may have quarantined steps since this
        manager last scanned (the consensus pass does)."""
        self._mgr.reload()

    def restore(self, state_like: Any, step: int | None = None) -> Any:
        step = step if step is not None else self._mgr.latest_step()
        if step is None:
            return None
        restored = self._mgr.restore(
            step, args=ocp.args.StandardRestore(to_savable(state_like))
        )
        return from_savable(restored, state_like)

    # -- integrity ladder ---------------------------------------------------

    def validate_and_restore(self, state_like: Any, step: int) -> Any:
        """One ladder rung: restore ``step`` or raise
        CheckpointCorruptError (damaged) / CheckpointMismatchError
        (readable but structurally foreign, e.g. written pre-upgrade).

        The finite-leaves rung scans every float leaf once on host —
        O(checkpoint size) reads, which the restore already paid for.
        """
        marker = os.path.join(self.directory, str(step), _COMMIT_MARKER)
        if not os.path.exists(marker):
            raise CheckpointCorruptError(
                f"step {step}: missing orbax commit marker {_COMMIT_MARKER} "
                "(interrupted mid-commit?)"
            )
        try:
            # Raises on unreadable/truncated arrays and on any mismatch
            # between the stored tree and the live state's structure.
            restored = self.restore(state_like, step)
        except Exception as e:
            # Disambiguate "damaged bytes" from "different layout" by the
            # one thing that separates them: can the record be read AS
            # STORED? A target-free restore reads every array with the
            # tree the record itself names — a truncated or garbled step
            # fails it (tensorstore DATA_LOSS), an intact record of a
            # different layout (old format / other trainer) passes, and
            # quarantining that one would destroy a checkpoint a rollback
            # could still use. (orbax's ``metadata()`` cannot tell them
            # apart: on a damaged step it logs a warning and returns.)
            # Only the failure path pays this second read.
            try:
                self._mgr.restore(step, args=ocp.args.StandardRestore())
            except Exception:
                raise CheckpointCorruptError(
                    f"step {step}: unreadable ({e})"
                ) from e
            raise CheckpointMismatchError(
                f"step {step}: readable but tree structure does not match "
                f"the live state ({e})"
            ) from e
        for path, leaf in jax.tree_util.tree_leaves_with_path(
            to_savable(restored)
        ):
            arr = np.asarray(leaf)
            # jnp.issubdtype also covers the ml_dtypes floats (bf16 params)
            # that numpy's own hierarchy does not classify as floating.
            if jnp.issubdtype(arr.dtype, jnp.floating) and not np.all(
                np.isfinite(arr)
            ):
                raise CheckpointCorruptError(
                    f"step {step}: non-finite leaf "
                    f"{jax.tree_util.keystr(path)}"
                )
        return restored

    def quarantine(self, step: int) -> None:
        """Move a corrupt step dir out of discovery, keeping it on disk.

        The destination embeds ``jax.process_index()``: on a shared
        filesystem every host runs the ladder over the same files, so
        concurrent quarantines would otherwise clobber each other's
        post-mortem artifacts. The losing host of a move race finds the
        source already gone — which is fine, the step is out of
        discovery either way."""
        _flight().record("checkpoint_quarantine", step=step,
                         directory=self.directory)
        src = os.path.join(self.directory, str(step))
        qdir = os.path.join(
            self.directory, "quarantine", f"p{jax.process_index()}"
        )
        os.makedirs(qdir, exist_ok=True)
        dst = os.path.join(qdir, str(step))
        n = 0
        while os.path.exists(dst):
            n += 1
            dst = os.path.join(qdir, f"{step}.{n}")
        try:
            if os.path.exists(src):
                shutil.move(src, dst)
        except (FileNotFoundError, shutil.Error) as e:
            logger.warning(
                f"quarantine of step {step} lost a move race ({e}): "
                "another host already moved it"
            )
        self._mgr.reload()  # drop the manager's cached step listing

    def restore_latest_valid(
        self, state_like: Any, extra_validate=None
    ) -> tuple[Any, int] | tuple[None, None]:
        """Walk retained steps newest-first; quarantine every CORRUPT one
        (structure mismatches are skipped in place — see
        CheckpointMismatchError); return ``(restored, step)`` for the
        first valid, or (None, None) when nothing survives.

        ``extra_validate(restored, step)`` lets the caller add a rung
        (e.g. the resume-point format tag) — raise
        CheckpointMismatchError from it to skip that step in place and
        keep walking."""
        for step in sorted(self._mgr.all_steps(), reverse=True):
            try:
                restored = self.validate_and_restore(state_like, step)
                if extra_validate is not None:
                    extra_validate(restored, step)
                _flight().record("integrity_ladder", step=step,
                                 verdict="valid")
                return restored, step
            except CheckpointCorruptError as e:
                logger.warning(
                    f"checkpoint integrity: {e} — quarantining and falling "
                    "back to the previous retained step"
                )
                _flight().record("integrity_ladder", step=step,
                                 verdict="corrupt", error=str(e)[:500])
                self.quarantine(step)
            except CheckpointMismatchError as e:
                logger.warning(
                    f"checkpoint integrity: {e} — leaving it on disk and "
                    "falling back to the previous retained step"
                )
                _flight().record("integrity_ladder", step=step,
                                 verdict="mismatch", error=str(e)[:500])
        _flight().record("integrity_ladder", step=None, verdict="nothing_valid")
        return None, None

    def restore_latest_valid_consensus(
        self, state_like: Any, extra_validate=None
    ) -> tuple[Any, int] | tuple[None, None]:
        """Multi-host-safe `restore_latest_valid`: every host restores
        the SAME step, or the job aborts loudly.

        Each host first runs the integrity ladder locally (quarantining
        its corrupt steps), then the fleet agrees through the
        distributed runtime:

        1. allgather each host's newest-valid step (-1 = nothing valid);
        2. all equal -> done (the common case; covers all--1 = every
           host starts fresh, which is consistent);
        3. some hosts valid, some with nothing -> abort with a per-host
           validity report (silently forking restored-vs-fresh training
           state is exactly the failure this exists to prevent);
        4. disagreeing steps -> every host re-validates the fleet MIN
           (hosts whose local newest is newer fall back; a checkpoint
           truncated on one host can only pull the fleet DOWN to a step
           everyone holds), a second allgather confirms all hosts hold
           it, and any failure aborts with the report.

        A final `barrier` pins the agreement before training resumes.
        Single-process: identical to `restore_latest_valid`.
        """
        restored, step = self.restore_latest_valid(state_like, extra_validate)
        if jax.process_count() == 1:
            return restored, step
        from genrec_tpu.parallel.mesh import allgather_host_ints, barrier

        steps = allgather_host_ints([-1 if step is None else step])[:, 0]
        report = ", ".join(
            f"p{i}={'none' if s < 0 else int(s)}" for i, s in enumerate(steps)
        )
        if (steps < 0).all():
            barrier("ckpt-consensus-fresh")
            return None, None
        if (steps < 0).any():
            raise RuntimeError(
                "checkpoint consensus: some hosts have NO valid checkpoint "
                f"while others do (newest-valid per host: {report}). "
                "Restoring would fork the replicated training state; "
                "restore or clear the affected hosts' checkpoint "
                "directories and relaunch."
            )
        target = int(steps.min())
        ok = 1
        if step != target:
            logger.warning(
                f"checkpoint consensus: local newest-valid step {step} != "
                f"fleet minimum {target} (per host: {report}) — falling "
                f"back to step {target}"
            )
            try:
                restored = self.validate_and_restore(state_like, target)
                if extra_validate is not None:
                    extra_validate(restored, target)
                step = target
                # Steps above the fleet-agreed restore are VALID locally
                # but abandoned by the consensus decision: retained, orbax
                # would silently drop every future save keyed below them,
                # and the stale-step refusal would abort only THIS host
                # while its peers enter training. Quarantine them like
                # corrupt steps — on disk for rollback, out of discovery.
                for s in [s for s in self.all_steps() if s > target]:
                    logger.warning(
                        f"checkpoint consensus: quarantining locally-valid "
                        f"step {s} abandoned by the fleet-agreed restore at "
                        f"step {target}"
                    )
                    self.quarantine(s)
            except (CheckpointCorruptError, CheckpointMismatchError) as e:
                logger.error(
                    f"checkpoint consensus: cannot restore fleet-agreed "
                    f"step {target} locally: {e}"
                )
                ok = 0
        all_ok = allgather_host_ints([ok])[:, 0]
        if not (all_ok > 0).all():
            failed = [f"p{i}" for i, o in enumerate(all_ok) if not o]
            raise RuntimeError(
                f"checkpoint consensus: hosts {failed} cannot restore the "
                f"fleet-agreed step {target} (newest-valid per host: "
                f"{report}). No step is valid on every host — refusing a "
                "forked restore; inspect the per-host quarantine dirs."
            )
        barrier("ckpt-consensus")
        return restored, step

    def close(self) -> None:
        self._mgr.close()
