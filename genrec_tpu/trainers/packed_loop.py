"""Shared step-granular train loop: one implementation of the plumbing
that was triplicated (with drift) across the sasrec/hstu/tiger trainers
and hand-rolled (epoch-granular, with preemption holes) in the
cobra/lcrec/notellm/rqvae trainers —

- the per-epoch repack closure (epoch-seeded `pack_examples` so example
  co-location re-mixes like the padded layout's per-epoch permutation);
- device-scalar epoch loss / real-token accumulation (float() only at
  logging boundaries, so the host never blocks async dispatch);
- the examples-per-step timer math (seq/s keeps meaning EXAMPLES when a
  packed row holds several) and the occupancy epilogue;
- wandb-interval step logging and ProfileWindow ticks —

plus the STEP-GRANULAR fault tolerance this PR adds, which lands here
once instead of three times:

- the PreemptionGuard is polled after every optimizer step; on fire, a
  resume point (full TrainState + data-iterator cursor,
  `core.fault_tolerance.save_resume_point`) is written durably and the
  epoch returns ``preempted=True`` — a resumed run continues at the
  exact next batch with identical losses/grads;
- `core.chaos` hooks (signal injection, NaN batch poisoning) run inside
  the same loop that serves production, so chaos tests exercise the real
  code path;
- the `NonFiniteMonitor` consumes the jitted non-finite guard's metrics
  (one step deferred — no dispatch stall), dumps offending batches, and
  aborts after N consecutive skipped steps;
- multi-host preemption agreement: each host polls its local
  `PreemptionGuard`, but the loop acts on the fleet-wide OR
  (`parallel.any_across_processes`) so every host writes its resume
  point at the SAME global step — one host checkpointing step N while
  another runs on to N+1 would deadlock the next collective and fork
  the saved state. Single-process runs short-circuit to the local flag
  (no collective); multi-host runs poll the collective OR every
  ``preempt_poll_interval`` steps (lockstep on every host) so the hot
  loop never blocks on an every-step allgather.

The epoch-granularity trainers plug in through three knobs:
``pack_sequences=False`` + ``train_arrays`` (fixed padded layout),
``step_log`` (trainer-specific wandb metric dicts), and ``step_hook`` +
``run_epoch(max_steps=...)`` (rqvae's iteration-gated eval/save cadence
and iteration-count stop).

Observability (genrec_tpu/obs, landing here once for all seven
trainers): every epoch's wall time is classified into goodput buckets
(compute / compile / checkpoint-save / restore / data-wait /
nonfinite-skipped / preemption-drain / other) and reported per epoch —
fleet-aggregated on multi-host; XLA compile events are tapped during
step dispatch so an unexpected mid-run recompile is counted and logged
the step it happens; and the crash flight recorder is pointed at
``<save_dir_root>/flight_recorder.json`` so a SIGTERM'd or crashed run
leaves a structured post-mortem. See docs/OBSERVABILITY.md.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from genrec_tpu.core import chaos
from genrec_tpu.core.fault_tolerance import (
    NonFiniteMonitor,
    resume_exact,
    save_resume_point,
)
from genrec_tpu.core.logging import log_goodput, log_occupancy
from genrec_tpu.core.profiling import StepTimer, log_epoch_perf
from genrec_tpu.data.batching import batch_iterator, prefetch_to_device
from genrec_tpu.obs.flight_recorder import get_flight_recorder
from genrec_tpu.obs.goodput import CompileEvents, GoodputMeter, fleet_goodput
from genrec_tpu.obs.memory import device_memory_stats
from genrec_tpu.obs.spans import NULL_TRACER


#: Counters a model's step may hand out beside ``real_tokens`` (models/
#: backbones/qwen.collect_counters). With a tracer on, the loop writes them
#: as attributes of the step's ``train_step`` span at its log interval,
#: where it already waits for the loss; with the tracer off they are never
#: read.
STEP_COUNTERS = ("expert_load_max_over_mean", "expert_picks_here_share",
                 "expert_pairs_per_held_expert", "sparse_keys_kept_share",
                 "kda_state_keep_share")


def _peak_device_bytes() -> int:
    """What the fullest moment held on the device: live buffers plus what
    compiled programs reserve for their temporaries (0 where the backend
    keeps no allocator counters, as on the CPU)."""
    mem = device_memory_stats()
    return mem.get("peak_bytes_in_use", 0) + mem.get("peak_bytes_reserved", 0)


@dataclasses.dataclass
class EpochResult:
    state: Any
    global_step: int
    preempted: bool
    n_batches: int


class PackedTrainLoop:
    """Owns epoch execution for one trainer; the trainer keeps ownership
    of eval, best-model tracking, and periodic checkpoint CADENCE.

    ``repack(epoch) -> (arrays, PackingReport)`` is called lazily per
    epoch when ``pack_sequences``; otherwise ``train_arrays`` is the
    fixed padded layout. ``rows_per_step`` is the batch rows consumed per
    optimizer step (batch_size, times grad-accum for TIGER);
    ``tokens_scale`` rescales the step's mean ``real_tokens`` metric back
    to whole-step tokens under accumulation. ``examples_per_row``
    rescales seq/s for layouts whose rows hold a fixed number of
    examples (NoteLLM: 2 per pair-unit row). ``step_log(metrics,
    global_step) -> dict`` replaces the default wandb-interval payload;
    ``step_hook(state, epoch, next_batch, global_step)`` runs after
    every step (rqvae's iteration-gated eval/save).
    """

    def __init__(
        self,
        *,
        logger,
        tracker,
        prof,
        mesh,
        guard=None,
        ckpt=None,
        rows_per_step: int,
        row_len: int,
        seed: int,
        pack_sequences: bool,
        repack: Callable[[int], tuple[dict, Any]] | None = None,
        train_arrays: dict | None = None,
        tokens_scale: float = 1.0,
        examples_per_row: float = 1.0,
        wandb_log_interval: int = 100,
        save_dir_root: str | None = None,
        max_consecutive_nonfinite: int = 3,
        step_log: Callable[[dict, int], dict] | None = None,
        step_hook: Callable[[Any, int, int, int], None] | None = None,
        preempt_poll_interval: int = 8,
        tracer=None,
    ):
        if pack_sequences and repack is None:
            raise ValueError("pack_sequences=True needs a repack closure")
        if not pack_sequences and train_arrays is None:
            raise ValueError("pack_sequences=False needs train_arrays")
        self.logger = logger
        self.tracker = tracker
        self.prof = prof
        self.mesh = mesh
        self.guard = guard
        self.ckpt = ckpt
        self.rows_per_step = rows_per_step
        self.row_len = row_len
        self.seed = seed
        self.pack_sequences = pack_sequences
        self._repack = repack
        self.tokens_scale = tokens_scale
        self.examples_per_row = examples_per_row
        self.wandb_log_interval = wandb_log_interval
        self.step_log = step_log
        self.step_hook = step_hook
        self.preempt_poll_interval = max(1, int(preempt_poll_interval))
        self.monitor = NonFiniteMonitor.for_run(
            save_dir_root, logger, max_consecutive_nonfinite
        )
        # Observability (genrec_tpu/obs): goodput buckets per epoch, the
        # process-wide XLA compile tap (unexpected mid-run recompiles are
        # counted + logged the step they happen), optional span tracing,
        # and the crash flight recorder pointed at the run directory.
        self.goodput = GoodputMeter()
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.recompiles = 0
        self._compile_events = CompileEvents.ensure()
        self._compile_events.attach(tracer)
        self._steps_run = 0
        self._in_preempt = False
        self._flight = get_flight_recorder()
        if save_dir_root:
            self._flight.configure(
                os.path.join(save_dir_root, "flight_recorder.json"),
                run_dir=save_dir_root,
            )
        self._ran_epoch = False
        self._arrays = train_arrays
        self._arrays_epoch: int | None = None
        self._report = None

    # -- layout ------------------------------------------------------------

    def _arrays_for(self, epoch: int) -> dict:
        # Lazy: a run resumed at epoch E packs ONCE (for E), not
        # epoch-0-then-E — restart latency sits inside the preemption
        # grace window on large datasets.
        if self.pack_sequences and self._arrays_epoch != epoch:
            with self.tracer.span("train.repack", trace_id=f"train-e{epoch}",
                                  epoch=epoch):
                self._arrays, rep = self._repack(epoch)
            self._arrays_epoch = epoch
            if self._report is None:
                # Rates only (n_examples/n_rows for timers): the example
                # multiset is epoch-invariant, so any epoch's report works.
                self._report = rep
                self.logger.info(str(rep))
        return self._arrays

    @property
    def pack_report(self):
        if self.pack_sequences and self._report is None:
            self._arrays_for(0)
        return self._report

    @property
    def examples_per_step(self) -> float:
        """MEAN examples per optimizer step: packed rows hold several
        examples, so seq/s keeps meaning sequences, not rows."""
        if self.pack_sequences:
            rep = self.pack_report
            return self.rows_per_step * rep.n_examples / rep.n_rows
        return float(self.rows_per_step) * self.examples_per_row

    def fleet_preempted(self, global_step: int | None = None) -> bool:
        """Fleet-wide preemption agreement: True iff ANY host's guard
        latched. Acting on the OR keeps all hosts preempting at the same
        global step instead of forking. Single-process: the local flag,
        no collective. Multi-host: a host-blocking allgather every step
        would serialize the hot loop against the fleet, so with a
        ``global_step`` the collective only runs every
        ``preempt_poll_interval`` steps — global_step advances in
        lockstep on every host, so all hosts poll (and so agree) at the
        same steps, and a latched signal is acted on within the interval
        (well inside any preemption grace window). Callers without a
        step (epoch boundaries) always poll."""
        if self.guard is None:
            return False
        if jax.process_count() == 1:
            return bool(self.guard.fired)
        if (
            global_step is not None
            and global_step % self.preempt_poll_interval != 0
        ):
            return False
        from genrec_tpu.parallel import any_across_processes

        return any_across_processes(self.guard.fired)

    def _note_compile(self, n: int, seconds: float, global_step: int) -> None:
        """XLA compiles observed during step dispatch (a persistent-cache
        load is not one). The run's FIRST
        step compiles by design; any later one is an unexpected mid-run
        recompile (shape drift, donation mismatch, cache eviction) —
        counted, logged at warning, and flight-recorded, the same
        discipline serving gets from check_serving_hlo."""
        if self._steps_run == 0:
            self.logger.info(
                f"step {global_step}: compiled train step "
                f"({n} XLA compile(s), {seconds:.1f}s)"
            )
            return
        self.recompiles += n
        self.logger.warning(
            f"step {global_step}: UNEXPECTED mid-run XLA recompile "
            f"({n} compile(s), {seconds:.2f}s; {self.recompiles} total this "
            "run) — a static shape or donation contract broke"
        )
        self._flight.record("recompile", step=global_step, n=n,
                            seconds=seconds)
        self.tracker.log({
            "global_step": global_step, "perf/recompiles": self.recompiles,
        })

    # -- resume + checkpoint -----------------------------------------------

    def resume(self, state_like, place_fn=None) -> tuple[Any, int, int, int]:
        """(state, start_epoch, start_batch, global_step) — exact cursor
        via the integrity ladder, or fresh-start values."""
        with self.goodput.measure("restore"):
            point = resume_exact(
                self.ckpt, state_like, place_fn,
                data_seed=self.seed, logger=self.logger,
            )
        if point is None:
            return state_like, 0, 0, 0
        self._flight.record(
            "resume", epoch=point.epoch, next_batch=point.next_batch,
            global_step=point.global_step,
        )
        return point.state, point.epoch, point.next_batch, point.global_step

    def save(self, state, *, epoch: int, next_batch: int, global_step: int,
             wait: bool = False) -> None:
        """Write a resume point (no-op without a checkpoint manager)."""
        if self.ckpt is not None:
            # Goodput: a preemption save is drain work, not the periodic
            # checkpoint cadence — classify by WHY it is being written.
            bucket = "preemption_drain" if self._in_preempt else "checkpoint_save"
            with self.goodput.measure(bucket):
                save_resume_point(
                    self.ckpt, state, epoch=epoch, next_batch=next_batch,
                    global_step=global_step, data_seed=self.seed, wait=wait,
                )

    def shutdown(self, preempted_epoch: int | None = None) -> None:
        """Close everything the loop owns (ckpt manager joins in-flight
        async saves, guard restores signal handlers, profiler and tracker
        flush) — the single exit sequence for both the preempted and the
        normal return paths of every packed trainer."""
        if self.ckpt is not None:
            self.ckpt.close()
        if self.guard is not None:
            self.guard.close()
        self.prof.close()
        run = self.goodput.run_report()
        if run["wall_s"] > 0 and self._steps_run:
            peak = _peak_device_bytes()
            self.logger.info(
                f"run goodput {run['goodput_pct']:.1f}% over "
                f"{run['wall_s']:.1f}s wall (see goodput/* metrics)"
                + (f"; peak device memory {peak / 2**20:.1f} MB"
                   if peak else "")
            )
        self.tracker.finish()
        self._flight.record(
            "run_shutdown", preempted_epoch=preempted_epoch,
            steps_run=self._steps_run, recompiles=self.recompiles,
        )
        if preempted_epoch is not None:
            self.logger.info(
                f"preempted: exiting during epoch {preempted_epoch}"
            )

    def _preempt(self, state, epoch: int, next_batch: int, global_step: int):
        # Durable save FIRST: the monitor's deferred check may abort the
        # run (NonFiniteLossError), and a preemption arriving on top of a
        # non-finite streak must still leave a resume point — the streak
        # itself is inside the saved state (nonfinite_count), so the
        # resumed run keeps counting toward the threshold.
        self._flight.record("preempt", epoch=epoch, next_batch=next_batch,
                            global_step=global_step)
        self._in_preempt = True
        try:
            self.save(state, epoch=epoch, next_batch=next_batch,
                      global_step=global_step, wait=True)
            self.logger.info(
                f"preempted: resume point at epoch {epoch} batch {next_batch} "
                f"(global step {global_step})"
            )
            with self.goodput.measure("preemption_drain"):
                self.monitor.flush()
        finally:
            self._in_preempt = False
            # The dump the PreemptionGuard wrote at signal receipt
            # predates the resume point; re-dump so the post-mortem's
            # last events show the drain completing.
            self._flight.dump(reason="preemption_drain")

    # -- the epoch ---------------------------------------------------------

    def run_epoch(self, state, step_fn, epoch: int, global_step: int,
                  start_batch: int = 0,
                  max_steps: int | None = None) -> EpochResult:
        """One epoch (or its remainder from ``start_batch``), polling the
        guard per step (fleet-wide OR on multi-host). Returns with
        ``preempted=True`` after writing a durable mid-epoch resume
        point. ``max_steps`` stops before the batch that would push
        ``global_step`` past it (rqvae's iteration mode)."""
        if self.fleet_preempted():
            # Fired between epochs (eval/checkpoint window): the cursor
            # is simply "this epoch, batch start_batch".
            self._preempt(state, epoch, start_batch, global_step)
            return EpochResult(state, global_step, True, 0)
        arrays = self._arrays_for(epoch)
        timer = StepTimer(
            self.examples_per_step,
            skip_first=0 if self._ran_epoch else 1,
        )
        self._ran_epoch = True
        self._flight.record("epoch_start", epoch=epoch,
                            global_step=global_step, start_batch=start_batch)
        skipped_before = self.monitor.skipped_steps
        epoch_loss, epoch_tokens, n_batches = None, None, 0
        consumed = start_batch
        batches = iter(prefetch_to_device(
            chaos.poison_batches(
                batch_iterator(
                    arrays, self.rows_per_step, shuffle=True, seed=self.seed,
                    epoch=epoch, drop_last=True, start_batch=start_batch,
                ),
                start_step=global_step,
            ),
            self.mesh,
        ))
        # Host phases of the loop, one span each on the epoch's trace, on
        # `time.monotonic()` like every span of the program. A step's
        # `train.host_tail` ends where the next wait for data begins, so
        # it is recorded then (or as the loop is left).
        trace_id = f"train-e{epoch}"
        tail = None  # (start, step) of a host tail not yet recorded

        def close_tail(t_end: float) -> None:
            nonlocal tail
            if tail is not None and self.tracer.enabled:
                self.tracer.record_span("train.host_tail", trace_id, tail[0],
                                        t_end, step=tail[1])
            tail = None

        while True:
            # Goodput: time blocked on the input pipeline (data_wait) is
            # measured apart from the step section, whose residual after
            # compile/skipped attribution is the compute bucket.
            t_wait = time.monotonic()
            close_tail(t_wait)
            try:
                sharded, _ = next(batches)
            except StopIteration:
                break
            t_step = time.monotonic()
            self.goodput.add("data_wait", t_step - t_wait)
            if max_steps is not None and global_step >= max_steps:
                break
            c_n0, c_s0 = self._compile_events.snapshot()
            l_n0, l_s0 = self._compile_events.load_snapshot()
            state, m = step_fn(state, sharded)
            t_dispatched = time.monotonic()
            c_n1, c_s1 = self._compile_events.snapshot()
            l_n1, l_s1 = self._compile_events.load_snapshot()
            # Guard-skipped steps contribute 0 to the epoch mean — one
            # NaN batch must not turn the whole epoch summary NaN (NaN*0
            # is still NaN, so select, don't scale; the per-step wandb
            # log still reports the raw loss).
            loss = m["loss"]
            if "nonfinite" in m:
                loss = jnp.where(m["nonfinite"] > 0, 0.0, loss)
            epoch_loss = loss if epoch_loss is None else epoch_loss + loss
            if "real_tokens" in m:
                tok = m["real_tokens"] * self.tokens_scale
                epoch_tokens = tok if epoch_tokens is None else epoch_tokens + tok
            timer.tick()
            n_batches += 1
            consumed += 1
            global_step += 1
            self.prof.tick(global_step, tracer=self.tracer)
            if c_n1 > c_n0:
                self._note_compile(c_n1 - c_n0, c_s1 - c_s0, global_step)
            counters = {}
            if global_step % self.wandb_log_interval == 0:
                self.tracker.log(
                    self.step_log(m, global_step)
                    if self.step_log is not None
                    else {"global_step": global_step,
                          "train/loss": float(m["loss"])}
                )
                if self.tracer.enabled:
                    counters = {k: float(m[k]) for k in STEP_COUNTERS if k in m}
            # Deferred non-finite policy: checks the PREVIOUS step's flag.
            self.monitor.observe(global_step, epoch, m, sharded)
            # Step section closes here: observe() synced on the previous
            # step's device scalar, so this interval really holds device
            # compute. step_hook (rqvae's iteration-gated eval/save) and
            # the preemption poll land in `other`.
            t_done = time.monotonic()
            self.goodput.note_step(t_done - t_step,
                                   compile_seconds=c_s1 - c_s0)
            self._steps_run += 1
            self._flight.record("step", step=global_step, epoch=epoch)
            if self.tracer.enabled:
                # `train_step` first: a reader that names an idle gap by
                # the last span committed over it gets the phase.
                rec = self.tracer.record_span
                rec("train_step", trace_id, t_step, t_done, step=global_step,
                    **counters)
                rec("train.data_wait", trace_id, t_wait, t_step,
                    step=global_step)
                rec("train.dispatch", trace_id, t_step, t_dispatched,
                    step=global_step)
                rec("train.sync", trace_id, t_dispatched, t_done,
                    step=global_step)
                if c_n1 > c_n0 or l_n1 > l_n0:
                    rec("train.compile", trace_id, t_step, t_dispatched,
                        step=global_step, n=c_n1 - c_n0,
                        seconds=c_s1 - c_s0, loads=l_n1 - l_n0)
            tail = (t_done, global_step)
            if self.step_hook is not None:
                self.step_hook(state, epoch, consumed, global_step)
            chaos.maybe_kill(step=global_step)
            if self.fleet_preempted(global_step):
                close_tail(time.monotonic())
                self._preempt(state, epoch, consumed, global_step)
                return EpochResult(state, global_step, True, n_batches)
        self.monitor.flush()
        # Fault-injection hook (core.chaos): deliver a real signal in the
        # between-epoch eval/checkpoint window — the top-of-epoch
        # preemption branch above is what catches it on the NEXT call.
        # One hook here covers all seven trainers; no-op outside a plan.
        chaos.maybe_kill(epoch=epoch)
        if n_batches:
            # Zero batches = an epoch resumed exactly at its end (the
            # preemption latched after the final batch): nothing ran, so
            # logging a fabricated 0.0 epoch loss would be a lie.
            log_epoch_perf(
                self.logger, self.tracker, epoch, epoch_loss, n_batches, timer,
                tokens_per_step=(
                    float(epoch_tokens) / n_batches
                    if epoch_tokens is not None else None
                ),
            )
            if epoch_tokens is not None:
                log_occupancy(
                    self.logger, self.tracker, epoch, float(epoch_tokens),
                    n_batches * self.rows_per_step * self.row_len,
                )
            # Goodput: classify this epoch window's wall time and report
            # it; on a fleet, also the all-host aggregate (collective —
            # epochs end in lockstep, so every host reaches this line).
            self.goodput.note_skipped(
                self.monitor.skipped_steps - skipped_before
            )
            report = self.goodput.end_epoch()
            # Peak device bytes ride the goodput summary where the
            # backend exposes allocator stats (TPU/GPU; CPU has none) —
            # the trainers' view of the same HBM lever the serving
            # ledger budgets (obs/memory.py).
            peak = _peak_device_bytes()
            if peak:
                report["peak_device_bytes"] = peak
            log_goodput(self.logger, self.tracker, epoch, report)
            if jax.process_count() > 1:
                # obs imports nothing upward (graftlint layering): the
                # collective is injected from the runtime layer here.
                from genrec_tpu.parallel.mesh import allgather_host_ints

                log_goodput(self.logger, self.tracker, epoch,
                            fleet_goodput(report, allgather_host_ints),
                            fleet=True)
        self._flight.record("epoch_end", epoch=epoch, global_step=global_step,
                            n_batches=n_batches)
        return EpochResult(state, global_step, False, n_batches)
