"""Profiling / tracing hooks.

The reference has NO tracing or profiling at all (SURVEY.md §5.1 — tqdm
bars only). Here profiling is a first-class utility: `ProfileWindow`
captures a jax.profiler trace of a few steps (TensorBoard-viewable XLA
traces incl. per-kernel timing) and `StepTimer` gives steps/sec + seq/sec
with compile-step exclusion.

Host-side span tracing, goodput accounting, and the crash flight
recorder live in `genrec_tpu/obs` (docs/OBSERVABILITY.md); a device
profile captured here joins those host spans by the clock anchor
`ProfileWindow` has the tracer leave (`SpanTracer.profile_anchor`), and
reads by the named_scope phase labels in core/harness.py, the models and
ops/trie.py.
"""

from __future__ import annotations

import time

import jax


class ProfileWindow:
    """Capture a jax.profiler trace of n_steps training steps, starting
    after ``start`` steps have completed (default 1: skip the compile
    step).

    Trainers construct one unconditionally (n_steps=0 or an empty logdir
    disables) and call ``tick(n_finished)`` after each optimizer step with
    the RUNNING COUNT of finished steps; ``close()`` stops a still-open
    trace when the run ends early. A ``tracer`` handed to ``tick`` that is
    on leaves its clock anchor in the profile as the trace starts.
    """

    def __init__(self, logdir: str, n_steps: int = 0, start: int = 1):
        self.logdir = logdir
        self.n_steps = n_steps
        self.start = start
        self._state = "idle" if (n_steps > 0 and logdir) else "done"

    def tick(self, n_finished: int, tracer=None) -> None:
        """Call after each step with the 1-based count of finished steps."""
        if self._state == "idle" and n_finished >= self.start:
            jax.profiler.start_trace(self.logdir)
            if tracer is not None:
                tracer.profile_anchor()
            self._state = "on"
        elif self._state == "on" and n_finished >= self.start + self.n_steps:
            jax.profiler.stop_trace()
            self._state = "done"

    def close(self) -> None:
        if self._state == "on":
            jax.profiler.stop_trace()
        self._state = "done"


def perf_summary(timer: "StepTimer") -> dict:
    """StepTimer summary extended with the per-chip north-star metric
    (BASELINE.md: seq/sec/chip)."""
    s = timer.summary()
    s["seq_per_sec_per_chip"] = s["seq_per_sec"] / max(jax.device_count(), 1)
    return s


def log_epoch_perf(logger, tracker, epoch, epoch_loss, n_batches, timer,
                   tokens_per_step: float | None = None) -> float:
    """Shared epoch-end summary used by every trainer: block once on the
    chained loss scalar (closing the async-dispatch timing window), log
    loss + throughput, feed the Tracker. Returns the mean loss.

    ``tokens_per_step``: mean REAL tokens per step (packed trainers pass
    the epoch's device-accumulated count / n_batches) — adds tokens/sec
    and tokens/sec/chip to the perf metrics."""
    if epoch_loss is not None:
        jax.block_until_ready(epoch_loss)
    perf = perf_summary(timer)
    if tokens_per_step is not None:
        tps = perf["steps_per_sec"] * tokens_per_step
        perf["tokens_per_sec"] = tps
        perf["tokens_per_sec_per_chip"] = tps / max(jax.device_count(), 1)
    mean_loss = float(epoch_loss) / n_batches if n_batches else 0.0
    extra = (
        f", {perf['tokens_per_sec']:.0f} tok/s" if "tokens_per_sec" in perf else ""
    )
    logger.info(
        f"epoch {epoch} loss {mean_loss:.4f} "
        f"[{perf['seq_per_sec']:.1f} seq/s, "
        f"{perf['seq_per_sec_per_chip']:.1f} seq/s/chip{extra}]"
    )
    tracker.log({
        "epoch": epoch, "train/loss": mean_loss,
        **{f"perf/{k}": v for k, v in perf.items()},
    })
    return mean_loss


class StepTimer:
    """Throughput meter that ignores the first (compile) step.

    >>> t = StepTimer(batch_size=256)
    >>> for batch in data:
    ...     state, m = step(state, batch)
    ...     t.tick(m["loss"])          # blocks on the step's result
    >>> t.summary()  # {'steps_per_sec': ..., 'seq_per_sec': ...}
    """

    def __init__(self, batch_size: int, skip_first: int = 1):
        self.batch_size = batch_size
        self.skip_first = skip_first
        self._count = 0
        # skip_first=0 means "time from construction".
        self._t0 = time.perf_counter() if skip_first == 0 else None

    def tick(self, result=None) -> None:
        if result is not None:
            jax.block_until_ready(result)
        self._count += 1
        if self._count == self.skip_first:
            self._t0 = time.perf_counter()

    def summary(self) -> dict:
        timed = self._count - self.skip_first
        if self._t0 is None or timed <= 0:
            return {"steps_per_sec": 0.0, "seq_per_sec": 0.0}
        dt = time.perf_counter() - self._t0
        return {
            "steps_per_sec": timed / dt,
            "seq_per_sec": timed * self.batch_size / dt,
        }
