"""The jitted train-step factory: one compiled function per model.

Replaces the reference's Accelerate loop body (`accelerator.accumulate` /
`backward` / `clip_grad_norm_` / `optimizer.step`, tiger_trainer.py:294-318)
with a single XLA program: microbatch `lax.scan` gradient accumulation,
global-norm clip, optax update. Mixed precision is a property of the model
(bf16 params/activations) rather than an autocast context; the loss and
grad-norm math here stays fp32.

Sharding: callers place the batch with `shard_batch` (leading dim on the
"data" axis) and params replicated; jit then compiles an SPMD program where
the gradient mean is an XLA all-reduce over ICI — the DDP equivalent with
no wrapper class.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax

from genrec_tpu.core.state import TrainState

# loss_fn(params, batch, rng) -> (loss, aux_metrics_dict)
LossFn = Callable[[Any, Any, jax.Array], tuple[jax.Array, dict]]


def jit_train_step(step):
    """THE production jit config for a trainer's step: the train state is
    consumed by the call (the loop rebinds it), so it is donated — an
    undonated state is a dead full-model copy held in HBM across every
    step. Every trainer AND its graftlint compile-manifest entry jit
    through this one helper, so the donation audit
    (analysis/ir.py missing_donation) audits what production compiles;
    dropping the donation here fails CI instead of silently
    double-buffering."""
    return jax.jit(step, donate_argnums=0)


def make_train_step(
    loss_fn: LossFn,
    optimizer: optax.GradientTransformation,
    accum_steps: int = 1,
    clip_norm: float | None = 1.0,
    skip_nonfinite: bool = True,
    name: str = "train_step",
):
    """Build `step(state, batch) -> (state, metrics)`, ready to jit.

    ``name`` (`<model>_train_step`, `<model>_train_step_packed`) is the
    step function's own: jitted, the executable reads `jit_<name>` on a
    device profile's `XLA Modules` line and in every op's scope path.

    With ``accum_steps > 1`` the batch's leading dim is split into
    ``accum_steps`` microbatches scanned sequentially — same semantics as
    `Accelerator(gradient_accumulation_steps=...)` but inside one compiled
    step, so the optimizer/clip always sees the averaged full-batch grad.

    ``skip_nonfinite`` (default on) is the jitted non-finite step guard:
    when the batch loss or the (pre-clip) gradient norm is NaN/Inf, the
    optimizer update is dropped — params, opt_state and ``state.step``
    pass through UNCHANGED (the per-step RNG still advances, so a skipped
    step perturbs nothing downstream), ``state.nonfinite_count`` counts
    the consecutive-skip streak (reset to 0 by any finite step), and the
    metrics gain ``nonfinite`` (0/1 flag) + ``nonfinite_count``. Skipping
    happens entirely on device via `jnp.where` — no host sync, no branch,
    identical numerics on the finite path. Host-side policy (dumping the
    offending batch, aborting after N consecutive skips) lives in
    `core.fault_tolerance.NonFiniteMonitor`.
    """

    def grads_of(params, batch, rng):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, rng
        )
        return loss, aux, grads

    def step(state: TrainState, batch) -> tuple[TrainState, dict]:
        from genrec_tpu.core.state import fast_step_rng

        rng, step_rng = jax.random.split(state.rng)
        # TPU: dropout bits come from the hardware RngBitGenerator instead
        # of threefry (~40% of a small-model step); state.rng itself stays
        # threefry so checkpoints are backend-portable (see fast_step_rng).
        step_rng = fast_step_rng(step_rng)

        # named_scope: phase labels survive into the compiled HLO/XLA
        # profile, so a device trace (core.profiling.ProfileWindow)
        # attributes kernel time to grads vs clip vs optimizer — the
        # device-side half of the obs layer's host spans.
        if accum_steps == 1:
            with jax.named_scope("grads"):
                loss, aux, grads = grads_of(state.params, batch, step_rng)
        else:
            def split_micro(x):
                return x.reshape((accum_steps, x.shape[0] // accum_steps) + x.shape[1:])

            micro = jax.tree_util.tree_map(split_micro, batch)
            keys = jax.random.split(step_rng, accum_steps)

            def body(carry, mb_and_key):
                mb, key = mb_and_key
                loss, aux, grads = grads_of(state.params, mb, key)
                acc_loss, acc_grads = carry
                acc_grads = jax.tree_util.tree_map(jnp.add, acc_grads, grads)
                return (acc_loss + loss, acc_grads), aux

            zero_grads = jax.tree_util.tree_map(
                lambda p: jnp.zeros_like(p, dtype=jnp.float32), state.params
            )
            with jax.named_scope("grads"):
                (loss_sum, grad_sum), auxes = jax.lax.scan(
                    body, (jnp.zeros((), jnp.float32), zero_grads), (micro, keys)
                )
            loss = loss_sum / accum_steps
            grads = jax.tree_util.tree_map(lambda g: g / accum_steps, grad_sum)
            aux = jax.tree_util.tree_map(lambda a: a.mean(axis=0), auxes)

        with jax.named_scope("grad_clip"):
            if clip_norm is not None:
                gnorm = optax.global_norm(grads)
                scale = jnp.minimum(1.0, clip_norm / jnp.maximum(gnorm, 1e-6))
                grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
            else:
                gnorm = optax.global_norm(grads)

        with jax.named_scope("optimizer_update"):
            updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
        metrics = {"loss": loss, "grad_norm": gnorm, **aux}
        if skip_nonfinite:
            # NaN/Inf batch: keep the old params/opt_state/step (the NaN
            # update would poison Adam's moments even at lr=0), bump the
            # consecutive-skip streak. `where` with a scalar predicate
            # selects whole buffers — on the finite path this is the
            # identity, bit-for-bit.
            with jax.named_scope("nonfinite_guard"):
                ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)
                keep = lambda new, old: jax.tree_util.tree_map(
                    lambda n, o: jnp.where(ok, n, o), new, old
                )
                params = keep(params, state.params)
                opt_state = keep(opt_state, state.opt_state)
                step = state.step + jnp.where(ok, 1, 0).astype(state.step.dtype)
                nonfinite_count = jnp.where(ok, 0, state.nonfinite_count + 1).astype(
                    state.nonfinite_count.dtype
                )
                metrics["nonfinite"] = (~ok).astype(jnp.float32)
                metrics["nonfinite_count"] = nonfinite_count.astype(jnp.float32)
        else:
            step = state.step + 1
            nonfinite_count = state.nonfinite_count
        new_state = state.replace(
            step=step, params=params, opt_state=opt_state, rng=rng,
            nonfinite_count=nonfinite_count,
        )
        return new_state, metrics

    step.__name__ = step.__qualname__ = name
    return step
