"""SASRec trainer (parity target: reference genrec/trainers/sasrec_trainer.py).

Loop shape matches the reference (epoch loop, Adam(b2=0.98), no LR
schedule, full-vocab eval every epoch, best-Recall@10 snapshot) but the
step is one compiled SPMD program over the data mesh and eval ranks stay
on device (no per-sample Python loops — sasrec_trainer.py:63-72 replaced
by `ops.batch_metrics`).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax

from genrec_tpu import configlib
from genrec_tpu.core.harness import jit_train_step, make_train_step
from genrec_tpu.core.logging import Tracker, setup_logger
from genrec_tpu.core.profiling import ProfileWindow
from genrec_tpu.core.state import TrainState
from genrec_tpu.data.batching import (
    batch_iterator,
    fold_valid,
    pack_examples,
    prefetch_to_device,
    right_align,
)
from genrec_tpu.data.synthetic import SyntheticSeqDataset
from genrec_tpu.models.sasrec import SASRec
from genrec_tpu.ops.metrics import first_match_ranks
from genrec_tpu.parallel import distributed_init, get_mesh, metric_allreduce, replicate


def make_eval_step(model, last_from_length: bool = False):
    @jax.jit
    def eval_step(params, batch, valid):
        logits, _ = model.apply({"params": params}, batch["input_ids"])
        if last_from_length:
            # Right-padded eval rows (packed training's position indexing):
            # the prediction sits at the last VALID slot, not slot -1.
            idx = jnp.maximum(jnp.sum(batch["input_ids"] != 0, axis=1) - 1, 0)
            last = jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0]
        else:
            last = logits[:, -1, :]
        last = last.at[:, 0].set(-jnp.inf)
        _, top = jax.lax.top_k(last, 10)
        # Padded rows (valid=0) are masked out of every sum.
        ranks = first_match_ranks(batch["targets"], top[..., None])
        v = valid.astype(jnp.float32)
        out = {"total": v.sum()}
        for k in (1, 5, 10):
            out[f"recall_sum@{k}"] = jnp.sum((ranks < k) * v)
            out[f"ndcg_sum@{k}"] = jnp.sum(
                jnp.where(ranks < k, 1.0 / jnp.log2(ranks.astype(jnp.float32) + 2.0), 0.0)
                * v
            )
        return out

    return eval_step


def evaluate(eval_step, params, arrays, batch_size, mesh) -> dict[str, float]:
    sums: dict[str, float] = {}
    # Prefetching iterator (valid mask folded in): eval overlaps H2D
    # transfer with compute like training.
    for sharded, _ in prefetch_to_device(
        fold_valid(batch_iterator(arrays, batch_size)), mesh
    ):
        got = eval_step(params, sharded, sharded["valid"])
        for k, v in got.items():
            sums[k] = sums.get(k, 0.0) + float(v)
    sums = metric_allreduce(sums)
    total = max(sums.get("total", 0.0), 1.0)
    out = {}
    for k in (1, 5, 10):
        out[f"Recall@{k}"] = sums[f"recall_sum@{k}"] / total
        out[f"NDCG@{k}"] = sums[f"ndcg_sum@{k}"] / total
    return out


@configlib.configurable
def train(
    epochs=10,
    batch_size=128,
    learning_rate=1e-3,
    weight_decay=0.0,
    max_seq_len=50,
    embed_dim=64,
    num_heads=2,
    num_blocks=2,
    ffn_dim=256,
    dropout=0.2,
    dataset="synthetic",
    dataset_folder="dataset/amazon",
    split="beauty",
    num_items=None,
    do_eval=True,
    eval_every_epoch=1,
    eval_batch_size=256,
    save_dir_root="out/sasrec",
    save_every_epoch=50,
    resume_from_checkpoint=False,
    wandb_logging=False,
    wandb_project="sasrec_training",
    wandb_log_interval=100,
    amp=True,
    mixed_precision_type="bf16",
    # Fused full-softmax CE over the tied item-embedding head
    # (kernels/fused_ce.py): same loss, no (B,L,V) logits in HBM.
    # auto = on when running on TPU (Mosaic-compiled only).
    use_fused_ce="auto",
    # First-fit-decreasing sequence packing (data/batching.pack_examples):
    # multiple short histories share one max_seq_len row with segment-aware
    # attention and within-segment positions, so the MXU stops paying for
    # padding. False restores the original one-example-per-row layout
    # (left-padded, absolute positions) exactly.
    pack_sequences=True,
    profile_steps=0,
    seed=0,
):
    """Returns final (valid_metrics, test_metrics) for programmatic use."""
    distributed_init()
    logger = setup_logger(save_dir_root)
    tracker = Tracker(wandb_logging, wandb_project, save_dir=save_dir_root)
    mesh = get_mesh()

    if dataset == "synthetic":
        ds = SyntheticSeqDataset(max_seq_len=max_seq_len, seed=seed)
        n_items = num_items or ds.num_items
    else:
        from genrec_tpu.data.amazon import AmazonSASRecData

        ds = AmazonSASRecData(root=dataset_folder, split=split, max_seq_len=max_seq_len)
        n_items = ds.num_items
    valid_arrays = ds.eval_arrays("valid")
    test_arrays = ds.eval_arrays("test")

    repack, train_arrays = None, None
    if pack_sequences:
        # The packer owns layout: raw examples only — never materialize
        # the padded (N, max_seq_len) train matrix just to discard it.
        # Re-packed per epoch (epoch-seeded example shuffle) so example
        # co-location in a row is re-mixed like the padded layout's
        # per-epoch permutation, not frozen at startup. PackedTrainLoop
        # calls this lazily per epoch.
        train_examples = ds.train_examples()

        def repack(epoch: int):
            arrays, rep = pack_examples(
                train_examples, row_len=max_seq_len, seed=(seed, epoch)
            )
            arrays.pop("segment_valid")  # unused by SASRec's token-level CE
            return arrays, rep

        # Eval rows must index positions the way packed training does
        # (token t at position t), and predictions come from the last
        # VALID slot (make_eval_step(last_from_length=True)).
        valid_arrays = right_align(valid_arrays)
        test_arrays = right_align(test_arrays)
    else:
        train_arrays = ds.train_arrays()

    compute_dtype = (
        jnp.bfloat16 if (amp and mixed_precision_type == "bf16") else jnp.float32
    )
    if use_fused_ce == "auto":
        from genrec_tpu.kernels.policy import auto_fused_ce

        use_fused_ce = auto_fused_ce()
    model = SASRec(
        num_items=n_items,
        max_seq_len=max_seq_len,
        embed_dim=embed_dim,
        num_heads=num_heads,
        num_blocks=num_blocks,
        ffn_dim=ffn_dim,
        dropout=dropout,
        fused_ce=bool(use_fused_ce),
        dtype=compute_dtype,
    )
    rng = jax.random.key(seed)
    init_rng, state_rng = jax.random.split(rng)
    params = model.init(
        init_rng, jnp.zeros((1, max_seq_len), jnp.int32), deterministic=True
    )["params"]

    # Reference uses Adam with beta2=0.98 and no schedule.
    optimizer = (
        optax.adamw(learning_rate, b2=0.98, weight_decay=weight_decay)
        if weight_decay
        else optax.adam(learning_rate, b2=0.98)
    )

    def loss_fn(params, batch, step_rng):
        _, loss = model.apply(
            {"params": params},
            batch["input_ids"],
            batch["targets"],
            deterministic=False,
            segment_ids=batch.get("segment_ids"),
            positions=batch.get("positions"),
            rngs={"dropout": step_rng},
        )
        aux = {}
        if "segment_ids" in batch:
            # tokens-per-step / occupancy surface in the step metrics.
            aux["real_tokens"] = jnp.sum(batch["segment_ids"] != 0).astype(jnp.float32)
        return loss, aux

    step_fn = jit_train_step(make_train_step(
        loss_fn, optimizer, clip_norm=None,
        name="sasrec_train_step_packed" if pack_sequences
        else "sasrec_train_step",
    ))
    state = replicate(mesh, TrainState.create(params, optimizer, state_rng))
    # One jit cache for every eval call; packed training reads predictions
    # from the last valid slot of right-padded eval rows.
    eval_step = make_eval_step(model, last_from_length=pack_sequences)

    from genrec_tpu.core.checkpoint import BestTracker, CheckpointManager, save_params
    from genrec_tpu.core.preemption import PreemptionGuard
    from genrec_tpu.trainers.packed_loop import PackedTrainLoop

    ckpt_mgr = CheckpointManager(os.path.join(save_dir_root, "checkpoints")) if save_dir_root else None
    best = BestTracker(save_dir_root)
    prof = ProfileWindow(
        os.path.join(save_dir_root, "profile") if save_dir_root else "",
        profile_steps,
    )
    guard = PreemptionGuard(logger)
    loop = PackedTrainLoop(
        logger=logger, tracker=tracker, prof=prof, mesh=mesh,
        guard=guard, ckpt=ckpt_mgr,
        rows_per_step=batch_size, row_len=max_seq_len, seed=seed,
        pack_sequences=pack_sequences, repack=repack, train_arrays=train_arrays,
        wandb_log_interval=wandb_log_interval,
        save_dir_root=save_dir_root,
    )
    start_epoch, start_batch, global_step = 0, 0, 0
    if resume_from_checkpoint:
        # Step-granular exact resume: restores TrainState + the data
        # cursor through the integrity ladder, continuing at the exact
        # next batch of a possibly mid-epoch resume point.
        state, start_epoch, start_batch, global_step = loop.resume(
            state, lambda s: replicate(mesh, s)
        )
    for epoch in range(start_epoch, epochs):
        res = loop.run_epoch(
            state, step_fn, epoch, global_step,
            start_batch=start_batch if epoch == start_epoch else 0,
        )
        state, global_step = res.state, res.global_step
        if res.preempted:
            # SIGTERM/SIGINT grace window: the loop already wrote a
            # durable mid-epoch resume point; exit cleanly so the
            # scheduler restarts us with resume_from_checkpoint.
            loop.shutdown(preempted_epoch=epoch)
            return {}, {}

        if ckpt_mgr is not None and (epoch + 1) % save_every_epoch == 0:
            # Epoch-boundary resume point: cursor = (next epoch, batch 0).
            loop.save(state, epoch=epoch + 1, next_batch=0, global_step=global_step)

        if do_eval and (epoch + 1) % eval_every_epoch == 0:
            m = evaluate(eval_step, state.params, valid_arrays, eval_batch_size, mesh)
            logger.info(
                f"epoch {epoch} valid " + ", ".join(f"{k}={v:.4f}" for k, v in m.items())
            )
            tracker.log({"epoch": epoch, **{f"eval/{k}": v for k, v in m.items()}})
            best.update(m["Recall@10"], state.params)

    final_params = best.best_params(like=state.params)
    if final_params is None:
        final_params = state.params
    valid_metrics = evaluate(eval_step, final_params, valid_arrays, eval_batch_size, mesh)
    test_metrics = evaluate(eval_step, final_params, test_arrays, eval_batch_size, mesh)
    logger.info("test " + ", ".join(f"{k}={v:.4f}" for k, v in test_metrics.items()))
    tracker.log({f"test/{k}": v for k, v in test_metrics.items()})

    if save_dir_root and best.value < 0:  # no eval ran: snapshot final params
        save_params(os.path.join(save_dir_root, "best_model"), final_params)
    loop.shutdown()
    return valid_metrics, test_metrics


# ---------------------------------------------------------------------------
# graftlint compile manifest (scripts/graftlint.py, docs/ANALYSIS.md)
# ---------------------------------------------------------------------------

from genrec_tpu.analysis.manifest import BuiltEntry, register_entry


@register_entry("train/sasrec_packed_step", tags=("train", "packed"))
def _graftlint_entry() -> BuiltEntry:
    """CI-shape replica of this trainer's jitted step, SAME jit config as
    train() above (make_train_step flags, donate_argnums=0): the IR rules
    audit what production compiles, at sizes a CPU lowers in seconds."""
    import numpy as np

    model = SASRec(num_items=50, max_seq_len=16, embed_dim=16, num_heads=2,
                   num_blocks=1, ffn_dim=32, dropout=0.0)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 16), jnp.int32), deterministic=True
    )["params"]
    optimizer = optax.adam(1e-3, b2=0.98)

    def loss_fn(p, batch, step_rng):
        _, loss = model.apply(
            {"params": p}, batch["input_ids"], batch["targets"],
            deterministic=False, segment_ids=batch["segment_ids"],
            positions=batch["positions"], rngs={"dropout": step_rng},
        )
        return loss, {"real_tokens": jnp.sum(batch["segment_ids"] != 0).astype(jnp.float32)}

    step_fn = jit_train_step(make_train_step(loss_fn, optimizer, clip_norm=None))
    state = TrainState.create(params, optimizer, jax.random.key(1))
    rng = np.random.default_rng(0)
    batch = {
        "input_ids": jnp.asarray(rng.integers(1, 51, (4, 16)), jnp.int32),
        "targets": jnp.asarray(rng.integers(1, 51, (4, 16)), jnp.int32),
        "segment_ids": jnp.asarray(rng.integers(0, 3, (4, 16)), jnp.int32),
        "positions": jnp.asarray(np.tile(np.arange(16), (4, 1)), jnp.int32),
    }
    # The train state is consumed by the step (the trainer rebinds it);
    # an undonated buffer there is a dead full-model copy in HBM.
    return BuiltEntry(fn=step_fn, args=(state, batch), expect_donated=(0,))


if __name__ == "__main__":
    configlib.parse_config()
    train()
