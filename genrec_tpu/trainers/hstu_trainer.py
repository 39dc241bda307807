"""HSTU trainer (parity target: reference genrec/trainers/hstu_trainer.py).

Identical skeleton to the SASRec trainer (epoch loop, Adam(b2=0.98), no LR
schedule, full-vocab eval) plus timestamp pass-through (hstu_trainer.py:152-157).
"""

from __future__ import annotations

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
)
from genrec_tpu.data.synthetic import SyntheticSeqDataset
from genrec_tpu.models.hstu import HSTU
from genrec_tpu.ops.metrics import first_match_ranks
from genrec_tpu.parallel import distributed_init, get_mesh, metric_allreduce, replicate


def make_eval_step(model):
    @jax.jit
    def eval_step(params, batch, valid):
        logits, _ = model.apply(
            {"params": params}, batch["input_ids"], batch.get("timestamps")
        )
        last = logits[:, -1, :].astype(jnp.float32).at[:, 0].set(-jnp.inf)
        _, top = jax.lax.top_k(last, 10)
        ranks = first_match_ranks(batch["targets"], top[..., None])
        v = valid.astype(jnp.float32)
        out = {"total": v.sum()}
        for k in (1, 5, 10):
            out[f"recall_sum@{k}"] = jnp.sum((ranks < k) * v)
            out[f"ndcg_sum@{k}"] = jnp.sum(
                jnp.where(ranks < k, 1.0 / jnp.log2(ranks.astype(jnp.float32) + 2.0), 0.0) * v
            )
        return out

    return eval_step


def evaluate(eval_step, params, arrays, batch_size, mesh):
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
    return {
        **{f"Recall@{k}": sums[f"recall_sum@{k}"] / total for k in (1, 5, 10)},
        **{f"NDCG@{k}": sums[f"ndcg_sum@{k}"] / total for k in (1, 5, 10)},
    }


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
    dropout=0.2,
    num_position_buckets=32,
    num_time_buckets=64,
    max_position_distance=128,
    use_temporal_bias=True,
    use_pallas="auto",
    # Fused full-softmax CE over the tied item-embedding head
    # (kernels/fused_ce.py): same loss, no (B,L,V) logits in HBM.
    # auto = on when running on TPU (Mosaic-compiled only).
    use_fused_ce="auto",
    # First-fit-decreasing sequence packing: segment-aware attention keeps
    # multiple short histories per row (temporal/positional buckets never
    # bridge segments — the Pallas kernel masks cross-segment pairs
    # in-register). HSTU's biases are relative-only, so eval stays on the
    # original left-padded rows. False restores the unpacked layout.
    pack_sequences=True,
    dataset="synthetic",
    dataset_folder="dataset/amazon",
    split="beauty",
    num_items=None,
    do_eval=True,
    eval_every_epoch=1,
    eval_batch_size=256,
    save_dir_root="out/hstu",
    save_every_epoch=50,
    resume_from_checkpoint=False,
    wandb_logging=False,
    wandb_project="hstu_training",
    wandb_log_interval=100,
    amp=True,
    mixed_precision_type="bf16",
    profile_steps=0,
    seed=0,
):
    distributed_init()
    logger = setup_logger(save_dir_root)
    tracker = Tracker(wandb_logging, wandb_project, save_dir=save_dir_root)
    mesh = get_mesh()

    if dataset == "synthetic":
        ds = SyntheticSeqDataset(max_seq_len=max_seq_len, seed=seed)
        n_items = num_items or ds.num_items
        valid_arrays = ds.eval_arrays_with_time("valid")
        test_arrays = ds.eval_arrays_with_time("test")
        train_examples = lambda: ds.train_examples(with_time=True)
        padded_train = ds.train_arrays_with_time
    else:
        from genrec_tpu.data.amazon import AmazonSASRecData

        ds = AmazonSASRecData(
            root=dataset_folder, split=split, max_seq_len=max_seq_len,
            with_timestamps=True,
        )
        n_items = ds.num_items
        valid_arrays = ds.eval_arrays("valid")
        test_arrays = ds.eval_arrays("test")
        train_examples = ds.train_examples
        padded_train = ds.train_arrays

    repack, train_arrays = None, None
    if pack_sequences:
        # Raw examples only — never materialize the padded train matrix
        # just to discard it for the packed layout. Re-packed per epoch
        # (epoch-seeded shuffle) so example co-location is re-mixed like
        # the padded layout's per-epoch permutation; PackedTrainLoop
        # calls this lazily per epoch.
        examples = train_examples()

        def repack(epoch: int):
            arrays, rep = pack_examples(
                examples, row_len=max_seq_len, seed=(seed, epoch)
            )
            # HSTU has no absolute positions and segment_valid has no
            # consumer in its token-level CE — don't ship them to device.
            arrays.pop("positions")
            arrays.pop("segment_valid")
            return arrays, rep

    else:
        train_arrays = padded_train()

    compute_dtype = jnp.bfloat16 if (amp and mixed_precision_type == "bf16") else jnp.float32
    if use_pallas == "auto":
        from genrec_tpu.kernels.policy import auto_pallas_attention

        use_pallas = auto_pallas_attention()
    if use_fused_ce == "auto":
        from genrec_tpu.kernels.policy import auto_fused_ce

        use_fused_ce = auto_fused_ce()
    model = HSTU(
        num_items=n_items,
        max_seq_len=max_seq_len,
        embed_dim=embed_dim,
        num_heads=num_heads,
        num_blocks=num_blocks,
        dropout=dropout,
        num_position_buckets=num_position_buckets,
        num_time_buckets=num_time_buckets,
        max_position_distance=max_position_distance,
        use_temporal_bias=use_temporal_bias,
        use_pallas=bool(use_pallas),
        fused_ce=bool(use_fused_ce),
        dtype=compute_dtype,
    )
    rng = jax.random.key(seed)
    init_rng, state_rng = jax.random.split(rng)
    params = model.init(
        init_rng, jnp.zeros((1, max_seq_len), jnp.int32),
        jnp.zeros((1, max_seq_len), jnp.int32),
    )["params"]

    optimizer = (
        optax.adamw(learning_rate, b2=0.98, weight_decay=weight_decay)
        if weight_decay
        else optax.adam(learning_rate, b2=0.98)
    )

    def loss_fn(p, batch, step_rng):
        _, loss = model.apply(
            {"params": p}, batch["input_ids"], batch.get("timestamps"),
            batch["targets"], deterministic=False,
            segment_ids=batch.get("segment_ids"), rngs={"dropout": step_rng},
        )
        aux = {}
        if "segment_ids" in batch:
            aux["real_tokens"] = jnp.sum(batch["segment_ids"] != 0).astype(jnp.float32)
        return loss, aux

    step_fn = jit_train_step(make_train_step(
        loss_fn, optimizer, clip_norm=None,
        name="hstu_train_step_packed" if pack_sequences else "hstu_train_step",
    ))
    state = replicate(mesh, TrainState.create(params, optimizer, state_rng))
    eval_step = make_eval_step(model)

    from genrec_tpu.core.checkpoint import BestTracker, CheckpointManager, save_params
    from genrec_tpu.core.preemption import PreemptionGuard
    from genrec_tpu.trainers.packed_loop import PackedTrainLoop

    ckpt = CheckpointManager(os.path.join(save_dir_root, "checkpoints")) if save_dir_root else None
    best = BestTracker(save_dir_root)
    prof = ProfileWindow(
        os.path.join(save_dir_root, "profile") if save_dir_root else "",
        profile_steps,
    )
    guard = PreemptionGuard(logger)
    loop = PackedTrainLoop(
        logger=logger, tracker=tracker, prof=prof, mesh=mesh,
        guard=guard, ckpt=ckpt,
        rows_per_step=batch_size, row_len=max_seq_len, seed=seed,
        pack_sequences=pack_sequences, repack=repack, train_arrays=train_arrays,
        wandb_log_interval=wandb_log_interval,
        save_dir_root=save_dir_root,
    )
    start_epoch, start_batch, global_step = 0, 0, 0
    if resume_from_checkpoint:
        # Step-granular exact resume through the integrity ladder.
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

        if ckpt is not None and (epoch + 1) % save_every_epoch == 0:
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


if __name__ == "__main__":
    configlib.parse_config()
    train()
