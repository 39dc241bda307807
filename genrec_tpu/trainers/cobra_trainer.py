"""COBRA trainer (parity target: reference genrec/trainers/cobra_trainer.py).

Epoch loop, AdamW + cosine schedule, weighted sparse+dense loss
(:359-362); eval recomputes all item dense vecs from the current encoder
(:303-334), runs `beam_fusion` (n_beam=20, alpha=0.5) and accumulates
TopKAccumulator + per-codebook top-1 accuracy (:414-452).
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
    pad_to_batch,
    prefetch_eval_batches,
)
from genrec_tpu.data.cobra_seq import CobraSeqData, synthetic_cobra_data
from genrec_tpu.models.cobra import Cobra, beam_fusion
from genrec_tpu.ops.metrics import TopKAccumulator
from genrec_tpu.ops.schedules import cosine_schedule_with_warmup
from genrec_tpu.parallel import distributed_init, get_mesh, replicate


import functools


@functools.partial(jax.jit, static_argnums=0)
def _encode_items_jit(model, params, txt):
    return model.apply({"params": params}, txt[:, None, :], method=Cobra.encode_items)[:, 0]


def compute_item_dense_vecs(model, params, item_texts: np.ndarray, batch_size=256):
    """Dense vectors for every item from the CURRENT encoder (re-done each
    eval; reference cobra_trainer.py:303-334). The jit is cached on
    (model, shapes), so repeat evals don't recompile."""
    outs = []
    n = len(item_texts)
    for s in range(0, n, batch_size):
        chunk = {"t": item_texts[s : s + batch_size]}
        n_real = len(chunk["t"])
        padded, _ = pad_to_batch(chunk, batch_size)
        outs.append(np.asarray(_encode_items_jit(model, params, padded["t"]))[:n_real])
    return jnp.asarray(np.concatenate(outs))


def make_fusion_fn(model, item_sem_ids, n_candidates, n_beam, alpha):
    @jax.jit
    def fuse(params, batch, item_vecs):
        return beam_fusion(
            model, params, batch["input_ids"], batch["encoder_input_ids"],
            item_vecs, item_sem_ids,
            n_candidates=n_candidates, n_beam=n_beam, alpha=alpha,
        )

    return fuse


def evaluate(fusion_fn, params, arrays, item_vecs, batch_size, mesh, C):
    from genrec_tpu.parallel import metric_allreduce

    acc = TopKAccumulator(ks=(1, 5, 10))
    cb_correct = np.zeros(C)
    cb_total = 0
    # Same prefetching iterator as the train loop: host batch assembly and
    # H2D transfer overlap the previous batch's beam fusion.
    for sharded, host, valid in prefetch_eval_batches(
        batch_iterator(arrays, batch_size), mesh
    ):
        out = fusion_fn(params, sharded, item_vecs)
        n = int(valid.sum())
        topk = np.asarray(out.sem_ids)[:n]
        target = host["target_sem_ids"][:n]
        acc.accumulate(jnp.asarray(target), jnp.asarray(topk))
        top1 = topk[:, 0, :]
        for c in range(C):
            cb_correct[c] += (top1[:, c] == target[:, c]).sum()
        cb_total += n
    metrics = acc.reduce(cross_process=True)
    # Same cross-host scope as the TopK metrics.
    cb = metric_allreduce({"correct": list(cb_correct), "total": float(cb_total)})
    metrics.update(
        {f"codebook_acc_{c}": cb["correct"][c] / max(cb["total"], 1) for c in range(C)}
    )
    return metrics


@configlib.configurable
def train(
    epochs=50,
    batch_size=64,
    learning_rate=3e-4,
    num_warmup_steps=100,
    weight_decay=0.01,
    sparse_loss_weight=1.0,
    dense_loss_weight=1.0,
    encoder_n_layers=1,
    encoder_hidden_dim=768,
    encoder_num_heads=8,
    encoder_vocab_size=32128,
    id_vocab_size=512,
    n_codebooks=3,
    d_model=768,
    max_len=1024,
    infonce_temperature=0.2,
    decoder_n_layers=8,
    decoder_num_heads=6,
    decoder_dropout=0.1,
    max_items=20,
    n_beam=20,
    fusion_alpha=0.5,
    dataset="synthetic",
    dataset_folder="dataset/amazon",
    split="beauty",
    sem_ids_path=None,
    do_eval=True,
    eval_every_epoch=10,
    eval_batch_size=32,
    # False: final valid/test with final-epoch weights — the reference
    # COBRA trainer's protocol (no best tracking); True keeps the
    # best-valid-Recall@10 snapshot protocol of sasrec/hstu.
    test_on_best=True,
    save_dir_root="out/cobra",
    save_every_epoch=50,
    resume_from_checkpoint=False,
    wandb_logging=False,
    wandb_project="cobra_training",
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

    if callable(dataset):
        # Injected data factory returning a CobraSeqData — mirrors the
        # reference trainer's dataset-class parameter (cobra_trainer.py:99)
        # and is how the parity harness feeds shared fixed token tables.
        data = dataset()
        id_vocab_size = data.id_vocab_size
        n_codebooks = data.C
    elif dataset == "synthetic":
        data = synthetic_cobra_data(
            id_vocab_size=id_vocab_size, n_codebooks=n_codebooks,
            text_vocab=encoder_vocab_size, max_items=max_items, seed=seed,
        )
    else:
        from genrec_tpu.data.cobra_seq import amazon_cobra_data

        if sem_ids_path is None:
            raise ValueError("amazon dataset needs sem_ids_path (RQ-VAE artifact)")
        data = amazon_cobra_data(
            dataset_folder, split, sem_ids_path, max_items=max_items
        )
        id_vocab_size = data.id_vocab_size
        n_codebooks = data.C

    train_arrays = data.train_arrays()
    valid_arrays = data.eval_arrays("valid")
    test_arrays = data.eval_arrays("test")
    item_sem_ids = jnp.asarray(data.sem_ids)

    compute_dtype = jnp.bfloat16 if (amp and mixed_precision_type == "bf16") else jnp.float32
    model = Cobra(
        encoder_n_layers=encoder_n_layers,
        encoder_hidden_dim=encoder_hidden_dim,
        encoder_num_heads=encoder_num_heads,
        encoder_vocab_size=encoder_vocab_size,
        id_vocab_size=id_vocab_size,
        n_codebooks=n_codebooks,
        d_model=d_model,
        max_len=max_len,
        temperature=infonce_temperature,
        decoder_n_layers=decoder_n_layers,
        decoder_num_heads=decoder_num_heads,
        decoder_dropout=decoder_dropout,
        dtype=compute_dtype,
    )
    rng = jax.random.key(seed)
    init_rng, state_rng = jax.random.split(rng)
    params = model.init(
        init_rng,
        jnp.full((1, (max_items + 1) * n_codebooks), data.pad_id, jnp.int32),
        jnp.zeros((1, max_items + 1, data.item_texts.shape[1]), jnp.int32),
    )["params"]

    steps_per_epoch = max(1, len(train_arrays["input_ids"]) // batch_size)
    total_steps = epochs * steps_per_epoch
    schedule = cosine_schedule_with_warmup(learning_rate, num_warmup_steps, total_steps)
    optimizer = optax.adamw(schedule, weight_decay=weight_decay)

    def loss_fn(p, batch, step_rng):
        out = model.apply(
            {"params": p}, batch["input_ids"], batch["encoder_input_ids"],
            deterministic=False, rngs={"dropout": step_rng},
        )
        loss = sparse_loss_weight * out.loss_sparse + dense_loss_weight * out.loss_dense
        return loss, {
            "loss_sparse": out.loss_sparse,
            "loss_dense": out.loss_dense,
            "acc": out.acc_correct / jnp.maximum(out.acc_total, 1),
            "codebook_entropy": out.codebook_entropy,
        }

    step_fn = jit_train_step(make_train_step(
        loss_fn, optimizer, clip_norm=1.0, name="cobra_train_step"))
    state = replicate(mesh, TrainState.create(params, optimizer, state_rng))
    # Reference eval: n_candidates=10 of n_beam=20 (cobra_trainer.py:433-435);
    # clamped so small-beam debug runs stay valid.
    fusion_fn = make_fusion_fn(
        model, item_sem_ids, min(10, n_beam), n_beam, fusion_alpha
    )

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

    def step_log(m, g):
        return {
            "global_step": g,
            "train/loss": float(m["loss"]),
            "train/loss_sparse": float(m["loss_sparse"]),
            "train/loss_dense": float(m["loss_dense"]),
            "train/acc": float(m["acc"]),
            "train/codebook_entropy": float(m["codebook_entropy"]),
        }

    loop = PackedTrainLoop(
        logger=logger, tracker=tracker, prof=prof, mesh=mesh,
        guard=guard, ckpt=ckpt,
        rows_per_step=batch_size,
        row_len=(max_items + 1) * n_codebooks, seed=seed,
        pack_sequences=False, train_arrays=train_arrays,
        wandb_log_interval=wandb_log_interval,
        save_dir_root=save_dir_root,
        step_log=step_log,
    )
    start_epoch, start_batch, global_step = 0, 0, 0
    if resume_from_checkpoint:
        # Step-granular exact resume: continues at the exact next batch
        # of a possibly mid-epoch resume point.
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
            # durable mid-epoch resume point (even mid-FINAL-epoch — the
            # hole the old epoch-granular guard left open); exit cleanly
            # so the scheduler restarts us with resume_from_checkpoint.
            loop.shutdown(preempted_epoch=epoch)
            return {}, {}

        if ckpt is not None and (epoch + 1) % save_every_epoch == 0:
            # Epoch-boundary resume point: cursor = (next epoch, batch 0).
            loop.save(state, epoch=epoch + 1, next_batch=0,
                      global_step=global_step)

        if do_eval and (epoch + 1) % eval_every_epoch == 0:
            item_vecs = compute_item_dense_vecs(model, state.params, data.item_texts)
            m = evaluate(fusion_fn, state.params, valid_arrays, item_vecs,
                         eval_batch_size, mesh, n_codebooks)
            logger.info(
                f"epoch {epoch} valid " + ", ".join(f"{k}={v:.4f}" for k, v in m.items())
            )
            tracker.log({"epoch": epoch, **{f"eval/{k}": v for k, v in m.items()}})
            best.update(m["Recall@10"], state.params)

    # Unconditional final resume point: closes the old hole where a
    # save_every_epoch cadence never firing left a completed run with
    # NOTHING on disk to resume from.
    loop.save(state, epoch=epochs, next_batch=0, global_step=global_step)
    final_params = best.best_params(like=state.params) if test_on_best else None
    if final_params is None:
        final_params = state.params
    item_vecs = compute_item_dense_vecs(model, final_params, data.item_texts)
    valid_metrics = evaluate(fusion_fn, final_params, valid_arrays, item_vecs,
                             eval_batch_size, mesh, n_codebooks)
    test_metrics = evaluate(fusion_fn, final_params, test_arrays, item_vecs,
                            eval_batch_size, mesh, n_codebooks)
    logger.info("test " + ", ".join(f"{k}={v:.4f}" for k, v in test_metrics.items()))
    tracker.log({f"test/{k}": v for k, v in test_metrics.items()})
    if save_dir_root and best.value < 0:  # no eval ran: snapshot final params
        save_params(os.path.join(save_dir_root, "best_model"), final_params)
    loop.shutdown()
    return valid_metrics, test_metrics


if __name__ == "__main__":
    configlib.parse_config()
    train()
