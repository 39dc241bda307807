"""NoteLLM (Query2Embedding) trainer — BEYOND the reference.

The reference ships NoteLLM as library code only ("no trainer or config
in-repo", genrec/models/notellm.py; SURVEY.md §2.1); this trainer makes
the family trainable end to end: paired contrastive SFT over interleaved
(query, positive) batches with the learnable temperature tau trained
jointly with the backbone, evaluated as paired top-k retrieval accuracy
(reference compute_metrics, notellm.py:236-265) on held-out topics.

Loop shape mirrors every other trainer here: one jitted SPMD step
(core/harness.make_train_step), data-parallel mesh, host-prefetched
batches, orbax checkpoints with auto-resume, BestTracker on the
retrieval metric, JSONL/wandb logging, per-epoch seq/s/chip.
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
from genrec_tpu.data.batching import batch_iterator, prefetch_to_device
from genrec_tpu.data.notellm_pairs import NoteLLMPairData
from genrec_tpu.models.backbones.qwen import QwenConfig, QwenLM
from genrec_tpu.models.notellm import paired_topk_accuracy, query2embedding_forward
from genrec_tpu.ops.schedules import cosine_schedule_with_warmup
from genrec_tpu.parallel import distributed_init, get_mesh, to_host


def _flatten_pairs(batch):
    """(B, 2, ...) pair-unit arrays -> (2B, ...) interleaved rows."""
    return {
        k: v.reshape((-1,) + v.shape[2:]) for k, v in batch.items()
    }


def make_embed_fn(model):
    @jax.jit
    def embed(params, batch):
        out = query2embedding_forward(
            model, params["backbone"], batch["input_ids"],
            batch["attention_mask"], batch["emb_idx"], params["tau"],
            return_loss=False,
        )
        return out.sentence_embedding

    return embed


def evaluate_retrieval(embed_fn, params, arrays, batch_pairs, mesh, topk=5):
    """Paired top-k accuracy over the full eval set (embeddings gathered
    on host; the sim matrix spans every eval pair, not one batch)."""
    embs = []
    # Prefetching iterator: H2D transfer overlaps the embed compute.
    for sharded, valid in prefetch_to_device(batch_iterator(arrays, batch_pairs), mesh):
        e = to_host(embed_fn(params, _flatten_pairs(sharded)))
        n = int(valid.sum())
        embs.append(e.reshape(-1, 2, e.shape[-1])[:n])
    flat = jnp.asarray(np.concatenate(embs).reshape(-1, embs[0].shape[-1]))
    return {f"top{topk}_acc": paired_topk_accuracy(flat, topk=topk)}


@configlib.configurable
def train(
    epochs=4,
    batch_pairs=16,
    learning_rate=1e-3,
    num_warmup_steps=20,
    weight_decay=0.01,
    max_text_len=12,
    num_topics=64,
    eval_topics=16,
    pairs_per_topic=4,
    hidden_size=64,
    intermediate_size=128,
    n_layers=2,
    num_heads=4,
    num_kv_heads=2,
    tau_init=3.0,
    eval_topk=5,
    do_eval=True,
    eval_every_epoch=2,
    eval_batch_pairs=16,
    resume_from_checkpoint=False,
    save_dir_root="out/notellm",
    save_every_epoch=10,
    wandb_logging=False,
    wandb_project="notellm_training",
    wandb_log_interval=50,
    amp=True,
    mixed_precision_type="bf16",
    profile_steps=0,
    seed=0,
):
    distributed_init()
    logger = setup_logger(save_dir_root)
    tracker = Tracker(wandb_logging, wandb_project, save_dir=save_dir_root)
    mesh = get_mesh()
    compute_dtype = (
        jnp.bfloat16 if (amp and mixed_precision_type == "bf16") else jnp.float32
    )

    rng = jax.random.key(seed)
    init_rng, state_rng = jax.random.split(rng)

    data = NoteLLMPairData(
        num_topics=num_topics, eval_topics=eval_topics,
        max_len=max_text_len, seed=seed,
    )
    cfg = QwenConfig(
        vocab_size=data.tokenizer.vocab_size, hidden_size=hidden_size,
        intermediate_size=intermediate_size, num_hidden_layers=n_layers,
        num_attention_heads=num_heads, num_key_value_heads=num_kv_heads,
        max_position_embeddings=max_text_len, rope_theta=10000.0,
        tie_word_embeddings=False,
    )
    model = QwenLM(cfg, dtype=compute_dtype)
    backbone = model.init(init_rng, jnp.zeros((1, 4), jnp.int32))["params"]
    # tau is trained jointly (reference notellm.py:170: learnable
    # temperature, exp'd in the loss).
    params = {"backbone": backbone, "tau": jnp.asarray(tau_init, jnp.float32)}
    logger.info(
        f"NoteLLM backbone {hidden_size}d x {n_layers} layers, "
        f"vocab {cfg.vocab_size}, tau_init {tau_init}"
    )

    train_arrays = data.train_arrays(pairs_per_topic)
    eval_arrays = data.eval_arrays()
    steps_per_epoch = max(1, len(train_arrays["input_ids"]) // batch_pairs)
    schedule = cosine_schedule_with_warmup(
        learning_rate, num_warmup_steps, epochs * steps_per_epoch
    )
    # Decay only matrix-shaped weights: tau is a plain learnable scalar
    # (reference notellm.py:170 — no decay; CLIP-style practice excludes
    # the logit scale) and norm vectors are conventionally undecayed too.
    optimizer = optax.adamw(
        schedule, weight_decay=weight_decay,
        mask=lambda p: jax.tree_util.tree_map(lambda x: jnp.ndim(x) >= 2, p),
    )

    def loss_fn(p, batch, step_rng):
        flat = _flatten_pairs(batch)
        out = query2embedding_forward(
            model, p["backbone"], flat["input_ids"], flat["attention_mask"],
            flat["emb_idx"], p["tau"],
            pair_groups=batch["topic_id"],
        )
        return out.loss, {"cl_loss": out.cl_loss}

    step_fn = jit_train_step(make_train_step(
        loss_fn, optimizer, clip_norm=1.0, name="notellm_train_step"))
    from genrec_tpu.parallel import replicate

    state = replicate(mesh, TrainState.create(params, optimizer, state_rng))
    embed_fn = make_embed_fn(model)

    from genrec_tpu.core.checkpoint import BestTracker, CheckpointManager
    from genrec_tpu.core.preemption import PreemptionGuard
    from genrec_tpu.trainers.packed_loop import PackedTrainLoop

    ckpt = (
        CheckpointManager(os.path.join(save_dir_root, "checkpoints"))
        if save_dir_root
        else None
    )
    best = BestTracker(save_dir_root, metric=f"top{eval_topk}_acc")
    prof = ProfileWindow(
        os.path.join(save_dir_root, "profile") if save_dir_root else "",
        profile_steps,
    )
    guard = PreemptionGuard(logger)
    loop = PackedTrainLoop(
        logger=logger, tracker=tracker, prof=prof, mesh=mesh,
        guard=guard, ckpt=ckpt,
        rows_per_step=batch_pairs, row_len=max_text_len, seed=seed,
        pack_sequences=False, train_arrays=train_arrays,
        # 2 rows per pair-unit row: seq/s counts sequences, like every
        # other trainer.
        examples_per_row=2.0,
        wandb_log_interval=wandb_log_interval,
        save_dir_root=save_dir_root,
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
            # durable mid-epoch resume point; exit cleanly so the
            # scheduler restarts us with resume_from_checkpoint.
            loop.shutdown(preempted_epoch=epoch)
            return {}

        if ckpt is not None and (epoch + 1) % save_every_epoch == 0:
            # Epoch-boundary resume point: cursor = (next epoch, batch 0).
            loop.save(state, epoch=epoch + 1, next_batch=0,
                      global_step=global_step)

        if do_eval and (epoch + 1) % eval_every_epoch == 0:
            m = evaluate_retrieval(
                embed_fn, state.params, eval_arrays, eval_batch_pairs, mesh,
                topk=eval_topk,
            )
            logger.info(
                f"epoch {epoch} eval "
                + ", ".join(f"{k}={v:.4f}" for k, v in m.items())
            )
            tracker.log({"epoch": epoch, **{f"eval/{k}": v for k, v in m.items()}})
            best.update(m[f"top{eval_topk}_acc"], state.params)

    final_params = best.best_params(like=state.params) or state.params
    test_m = evaluate_retrieval(
        embed_fn, final_params, eval_arrays, eval_batch_pairs, mesh, topk=eval_topk
    )
    logger.info("test " + ", ".join(f"{k}={v:.4f}" for k, v in test_m.items()))
    tracker.log({f"test/{k}": v for k, v in test_m.items()})
    # Unconditional final resume point: the trained state is durable even
    # off the save_every_epoch cadence.
    loop.save(state, epoch=epochs, next_batch=0, global_step=global_step)
    loop.shutdown()
    return test_m


if __name__ == "__main__":
    configlib.parse_config()
    train()
