"""TIGER trainer (parity target: reference genrec/trainers/tiger_trainer.py).

Loop shape mirrors the reference: epoch loop, AdamW + cosine warmup
schedule (:223-227), gradient accumulation (:126, 297) and clip-on-sync
(:313-318) — both folded into the single jitted step — and eval via
trie-constrained generate -> TopKAccumulator R@5/10, N@5/10 (:241-288).
The generate path is the jitted beam search of models/tiger.py; the trie
is built once from the dataset's item sem-ids.
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
    pack_examples,
    prefetch_eval_batches,
)
from genrec_tpu.data.tiger_seq import TigerSeqData, synthetic_tiger_data
from genrec_tpu.models.tiger import Tiger, tiger_generate
from genrec_tpu.ops.metrics import TopKAccumulator
from genrec_tpu.ops.schedules import cosine_schedule_with_warmup
from genrec_tpu.ops.trie import build_trie
from genrec_tpu.parallel import distributed_init, get_mesh, make_mesh


def make_generate_fn(model, trie, temperature, n_candidates):
    @jax.jit
    def gen(params, batch, rng):
        out = tiger_generate(
            model, params, trie,
            batch["user_ids"], batch["item_input_ids"], batch["token_type_ids"],
            batch["seq_mask"], rng,
            temperature=temperature, n_top_k_candidates=n_candidates,
        )
        return out.sem_ids

    return gen


def evaluate(gen_fn, params, arrays, batch_size, mesh, rng):
    acc = TopKAccumulator(ks=(5, 10))
    # Same prefetching iterator as the train loop: host batch assembly and
    # H2D transfer overlap the previous batch's generate.
    for sharded, host, valid in prefetch_eval_batches(
        batch_iterator(arrays, batch_size), mesh
    ):
        rng, sub = jax.random.split(rng)
        top = np.asarray(gen_fn(params, sharded, sub))  # (B, K, D)
        n = int(valid.sum())
        acc.accumulate(jnp.asarray(host["target_ids"][:n]), jnp.asarray(top[:n]))
    return acc.reduce(cross_process=True)


@configlib.configurable
def train(
    epochs=100,
    batch_size=256,
    learning_rate=1e-4,
    num_warmup_steps=100,
    weight_decay=0.035,
    gradient_accumulate_every=1,
    embedding_dim=128,
    attn_dim=384,
    dropout=0.1,
    num_heads=6,
    n_layers=8,
    sem_id_dim=3,
    codebook_size=256,
    max_items=20,
    num_user_embeddings=10_000,
    dataset="synthetic",
    dataset_folder="dataset/amazon",
    split="beauty",
    # Synthetic-dataset scale knob (tests/chaos harness shrink it).
    num_users=500,
    sem_ids_path=None,
    add_disambiguation=False,
    tensor_parallel=1,
    # First-fit-decreasing sequence packing of the ENCODER stream: several
    # (user token + history) examples share one row with segment-restricted
    # attention (segments are contiguous, so the T5 relative bias of slot
    # distance is each segment's own); decoders stay per example,
    # cross-attending into their own segment of the packed memory.
    # False restores the original one-example-per-row layout exactly.
    pack_sequences=True,
    # Decoder rows are sized rows x MAX-segments-per-row, so one dense row
    # of tiny histories would make every row pay for its segment count;
    # capping trades a little encoder occupancy for a bounded decoder batch
    # (measured on the Amazon-like distribution: cap 4 keeps occupancy
    # within a few percent and the packed step ~2x padded examples/sec).
    pack_max_segments=4,
    generate_temperature=0.2,
    do_eval=True,
    eval_every_epoch=10,
    eval_batch_size=64,
    # True (default): final valid/test run with the best-valid-Recall@10
    # snapshot (the sasrec/hstu reference protocol). False: final-epoch
    # weights — the reference TIGER trainer's protocol (it keeps no best
    # model, tiger_trainer.py:345); the parity harness uses this.
    test_on_best=True,
    save_dir_root="out/tiger",
    save_every_epoch=100,
    resume_from_checkpoint=False,
    wandb_logging=False,
    wandb_project="tiger_training",
    wandb_log_interval=100,
    amp=True,
    mixed_precision_type="bf16",
    profile_steps=0,
    seed=0,
):
    distributed_init()
    logger = setup_logger(save_dir_root)
    tracker = Tracker(wandb_logging, wandb_project, save_dir=save_dir_root)
    if tensor_parallel > 1:
        # 2-D mesh: batch on "data", vocab/embedding/FFN weights on "model"
        # (parallel/shardings.tiger_rules). XLA inserts the tp collectives.
        mesh = make_mesh({"data": -1, "model": tensor_parallel})
        logger.info(f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}")
    else:
        mesh = get_mesh()

    if dataset == "synthetic":
        data = synthetic_tiger_data(
            codebook_size=codebook_size, sem_id_dim=sem_id_dim,
            max_items=max_items, seed=seed, num_users=num_users,
        )
    else:
        from genrec_tpu.data.amazon import load_sequences
        from genrec_tpu.data.sem_ids import load_sem_ids

        seqs, _, _ = load_sequences(dataset_folder, split)
        if sem_ids_path is None:
            raise ValueError("amazon dataset needs sem_ids_path (RQ-VAE artifact)")
        sem_ids, codebook_size = load_sem_ids(sem_ids_path)
        if add_disambiguation:
            # Optional 4th code resolving sem-id collisions (reference
            # amazon.py:323-353; disabled in its shipped configs). The
            # rank-based PackedTrie handles the deeper id space.
            from genrec_tpu.data.sem_ids import dedup_sem_ids

            sem_ids = dedup_sem_ids(sem_ids, codebook_size)
        data = TigerSeqData(seqs, sem_ids, max_items=max_items,
                            user_hash_size=num_user_embeddings)
        sem_id_dim = data.D

    valid_arrays = data.eval_arrays("valid")
    test_arrays = data.eval_arrays("test")
    trie = build_trie(data.valid_item_sem_ids(), codebook_size)

    pack_row_len = 1 + max_items * sem_id_dim  # user token + item stream
    repack, train_arrays = None, None
    if pack_sequences:
        # Raw examples only — the padded (N, L) train matrix is never
        # materialized when the packer owns layout. Re-packed per epoch
        # (epoch-seeded shuffle) so example co-location is re-mixed like
        # the padded layout's per-epoch permutation; PackedTrainLoop
        # calls this lazily per epoch.
        examples = data.train_examples()

        def repack(epoch: int):
            return pack_examples(
                examples, row_len=pack_row_len,
                segment_keys=("target_ids",), max_segments=pack_max_segments,
                seed=(seed, epoch),
            )

    else:
        train_arrays = data.train_arrays()

    from genrec_tpu.core.checkpoint import BestTracker, CheckpointManager, save_params
    from genrec_tpu.core.preemption import PreemptionGuard
    from genrec_tpu.trainers.packed_loop import PackedTrainLoop

    ckpt = CheckpointManager(os.path.join(save_dir_root, "checkpoints")) if save_dir_root else None
    prof = ProfileWindow(
        os.path.join(save_dir_root, "profile") if save_dir_root else "",
        profile_steps,
    )
    guard = PreemptionGuard(logger)
    # One optimizer step consumes batch_size * accum rows (packed rows
    # hold several examples each; state.step counts optimizer steps).
    rows_per_step = batch_size * gradient_accumulate_every
    loop = PackedTrainLoop(
        logger=logger, tracker=tracker, prof=prof, mesh=mesh,
        guard=guard, ckpt=ckpt,
        rows_per_step=rows_per_step, row_len=pack_row_len, seed=seed,
        pack_sequences=pack_sequences, repack=repack, train_arrays=train_arrays,
        # make_train_step MEANS aux over microbatches; scale real_tokens
        # back to whole-step counts.
        tokens_scale=float(gradient_accumulate_every),
        wandb_log_interval=wandb_log_interval,
        save_dir_root=save_dir_root,
    )
    # Accessing the report here materializes the epoch-0 pack (the jitted
    # loss closure below needs its rates before any resume decision), so a
    # resume at epoch E packs twice for TIGER — seconds, vs the ~30s+ step
    # recompile every restart pays anyway.
    pack_report = loop.pack_report

    compute_dtype = jnp.bfloat16 if (amp and mixed_precision_type == "bf16") else jnp.float32
    model = Tiger(
        embedding_dim=embedding_dim,
        attn_dim=attn_dim,
        dropout=dropout,
        num_heads=num_heads,
        n_layers=n_layers,
        num_item_embeddings=codebook_size,
        num_user_embeddings=num_user_embeddings,
        sem_id_dim=sem_id_dim,
        dtype=compute_dtype,
        # Round vocab/embedding rows up so the TP rules can actually shard
        # them (the natural flat vocab is odd); pad logits are masked.
        pad_vocab_to=tensor_parallel,
    )
    rng = jax.random.key(seed)
    init_rng, state_rng, eval_rng = jax.random.split(rng, 3)
    L = max_items * sem_id_dim
    params = model.init(
        init_rng,
        jnp.zeros((1,), jnp.int32),
        jnp.zeros((1, L), jnp.int32),
        jnp.zeros((1, L), jnp.int32),
        jnp.zeros((1, sem_id_dim), jnp.int32),
        jnp.zeros((1, sem_id_dim), jnp.int32),
        jnp.ones((1, L), jnp.int32),
    )["params"]

    n_train_rows = (
        pack_report.n_rows if pack_sequences
        else next(iter(train_arrays.values())).shape[0]
    )
    opt_steps_per_epoch = max(1, n_train_rows // rows_per_step)
    total_steps = epochs * opt_steps_per_epoch
    schedule = cosine_schedule_with_warmup(learning_rate, num_warmup_steps, total_steps)
    optimizer = optax.adamw(schedule, weight_decay=weight_decay)

    tgt_types = jnp.broadcast_to(jnp.arange(sem_id_dim), (1, sem_id_dim))

    if pack_sequences:
        # Expected examples per microbatch (static). make_train_step
        # averages microbatch losses with EQUAL weight; packed microbatches
        # carry varying example counts, so under accumulation each loss is
        # rescaled by actual/expected count — every example then weighs the
        # same in the averaged gradient (a fixed count makes this exact for
        # unpacked batches; accum=1 keeps the exact mean-over-valid loss).
        expected_per_micro = batch_size * pack_report.n_examples / pack_report.n_rows

        def loss_fn(params, batch, step_rng):
            out = model.apply(
                {"params": params},
                batch["item_input_ids"], batch["token_type_ids"],
                batch["user_token_ids"], batch["user_mask"],
                batch["segment_ids"], batch["positions"],
                batch["target_ids"], batch["segment_valid"],
                deterministic=False,
                rngs={"dropout": step_rng},
                method=Tiger.forward_packed,
            )
            loss = out.loss
            if gradient_accumulate_every > 1:
                count = jnp.sum(batch["segment_valid"]).astype(jnp.float32)
                loss = loss * count / expected_per_micro
            return loss, {"real_tokens": out.real_tokens.astype(jnp.float32)}
    else:
        def loss_fn(params, batch, step_rng):
            B = batch["user_ids"].shape[0]
            out = model.apply(
                {"params": params},
                batch["user_ids"], batch["item_input_ids"], batch["token_type_ids"],
                batch["target_ids"], jnp.broadcast_to(tgt_types, (B, sem_id_dim)),
                batch["seq_mask"],
                deterministic=False,
                rngs={"dropout": step_rng},
            )
            return out.loss, {}

    step_fn = jit_train_step(
        make_train_step(
            loss_fn, optimizer,
            accum_steps=gradient_accumulate_every, clip_norm=1.0,
            name="tiger_train_step_packed" if pack_sequences
            else "tiger_train_step",
        )
    )
    from genrec_tpu.parallel.shardings import make_place_state, tiger_rules

    place_state = make_place_state(
        mesh, tiger_rules() if tensor_parallel > 1 else None, log_fn=logger.info
    )
    state = place_state(TrainState.create(params, optimizer, state_rng))
    gen_fn = make_generate_fn(model, trie, generate_temperature, 10)

    start_epoch, start_batch, global_step = 0, 0, 0
    if resume_from_checkpoint:
        # Step-granular exact resume through the integrity ladder;
        # place_state preserves the tensor-parallel layout on restore.
        state, start_epoch, start_batch, global_step = loop.resume(state, place_state)
    best = BestTracker(save_dir_root)
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

        if do_eval and (epoch + 1) % eval_every_epoch == 0:
            eval_rng, sub = jax.random.split(eval_rng)
            metrics = evaluate(gen_fn, state.params, valid_arrays, eval_batch_size, mesh, sub)
            logger.info(
                f"epoch {epoch} valid " + ", ".join(f"{k}={v:.4f}" for k, v in metrics.items())
            )
            tracker.log({"epoch": epoch, **{f"eval/{k}": v for k, v in metrics.items()}})
            best.update(metrics["Recall@10"], state.params)

        if ckpt is not None and (epoch + 1) % save_every_epoch == 0:
            # Epoch-boundary resume point: cursor = (next epoch, batch 0).
            loop.save(state, epoch=epoch + 1, next_batch=0, global_step=global_step)

    final_params = best.best_params(like=state.params) if test_on_best else None
    if final_params is None:
        final_params = state.params
    eval_rng, s1, s2 = jax.random.split(eval_rng, 3)
    valid_metrics = evaluate(gen_fn, final_params, valid_arrays, eval_batch_size, mesh, s1)
    test_metrics = evaluate(gen_fn, final_params, test_arrays, eval_batch_size, mesh, s2)
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


@register_entry("train/tiger_step", tags=("train",))
def _graftlint_entry() -> BuiltEntry:
    """CI-shape replica of this trainer's jitted step (unpacked path),
    SAME jit config as train() above (accum/clip flags, donate_argnums=0)."""
    import numpy as np

    model = Tiger(embedding_dim=16, attn_dim=32, dropout=0.0, num_heads=4,
                  n_layers=2, num_item_embeddings=8, num_user_embeddings=20,
                  sem_id_dim=3)
    D, B, items = 3, 4, 4
    L = items * D
    rng = np.random.default_rng(0)
    user = jnp.asarray(rng.integers(0, 20, (B,)), jnp.int32)
    ids = jnp.asarray(rng.integers(0, 8, (B, L)), jnp.int32)
    types = jnp.asarray(np.tile(np.arange(D), (B, items)), jnp.int32)
    tgt = jnp.asarray(rng.integers(0, 8, (B, D)), jnp.int32)
    tgt_types = jnp.asarray(np.tile(np.arange(D), (B, 1)), jnp.int32)
    mask = jnp.ones((B, L), jnp.int32)
    params = model.init(
        jax.random.key(0), user, ids, types, tgt, tgt_types, mask
    )["params"]
    optimizer = optax.adamw(1e-3, weight_decay=0.01)

    def loss_fn(p, batch, step_rng):
        out = model.apply(
            {"params": p},
            batch["user_ids"], batch["item_input_ids"],
            batch["token_type_ids"], batch["target_ids"],
            batch["target_token_type_ids"], batch["seq_mask"],
            deterministic=False, rngs={"dropout": step_rng},
        )
        return out.loss, {}

    step_fn = jit_train_step(
        make_train_step(loss_fn, optimizer, accum_steps=1, clip_norm=1.0)
    )
    state = TrainState.create(params, optimizer, jax.random.key(1))
    batch = {
        "user_ids": user, "item_input_ids": ids, "token_type_ids": types,
        "target_ids": tgt, "target_token_type_ids": tgt_types,
        "seq_mask": mask,
    }
    return BuiltEntry(fn=step_fn, args=(state, batch), expect_donated=(0,))


if __name__ == "__main__":
    configlib.parse_config()
    train()
