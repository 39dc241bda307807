"""RQ-VAE trainer (parity target: reference genrec/trainers/rqvae_trainer.py).

Loop shape mirrors the reference: epoch- or iteration-based (mutually
exclusive, :91-96), AdamW + linear-warmup schedule (:160-171), grad-clip
1.0, fixed gumbel temperature 0.2 (:215), ~20k-row k-means warmup before
step 0 (:218-228), eval = losses + collision rate over the full item set
(:26-47). Differences, by design:

- k-means warmup is an explicit seeded `kmeans_init_params` call, not a
  throwaway forward on a giant batch (deterministic across replicas,
  SURVEY.md §5.2);
- collision rate is computed on device via sort-unique, no host set();
- on exit the trainer exports the portable sem-id artifact that
  downstream TIGER/LCRec/COBRA datasets consume (data/sem_ids.py).
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
from genrec_tpu.data.batching import pad_to_batch
from genrec_tpu.data.items import ItemEmbeddingData, SyntheticItemEmbeddings
from genrec_tpu.data.sem_ids import save_sem_ids
from genrec_tpu.models.rqvae import (
    QuantizeForwardMode,
    RqVae,
    count_distinct,
    kmeans_init_params,
)
from genrec_tpu.ops.schedules import linear_schedule_with_warmup
from genrec_tpu.parallel import distributed_init, get_mesh, replicate


@functools.partial(jax.jit, static_argnums=0)
def _sem_ids_of(model, params, x):
    out = model.apply({"params": params}, x, 0.001, method=RqVae.get_semantic_ids)
    return out.sem_ids


@functools.partial(jax.jit, static_argnums=0)
def _sem_ids_of_pallas(model, params, x):
    """Encode with the MLP, then run the fused residual-cascade kernel
    (kernels/rq_cascade.py) — one VMEM-resident pass over all layers."""
    import jax.numpy as jnp

    from genrec_tpu.kernels.rq_cascade import rq_cascade_pallas

    enc = model.apply({"params": params}, x, method=RqVae.encode)
    codebooks = jnp.stack(
        [params[f"quantize_{l}"]["codebook"] for l in range(model.n_layers)]
    )
    ids, _ = rq_cascade_pallas(enc, codebooks)
    return ids


def compute_sem_ids(model, params, embeddings: np.ndarray, batch_size: int = 4096,
                    use_pallas: bool = False):
    """Semantic ids for every item (row i -> item id i+1). The jitted
    forward is cached on (model, shapes), so repeated evals don't
    recompile. The fused Pallas cascade (raw codebooks only — no sim_vq
    projection / normalization) is opt-in: measured on v5e the XLA path
    runs the cascade in 0.16ms vs the kernel's 1.49ms at B2048/K256 —
    XLA's own fusion wins at rqvae scales, so the kernel is kept
    validated (kernels/preflight.py) but off by default."""
    fused_ok = use_pallas and not (model.codebook_sim_vq or model.codebook_normalize)
    fn = _sem_ids_of_pallas if fused_ok else _sem_ids_of
    chunks = []
    for s in range(0, len(embeddings), batch_size):
        chunk = {"x": embeddings[s : s + batch_size]}
        n_real = len(chunk["x"])
        padded, _ = pad_to_batch(chunk, batch_size)
        chunks.append(np.asarray(fn(model, params, padded["x"]))[:n_real])
    return np.concatenate(chunks)


def compute_collision_rate(model, params, embeddings: np.ndarray):
    sem_ids = compute_sem_ids(model, params, embeddings)
    n = len(sem_ids)
    unique = int(count_distinct(jnp.asarray(sem_ids)))
    return (n - unique) / n, n, unique


@configlib.configurable
def train(
    epochs=None,
    iterations=None,
    warmup_epochs=0,
    warmup_iters=0,
    batch_size=1024,
    learning_rate=1e-3,
    weight_decay=1e-4,
    vae_input_dim=768,
    vae_n_cat_feats=0,
    vae_hidden_dims=(512, 256, 128, 64),
    vae_embed_dim=32,
    vae_codebook_size=256,
    vae_codebook_normalize=False,
    vae_sim_vq=False,
    vae_n_layers=3,
    vae_codebook_mode=QuantizeForwardMode.STE,
    vae_codebook_last_layer_mode=QuantizeForwardMode.SINKHORN,
    commitment_weight=0.25,
    gumbel_temperature=0.2,
    use_kmeans_init=True,
    kmeans_warmup_rows=20000,
    dataset="synthetic",
    dataset_folder="dataset/amazon",
    split="beauty",
    do_eval=True,
    eval_every=50,
    save_model_every=50,
    save_dir_root="out/rqvae",
    resume_from_checkpoint=False,
    sem_ids_path=None,
    wandb_logging=False,
    wandb_project="rqvae_training",
    wandb_log_interval=100,
    profile_steps=0,
    seed=0,
):
    if (epochs is None) == (iterations is None):
        raise ValueError("specify exactly one of 'epochs' or 'iterations'")
    use_epochs = epochs is not None

    distributed_init()
    logger = setup_logger(save_dir_root)
    tracker = Tracker(wandb_logging, wandb_project, save_dir=save_dir_root)
    mesh = get_mesh()

    if dataset == "synthetic":
        src = SyntheticItemEmbeddings(dim=vae_input_dim, seed=seed)
        train_x, eval_x = src.arrays()
        all_x = src.embeddings
    elif dataset == "p5":
        # Reference default source (P5AmazonReviewsItemDataset): items
        # filtered by the seed-42 train mask (p5_amazon.py:365-367).
        from genrec_tpu.data.p5_amazon import P5AmazonData, item_train_mask

        p5 = P5AmazonData(dataset_folder, split)
        all_x = p5.item_embeddings()  # one disk read
        mask = item_train_mask(len(all_x))
        train_x, eval_x = all_x[mask], all_x[~mask]
    else:
        src = ItemEmbeddingData(root=dataset_folder, split=split)
        train_x, eval_x = src.arrays()
        all_x = src.embeddings

    model = RqVae(
        input_dim=vae_input_dim,
        embed_dim=vae_embed_dim,
        hidden_dims=tuple(vae_hidden_dims),
        codebook_size=vae_codebook_size,
        codebook_normalize=vae_codebook_normalize,
        codebook_sim_vq=vae_sim_vq,
        codebook_mode=vae_codebook_mode,
        codebook_last_layer_mode=vae_codebook_last_layer_mode,
        n_layers=vae_n_layers,
        commitment_weight=commitment_weight,
        n_cat_features=vae_n_cat_feats,
    )

    rng = jax.random.key(seed)
    init_rng, km_rng, state_rng = jax.random.split(rng, 3)
    params = model.init(
        {"params": init_rng, "gumbel": init_rng},
        jnp.zeros((2, vae_input_dim), jnp.float32),
        0.2,
    )["params"]

    if use_kmeans_init:
        warm = train_x[:kmeans_warmup_rows]
        params = kmeans_init_params(model, params, jnp.asarray(warm), km_rng)
        logger.info(f"kmeans init on {len(warm)} rows")

    steps_per_epoch = max(1, len(train_x) // batch_size)
    if epochs is not None:
        total_steps = epochs * steps_per_epoch
        warmup_steps = warmup_epochs * steps_per_epoch
    else:
        total_steps = iterations
        warmup_steps = warmup_iters
        epochs = (iterations + steps_per_epoch - 1) // steps_per_epoch

    schedule = linear_schedule_with_warmup(learning_rate, warmup_steps, total_steps)
    optimizer = optax.adamw(schedule, weight_decay=weight_decay)

    def loss_fn(p, batch, step_rng):
        out = model.apply(
            {"params": p}, batch["x"], gumbel_temperature, training=True,
            rngs={"gumbel": step_rng},
        )
        return out.loss, {
            "reconstruction_loss": out.reconstruction_loss,
            "rqvae_loss": out.rqvae_loss,
            "p_unique_ids": out.p_unique_ids,
        }

    step_fn = jit_train_step(make_train_step(
        loss_fn, optimizer, clip_norm=1.0, name="rqvae_train_step"))
    state = replicate(mesh, TrainState.create(params, optimizer, state_rng))

    @jax.jit
    def eval_losses(p, x):
        out = model.apply({"params": p}, x, gumbel_temperature, training=False)
        return out.loss, out.reconstruction_loss, out.rqvae_loss

    from genrec_tpu.core.checkpoint import CheckpointManager
    from genrec_tpu.core.preemption import PreemptionGuard
    from genrec_tpu.trainers.packed_loop import PackedTrainLoop

    ckpt = CheckpointManager(os.path.join(save_dir_root, "checkpoints")) if save_dir_root else None
    prof = ProfileWindow(
        os.path.join(save_dir_root, "profile") if save_dir_root else "",
        profile_steps,
    )
    guard = PreemptionGuard(logger)

    def step_log(m, g):
        return {
            "global_step": g,
            "total_loss": float(m["loss"]),
            "reconstruction_loss": float(m["reconstruction_loss"]),
            "rqvae_loss": float(m["rqvae_loss"]),
            "p_unique_ids": float(m["p_unique_ids"]),
            "learning_rate": float(schedule(g)),
        }

    def step_hook(hook_state, epoch, next_batch, g):
        if use_epochs:
            return
        # Iteration mode gates eval/save on ITERATIONS (reference
        # rqvae_trainer.py:393,419), not derived epochs.
        if do_eval and g % eval_every == 0:
            le = eval_losses(hook_state.params, jnp.asarray(eval_x))
            cr, n, uniq = compute_collision_rate(model, hook_state.params, all_x)
            logger.info(
                f"iter {g} eval loss {float(le[0]):.4f} "
                f"collision {cr:.4f} ({uniq}/{n})"
            )
        if g % save_model_every == 0:
            loop.save(hook_state, epoch=epoch, next_batch=next_batch,
                      global_step=g)

    loop = PackedTrainLoop(
        logger=logger, tracker=tracker, prof=prof, mesh=mesh,
        guard=guard, ckpt=ckpt,
        rows_per_step=batch_size, row_len=1, seed=seed,
        pack_sequences=False, train_arrays={"x": train_x},
        wandb_log_interval=wandb_log_interval,
        save_dir_root=save_dir_root,
        step_log=step_log, step_hook=step_hook,
    )
    start_epoch, start_batch, global_step = 0, 0, 0
    if resume_from_checkpoint:
        # Step-granular exact resume (TrainState + data cursor through
        # the integrity ladder): continues at the exact next batch of a
        # possibly mid-epoch resume point.
        state, start_epoch, start_batch, global_step = loop.resume(
            state, lambda s: replicate(mesh, s)
        )
    for epoch in range(start_epoch, epochs):
        res = loop.run_epoch(
            state, step_fn, epoch, global_step,
            start_batch=start_batch if epoch == start_epoch else 0,
            max_steps=None if use_epochs else total_steps,
        )
        state, global_step = res.state, res.global_step
        if res.preempted:
            # SIGTERM/SIGINT grace window: the loop already wrote a
            # durable mid-epoch resume point; exit cleanly so the
            # scheduler restarts us with resume_from_checkpoint.
            loop.shutdown(preempted_epoch=epoch)
            return state.params, None

        if use_epochs and do_eval and ((epoch + 1) % eval_every == 0 or epoch + 1 == epochs):
            le = eval_losses(state.params, jnp.asarray(eval_x))
            cr, n, uniq = compute_collision_rate(model, state.params, all_x)
            logger.info(
                f"epoch {epoch+1} eval loss {float(le[0]):.4f} rec {float(le[1]):.4f} "
                f"vq {float(le[2]):.4f} collision {cr:.4f} ({uniq}/{n})"
            )
            tracker.log(
                {
                    "eval_total_loss": float(le[0]),
                    "eval_reconstruction_loss": float(le[1]),
                    "eval_rqvae_loss": float(le[2]),
                    "collision_rate": cr,
                    "unique_semantic_ids": uniq,
                }
            )

        if ckpt is not None and (
            (use_epochs and ((epoch + 1) % save_model_every == 0 or epoch + 1 == epochs))
            or (not use_epochs and epoch + 1 == epochs)
        ):
            # Epoch-boundary resume point (cursor = next epoch, batch 0):
            # one resumable step-keyed format everywhere, and the
            # unconditional final-epoch save means even a signal during
            # the LAST epoch's eval window leaves a resumable record.
            loop.save(state, epoch=epoch + 1, next_batch=0,
                      global_step=global_step)

    # Export the portable sem-id artifact for downstream stages.
    sem_ids = compute_sem_ids(model, state.params, all_x)
    out_path = sem_ids_path or os.path.join(save_dir_root, "sem_ids.npz")
    save_sem_ids(out_path, sem_ids, vae_codebook_size)
    logger.info(f"exported semantic ids -> {out_path}")
    loop.shutdown()
    return state.params, sem_ids


if __name__ == "__main__":
    configlib.parse_config()
    train()
