"""Drive the genrec_tpu trainer on the shared synthetic data (CPU backend).

Calls the real trainer train() with the SAME hyperparameters as
run_ref.py (scripts/parity/hparams.py) and extracts the per-epoch valid
curve from the Tracker's metrics.jsonl plus the returned final metrics.

Usage: python -m scripts.parity.run_tpu sasrec --root dataset/parity \
           --out results/parity/tpu_sasrec.json [--epochs N]
"""

from __future__ import annotations

import argparse
import json
import os


def run_model(model: str, root: str, split: str, out_path: str, epochs: int | None):
    # The parity comparison runs on the CPU whatever the session names.
    import jax

    from genrec_tpu.parallel.mesh import pin_platform

    pin_platform("cpu")

    from scripts.parity import hparams, synth

    hp = dict(hparams.BY_MODEL[model])
    if epochs:
        hp["epochs"] = epochs
    extra = {}
    dataset = "amazon"
    if model == "sasrec":
        from genrec_tpu.trainers.sasrec_trainer import train

        # Strict layout parity with the torch reference: one example per
        # left-padded row, absolute positions (packing is a beyond-parity
        # throughput feature; its exactness is pinned separately by
        # tests/test_packed_parity.py).
        extra = dict(pack_sequences=False)
    elif model == "hstu":
        from genrec_tpu.trainers.hstu_trainer import train

        extra = dict(pack_sequences=False)  # see sasrec note
    elif model == "tiger":
        from genrec_tpu.trainers.tiger_trainer import train

        # Shared sem-id artifact (same table the reference adapter uses);
        # mirror the reference run's eval cadence (valid every 2 epochs).
        extra = dict(
            sem_ids_path=synth.ensure_sem_ids(
                root, split, codebook_size=hp["codebook_size"],
                sem_id_dim=hp["sem_id_dim"],
            ),
            eval_every_epoch=2,
            eval_batch_size=hp["batch_size"],
            # Protocol match: the reference TIGER trainer evaluates test
            # with FINAL-epoch weights (no best tracking).
            test_on_best=False,
            pack_sequences=False,  # strict layout parity (see sasrec note)
        )
    elif model == "cobra":
        from genrec_tpu.data.amazon import load_sequences
        from genrec_tpu.data.cobra_seq import CobraSeqData
        from genrec_tpu.data.sem_ids import load_sem_ids
        from genrec_tpu.trainers.cobra_trainer import train

        sem_path = synth.ensure_sem_ids(
            root, split, codebook_size=hp["id_vocab_size"],
            sem_id_dim=hp["n_codebooks"],
        )
        table = synth.item_token_table(
            max_text_len=hp["max_text_len"], vocab=hp["encoder_vocab_size"]
        )
        max_items = hp["max_items"]

        def dataset():  # callable-dataset hook (mirrors the reference's)
            seqs, _, _ = load_sequences(root, split, download=False)
            sem_ids, K = load_sem_ids(sem_path)
            return CobraSeqData(
                seqs, sem_ids, table, id_vocab_size=K, max_items=max_items
            )

        # Name mapping onto our trainer's signature.
        hp["infonce_temperature"] = hp.pop("temperature")
        del hp["max_text_len"]  # carried by the shared token table
        extra = dict(
            # epochs+1: no in-loop valid eval at all — the post-loop
            # final-weights valid eval IS the comparison point (the
            # reference COBRA loop has no test eval), and this matches
            # run_ref's empty valid_curve without evaluating twice.
            eval_every_epoch=hp["epochs"] + 1,
            eval_batch_size=hp["batch_size"],
            test_on_best=False,  # reference protocol: final-epoch weights
        )
    elif model == "lcrec":
        from genrec_tpu.trainers.lcrec_trainer import train

        synth.ensure_meta(root, split)
        qwen_dir = synth.ensure_tiny_qwen(root)
        sem_path = synth.ensure_sem_ids(
            root, split, codebook_size=hp["codebook_size"],
            sem_id_dim=hp["num_codebooks"],
        )
        # Reference warmup is a ratio of total steps
        # (lcrec_trainer.py:343-344); ours takes absolute steps.
        steps_per_epoch = hp["max_train_samples"] // hp["batch_size"]
        num_warmup = int(hp["warmup_ratio"] * steps_per_epoch * hp["epochs"])
        # The reference's task-opportunity weights
        # (amazon_lcrec.py:214-221), normalized onto our per-sample
        # categorical over data.lcrec_tasks.TASKS (same task order).
        ref_w = (1.0, 0.5, 0.5, 0.5, 0.3, 0.3)
        task_weights = tuple(w / sum(ref_w) for w in ref_w)
        # samples_per_user so OUR sampler can fill the same train budget
        # the reference's per-position generator is capped to; scaled to
        # the root's ACTUAL user count (run_all --n-users roots differ).
        spu = max(
            1, -(-hp["max_train_samples"] // synth.users_in(root, split))
        )
        hp_map = dict(
            epochs=hp["epochs"], batch_size=hp["batch_size"],
            learning_rate=hp["learning_rate"],
            weight_decay=hp["weight_decay"],
            num_warmup_steps=num_warmup,
            num_codebooks=hp["num_codebooks"],
            codebook_size=hp["codebook_size"],
            beam_width=hp["eval_beam_width"],
            max_text_len=hp["max_length"],
            max_history=hp["max_seq_len"],
            samples_per_user=spu,
            max_train_samples=hp["max_train_samples"],
            max_eval_samples=hp["max_eval_samples"],
            eval_batch_size=hp["eval_batch_size"],
            amp=hp["amp"],
        )
        hp.clear()
        hp.update(hp_map)
        extra = dict(
            sem_ids_path=sem_path,
            pretrained_path=qwen_dir,
            task_weights=task_weights,
            eval_every_epoch=1,
            save_every_epoch=10_000,
            use_fused_ce=False,  # CPU parity run; auto would be off anyway
            test_on_best=False,  # reference protocol: final-epoch weights
        )
    elif model == "rqvae":
        _run_rqvae(root, split, out_path, hp)
        return
    else:
        raise ValueError(f"unsupported model {model!r}")

    save_dir = os.path.join(os.path.dirname(out_path) or ".", f"tpu_{model}_rundir")
    # Start from an empty rundir: Tracker appends to metrics.jsonl (curves
    # would interleave) and BestTracker seeds itself from a leftover
    # best_model.json (a stale best would be reported as THIS run's test
    # metrics).
    import shutil

    shutil.rmtree(save_dir, ignore_errors=True)
    os.makedirs(save_dir, exist_ok=True)
    valid_metrics, test_metrics = train(
        dataset=dataset, dataset_folder=root, split=split,
        save_dir_root=save_dir, wandb_logging=False, seed=0, **hp, **extra,
    )

    curve = []
    with open(os.path.join(save_dir, "metrics.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            if "eval/Recall@10" in rec:
                curve.append(
                    {
                        k.removeprefix("eval/"): v
                        for k, v in rec.items()
                        if k.startswith("eval/")
                    }
                )

    out = {
        "model": model,
        "framework": "genrec_tpu",
        "hparams": hp,
        "valid_curve": curve,
        "valid_final": valid_metrics,
        "test": test_metrics,
    }
    if model in ("cobra", "lcrec"):
        # The reference COBRA and LCRec trainers have no test eval;
        # compare on the final-epoch valid eval (same weights, same split
        # on both sides).
        out["test"] = valid_metrics
        out["protocol_note"] = (
            "'test' is the final-epoch valid eval to match the reference "
            f"{model} trainer (which never evaluates its test split)"
        )
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    # Print the SAME 'test' the artifact carries (for cobra that is the
    # protocol-adjusted value) so stdout and JSON never contradict.
    print(json.dumps({"model": model, "framework": "genrec_tpu", "test": out["test"]}))


def _run_rqvae(root: str, split: str, out_path: str, hp: dict):
    """RQ-VAE stage 1 on the shared fabricated embeddings through the
    trainer's own 'amazon' path (ItemEmbeddingData reads
    <root>/processed/<split>_item_emb.npy — we place the shared matrix
    there; the 95/5 split function is shared by construction)."""
    import shutil

    import numpy as np

    from genrec_tpu.trainers.rqvae_trainer import train
    from scripts.parity import synth

    emb_path = os.path.join(root, "processed", f"{split}_item_emb.npy")
    os.makedirs(os.path.dirname(emb_path), exist_ok=True)
    emb = synth.item_embedding_matrix(dim=hp["vae_input_dim"])
    np.save(emb_path, emb)

    save_dir = os.path.join(os.path.dirname(out_path) or ".", "tpu_rqvae_rundir")
    shutil.rmtree(save_dir, ignore_errors=True)
    os.makedirs(save_dir, exist_ok=True)
    train(
        epochs=hp["epochs"], warmup_epochs=hp.get("warmup_epochs", 0),
        batch_size=hp["batch_size"], learning_rate=hp["learning_rate"],
        weight_decay=hp["weight_decay"],
        vae_input_dim=hp["vae_input_dim"], vae_n_cat_feats=0,
        vae_hidden_dims=tuple(hp["vae_hidden_dims"]),
        vae_embed_dim=hp["vae_embed_dim"],
        vae_codebook_size=hp["vae_codebook_size"],
        vae_n_layers=hp["vae_n_layers"],
        commitment_weight=hp["commitment_weight"],
        dataset="amazon", dataset_folder=root, split=split,
        do_eval=True, eval_every=hp["eval_every"],
        save_model_every=10**9, save_dir_root=save_dir, wandb_logging=False,
        seed=0,
    )

    collisions, losses = [], []
    with open(os.path.join(save_dir, "metrics.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            if "collision_rate" in rec:
                collisions.append({"collision_rate": rec["collision_rate"]})
            if "eval_total_loss" in rec:
                losses.append({
                    k: rec[k]
                    for k in ("eval_total_loss", "eval_reconstruction_loss",
                              "eval_rqvae_loss")
                    if k in rec
                })
    out = {
        "model": "rqvae",
        "framework": "genrec_tpu",
        "hparams": hp,
        "collision_curve": collisions,
        "loss_curve": losses,
        "test": {
            **(collisions[-1] if collisions else {}),
            **(losses[-1] if losses else {}),
        },
    }
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"model": "rqvae", "framework": "genrec_tpu",
                      "test": out["test"]}))


def main():
    p = argparse.ArgumentParser()
    p.add_argument(
        "model",
        choices=["sasrec", "hstu", "tiger", "cobra", "rqvae", "lcrec"],
    )
    p.add_argument("--root", default="dataset/parity")
    p.add_argument("--split", default="beauty")
    p.add_argument("--out", required=True)
    p.add_argument("--epochs", type=int, default=None)
    a = p.parse_args()
    run_model(a.model, a.root, a.split, a.out, a.epochs)


if __name__ == "__main__":
    main()
