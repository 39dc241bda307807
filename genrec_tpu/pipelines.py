"""One-command multi-stage pipelines.

The reference's TIGER/LCRec/COBRA flows require manually sequencing an
RQ-VAE run and a generator run whose configs must agree on artifact paths
(README.md:82-134). This runner executes the stages in order, threading
the sem-id artifact automatically:

    python -m genrec_tpu.pipelines tiger \
        --rqvae-config config/tiger/amazon/rqvae.gin \
        --model-config config/tiger/amazon/tiger.gin \
        --split beauty [--gin k=v ...]

Stage overrides: ``--rqvae-gin`` / ``--model-gin`` apply to one stage;
``--gin`` applies to both.
"""

from __future__ import annotations

import argparse
import os


def run_two_stage(
    trainer_module: str,
    rqvae_config: str,
    model_config: str,
    split: str,
    gin: list[str],
    rqvae_gin: list[str],
    model_gin: list[str],
    workdir: str = "out/pipeline",
):
    import importlib

    from genrec_tpu import configlib
    from genrec_tpu.configlib import clear_bindings, clear_macros, parse_binding
    from genrec_tpu.configlib.parser import parse_file

    sem_path = os.path.join(workdir, split, "sem_ids.npz")

    # Stage 1: RQ-VAE -> sem-id artifact.
    clear_bindings()
    clear_macros()
    parse_file(rqvae_config, substitutions={"split": split})
    for b in gin + rqvae_gin:
        parse_binding(b)
    parse_binding(f"train.sem_ids_path='{sem_path}'")
    from genrec_tpu.trainers import rqvae_trainer

    rqvae_trainer.train()

    # Stage 2: the generator consumes the artifact.
    clear_bindings()
    clear_macros()
    parse_file(model_config, substitutions={"split": split})
    for b in gin + model_gin:
        parse_binding(b)
    parse_binding(f"train.sem_ids_path='{sem_path}'")
    trainer = importlib.import_module(f"genrec_tpu.trainers.{trainer_module}")
    return trainer.train()


def main(argv=None):
    ap = argparse.ArgumentParser(description="genrec_tpu multi-stage pipeline")
    ap.add_argument("pipeline", choices=["tiger", "cobra", "lcrec"])
    ap.add_argument("--rqvae-config", required=True)
    ap.add_argument("--model-config", required=True)
    ap.add_argument("--split", default="beauty")
    ap.add_argument("--gin", action="append", default=[], help="both stages")
    ap.add_argument("--rqvae-gin", action="append", default=[])
    ap.add_argument("--model-gin", action="append", default=[])
    ap.add_argument("--workdir", default="out/pipeline")
    ap.add_argument(
        "--platform", default=None, choices=("cpu", "tpu"),
        help="pin the JAX platform (same as JAX_PLATFORMS, from the "
             "command line)",
    )
    args = ap.parse_args(argv)
    from genrec_tpu.parallel.mesh import enable_compile_cache, pin_platform

    if args.platform:
        pin_platform(args.platform)
    enable_compile_cache()
    return run_two_stage(
        f"{args.pipeline}_trainer",
        args.rqvae_config,
        args.model_config,
        args.split,
        args.gin,
        args.rqvae_gin,
        args.model_gin,
        args.workdir,
    )


if __name__ == "__main__":
    main()
