"""Command-line entry shared by all trainers.

Mirrors the reference CLI contract (reference genrec/modules/utils.py:85-117):

    python -m genrec_tpu.trainers.<x>_trainer <config.gin> \
        [--split beauty] [--gin "k=v"]...

The ``{split}`` placeholder in the config text is substituted before parsing
and ``--gin`` override bindings are applied after the file, so they win.
"""

from __future__ import annotations

import argparse
from typing import Sequence

from genrec_tpu.configlib import parser as _parser


def parse_config(argv: Sequence[str] | None = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="genrec_tpu trainer")
    ap.add_argument("config", help="path to a .gin config file")
    ap.add_argument("--split", default="beauty", help="dataset split substituted for {split}")
    ap.add_argument(
        "--gin",
        action="append",
        default=[],
        metavar="BINDING",
        help='override binding, e.g. --gin "train.epochs=1" (repeatable)',
    )
    ap.add_argument(
        "--platform",
        default=None,
        choices=("cpu", "tpu"),
        help="pin the JAX platform (same as JAX_PLATFORMS, from the command line)",
    )
    args = ap.parse_args(argv)

    if args.platform:
        from genrec_tpu.parallel.mesh import pin_platform

        pin_platform(args.platform)

    _parser.parse_file(args.config, substitutions={"split": args.split})
    for binding in args.gin:
        _parser.parse_binding(binding)
    return args
