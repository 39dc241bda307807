"""A tiny copy of the benchmark for CPU tests: the same code files, with the
configuration and traffic files cut to toy sizes (float32, no dropout, so the
limits can be tight without a chip)."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FAKE_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def _edit(path, fn):
    with open(path) as f:
        doc = json.load(f)
    fn(doc)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)


def tiny_root(tmp_path) -> str:
    root = str(tmp_path / "root")
    os.makedirs(root)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = os.path.join(root, "benchmark")

    def cfg(c):
        c.update(embedding_dim=16, attn_dim=32, num_heads=4, head_dim=8,
                 ffn_dim=64, codebook_size=8, num_user_embeddings=50,
                 max_items=4, compute_dtype="float32", dropout=0.0)
        c["assumed"]["catalog_items"] = 60
        c["assumed"]["beam"] = 4
        c["assumed"]["serve"].update(
            num_pages=64, max_slots=8, max_batch=4, batch_buckets=[1, 4],
            history_buckets=[2, 4], prefix_cache_entries=32, page_size=8)
        c["limits"] = {
            "train": {"loss_gap": 1e-4, "grad_gap": 1e-3, "change_gap": 1e-2,
                      "decay_gap": 0.1},
            "serve": {"score_gap": 1e-2, "beam_gap": 1e-2, "bad_items": 0},
        }

    _edit(os.path.join(bench, "configs", "tiger_amazon", "config.json"), cfg)
    _edit(os.path.join(bench, "traffic", "train_packed.json"),
          lambda t: t.update(rows_per_step_per_chip=16, corpus_examples=120,
                             n_users=40, reference_block_rows=64,
                             trace_seconds=0.5, trace_at=0.1))
    _edit(os.path.join(bench, "traffic", "tiger_steady.json"),
          lambda t: t.update(rate_per_s=30.0, n_users=20, check_requests=12,
                             preroll_requests=100, trace_seconds=0.5))
    return root
