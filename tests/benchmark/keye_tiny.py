"""A tiny copy of the benchmark's Keye cell for CPU tests: the same code
files, with the configuration and traffic files cut to toy sizes (float32, so
the limits can be tight without a chip). ``bench_tiny.py`` is for TIGER."""

from __future__ import annotations

import copy
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG_DIR = os.path.join(REPO, "benchmark", "configs", "keye_vl2_30b_a3b")
FAKE_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}

#: hidden 64, 4 x 16 heads over 2 KV, indexer 2 x 8, top-k 16 at L = 64 (the
#: selection bites on three quarters of the positions), 16 experts top 4.
TINY = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    moe_intermediate_size=32, num_experts_per_tok=4, num_hidden_layers=2,
    num_experts=16, num_experts_published=16, first_expert=0,
    vocab_size=80, base_vocab=40, codebook_size=8, instruction_tokens=6,
    compute_dtype="float32",
)
TINY_SA = dict(indexer_num_heads=2, indexer_head_dim=8, topk=16,
               q_chunk_size=16, kv_chunk_size=16)


def tiny_config(**over) -> dict:
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        cfg = json.load(f)
    cfg.update(copy.deepcopy(TINY))
    cfg["sa_config"].update(TINY_SA)
    cfg["assumed"]["reference_query_block"] = 16
    cfg["limits"] = {"train": {"loss_gap": 1e-4, "grad_gap": 1e-3,
                               "change_gap": 1e-2, "decay_gap": 0.1}}
    cfg.update(over)
    return cfg


def module(stem: str):
    """adapter / reference / flops of the configuration, found as the
    harness finds them."""
    from benchmark.harness.spec import load_module

    return load_module(os.path.join(CONFIG_DIR, stem + ".py"),
                       f"configs.keye_vl2_30b_a3b.{stem}")


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
    with open(os.path.join(bench, "configs", "keye_vl2_30b_a3b", "config.json"), "w") as f:
        json.dump(tiny_config(), f, indent=1)
    _edit(os.path.join(bench, "traffic", "sft_long_history.json"),
          lambda t: (t.update(row_len=64, corpus_rows=8, trace_seconds=0.5,
                              trace_at=0.1),
                     t["history_tokens"].update(median=44, min=21, max=64)))
    return root
