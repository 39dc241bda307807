"""A tiny copy of the benchmark's Kimi-Linear cell for CPU tests: the same
code files, with the configuration and traffic files cut to toy sizes
(float32, so the limits can be tight without a chip). ``keye_tiny.py`` is for
Keye, ``bench_tiny.py`` for TIGER."""

from __future__ import annotations

import copy
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG_DIR = os.path.join(REPO, "benchmark", "configs", "kimi_linear_48b_a3b")
FAKE_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}

#: hidden 64; KDA 4 heads x 16, kernel 4 (rows of 96 span two chunks of 64);
#: latent attention 4 heads, latent 32, nope 16, rope-part 8, v 16, query tiles of 32; 16 experts
#: top 4 of width 32 and 1 shared; dense width 128; layers as published:
#: KDA + dense, KDA, KDA, MLA, KDA.
TINY = dict(
    hidden_size=64, intermediate_size=128, num_attention_heads=4,
    num_key_value_heads=4, head_dim=16, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, moe_intermediate_size=32,
    num_experts_per_token=4, num_experts=16, num_experts_published=16,
    first_expert=0, vocab_size=80, base_vocab=40, codebook_size=8,
    instruction_tokens=6, compute_dtype="float32",
)
TINY_KDA = dict(head_dim=16, num_heads=4)
TINY_ASSUMED = dict(attention_query_tile=32, reference_query_block=16)


def tiny_config(**over) -> dict:
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        cfg = json.load(f)
    cfg.update(copy.deepcopy(TINY))
    cfg["linear_attn_config"].update(TINY_KDA)
    cfg["assumed"].update(TINY_ASSUMED)
    cfg["limits"] = {"train": {"loss_gap": 1e-4, "grad_gap": 1e-3,
                               "change_gap": 1e-2, "decay_gap": 0.1}}
    cfg.update(over)
    return cfg


def module(stem: str):
    """adapter / reference / flops of the configuration, found as the
    harness finds them."""
    from benchmark.harness.spec import load_module

    return load_module(os.path.join(CONFIG_DIR, stem + ".py"),
                       f"configs.kimi_linear_48b_a3b.{stem}")


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
    with open(os.path.join(bench, "configs", "kimi_linear_48b_a3b", "config.json"), "w") as f:
        json.dump(tiny_config(), f, indent=1)
    _edit(os.path.join(bench, "traffic", "sft_lifelong_8k.json"),
          lambda t: (t.update(row_len=96, corpus_rows=8, trace_seconds=0.5,
                              trace_at=0.1),
                     t["history_tokens"].update(median=66, min=31, max=96)))
    return root
