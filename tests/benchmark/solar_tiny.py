"""A tiny copy of the benchmark's Solar-Open2 cell for CPU tests: the same
code files, with the configuration and traffic files cut to toy sizes
(float32, so the limits can be tight without a chip). ``kimi_tiny.py`` is for
Kimi-Linear, ``keye_tiny.py`` for Keye, ``bench_tiny.py`` for TIGER."""

from __future__ import annotations

import copy
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG_DIR = os.path.join(REPO, "benchmark", "configs", "solar_open2_250b")
FAKE_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
CELL = "solar_open2_serve_lifelong"

#: two periods (GQA, KDA, KDA, KDA twice); hidden 64; GQA 8 / 2 heads of 16;
#: KDA 4 heads x 16, kernel 4; 16 experts (4 held) top 4 of width 32 and 1
#: shared; 3 codebooks of 8 over a base vocabulary of 40; histories to 24
#: items (72 prompt tokens: two chunks of the scan); attention tiles of 32.
TINY = dict(
    hidden_size=64, intermediate_size=128, num_hidden_layers=8,
    num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    moe_intermediate_size=32, num_experts_per_tok=4, n_routed_experts=4,
    n_routed_experts_published=16, first_expert=0, vocab_size=64, base_vocab=40,
    num_codebooks=3, codebook_size=8, sem_id_dim=3, max_items=24,
    compute_dtype="float32", param_dtype="float32",
)
TINY_KDA = dict(head_dim=16, num_heads=4)
TINY_ASSUMED = dict(attention_query_tile=32, reference_query_block=16,
                    control_requests=8,
                    catalog_items=120, beam=4)
TINY_SERVE = dict(max_slots=4, page_size=8, num_pages=512, batch_buckets=[1, 2],
                  history_buckets=[8, 24], max_batch=2, max_wait_ms=1.0,
                  prefix_cache_entries=48)
LIMITS = {"serve": {"score_gap": 2e-3, "beam_gap": 2e-3, "bad_items": 0,
                    "state_gap": 2e-3, "state_bf16_share": 0.5}}


def tiny_config(**over) -> dict:
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        cfg = json.load(f)
    cfg.update(copy.deepcopy(TINY))
    cfg["linear_attn_config"].update(TINY_KDA)
    cfg["assumed"].update(copy.deepcopy(TINY_ASSUMED))
    cfg["assumed"]["serve"].update(copy.deepcopy(TINY_SERVE))
    cfg["limits"] = copy.deepcopy(LIMITS)
    cfg.update(over)
    return cfg


def module(stem: str):
    """adapter / reference / flops / check of the configuration, found as the
    harness finds them."""
    from benchmark.harness.spec import load_module

    return load_module(os.path.join(CONFIG_DIR, stem + ".py"),
                       f"configs.solar_open2_250b.{stem}")


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
    with open(os.path.join(bench, "configs", "solar_open2_250b", "config.json"), "w") as f:
        json.dump(tiny_config(), f, indent=1)
    _edit(os.path.join(bench, "traffic", "lifelong_steady.json"),
          lambda t: (t.update(rate_per_s=20.0, n_users=12, preroll_requests=60,
                              check_requests=8, trace_seconds=0.5),
                     t["history_lengths"].update(min_events=4, geometric_p=0.08)))
    return root
