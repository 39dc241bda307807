"""Measure the TORCH REFERENCE TIGER train step on this host's CPU.

BASELINE.md committed to replacing the guessed A100 throughput with a
measured torch number. No GPU exists here, but a same-host CPU-vs-CPU
ratio is an honest, reproducible comparison: this script times the
reference implementation (imported from the read-only checkout, gin
stubbed) on the exact shapes bench.py's CPU fallback uses, and writes
BASELINE_MEASURED.json at the repo root. bench.py then reports
``vs_torch_cpu_same_host`` alongside the A100-estimate ratio.

Usage: python scripts/bench_torch_ref.py [--reference /root/reference]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types


def _stub_gin():
    """The reference decorates with gin, which is not installed; identity
    stubs preserve behavior (we only measure, never configure)."""
    gin = types.ModuleType("gin")

    def configurable(fn_or_name=None, *a, **k):
        if callable(fn_or_name):
            return fn_or_name
        return lambda fn: fn

    gin.configurable = configurable
    gin.constants_from_enum = lambda cls=None, **k: cls if cls else (lambda c: c)
    gin.REQUIRED = object()
    sys.modules["gin"] = gin


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reference", default="/root/reference")
    ap.add_argument("--batch-size", type=int, default=None)
    ap.add_argument("--threads", type=int, default=1,
                    help="torch CPU threads; pinned so the measurement is "
                         "reproducible across hosts")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    # Same architecture as bench.py — imported, not copied, so it cannot
    # drift. The batch is this script's own: one CPU core finishes 32.
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo_root)
    from bench import BENCH_ITEMS, TIGER_BENCH_ARCH, host_fingerprint

    _stub_gin()
    sys.path.insert(0, args.reference)
    import numpy as np
    import torch

    from genrec.models.tiger import Tiger  # reference implementation

    torch.set_num_threads(args.threads)
    torch.manual_seed(0)
    B = args.batch_size or 32
    items, D = BENCH_ITEMS, TIGER_BENCH_ARCH["sem_id_dim"]
    L = items * D
    model = Tiger(**TIGER_BENCH_ARCH)
    model.train()
    opt = torch.optim.AdamW(model.parameters(), lr=1e-4)
    rng = np.random.default_rng(0)
    batch = dict(
        user_ids=torch.as_tensor(rng.integers(0, 10_000, (B, 1)), dtype=torch.long),
        item_input_ids=torch.as_tensor(rng.integers(0, 256, (B, L)), dtype=torch.long),
        token_type_ids=torch.as_tensor(np.tile(np.arange(D), (B, items)), dtype=torch.long),
        target_ids=torch.as_tensor(rng.integers(0, 256, (B, D)), dtype=torch.long),
        tgt_types=torch.as_tensor(np.tile(np.arange(D), (B, 1)), dtype=torch.long),
        seq_mask=torch.ones((B, L), dtype=torch.long),
    )

    def step():
        opt.zero_grad(set_to_none=True)
        out = model(
            batch["user_ids"], batch["item_input_ids"], batch["token_type_ids"],
            batch["target_ids"], batch["tgt_types"], batch["seq_mask"],
        )
        out.loss.backward()
        torch.nn.utils.clip_grad_norm_(model.parameters(), 1.0)
        opt.step()
        return float(out.loss)

    step()  # warmup
    t0 = time.perf_counter()
    step()
    per = time.perf_counter() - t0
    n_steps = max(3, min(50, int(15.0 / max(per, 1e-3))))
    t0 = time.perf_counter()
    for _ in range(n_steps):
        loss = step()
    dt = time.perf_counter() - t0

    result = {
        "torch_cpu_seq_per_sec": round(n_steps * B / dt, 3),
        "torch_cpu_step_ms": round(dt / n_steps * 1e3, 2),
        "batch_size": B,
        "n_steps": n_steps,
        "final_loss": round(loss, 4),
        "torch_version": torch.__version__,
        "threads": torch.get_num_threads(),
        "host": host_fingerprint(),
        "arch": dict(TIGER_BENCH_ARCH),
        "note": "reference TIGER fwd+bwd+clip+adamw on this host's CPU (B%d, "
                "L%d); arch imported from bench.TIGER_BENCH_ARCH" % (B, L),
    }
    out_path = args.out or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BASELINE_MEASURED.json",
    )
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
