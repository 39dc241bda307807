"""TIGER train-step profiling on hardware: where does the step time go?

VERDICT r3 weak #4: the 16.46 ms/step headline (B=256, bf16) was estimated
~35% MFU at the time; XLA cost analysis later measured 21.8% for the same
configuration (superseded — see docs/PERF.md). This script:

1. times the jitted train step at several batch sizes (256/512/1024),
2. computes achieved FLOP/s and MFU from the XLA cost analysis,
3. captures a jax.profiler trace for the best configuration,
4. prints a JSON summary (committed to results/tpu/profile_summary.json
   by the caller).

Run on the chip:  python scripts/profile_tiger.py [--trace-dir out/trace]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))



def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace-dir", default="out/trace")
    ap.add_argument("--batches", type=int, nargs="+", default=[256, 512, 1024])
    ap.add_argument("--out", default="results/tpu/profile_summary.json")
    ap.add_argument(
        "--platform", default=None, choices=("cpu", "tpu"),
        help="pin the JAX platform (same as JAX_PLATFORMS, from the "
             "command line)",
    )
    args = ap.parse_args()

    import jax

    from genrec_tpu.parallel.mesh import enable_compile_cache, pin_platform

    if args.platform:
        pin_platform(args.platform)
    enable_compile_cache()
    import jax.numpy as jnp
    import numpy as np
    import optax

    from bench import BENCH_ITEMS, TIGER_BENCH_ARCH, device_peaks
    from genrec_tpu.core.harness import make_train_step
    from genrec_tpu.core.state import TrainState
    from genrec_tpu.models.tiger import Tiger
    from genrec_tpu.obs.spans import SpanTracer

    from genrec_tpu.parallel.mesh import device_summary

    device = device_summary()
    # MFU is a share of THIS device's published peak: a device_kind that
    # is not in bench.DEVICE_PEAKS is an error here, before any work.
    peak_flops = device_peaks(device["kind"])["bf16_flops"]
    summary: dict = {"backend": device["platform"], "device": device,
                     "peak_flops": peak_flops, "configs": []}

    model = Tiger(**TIGER_BENCH_ARCH, dtype=jnp.bfloat16)
    D = TIGER_BENCH_ARCH["sem_id_dim"]
    L = BENCH_ITEMS * D
    optimizer = optax.adamw(1e-4)

    best = None
    for B in args.batches:
        rng = np.random.default_rng(0)
        batch = dict(
            user_ids=jnp.asarray(rng.integers(0, 10_000, (B,)), jnp.int32),
            item_input_ids=jnp.asarray(rng.integers(0, 256, (B, L)), jnp.int32),
            token_type_ids=jnp.asarray(
                np.tile(np.arange(D), (B, BENCH_ITEMS)), jnp.int32
            ),
            target_ids=jnp.asarray(rng.integers(0, 256, (B, D)), jnp.int32),
            seq_mask=jnp.ones((B, L), jnp.int32),
        )
        params = model.init(
            jax.random.key(0), batch["user_ids"], batch["item_input_ids"],
            batch["token_type_ids"], batch["target_ids"],
            jnp.broadcast_to(jnp.arange(D), (B, D)), batch["seq_mask"],
        )["params"]

        def loss_fn(p, b, key):
            out = model.apply(
                {"params": p}, b["user_ids"], b["item_input_ids"],
                b["token_type_ids"], b["target_ids"],
                jnp.broadcast_to(jnp.arange(D), (b["user_ids"].shape[0], D)),
                b["seq_mask"], deterministic=False, rngs={"dropout": key},
            )
            return out.loss, {}

        step = jax.jit(
            make_train_step(loss_fn, optimizer, clip_norm=1.0,
                            name="tiger_train_step"),
            donate_argnums=0,
        )
        state = TrainState.create(params, optimizer, jax.random.key(1))

        # FLOP estimate from XLA's own cost analysis of the compiled step.
        lowered = step.lower(state, batch)
        compiled = lowered.compile()
        flops_per_step = float(compiled.cost_analysis()["flops"])

        state, m = step(state, batch)
        jax.block_until_ready(m["loss"])
        n = 30
        t0 = time.perf_counter()
        for _ in range(n):
            state, m = step(state, batch)
        jax.block_until_ready(m["loss"])
        dt = (time.perf_counter() - t0) / n
        entry = {
            "batch_size": B,
            "step_ms": round(dt * 1e3, 3),
            "seq_per_sec": round(B / dt, 1),
            "flops_per_step": flops_per_step,
            "mfu": round(flops_per_step / dt / peak_flops, 4),
        }
        summary["configs"].append(entry)
        print(json.dumps(entry), flush=True)
        if best is None or entry["seq_per_sec"] > best[1]["seq_per_sec"]:
            best = (B, entry, state, batch, step)

    # Trace the best configuration: 10 steps under the profiler.
    B, entry, state, batch, step = best
    os.makedirs(args.trace_dir, exist_ok=True)
    # Host spans beside the device profile, on one clock: the tracer's
    # anchor annotation is in the profile, its anchor span in the dump.
    tracer = SpanTracer()
    jax.profiler.start_trace(args.trace_dir)
    tracer.profile_anchor()
    for i in range(10):
        t0 = time.monotonic()
        state, m = step(state, batch)
        tracer.record_span("train.dispatch", "train-e0", t0, time.monotonic(),
                           step=i)
    t0 = time.monotonic()
    jax.block_until_ready(m["loss"])
    tracer.record_span("train.sync", "train-e0", t0, time.monotonic())
    jax.profiler.stop_trace()
    tracer.dump(os.path.join(args.trace_dir, "host_spans.json"))
    summary["trace_dir"] = args.trace_dir
    summary["best_batch"] = B

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({"summary": args.out, **{k: summary[k] for k in ("backend", "best_batch")}}))


if __name__ == "__main__":
    main()
