"""Hardware checklist (VERDICT r4 next #7, docs/PERF.md): does XLA
partition the compiled fused-CE train step without wrapping the
pallas_call in unexpected full-gathers?

Built on the shared graftlint IR harness (genrec_tpu/analysis/ir.py) —
the CLI, verdict JSON and rc conventions (including rc 2 =
ran-but-inconclusive) are unchanged; only the duplicated
lower/compile/emit plumbing moved there.

Jit the SASRec fused-CE train step under a {"data": n_devices} mesh with
sharded-batch annotations and inspect the optimized HLO around the
Mosaic custom call:

  - `all-gather` results feeding a `tpu_custom_call` operand — a
    full-gather of activations or head weights around the kernel would
    mean GSPMD chose to unshard rather than partition, the failure mode
    the single-chip auto gate guards against (kernels/policy.py).
  - the custom call's operand shapes vs the logical batch: per-device
    row counts equal to the GLOBAL row count on a >1-device mesh mean
    replicated (gathered) inputs even without a literal all-gather op.

HONESTY NOTE (single-chip): on a 1-device mesh XLA elides every
collective, so both checks are vacuous there — the script then reports
`conclusive: false` and only certifies that the Mosaic kernel compiled
inside the sharded-jit program. The partitioning question itself needs
>= 2 devices (a real slice, or an AOT topology compile once supported);
the verdict text and the docs/PERF.md note say which of the two cases
was actually observed.

Run on the chip:  python scripts/check_fused_ce_hlo.py
Appends a verdict line to docs/PERF.md when --write-note is passed.
"""

from __future__ import annotations

import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from genrec_tpu.analysis import ir  # noqa: E402


def main(argv=None):
    args = ir.check_args(
        argv,
        small_help="tiny shapes for fast CI runs (scripts/ci_checks.sh --smoke)",
    )
    if args.platform:
        # Platform pinning stays OUT of the leaf analysis package (its own
        # layering rule): scripts import the runtime helper directly.
        from genrec_tpu.parallel.mesh import pin_platform

        pin_platform(args.platform)
    # `--platform cpu` is the caller asking for the CPU certify run: the
    # only way the fused-CE kernel runs there is the Pallas interpreter,
    # so that is asked for too (kernels/policy.py never infers it).
    import contextlib

    from genrec_tpu.kernels.policy import interpret_mode

    with interpret_mode() if args.platform == "cpu" else contextlib.nullcontext():
        return _check(args)


def _check(args):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from genrec_tpu.core.harness import make_train_step
    from genrec_tpu.core.state import TrainState
    from genrec_tpu.models.sasrec import SASRec

    backend = jax.default_backend()
    n_dev = jax.device_count()
    mesh = Mesh(np.array(jax.devices()).reshape(n_dev), ("data",))

    B, L, V, D = (16, 16, 640, 16) if args.small else (64, 50, 12160, 64)
    model = SASRec(
        num_items=V, max_seq_len=L, embed_dim=D, num_heads=2, num_blocks=2,
        ffn_dim=256, dropout=0.0, fused_ce=True, dtype=jnp.bfloat16,
    )
    rng = jax.random.key(0)
    ids = jnp.zeros((B, L), jnp.int32)
    params = model.init(rng, ids, deterministic=True)["params"]
    optimizer = optax.adamw(1e-3)

    def loss_fn(p, batch, step_rng):
        _, loss = model.apply(
            {"params": p}, batch["input_ids"], targets=batch["targets"],
            deterministic=True,
        )
        return loss, {}

    step = make_train_step(loss_fn, optimizer, clip_norm=1.0)
    state = TrainState.create(params, optimizer, rng)
    batch = {
        "input_ids": jax.device_put(ids, NamedSharding(mesh, P("data"))),
        "targets": jax.device_put(ids, NamedSharding(mesh, P("data"))),
    }
    hlo = ir.optimized_hlo(step, state, batch)

    custom_calls = re.findall(r".*custom-call.*tpu_custom_call.*", hlo)
    gathers = re.findall(r".*(all-gather|all-reduce|collective-permute).*", hlo)
    gather_ids = {
        m.group(1)
        for m in re.finditer(r"(\S+) = \S+ all-gather", hlo)
    }
    suspicious = [
        line for line in custom_calls
        if any(g in line for g in gather_ids)
    ]
    # Shape check: the fused-CE row-block inputs should carry the
    # PER-DEVICE row count (B*L/n_dev rows after padding), not the global
    # one — global-sized operands on a >1-device mesh mean replicated
    # (gathered) inputs even without a literal all-gather op.
    rows_global = B * L
    global_sized = [
        line
        for line in custom_calls
        if n_dev > 1 and re.search(rf"\b{rows_global}\b", line)
    ]

    # Off-TPU the Pallas call runs in interpret mode, so no Mosaic custom
    # call can appear — only a >=2-device TPU run answers the partitioning
    # question; anything else merely certifies the sharded-jit compile.
    conclusive = n_dev > 1 and backend == "tpu"
    # ok answers "is partitioning VERIFIED good" — inconclusive runs must
    # not read as a pass to automation keying on ok/rc.
    ok = (
        conclusive
        and bool(custom_calls)
        and not suspicious
        and not global_sized
    )
    verdict = {
        "backend": backend,
        "devices": n_dev,
        "conclusive": conclusive,
        "mosaic_custom_calls": len(custom_calls),
        "collectives_in_module": len(gathers),
        "all_gather_feeding_custom_call": len(suspicious),
        "global_sized_custom_call_operands": len(global_sized),
        "ok": ok,
    }
    ir.emit_verdict(verdict)

    if args.write_note:
        if not conclusive:
            what = (
                "compiled inside the sharded-jit program" if custom_calls
                else ("interpret-mode (non-TPU) run: sharded-jit compile "
                      "certified only" if backend != "tpu"
                      else "NOT found in the compiled module")
            )
            msg = (
                f"inconclusive run: Mosaic kernel {what}; "
                "partitioning question still open (needs >= 2 TPU chips)"
            )
        elif ok:
            msg = ("OK: kernel partitioned — no all-gather feeds it and "
                   "operands are per-device-sized")
        else:
            msg = "ATTENTION: inspect out/fused_ce_hlo.txt"
        ir.append_perf_note(
            f"\n- HLO check (scripts/check_fused_ce_hlo.py, backend="
            f"{backend}, {n_dev} device(s)): {len(custom_calls)} Mosaic "
            f"custom-call(s) -> {msg}\n"
        )
        ir.dump_artifact("fused_ce_hlo.txt", hlo)
    # rc: 0 = verified good; 2 = ran fine but inconclusive (1 device or
    # non-TPU backend, where Mosaic cannot appear at all); 1 = a check
    # failed (including a TPU run whose kernel vanished from the module).
    if ok:
        return 0
    if not conclusive:
        return 2 if (custom_calls or backend != "tpu") else 1
    return 1


if __name__ == "__main__":
    sys.exit(main())
