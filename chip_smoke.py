"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py

drives the main path once, through the entry points a user calls, at the
full width of the one model every layer of the repo serves — TIGER at the
reference width (config/tiger/amazon/tiger.gin: d_model 128, attention
384, 6 heads, 8 layers, codebook 256 x 3, 20 items, batch 256, bf16),
random weights from a seed, synthetic data from a seed:

1. **train** — `configlib.parse_config` + `tiger_trainer.train()`: packed
   optimizer steps on every chip there is, the trie-constrained beam
   evaluate passes, a `CheckpointManager` resume point and a `save_params`
   snapshot. Then the saved params are loaded back and one more generate
   batch is checked tuple by tuple against the trie.
2. **serve** — `ServingEngine([TigerGenerativeHead(...)], params,
   paged=True, ...)` on the params the trainer just saved: AOT warmup,
   requests of mixed history lengths with one repeated (a warm prefix
   admit), then the compiled text of every paged executable read for a
   whole-pool copy (there must be none), drain, stop.
   Then **serve_lcrec**: one request, cold and warm, through
   `LCRecGenerativeHead`'s paged path on a backbone of Solar-Open2's
   layer kinds at its published widths (gated NoPE GQA 64/8 x 128 whose
   K and V go to pages; three KDA layers of 64 x 128 with negative
   eigenvalues whose states live a beam in the slot table; sigmoid
   routing over 320 outputs, 8 experts held), with the same reads of the
   compiled text for a whole-pool or whole-state copy.
3. **kernels** — the `kernels.preflight` legs compiled (interpret=False):
   the paged kernel at this engine's shapes in fp32, int8 and the pool's
   own dtype; the other default-on kernels at their preflight shapes.
4. **lcrec_keye** — `lcrec_trainer.train()` on
   `config/lcrec/keye_vl2_30b_a3b.gin`: two optimizer steps of the cut
   Keye-VL-2.0 language model at its published widths (indexer-selected
   attention over 8,192-slot rows, dropless experts on the 16 held of
   128), then the trainer's constrained-beam evaluate through the cache
   that holds the indexer's keys.
5. **lcrec_kimi_linear** — the same on
   `config/lcrec/kimi_linear_48b_a3b.gin`: two optimizer steps of the cut
   Kimi-Linear language model at its published widths (the chunked KDA
   scan, NoPE latent attention, sigmoid-routed experts on the 8 held of
   256 beside the shared one; rows of 2,048 slots, which is traffic and
   not a width), then the evaluate through the cache that holds a
   recurrent state or a latent row by layer kind.
6. **four_chip** — with four or more chips: the trainer data parallel
   over four and at tensor_parallel=2, against a one-chip step of the
   same seed. On fewer chips the leg prints that it did not run. The
   engine with ``mesh=`` is left out of it, by name: see `LEFT_OUT`.

Every check raises and every phase's failure is the script's:
nothing is caught and carried past. Off a TPU it exits non-zero before
doing any work. Only if everything passed does it exit 0, and then its
last two lines are ``chip_smoke: summary {...}`` — per-phase wall /
XLA-compile / cache-load / run seconds, peak device
memory, the kernel table, ``"claim": null`` — and, LAST, the one JSON
object the driver reads: ``{"ok": true, "device": {"platform", "kind",
"count"}}`` with exactly those keys, the device as JAX reports it.

``--rehearse`` is the CPU rehearsal the caller asks for by name: the same
phases at a tiny width on four virtual CPU devices with the kernels
interpreted. The default invocation never reaches it.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import shutil
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
GIN = os.path.join(REPO, "config", "tiger", "amazon", "tiger.gin")

#: Depth and data cuts for the smoke: the model's WIDTH stays whole.
SMOKE_BINDINGS = {
    "dataset": "synthetic", "wandb_logging": False, "wandb_log_interval": 1,
    "epochs": 2, "num_users": 200, "do_eval": False, "save_every_epoch": 1,
}
#: --rehearse only: a width one CPU core finishes in minutes.
REHEARSE_BINDINGS = {
    "embedding_dim": 16, "attn_dim": 32, "num_heads": 2, "n_layers": 2,
    "codebook_size": 16, "batch_size": 16, "eval_batch_size": 16,
    "num_users": 24, "max_items": 6, "num_user_embeddings": 50,
}
BEAMS = 10
#: The LCRec leg: the cut Keye-VL-2.0 language model at its published widths
#: (config/lcrec/keye_vl2_30b_a3b.gin), two optimizer steps and the
#: trainer's own constrained-beam evaluate through the cache.
KEYE_GIN = os.path.join(REPO, "config", "lcrec", "keye_vl2_30b_a3b.gin")
KEYE_BINDINGS = {"epochs": 1, "max_eval_samples": 2, "eval_every_epoch": 1}
#: --rehearse only: every mechanism on, at a width one CPU core finishes.
KEYE_REHEARSE_BINDINGS = {
    "hidden_size": 32, "intermediate_size": 64, "num_heads": 4,
    "num_kv_heads": 2, "head_dim": 8, "n_layers": 2, "sparse_topk": 16,
    "indexer_heads": 2, "indexer_head_dim": 8, "sparse_chunk": 32,
    "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 16,
    "moe_experts_held": 4, "codebook_size": 8, "num_codebooks": 3,
    "vocab_rows": 0, "max_text_len": 96, "amp": False,
}
#: The second LCRec backbone: the cut Kimi-Linear language model at its
#: published widths (config/lcrec/kimi_linear_48b_a3b.gin). One row a step is
#: what fits; rows of 2,048 slots keep the leg short (the benchmark cell
#: kimi_linear_sft_lifelong runs the 8,192).
KIMI_GIN = os.path.join(REPO, "config", "lcrec", "kimi_linear_48b_a3b.gin")
KIMI_BINDINGS = {"epochs": 1, "max_eval_samples": 2, "eval_every_epoch": 1,
                 "max_text_len": 2048}
KIMI_REHEARSE_BINDINGS = {
    "hidden_size": 32, "intermediate_size": 64, "num_heads": 4,
    "num_kv_heads": 4, "head_dim": 8, "kda_heads": 2, "kda_head_dim": 8,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "v_head_dim": 8, "sparse_chunk": 32,
    "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 16,
    "moe_experts_held": 4, "codebook_size": 8, "num_codebooks": 3,
    "vocab_rows": 0, "max_text_len": 96, "amp": False,
}
KEYE_WIDTHS = ("hidden_size", "n_layers", "num_experts", "moe_experts_held",
               "sparse_topk", "max_text_len", "vocab_rows")
KIMI_WIDTHS = ("hidden_size", "n_layers", "kda_layers", "mla_layers",
               "num_experts", "moe_experts_held", "n_shared_experts",
               "max_text_len", "vocab_rows")
#: Not run, and why. `ServingEngine(mesh=...)` at model axis 2 was tried on
#: a four-chip host (PR 21): params and pools shard, then warmup() dies in
#: `SlotTable.compile` — GSPMD meets the paged pallas_call and jax raises
#: "NotImplementedError: Mosaic kernels cannot be automatically
#: partitioned. Please wrap the call in a shard_map." ROADMAP S6 owns it.
LEFT_OUT = {
    "four_chip/engine_mesh_model2":
        "Mosaic kernels cannot be automatically partitioned (ROADMAP S6)",
}


def check(ok, *detail) -> None:
    """The smoke's assertion: raises whatever the interpreter flags (a bare
    ``assert`` disappears under ``python -O`` and the smoke with it)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {detail}")


class _Phases:
    """Wall / XLA-compile / cache-load / run seconds per phase, from the
    host clock and the repo's process-wide compile tap
    (obs.goodput.CompileEvents: a load from the persistent cache is not a
    compile)."""

    def __init__(self):
        from genrec_tpu.obs.goodput import CompileEvents

        self._events = CompileEvents.ensure()
        self.report: dict = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        print(f"chip_smoke: phase {name} ...", flush=True)
        t0, (n0, s0) = time.perf_counter(), self._events.snapshot()
        l0, ls0 = self._events.load_snapshot()
        entry = self.report[name] = {}
        yield entry
        wall, (n1, s1) = time.perf_counter() - t0, self._events.snapshot()
        l1, ls1 = self._events.load_snapshot()
        built = (s1 - s0) + (ls1 - ls0)
        entry.update(
            wall_s=round(wall, 2), xla_compiles=n1 - n0,
            xla_compile_s=round(s1 - s0, 2), cache_loads=l1 - l0,
            cache_load_s=round(ls1 - ls0, 2), run_s=round(wall - built, 2),
        )
        print(f"chip_smoke: phase {name} ok {json.dumps(entry)}", flush=True)


def _gin_argv(save_dir: str, bindings: dict) -> list[str]:
    argv = [GIN]
    for key, value in {**bindings, "save_dir_root": save_dir}.items():
        argv += ["--gin", f"train.{key}={value!r}"]
    return argv


def _read_metrics(save_dir: str) -> list[dict]:
    with open(os.path.join(save_dir, "metrics.jsonl")) as f:
        return [json.loads(line) for line in f]


def run_trainer(save_dir: str, bindings: dict, on_step=None) -> dict:
    """One `train()` the way a user runs it — gin file, ``--gin``
    overrides, `parse_config`, `train()` — and what it left behind:
    per-step losses (metrics.jsonl), final metrics, the run's bindings.
    ``on_step(state, batch)`` sees what the jitted step is handed."""
    from genrec_tpu import configlib
    from genrec_tpu.configlib.parser import clear_macros
    from genrec_tpu.trainers import tiger_trainer

    configlib.clear_bindings()
    clear_macros()
    configlib.parse_config(_gin_argv(save_dir, bindings))
    cfg = configlib.get_bindings("train")
    jit_train_step = tiger_trainer.jit_train_step
    if on_step is not None:
        def spying(step_fn):
            step = jit_train_step(step_fn)

            def spied(state, batch):
                on_step(state, batch)
                return step(state, batch)

            return spied

        tiger_trainer.jit_train_step = spying
    try:
        valid, test = tiger_trainer.train()
    finally:
        tiger_trainer.jit_train_step = jit_train_step
    losses = [m["train/loss"] for m in _read_metrics(save_dir)
              if "global_step" in m and "train/loss" in m]
    check(losses, "trainer logged no optimizer step")
    check(all(l is not None and math.isfinite(l) for l in losses), losses)
    for name, metrics in (("valid", valid), ("test", test)):
        check(metrics, f"no {name} metrics")
        for key, value in metrics.items():
            check(0.0 <= value <= 1.0, (name, key, value))
    # No rule-matched weight may have fallen back to replication
    # (parallel.shardings.param_specs reports each through the logger).
    with open(os.path.join(save_dir, "train.log")) as f:
        fallbacks = [line.strip() for line in f if "replicating" in line]
    check(not fallbacks, fallbacks)
    return {"cfg": cfg, "losses": losses, "valid": valid, "test": test}


def _devices_per_array(tree) -> int:
    """Fewest devices any leaf of ``tree`` lives on: 1 means something sat
    on one chip while the mesh had more."""
    import jax

    return min(
        len(x.sharding.device_set) for x in jax.tree_util.tree_leaves(tree)
    )


def _spread_spy():
    """(record, on_step): on the first step, how many devices the arrays
    handed to the jitted step really span."""
    spread: dict = {}

    def on_step(state, batch):
        spread.setdefault("params", _devices_per_array(state.params))
        spread.setdefault("batch", _devices_per_array(batch))

    return spread, on_step


def _tiger_from(cfg: dict):
    """The model and synthetic corpus `train()` built from these bindings
    (same constructors, same seed)."""
    import jax.numpy as jnp

    from genrec_tpu.data.tiger_seq import synthetic_tiger_data
    from genrec_tpu.models.tiger import Tiger

    model = Tiger(
        embedding_dim=cfg["embedding_dim"], attn_dim=cfg["attn_dim"],
        dropout=cfg["dropout"], num_heads=cfg["num_heads"],
        n_layers=cfg["n_layers"], num_item_embeddings=cfg["codebook_size"],
        num_user_embeddings=cfg["num_user_embeddings"],
        sem_id_dim=cfg["sem_id_dim"], dtype=jnp.bfloat16,
    )
    data = synthetic_tiger_data(
        codebook_size=cfg["codebook_size"], sem_id_dim=cfg["sem_id_dim"],
        max_items=cfg["max_items"], seed=0, num_users=cfg["num_users"],
    )
    return model, data


def _load_saved_params(model, cfg: dict, save_dir: str):
    import jax
    import jax.numpy as jnp

    from genrec_tpu.core.checkpoint import load_params

    L, D = cfg["max_items"] * cfg["sem_id_dim"], cfg["sem_id_dim"]
    like = jax.eval_shape(
        lambda: model.init(
            jax.random.key(0), jnp.zeros((1,), jnp.int32),
            jnp.zeros((1, L), jnp.int32), jnp.zeros((1, L), jnp.int32),
            jnp.zeros((1, D), jnp.int32), jnp.zeros((1, D), jnp.int32),
            jnp.ones((1, L), jnp.int32),
        )["params"]
    )
    like = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), like)
    params = load_params(os.path.join(save_dir, "best_model"), like=like)
    params = jax.tree_util.tree_map(jnp.asarray, params)  # resident once
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    return params, n_params


def phase_train(entry: dict, save_dir: str, bindings: dict, on_tpu: bool):
    import jax
    import numpy as np

    from genrec_tpu.core.state import fast_step_rng
    from genrec_tpu.data.batching import batch_iterator
    from genrec_tpu.ops.trie import build_trie, tuples_are_valid
    from genrec_tpu.parallel import get_mesh, shard_batch
    from genrec_tpu.trainers.tiger_trainer import make_generate_fn

    spread, on_step = _spread_spy()
    run = run_trainer(save_dir, bindings, on_step=on_step)
    cfg, losses = run["cfg"], run["losses"]
    n_dev = jax.device_count()
    check(spread == {"params": n_dev, "batch": n_dev}, (spread, n_dev))
    steps = [d for d in os.listdir(os.path.join(save_dir, "checkpoints"))
             if d.isdigit()]
    check(steps, "CheckpointManager left no committed step")
    # The step's RNG branch a CPU never enters: 'rbg' keys on a TPU.
    impl = str(jax.random.key_impl(fast_step_rng(jax.random.key(0))))
    check(("rbg" in impl) == on_tpu, impl)

    model, data = _tiger_from(cfg)
    params, n_params = _load_saved_params(model, cfg, save_dir)
    trie = build_trie(data.valid_item_sem_ids(), cfg["codebook_size"])
    gen = make_generate_fn(model, trie, 0.2, BEAMS)
    arrays = data.eval_arrays("valid")
    host, _ = next(iter(batch_iterator(arrays, cfg["eval_batch_size"])))
    sem_ids = gen(params, shard_batch(get_mesh(), host), jax.random.key(1))
    sem_ids = jax.block_until_ready(sem_ids)
    check(sem_ids.shape == (cfg["eval_batch_size"], BEAMS, cfg["sem_id_dim"]),
          sem_ids.shape)
    check(bool(np.asarray(tuples_are_valid(trie, sem_ids)).all()))
    entry.update(
        steps=len(losses), loss_first=round(losses[0], 4),
        loss_last=round(losses[-1], 4), n_params=int(n_params),
        devices_per_array=spread, step_rng_impl=impl,
        valid={k: round(v, 4) for k, v in run["valid"].items()},
        test={k: round(v, 4) for k, v in run["test"].items()},
        generated_tuples_trie_valid=int(sem_ids.shape[0] * sem_ids.shape[1]),
    )
    return model, data, params, cfg


def phase_lcrec(entry: dict, save_dir: str, gin: str, bindings: dict,
                widths: tuple, min_rows: int) -> None:
    """`lcrec_trainer.train()` on a backbone's gin the way a user runs it:
    two optimizer steps of the cut, then the trainer's evaluate, whose
    constrained beam runs through the backbone's cache (the indexer's keys
    for Keye; a recurrent state or a latent row by layer kind for Kimi)."""
    import jax

    from genrec_tpu import configlib
    from genrec_tpu.configlib.parser import clear_macros
    from genrec_tpu.trainers import lcrec_trainer

    rows = max(min_rows, jax.device_count())
    bindings = {**bindings, "batch_size": rows, "eval_batch_size": rows,
                "max_train_samples": 2 * rows, "save_dir_root": save_dir}
    configlib.clear_bindings()
    clear_macros()
    argv = [gin]
    for key, value in bindings.items():
        argv += ["--gin", f"train.{key}={value!r}"]
    configlib.parse_config(argv)
    cfg = configlib.get_bindings("train")
    valid, test = lcrec_trainer.train()
    losses = [m["train/loss"] for m in _read_metrics(save_dir)
              if "global_step" in m and "train/loss" in m]
    check(len(losses) == 2, losses)
    check(all(l is not None and math.isfinite(l) for l in losses), losses)
    for name, metrics in (("valid", valid), ("test", test)):
        check("Recall@10" in metrics, (name, metrics))
        check(all(0.0 <= v <= 1.0 for v in metrics.values()), (name, metrics))
    entry.update(
        steps=len(losses), loss_first=round(losses[0], 4),
        loss_last=round(losses[-1], 4),
        widths={k: cfg[k] for k in widths},
    )


def phase_lcrec_keye(entry: dict, save_dir: str, rehearse: bool) -> None:
    """Indexer-selected attention, dropless experts on the 16 held of 128."""
    phase_lcrec(entry, save_dir, KEYE_GIN,
                {**KEYE_BINDINGS, **(KEYE_REHEARSE_BINDINGS if rehearse else {})},
                KEYE_WIDTHS, min_rows=2)


def phase_lcrec_kimi_linear(entry: dict, save_dir: str, rehearse: bool) -> None:
    """The chunked KDA scan, NoPE latent attention, sigmoid-routed experts on
    the 8 held of 256 beside the shared one; one row a step is what fits."""
    phase_lcrec(entry, save_dir, KIMI_GIN,
                {**KIMI_BINDINGS, **(KIMI_REHEARSE_BINDINGS if rehearse else {})},
                KIMI_WIDTHS, min_rows=1)


def _check_responses(responses, item_sem_ids) -> None:
    import numpy as np

    for r in responses:
        items, sem = np.asarray(r.items), np.asarray(r.sem_ids)
        check(items.shape == (BEAMS,) and sem.shape[0] == BEAMS, (items, sem))
        check(((0 <= items) & (items < len(item_sem_ids))).all(), items)
        # Every answer is a real item: its beam IS that item's sem-id.
        np.testing.assert_array_equal(item_sem_ids[items], sem)
        scores = np.asarray(r.scores, np.float32)
        check(np.isfinite(scores).all(), scores)
        check((np.diff(scores) <= 1e-6).all(), scores)


def _check_pool_stays_put(runner) -> dict:
    """The page pool lives in ONE layout between launches: print what the
    runtime gave a leaf, and fail if the compiled text of any executable
    of the engine produces a pool-sized value by a copy, a transpose or
    a convert (the in-place page scatter and the kernel's custom call
    are what may touch one). The first check to fail if a later JAX or
    libtpu changes its mind about the pool's layout."""
    import jax

    from genrec_tpu.analysis.ir import hlo_ops_of_size

    leaf = jax.tree_util.tree_leaves(runner.pool.k_pools)[0]
    print(f"chip_smoke: pool leaf {leaf.dtype}{list(leaf.shape)} "
          f"format={leaf.format}", flush=True)
    executables = {
        **{f"prefill_b{b}_l{l}": e for (b, l), e in runner._prefill.items()},
        **{f"decode_s{s}": e for s, e in runner.slots.executables.items()},
    }
    check(executables, "the engine holds no paged executable")
    # The rehearsal's CPU backend widens a bf16 scatter to float32 and
    # back (a `convert` each way); the chip scatters bf16 in place. A
    # `copy-done` is not a relayout: it ends the asynchronous prefetch
    # into fast memory that the compiler gives a pool as small as the
    # smoke's, in the layout the pool has.
    moving = ("copy", "transpose") + (
        ("convert",) if jax.default_backend() == "tpu" else ())
    pool_ops = {}
    for name, exe in executables.items():
        ops = hlo_ops_of_size(exe.as_text(), math.prod(leaf.shape))
        moved = [line for op, line in ops if op in moving]
        check(not moved, f"{name} relays a whole pool", moved[:2])
        pool_ops[name] = sorted({op for op, _ in ops})
    return pool_ops


def _check_slot_state_stays_put(runner) -> dict:
    """The slot state lives on the device between steps: a rung below the
    whole table takes the table donated and writes its rows back in
    place, so its compiled text must produce no value of the largest
    state leaf's size (a suffix cache) by a copy, a transpose or a
    convert. (At the top rung the step's own output IS the whole leaf.)
    Judged on the chip only: the CPU backend has no donation, so there
    the in-place write is a copy by construction."""
    import jax

    from genrec_tpu.analysis.ir import hlo_ops_of_size

    table = runner.slots
    shapes = jax.eval_shape(
        lambda: runner.head.paged_state_zeros(runner.cfg.max_slots))
    name, leaf = max(shapes.items(), key=lambda kv: math.prod(kv[1].shape))
    print(f"chip_smoke: largest slot-state leaf {name} "
          f"{leaf.dtype}{list(leaf.shape)}", flush=True)
    check(table.writer is not None, "the slot table holds no row-write program")
    state_ops = {}
    for S, exe in table.executables.items():
        if S == runner.cfg.max_slots:
            continue
        ops = hlo_ops_of_size(exe.as_text(), math.prod(leaf.shape))
        moved = [line for op, line in ops
                 if op in ("copy", "transpose", "convert")]
        if jax.default_backend() == "tpu":
            check(not moved, f"decode_s{S} moves a whole {name}", moved[:2])
        state_ops[f"decode_s{S}"] = sorted({op for op, _ in ops})
    return state_ops


def phase_serve(model, params, item_sem_ids, cfg: dict) -> dict:
    """Build, warm, query, drain and stop one paged engine."""
    import numpy as np

    from genrec_tpu.serving import (
        BucketLadder, PagedConfig, Request, ServingEngine,
    )
    from genrec_tpu.serving.heads import TigerGenerativeHead

    max_items = cfg["max_items"]
    head = TigerGenerativeHead(model, item_sem_ids, top_k=BEAMS)
    kv_tokens = head.paged_kv_tokens(max_items, max_items)
    paged_config = PagedConfig(
        max_slots=8, page_size=16, pages_per_slot=-(-kv_tokens // 16)
    )
    engine = ServingEngine(
        [head], params, paged=True, paged_config=paged_config,
        ladder=BucketLadder((1, 4), (max(max_items // 2, 1), max_items)),
        max_batch=4, handle_signals=False,
    )
    t0 = time.perf_counter()
    engine.start()  # AOT warmup of the whole ladder
    warmup_s = time.perf_counter() - t0
    try:
        rng = np.random.default_rng(0)
        lengths = (2, max_items // 3, max_items // 2, max_items - 1, max_items)
        requests = [
            Request(head=head.name, user_id=7 + i,
                    history=rng.integers(0, len(item_sem_ids), n))
            for i, n in enumerate(lengths)
        ]
        cold = [f.result(300) for f in [engine.submit(r) for r in requests]]
        # The same request again: its prefill is retained, so this admit
        # is warm, and a warm answer equals the cold one bit for bit.
        warm = engine.submit(requests[1]).result(300)
        _check_responses([*cold, warm], item_sem_ids)
        np.testing.assert_array_equal(warm.items, cold[1].items)
        np.testing.assert_array_equal(warm.scores, cold[1].scores)
        pool_ops = _check_pool_stays_put(engine._runners[head.name])
        state_ops = _check_slot_state_stays_put(engine._runners[head.name])
    finally:
        stats = engine.stop()
    check(stats["completed"] == len(requests) + 1, stats["completed"])
    check(stats["recompilations"] == 0, stats["recompilations"])
    prefix = stats["prefix_cache"][head.name]
    check(prefix["hits"] >= 1, prefix)
    pool = stats["kv_pool"][head.name]
    check(pool["pages_in_use"] == 0 and pool["slots_active"] == 0, pool)
    return {
        "warmup_s": round(warmup_s, 2),
        "warmup_executables": stats["warmup_compiles"],
        "answered": stats["completed"], "recompilations": 0,
        "warm_prefix_hits": prefix["hits"],
        "pool_pages_total": pool["pages_in_use"] + pool["pages_free"],
        "pool_sized_ops": pool_ops,
        "slot_state_sized_ops": state_ops,
        "paged_config": [paged_config.max_slots, BEAMS, model.num_heads,
                         model.attn_dim // model.num_heads,
                         paged_config.page_size, paged_config.pages_per_slot],
    }


#: The paged LCRec leg's backbone: Solar-Open2's layer kinds at its published
#: widths (benchmark/configs/solar_open2_250b), one period, a share of 8 of the
#: 320 experts and a small vocabulary so that the leg takes seconds.
LCREC_PAGED = dict(
    hidden_size=4096, intermediate_size=10240, num_hidden_layers=4,
    num_attention_heads=64, num_key_value_heads=8, head_dim=128,
    attention_bias=False, tie_word_embeddings=False, rms_norm_eps=1e-5,
    use_rope=False, attn_output_gate=True, kda_neg_eigval=True,
    kda_layers=(2, 3, 4), kda_heads=64, kda_head_dim=128,
    num_experts=320, num_experts_per_tok=8, moe_intermediate_size=1280,
    moe_capacity_factor=None, moe_experts_held=8, moe_scoring="sigmoid",
    n_shared_experts=1, router_aux_coef=0.0,
)
#: --rehearse only: the same kinds at a width one CPU core finishes.
LCREC_PAGED_REHEARSE = dict(
    hidden_size=64, intermediate_size=128, num_attention_heads=8,
    num_key_value_heads=2, head_dim=16, kda_heads=4, kda_head_dim=16,
    num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
    moe_experts_held=4, sparse_chunk=32,
)


def phase_serve_lcrec(rehearse: bool) -> dict:
    """One request, cold and then warm, through the paged LCRec head: the
    prompt's K and V in pages, a KDA state a beam in the slot table, the
    snapshot in the prefix entry."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from genrec_tpu.models.backbones.qwen import QwenConfig, QwenLM
    from genrec_tpu.serving import (
        BucketLadder, PagedConfig, Request, ServingEngine,
    )
    from genrec_tpu.serving.heads import LCRecGenerativeHead

    C, K, base, items = 5, 256, 1024, 64
    sizes = {**LCREC_PAGED, **(LCREC_PAGED_REHEARSE if rehearse else {})}
    dtype = jnp.bfloat16
    model = QwenLM(QwenConfig(vocab_size=base + C * K, **sizes), dtype=dtype)
    keep = ("A_log", "dt_bias")  # float32 whatever the weights are
    params = jax.jit(lambda k: jax.tree_util.tree_map_with_path(
        lambda path, x: x if str(path[-1].key) in keep else x.astype(dtype),
        model.init(k, jnp.zeros((1, 4), jnp.int32))["params"]))(jax.random.key(0))
    rng = np.random.default_rng(0)
    item_sem_ids = np.unique(rng.integers(0, K, (2000, C)), axis=0)
    head = LCRecGenerativeHead(model, base, C, K, item_sem_ids=item_sem_ids,
                               top_k=BEAMS, name="lcrec")
    page = 128 if not rehearse else 8
    paged_config = PagedConfig(
        max_slots=4, page_size=page,
        pages_per_slot=-(-head.paged_kv_tokens(items, items) // page))
    engine = ServingEngine(
        [head], params, paged=True, paged_config=paged_config,
        ladder=BucketLadder((1, 2), (items // 2, items)), max_batch=2,
        handle_signals=False,
    )
    t0 = time.perf_counter()
    engine.start()
    warmup_s = time.perf_counter() - t0
    try:
        req = Request(head=head.name, history=rng.integers(0, len(item_sem_ids), 48))
        cold = engine.submit(req).result(300)
        warm = engine.submit(req).result(300)
        _check_responses([cold, warm], item_sem_ids)
        np.testing.assert_array_equal(warm.items, cold.items)
        np.testing.assert_array_equal(warm.scores, cold.scores)
        runner = engine._runners[head.name]
        pool_ops = _check_pool_stays_put(runner)
        state_ops = _check_slot_state_stays_put(runner)
        recurrent = runner.slots.recurrent_nbytes
    finally:
        stats = engine.stop()
    check(stats["completed"] == 2 and stats["recompilations"] == 0, stats)
    prefix = stats["prefix_cache"][head.name]
    check(prefix["hits"] == 1 and prefix["snapshot_bytes"] == 0, prefix)
    return {
        "warmup_s": round(warmup_s, 2),
        "warmup_executables": stats["warmup_compiles"],
        "answered": stats["completed"], "warm_prefix_hits": prefix["hits"],
        "page_layers": head.paged_layout()[0], "layers": sizes["num_hidden_layers"],
        "recurrent_state_bytes": recurrent,
        "pool_sized_ops": pool_ops, "slot_state_sized_ops": state_ops,
    }


def phase_kernels(entry: dict, paged_shape, interpret: bool) -> dict:
    from genrec_tpu.kernels.preflight import DEFAULT_ON, run_legs

    paged = {"shape": tuple(paged_shape)}
    table = run_legs({
        "paged_attention": ("paged_attention", paged),
        "paged_attention_int8": ("paged_attention_int8", paged),
        "paged_attention[bfloat16 pool]": (
            "paged_attention", {**paged, "dtype": "bfloat16"}),
        "fused_linear_ce": ("fused_linear_ce", {}),
        "sharded_fused_linear_ce": ("sharded_fused_linear_ce", {}),
        "hstu_attention": ("hstu_attention", {}),
        "hstu_attention_bwd": ("hstu_attention_bwd", {}),
    }, interpret=interpret)
    for name, row in table.items():
        print(f"chip_smoke: kernel {name}: compiled={row['compiled']} "
              + (f"max_abs_err={row['max_abs_err']:.3g} "
                 f"max_rel_err={row['max_rel_err']:.3g} tol={row['tol']:g}"
                 if row["compiled"] else f"error={row['error']}"), flush=True)
    bad = [name for name, row in table.items() if not row["ok"]]
    check(not bad, f"kernels failed: {bad}")
    check(set(DEFAULT_ON) <= set(table), "a default-on kernel has no row")
    entry["rows"] = len(table)
    return table


def phase_four_chip(entry: dict, out_dir: str, bindings: dict) -> None:
    """Same path, four chips. Dropout is off in this leg's trainer runs:
    mask bits depend on how the step is partitioned, and the point here is
    that the LOSS does not."""
    import jax

    from genrec_tpu.parallel import make_mesh
    from genrec_tpu.trainers import tiger_trainer

    base = {**bindings, "epochs": 1, "dropout": 0.0,
            "num_users": max(bindings["num_users"] // 2, 12)}
    runs: dict = {}

    def leg(name: str, extra: dict, want_devices: int, get_mesh=None):
        spread, on_step = _spread_spy()
        real_get_mesh = tiger_trainer.get_mesh
        if get_mesh is not None:
            tiger_trainer.get_mesh = get_mesh
        try:
            run = run_trainer(os.path.join(out_dir, name), {**base, **extra},
                              on_step=on_step)
        finally:
            tiger_trainer.get_mesh = real_get_mesh
        check(spread == {"params": want_devices, "batch": want_devices},
              name, spread)
        runs[name] = {"loss_step1": round(run["losses"][0], 4),
                      "steps": len(run["losses"]), "devices": spread}
        return run["losses"][0]

    four = jax.devices()[:4]
    one = leg("one_chip", {}, 1,
              get_mesh=lambda: make_mesh({"data": 1}, devices=four[:1]))
    dp4 = leg("dp4", {}, 4,
              get_mesh=lambda: make_mesh({"data": 4}, devices=four))
    # tensor_parallel builds its own {"data": -1, "model": 2} mesh over
    # every device; on exactly four chips that is dp=2 x tp=2.
    tp2 = leg("dp2_tp2", {"tensor_parallel": 2}, jax.device_count())
    # Data parallel changes the reduction order and nothing else. Tensor
    # parallel also pads the vocab to a multiple of 2 (769 -> 770 rows), so
    # its random init is another draw: it agrees to init noise, not bf16.
    for name, loss, rel in (("dp4", dp4, 0.02), ("dp2_tp2", tp2, 0.10)):
        check(abs(loss - one) < rel * abs(one), (name, loss, one))
    entry["trainer"] = runs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse", action="store_true",
        help="CPU rehearsal: tiny width, four virtual devices, kernels "
             "interpreted. Proves the script, not the chip.",
    )
    ap.add_argument(
        "--out", default=os.path.join(REPO, "out", "chip_smoke"),
        help="where run directories go (trainer logs, checkpoints: a few "
             "hundred MB at full width). A run that passes removes its own.",
    )
    args = ap.parse_args(argv)
    env_platforms = os.environ.get("JAX_PLATFORMS")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4"
        ).strip()

    import jax

    from genrec_tpu.kernels.policy import interpret_mode
    from genrec_tpu.obs.memory import device_memory_stats
    from genrec_tpu.parallel.mesh import (
        device_summary, enable_compile_cache, require_tpu,
    )

    cache_dir = enable_compile_cache()
    device = device_summary()
    print(
        f"chip_smoke: platform={device['platform']} "
        f"device_kind={device['kind']} count={device['count']} "
        f"jax={jax.__version__} compile_cache={cache_dir} "
        f"JAX_PLATFORMS={env_platforms!r}", flush=True,
    )
    if not args.rehearse:
        require_tpu("chip_smoke")
    phases = _Phases()
    bindings = dict(SMOKE_BINDINGS)
    if args.rehearse:
        bindings.update(REHEARSE_BINDINGS)
    os.makedirs(args.out, exist_ok=True)
    out = tempfile.mkdtemp(prefix="run-", dir=args.out)  # never reuse a run dir

    with interpret_mode() if args.rehearse else contextlib.nullcontext():
        with phases.phase("train") as entry:
            model, data, params, cfg = phase_train(
                entry, os.path.join(out, "train"), bindings,
                on_tpu=not args.rehearse,
            )
        item_sem_ids = data.valid_item_sem_ids()
        with phases.phase("serve") as entry:
            entry.update(phase_serve(model, params, item_sem_ids, cfg))
        with phases.phase("serve_lcrec") as entry:
            entry.update(phase_serve_lcrec(rehearse=args.rehearse))
        with phases.phase("kernels") as entry:
            kernels = phase_kernels(
                entry, phases.report["serve"]["paged_config"],
                interpret=args.rehearse,
            )
        with phases.phase("lcrec_keye") as entry:
            phase_lcrec_keye(entry, os.path.join(out, "lcrec_keye"),
                             rehearse=args.rehearse)
        with phases.phase("lcrec_kimi_linear") as entry:
            phase_lcrec_kimi_linear(entry, os.path.join(out, "lcrec_kimi_linear"),
                                    rehearse=args.rehearse)
        if device["count"] >= 4:
            with phases.phase("four_chip") as entry:
                phase_four_chip(entry, out, bindings)
        else:
            why = f"needs 4 chips, found {device['count']}"
            print(f"chip_smoke: phase four_chip did not run: {why}", flush=True)
            phases.report["four_chip"] = {"ran": False, "why": why}
        for name, why in LEFT_OUT.items():
            print(f"chip_smoke: {name} left out: {why}", flush=True)

    peak = device_memory_stats().get("peak_bytes_in_use")
    shutil.rmtree(out)  # passed: nothing left to look at
    print("chip_smoke: summary " + json.dumps({
        "jax": jax.__version__,
        "rehearsal": args.rehearse,
        "compile_cache": {"dir": cache_dir},
        "phases": phases.report,
        "peak_bytes_in_use": peak,
        "kernels": kernels,
        "left_out": LEFT_OUT,
        "claim": None,
    }), flush=True)
    # The driver's contract: the LAST line is this object and nothing more.
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
