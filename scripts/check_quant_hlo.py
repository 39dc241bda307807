"""Quantized-serving compilation + ledger check (on the shared graftlint
harness, genrec_tpu/analysis/ir.py — CLI, verdict JSON and rc
conventions unchanged): does int8 serving hold the repo's compile and
accounting discipline?

Three properties, each a silent-regression magnet:

1. **mixed-dtype churn, zero steady-state recompiles** — ONE engine
   hosting an int8-KV TIGER generative head (quantized page pool,
   prefix cache COW-sharing quantized runs) beside a ``quantized=True``
   SASRec retrieval head (int8 table as a runtime operand) is churned
   with staggered mixed-length traffic plus a repeat-user warm tail.
   The quantized containers are registered pytrees, so every executable
   must keep the exact fp32-era shape set: any recompile means a dtype
   leaked into a compile surface.
2. **ledger == quantized byte math** — the engine's HBM ledger must
   report the page pool at its REAL int8+scales size
   (``PagedConfig.hbm_bytes``), and the quantized retrieval table as a
   ``catalog_operands`` entry sized int8-data + fp32-scales. Refusal
   math that still assumed fp32 bytes would over-admit by ~4x.
3. **no fp32 upcast of the page pool in optimized HLO** — the dequant
   must happen AFTER the page gather (a slot-view-sized convert), never
   as a whole-pool ``convert`` baked into the optimized program, or the
   memory saving silently evaporates at runtime. Checked on the lowered
   text of the paged-attention fallback over a distinctively-sized pool.

Run:  python scripts/check_quant_hlo.py             (default shapes)
      python scripts/check_quant_hlo.py --small     (CI-speed shapes)
Appends a verdict line to docs/PERF.md when --write-note is passed.
Prints ONE JSON verdict line on stdout; rc 0 ok / 1 failed.
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from genrec_tpu.analysis import ir  # noqa: E402


def _drive_mixed_churn(engine, tiger_head, sas_head, valid_ids, n_items,
                       n_requests, max_hist, n_users, rng):
    """Rolling-window churn across BOTH heads: admissions land while
    other slots are mid-decode, retrieval batches interleave with paged
    generative batches, and a repeat-user tail replays recently served
    TIGER histories so the prefix cache serves warm (quantized, COW)
    hits under the same churn."""
    import collections

    import numpy as np

    from genrec_tpu.serving import Request

    submitted, items_ok = 0, True
    inflight = collections.deque()
    served: list = []
    window = 2 * engine._max_batch + 1
    n_repeat = max(engine._max_batch, 4)
    total = n_requests + n_repeat
    while submitted < total or inflight:
        while submitted < total and len(inflight) < window:
            if submitted < n_requests:
                n = int(rng.integers(1, max_hist + 1))
                if submitted % 2 == 0:
                    req = Request(
                        head=tiger_head.name,
                        history=rng.integers(0, len(valid_ids), n),
                        user_id=int(rng.integers(0, n_users)),
                    )
                    served.append(req)
                else:
                    req = Request(
                        head=sas_head.name,
                        history=rng.integers(1, n_items + 1, n),
                        user_id=int(rng.integers(0, n_users)),
                    )
            else:
                recent = min(len(served), engine._max_batch)
                prev = served[-1 - int(rng.integers(recent))]
                req = Request(head=tiger_head.name, history=prev.history,
                              user_id=prev.user_id)
            inflight.append(engine.submit(req))
            submitted += 1
        r = inflight.popleft().result(300)
        items_ok = items_ok and bool((np.asarray(r.items) >= 0).all())
    return submitted, n_repeat, items_ok


def _check_pool_hlo() -> dict:
    """Property 3: lower the paged-attention fallback over an int8 pool
    of a DISTINCTIVE size and grep the optimized text — the pool
    parameter must stay s8, and no tensor of the full pool's shape may
    appear at f32 (the dequant is per gathered slot view only)."""
    import jax
    import numpy as np

    from genrec_tpu.ops.paged import paged_attention_stats
    from genrec_tpu.ops.quant import QuantizedKVPool

    P, page, H, hd, S, K, Pm = 37, 8, 2, 16, 3, 4, 5
    pool_sds = QuantizedKVPool(
        jax.ShapeDtypeStruct((P, page, H * hd), np.int8),
        jax.ShapeDtypeStruct((P, page), np.float32),
    )
    args = (
        jax.ShapeDtypeStruct((S, K, H, hd), np.float32),
        pool_sds, pool_sds,
        jax.ShapeDtypeStruct((S, Pm), np.int32),
        jax.ShapeDtypeStruct((S,), np.int32),
    )
    hlo = ir.optimized_hlo(
        lambda q, kp, vp, bt, sl: paged_attention_stats(
            q, kp, vp, bt, sl, use_kernel=False
        ),
        *args,
    )
    full_pool_f32 = f"f32[{P},{page},{H * hd}]"
    pool_s8 = f"s8[{P},{page},{H * hd}]"
    big_consts = [c for c in ir.hlo_constants(hlo) if c["bytes"] > 64 * 1024]
    rec = {
        "pool_param_s8": pool_s8 in hlo,
        "full_pool_f32_upcast": full_pool_f32 in hlo,
        "baked_constants_over_64k": len(big_consts),
    }
    rec["ok"] = (
        rec["pool_param_s8"]
        and not rec["full_pool_f32_upcast"]
        and not big_consts
    )
    if not rec["ok"]:
        rec["hlo_artifact"] = ir.dump_artifact("check_quant_hlo_pool.txt", hlo)
    return rec


def main(argv=None):
    args = ir.check_args(argv)

    import jax

    if args.platform:
        from genrec_tpu.parallel.mesh import pin_platform

        pin_platform(args.platform)

    import jax.numpy as jnp
    import numpy as np

    from genrec_tpu.models.sasrec import SASRec
    from genrec_tpu.models.tiger import Tiger
    from genrec_tpu.serving import BucketLadder, PagedConfig, ServingEngine
    from genrec_tpu.serving.heads import RetrievalHead, TigerGenerativeHead

    backend = jax.default_backend()
    if args.small:
        n_corpus, n_items = 50, 40
        arch = dict(embedding_dim=16, attn_dim=32, dropout=0.0, num_heads=4,
                    n_layers=2, num_item_embeddings=8, num_user_embeddings=20,
                    sem_id_dim=3)
        ladder = BucketLadder((1, 2), (4, 8))
        n_requests = 16
    else:
        n_corpus, n_items = 1000, 5000
        arch = dict(embedding_dim=64, attn_dim=128, dropout=0.0, num_heads=4,
                    n_layers=4, num_item_embeddings=64,
                    num_user_embeddings=10_000, sem_id_dim=3)
        ladder = BucketLadder((1, 4, 8), (8, 16))
        n_requests = 48
    D = arch["sem_id_dim"]
    Kcb = arch["num_item_embeddings"]
    max_hist = ladder.history_buckets[-1]
    n_users = arch["num_user_embeddings"]

    tiger = Tiger(**arch)
    rng = np.random.default_rng(0)
    valid_ids = np.unique(rng.integers(0, Kcb, (n_corpus, D)), axis=0)
    B0, L0 = 2, 2 * D
    tparams = tiger.init(
        jax.random.key(0),
        jnp.zeros((B0,), jnp.int32), jnp.zeros((B0, L0), jnp.int32),
        jnp.zeros((B0, L0), jnp.int32), jnp.zeros((B0, D), jnp.int32),
        jnp.zeros((B0, D), jnp.int32), jnp.ones((B0, L0), jnp.int32),
    )["params"]
    sas = SASRec(num_items=n_items, max_seq_len=max_hist,
                 embed_dim=arch["embedding_dim"], num_heads=2, num_blocks=1,
                 ffn_dim=2 * arch["embedding_dim"], dropout=0.0)
    sparams = sas.init(
        jax.random.key(1), jnp.zeros((2, max_hist), jnp.int32)
    )["params"]

    tiger_head = TigerGenerativeHead(tiger, valid_ids, top_k=5, name="tiger")
    sas_head = RetrievalHead("sasrec", sas, top_k=5, quantized=True)
    max_kv = tiger_head.paged_kv_tokens(10**9, max_hist)
    cfg = PagedConfig(
        max_slots=ladder.max_batch, page_size=8,
        pages_per_slot=-(-max_kv // 8), kv_dtype="int8",
    )
    engine = ServingEngine(
        [tiger_head, sas_head], {"tiger": tparams, "sasrec": sparams},
        ladder=ladder, max_batch=ladder.max_batch, max_wait_ms=1.0,
        handle_signals=False, paged_config=cfg,
    ).start()
    served, n_repeat, items_ok = _drive_mixed_churn(
        engine, tiger_head, sas_head, valid_ids, n_items, n_requests,
        max_hist, n_users, rng,
    )
    stats = engine.stop()

    # Property 2: ledger totals come from the QUANTIZED bytes. The pool
    # entry must equal PagedConfig.hbm_bytes under kv_dtype=int8, and the
    # quantized table rides as a catalog operand at int8+fp32-scale size.
    nl, H, hd, _ = tiger_head.paged_layout()
    expect_pool = cfg.hbm_bytes(n_layers=nl, n_heads=H, head_dim=hd)
    hbm = stats["hbm"]["heads"]
    pool_bytes = hbm["tiger"]["operands"].get("kv_page_pool", -1)
    V, d = sparams["item_embedding"].shape
    expect_table = V * d * 1 + V * 4  # int8 rows + one fp32 scale per row
    table_bytes = hbm["sasrec"]["operands"].get("catalog_operands", -1)
    prefix = stats["prefix_cache"].get("tiger", {})
    pool = stats["kv_pool"]["tiger"]
    churn = {
        "steady_state_requests": served,
        "recompilations": stats["recompilations"],
        "completed": stats["completed"],
        "constrained_items_valid": items_ok,
        "kv_dtype": pool["kv_dtype"],
        "prefix_hits": prefix.get("hits", 0),
        "pages_in_use_final": pool["pages_in_use"],
        "ledger_kv_page_pool_bytes": pool_bytes,
        "expected_kv_page_pool_bytes": expect_pool,
        "ledger_quant_table_bytes": table_bytes,
        "expected_quant_table_bytes": expect_table,
        "fp32_pool_bytes_would_be": PagedConfig(
            max_slots=cfg.max_slots, page_size=cfg.page_size,
            pages_per_slot=cfg.pages_per_slot,
        ).hbm_bytes(n_layers=nl, n_heads=H, head_dim=hd),
    }
    churn["ok"] = (
        stats["recompilations"] == 0
        and stats["completed"] == served
        and items_ok
        and pool["kv_dtype"] == "int8"
        and prefix.get("hits", 0) >= n_repeat
        and pool["pages_in_use"] == 0
        and pool_bytes == expect_pool
        and table_bytes == expect_table
    )

    hlo_rec = _check_pool_hlo()

    ok = churn["ok"] and hlo_rec["ok"]
    verdict = {
        "backend": backend,
        "churn": churn,
        "pool_hlo": hlo_rec,
        "recompilations": churn["recompilations"],
        "ok": ok,
    }
    ir.emit_verdict(verdict)

    if args.write_note:
        if ok:
            saved = churn["fp32_pool_bytes_would_be"] - churn[
                "ledger_kv_page_pool_bytes"]
            msg = (
                f"OK: {served} mixed-dtype requests (int8 KV + int8 "
                f"retrieval table on one engine), 0 recompilations, "
                f"{churn['prefix_hits']} quantized warm prefix hits, ledger "
                f"pool {churn['ledger_kv_page_pool_bytes']} B == quantized "
                f"byte math ({saved} B under fp32), no whole-pool f32 "
                "upcast in optimized HLO"
            )
        else:
            msg = "ATTENTION: quantized serving broke compile/ledger discipline"
        ir.append_perf_note(
            f"\n- Quantized serving check (scripts/check_quant_hlo.py, "
            f"backend={backend}): {msg}\n"
        )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
