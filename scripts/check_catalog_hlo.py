"""Live-catalog compilation check (shared graftlint harness, analysis/ir):
is the trie REALLY a runtime operand?

One warmed serving engine (per mode: dense bucket ladder, paged
continuous batching) serves constrained-decode traffic against catalog
snapshot A, hot-swaps to snapshot B (same capacity rung) THROUGH
`stage_catalog`, and keeps serving. Asserts:

- ZERO steady-state recompilations across the swap (the swap is a pure
  operand change — one executable, two catalogs);
- every answer is a real item of the catalog version its response
  reports (no version mixing);
- the optimized HLO of the live executables contains NO catalog-sized
  constant (>= the trie's smallest table) — the machine proof the baked
  trie debt stays retired;
- bit-identical sem_ids vs the baked-DenseTrie `tiger_generate`
  reference on the shared catalog (the acceptance criterion).

Run:  python scripts/check_catalog_hlo.py             (default shapes)
      python scripts/check_catalog_hlo.py --small     (CI-speed shapes)
Prints ONE JSON verdict line on stdout; rc 0 ok / 1 failed.
"""

from __future__ import annotations

import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from genrec_tpu.analysis import ir  # noqa: E402


def _corpora(rng, n, k, d):
    """Two same-rung corpora with disjoint first codes, so a version mix
    is detectable (a mixed beam is valid in NEITHER corpus)."""
    import numpy as np

    a = np.unique(np.concatenate(
        [rng.integers(0, k // 2, (n, 1)), rng.integers(0, k, (n, d - 1))],
        axis=1), axis=0)
    b = np.unique(np.concatenate(
        [rng.integers(k // 2, k, (n, 1)), rng.integers(0, k, (n, d - 1))],
        axis=1), axis=0)
    return a, b


def _executable_hlos(engine, head_name):
    """Optimized-HLO text of every live executable serving ``head_name``."""
    texts = []
    runner = engine._runners.get(head_name)
    if runner is not None:
        texts += [c.as_text() for c in runner.slots.executables.values()]
        texts += [c.as_text() for c in runner._prefill.values()]
    texts += [
        c.as_text() for (h, _b, _l), c in engine._exec.items() if h == head_name
    ]
    return texts


def main(argv=None):
    args = ir.check_args(argv)

    import jax

    if args.platform:
        from genrec_tpu.parallel.mesh import pin_platform

        pin_platform(args.platform)

    import jax.numpy as jnp
    import numpy as np

    from genrec_tpu.catalog import CatalogSnapshot
    from genrec_tpu.models.tiger import Tiger, tiger_generate
    from genrec_tpu.ops.trie import DenseTrie
    from genrec_tpu.serving import BucketLadder, Request, ServingEngine
    from genrec_tpu.serving.heads import TigerGenerativeHead

    backend = jax.default_backend()
    if args.small:
        n_corpus = 40
        arch = dict(embedding_dim=16, attn_dim=32, dropout=0.0, num_heads=4,
                    n_layers=2, num_item_embeddings=8, num_user_embeddings=20,
                    sem_id_dim=3)
        ladder = BucketLadder((1, 2), (4, 8))
        n_requests = 10
    else:
        n_corpus = 400
        arch = dict(embedding_dim=64, attn_dim=128, dropout=0.0, num_heads=4,
                    n_layers=4, num_item_embeddings=64,
                    num_user_embeddings=10_000, sem_id_dim=3)
        ladder = BucketLadder((1, 4, 8), (8, 16))
        n_requests = 32
    D = arch["sem_id_dim"]
    Kcb = arch["num_item_embeddings"]
    max_hist = ladder.history_buckets[-1]

    model = Tiger(**arch)
    rng = np.random.default_rng(0)
    valid_a, valid_b = _corpora(rng, n_corpus, Kcb, D)
    snap_a = CatalogSnapshot.build(valid_a, Kcb)
    # Pin B to A's capacity rung: this check asserts the SAME-RUNG swap
    # is compile-free, so the rung must not depend on where the random
    # corpus sizes happen to land relative to a ladder boundary.
    snap_b = CatalogSnapshot.build(
        valid_b, Kcb, capacity=snap_a.trie().capacity
    )
    assert snap_a.trie().aval_signature() == snap_b.trie().aval_signature()
    sets = {
        snap_a.version: {tuple(int(c) for c in r) for r in valid_a},
        snap_b.version: {tuple(int(c) for c in r) for r in valid_b},
    }
    n_items = min(len(valid_a), len(valid_b))
    B0, L0 = 2, 2 * D
    params = model.init(
        jax.random.key(0),
        jnp.zeros((B0,), jnp.int32), jnp.zeros((B0, L0), jnp.int32),
        jnp.zeros((B0, L0), jnp.int32), jnp.zeros((B0, D), jnp.int32),
        jnp.zeros((B0, D), jnp.int32), jnp.ones((B0, L0), jnp.int32),
    )["params"]
    # The smallest trie table across both snapshots: any literal at or
    # above it in an executable would be a (partially) baked catalog.
    trie_bytes = min(
        4 * snap_a.trie().keys.size, 4 * snap_b.trie().keys.size
    )

    def drive(engine, n, corpus_version):
        ok = True
        futs = []
        for _ in range(n):
            futs.append(engine.submit(Request(
                head="tiger",
                history=rng.integers(0, n_items, int(rng.integers(1, max_hist + 1))),
            )))
        for f in futs:
            r = f.result(600)
            good = all(
                tuple(int(c) for c in t) in sets[r.catalog_version]
                for t in np.asarray(r.sem_ids).reshape(-1, D)
            )
            ok = ok and good and (np.asarray(r.items) >= 0).all()
            if corpus_version is not None:
                ok = ok and r.catalog_version == corpus_version
        return ok

    phases = {}
    for phase, paged in (("dense", False), ("paged", True)):
        head = TigerGenerativeHead(model, catalog=snap_a, top_k=5)
        engine = ServingEngine(
            [head], params, ladder=ladder, max_batch=ladder.max_batch,
            max_wait_ms=1.0, handle_signals=False, paged=paged,
        ).start()
        items_ok = drive(engine, n_requests, snap_a.version)
        # Hot swap A -> B mid-life; serve more traffic until it applies,
        # then a steady batch pinned to B.
        engine.stage_catalog("tiger", snap_b)
        deadline = time.monotonic() + 300
        while engine.catalog_version("tiger") != snap_b.version:
            if time.monotonic() > deadline:
                break
            items_ok = items_ok and drive(engine, 1, None)
        swapped = engine.catalog_version("tiger") == snap_b.version
        items_ok = items_ok and drive(engine, n_requests, snap_b.version)

        # Acceptance: engine answer (under B, through the SWAPPED
        # executables) == the baked-DenseTrie reference on the shared
        # catalog, bit-identical sem_ids.
        fixed = Request(head="tiger", history=np.arange(min(4, n_items)))
        r = engine.serve(fixed, timeout=600)
        Bb = ladder.batch_bucket(1)
        Lb = ladder.history_bucket(len(fixed.history))
        batch = head.make_batch([fixed], Bb, Lb)
        ref = tiger_generate(
            model, params, DenseTrie.build(valid_b, Kcb), *batch,
            jax.random.key(0), n_top_k_candidates=5, deterministic=True,
        )
        bit_identical = bool(
            (np.asarray(ref.sem_ids)[0] == np.asarray(r.sem_ids)).all()
        )

        # No catalog-sized literal in ANY live executable.
        baked = []
        for hlo in _executable_hlos(engine, "tiger"):
            baked += [
                c for c in ir.hlo_constants(hlo) if c["bytes"] >= trie_bytes
            ]
        stats = engine.stop()
        rec = {
            "warmup_compiles": stats["warmup_compiles"],
            "recompilations": stats["recompilations"],
            "catalog_swaps": stats["catalog_swaps"],
            "catalog_compiles": stats["catalog_compiles"],
            "swapped": swapped,
            "items_valid_per_version": items_ok,
            "bit_identical_vs_baked": bit_identical,
            "catalog_sized_constants": len(baked),
            "trie_bytes_threshold": trie_bytes,
        }
        rec["ok"] = (
            stats["recompilations"] == 0
            and stats["catalog_compiles"] == 0  # same rung: operand swap only
            and stats["catalog_swaps"] == 1
            and swapped
            and items_ok
            and bit_identical
            and not baked
        )
        phases[phase] = rec

    ok = all(p["ok"] for p in phases.values())
    ir.emit_verdict({
        "backend": backend,
        "dense": phases["dense"],
        "paged": phases["paged"],
        "ok": ok,
    })
    if args.write_note:
        msg = (
            "OK: one warmed engine served two catalog snapshots (dense+paged), "
            "0 recompiles, 0 catalog-sized constants, bit-identical vs baked trie"
            if ok else "ATTENTION: catalog swap recompiled or baked the trie"
        )
        ir.append_perf_note(
            f"\n- Catalog HLO check (scripts/check_catalog_hlo.py, backend="
            f"{backend}): {msg}\n"
        )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
