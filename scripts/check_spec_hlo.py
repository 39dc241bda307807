"""Speculative tree decode check (shared analysis/ir.py harness: one
verdict JSON on stdout, rc 0 ok / 1 failed, --small/--platform/
--write-note CLI like every check_* script).

What it proves, on a warmed speculative TIGER engine under staggered
admit/evict churn (the traffic shape continuous batching exists for,
with slots sitting at MIXED steps while trees verify):

1. **Zero steady-state recompiles** — drafting, verification and the
   accept scan are all inside ONE fixed-shape executable per slot-count
   rung; speculation adds nothing to the steady-state compile surface.
2. **Exactly one tree topology per rung** — the runner's executable set
   holds one tree-verify executable per slot rung (and NO plain decode
   executables: the verified-rejection worst case IS the plain step),
   all sharing a single (beams, fanout, depth) topology.
3. **Accepted output == plain engine** — the same request sequence
   through a plain engine yields bit-identical items/sem_ids (scores to
   float association <= 1e-5, the paged==dense pin), while the spec
   engine spends strictly fewer target invocations and commits > 1 code
   per slot-step on average.
4. **Pools clean after drain** — no leaked slot pages, no lingering
   scratch reservation, no retained prefix pages, slots all free.
5. **Span shape** — a traced spec request carries the draft ->
   tree_verify -> accept triple in place of per-code decode_step spans
   (scripts/check_obs.py's completeness rule accepts both shapes).

Usage: python scripts/check_spec_hlo.py [--small] [--platform cpu]
"""

from __future__ import annotations

import collections
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from genrec_tpu.analysis import ir  # noqa: E402


def _drive_churn(engine, head, valid_ids, n_requests, max_hist, n_users, rng):
    """Staggered rolling-window churn (check_serving_hlo's shape): new
    requests admit into slots while other slots are mid-verify, so spec
    iterations run at mixed per-slot steps. Returns ordered responses."""
    import numpy as np

    from genrec_tpu.serving import Request

    reqs = [
        Request(
            head=head.name,
            history=rng.integers(0, len(valid_ids), int(rng.integers(1, max_hist + 1))),
            user_id=int(rng.integers(0, n_users)),
        )
        for _ in range(n_requests)
    ]
    inflight = collections.deque()
    window = 2 * engine._max_batch + 1
    out = []
    i = 0
    while i < len(reqs) or inflight:
        while i < len(reqs) and len(inflight) < window:
            inflight.append(engine.submit(reqs[i]))
            i += 1
        out.append(inflight.popleft().result(300))
    return reqs, out


def main(argv=None):
    args = ir.check_args(argv)

    import jax

    if args.platform:
        from genrec_tpu.parallel.mesh import pin_platform

        pin_platform(args.platform)

    import jax.numpy as jnp
    import numpy as np

    from genrec_tpu.models.tiger import Tiger
    from genrec_tpu.obs import SpanTracer
    from genrec_tpu.serving import BucketLadder, ServingEngine
    from genrec_tpu.serving.heads import TigerGenerativeHead

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from check_obs import check_span_tree

    backend = jax.default_backend()
    if args.small:
        n_corpus = 50
        arch = dict(embedding_dim=16, attn_dim=32, dropout=0.0, num_heads=4,
                    n_layers=2, num_item_embeddings=8, num_user_embeddings=20,
                    sem_id_dim=3)
        ladder = BucketLadder((1, 2), (4, 8))
        n_requests = 14
    else:
        n_corpus = 1000
        arch = dict(embedding_dim=64, attn_dim=128, dropout=0.0, num_heads=4,
                    n_layers=4, num_item_embeddings=64,
                    num_user_embeddings=10_000, sem_id_dim=3)
        ladder = BucketLadder((1, 4, 8), (8, 16))
        n_requests = 40
    D = arch["sem_id_dim"]
    Kcb = arch["num_item_embeddings"]
    max_hist = ladder.history_buckets[-1]
    n_users = arch["num_user_embeddings"]

    model = Tiger(**arch)
    rng = np.random.default_rng(0)
    valid_ids = np.unique(rng.integers(0, Kcb, (n_corpus, D)), axis=0)
    B0, L0 = 2, 2 * D
    params = model.init(
        jax.random.key(0),
        jnp.zeros((B0,), jnp.int32), jnp.zeros((B0, L0), jnp.int32),
        jnp.zeros((B0, L0), jnp.int32), jnp.zeros((B0, D), jnp.int32),
        jnp.zeros((B0, D), jnp.int32), jnp.ones((B0, L0), jnp.int32),
    )["params"]

    tracer = SpanTracer(capacity=16384)
    seed = np.random.default_rng(7)

    # -- speculative engine under churn --------------------------------------
    head = TigerGenerativeHead(model, valid_ids, top_k=5)
    engine = ServingEngine(
        [head], params, ladder=ladder, max_batch=ladder.max_batch,
        max_wait_ms=1.0, handle_signals=False, spec_decode=True,
        spec_fanout=min(16, Kcb), tracer=tracer,
    ).start()
    runner = engine._runners[head.name]
    rungs = list(runner.slots.rungs)
    # One table of executables by rung: told apart by the name each
    # carries (`jit_<head>_spec_s<S>` / `jit_<head>_decode_s<S>`).
    is_spec = {S: "_spec_s" in c.as_text().split("\n", 1)[0]
               for S, c in runner.slots.executables.items()}
    spec_execs = sorted(S for S, spec in is_spec.items() if spec)
    plain_execs = sorted(S for S, spec in is_spec.items() if not spec)
    topology = runner.spec_topology.signature()
    scratch_reserved = runner.pool.scratch_page_count
    reqs, spec_resps = _drive_churn(
        engine, head, valid_ids, n_requests, max_hist, n_users,
        np.random.default_rng(7),
    )
    first_id = spec_resps[0].request_id
    spans_ok = True
    try:
        names = check_span_tree(tracer.spans(first_id))
        if not {"draft", "tree_verify", "accept"} <= set(names):
            raise AssertionError(f"spec span triple missing (got {names})")
        if "decode_step" in names:
            raise AssertionError("spec iteration still emitted decode_step")
    except AssertionError as e:
        spans_ok = False
        span_err = str(e)
    spec_stats = engine.stop()

    # -- plain engine, identical request sequence ----------------------------
    head2 = TigerGenerativeHead(model, valid_ids, top_k=5)
    engine2 = ServingEngine(
        [head2], params, ladder=ladder, max_batch=ladder.max_batch,
        max_wait_ms=1.0, handle_signals=False, spec_decode=False,
    ).start()
    _, plain_resps = _drive_churn(
        engine2, head2, valid_ids, n_requests, max_hist, n_users,
        np.random.default_rng(7),
    )
    plain_stats = engine2.stop()

    parity_ok = all(
        np.array_equal(a.items, b.items)
        and np.array_equal(a.sem_ids, b.sem_ids)
        # float32 sums of magnitude 5-17 (one ulp 1e-6 to 2e-6) added in
        # another order: the relative term tests/test_spec_decode.py carries.
        and np.allclose(a.scores, b.scores, atol=1e-5, rtol=1e-5)
        for a, b in zip(spec_resps, plain_resps)
    )
    spec = spec_stats["spec"].get(head.name, {})
    pool = spec_stats["kv_pool"][head.name]
    codes_per_inv = spec.get("codes_per_invocation", 0.0)

    ok = (
        spec_stats["recompilations"] == 0
        and plain_stats["recompilations"] == 0
        and spec_execs == rungs          # one tree-verify executable per rung
        and plain_execs == []            # and no plain decode beside it
        and scratch_reserved > 0
        and parity_ok
        and spans_ok
        and spec_stats["completed"] == n_requests
        and spec_stats["decode_steps"] < plain_stats["decode_steps"]
        and codes_per_inv > 1.0
        and pool["pages_in_use"] == 0
        and pool["scratch_pages"] == 0
        and pool["slots_active"] == 0
    )
    verdict = {
        "backend": backend,
        "submitted": n_requests,
        "completed": spec_stats["completed"],
        "recompilations": spec_stats["recompilations"]
        + plain_stats["recompilations"],
        "rungs": rungs,
        "topology": list(topology),
        "topologies_per_rung": 1 if spec_execs == rungs else len(spec_execs),
        "spec_steps": spec.get("spec_steps", 0),
        "plain_decode_steps": plain_stats["decode_steps"],
        "spec_decode_steps": spec_stats["decode_steps"],
        "codes_per_invocation": codes_per_inv,
        "accept_hist": spec.get("accept_len_hist", {}),
        "scratch_pages_reserved": scratch_reserved,
        "parity_ok": parity_ok,
        "spans_ok": spans_ok,
        "pages_in_use_final": pool["pages_in_use"],
        "scratch_pages_final": pool["scratch_pages"],
        "slots_active_final": pool["slots_active"],
        "ok": ok,
    }
    if not spans_ok:
        verdict["span_error"] = span_err
    ir.emit_verdict(verdict)

    if args.write_note:
        if ok:
            msg = (
                f"OK: {n_requests} churned requests bit-identical to the "
                f"plain engine at {codes_per_inv:.2f} codes/invocation "
                f"({spec_stats['decode_steps']} spec vs "
                f"{plain_stats['decode_steps']} plain target invocations), "
                f"one ({topology[0]}x{topology[1]}x{topology[2]}) topology "
                f"across rungs {rungs}, 0 recompiles, pools clean"
            )
        else:
            msg = "ATTENTION: speculative decode check failed"
        ir.append_perf_note(
            f"\n- Speculative decode check (scripts/check_spec_hlo.py, "
            f"backend={backend}): {msg}\n"
        )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
