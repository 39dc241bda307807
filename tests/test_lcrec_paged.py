"""The paged LCRec head over the Solar-Open2 layer kinds (NoPE gated GQA
beside KDA with negative eigenvalues, sigmoid-routed experts of which a share
is held) at a small size on the CPU: the backbone against the configuration's
plain reference; the shares of the experts add up to the uncut layer; the
paged head == the head's dense ``make_fn`` == the reference's full forward;
an answer does not depend on how the micro-batch formed, nor on whether the
admit was warm; a beam reorder carries the recurrent state with its row; the
options the head refuses raise by name."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "benchmark"))

from solar_tiny import module, tiny_config  # noqa: E402

from genrec_tpu.models.backbones.qwen import QwenLM, QwenMoEMLP  # noqa: E402
from genrec_tpu.models.lcrec import (  # noqa: E402
    generate_topk_constrained,
    lcrec_paged_decode_step,
)
from genrec_tpu.serving import (  # noqa: E402
    BucketLadder,
    PagedConfig,
    Request,
    ServingEngine,
)

SEED = 2**31 + 17


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config()
    ad, ref = module("adapter"), module("reference")
    params = ad.make_params(cfg, SEED)
    catalog = ad.make_catalog(cfg, SEED)
    return cfg, ad, ref, params, catalog


def _rows(cfg, catalog, lengths, L, seed=0):
    """Left-padded prompt rows of the given item counts at ``L`` items."""
    rng = np.random.default_rng(seed)
    ad = module("adapter")
    D = cfg["sem_id_dim"]
    ids = np.zeros((len(lengths), L * D), np.int32)
    mask = np.zeros_like(ids)
    for i, n in enumerate(lengths):
        toks = ad.prompt_tokens(cfg, catalog, rng.integers(0, len(catalog), n))
        ids[i, L * D - len(toks):] = toks
        mask[i, L * D - len(toks):] = 1
    return ids, mask


# ---------------------------------------------------------------------------
# backbone against the reference
# ---------------------------------------------------------------------------


def test_backbone_logits_match_the_reference(tiny):
    cfg, ad, ref, params, catalog = tiny
    ids, mask = _rows(cfg, catalog, [24, 9], 24)
    model = QwenLM(ad.model_config(cfg), dtype=jnp.float32)
    got = model.apply({"params": params}, jnp.asarray(ids),
                      attention_mask=jnp.asarray(mask))
    want = ref.forward(params, cfg, ids, mask)
    real = mask.astype(bool)
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real],
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("knob, variant", [
    ("use_rope", None), ("attn_output_gate", "no_gate"),
    ("kda_neg_eigval", "beta_single")])
def test_each_key_of_the_row_matters(tiny, knob, variant):
    """NoPE, the output gate and the doubled write strength: the program
    with one of them flipped leaves the reference far behind, and where the
    reference can play the flipped form too, it follows."""
    import dataclasses

    cfg, ad, ref, params, catalog = tiny
    ids, mask = _rows(cfg, catalog, [20], 24)
    flipped = dataclasses.replace(
        ad.model_config(cfg), **{knob: not getattr(ad.model_config(cfg), knob)})
    p = params
    if knob == "attn_output_gate":  # a model without the gate has no gate_proj
        p = jax.tree_util.tree_map(lambda x: x, params)
        for name in list(p):
            if name.startswith("layer_") and "self_attn" in p[name]:
                p[name] = dict(p[name], self_attn={
                    k: v for k, v in p[name]["self_attn"].items() if k != "gate_proj"})
    got = QwenLM(flipped, dtype=jnp.float32).apply(
        {"params": p}, jnp.asarray(ids), attention_mask=jnp.asarray(mask))
    want = ref.forward(params, cfg, ids, mask)
    real = mask.astype(bool)
    gap = np.abs(np.asarray(got)[real] - np.asarray(want)[real]).max()
    assert gap > 1e-2, gap
    if variant is not None:
        played = ref.forward(params, cfg, ids, mask, variant=variant)
        np.testing.assert_allclose(np.asarray(got)[real], np.asarray(played)[real],
                                   atol=2e-4, rtol=2e-4)


def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(tiny):
    cfg, ad, ref, _, _ = tiny
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.standard_normal((2, 12, cfg["hidden_size"])), jnp.float32)
    valid = jnp.asarray(rng.random((2, 12)) > 0.2)
    whole = ad.make_params(cfg, SEED, share=(0, 16))["layer_0"]["moe"]
    flat = u.reshape(-1, cfg["hidden_size"])
    want = ref.moe(whole, cfg, flat, valid.reshape(-1), "f32", share=(0, 16))
    shared = ref.swiglu(whole["shared_expert"], flat, "f32")
    routed = jnp.zeros_like(want)
    for first in (0, 4, 8, 12):
        part = ad.make_params(cfg, SEED, share=(first, 4))["layer_0"]["moe"]
        moe = QwenMoEMLP(ad.model_config(cfg, share=(first, 4)), jnp.float32)
        y = moe.apply({"params": part}, u, valid).reshape(want.shape)
        routed = routed + (y - shared)
    np.testing.assert_allclose(np.asarray(routed + shared), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# the paged head
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served(tiny):
    cfg, ad, ref, params, catalog = tiny
    head = ad.make_head(cfg, catalog)
    a = cfg["assumed"]["serve"]
    engine = ServingEngine(
        [head], params, paged=True, paged_config=ad.paged_config(cfg, head),
        ladder=BucketLadder(tuple(a["batch_buckets"]), tuple(a["history_buckets"])),
        max_batch=a["max_batch"], max_wait_ms=a["max_wait_ms"],
        prefix_cache=True, prefix_cache_entries=a["prefix_cache_entries"],
        handle_signals=False)
    engine.start()
    yield engine, head
    engine.stop()


def _ask(engine, head, history):
    return engine.submit(Request(head=head.name, history=np.asarray(history))
                         ).result(120)


def _dense(cfg, head, params, history, L):
    req = Request(head=head.name, history=np.asarray(history))
    ids, mask = head.make_batch([req], 1, L)
    D = cfg["sem_id_dim"]
    out = generate_topk_constrained(
        head.model, params, ids, mask, cfg["base_vocab"], D,
        cfg["codebook_size"], beam_width=head.top_k, max_cache=L * D + D,
        trie=head.catalog.device_trie())
    return np.asarray(out.sem_ids[0]), np.asarray(out.log_probas[0])


@pytest.mark.parametrize("n_items, bucket", [(3, 8), (8, 8), (17, 24), (24, 24)])
def test_paged_head_equals_dense_make_fn_equals_reference(tiny, served, n_items,
                                                          bucket):
    cfg, ad, ref, params, catalog = tiny
    engine, head = served
    history = np.random.default_rng(n_items).integers(0, len(catalog), n_items)
    r = _ask(engine, head, history)
    assert r.bucket[1] == bucket
    sem, logp = _dense(cfg, head, params, history, bucket)
    np.testing.assert_array_equal(r.sem_ids, sem)
    np.testing.assert_allclose(r.scores, logp, atol=2e-4)
    # the reference's full forward over prompt ++ each served beam
    cat = ref.Catalog(catalog, cfg["codebook_size"])
    prompt = ad.prompt_tokens(cfg, catalog, history)
    sem = np.asarray(r.sem_ids).astype(np.int64)
    rows = ref.served_logps(params, cfg, cat, [prompt], sem[None])[0]
    path = [sum(rows[w, d, sem[w, d]] for d in range(sem.shape[1]))
            for w in range(len(sem))]
    np.testing.assert_allclose(r.scores, path, atol=5e-4)
    # and the reference's own plain beam over the trie gives the same answer
    beams, scores = ref.beam_search(params, cfg, cat, prompt, len(sem))
    np.testing.assert_array_equal(beams, sem)
    np.testing.assert_allclose(scores, r.scores, atol=5e-4)
    assert (r.items >= 0).all()


def test_alone_equals_cobatched_under_a_longer_bucket(tiny, served):
    """What sank PR 22: the same request, alone in its own bucket and
    co-batched with a longer one (so prefilled at the longer bucket, its
    chunks of the scan cut elsewhere), answers the same."""
    cfg, _, _, _, catalog = tiny
    engine, head = served
    rng = np.random.default_rng(5)
    short, long_ = rng.integers(0, len(catalog), 5), rng.integers(0, len(catalog), 22)
    engine._runners[head.name].clear_prefix_cache("test")
    alone = _ask(engine, head, short)
    assert alone.bucket == (1, 8)
    engine._runners[head.name].clear_prefix_cache("test")
    futs = [engine.submit(Request(head=head.name, history=h))
            for h in (long_, short)]
    both = [f.result(120) for f in futs]
    assert both[1].bucket == (2, 24), both[1].bucket
    np.testing.assert_array_equal(both[1].sem_ids, alone.sem_ids)
    np.testing.assert_allclose(both[1].scores, alone.scores, atol=2e-4)


def test_warm_admit_from_a_snapshot_equals_the_cold_admit(tiny, served):
    cfg, _, _, _, catalog = tiny
    engine, head = served
    history = np.random.default_rng(9).integers(0, len(catalog), 19)
    runner = engine._runners[head.name]
    runner.clear_prefix_cache("test")
    before = engine.stats()
    cold = _ask(engine, head, history)
    mid = engine.stats()
    warm = _ask(engine, head, history)
    after = engine.stats()
    hits = lambda s: s["prefix_cache"].get(head.name, {}).get("hits", 0)
    assert hits(mid) == hits(before) and hits(after) == hits(mid) + 1
    assert after["batches"] == mid["batches"]  # no prefill ran for the repeat
    np.testing.assert_array_equal(warm.sem_ids, cold.sem_ids)
    np.testing.assert_array_equal(warm.scores, cold.scores)
    # the snapshot's bytes are counted, and the table's recurrent leaves
    prefix = after["prefix_cache"][head.name]
    per_entry = sum(int(np.prod(v.shape[1:])) * 4 for k, v in
                    head.paged_state_zeros(1).items()
                    if k in head.paged_init_leaves)
    assert prefix["snapshot_bytes"] == prefix["entries"] * per_entry
    assert prefix["snapshot_device_bytes"] == 0
    assert after["kv_pool"][head.name]["recurrent_state_bytes"] == \
        runner.slots.recurrent_nbytes > 0
    assert after["prefill_prompt_tokens"] - before["prefill_prompt_tokens"] == \
        19 * cfg["sem_id_dim"]


def test_a_beam_reorder_carries_the_recurrent_state_with_its_row(tiny):
    """Every per-beam leaf is read through the last step's ``parent``: a
    step on a state whose beams were permuted, with the permutation as its
    parent, is the step on the state in order."""
    from genrec_tpu.ops.paged import zero_pool

    cfg, ad, _, params, catalog = tiny
    head = ad.make_head(cfg, catalog)
    S, W, D = 2, head.top_k, cfg["sem_id_dim"]
    rng = np.random.default_rng(11)
    state = {k: jnp.asarray(rng.standard_normal(v.shape), v.dtype)
             if jnp.issubdtype(v.dtype, jnp.floating) else v
             for k, v in head.paged_state_zeros(S).items()}
    state["beam_seqs"] = jnp.asarray(rng.integers(0, cfg["codebook_size"], (S, W, D)),
                                     jnp.int32)
    state["beam_logps"] = jnp.asarray(-np.sort(rng.random((S, W)), axis=1), jnp.float32)
    layers, heads, hd, dtype = head.paged_layout()
    pools = tuple(zero_pool(4, 8, heads, hd, dtype) for _ in range(layers))
    args = (jnp.full((S,), 2, jnp.int32), jnp.zeros((S, 2), jnp.int32),
            jnp.zeros((S,), jnp.int32), pools, pools, cfg["base_vocab"],
            cfg["codebook_size"])
    step = lambda st: lcrec_paged_decode_step(
        head.model, params, None, st, *args)
    perm = np.stack([rng.permutation(W) for _ in range(S)])
    inv = np.argsort(perm, axis=1)
    per_beam = [k for k in state if k.startswith(("kda_s", "kda_conv", "suf_"))
                and "0_" not in k]
    assert per_beam
    in_order = dict(state, parent=jnp.broadcast_to(jnp.arange(W), (S, W)))
    # beam w's state sits in row inv[w]; parent[w] = inv[w] brings it back
    moved = dict(state, parent=jnp.asarray(inv, jnp.int32), **{
        k: jnp.take_along_axis(
            state[k], jnp.asarray(perm).reshape((S, W) + (1,) * (state[k].ndim - 2)),
            axis=1) for k in per_beam})
    a, b = step(in_order), step(moved)
    for k in a:
        np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]), atol=1e-6,
                                   err_msg=k)
    # and the state is the beam's own: another beam's state changes the answer
    other = dict(in_order, **{k: state[k][:, ::-1] for k in per_beam})
    assert np.abs(np.asarray(step(other)["beam_logps"])
                  - np.asarray(a["beam_logps"])).max() > 1e-4


@pytest.mark.parametrize("option, kwargs, paged", [
    ("kv_dtype='int8'", {}, {"kv_dtype": "int8"}),
    ("spec_decode", {"spec_decode": True}, {}),
    ("mesh", {"mesh": "mesh"}, {}),
])
def test_the_engine_options_the_paged_head_refuses_raise_by_name(tiny, option,
                                                                 kwargs, paged):
    cfg, ad, _, params, catalog = tiny
    head = ad.make_head(cfg, catalog)
    if "mesh" in kwargs:
        from genrec_tpu.parallel import make_mesh

        kwargs = {"mesh": make_mesh({"model": 2}, devices=jax.devices()[:2])}
    base = ad.paged_config(cfg, head)
    pc = PagedConfig(max_slots=base.max_slots, page_size=base.page_size,
                     pages_per_slot=base.pages_per_slot, num_pages=base.num_pages,
                     **paged)
    engine = ServingEngine([head], params, paged=True, paged_config=pc,
                           ladder=BucketLadder((1,), (8, 24)), max_batch=1,
                           handle_signals=False, **kwargs)
    with pytest.raises(ValueError, match=option.replace("'", ".")):
        engine.start()


def test_the_handoff_is_refused_by_name(tiny):
    cfg, ad, _, _, catalog = tiny
    head = ad.make_head(cfg, catalog)
    with pytest.raises(ValueError, match="hand-off"):
        head.paged_check_options(handoff=True)
    head.paged_check_options()  # the plain paged engine is what it serves


def test_a_backbone_with_latent_attention_keeps_the_dense_path(tiny):
    import dataclasses

    cfg, ad, _, _, catalog = tiny
    mcfg = dataclasses.replace(
        ad.model_config(cfg), mla_layers=(1,), kv_lora_rank=8,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8)
    from genrec_tpu.serving.heads import LCRecGenerativeHead

    head = LCRecGenerativeHead(QwenLM(mcfg), cfg["base_vocab"], cfg["sem_id_dim"],
                               cfg["codebook_size"], item_sem_ids=catalog, top_k=4)
    assert head.supports_paged is False
    assert ad.make_head(cfg, catalog).supports_paged is True
