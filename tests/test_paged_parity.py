"""Paged decode path: paged == dense-cache parity for TIGER and COBRA.

Same harness discipline as tests/test_decode_cache.py (tiny models,
module-scoped fixtures, cached path as the reference) with the masks
CONTIGUOUS — the serving layout the paged path's seq_lens contract
requires. sem_ids must match bit-exactly, scores <= 1e-5 (the acceptance
pin), for both trie types and with the trie-constrained serving
configuration.

The ragged (per-row step) primitives are additionally pinned against
their static-step twins, because the engine runs slots at MIXED steps —
a configuration the lockstep parity drivers never exercise.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from genrec_tpu.models.cobra import Cobra, cobra_generate, cobra_generate_paged
from genrec_tpu.models.tiger import Tiger, tiger_generate, tiger_generate_paged
from genrec_tpu.ops.trie import (
    DenseTrie,
    PackedTrie,
    advance_ragged,
    legal_mask_ragged,
    tuples_are_valid,
)

K_CB = 8


@pytest.fixture(scope="module")
def tiger_setup():
    model = Tiger(embedding_dim=16, attn_dim=32, dropout=0.0, num_heads=4,
                  n_layers=4, num_item_embeddings=K_CB, num_user_embeddings=20,
                  sem_id_dim=3, max_pos=64)
    rng = np.random.default_rng(0)
    valid = np.unique(rng.integers(0, K_CB, (30, 3)), axis=0)
    B, L = 3, 12
    # Contiguous valid prefixes of MIXED lengths (the serving layout):
    # the whole point of paging is rows resident at different lengths.
    mask = np.zeros((B, L), np.int32)
    for i, n in enumerate((12, 6, 9)):
        mask[i, :n] = 1
    batch = dict(
        user=jnp.asarray(rng.integers(0, 20, (B,)), jnp.int32),
        items=jnp.asarray(rng.integers(0, K_CB, (B, L)), jnp.int32),
        types=jnp.asarray(np.tile(np.arange(3), (B, L // 3)), jnp.int32),
        mask=jnp.asarray(mask),
    )
    params = model.init(
        jax.random.key(0), batch["user"], batch["items"], batch["types"],
        jnp.zeros((B, 3), jnp.int32), jnp.zeros((B, 3), jnp.int32), batch["mask"],
    )["params"]
    return model, params, valid, batch


def _tiger_pair(model, params, trie, b, deterministic):
    kw = dict(n_top_k_candidates=5, deterministic=deterministic)
    dense = tiger_generate(model, params, trie, b["user"], b["items"], b["types"],
                           b["mask"], jax.random.key(7), use_cache=True, **kw)
    paged = tiger_generate_paged(model, params, trie, b["user"], b["items"],
                                 b["types"], b["mask"], jax.random.key(7), **kw)
    return dense, paged


def test_tiger_paged_matches_dense_constrained(tiger_setup):
    model, params, valid, b = tiger_setup
    trie = DenseTrie.build(valid, K_CB)
    dense, paged = _tiger_pair(model, params, trie, b, deterministic=True)
    np.testing.assert_array_equal(
        np.asarray(dense.sem_ids), np.asarray(paged.sem_ids)
    )
    np.testing.assert_allclose(
        np.asarray(dense.log_probas), np.asarray(paged.log_probas), atol=1e-5
    )
    # Constraint held through the paged path: every beam is a real item.
    assert bool(np.asarray(tuples_are_valid(trie, paged.sem_ids)).all())


@pytest.mark.slow
@pytest.mark.parametrize("trie_cls", [DenseTrie, PackedTrie])
@pytest.mark.parametrize("deterministic", [True, False])
def test_tiger_paged_matches_dense_all_modes(tiger_setup, trie_cls, deterministic):
    model, params, valid, b = tiger_setup
    trie = trie_cls.build(valid, K_CB)
    dense, paged = _tiger_pair(model, params, trie, b, deterministic)
    np.testing.assert_array_equal(
        np.asarray(dense.sem_ids), np.asarray(paged.sem_ids)
    )
    np.testing.assert_allclose(
        np.asarray(dense.log_probas), np.asarray(paged.log_probas), atol=1e-5
    )


@pytest.fixture(scope="module")
def cobra_setup():
    model = Cobra(encoder_n_layers=1, encoder_hidden_dim=16, encoder_num_heads=2,
                  encoder_vocab_size=50, id_vocab_size=K_CB, n_codebooks=3,
                  d_model=16, max_len=64, temperature=0.2, decoder_n_layers=2,
                  decoder_num_heads=2, decoder_dropout=0.0)
    rng = np.random.default_rng(0)
    B, T, C, Ltxt = 3, 4, 3, 5
    ids = rng.integers(0, K_CB, (B, T * C)).astype(np.int32)
    # Partially-padded rows exercise the prefill-tail read (h_pre at
    # n_valid + c - 1), full rows the incremental suffix read.
    ids[1, 2 * C:] = model.pad_id
    ids[2, 3 * C:] = model.pad_id
    txt = rng.integers(1, 50, (B, T, Ltxt)).astype(np.int32)
    valid = np.unique(rng.integers(0, K_CB, (30, 3)), axis=0)
    params = model.init(jax.random.key(0), jnp.asarray(ids), jnp.asarray(txt))["params"]
    return model, params, jnp.asarray(ids), jnp.asarray(txt), valid


@pytest.mark.parametrize("constrained", [True, False])
def test_cobra_paged_matches_dense(cobra_setup, constrained):
    model, params, ids, txt, valid = cobra_setup
    trie = DenseTrie.build(valid, K_CB) if constrained else None
    dense = cobra_generate(model, params, ids, txt, n_candidates=4,
                           temperature=1.0, use_cache=True, trie=trie)
    paged = cobra_generate_paged(model, params, ids, txt, n_candidates=4,
                                 temperature=1.0, trie=trie)
    np.testing.assert_array_equal(
        np.asarray(dense.sem_ids), np.asarray(paged.sem_ids)
    )
    np.testing.assert_allclose(
        np.asarray(dense.scores), np.asarray(paged.scores), atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(dense.dense_vecs), np.asarray(paged.dense_vecs), atol=1e-5
    )
    if trie is not None:
        assert bool(np.asarray(tuples_are_valid(trie, paged.sem_ids)).all())


# ---- the programs ask for no pool relayout ----------------------------------

# 11 pages: the pool's element count then has a factor nothing else in
# these tiny programs has, so "a value of the pool's size" IS a pool.
_POOL_PAGES, _PAGE = 11, 8


def _tiger_paged_programs(model, params, valid, b):
    from genrec_tpu.models.tiger import (
        init_tiger_paged_state, tiger_paged_decode_step, tiger_prefill_paged,
    )

    B = b["items"].shape[0]
    trie = DenseTrie.build(valid, K_CB)
    bt = jnp.asarray(1 + np.arange(B * 2).reshape(B, 2), jnp.int32)
    state = init_tiger_paged_state(model, B, 5)
    steps = jnp.ones((B,), jnp.int32)
    sl = jnp.full((B,), 9, jnp.int32)
    geo = (model.n_layers // 2, model.num_heads,
           model.attn_dim // model.num_heads, model.dtype)
    return geo, {
        "prefill": lambda kp, vp: tiger_prefill_paged(
            model, params, b["user"], b["items"], b["types"], b["mask"],
            bt, kp, vp),
        "decode": lambda kp, vp: tiger_paged_decode_step(
            model, params, trie, state, steps, bt, sl, kp, vp),
    }


def _cobra_paged_programs(model, params, ids, txt, valid):
    from genrec_tpu.models.cobra import (
        cobra_paged_decode_step, cobra_prefill_paged, init_cobra_paged_state,
    )

    B = ids.shape[0]
    trie = DenseTrie.build(valid, K_CB)
    vecs = model.apply({"params": params}, txt, method=Cobra.encode_items)
    bt = jnp.asarray(1 + np.arange(B * 2).reshape(B, 2), jnp.int32)
    state = init_cobra_paged_state(model, B, 4)
    steps = jnp.ones((B,), jnp.int32)
    sl = jnp.full((B,), 9, jnp.int32)
    geo = (model.decoder_n_layers, model.decoder_num_heads,
           model.d_model // model.decoder_num_heads, model.dtype)
    return geo, {
        "prefill": lambda kp, vp: cobra_prefill_paged(
            model, params, ids, vecs, bt, kp, vp, trie, 4, 1.0),
        "decode": lambda kp, vp: cobra_paged_decode_step(
            model, params, trie, state, steps, bt, sl, kp, vp),
    }


@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernel"])
@pytest.mark.parametrize("program", ["tiger_prefill", "tiger_decode",
                                     "cobra_prefill", "cobra_decode"])
def test_paged_programs_ask_for_no_pool_relayout(
        program, kernel, kv_dtype, request, monkeypatch):
    """A pool leaf is consumed by the page scatter, the fallback's page
    gather or the Pallas call, and by nothing else: no reshape,
    transpose or convert of a pool-sized value is ever traced, so no
    launch can owe a whole-pool copy to what the PROGRAM asks for (what
    the chip's compiler then does is chip_smoke.py's check)."""
    from genrec_tpu.analysis.ir import primitives_of_size
    from genrec_tpu.kernels import policy
    from genrec_tpu.ops.paged import zero_pool

    head, which = program.split("_")
    setup = request.getfixturevalue(f"{head}_setup")
    (nl, H, hd, dtype), programs = (
        _tiger_paged_programs if head == "tiger" else _cobra_paged_programs
    )(*setup)
    monkeypatch.setattr(policy, "auto_paged_attention", lambda: kernel)
    pools = tuple(zero_pool(_POOL_PAGES, _PAGE, H, hd, dtype, kv_dtype)
                  for _ in range(nl))
    jaxpr = jax.make_jaxpr(programs[which])(pools, pools)
    prims = primitives_of_size(jaxpr.jaxpr, _POOL_PAGES * _PAGE * H * hd)
    read = "pallas_call" if kernel else "gather"
    want = {"scatter"} if which == "prefill" else {read}
    assert prims == want, prims


# ---- ragged primitives at MIXED steps ---------------------------------------


@pytest.mark.parametrize("trie_cls", [DenseTrie, PackedTrie])
def test_trie_ragged_helpers_match_static_steps(trie_cls, rng):
    """legal_mask_ragged/advance_ragged row t must equal the static-step
    call at t — for rows at DIFFERENT steps in one call, which is the
    configuration the engine's decode executable actually runs."""
    valid = np.unique(rng.integers(0, K_CB, (40, 3)), axis=0)
    trie = trie_cls.build(valid, K_CB)
    S, K = 6, 4
    steps = jnp.asarray([0, 1, 2, 2, 1, 0], jnp.int32)
    # Per-row prefixes valid FOR that row's step: walk real tuples.
    prefix = np.zeros((S, K), np.int64)
    for s in range(S):
        for k in range(K):
            row = valid[rng.integers(len(valid))]
            p = jnp.zeros((), jnp.int32)
            for t in range(int(steps[s])):
                p = trie.advance(p[None], jnp.asarray(row[t])[None], t)[0]
            prefix[s, k] = int(p)
    prefix = jnp.asarray(prefix, jnp.int32)
    tok = jnp.asarray(rng.integers(0, K_CB, (S, K)), jnp.int32)

    got_mask = legal_mask_ragged(trie, prefix, steps)
    got_adv = advance_ragged(trie, prefix, tok, steps)
    for s in range(S):
        t = int(steps[s])
        np.testing.assert_array_equal(
            np.asarray(got_mask[s]), np.asarray(trie.legal_mask(prefix[s], t))
        )
        np.testing.assert_array_equal(
            np.asarray(got_adv[s]), np.asarray(trie.advance(prefix[s], tok[s], t))
        )


def test_decode_self_ragged_matches_static(rng):
    """T5Attention.decode_self_ragged at mixed per-row steps == the
    static decode_self applied row-by-row at each row's step."""
    from genrec_tpu.models.t5transformer import T5Attention

    B, K, d, H, S = 4, 3, 16, 2, 5
    attn = T5Attention(d_model=d, n_heads=H)
    x = jnp.asarray(rng.normal(size=(B, K, d)), jnp.float32)
    params = attn.init(jax.random.key(0), x)["params"]  # (B, L=K, d) trace
    cache = {
        "k": jnp.asarray(rng.normal(size=(B, K, S, H, d // H)), jnp.float32),
        "v": jnp.asarray(rng.normal(size=(B, K, S, H, d // H)), jnp.float32),
    }
    steps = jnp.asarray([0, 2, 4, 1], jnp.int32)
    out_r, cache_r = attn.apply(
        {"params": params}, x, cache, steps, method=T5Attention.decode_self_ragged
    )
    for b in range(B):
        row = lambda t: jax.tree_util.tree_map(lambda a: a[b : b + 1], t)
        out_s, cache_s = attn.apply(
            {"params": params}, x[b : b + 1], row(cache), int(steps[b]),
            method=T5Attention.decode_self,
        )
        np.testing.assert_allclose(
            np.asarray(out_r[b]), np.asarray(out_s[0]), atol=1e-5
        )
        for leaf in ("k", "v"):
            np.testing.assert_allclose(
                np.asarray(cache_r[leaf][b]), np.asarray(cache_s[leaf][0]),
                atol=1e-6,
            )
