"""Packed-vs-padded exactness: the contract of ISSUE 2.

Packing is a LAYOUT change, not a model change — a packed batch must
produce the same per-example losses and gradients as the equivalent
padded batch (1e-5 fp32) for SASRec, HSTU (XLA + Pallas paths), and the
TIGER encoder-decoder, and a query in segment 2 must never attend to
segment 1 (leak checks perturb a neighbor segment and assert the victim's
loss is bit-stable)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from genrec_tpu.data.batching import pack_examples
from genrec_tpu.data.synthetic import SyntheticSeqDataset
from genrec_tpu.models.hstu import HSTU
from genrec_tpu.models.sasrec import SASRec
from genrec_tpu.ops.losses import cross_entropy_with_ignore

ROW = 16


def _padded_rows(exs, keys=("input_ids", "targets")):
    """One example per row, right-aligned at slot 0 — the padded layout
    whose position indexing matches the packer's within-segment positions."""
    n = len(exs)
    out = {k: np.zeros((n, ROW), np.asarray(exs[0][k]).dtype) for k in keys}
    for i, e in enumerate(exs):
        ln = len(e[keys[0]])
        for k in keys:
            out[k][i, :ln] = e[k]
    return out


def _sasrec(dropout=0.0):
    model = SASRec(num_items=30, max_seq_len=ROW, embed_dim=16, num_heads=2,
                   num_blocks=2, ffn_dim=32, dropout=dropout)
    params = model.init(jax.random.key(0), jnp.zeros((1, ROW), jnp.int32))["params"]
    return model, params


def _sasrec_data(seed=0):
    ds = SyntheticSeqDataset(num_items=30, num_users=24, max_seq_len=ROW, seed=seed)
    return ds.train_examples()


def test_sasrec_packed_loss_and_grads_match_padded():
    model, params = _sasrec()
    exs = _sasrec_data()
    packed, rep = pack_examples(exs, ROW)
    assert rep.n_rows < rep.padded_rows  # the pack actually packed
    padded = _padded_rows(exs)

    def loss_padded(p):
        _, loss = model.apply({"params": p}, jnp.asarray(padded["input_ids"]),
                              jnp.asarray(padded["targets"]))
        return loss

    def loss_packed(p):
        _, loss = model.apply(
            {"params": p}, jnp.asarray(packed["input_ids"]),
            jnp.asarray(packed["targets"]),
            segment_ids=jnp.asarray(packed["segment_ids"]),
            positions=jnp.asarray(packed["positions"]),
        )
        return loss

    lp, gp = jax.value_and_grad(loss_padded)(params)
    lq, gq = jax.value_and_grad(loss_packed)(params)
    assert float(lp) == pytest.approx(float(lq), abs=1e-5)
    # Grads through every layer (embeddings, attention, FFN, norms).
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4
        ),
        gp, gq,
    )


def test_sasrec_packed_per_example_losses_match():
    """Per-token CE summed per segment == the same example's padded row."""
    model, params = _sasrec()
    exs = _sasrec_data(seed=1)
    packed, rep = pack_examples(exs, ROW)
    padded = _padded_rows(exs)

    logits_pad, _ = model.apply({"params": params}, jnp.asarray(padded["input_ids"]))
    per_pad, _ = cross_entropy_with_ignore(
        logits_pad, jnp.asarray(padded["targets"]), ignore_index=0
    )
    per_pad = np.asarray(per_pad.sum(axis=1))

    logits_pk, _ = model.apply(
        {"params": params}, jnp.asarray(packed["input_ids"]),
        segment_ids=jnp.asarray(packed["segment_ids"]),
        positions=jnp.asarray(packed["positions"]),
    )
    per_pk, _ = cross_entropy_with_ignore(
        logits_pk, jnp.asarray(packed["targets"]), ignore_index=0
    )
    per_pk = np.asarray(per_pk)

    # Match segments back to examples via the packer's deterministic FFD
    # order (token content alone is not guaranteed unique).
    from genrec_tpu.data.batching import first_fit_decreasing

    bins = first_fit_decreasing([len(e["input_ids"]) for e in exs], ROW)
    for r, bin_idx in enumerate(bins):
        cursor = 0
        for idx in bin_idx:
            ln = len(exs[idx]["input_ids"])
            got = per_pk[r, cursor:cursor + ln].sum()
            assert got == pytest.approx(per_pad[idx], abs=1e-5)
            cursor += ln


def test_sasrec_segment_boundary_leak():
    """Perturbing segment 1's tokens must not change segment 2's
    per-token losses (attention leak check), and the packed forward must
    differ from a no-segment forward on the same rows (mask is real)."""
    model, params = _sasrec()
    rng = np.random.default_rng(0)
    a = rng.integers(1, 31, 6).astype(np.int32)
    b = rng.integers(1, 31, 7).astype(np.int32)
    a2 = rng.integers(1, 31, 6).astype(np.int32)  # replacement segment 1
    tg = rng.integers(1, 31, 13).astype(np.int32)

    def row(first):
        ids = np.zeros((1, ROW), np.int32)
        ids[0, :6] = first
        ids[0, 6:13] = b
        seg = np.zeros((1, ROW), np.int32)
        seg[0, :6] = 1
        seg[0, 6:13] = 2
        pos = np.zeros((1, ROW), np.int32)
        pos[0, :6] = np.arange(6)
        pos[0, 6:13] = np.arange(7)
        tgt = np.zeros((1, ROW), np.int32)
        tgt[0, :13] = tg
        return ids, seg, pos, tgt

    outs = []
    for first in (a, a2):
        ids, seg, pos, tgt = row(first)
        logits, _ = model.apply(
            {"params": params}, jnp.asarray(ids),
            segment_ids=jnp.asarray(seg), positions=jnp.asarray(pos),
        )
        per, _ = cross_entropy_with_ignore(logits, jnp.asarray(tgt), ignore_index=0)
        outs.append(np.asarray(per[0, 6:13]))
    np.testing.assert_array_equal(outs[0], outs[1])  # seg 2 is bit-stable

    # Sanity: without the segment mask the same perturbation DOES leak.
    ids, _, _, tgt = row(a)
    ids2, _, _, _ = row(a2)
    l1, _ = model.apply({"params": params}, jnp.asarray(ids))
    l2, _ = model.apply({"params": params}, jnp.asarray(ids2))
    assert np.abs(np.asarray(l1[0, 6:13]) - np.asarray(l2[0, 6:13])).max() > 1e-6


# --------------------------------------------------------------------- HSTU


def _hstu(use_pallas):
    # The Pallas variant runs the interpreter (slow): one block is enough
    # to pin "grads through at least one layer"; the XLA variant keeps two.
    model = HSTU(num_items=30, max_seq_len=ROW, embed_dim=16, num_heads=2,
                 num_blocks=1 if use_pallas else 2, dropout=0.0,
                 use_pallas=use_pallas)
    params = model.init(jax.random.key(0), jnp.zeros((1, ROW), jnp.int32),
                        jnp.zeros((1, ROW), jnp.int32))["params"]
    return model, params


@pytest.mark.parametrize("use_pallas", [False, True])
def test_hstu_packed_loss_and_grads_match_padded(use_pallas):
    model, params = _hstu(use_pallas)
    ds = SyntheticSeqDataset(num_items=30, num_users=20, max_seq_len=ROW, seed=2)
    exs = ds.train_examples(with_time=True)
    packed, rep = pack_examples(exs, ROW)
    assert rep.n_rows < rep.padded_rows
    padded = _padded_rows(exs, keys=("input_ids", "targets", "timestamps"))

    def loss_padded(p):
        _, loss = model.apply(
            {"params": p}, jnp.asarray(padded["input_ids"]),
            jnp.asarray(padded["timestamps"]), jnp.asarray(padded["targets"]),
        )
        return loss

    def loss_packed(p):
        _, loss = model.apply(
            {"params": p}, jnp.asarray(packed["input_ids"]),
            jnp.asarray(packed["timestamps"]), jnp.asarray(packed["targets"]),
            segment_ids=jnp.asarray(packed["segment_ids"]),
        )
        return loss

    lp, gp = jax.value_and_grad(loss_padded)(params)
    lq, gq = jax.value_and_grad(loss_packed)(params)
    assert float(lp) == pytest.approx(float(lq), abs=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, rtol=1e-4
        ),
        gp, gq,
    )


@pytest.mark.parametrize("use_pallas", [False, True])
def test_hstu_segment_boundary_leak(use_pallas):
    """Cross-segment attention AND temporal-bucket bridging: perturbing
    segment 1's tokens and timestamps must leave segment 2's logits
    bit-identical on both kernel paths."""
    model, params = _hstu(use_pallas)
    rng = np.random.default_rng(1)

    def row(first, t_first):
        ids = np.zeros((1, ROW), np.int32)
        ids[0, :5] = first
        ids[0, 5:12] = rng0_b
        seg = np.zeros((1, ROW), np.int32)
        seg[0, :5] = 1
        seg[0, 5:12] = 2
        ts = np.zeros((1, ROW), np.int64)
        ts[0, :5] = t_first
        ts[0, 5:12] = tb
        return ids, seg, ts

    rng0_b = rng.integers(1, 31, 7).astype(np.int32)
    tb = np.cumsum(rng.integers(3600, 2e5, 7)) + 1_600_000_000
    a = rng.integers(1, 31, 5).astype(np.int32)
    ta = np.cumsum(rng.integers(3600, 2e5, 5)) + 1_500_000_000
    a2 = rng.integers(1, 31, 5).astype(np.int32)
    ta2 = np.cumsum(rng.integers(3600, 2e5, 5)) + 1_000_000  # very different

    outs = []
    for first, tf in ((a, ta), (a2, ta2)):
        ids, seg, ts = row(first, tf)
        logits, _ = model.apply(
            {"params": params}, jnp.asarray(ids), jnp.asarray(ts),
            segment_ids=jnp.asarray(seg),
        )
        outs.append(np.asarray(logits[0, 5:12]))
    np.testing.assert_array_equal(outs[0], outs[1])


# -------------------------------------------------------------------- TIGER


@functools.lru_cache(maxsize=None)
def _tiger_small(n_layers=2):
    """The tiny TIGER every test below shares (n_layers counts encoder +
    decoder layers); built once per depth."""
    from genrec_tpu.models.tiger import Tiger

    model = Tiger(embedding_dim=16, attn_dim=32, dropout=0.0, num_heads=4,
                  n_layers=n_layers, num_item_embeddings=16,
                  num_user_embeddings=100, sem_id_dim=3)
    D = 3
    params = model.init(
        jax.random.key(0), jnp.zeros((1,), jnp.int32),
        jnp.zeros((1, 18), jnp.int32), jnp.zeros((1, 18), jnp.int32),
        jnp.zeros((1, D), jnp.int32), jnp.zeros((1, D), jnp.int32),
        jnp.ones((1, 18), jnp.int32),
    )["params"]
    return model, params


def test_tiger_packed_loss_and_grads_match_unpacked():
    """forward_packed == the unpacked encoder-decoder on the same example
    set: batch loss and grads through the full model (encoder rel-bias
    from within-segment positions, per-segment cross-attention)."""
    from genrec_tpu.data.tiger_seq import synthetic_tiger_data
    from genrec_tpu.models.tiger import Tiger

    data = synthetic_tiger_data(num_items=40, codebook_size=16, sem_id_dim=3,
                                max_items=6, seed=0, num_users=16)
    exs = data.train_examples()
    L = 1 + 6 * 3
    packed, rep = pack_examples(exs, L, segment_keys=("target_ids",))
    assert rep.n_rows < rep.padded_rows
    arrays = data.train_arrays()

    model, params = _tiger_small()
    D = 3

    B = arrays["user_ids"].shape[0]
    tt = jnp.broadcast_to(jnp.arange(D), (B, D))

    def loss_unpacked(p):
        out = model.apply(
            {"params": p}, jnp.asarray(arrays["user_ids"]),
            jnp.asarray(arrays["item_input_ids"]),
            jnp.asarray(arrays["token_type_ids"]),
            jnp.asarray(arrays["target_ids"]), tt,
            jnp.asarray(arrays["seq_mask"]),
        )
        return out.loss

    def loss_packed(p):
        out = model.apply(
            {"params": p}, jnp.asarray(packed["item_input_ids"]),
            jnp.asarray(packed["token_type_ids"]),
            jnp.asarray(packed["user_token_ids"]),
            jnp.asarray(packed["user_mask"]),
            jnp.asarray(packed["segment_ids"]), jnp.asarray(packed["positions"]),
            jnp.asarray(packed["target_ids"]), jnp.asarray(packed["segment_valid"]),
            method=Tiger.forward_packed,
        )
        return out.loss

    lp, gp = jax.value_and_grad(loss_unpacked)(params)
    lq, gq = jax.value_and_grad(loss_packed)(params)
    assert float(lp) == pytest.approx(float(lq), abs=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-4
        ),
        gp, gq,
    )


def test_tiger_packed_per_example_losses_match_unpacked():
    from genrec_tpu.data.batching import first_fit_decreasing
    from genrec_tpu.data.tiger_seq import synthetic_tiger_data
    from genrec_tpu.models.tiger import Tiger

    data = synthetic_tiger_data(num_items=40, codebook_size=16, sem_id_dim=3,
                                max_items=6, seed=1, num_users=12)
    exs = data.train_examples()
    L = 1 + 6 * 3
    packed, rep = pack_examples(exs, L, segment_keys=("target_ids",))
    arrays = data.train_arrays()

    model, params = _tiger_small()
    D = 3

    # Unpacked per-example token-sum CE.
    B = arrays["user_ids"].shape[0]
    tt = jnp.broadcast_to(jnp.arange(D), (B, D))
    out = model.apply(
        {"params": params}, jnp.asarray(arrays["user_ids"]),
        jnp.asarray(arrays["item_input_ids"]), jnp.asarray(arrays["token_type_ids"]),
        jnp.asarray(arrays["target_ids"]), tt, jnp.asarray(arrays["seq_mask"]),
    )
    from genrec_tpu.ops.losses import cross_entropy_with_ignore

    tv = np.asarray(tt) * 16 + arrays["target_ids"]
    per_tok, _ = cross_entropy_with_ignore(
        out.logits[:, :-1, :], jnp.asarray(tv), ignore_index=-1
    )
    per_unpacked = np.asarray(per_tok.sum(axis=1))

    pk = model.apply(
        {"params": params}, jnp.asarray(packed["item_input_ids"]),
        jnp.asarray(packed["token_type_ids"]), jnp.asarray(packed["user_token_ids"]),
        jnp.asarray(packed["user_mask"]), jnp.asarray(packed["segment_ids"]),
        jnp.asarray(packed["positions"]), jnp.asarray(packed["target_ids"]),
        jnp.asarray(packed["segment_valid"]), method=Tiger.forward_packed,
    )
    per_packed = np.asarray(pk.per_example_loss)

    bins = first_fit_decreasing(
        [len(e["item_input_ids"]) for e in exs], L
    )
    for r, bin_idx in enumerate(bins):
        for s, idx in enumerate(bin_idx):
            assert per_packed[r, s] == pytest.approx(per_unpacked[idx], abs=1e-5)


def test_tiger_packed_accum_weighting_invariant_to_row_order():
    """Under gradient accumulation, packed microbatches carry VARYING
    example counts; the trainer rescales each microbatch loss by
    actual/expected count so every example weighs the same in the averaged
    gradient — the resulting update must not depend on which microbatch a
    row landed in."""
    import optax

    from genrec_tpu.core.harness import make_train_step
    from genrec_tpu.core.state import TrainState
    from genrec_tpu.data.tiger_seq import synthetic_tiger_data
    from genrec_tpu.models.tiger import Tiger

    data = synthetic_tiger_data(num_items=40, codebook_size=16, sem_id_dim=3,
                                max_items=6, seed=3, num_users=10)
    exs = data.train_examples()
    L = 1 + 6 * 3
    packed, rep = pack_examples(exs, L, segment_keys=("target_ids",))
    R = rep.n_rows - (rep.n_rows % 2)  # even row count for accum=2

    model, params = _tiger_small()
    opt = optax.sgd(0.1)
    expected_per_micro = (R // 2) * rep.n_examples / rep.n_rows

    def loss_fn(p, b, key):
        out = model.apply(
            {"params": p}, b["item_input_ids"], b["token_type_ids"],
            b["user_token_ids"], b["user_mask"], b["segment_ids"],
            b["positions"], b["target_ids"], b["segment_valid"],
            method=Tiger.forward_packed,
        )
        count = jnp.sum(b["segment_valid"]).astype(jnp.float32)
        return out.loss * count / expected_per_micro, {}

    step = jax.jit(make_train_step(loss_fn, opt, accum_steps=2, clip_norm=None))

    def run(order):
        batch = {k: jnp.asarray(np.asarray(v)[order]) for k, v in packed.items()}
        state = TrainState.create(params, opt, jax.random.key(1))
        state, _ = step(state, batch)
        return state.params

    # FFD order packs dense rows first: reversing it changes which
    # microbatch each row (and its example count) lands in.
    p_fwd = run(np.arange(R))
    p_rev = run(np.arange(R)[::-1])
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5
        ),
        p_fwd, p_rev,
    )


def test_tiger_encoder_segment_boundary_leak():
    """A second segment in the packed row must not change the first
    segment's per-example loss (encoder attention + cross-attention are
    both segment-restricted)."""
    from genrec_tpu.data.tiger_seq import synthetic_tiger_data
    from genrec_tpu.models.tiger import Tiger

    data = synthetic_tiger_data(num_items=40, codebook_size=16, sem_id_dim=3,
                                max_items=6, seed=2, num_users=12)
    exs = data.train_examples()
    # e1 (length 7) packs first; the two length-4 neighbors must carry
    # target tuples distinct from e1's so its segment is identifiable.
    e1 = next(e for e in exs if len(e["item_input_ids"]) == 7)
    others = [
        e for e in exs
        if len(e["item_input_ids"]) == 4
        and not np.array_equal(e["target_ids"], e1["target_ids"])
    ]
    e2, e3 = others[0], others[1]
    L = 1 + 6 * 3

    model, params = _tiger_small()

    def packed_loss_of_first(neighbor):
        packed, _ = pack_examples([e1, neighbor], L, segment_keys=("target_ids",))
        # Both must share one row for the check to bite.
        assert packed["segment_ids"].shape[0] == 1
        assert packed["segment_ids"].max() == 2
        pk = model.apply(
            {"params": params}, jnp.asarray(packed["item_input_ids"]),
            jnp.asarray(packed["token_type_ids"]),
            jnp.asarray(packed["user_token_ids"]), jnp.asarray(packed["user_mask"]),
            jnp.asarray(packed["segment_ids"]), jnp.asarray(packed["positions"]),
            jnp.asarray(packed["target_ids"]), jnp.asarray(packed["segment_valid"]),
            method=Tiger.forward_packed,
        )
        # e1 is the LONGER-or-equal example; find its segment by matching
        # target tuples (unique per example here).
        tgts = np.asarray(packed["target_ids"][0])
        s1 = next(
            s for s in range(tgts.shape[0])
            if np.array_equal(tgts[s], e1["target_ids"])
        )
        return float(pk.per_example_loss[0, s1])

    assert packed_loss_of_first(e2) == packed_loss_of_first(e3)


# ------------------------------------- TIGER: the shared relative-bias grid


def _tiger_packed_loss(model, packed):
    from genrec_tpu.models.tiger import Tiger

    b = {k: jnp.asarray(v) for k, v in packed.items()}

    def loss(p):
        return model.apply(
            {"params": p}, b["item_input_ids"], b["token_type_ids"],
            b["user_token_ids"], b["user_mask"], b["segment_ids"],
            b["positions"], b["target_ids"], b["segment_valid"],
            method=Tiger.forward_packed,
        ).loss

    return loss


def _lookup_sizes(jaxpr):
    """Element counts of every gather RESULT and every scatter UPDATE in a
    jaxpr, sub-jaxprs (pjit, custom_vjp, remat, scan…) included."""
    sizes = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "gather":
            sizes.append(("gather", int(np.prod(eqn.outvars[0].aval.shape))))
        elif name.startswith("scatter"):
            # operands: (operand, indices, updates)
            sizes.append((name, int(np.prod(eqn.invars[2].aval.shape))))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            sizes.extend(_lookup_sizes(sub))
    return sizes


def test_tiger_packed_grad_has_no_per_row_bias_lookup():
    """The packed encoder's relative bias is ONE (1, H, L, L) grid a layer,
    broadcast over rows. A per-row grid (rows x H x L x L indices into the
    192-entry table) was 89% of the v5e step until PR 27: no gather result
    and no scatter update of the lowered gradient may be that large, so
    the lookup cannot come back unnoticed."""
    R, H, L, S = 8, 4, 19, 4
    model, params = _tiger_small(n_layers=4)  # 2 encoder + 2 decoder layers
    z = lambda *shape: np.zeros(shape, np.int32)
    packed = {
        "item_input_ids": z(R, L), "token_type_ids": z(R, L),
        "user_token_ids": z(R, L), "user_mask": z(R, L),
        "segment_ids": z(R, L), "positions": z(R, L),
        "target_ids": z(R, S, 3), "segment_valid": z(R, S),
    }
    jaxpr = jax.make_jaxpr(jax.grad(_tiger_packed_loss(model, packed)))(params)
    sizes = _lookup_sizes(jaxpr.jaxpr)
    per_row = R * H * L * L
    too_big = [(n, s) for n, s in sizes if s >= per_row]
    assert not too_big, f"per-row-sized lookups {too_big} (R*H*L*L = {per_row})"
    # The shared grid IS there, forward and backward, once per encoder
    # layer: the walk sees lookups, sub-jaxprs included.
    assert sum(1 for n, s in sizes if n == "gather" and s == H * L * L) == 2
    assert sum(1 for n, s in sizes if n != "gather" and s == H * L * L) == 2

    # The detector bites on what it exists to catch: the per-row lookup
    # the model used to make, written out here.
    def per_row_bias_loss(rel_bias, positions):
        from genrec_tpu.ops.buckets import t5_relative_position_bucket

        rel = positions[:, None, :] - positions[:, :, None]
        idx = (t5_relative_position_bucket(rel, 32, 128)[:, None]
               + (jnp.arange(H) * 32)[None, :, None, None])
        return rel_bias[idx, 0].sum()

    old = jax.make_jaxpr(jax.grad(per_row_bias_loss))(
        jnp.zeros((H * 32, 1)), jnp.zeros((R, L), jnp.int32))
    old_sizes = _lookup_sizes(old.jaxpr)
    assert ("gather", per_row) in old_sizes
    assert any(n != "gather" and s == per_row for n, s in old_sizes)


def test_tiger_packed_rel_bias_grads_match_at_the_segment_cap():
    """Rows that hold the cap of FOUR segments, at offsets 0, 7, 11, 15 in
    some rows and 0, 4, 8, 12 in others: the shared slot-distance grid
    must give every encoder layer's `rel_bias` the unpacked gradient,
    leaf by leaf, since a segment's offset in its row cancels out of
    k - q."""
    from genrec_tpu.data.tiger_seq import synthetic_tiger_data

    data = synthetic_tiger_data(num_items=40, codebook_size=16, sem_id_dim=3,
                                max_items=6, seed=4, num_users=24)
    exs = data.train_examples()
    arrays = data.train_arrays()
    L = 1 + 6 * 3
    long, short = ([i for i, e in enumerate(exs) if len(e["item_input_ids"]) == n]
                   for n in (7, 4))
    assert len(long) >= 2 and len(short) >= 14
    # 7 + 4 + 4 + 4 fills a row; 4 + 4 + 4 + 4 leaves three pad slots.
    groups = [[long[0]] + short[0:3], [long[1]] + short[3:6],
              short[6:10], short[10:14]]
    pick = [i for g in groups for i in g]
    # One call a row: FFD over the whole set would pair the long examples.
    rows = [pack_examples([exs[i] for i in g], L, segment_keys=("target_ids",),
                          max_segments=4)[0] for g in groups]
    packed = {k: np.concatenate([row[k] for row in rows]) for k in rows[0]}
    assert packed["segment_ids"].shape == (len(groups), L)
    assert (packed["segment_valid"] == 1).all()
    starts = [[int(np.flatnonzero(row == s)[0]) for s in (1, 2, 3, 4)]
              for row in packed["segment_ids"]]
    assert starts == [[0, 7, 11, 15]] * 2 + [[0, 4, 8, 12]] * 2

    model, params = _tiger_small(n_layers=4)  # 2 encoder layers
    sub = {k: jnp.asarray(np.asarray(v)[pick]) for k, v in arrays.items()}
    D = 3
    tt = jnp.broadcast_to(jnp.arange(D), (len(pick), D))

    def loss_unpacked(p):
        return model.apply(
            {"params": p}, sub["user_ids"], sub["item_input_ids"],
            sub["token_type_ids"], sub["target_ids"], tt, sub["seq_mask"],
        ).loss

    lp, gp = jax.value_and_grad(loss_unpacked)(params)
    lq, gq = jax.value_and_grad(_tiger_packed_loss(model, packed))(params)
    assert float(lp) == pytest.approx(float(lq), abs=1e-5)
    enc_p, enc_q = gp["transformer"]["encoder"], gq["transformer"]["encoder"]
    assert sorted(enc_p) == ["layer_0", "layer_1"]
    for layer in enc_p:
        a = np.asarray(enc_p[layer]["self_attn"]["rel_bias"])
        b = np.asarray(enc_q[layer]["self_attn"]["rel_bias"])
        assert a.shape == (4 * 32, 1)
        assert np.abs(a).max() > 1e-4, layer  # the leaf is trained at all
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-4, err_msg=layer)
