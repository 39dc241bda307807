"""Keye-VL-2.0's language model in the Qwen backbone against its plain
reference (benchmark/configs/keye_vl2_30b_a3b/reference.py), at a small size
on the CPU in float32: hidden 64, 4 x 16 heads over 2 KV heads, indexer 2 x 8,
top-k 16 at L = 64 (the selection bites on three quarters of the positions),
16 experts top 4. Limits are float32 round-off of sums a few hundred long
(1e-5 of the values' scale), except where a reason is given."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "benchmark"))
import keye_tiny as kt  # noqa: E402

from genrec_tpu.models.backbones import qwen  # noqa: E402
from genrec_tpu.models.backbones.qwen import QwenLM, QwenMoEMLP  # noqa: E402

L = 64


@pytest.fixture(scope="module")
def tiny():
    ad, ref = kt.module("adapter"), kt.module("reference")
    cfg = kt.tiny_config()
    return ad, ref, cfg, ad.make_params(cfg, 3)


def _rows(seed=0):
    """Three rows: full, left-padded, and shorter than top-k (9 real)."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 80, (3, L)).astype(np.int32)
    mask = np.ones((3, L), np.int32)
    mask[1, :20] = 0
    mask[2, :55] = 0
    labels = np.where(mask == 1, ids, -100).astype(np.int32)
    labels[:, :30] = -100
    labels[2, :58] = -100  # a padding position predicts nothing
    return ids, mask, labels


@pytest.mark.parametrize("key_chunk", [4096, 24])
def test_sparse_attention_selects_and_attends_as_the_reference(tiny, monkeypatch,
                                                               key_chunk):
    """``key_chunk`` 24: the softmax merged over three chunks of keys, the
    first of them without a selected key in the left-padded rows."""
    ad, ref, cfg, params = tiny
    monkeypatch.setattr(qwen, "_KEY_CHUNK", key_chunk)
    ids, mask, _ = _rows()
    x = params["embed_tokens"][ids]
    lp = params["layer_0"]
    h = ref.rms(x, lp["input_layernorm"]["weight"], cfg["rms_norm_eps"])
    pos = jnp.broadcast_to(jnp.arange(L), (3, L))
    want = [ref.attention_row(lp["self_attn"], cfg, h[b], pos[b],
                              jnp.asarray(mask[b], bool), "f32", 16)
            for b in range(3)]

    picked = []
    real_select = qwen.select_topk

    def spy(scores, allowed, k):
        sel = real_select(scores, allowed, k)
        picked.append(np.asarray(sel))
        return sel

    monkeypatch.setattr(qwen, "select_topk", spy)
    attn = qwen.QwenAttention(ad.model_config(cfg), jnp.float32)
    got, _ = attn.apply({"params": lp["self_attn"]}, h, pos, None,
                        key_valid=jnp.asarray(mask))
    # the program's tiles, laid out as (row, query, key) over all 64 keys
    sel = np.zeros((3, L, L), bool)
    for i, tile in enumerate(picked):
        sel[:, 16 * i:16 * (i + 1), :tile.shape[-1]] = tile
    for b in range(3):
        real = mask[b] == 1
        o_ref, sel_ref = want[b]
        np.testing.assert_array_equal(sel[b][real], np.asarray(sel_ref)[real])
        kept = sel[b][real].sum(-1)
        avail = np.minimum(np.arange(L)[real] - np.argmax(real) + 1, 10**9)
        np.testing.assert_array_equal(kept, np.minimum(avail, 16))
        np.testing.assert_allclose(np.asarray(got[b])[real],
                                   np.asarray(o_ref)[real], atol=1e-5)
    assert (sel[0].sum(-1) == 16).sum() == L - 15  # it bites from t = 15 on


def test_select_topk_breaks_ties_to_the_lowest_index():
    scores = jnp.asarray([[1.0, 3.0, 3.0, -0.0, 0.0, 3.0, 2.0, 0.0]])
    allowed = jnp.asarray([[True] * 7 + [False]])
    got = qwen.select_topk(scores, allowed, 2)
    np.testing.assert_array_equal(np.asarray(got)[0],
                                  [0, 1, 1, 0, 0, 0, 0, 0])
    got = qwen.select_topk(scores, allowed, 6)  # 3,3,3,2,1 then the first zero
    np.testing.assert_array_equal(np.asarray(got)[0],
                                  [1, 1, 1, 1, 0, 1, 1, 0])
    few = jnp.asarray([[True, False, True, False, False, False, False, False]])
    np.testing.assert_array_equal(np.asarray(qwen.select_topk(scores, few, 3)),
                                  np.asarray(few))


def _moe_io(tiny, router_push=0.0):
    ad, ref, cfg, params = tiny
    rng = np.random.default_rng(1)
    u = rng.normal(size=(2, 32, cfg["hidden_size"])).astype(np.float32)
    valid = np.ones((2, 32), np.int32)
    valid[1, :5] = 0
    p = jax.tree_util.tree_map(lambda a: a, params["layer_0"]["moe"])
    if router_push:
        # every token has a component along `ones`; expert 3's router column
        # reads it: nearly every token routes to expert 3
        u = u + 1.5
        k = np.asarray(p["router"]["kernel"]).copy()
        k[:, 3] += router_push
        p = dict(p, router={"kernel": jnp.asarray(k)})
    return ad, ref, cfg, p, jnp.asarray(u), valid


def _moe_reference(ref, cfg, p, u, valid, share=None):
    flat = u.reshape(-1, u.shape[-1])
    probs, eidx, gates = ref.route(p, cfg, flat, "f32")
    y = ref.experts(p, cfg, flat, eidx, gates, jnp.asarray(valid.reshape(-1), bool),
                    "f32", share)
    return np.asarray(y).reshape(u.shape), np.asarray(eidx)


def test_dropless_experts_under_forced_imbalance(tiny):
    ad, ref, cfg, p, u, valid = _moe_io(tiny, router_push=2.0)
    want, eidx = _moe_reference(ref, cfg, p, u, valid)
    load = np.bincount(eidx[valid.reshape(-1) == 1].reshape(-1), minlength=16)
    assert load[3] >= 0.95 * valid.sum()  # the capacity path would drop most
    moe = QwenMoEMLP(ad.model_config(cfg), jnp.float32)
    got, mut = moe.apply({"params": p}, u, jnp.asarray(valid), mutable=["counters"])
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    assert np.abs(want[valid == 1]).min(axis=-1).max() > 0  # every real token served
    np.testing.assert_array_equal(np.asarray(got)[valid == 0], 0.0)
    c = mut["counters"]
    assert float(c["expert_picks_here_share"][0]) == pytest.approx(100.0)
    assert float(c["expert_load_max_over_mean"][0]) == pytest.approx(
        load.max() * 16 / load.sum(), rel=1e-6)


def test_the_eight_shares_add_up_to_the_uncut_layer(tiny):
    """The share ties to the model: each of eight chips holds 2 of the 16
    experts, routes over all 16, and computes its own experts' part; the
    parts add up to the uncut reference layer."""
    ad, ref, cfg, p, u, valid = _moe_io(tiny)
    want, _ = _moe_reference(ref, cfg, p, u, valid, share=(0, 16))
    total = np.zeros_like(want)
    picks = 0.0
    for s in range(8):
        lo = 2 * s
        part = dict(p, **{k: p[k][lo:lo + 2] for k in ("gate_proj", "up_proj", "down_proj")})
        moe = QwenMoEMLP(ad.model_config(cfg, share=(lo, 2)), jnp.float32)
        got, mut = moe.apply({"params": part}, u, jnp.asarray(valid),
                             mutable=["counters"])
        ref_part, _ = _moe_reference(ref, cfg, part, u, valid, share=(lo, 2))
        np.testing.assert_allclose(np.asarray(got), ref_part, atol=2e-5)
        total += np.asarray(got)
        picks += float(mut["counters"]["expert_picks_here_share"][0])
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert picks == pytest.approx(100.0, rel=1e-5)


def test_loss_and_every_gradient_leaf_match_the_reference(tiny):
    from genrec_tpu.models.lcrec import sft_loss

    ad, ref, cfg, params = tiny
    ids, mask, labels = _rows()
    model = ad._model(cfg)  # rematerialised blocks, as the cell runs them
    loss, grads = jax.value_and_grad(
        lambda p: sft_loss(model, p, ids, mask, labels))(params)
    want_loss, want = ref.loss_and_grads(
        params, cfg, {"input_ids": ids, "attention_mask": mask, "labels": labels})
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    got = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, g), w in zip(got, jax.tree_util.tree_leaves(want)):
        name = "/".join(str(k.key) for k in path)
        if "idx_" in name:  # the selection is discrete: exactly zero, both sides
            assert not np.asarray(g).any() and not np.asarray(w).any(), name
        else:
            assert float(jnp.linalg.norm(w)) > 0, name
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-6,
                                       err_msg=name)


def test_prefill_then_decode_matches_the_full_forward(tiny):
    """Through the cache (K, V and the indexer's keys; selection over the
    cache's slots) against the reference's full forward: logits compared."""
    ad, ref, cfg, params = tiny
    ids, mask, _ = _rows(seed=5)
    mask[2, 30:] = 1  # ten real prompt tokens: fewer than top-k
    model = QwenLM(ad.model_config(cfg), dtype=jnp.float32)
    P, T = 40, 6  # prompt slots, then six tokens fed one at a time
    pos = jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0)
    want = np.asarray(ref.forward(params, cfg, ids[:, :P + T], mask[:, :P + T],
                                  positions=pos[:, :P + T]))
    S = P + T
    caches = model.apply({"params": params}, 3, S, method=QwenLM.init_cache)
    assert caches[0]["ki"].shape == (3, S, cfg["sa_config"]["indexer_head_dim"])
    pad = np.concatenate([mask[:, :P], np.zeros((3, T), np.int32)], axis=1)
    logits, caches = model.apply(
        {"params": params}, ids[:, :P], pos[:, :P], caches, jnp.asarray(pad),
        method=QwenLM.decode_step)
    real = mask[:, P - 1] == 1
    np.testing.assert_allclose(np.asarray(logits)[real], want[real, P - 1], atol=2e-5)
    for t in range(T):
        pad[:, P + t] = 1
        logits, caches = model.apply(
            {"params": params}, ids[:, P + t:P + t + 1], pos[:, P + t:P + t + 1],
            caches, jnp.asarray(pad), method=QwenLM.decode_step)
        np.testing.assert_allclose(np.asarray(logits), want[:, P + t], atol=2e-5)


def test_a_share_of_the_experts_needs_the_dropless_path():
    with pytest.raises(ValueError, match="dropless"):
        qwen.QwenConfig(num_experts=8, moe_experts_held=2)
    with pytest.raises(ValueError, match="indexer"):
        qwen.QwenConfig(sparse_topk=4)


def test_flops_count_selected_keys_and_held_experts_only():
    flops = kt.module("flops")
    cfg = kt.tiny_config()
    assert flops.selected_keys(cfg, 10) == 55
    assert flops.selected_keys(cfg, 64) == 16 * 17 / 2 + 48 * 16
    one = flops.train_example(cfg, 59)  # a full row of 64
    half = flops.train_example(dict(cfg, num_experts=8), 59)
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    # 4 picks a token, half of them on the 8 held: 2 picks fewer a token
    assert one - half == pytest.approx(3 * 2 * 64 * 2 * 2 * 3 * d * f)
