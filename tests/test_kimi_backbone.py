"""Kimi-Linear's language model in the Qwen backbone against its plain
reference (benchmark/configs/kimi_linear_48b_a3b/reference.py), at a small
size on the CPU in float32: hidden 64; KDA 4 heads x 16, kernel 4; latent
attention 4 heads, latent 32, nope 16, rope-part 8, v 16; 16 experts top 4 of
width 32 and 1 shared; dense width 128; layers KDA + dense, KDA, KDA, MLA,
KDA; L = 96, so a row spans two of the scan's chunks of 64. Limits are float32
round-off of sums a few hundred long (1e-5 of the values' scale), except
where a reason is given."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "benchmark"))
import kimi_tiny as kt  # noqa: E402

from genrec_tpu.models.backbones import kda, qwen  # noqa: E402
from genrec_tpu.models.backbones.kda import KimiDeltaAttention  # noqa: E402
from genrec_tpu.models.backbones.mla import LatentAttention  # noqa: E402
from genrec_tpu.models.backbones.qwen import QwenLM, QwenMoEMLP  # noqa: E402

L = 96


@pytest.fixture(scope="module")
def tiny():
    ad, ref = kt.module("adapter"), kt.module("reference")
    cfg = kt.tiny_config()
    return ad, ref, cfg, ad.make_params(cfg, 3)


def _rows(seed=0):
    """Three rows: full, left-padded, and shorter than one chunk (9 real)."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 80, (3, L)).astype(np.int32)
    mask = np.ones((3, L), np.int32)
    mask[1, :20] = 0
    mask[2, :87] = 0
    labels = np.where(mask == 1, ids, -100).astype(np.int32)
    labels[:, :30] = -100
    labels[2, :90] = -100  # a padding position predicts nothing
    return ids, mask, labels


def _normed(tiny, layer, seed=0):
    ad, ref, cfg, params = tiny
    ids, mask, _ = _rows(seed)
    lp = params[f"layer_{layer}"]
    x = params["embed_tokens"][ids]
    return lp, ref.rms(x, lp["input_layernorm"]["weight"], cfg["rms_norm_eps"]), mask


# -- (1) KDA: the chunked form against the token recurrence -------------------


def _scan_inputs(fast_decay: bool):
    """q, k unit vectors, v, a gate and a write strength for three rows of
    4 heads x 16; ``fast_decay`` draws g so that -G passes 100 inside a
    chunk of 32 (exp(-G) alone would overflow float32)."""
    rng = np.random.default_rng(4)
    shape = (3, L, 4, 16)
    q = kda.unit_vector(jnp.asarray(rng.normal(size=shape), jnp.float32)) * 0.25
    k = kda.unit_vector(jnp.asarray(rng.normal(size=shape), jnp.float32))
    v = jnp.asarray(rng.normal(size=shape), jnp.float32)
    hi = 8.0 if fast_decay else 0.5
    g = -jnp.asarray(rng.uniform(1e-3, hi, size=shape), jnp.float32)
    b = jnp.asarray(rng.uniform(0, 1, size=shape[:3]), jnp.float32)
    m = jnp.asarray(_rows()[1], jnp.float32)
    return q, k, v, g * m[..., None, None], b * m[..., None]


def _start_state():
    return jnp.asarray(np.random.default_rng(5).normal(size=(3, 4, 16, 16)) * 0.5,
                       jnp.float32)


def _eqns(jaxpr):
    """Every equation of a jaxpr, outermost first, through whatever holds a
    jaxpr of its own (a loop's body, a rematerialised function)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _scans(jaxpr):
    return [e for e in _eqns(jaxpr) if e.primitive.name == "scan"]


# (chunk, a start state, _GROUP_BYTES): the budget of the last two holds two
# chunks of these shapes, so 96 tokens are 3 groups of 2 chunks of 16, and 2
# groups of 2 chunks of 32 with a chunk of padding after the row
_CASES = [(32, False, None), (kda._CHUNK, False, None), (128, False, None),
          (kda._CHUNK, True, None), (16, True, 400_000), (32, True, 1_600_000)]


@pytest.mark.parametrize("fast_decay", [False, True])
@pytest.mark.parametrize("chunk, start, group_bytes", _CASES)
def test_kda_chunked_form_is_the_recurrence(tiny, monkeypatch, chunk, start,
                                            group_bytes, fast_decay):
    """Outputs and final state, with left padding, at a row shorter than one
    chunk (and a chunk longer than the row), with no clamp where the running
    decay leaves float32's range, from a start state that is not zero, and
    over several groups of chunks."""
    ref = tiny[1]
    q, k, v, g, b = _scan_inputs(fast_decay)
    s0 = _start_state() if start else None
    if fast_decay:
        assert float(jnp.cumsum(g[0], axis=0)[31].min()) < -100.0
    if group_bytes is not None:
        monkeypatch.setattr(kda, "_GROUP_BYTES", group_bytes)
        groups = _scans(jax.make_jaxpr(
            lambda: kda.kda_chunked(q, k, v, g, b, chunk, s0))().jaxpr)[0]
        assert groups.params["length"] == {16: 3, 32: 2}[chunk]
    o, s = kda.kda_chunked(q, k, v, g, b, chunk, s0)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s).all())
    for r in range(3):
        o_ref, s_ref = ref.delta_rule(q[r], k[r], v[r], g[r], b[r],
                                      None if s0 is None else s0[r])
        np.testing.assert_allclose(np.asarray(o[r]), np.asarray(o_ref), atol=2e-6)
        np.testing.assert_allclose(np.asarray(s[r]), np.asarray(s_ref), atol=2e-5)
    if s0 is None:
        np.testing.assert_array_equal(np.asarray(o[2, :87]), 0.0)  # nothing written yet


@pytest.mark.parametrize("group_bytes", [None, 400_000])
def test_kda_chunked_gradient_is_the_recurrences(tiny, monkeypatch, group_bytes):
    """Every input's gradient, the start state's among them, through the
    outputs and the final state; one group, and 3 groups of 2 chunks of 16."""
    ref = tiny[1]
    args = _scan_inputs(False) + (_start_state(),)
    rng = np.random.default_rng(6)
    wo = jnp.asarray(rng.normal(size=args[2].shape), jnp.float32)
    ws = jnp.asarray(rng.normal(size=args[5].shape), jnp.float32)
    if group_bytes is not None:
        monkeypatch.setattr(kda, "_GROUP_BYTES", group_bytes)

    def loss(scan):
        def f(*a):
            o, s = scan(*a)
            return jnp.sum(wo * o) + jnp.sum(ws * s)
        return f

    got = jax.grad(loss(lambda q, k, v, g, b, s0: kda.kda_chunked(
        q, k, v, g, b, 16, s0)), argnums=tuple(range(6)))(*args)
    want = jax.grad(loss(jax.vmap(ref.delta_rule)), argnums=tuple(range(6)))(*args)
    for name, a, t in zip("q k v g b s0".split(), got, want):
        assert float(jnp.linalg.norm(t)) > 0, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(t), err_msg=name,
                                   atol=2e-5 * max(1.0, float(jnp.abs(t).max())))


@pytest.mark.parametrize("group_bytes", [kda._GROUP_BYTES, 400_000])
def test_kda_state_scan_holds_no_state_free_work(monkeypatch, group_bytes):
    """The one loop that runs a chunk at a time WITH the state carries no
    ``exp`` and no triangular solve: the pairwise decay, the solve and the
    decayed keys and queries are made before it, over a whole group."""
    monkeypatch.setattr(kda, "_GROUP_BYTES", group_bytes)
    q, k, v, g, b = _scan_inputs(False)
    jaxpr = jax.make_jaxpr(lambda *a: kda.kda_chunked(*a, 16, _start_state()))(
        q, k, v, g, b).jaxpr
    assert {"exp", "triangular_solve"} <= {e.primitive.name for e in _eqns(jaxpr)}
    carrying = [e for e in _scans(jaxpr) if e.params["num_carry"] > 0]
    groups, state = carrying  # the groups' loop, and the chunks' inside it
    assert state in _scans(groups.params["jaxpr"].jaxpr)
    assert groups.params["length"] * state.params["length"] == 6  # 96 tokens
    body = {e.primitive.name for e in _eqns(state.params["jaxpr"].jaxpr)}
    assert "dot_general" in body
    assert not body & {"exp", "exp2", "triangular_solve", "scan", "while",
                       "cumsum", "custom_linear_solve"}, body


def test_kda_mixer_matches_the_reference_and_counts_what_it_keeps(tiny):
    ad, ref, cfg, _ = tiny
    lp, h, mask = _normed(tiny, 1)
    mixer = KimiDeltaAttention(ad.model_config(cfg), jnp.float32)
    (got, _), mut = mixer.apply({"params": lp["kda"]}, h, jnp.asarray(mask),
                                mutable=["counters"])
    keep = []
    for r in range(3):
        valid = jnp.asarray(mask[r], bool)
        want, _ = ref.kda_row(lp["kda"], cfg, h[r], valid, "f32")
        real = mask[r] == 1
        np.testing.assert_allclose(np.asarray(got[r])[real], np.asarray(want)[real],
                                   atol=1e-5)
        g, _ = ref.kda_gates(lp["kda"], cfg, h[r] * mask[r][:, None],
                             jnp.asarray(mask[r], jnp.float32), "f32")
        keep.append(np.exp(np.asarray(g))[real].reshape(-1))
    share = float(mut["counters"]["kda_state_keep_share"][0])
    assert share == pytest.approx(100.0 * np.concatenate(keep).mean(), rel=1e-5)
    assert 50.0 < share < 99.9  # the seed's gates neither shut nor saturated


# -- (2) KDA: every leaf's gradient -------------------------------------------


def test_kda_gradient_of_every_leaf_matches_the_reference(tiny):
    ad, ref, cfg, _ = tiny
    lp, h, mask = _normed(tiny, 2, seed=1)
    w = jnp.asarray(np.random.default_rng(2).normal(size=h.shape), jnp.float32)
    w = w * mask[..., None]
    mixer = KimiDeltaAttention(ad.model_config(cfg), jnp.float32)
    got = jax.grad(lambda p: jnp.sum(
        w * mixer.apply({"params": p}, h, jnp.asarray(mask))[0]))(lp["kda"])
    want = jax.grad(lambda p: sum(
        jnp.sum(w[r] * ref.kda_row(p, cfg, h[r], jnp.asarray(mask[r], bool), "f32")[0])
        for r in range(3)))(lp["kda"])
    for (path, g), t in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        name = "/".join(str(k.key) for k in path)
        assert float(jnp.linalg.norm(t)) > 0, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(t), err_msg=name,
                                   atol=2e-5 * max(1.0, float(jnp.abs(t).max())))


# -- (3) latent attention ------------------------------------------------------


@pytest.mark.parametrize("key_chunk", [4096, 40])
def test_latent_attention_tiled_matches_full_rows(tiny, monkeypatch, key_chunk):
    """``key_chunk`` 40: the softmax merged over three chunks of keys, the
    first of them without a real key in the left-padded rows."""
    ad, ref, cfg, _ = tiny
    monkeypatch.setattr(qwen, "_KEY_CHUNK", key_chunk)
    lp, h, mask = _normed(tiny, 3)
    mixer = LatentAttention(ad.model_config(cfg), jnp.float32)
    got, _ = mixer.apply({"params": lp["mla"]}, h, jnp.asarray(mask))
    for r in range(3):
        want = ref.mla_row(lp["mla"], cfg, h[r], jnp.asarray(mask[r], bool), "f32", 16)
        real = mask[r] == 1
        np.testing.assert_allclose(np.asarray(got[r])[real], np.asarray(want)[real],
                                   atol=1e-5)


def test_latent_attention_reads_no_position(tiny):
    """Permuting the mixer's inputs before the last query leaves that
    query's output unchanged: nothing is rotated, order enters nowhere."""
    ad, _, cfg, _ = tiny
    lp, h, _ = _normed(tiny, 3)
    perm = np.concatenate([np.random.default_rng(0).permutation(L - 1), [L - 1]])
    mixer = LatentAttention(ad.model_config(cfg), jnp.float32)
    a, _ = mixer.apply({"params": lp["mla"]}, h[:1])
    b, _ = mixer.apply({"params": lp["mla"]}, h[:1, perm])
    np.testing.assert_allclose(np.asarray(a[0, -1]), np.asarray(b[0, -1]), atol=1e-5)
    assert np.abs(np.asarray(a[0, 5]) - np.asarray(b[0, 5])).max() > 1e-3


# -- (4), (5) the expert layer -------------------------------------------------


def _moe_io(tiny, router_push=0.0, experts=16):
    ad, ref, cfg, params = tiny
    cfg = dict(cfg, num_experts=experts, num_experts_published=experts)
    rng = np.random.default_rng(1)
    u = rng.normal(size=(2, 32, cfg["hidden_size"])).astype(np.float32)
    valid = np.ones((2, 32), np.int32)
    valid[1, :5] = 0
    if experts == 16:
        p = dict(params["layer_1"]["moe"])
    else:
        p = dict(ad.make_params(cfg, 5)["layer_1"]["moe"])
    if router_push:
        # every token has a component along `ones`; expert 3's router column
        # reads it: nearly every token routes to expert 3
        u = u + 1.5
        k = np.asarray(p["router"]["kernel"]).copy()
        k[:, 3] += router_push
        p["router"] = {"kernel": jnp.asarray(k)}
    return ad, ref, cfg, p, jnp.asarray(u), valid


def _moe_reference(ref, cfg, p, u, valid, share=None, shared=True):
    flat = u.reshape(-1, u.shape[-1])
    _, eidx, gates = ref.route(p, cfg, flat, "f32")
    y = ref.experts(p, cfg, flat, eidx, gates, jnp.asarray(valid.reshape(-1), bool),
                    "f32", share)
    if shared:
        y = y + ref.swiglu(p["shared_expert"], flat, "f32")
    return np.asarray(y).reshape(u.shape), np.asarray(eidx), np.asarray(gates)


def test_sigmoid_router_scale_and_nothing_dropped_under_imbalance(tiny):
    ad, ref, cfg, p, u, valid = _moe_io(tiny, router_push=2.0)
    want, eidx, gates = _moe_reference(ref, cfg, p, u, valid)
    np.testing.assert_allclose(gates.sum(-1), cfg["routed_scaling_factor"], rtol=1e-5)
    load = np.bincount(eidx[valid.reshape(-1) == 1].reshape(-1), minlength=16)
    assert load[3] >= 0.95 * valid.sum()  # the capacity path would drop most
    moe = QwenMoEMLP(ad.model_config(cfg), jnp.float32)
    got, mut = moe.apply({"params": p}, u, jnp.asarray(valid), mutable=["counters"])
    np.testing.assert_allclose(np.asarray(got), want, atol=5e-5)
    c = mut["counters"]
    assert float(c["expert_picks_here_share"][0]) == pytest.approx(100.0)
    assert float(c["expert_load_max_over_mean"][0]) == pytest.approx(
        load.max() * 16 / load.sum(), rel=1e-6)
    assert float(c["expert_pairs_per_held_expert"][0]) == pytest.approx(
        load.sum() / 16, rel=1e-6)


def test_the_bias_enters_the_selection_and_not_the_gates(tiny):
    """A bias that flips the choice changes T and not s_e of the survivors:
    their gates move only through the renormalisation over the new T."""
    ad, ref, cfg, p, u, valid = _moe_io(tiny)
    flat = u.reshape(-1, u.shape[-1])
    scores, eidx0, _ = ref.route(p, cfg, flat, "f32")
    scores = np.asarray(scores)
    bias = np.zeros(16, np.float32)
    bias[7] = 10.0  # expert 7 is now chosen by every token
    pb = dict(p, selection_bias=jnp.asarray(bias))
    _, eidx1, gates1 = ref.route(pb, cfg, flat, "f32")
    eidx0, eidx1, gates1 = map(np.asarray, (eidx0, eidx1, gates1))
    assert (eidx1 == 7).any(axis=1).all() and not (eidx0 == 7).any(axis=1).all()
    picked = np.take_along_axis(scores, eidx1, axis=1)
    np.testing.assert_allclose(
        gates1, cfg["routed_scaling_factor"] * picked / picked.sum(1, keepdims=True),
        rtol=1e-5)  # from the scores alone: the bias is in no gate
    moe = QwenMoEMLP(ad.model_config(cfg), jnp.float32)
    got = moe.apply({"params": pb}, u, jnp.asarray(valid))
    want, _, _ = _moe_reference(ref, cfg, pb, u, valid)
    np.testing.assert_allclose(np.asarray(got), want, atol=5e-5)
    g = jax.grad(lambda q: jnp.sum(moe.apply({"params": q}, u, jnp.asarray(valid))))(pb)
    assert not np.asarray(g["selection_bias"]).any()  # a buffer: no gradient


def test_the_32_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(tiny):
    """The share ties to the model: each of 32 chips holds one of 32
    experts, routes over all 32, computes its own expert's part and the
    shared expert; the routed parts plus the shared expert counted ONCE
    add up to the uncut reference layer."""
    ad, ref, cfg, p, u, valid = _moe_io(tiny, experts=32)
    want, _, _ = _moe_reference(ref, cfg, p, u, valid, share=(0, 32))
    flat = u.reshape(-1, u.shape[-1])
    shared = np.asarray(ref.swiglu(p["shared_expert"], flat, "f32")).reshape(u.shape)
    total = np.zeros_like(want)
    picks = 0.0
    for s in range(32):
        part = dict(p, **{k: p[k][s:s + 1] for k in ("gate_proj", "up_proj", "down_proj")})
        moe = QwenMoEMLP(ad.model_config(cfg, share=(s, 1)), jnp.float32)
        got, mut = moe.apply({"params": part}, u, jnp.asarray(valid),
                             mutable=["counters"])
        ref_part, _, _ = _moe_reference(ref, cfg, part, u, valid, share=(s, 1))
        np.testing.assert_allclose(np.asarray(got), ref_part, atol=5e-5)
        total += np.asarray(got) - shared
        picks += float(mut["counters"]["expert_picks_here_share"][0])
    np.testing.assert_allclose(total + shared, want, atol=2e-4)
    assert picks == pytest.approx(100.0, rel=1e-5)


# -- (6) the model -------------------------------------------------------------


def test_loss_and_every_gradient_leaf_match_the_reference(tiny):
    from genrec_tpu.models.lcrec import sft_loss

    ad, ref, cfg, params = tiny
    ids, mask, labels = _rows()
    model = ad._model(cfg)  # rematerialised blocks, as the cell runs them
    assert [model.cfg.mixer_kind(i) for i in range(5)] == ["kda", "kda", "kda", "mla", "kda"]
    assert [model.cfg.mlp_kind(i) for i in range(5)] == ["dense"] + ["moe"] * 4
    loss, grads = jax.value_and_grad(
        lambda p: sft_loss(model, p, ids, mask, labels))(params)
    want_loss, want = ref.loss_and_grads(
        params, cfg, {"input_ids": ids, "attention_mask": mask, "labels": labels})
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    got = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, g), w in zip(got, jax.tree_util.tree_leaves(want)):
        name = "/".join(str(k.key) for k in path)
        if name.endswith("selection_bias"):  # a buffer: exactly zero, both sides
            assert not np.asarray(g).any() and not np.asarray(w).any(), name
        else:
            assert float(jnp.linalg.norm(w)) > 0, name
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-6,
                                       err_msg=name)


# -- (7) the cache ---------------------------------------------------------------


def test_prefill_then_decode_matches_the_full_forward(tiny):
    """Through the two-kind cache (a recurrent state and the convolutions'
    last inputs for KDA, a latent row a token for MLA): prefill through the
    chunked form, then steps through the recurrence, logits compared with
    the reference's full forward."""
    ad, ref, cfg, params = tiny
    ids, mask, _ = _rows(seed=5)
    mask[2, 70:] = 1  # ten real prompt tokens: fewer than a chunk
    model = QwenLM(ad.model_config(cfg), dtype=jnp.float32)
    P, T = 80, 6  # prompt slots, then six tokens fed one at a time
    want = np.asarray(ref.forward(params, cfg, ids[:, :P + T], mask[:, :P + T]))
    S = P + T
    pos = jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0)
    caches = model.apply({"params": params}, 3, S, method=QwenLM.init_cache)
    assert caches[0]["s"].shape == (3, 4, 16, 16) and caches[0]["conv"].shape == (3, 3, 192)
    assert caches[3]["latent"].shape == (3, S, 32 + 8)
    assert all(set(c) == {"s", "conv", "idx"} for i, c in enumerate(caches) if i != 3)
    pad = np.concatenate([mask[:, :P], np.zeros((3, T), np.int32)], axis=1)
    logits, caches = model.apply(
        {"params": params}, ids[:, :P], pos[:, :P], caches, jnp.asarray(pad),
        method=QwenLM.decode_step)
    real = mask[:, P - 1] == 1
    np.testing.assert_allclose(np.asarray(logits)[real], want[real, P - 1], atol=5e-5)
    for t in range(T):
        pad[:, P + t] = 1
        logits, caches = model.apply(
            {"params": params}, ids[:, P + t:P + t + 1], pos[:, P + t:P + t + 1],
            caches, jnp.asarray(pad), method=QwenLM.decode_step)
        np.testing.assert_allclose(np.asarray(logits), want[:, P + t], atol=5e-5)


def test_the_beams_reorder_moves_the_state_with_its_row(tiny, monkeypatch):
    """Constrained beam search through the cache: every surviving beam's
    score is the sum of the log-probabilities the FULL forward gives its
    tokens, which fails if a reorder leaves a KDA state or a latent row
    behind with another beam."""
    from genrec_tpu.models import lcrec

    ad, ref, cfg, params = tiny
    ids, mask, _ = _rows(seed=7)
    ids, mask = ids[:2, 40:], mask[:2, 40:]  # two prompts of 56 slots, one padded
    model = QwenLM(ad.model_config(cfg), dtype=jnp.float32)
    base, C, K, W = cfg["base_vocab"], 3, cfg["codebook_size"], 4
    out = lcrec.generate_topk_constrained(
        model, params, jnp.asarray(ids), jnp.asarray(mask), base, C, K, beam_width=W)
    sem, scores = np.asarray(out.sem_ids), np.asarray(out.log_probas)
    for r in range(2):
        assert len({tuple(s) for s in sem[r]}) == W  # distinct beams
        for w in range(W):
            toks = base + np.arange(C) * K + sem[r, w]
            row = np.concatenate([ids[r], toks[:-1]])[None]
            m = np.concatenate([mask[r], np.ones(C - 1, np.int32)])[None]
            z = np.asarray(ref.forward(params, cfg, row, m))[0]
            total = 0.0
            for c in range(C):
                logp = jax.nn.log_softmax(z[ids.shape[1] - 1 + c])
                total += float(logp[toks[c]])
            assert scores[r, w] == pytest.approx(total, abs=2e-4)


# -- the config's refusals and the FLOPs --------------------------------------


def test_layer_kinds_default_to_what_every_config_meant_before():
    cfg = qwen.QwenConfig(num_hidden_layers=3)
    assert [cfg.mixer_kind(i) for i in range(3)] == ["attention"] * 3
    assert [cfg.mlp_kind(i) for i in range(3)] == ["dense"] * 3
    assert cfg.dense_attention_layers
    moe = qwen.QwenConfig(num_hidden_layers=2, num_experts=4)
    assert [moe.mlp_kind(i) for i in range(2)] == ["moe", "moe"]
    with pytest.raises(ValueError, match="kda_heads"):
        qwen.QwenConfig(kda_layers=(1,))
    with pytest.raises(ValueError, match="kv_lora_rank"):
        qwen.QwenConfig(mla_layers=(1,))
    with pytest.raises(ValueError, match="overlap"):
        qwen.QwenConfig(kda_layers=(1,), mla_layers=(1,), kda_heads=2, kda_head_dim=8,
                        kv_lora_rank=8, qk_nope_head_dim=8, v_head_dim=8)
    with pytest.raises(ValueError, match="softmax or sigmoid"):
        qwen.QwenConfig(moe_scoring="tanh")


def test_the_pipeline_refuses_layers_of_several_kinds():
    # its stage body stacks ONE block over every layer
    from genrec_tpu.models.pp_sft import make_pp_sft_loss
    from genrec_tpu.parallel import make_mesh

    cfg = qwen.QwenConfig(num_hidden_layers=2, num_experts=4, first_k_dense_replace=1)
    with pytest.raises(ValueError, match="layers differ in kind"):
        make_pp_sft_loss(cfg, make_mesh({"data": 4, "pipe": 2}), n_micro=2)


def test_flops_count_the_equations_and_the_held_experts_only():
    flops = kt.module("flops")
    cfg = kt.tiny_config()
    d, K, H = 64, 16, 4
    assert flops.kda_token(cfg) == (2 * 4 * d * H * K + 4 * (d * K + K * H * K)
                                    + 2 * d * H + 2 * 4 * 3 * H * K + 6 * K * K * H)
    assert flops.mla_pair(cfg) == 2 * 4 * (16 + 8 + 16)
    one = flops.train_example(cfg, 91)  # a full row of 96
    half = flops.train_example(dict(cfg, num_experts=8), 91)
    f = cfg["moe_intermediate_size"]
    # 4 picks a token, half of them on the 8 held: 2 picks fewer a token in
    # each of the 4 expert layers; the shared expert stays
    assert one - half == pytest.approx(3 * 4 * 96 * 2 * 2 * 3 * d * f)
    with_keys = flops.forward_row(cfg, 96) - flops.forward_row(dict(cfg, num_attention_heads=0), 96)
    assert with_keys > flops.mla_pair(cfg) * 96 * 97 / 2  # causal keys only, plus projections
