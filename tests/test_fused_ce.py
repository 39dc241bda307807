"""Fused linear+CE kernel vs materialized-logits XLA reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from genrec_tpu.kernels.fused_ce import (
    fused_linear_ce,
    fused_linear_ce_fwd,
    linear_ce_xla,
)


def _inputs(R=300, V=1000, d=48, seed=0, ignore_frac=0.2):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(R, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(V, d)) * 0.1, jnp.float32)
    tgt = rng.integers(0, V, size=(R,))
    tgt[rng.random(R) < ignore_frac] = 0  # ignore_index rows
    return x, w, jnp.asarray(tgt, jnp.int32)


@pytest.mark.parametrize("shape", [(300, 1000, 48), (128, 512, 128), (37, 700, 64)])
def test_fwd_matches_xla(shape):
    R, V, d = shape
    x, w, tgt = _inputs(R, V, d)
    ref = linear_ce_xla(x, w, tgt)
    got, _ = fused_linear_ce_fwd(x, w, tgt, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4, rtol=1e-5)


def test_grads_match_xla():
    x, w, tgt = _inputs(R=200, V=900, d=32)

    def loss_ref(x, w):
        per_row = linear_ce_xla(x, w, tgt)
        return per_row.sum() / jnp.maximum((tgt != 0).sum(), 1)

    def loss_fused(x, w):
        per_row = fused_linear_ce(x, w, tgt)
        return per_row.sum() / jnp.maximum((tgt != 0).sum(), 1)

    gx_ref, gw_ref = jax.grad(loss_ref, argnums=(0, 1))(x, w)
    gx, gw = jax.grad(loss_fused, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_ref), atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_ref), atol=1e-5, rtol=1e-4)


def test_all_rows_ignored():
    x, w, _ = _inputs(R=64, V=300, d=16)
    tgt = jnp.zeros((64,), jnp.int32)
    got, _ = fused_linear_ce_fwd(x, w, tgt, interpret=True)
    assert float(jnp.abs(got).sum()) == 0.0
    gx = jax.grad(lambda x: fused_linear_ce(x, w, tgt).sum())(x)
    assert float(jnp.abs(gx).sum()) == 0.0


def test_sasrec_fused_ce_loss_and_grads_match():
    """SASRec with fused_ce=True: identical loss AND grads to the
    materialized-logits model (the default-on TPU path is a pure drop-in)."""
    from genrec_tpu.models.sasrec import SASRec

    rng = np.random.default_rng(3)
    B, L, V = 8, 20, 120
    ids = jnp.asarray(rng.integers(0, V + 1, (B, L)), jnp.int32)
    tgt = jnp.asarray(rng.integers(0, V + 1, (B, L)), jnp.int32)

    base = SASRec(num_items=V, max_seq_len=L, embed_dim=32, ffn_dim=64)
    fused = SASRec(num_items=V, max_seq_len=L, embed_dim=32, ffn_dim=64,
                   fused_ce=True)
    params = base.init(jax.random.key(0), ids)["params"]

    def loss_base(p):
        _, loss = base.apply({"params": p}, ids, tgt)
        return loss

    def loss_fused(p):
        _, loss = fused.apply({"params": p}, ids, tgt)
        return loss

    l0, g0 = jax.value_and_grad(loss_base)(params)
    l1, g1 = jax.value_and_grad(loss_fused)(params)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)
    flat0 = jax.tree_util.tree_leaves(g0)
    flat1 = jax.tree_util.tree_leaves(g1)
    for a, b in zip(flat0, flat1):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=1e-5, rtol=1e-4)


def test_hstu_fused_ce_loss_matches():
    from genrec_tpu.models.hstu import HSTU

    rng = np.random.default_rng(4)
    B, L, V = 4, 16, 90
    ids = jnp.asarray(rng.integers(0, V + 1, (B, L)), jnp.int32)
    ts = jnp.asarray(np.cumsum(rng.integers(1, 9999, (B, L)), 1), jnp.int32)
    tgt = jnp.asarray(rng.integers(0, V + 1, (B, L)), jnp.int32)

    base = HSTU(num_items=V, max_seq_len=L, embed_dim=32)
    fused = HSTU(num_items=V, max_seq_len=L, embed_dim=32, fused_ce=True)
    params = base.init(jax.random.key(0), ids, ts)["params"]
    _, l0 = base.apply({"params": params}, ids, ts, tgt)
    _, l1 = fused.apply({"params": params}, ids, ts, tgt)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)


def test_qwen_sft_fused_ce_matches_dense():
    """sft_loss(use_fused_ce=True) == the materialized-logits sft_loss,
    values AND grads, including valid_vocab row-slicing and -100 labels
    (the LCRec SFT head at real vocab is the kernel's biggest win)."""
    from genrec_tpu.models.backbones.qwen import QwenConfig, QwenLM
    from genrec_tpu.models.lcrec import sft_loss

    cfg = QwenConfig(
        vocab_size=96, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=1,
        max_position_embeddings=32, rope_theta=10000.0,
        tie_word_embeddings=False,
    )
    model = QwenLM(cfg)
    rng = np.random.default_rng(8)
    B, L = 4, 24
    ids = jnp.asarray(rng.integers(0, 80, (B, L)), jnp.int32)
    am = jnp.ones((B, L), jnp.int32)
    labels = np.asarray(ids).copy()
    labels[:, :6] = -100  # prompt-masked
    labels = jnp.asarray(labels)
    params = model.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]

    def dense(p):
        return sft_loss(model, p, ids, am, labels, valid_vocab=80)

    def fused(p):
        return sft_loss(model, p, ids, am, labels, valid_vocab=80,
                        use_fused_ce=True)

    l0, g0 = jax.value_and_grad(dense)(params)
    l1, g1 = jax.value_and_grad(fused)(params)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=2e-5, rtol=1e-4)


def test_sasrec_fused_ce_under_data_mesh():
    """Fused-CE SASRec train step over the 8-device data mesh == the
    materialized-logits step: the kernel's per-row losses are
    data-parallel by construction, and the sharded jit must agree with
    the replicated math. (Interpret-mode lowering on CPU — the compiled
    Mosaic partitioning is hardware-validated by the preflight.)"""
    from genrec_tpu.models.sasrec import SASRec
    from genrec_tpu.parallel import get_mesh, replicate, shard_batch

    rng = np.random.default_rng(5)
    B, L, V = 16, 12, 150
    ids = rng.integers(0, V + 1, (B, L)).astype(np.int32)
    tgt = rng.integers(0, V + 1, (B, L)).astype(np.int32)

    def run(fused):
        model = SASRec(num_items=V, max_seq_len=L, embed_dim=32, ffn_dim=64,
                       dropout=0.0, fused_ce=fused)
        params = model.init(jax.random.key(0), jnp.asarray(ids))["params"]

        def loss_fn(p, b):
            _, loss = model.apply({"params": p}, b["input_ids"], b["targets"],
                                  deterministic=True)
            return loss

        grad_fn = jax.jit(jax.value_and_grad(loss_fn))
        mesh = get_mesh()
        placed = replicate(mesh, params)
        sharded = shard_batch(mesh, {"input_ids": ids, "targets": tgt})
        loss, grads = grad_fn(placed, sharded)
        return float(loss), grads

    l_dense, g_dense = run(False)
    l_fused, g_fused = run(True)
    np.testing.assert_allclose(l_fused, l_dense, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g_dense),
                    jax.tree_util.tree_leaves(g_fused)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=1e-5, rtol=1e-4)


def test_bf16_inputs():
    x, w, tgt = _inputs(R=128, V=600, d=64)
    got, _ = fused_linear_ce_fwd(
        x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), tgt, interpret=True
    )
    ref = linear_ce_xla(
        x.astype(jnp.bfloat16).astype(jnp.float32),
        w.astype(jnp.bfloat16).astype(jnp.float32),
        tgt,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-2, rtol=1e-2)


# -- the vocabulary tile follows the width (PR 34) ------------------------------


def test_vocab_tile_halves_past_width_2048():
    from genrec_tpu.kernels.fused_ce import _vocab_block

    assert _vocab_block(1536) == _vocab_block(2048) == 512
    assert _vocab_block(2304) == _vocab_block(4096) == 256


@pytest.fixture(scope="module")
def one_chip():
    """A v5e chip that is described and not attached (inside a fixture,
    never while a module is imported)."""
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here, or it is taken
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("d, rows_v", [(2048, 20272), (2304, 21760)])
def test_backward_kernels_fit_the_chips_scoped_memory(one_chip, monkeypatch, d, rows_v):
    """The dw kernel holds its (tile, d) float32 output block twice beside
    the head's tile: at d = 2,304 a 512-row tile is 16.26 MB of the 16 MB a
    kernel may scope, and the chip's compiler refuses it. Compiled here at
    the two language models' head shapes, a row of 8,192 tokens."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    from genrec_tpu.kernels import policy
    from genrec_tpu.kernels.fused_ce import fused_linear_ce_bwd

    # the suite's interpreter off and the chip's branch taken: the Mosaic
    # kernel itself is what the described chip's compiler gets
    monkeypatch.setattr(policy, "_interpret_requested", False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    R = 8192
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        compiled = jax.jit(
            lambda x, w, t, lse, g: fused_linear_ce_bwd(x, w, t, lse, g, -100)
        ).lower(sds((R, d), jnp.bfloat16), sds((rows_v, d), jnp.bfloat16),
                sds((R,), jnp.int32), sds((R,), jnp.float32),
                sds((R,), jnp.float32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    assert "tpu_custom_call" in compiled.as_text()
