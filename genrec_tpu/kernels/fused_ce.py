"""Fused full-softmax cross-entropy over a linear head (Pallas).

The reference SASRec/HSTU heads materialize (B, L, V) logits in HBM
(`logits = x @ emb.T` then CE, sasrec.py:121-128) — at Amazon scale
(B·L=6400 rows, V~12-22k items) that is hundreds of MB of HBM traffic per
step for a tensor that is immediately reduced to one scalar per row. This
kernel computes the EXACT same loss (full softmax, ignore_index
semantics) without ever writing the logits:

  forward:  grid (row-block, vocab-block), vocab innermost. Each tile
            computes its (blk_r, blk_v) logits on the MXU and folds them
            into running (max, sumexp, target-logit) accumulators held in
            VMEM scratch (online logsumexp, the flash-attention trick).
            The last vocab step writes per-row loss and logsumexp.
  backward: two kernels recompute tile logits flash-style:
            dx accumulates g*(softmax - onehot) @ W over vocab blocks;
            dW runs the transposed grid and accumulates over row blocks.

Exactness (vs sampled softmax, the other candidate the north star names)
keeps training parity with the reference bit-comparable in expectation —
nothing about the loss changes, only where it is computed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from genrec_tpu.kernels.policy import resolve_interpret

NEG = -1e30


def _round_up(x, m):
    return (x + m - 1) // m * m


def _tile_logits(x_ref, w_ref, vlim, j, blk_v, V):
    """(blk_r, blk_v) fp32 logits for this tile; cols at/past min(V, vlim)
    — shape padding or live vocab limit (head pad rows under TP) — at NEG."""
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    logits = jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    col = j * blk_v + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    limit = jnp.minimum(jnp.int32(V), vlim)
    return jnp.where(col < limit, logits, NEG), col


# Per-row operands and statistics (targets, lse, cotangent, loss, the
# running max / sumexp / target logit) are (blk_r, 1) COLUMNS end to end:
# rows stay on sublanes from the logits tile through every reduction
# (keepdims) to the store, so no value ever changes between a lane-major
# and a sublane-major layout inside the kernel.


def _fwd_kernel(v_ref, x_ref, w_ref, tgt_ref, loss_ref, lse_ref, m_sc, s_sc,
                t_sc, *, blk_v: int, V: int, ignore_index: int):
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    logits, col = _tile_logits(x_ref, w_ref, v_ref[0, 0], j, blk_v, V)
    tgt = tgt_ref[...]  # (blk_r, 1)
    # Target logit if it falls inside this vocab tile (sum-select: no
    # dynamic gather on TPU).
    t_here = jnp.sum(jnp.where(col == tgt, logits, 0.0), axis=1, keepdims=True)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc[...], NEG)
        s_sc[...] = jnp.zeros_like(s_sc[...])
        t_sc[...] = jnp.zeros_like(t_sc[...])

    m_old = m_sc[...]
    m_new = jnp.maximum(m_old, jnp.max(logits, axis=1, keepdims=True))
    s_sc[...] = s_sc[...] * jnp.exp(m_old - m_new) + jnp.sum(
        jnp.exp(logits - m_new), axis=1, keepdims=True
    )
    m_sc[...] = m_new
    t_sc[...] = t_sc[...] + t_here

    @pl.when(j == nj - 1)
    def _fin():
        lse = m_sc[...] + jnp.log(s_sc[...])
        loss = lse - t_sc[...]
        loss_ref[...] = jnp.where(tgt == ignore_index, 0.0, loss)
        lse_ref[...] = lse


def _dx_kernel(v_ref, x_ref, w_ref, tgt_ref, lse_ref, g_ref, dx_ref,
               *, blk_v: int, V: int):
    j = pl.program_id(1)
    logits, col = _tile_logits(x_ref, w_ref, v_ref[0, 0], j, blk_v, V)
    p = jnp.exp(logits - lse_ref[...])  # softmax tile
    onehot = (col == tgt_ref[...]).astype(jnp.float32)
    coeff = g_ref[...] * (p - onehot)  # (blk_r, blk_v)

    @pl.when(j == 0)
    def _init():
        dx_ref[...] = jnp.zeros_like(dx_ref[...])

    dx_ref[...] += jnp.dot(
        coeff, w_ref[...].astype(jnp.float32), preferred_element_type=jnp.float32
    )


def _dw_kernel(v_ref, x_ref, w_ref, tgt_ref, lse_ref, g_ref, dw_ref,
               *, blk_v: int, V: int):
    # Transposed grid: i = vocab block, inner j = row block.
    i = pl.program_id(0)
    j = pl.program_id(1)
    logits, col = _tile_logits(x_ref, w_ref, v_ref[0, 0], i, blk_v, V)
    p = jnp.exp(logits - lse_ref[...])
    onehot = (col == tgt_ref[...]).astype(jnp.float32)
    coeff = g_ref[...] * (p - onehot)  # (blk_r, blk_v)

    @pl.when(j == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref[...])

    dw_ref[...] += jax.lax.dot_general(
        coeff, x_ref[...].astype(jnp.float32),
        (((0,), (0,)), ((), ())),  # coeff^T @ x -> (blk_v, dp)
        preferred_element_type=jnp.float32,
    )


def _vocab_block(d: int) -> int:
    """Vocabulary rows a tile: 512 while a (tile, d) float32 block is at
    most 4 MiB (d <= 2,048), else 256. The dw kernel holds its output block
    twice beside the head's tile and the product: at d = 2,304 and 512 rows
    that is 16.26 MB of the 16 MB a kernel may scope (the chip's compiler
    refused it; PERF.md section 6, PR 34)."""
    return 512 if _round_up(d, 128) <= 2048 else 256


def _prep(x, w, targets, blk_r, blk_v):
    R, d = x.shape
    V = w.shape[0]
    Rp, Vp, dp = _round_up(R, blk_r), _round_up(V, blk_v), _round_up(d, 128)
    xf = jnp.pad(x, ((0, Rp - R), (0, dp - d)))
    wf = jnp.pad(w, ((0, Vp - V), (0, dp - d)))
    # Padded rows get target -1: never equal to any column, never ignored
    # into the loss (their loss rows are sliced off anyway).
    tf = jnp.pad(targets.astype(jnp.int32), (0, Rp - R), constant_values=-1)
    return xf, wf, tf[:, None], R, V, Rp, Vp, dp


def _vlim_operand(V, vlim):
    """The live-vocab limit as the kernels read it: one int32 in SMEM (a
    scalar read from a VMEM block is not a thing Mosaic does)."""
    return jnp.full((1, 1), V if vlim is None else vlim, jnp.int32)


_VLIM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def fused_linear_ce_fwd(x, w, targets, ignore_index=0, blk_r=128, blk_v=None,
                        interpret: bool = False, vlim=None):
    """Per-row CE losses (0 at ignored rows) and per-row logsumexp.

    x: (R, d) activations; w: (V, d) head weights (logits = x @ w.T);
    targets: (R,) int. ``vlim`` (optional traced int32): live-vocab limit —
    cols at/past it are excluded from the softmax (head pad rows under TP).
    Returns (loss (R,) f32, lse (R,) f32)."""
    interpret = resolve_interpret(interpret, "fused_linear_ce[fwd]")
    blk_v = blk_v or _vocab_block(x.shape[1])
    xf, wf, tf, R, V, Rp, Vp, dp = _prep(x, w, targets, blk_r, blk_v)
    n_rb, n_vb = Rp // blk_r, Vp // blk_v

    kernel = functools.partial(
        _fwd_kernel, blk_v=blk_v, V=V, ignore_index=ignore_index
    )
    col_spec = pl.BlockSpec((blk_r, 1), lambda i, j: (i, 0))
    loss, lse = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((Rp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Rp, 1), jnp.float32),
        ],
        grid=(n_rb, n_vb),
        in_specs=[
            _VLIM_SPEC,
            pl.BlockSpec((blk_r, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((blk_v, dp), lambda i, j: (j, 0)),
            col_spec,
        ],
        out_specs=[col_spec, col_spec],
        scratch_shapes=[
            pltpu.VMEM((blk_r, 1), jnp.float32),
            pltpu.VMEM((blk_r, 1), jnp.float32),
            pltpu.VMEM((blk_r, 1), jnp.float32),
        ],
        interpret=interpret,
        name="fused_ce_fwd",
    )(_vlim_operand(V, vlim), xf, wf, tf)
    return loss[:R, 0], lse[:R, 0]


def fused_linear_ce_bwd(x, w, targets, lse, g, ignore_index=0, blk_r=128,
                        blk_v=None, interpret: bool = False, vlim=None):
    """(dx, dw) for the fused CE. g: (R,) cotangent of the per-row losses.
    Ignored rows must carry g=0 (the forward zeroed their losses, so any
    upstream reduction gives them zero cotangent through the where)."""
    interpret = resolve_interpret(interpret, "fused_linear_ce[bwd]")
    blk_v = blk_v or _vocab_block(x.shape[1])
    xf, wf, tf, R, V, Rp, Vp, dp = _prep(x, w, targets, blk_r, blk_v)
    n_rb, n_vb = Rp // blk_r, Vp // blk_v
    vf = _vlim_operand(V, vlim)
    # Zero cotangent at ignored AND padded rows.
    gf = jnp.pad(g.astype(jnp.float32), (0, Rp - R))[:, None]
    gf = jnp.where(tf == ignore_index, 0.0, gf)
    lsef = jnp.pad(lse.astype(jnp.float32), (0, Rp - R))[:, None]

    def specs(row, voc):
        """Operand specs for a grid whose (i, j) program ids pick the row
        block through ``row`` and the vocab block through ``voc``."""
        col_spec = pl.BlockSpec((blk_r, 1), lambda i, j: (row(i, j), 0))
        return [
            _VLIM_SPEC,
            pl.BlockSpec((blk_r, dp), lambda i, j: (row(i, j), 0)),
            pl.BlockSpec((blk_v, dp), lambda i, j: (voc(i, j), 0)),
            col_spec, col_spec, col_spec,
        ]

    first, second = (lambda i, j: i), (lambda i, j: j)
    dx = pl.pallas_call(
        functools.partial(_dx_kernel, blk_v=blk_v, V=V),
        out_shape=jax.ShapeDtypeStruct((Rp, dp), jnp.float32),
        grid=(n_rb, n_vb),
        in_specs=specs(row=first, voc=second),
        out_specs=pl.BlockSpec((blk_r, dp), lambda i, j: (i, 0)),
        interpret=interpret,
        name="fused_ce_dx",
    )(vf, xf, wf, tf, lsef, gf)

    dw = pl.pallas_call(
        functools.partial(_dw_kernel, blk_v=blk_v, V=V),
        out_shape=jax.ShapeDtypeStruct((Vp, dp), jnp.float32),
        grid=(n_vb, n_rb),
        in_specs=specs(row=second, voc=first),
        out_specs=pl.BlockSpec((blk_v, dp), lambda i, j: (i, 0)),
        interpret=interpret,
        name="fused_ce_dw",
    )(vf, xf, wf, tf, lsef, gf)

    return dx[:R, : x.shape[1]].astype(x.dtype), dw[:V, : w.shape[1]].astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_linear_ce(x, w, targets, ignore_index=0):
    """Exact full-softmax CE over logits = x @ w.T without materializing
    them. Returns per-row losses, 0 at rows where target == ignore_index."""
    loss, _ = fused_linear_ce_fwd(x, w, targets, ignore_index)
    return loss


def _vjp_fwd(x, w, targets, ignore_index):
    loss, lse = fused_linear_ce_fwd(x, w, targets, ignore_index)
    return loss, (x, w, targets, lse)


def _vjp_bwd(ignore_index, res, g):
    x, w, targets, lse = res
    dx, dw = fused_linear_ce_bwd(x, w, targets, lse, g, ignore_index)
    return dx, dw, None


fused_linear_ce.defvjp(_vjp_fwd, _vjp_bwd)


def fused_ce_mean_loss(x, head_weights, targets, ignore_index=0):
    """Shared model-side wrapper: mean fused CE over valid (non-ignored)
    positions — the reference trainers' `sum / max(valid, 1)` convention
    (sasrec.py:124-128). x: (..., d); targets: (...) matching x's leading
    shape; head logits = x @ head_weights.T."""
    d = x.shape[-1]
    per_row = fused_linear_ce(
        x.reshape(-1, d), head_weights, targets.reshape(-1), ignore_index
    )
    valid = (targets.reshape(-1) != ignore_index).astype(jnp.float32)
    return per_row.sum() / jnp.maximum(valid.sum(), 1.0)


# ---------------------------------------------------------------------------
# Vocab-sharded fused CE (tensor parallelism over the head).
#
# Under tp>1 the head weights are vocab-sharded over the "model" mesh axis
# (parallel/shardings.qwen_rules dim 0) — exactly the configuration where a
# fused CE matters most (LCRec's ~150k-row head) and where the dense kernel
# above cannot be GSPMD-partitioned. Inside shard_map each shard runs the
# dense local kernels over its (V/tp, d) slice with offset-mapped targets,
# then the online-softmax accumulators combine across shards with one pmax
# + two psums (flash-style merge of per-shard logsumexps). Loss and grads
# match the replicated fused path to fp32 rounding; dW stays sharded, dx is
# psum-replicated.
#
# Structure note: the custom_vjp sits at the GLOBAL level and its fwd and
# bwd each run their own primal-only shard_map with every cross-shard
# reduction written explicitly. Differentiating *through* a shard_map whose
# replication checking is off mis-scales cotangents of outputs replicated
# over unmentioned axes (observed: dW halved at tp=2), so transposition of
# a shard_map region is deliberately never relied on here.
# ---------------------------------------------------------------------------


def _local_shard_stats(x, w_shard, targets, axis_name, valid_vocab):
    """Per-shard (local_targets, local_vlim, lse_local, target_logit_local).

    Targets are global vocab ids; ids outside this shard's [off, off+Vs)
    window map to -1, which never matches a column (so the shard
    contributes exactly 0.0 to the target-logit sum). The local kernel
    runs with ignore_index=-2 (never matches): row-level ignore semantics
    are applied globally by the caller, on the GLOBAL target id.
    ``valid_vocab`` (global live-vocab limit, or None) becomes the traced
    per-shard column limit clip(valid_vocab - off, 0, Vs).
    """
    local_tgt, vlim = _local_shard_targets(
        w_shard, targets, axis_name, valid_vocab
    )
    # loss_l = lse_l - t_l (no rows zeroed at ignore_index=-2), so the
    # target-logit partial is recoverable without a second kernel.
    loss_l, lse_l = fused_linear_ce_fwd(
        x, w_shard, local_tgt, ignore_index=-2, vlim=vlim
    )
    return local_tgt, vlim, lse_l, lse_l - loss_l


def _tp_shard_map(body, mesh, model_axis, data_axis, in_specs, out_specs):
    from jax.sharding import PartitionSpec as P

    def fix(spec):
        # Drop the data axis from specs when the mesh has no such axis
        # (pure-tp meshes).
        if data_axis is None or data_axis not in mesh.axis_names:
            return P(*(a for a in spec if a != data_axis))
        return spec

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(fix(s) for s in in_specs),
        out_specs=tuple(fix(s) for s in out_specs),
        check_vma=False,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def sharded_fused_linear_ce(x, w, targets, mesh, model_axis="model",
                            data_axis="data", ignore_index=0,
                            valid_vocab=None):
    """Exact full-softmax CE with the head vocab-sharded over
    ``model_axis``.

    Call at the GLOBAL (GSPMD) level: x (R, d) activations, w (Vpad, d)
    head weights laid out P(model_axis, None), targets (R,) global vocab
    ids; rows shard over ``data_axis`` when the mesh has one. Vpad must
    divide by the model-axis size (the trainer's extend_vocab pad_to
    guarantees this) and head pad rows past ``valid_vocab`` (static int)
    are excluded from the softmax, matching mask_vocab_logits /
    w[:valid_vocab] on the replicated path. Returns per-row losses, 0 at
    ignored rows.
    """
    loss, _ = _tp_vjp_fwd(
        x, w, targets, mesh, model_axis, data_axis, ignore_index, valid_vocab
    )
    return loss


def _tp_vjp_fwd(x, w, targets, mesh, model_axis, data_axis, ignore_index,
                valid_vocab):
    from jax.sharding import PartitionSpec as P

    def body(x, w_shard, t):
        _, _, lse_l, t_l = _local_shard_stats(
            x, w_shard, t, model_axis, valid_vocab
        )
        # A shard whose live window is empty (all pad rows) reports
        # lse_l ~ NEG; exp(lse_l - m) underflows to 0 in the merge.
        m = jax.lax.pmax(lse_l, model_axis)
        lse_g = m + jnp.log(jax.lax.psum(jnp.exp(lse_l - m), model_axis))
        t_g = jax.lax.psum(t_l, model_axis)
        t32 = t.astype(jnp.int32)
        loss = jnp.where(t32 == ignore_index, 0.0, lse_g - t_g)
        return loss, lse_g

    loss, lse_g = _tp_shard_map(
        body, mesh, model_axis, data_axis,
        in_specs=(P(data_axis), P(model_axis), P(data_axis)),
        out_specs=(P(data_axis), P(data_axis)),
    )(x, w, targets)
    return loss, (x, w, targets, lse_g)


def _tp_vjp_bwd(mesh, model_axis, data_axis, ignore_index, valid_vocab,
                res, g):
    from jax.sharding import PartitionSpec as P

    x, w, targets, lse_g = res

    def body(x, w_shard, t, lse, g):
        local_tgt, vlim, = _local_shard_targets(
            w_shard, t, model_axis, valid_vocab
        )
        t32 = t.astype(jnp.int32)
        g = jnp.where(t32 == ignore_index, 0.0, g.astype(jnp.float32))
        dx_l, dw_l = fused_linear_ce_bwd(
            x, w_shard, local_tgt, lse, g, ignore_index=-2, vlim=vlim
        )
        # dx: each model shard covers its vocab slice of
        # g*(softmax - onehot) @ W; the full row-grad is their sum.
        dx = jax.lax.psum(dx_l, model_axis)
        # dW: shard-local in the vocab dim (pad rows past vlim get exactly
        # zero, their cols are NEG-masked), but each data shard only saw
        # its batch rows — sum the batch contributions explicitly.
        if data_axis is not None and data_axis in mesh.axis_names:
            dw_l = jax.lax.psum(dw_l, data_axis)
        return dx, dw_l

    dx, dw = _tp_shard_map(
        body, mesh, model_axis, data_axis,
        in_specs=(
            P(data_axis), P(model_axis), P(data_axis), P(data_axis),
            P(data_axis),
        ),
        out_specs=(P(data_axis), P(model_axis)),
    )(x, w, targets, lse_g, g)
    return dx.astype(x.dtype), dw.astype(w.dtype), None


def _local_shard_targets(w_shard, targets, axis_name, valid_vocab):
    """(local_targets, local_vlim) — the offset mapping of
    _local_shard_stats without running the forward kernel."""
    Vs = w_shard.shape[0]
    off = jax.lax.axis_index(axis_name).astype(jnp.int32) * Vs
    t32 = targets.astype(jnp.int32)
    here = (t32 >= off) & (t32 < off + Vs)
    local_tgt = jnp.where(here, t32 - off, -1)
    vlim = (
        None
        if valid_vocab is None
        else jnp.clip(jnp.int32(valid_vocab) - off, 0, Vs)
    )
    return local_tgt, vlim


sharded_fused_linear_ce.defvjp(_tp_vjp_fwd, _tp_vjp_bwd)


def linear_ce_xla(x, w, targets, ignore_index=0):
    """Reference path: materialized logits + CE (what the kernel replaces)."""
    logits = (x.astype(jnp.float32) @ w.T.astype(jnp.float32))
    lse = jax.nn.logsumexp(logits, axis=-1)
    t = jnp.take_along_axis(logits, targets[:, None].astype(jnp.int32), axis=1)[:, 0]
    return jnp.where(targets == ignore_index, 0.0, lse - t)
