"""Fused HSTU SiLU-attention Pallas kernel.

The reference materializes a (B, H, L, L) float bias tensor per layer per
step (hstu.py:386-409) — at L=50 that's noise, but it scales O(L^2) in HBM
traffic and is exactly what SURVEY.md §5.7 flags as the kernel-fusion
target. This kernel computes, per (batch*head, q-block) tile:

    scores = Q_blk @ K^T                       (MXU, fp32 accumulate)
    scores += pos_bias[bucket(j - i)]          (bucket math in-registers)
    scores += time_bias[bucket(|t_i - t_j|)]
    scores  = -1e9 where causal/padding masked
    out     = silu(scores) @ V                 (MXU)

so neither bias nor the (L, L) score matrix ever round-trips to HBM.
Bias-table lookups use a one-hot select loop over the (tiny) bucket tables
— TPU-friendly, no dynamic gather.

`hstu_attention` wraps the kernel in jax.custom_vjp with a fused Pallas
backward (`hstu_attention_bwd_pallas`): each (batch*head, q-block) tile
recomputes scores + biases flash-style (nothing saved but the inputs),
then emits dq per tile, accumulates dk/dv into revisited output blocks
across the sequentially-executed q-block grid dimension, and writes
per-tile bias-table partials that XLA sums afterwards — so training,
like inference, never materializes the (B, H, L, L) score/bias tensors
the reference does (hstu.py:386-409).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from genrec_tpu.kernels.policy import resolve_interpret

NEG = -1e9


def _pos_bucket_f(rel, num_buckets, max_distance):
    """hstu_position_bucket (ops/buckets.py) in kernel-safe form."""
    rp = jnp.maximum(rel, 0)
    max_exact = num_buckets // 2
    large = max_exact + (
        jnp.log(jnp.maximum(rp, 1).astype(jnp.float32) / max_exact)
        / math.log(max_distance / max_exact)
        * (num_buckets - max_exact)
    ).astype(jnp.int32)
    large = jnp.minimum(large, num_buckets - 1)
    return jnp.where(rp < max_exact, rp, large)


def _time_bucket_f(diff, num_buckets):
    abs_diff = jnp.maximum(jnp.abs(diff), 1).astype(jnp.float32)
    b = (jnp.log(abs_diff) / 0.693).astype(jnp.int32)
    return jnp.clip(b, 0, num_buckets - 1)


def _table_bias(buckets, tab_ref, h, num_buckets: int):
    """sum_b where(buckets == b, table[h, b]): the (tiny) bucket table
    sits whole in SMEM and is read one scalar at a time — TPU-friendly,
    no dynamic gather."""
    bias = jnp.zeros(buckets.shape, jnp.float32)
    for b in range(num_buckets):
        bias = bias + jnp.where(buckets == b, tab_ref[h, b], 0.0)
    return bias


def _masked_scores(
    q, k, ts_ref, tsq_ref, mask_ref, seg_ref, segq_ref, ptab_ref, ttab_ref,
    *, n_heads: int, blk_q: int, num_pos_buckets: int, num_time_buckets: int,
    max_position_distance: int, use_time: bool, use_seg: bool,
):
    """(scores with -1e9 at masked pairs, mask, pos buckets, time buckets)
    for this (batch*head, q-block) tile. The forward and the backward
    kernel recompute identical scores only because both run THIS body."""
    h = pl.program_id(0) % n_heads
    j = pl.program_id(1)
    L = k.shape[0]
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (blk_q, L)

    q_pos = j * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, L), 0)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (blk_q, L), 1)

    # Replicated reference quirk: rel = key - query, clamped >= 0 in the
    # bucket fn (see models/hstu.py RelativePositionBias).
    pbucket = _pos_bucket_f(k_pos - q_pos, num_pos_buckets, max_position_distance)
    scores = scores + _table_bias(pbucket, ptab_ref, h, num_pos_buckets)

    tbucket = None
    if use_time:
        # The q-tile timestamps (and segment ids below) arrive as their
        # own blocked (1, blk_q) operand — dynamic_slice on a ref is not
        # lowerable in Mosaic TC kernels — and become a column by a 2-D
        # transpose, never through a 1-D value.
        tdiff = tsq_ref[0].T - ts_ref[0]  # (blk_q, 1) - (1, L)
        tbucket = _time_bucket_f(tdiff, num_time_buckets)
        scores = scores + _table_bias(tbucket, ttab_ref, h, num_time_buckets)

    masked = jnp.logical_or(k_pos > q_pos, mask_ref[0] != 0)
    if use_seg:
        # Packed rows: a query must not see keys from another segment
        # (same in-register fold as the causal/padding mask — packing does
        # not force the unfused fallback).
        masked = jnp.logical_or(masked, segq_ref[0].T != seg_ref[0])
    return jnp.where(masked, NEG, scores), masked, pbucket, tbucket


def _kernel(
    q_ref, k_ref, v_ref, ts_ref, tsq_ref, mask_ref, seg_ref, segq_ref,
    ptab_ref, ttab_ref, out_ref, **cfg,
):
    scores, _, _, _ = _masked_scores(
        q_ref[0], k_ref[0], ts_ref, tsq_ref, mask_ref, seg_ref, segq_ref,
        ptab_ref, ttab_ref, **cfg,
    )
    attn = scores * jax.nn.sigmoid(scores)  # silu
    out_ref[0] = jnp.dot(
        attn.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32
    ).astype(out_ref.dtype)


# The (H, buckets) bias tables are read one scalar at a time: whole, in
# SMEM, indexed [head, bucket].
_TABLE_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)
_BWD_VMEM_BYTES = 64 * 1024 * 1024


def _round_up(x, m):
    return (x + m - 1) // m * m


def _pad(x, target_len, axis, value=0):
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, target_len - x.shape[axis])
    return jnp.pad(x, cfg, constant_values=value)


def _pad_inputs(q, k, v, timestamps, padding_mask, time_table, blk_q,
                segment_ids=None):
    """Shared fwd/bwd input prep: flatten (B,H) and pad L to the q-block
    multiple and hd to the 128-lane multiple. Padded key positions are
    masked (value=1); absent timestamps/time_table/segment_ids get inert
    zeros so the operand list keeps a static shape. The forward and
    backward kernels recompute identical scores only because they run
    through this ONE helper."""
    B, H, L, hd = q.shape
    Lp = _round_up(L, blk_q)
    hp = _round_up(hd, 128)
    qf = _pad(_pad(q.reshape(B * H, L, hd), Lp, 1), hp, 2)
    kf = _pad(_pad(k.reshape(B * H, L, hd), Lp, 1), hp, 2)
    vf = _pad(_pad(v.reshape(B * H, L, hd), Lp, 1), hp, 2)
    maskf = _pad(padding_mask.astype(jnp.int32), Lp, 1, value=1)
    if timestamps is not None and time_table is not None:
        tsf = _pad(timestamps.astype(jnp.int32), Lp, 1)
    else:
        tsf = jnp.zeros((B, Lp), jnp.int32)
        time_table = jnp.zeros((H, 1), jnp.float32)
    if segment_ids is not None:
        segf = _pad(segment_ids.astype(jnp.int32), Lp, 1)
    else:
        segf = jnp.zeros((B, Lp), jnp.int32)
    return qf, kf, vf, maskf, tsf, segf, time_table, Lp, hp


def hstu_attention_pallas(
    q, k, v, timestamps, padding_mask, pos_table, time_table,
    max_position_distance: int = 128, blk_q: int = 128, interpret: bool = False,
    segment_ids=None,
):
    """Fused SiLU attention.

    Args:
        q, k, v: (B, H, L, hd)
        timestamps: (B, L) int32 or None
        padding_mask: (B, L) bool/int — True/1 = padding
        pos_table: (H, num_pos_buckets)
        time_table: (H, num_time_buckets) or None
        segment_ids: (B, L) int32 or None — packed-row segments (0 = pad);
            cross-segment pairs are masked in-register.
    Returns:
        (B, H, L, hd) attention output (same dtype as v).
    """
    B, H, L, hd = q.shape
    use_time = timestamps is not None and time_table is not None
    use_seg = segment_ids is not None
    interpret = resolve_interpret(interpret, "hstu_attention[fwd]")
    qf, kf, vf, maskf, tsf, segf, time_table, Lp, hp = _pad_inputs(
        q, k, v, timestamps, padding_mask, time_table, blk_q, segment_ids
    )
    n_q = Lp // blk_q
    grid = (B * H, n_q)

    kernel = functools.partial(
        _kernel,
        n_heads=H,
        blk_q=blk_q,
        num_pos_buckets=pos_table.shape[1],
        num_time_buckets=time_table.shape[1],
        max_position_distance=max_position_distance,
        use_time=use_time,
        use_seg=use_seg,
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B * H, Lp, hp), v.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_q, hp), lambda i, j: (i, j, 0)),  # q block
            pl.BlockSpec((1, Lp, hp), lambda i, j: (i, 0, 0)),  # full k
            pl.BlockSpec((1, Lp, hp), lambda i, j: (i, 0, 0)),  # full v
            # Small per-batch/per-head operands carry a leading select dim:
            # Mosaic requires the last two BLOCK dims to be (8,128)-aligned
            # or equal to the full array dims, and leading dims are free —
            # a (1, Lp) block over (B, Lp) is illegal when B != 1 (the
            # round-1 compiled-path failure).
            pl.BlockSpec((1, 1, Lp), lambda i, j: (i // H, 0, 0)),  # timestamps (keys)
            pl.BlockSpec((1, 1, blk_q), lambda i, j: (i // H, 0, j)),  # ts q-tile
            pl.BlockSpec((1, 1, Lp), lambda i, j: (i // H, 0, 0)),  # padding mask
            pl.BlockSpec((1, 1, Lp), lambda i, j: (i // H, 0, 0)),  # segments (keys)
            pl.BlockSpec((1, 1, blk_q), lambda i, j: (i // H, 0, j)),  # seg q-tile
            _TABLE_SPEC,
            _TABLE_SPEC,
        ],
        out_specs=pl.BlockSpec((1, blk_q, hp), lambda i, j: (i, j, 0)),
        interpret=interpret,
        name="hstu_attention_fwd",
    )(qf, kf, vf, tsf[:, None], tsf[:, None], maskf[:, None],
      segf[:, None], segf[:, None], pos_table.astype(jnp.float32),
      time_table.astype(jnp.float32))
    return out.reshape(B, H, Lp, hp)[:, :, :L, :hd]


def _table_grad(buckets, ds, num_buckets: int):
    """(1, num_buckets) row of sum(ds where buckets == b). Each bucket's
    scalar lands in its lane through an iota select: a vector is never
    assembled from scalars."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, num_buckets), 1)
    row = jnp.zeros((1, num_buckets), jnp.float32)
    for b in range(num_buckets):
        row = row + jnp.where(
            lane == b, jnp.sum(jnp.where(buckets == b, ds, 0.0)), 0.0
        )
    return row


def _bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, ts_ref, tsq_ref, mask_ref, seg_ref, segq_ref,
    ptab_ref, ttab_ref,
    dq_ref, dk_ref, dv_ref, dpt_ref, dtt_ref, **cfg,
):
    j = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)  # (blk_q, hd)
    k = k_ref[0].astype(jnp.float32)  # (L, hd)
    v = v_ref[0].astype(jnp.float32)  # (L, hd)
    do = do_ref[0].astype(jnp.float32)  # (blk_q, hd)

    # --- Recompute the masked scores exactly as the forward kernel does.
    s, masked, pbucket, tbucket = _masked_scores(
        q, k, ts_ref, tsq_ref, mask_ref, seg_ref, segq_ref, ptab_ref,
        ttab_ref, **cfg,
    )

    # --- Local grads. silu(s) = s*sig(s); silu'(s) = sig(s)*(1 + s*(1-sig(s))).
    sig = jax.nn.sigmoid(s)
    attn = s * sig  # (blk_q, L)
    d_attn = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (blk_q, L)
    # Gradient at the PRE-mask scores: masked entries get exactly zero
    # (the where() in the forward routes no gradient to them).
    ds = jnp.where(masked, 0.0, d_attn * sig * (1.0 + s * (1.0 - sig)))

    # --- Input grads. dq per tile; dk/dv accumulate across the j grid
    # dim (sequential on TPU; the output blocks are revisited).
    dq_ref[0] = jnp.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _init():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])
        dpt_ref[0] = jnp.zeros_like(dpt_ref[0])
        # Zero even when use_time is False (1-wide dummy table): the output
        # buffer is otherwise uninitialized memory for any future consumer.
        dtt_ref[0] = jnp.zeros_like(dtt_ref[0])

    tn = (((0,), (0,)), ((), ()))  # a.T @ b without materializing a.T
    dk_ref[0] += jax.lax.dot_general(ds, q, tn, preferred_element_type=jnp.float32)
    dv_ref[0] += jax.lax.dot_general(attn, do, tn, preferred_element_type=jnp.float32)

    # --- Bias-table partials for this tile (summed over tiles in XLA).
    dpt_ref[0] += _table_grad(pbucket, ds, cfg["num_pos_buckets"])
    if cfg["use_time"]:
        dtt_ref[0] += _table_grad(tbucket, ds, cfg["num_time_buckets"])


def hstu_attention_bwd_pallas(
    q, k, v, timestamps, padding_mask, pos_table, time_table, g,
    max_position_distance: int = 128, blk_q: int = 128, interpret: bool = False,
    segment_ids=None,
):
    """Fused flash-style backward. Returns (dq, dk, dv, dpos_table,
    dtime_table) with input dtypes; accumulation is fp32 in-kernel."""
    B, H, L, hd = q.shape
    use_time = timestamps is not None and time_table is not None
    use_seg = segment_ids is not None
    interpret = resolve_interpret(interpret, "hstu_attention[bwd]")
    qf, kf, vf, maskf, tsf, segf, ttab, Lp, hp = _pad_inputs(
        q, k, v, timestamps, padding_mask, time_table, blk_q, segment_ids
    )
    gf = _pad(_pad(g.reshape(B * H, L, hd), Lp, 1), hp, 2)
    n_q = Lp // blk_q
    grid = (B * H, n_q)
    nb, ntb = pos_table.shape[1], ttab.shape[1]

    kernel = functools.partial(
        _bwd_kernel,
        n_heads=H,
        blk_q=blk_q,
        num_pos_buckets=nb,
        num_time_buckets=ntb,
        max_position_distance=max_position_distance,
        use_time=use_time,
        use_seg=use_seg,
    )
    dq, dk, dv, dpt, dtt = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Lp, hp), jnp.float32),  # dq
            jax.ShapeDtypeStruct((B * H, Lp, hp), jnp.float32),  # dk
            jax.ShapeDtypeStruct((B * H, Lp, hp), jnp.float32),  # dv
            jax.ShapeDtypeStruct((B * H, 1, nb), jnp.float32),  # dpos partials
            jax.ShapeDtypeStruct((B * H, 1, ntb), jnp.float32),  # dtime partials
        ],
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_q, hp), lambda i, j: (i, j, 0)),  # q block
            pl.BlockSpec((1, Lp, hp), lambda i, j: (i, 0, 0)),  # full k
            pl.BlockSpec((1, Lp, hp), lambda i, j: (i, 0, 0)),  # full v
            pl.BlockSpec((1, blk_q, hp), lambda i, j: (i, j, 0)),  # dO block
            pl.BlockSpec((1, 1, Lp), lambda i, j: (i // H, 0, 0)),  # ts (keys)
            pl.BlockSpec((1, 1, blk_q), lambda i, j: (i // H, 0, j)),  # ts q-tile
            pl.BlockSpec((1, 1, Lp), lambda i, j: (i // H, 0, 0)),  # padding mask
            pl.BlockSpec((1, 1, Lp), lambda i, j: (i // H, 0, 0)),  # segments (keys)
            pl.BlockSpec((1, 1, blk_q), lambda i, j: (i // H, 0, j)),  # seg q-tile
            _TABLE_SPEC,
            _TABLE_SPEC,
        ],
        out_specs=[
            pl.BlockSpec((1, blk_q, hp), lambda i, j: (i, j, 0)),  # dq per tile
            pl.BlockSpec((1, Lp, hp), lambda i, j: (i, 0, 0)),  # dk accumulated
            pl.BlockSpec((1, Lp, hp), lambda i, j: (i, 0, 0)),  # dv accumulated
            pl.BlockSpec((1, 1, nb), lambda i, j: (i, 0, 0)),  # dpos accumulated
            pl.BlockSpec((1, 1, ntb), lambda i, j: (i, 0, 0)),  # dtime accumulated
        ],
        # Whole-sequence K/V blocks plus a handful of live (blk_q, L) fp32
        # score-sized temporaries outgrow the default scoped-VMEM window
        # at long L (preflight runs L=2048).
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_BWD_VMEM_BYTES),
        interpret=interpret,
        name="hstu_attention_bwd",
    )(qf, kf, vf, gf, tsf[:, None], tsf[:, None], maskf[:, None],
      segf[:, None], segf[:, None], pos_table.astype(jnp.float32),
      ttab.astype(jnp.float32))

    dq = dq.reshape(B, H, Lp, hp)[:, :, :L, :hd].astype(q.dtype)
    dk = dk.reshape(B, H, Lp, hp)[:, :, :L, :hd].astype(k.dtype)
    dv = dv.reshape(B, H, Lp, hp)[:, :, :L, :hd].astype(v.dtype)
    # Per-(b,h) partials -> per-head tables (sum over the batch).
    dpt = dpt.reshape(B, H, nb).sum(0).astype(pos_table.dtype)
    dttab = (
        dtt.reshape(B, H, ntb).sum(0).astype(time_table.dtype) if use_time else None
    )
    return dq, dk, dv, dpt, dttab


def hstu_attention_xla(
    q, k, v, timestamps, padding_mask, pos_table, time_table,
    max_position_distance: int = 128, segment_ids=None,
):
    """Reference-shaped XLA implementation (materializes the bias); used as
    fallback and as the source of the backward pass."""
    from genrec_tpu.ops.buckets import hstu_log_bucket, hstu_position_bucket

    B, H, L, hd = q.shape
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
    pos = jnp.arange(L)
    rel = pos[None, :] - pos[:, None]  # [i, j] = j - i (reference quirk)
    pbuckets = hstu_position_bucket(rel, pos_table.shape[1], max_position_distance)
    scores = scores + pos_table.T[pbuckets].transpose(2, 0, 1)[None]
    if timestamps is not None and time_table is not None:
        diff = timestamps[:, :, None] - timestamps[:, None, :]
        tbuckets = hstu_log_bucket(diff, time_table.shape[1])
        scores = scores + time_table.T[tbuckets].transpose(0, 3, 1, 2)
    causal = jnp.triu(jnp.ones((L, L), bool), k=1)
    scores = jnp.where(causal[None, None], NEG, scores)
    scores = jnp.where(padding_mask.astype(bool)[:, None, None, :], NEG, scores)
    if segment_ids is not None:
        cross = segment_ids[:, :, None] != segment_ids[:, None, :]  # (B, L, L)
        scores = jnp.where(cross[:, None], NEG, scores)
    attn = jax.nn.silu(scores).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", attn, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def hstu_attention(q, k, v, timestamps, padding_mask, pos_table, time_table,
                   segment_ids=None, max_position_distance=128):
    """Kernel forward + fused flash-style Pallas backward."""
    return hstu_attention_pallas(
        q, k, v, timestamps, padding_mask, pos_table, time_table,
        max_position_distance, segment_ids=segment_ids,
    )


def _fwd(q, k, v, timestamps, padding_mask, pos_table, time_table, segment_ids,
         mpd):
    out = hstu_attention_pallas(
        q, k, v, timestamps, padding_mask, pos_table, time_table, mpd,
        segment_ids=segment_ids,
    )
    return out, (q, k, v, timestamps, padding_mask, pos_table, time_table,
                 segment_ids)


def _bwd(mpd, res, g):
    q, k, v, timestamps, padding_mask, pos_table, time_table, segment_ids = res
    dq, dk, dv, dpt, dtt = hstu_attention_bwd_pallas(
        q, k, v, timestamps, padding_mask, pos_table, time_table, g, mpd,
        segment_ids=segment_ids,
    )
    if dtt is None and time_table is not None:
        dtt = jnp.zeros_like(time_table)
    return dq, dk, dv, None, None, dpt, dtt, None


hstu_attention.defvjp(_fwd, _bwd)
