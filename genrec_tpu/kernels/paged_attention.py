"""Pallas paged decode-attention kernel (Ragged Paged Attention,
arxiv 2604.15464).

One decode step reads K/V straight from the page pool through the
per-slot block table — the gathered (S, pages*page_size, H*hd)
contiguous copy the pure-JAX fallback materializes (ops/paged.py) never
exists in HBM. The grid walks (slot, page); the block table and sequence
lengths ride as SCALAR-PREFETCH operands so each page's index_map can
resolve its pool row before the kernel body runs, and the softmax
accumulates flash-style across the sequentially-executed page axis
(running max / sum / unnormalized accumulator in revisited output
blocks, the same accumulation discipline as the HSTU backward kernel).

Layout. The pool is stored ``(P, page, H*hd)`` (``ops.paged.zero_pool``)
and the kernel reads it AS STORED, with no reshape: one K/V block is a
whole page of every head, ``(page, H*hd)``: sublanes carry the page,
lanes carry head-major features, and both block dims equal the array's
(Mosaic's rule for the last two block dims). H and hd come from the
query; why the pool has no separate head axis is in ``ops/paged.py``'s
module docstring. All heads share one MXU call per page through a
BLOCK-DIAGONAL query: row ``h*Kp + k`` of the ``(H*Kp, H*hd)`` query
holds beam k's head-h query in lanes ``[h*hd, (h+1)*hd)`` and zeros
elsewhere, so ``q_bd @ k_page.T`` is exactly the per-head scores. The PV
product yields every (query head, value head) pair; the wrapper keeps the
diagonal. Decode attention is bandwidth-bound and tiny (H*Kp ~ 100
rows), so the H-fold MXU over-compute costs less than H grid steps did.

Numerics contract == ops/paged.py `_stats_fallback`: masked positions
(token index >= seq_len) are FILLED with -1e9 and stay inside the
softmax, so paged == dense parity survives the kernel path too (pinned
in tests/test_kv_pool.py the way test_hstu_kernel pins the HSTU kernel
against its XLA reference).

``page_size`` must be a multiple of 8 (the sublane dimension of the K/V
blocks). `kernels.policy.auto_paged_attention` gates the kernel in on
TPU; interpret mode is the caller's to ask for (`policy.interpret_mode`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from genrec_tpu.kernels.policy import resolve_interpret

NEG = -1e9

_NT = (((1,), (1,)), ((), ()))  # a @ b.T without materializing b.T


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _flash_update(scores, p, s, sl_ref, acc_ref, m_ref, l_ref, *, page):
    """Mask one page's (R, page) scores and fold them into the running
    (m, l) blocks; returns the page's softmax weights ``e`` and the
    rescale ``corr`` the caller applies to its accumulator. m and l live
    lane-replicated in (R, 128) blocks: a lane-1 output block is not
    tileable, so every lane carries the row's value."""

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
        # -1e9 start: a fully-masked slot keeps m == -1e9, so every masked
        # score contributes exp(0) == 1 — the fallback's exact behavior
        # (and the dense paths': -1e9 additive fill, not exclusion).
        m_ref[...] = jnp.full(m_ref.shape, NEG, m_ref.dtype)
        l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)

    tok = p * page + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(tok >= sl_ref[s], NEG, scores)

    m_prev = m_ref[0]  # (R, 128)
    m_new = jnp.maximum(m_prev, scores.max(axis=1, keepdims=True))
    e = jnp.exp(scores - m_new[:, :1])  # (R, page)
    corr = jnp.exp(m_prev - m_new)  # (R, 128), lane-replicated
    l_ref[0] = l_ref[0] * corr + e.sum(axis=1, keepdims=True)
    m_ref[0] = m_new
    return e, corr


def _kernel(bt_ref, sl_ref, q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
            *, page: int, scale: float):
    s = pl.program_id(0)
    p = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)  # (R, H*hd) block-diagonal
    k = k_ref[0].astype(jnp.float32)  # (page, H*hd)
    v = v_ref[0].astype(jnp.float32)
    scores = jax.lax.dot_general(
        q, k, _NT, preferred_element_type=jnp.float32
    ) * scale  # (R, page)
    e, corr = _flash_update(scores, p, s, sl_ref, acc_ref, m_ref, l_ref,
                            page=page)
    acc_ref[0] = acc_ref[0] * corr[:, :1] + jnp.dot(
        e, v, preferred_element_type=jnp.float32
    )


def _kernel_quant(bt_ref, sl_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
                  acc_ref, m_ref, l_ref, *, page: int, scale: float):
    """Dequant-in-kernel twin of ``_kernel``: K/V blocks arrive int8 with
    one fp32 scale per page row, delivered as a (1, page) lane-major row.
    A row's scale is common to all its heads, so it factors out of both
    dots and is applied to the small (R, page) score / weight matrices
    instead of the (page, H*hd) blocks — the pool is never upcast outside
    the kernel, and inside it only through the int8 -> fp32 cast the MXU
    operands need."""
    s = pl.program_id(0)
    p = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)  # (page, H*hd), unscaled
    v = v_ref[0].astype(jnp.float32)
    scores = jax.lax.dot_general(
        q, k, _NT, preferred_element_type=jnp.float32
    ) * (ks_ref[0] * scale)  # (R, page) * (1, page)
    e, corr = _flash_update(scores, p, s, sl_ref, acc_ref, m_ref, l_ref,
                            page=page)
    acc_ref[0] = acc_ref[0] * corr[:, :1] + jnp.dot(
        e * vs_ref[0], v, preferred_element_type=jnp.float32
    )


def _block_diag_queries(q, Kp: int):
    """(S, K, H, hd) -> (S, H*Kp, H*hd): row h*Kp + k carries beam k's
    head-h query in lanes [h*hd, (h+1)*hd), zeros elsewhere. Built with a
    select, not a matmul against an identity: the TPU's default matmul
    precision would round the queries to bf16."""
    S, K, H, hd = q.shape
    qp = jnp.pad(q, ((0, 0), (0, Kp - K), (0, 0), (0, 0)))
    qp = qp.transpose(0, 2, 1, 3)  # (S, H, Kp, hd)
    same_head = jnp.eye(H, dtype=bool)[None, :, None, :, None]
    qbd = jnp.where(same_head, qp[:, :, :, None, :], 0)  # (S, H, Kp, H, hd)
    return qbd.reshape(S, H * Kp, H * hd)


def _paged_call(kernel, q, pool_operands, pool_specs, block_tables, seq_lens,
                page: int, interpret: bool, name: str):
    """Shared pallas_call of the fp32 and int8 kernels: block-diagonal
    queries in, per-head (acc, m, l) stats out. ``name`` is the kernel's
    own on a device profile; it alone may hold the word `paged`, by
    which a profile reader finds the kernel's custom calls (an enclosing
    scope with that word would claim every other custom call inside it)."""
    S, K, H, hd = q.shape
    Pm = block_tables.shape[1]
    Kp = _round_up(K, 8)
    R, HD = H * Kp, H * hd
    # K(beam) padding rows produce garbage stats that are sliced away below.
    qbd = _block_diag_queries(q, Kp)

    row_spec = lambda lanes: pl.BlockSpec(  # noqa: E731
        (1, R, lanes), lambda s, p, bt, sl: (s, 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, Pm),
        in_specs=[row_spec(HD), *pool_specs],
        out_specs=[row_spec(HD), row_spec(128), row_spec(128)],
    )
    acc, m, l = pl.pallas_call(
        functools.partial(kernel, page=page, scale=hd**-0.5),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((S, R, HD), jnp.float32),
            jax.ShapeDtypeStruct((S, R, 128), jnp.float32),
            jax.ShapeDtypeStruct((S, R, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name=name,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32), qbd,
      *pool_operands)

    # acc holds every (query head, value head) pair; keep the diagonal.
    acc = jnp.diagonal(acc.reshape(S, H, Kp, H, hd), axis1=1, axis2=3)
    acc = acc.transpose(0, 1, 3, 2)[:, :K]  # (S, Kp, hd, H) -> (S, K, H, hd)
    m = m[:, :, 0].reshape(S, H, Kp).transpose(0, 2, 1)[:, :K]  # (S, K, H)
    l = l[:, :, 0].reshape(S, H, Kp).transpose(0, 2, 1)[:, :K]
    return acc, m, l


def _check_page(page: int) -> None:
    if page % 8 != 0:
        raise ValueError(f"page_size {page} must be a multiple of 8 (sublanes)")


# The paged read: index_map resolves the pool row from the prefetched
# block table — page bt[s, p], every head of it.
_page_index = lambda s, p, bt, sl: (bt[s, p], 0, 0)  # noqa: E731


def paged_attention_stats_pallas_quantized(q, k_pool, v_pool, block_tables,
                                           seq_lens, interpret: bool = False):
    """Quantized-pool kernel path: pools are ``ops.quant.QuantizedKVPool``
    (int8 data (P, page, H*hd) + fp32 scale (P, page)); the per-page-row
    scales ride as their own (1, page) blocks resolved through the same
    block-table index_map, and dequantization happens inside the kernel
    body. Same (acc, m, l) contract as the fp32 twin, pinned against the
    dequant-after-gather fallback in tests/test_quantized.py.
    """
    _, page, HD = k_pool.data.shape
    _check_page(page)
    interpret = resolve_interpret(interpret, "paged_attention[int8]")
    data_spec = pl.BlockSpec((1, page, HD), _page_index)
    scale_spec = pl.BlockSpec((1, 1, page), _page_index)
    return _paged_call(
        _kernel_quant, q,
        (k_pool.data, k_pool.scale[:, None, :],
         v_pool.data, v_pool.scale[:, None, :]),
        (data_spec, scale_spec, data_spec, scale_spec),
        block_tables, seq_lens, page, interpret, "paged_attention_int8",
    )


def paged_attention_stats_pallas(q, k_pool, v_pool, block_tables, seq_lens,
                                 interpret: bool = False):
    """Kernel twin of ops/paged.py `_stats_fallback`: (acc, m, l) fp32.

    q (S, K, H, hd); pools (P, page, H*hd); block_tables (S, Pm) int32;
    seq_lens (S,) int32.
    """
    _, page, HD = k_pool.shape
    _check_page(page)
    interpret = resolve_interpret(interpret, "paged_attention")
    spec = pl.BlockSpec((1, page, HD), _page_index)
    return _paged_call(
        _kernel, q, (k_pool, v_pool),
        (spec, spec), block_tables, seq_lens, page, interpret,
        "paged_attention",
    )
