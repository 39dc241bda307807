"""Paged KV attention: the pure-JAX reference for the ragged decode path.

Ragged Paged Attention (PAPERS.md, arxiv 2604.15464) decouples decode KV
memory from the serving bucket a request landed in: K/V live in a global
page pool and each decode slot names its pages through a block-table
row, so HBM scales with the tokens actually resident, not with
``max_slots x max_history``.

ONE pool shape, made by ``zero_pool`` and nowhere else: ``(num_pages,
page_size, heads * head_dim)``, head-major features in the last axis.
It is the shape both device-side consumers already want — the kernel
reads a ``(1, page, H*hd)`` block a page, ``write_pages`` scatters whole
``(page, H*hd)`` pages — so a pool leaf passes from launch to launch in
the layout it is read and written in. The 4-D ``(..., heads, head_dim)``
form it replaced had minor axes ``(6, 64)``, which the TPU runtime stored
page-minor to avoid padding them to an ``(8, 128)`` tile; every prefill
and decode launch then paid whole-pool relayout copies in and out
(PERF.md section 6, PR 31). Nothing here reshapes a pool: heads and
head_dim come from the query.

This module is the gather/segment fallback (and the numerics contract)
for the Pallas kernel in ``kernels/paged_attention.py``: non-TPU
backends run these exact ops, and the kernel is pinned against them the
same way the HSTU kernel is pinned against its XLA reference.

Conventions shared by fallback and kernel:

- page 0 is the reserved NULL page: unused block-table entries point at
  it, prefill writes of padded tails land in it, and attention never
  reads it unmasked (every position >= ``seq_lens[s]`` scores -1e9);
- masked positions are FILLED with -1e9 and kept inside the softmax —
  the same additive-mask semantics as the dense decode paths, so
  ``exp(-1e9 - max)`` underflows to exactly 0 and paged == dense holds
  bit-for-bit up to float association;
- valid tokens must be a CONTIGUOUS prefix of the slot's pages (the
  serving layout; ``seq_lens`` is the only mask).

The stats form ``(acc, m, l)`` (unnormalized flash accumulator, running
max, running sum) exists so COBRA can merge the paged history scores
with its dense suffix-cache scores into ONE softmax — flash-attention's
merge identity makes the two-part softmax exactly equal to the dense
path's joint softmax over ``[history ++ suffix]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from genrec_tpu.ops.quant import QuantizedKVPool, quantize_symmetric

NEG = -1e9


def zero_pool(num_pages: int, page_size: int, n_heads: int, head_dim: int,
              dtype, kv_dtype: str = "float32"):
    """One layer's all-zero K or V pool: the one place the pool's shape
    is written down (module docstring). ``kv_dtype="int8"`` gives the
    quantized container, whose ``data`` leaf has the same shape."""
    shape = (num_pages, page_size, n_heads * head_dim)
    if kv_dtype == "int8":
        return QuantizedKVPool.zeros(shape)
    return jnp.zeros(shape, dtype)


def gather_pages(pool, block_tables: jax.Array) -> jax.Array:
    """(P, page, H*hd) pool + (S, Pm) block tables -> (S, Pm*page, H*hd)
    contiguous per-slot K or V (the fallback's materialized view).

    A ``QuantizedKVPool`` dequantizes AFTER the gather — only the
    gathered slot view is ever upcast to fp32, never the whole pool
    (the HLO property scripts/check_quant_hlo.py pins).
    """
    S, Pm = block_tables.shape
    _, page, HD = pool.shape
    if isinstance(pool, QuantizedKVPool):
        rows = pool.data[block_tables].astype(jnp.float32)  # (S, Pm, page, H*hd)
        out = rows * pool.scale[block_tables][..., None]
    else:
        out = pool[block_tables]  # (S, Pm, page, H*hd)
    return out.reshape(S, Pm * page, HD)


def write_pages(pool, block_tables: jax.Array, kv: jax.Array):
    """Scatter one layer's prefill K or V into its slots' pages.

    kv: (B, H, L, hd) — the (batch-major, head-split) layout the decode
    prefills produce. block_tables: (B, Pm) page ids per batch row; rows
    whose request occupies fewer than Pm pages pad with page 0, which
    absorbs the padded-tail writes harmlessly (never read unmasked).
    Requires L <= Pm * page_size (the engine sizes pages_per_slot off the
    largest history bucket, so this is a config invariant, asserted).

    The rows land head-major in the pool's merged last axis, ``(B, Pm,
    page, H*hd)``: ONE scatter of whole pages into the (donated) pool,
    and the only operation that touches it.

    A ``QuantizedKVPool`` quantizes HERE — per (page, position) row over
    heads x head_dim — so pages land already-int8 and their scales land
    at the same page index (COW shares and disagg gathers move both
    together for free).
    """
    B, H, L, hd = kv.shape
    page = pool.shape[1]
    Pm = block_tables.shape[1]
    cap = Pm * page
    if L > cap:
        raise ValueError(
            f"prefill KV of {L} tokens exceeds the {Pm} x {page} page "
            f"capacity of a slot's block-table row"
        )
    with jax.named_scope("kv_write"):
        rows = jnp.moveaxis(kv, 1, 2).reshape(B, L, H * hd)
        rows = jnp.pad(rows, ((0, 0), (0, cap - L), (0, 0)))
        rows = rows.reshape(B, Pm, page, H * hd)
        if isinstance(pool, QuantizedKVPool):
            data, scale = quantize_symmetric(rows, (-1,))  # scale (B, Pm, page)
            return QuantizedKVPool(
                pool.data.at[block_tables].set(data),
                pool.scale.at[block_tables].set(scale),
            )
        return pool.at[block_tables].set(rows.astype(pool.dtype))


def paged_attention_stats(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    seq_lens: jax.Array,
    use_kernel: bool | None = None,
):
    """Flash-stats attention of per-slot queries over paged K/V.

    q: (S, K, H, hd) — K beams per slot, all sharing the slot's pages
    (beam-sharing: a beam reorder never remaps pages, only the tiny
    dense suffix caches). Pools: (P, page, H*hd). block_tables: (S, Pm)
    int32. seq_lens: (S,) int32 valid-token counts.

    Returns (acc, m, l) all fp32: acc (S, K, H, hd) = sum_j exp(s_j - m)
    v_j, m (S, K, H) running max, l (S, K, H) = sum_j exp(s_j - m) —
    over ALL Pm*page positions with masked ones at -1e9 (see module
    docstring for why that matches the dense additive mask exactly).

    use_kernel: None resolves through kernels.policy.auto_paged_attention
    (TPU-only); True forces the Pallas kernel (off-TPU that needs
    `kernels.policy.interpret_mode`); False forces this pure-JAX gather. ``QuantizedKVPool`` pools route
    to the dequant-in-kernel twin (or the dequant-after-gather fallback)
    with identical (acc, m, l) semantics.
    """
    if use_kernel is None:
        from genrec_tpu.kernels.policy import auto_paged_attention

        use_kernel = auto_paged_attention()
    if use_kernel:
        if isinstance(k_pool, QuantizedKVPool):
            from genrec_tpu.kernels.paged_attention import (
                paged_attention_stats_pallas_quantized,
            )

            return paged_attention_stats_pallas_quantized(
                q, k_pool, v_pool, block_tables, seq_lens
            )
        from genrec_tpu.kernels.paged_attention import paged_attention_stats_pallas

        return paged_attention_stats_pallas(q, k_pool, v_pool, block_tables, seq_lens)
    return _stats_fallback(q, k_pool, v_pool, block_tables, seq_lens)


def _stats_fallback(q, k_pool, v_pool, block_tables, seq_lens):
    S, K, H, hd = q.shape
    k = gather_pages(k_pool, block_tables).reshape(S, -1, H, hd)  # (S, M, H, hd)
    v = gather_pages(v_pool, block_tables).reshape(S, -1, H, hd)
    M = k.shape[1]
    s = jnp.einsum("skhd,smhd->skhm", q, k).astype(jnp.float32) * (hd**-0.5)
    tok = jnp.arange(M)
    s = jnp.where(tok[None, None, None, :] >= seq_lens[:, None, None, None], NEG, s)
    m = s.max(axis=-1)  # (S, K, H)
    e = jnp.exp(s - m[..., None])
    l = e.sum(axis=-1)
    acc = jnp.einsum("skhm,smhd->skhd", e, v.astype(jnp.float32))
    return acc, m, l


def tree_suffix_stats(q, vc_k, vc_v, node_steps):
    """Flash stats of per-NODE queries over per-node virtual suffix
    caches with the tree-causal mask — the speculative-decode twin of
    the dense suffix partial in COBRA's paged suffix step.

    q: (S, N, H, hd) — one query per tree node (N replaces the beam
    axis). vc_k/vc_v: (S, N, Sc, H, hd) — each node's virtual cache
    (committed beam cache + ancestor K/V, ops/spec_tree.
    tree_virtual_cache). node_steps: (S, N) — the node's own cache slot;
    positions past it (other branches, garbage tail) score -1e9 inside
    the softmax, the same additive-mask semantics as the plain step, so
    an accepted path's stats are bitwise the plain step's.

    Returns (acc, m, l) fp32, mergeable through `merge_attention_stats`
    with the paged-history partial exactly like the plain suffix step.
    """
    hd = q.shape[-1]
    Sc = vc_k.shape[2]
    s = jnp.einsum("bkhd,bkshd->bkhs", q, vc_k).astype(jnp.float32) * (hd**-0.5)
    s = jnp.where(
        jnp.arange(Sc)[None, None, None, :] > node_steps[:, :, None, None],
        NEG, s,
    )
    m = s.max(axis=-1)
    e = jnp.exp(s - m[..., None])
    l = e.sum(axis=-1)
    acc = jnp.einsum("bkhs,bkshd->bkhd", e, vc_v.astype(jnp.float32))
    return acc, m, l


def merge_attention_stats(acc_a, m_a, l_a, acc_b, m_b, l_b):
    """Combine two flash partials into the jointly-softmaxed output.

    Exactly softmax(concat(scores_a, scores_b)) @ concat(values) up to
    float association — the COBRA paged suffix step merges its paged
    history partial with its dense suffix partial through this.
    """
    m = jnp.maximum(m_a, m_b)
    ca = jnp.exp(m_a - m)
    cb = jnp.exp(m_b - m)
    l = l_a * ca + l_b * cb
    acc = acc_a * ca[..., None] + acc_b * cb[..., None]
    return acc / jnp.maximum(l, 1e-30)[..., None]


def paged_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    seq_lens: jax.Array,
    use_kernel: bool | None = None,
) -> jax.Array:
    """Normalized paged attention output, (S, K, H, hd) in q's dtype.

    The full-softmax form (TIGER's cross-attention — no suffix to merge
    with): out = acc / l from the stats primitive.
    """
    # `kv_attend`, not a name with `paged` in it: see the kernel's `name=`.
    with jax.named_scope("kv_attend"):
        acc, _, l = paged_attention_stats(
            q, k_pool, v_pool, block_tables, seq_lens, use_kernel=use_kernel
        )
        return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
