"""COBRA: cascaded sparse-dense generative recommendation (arXiv:2503.02453).

Parity target: reference genrec/models/cobra.py — interleaved C sparse
codebook tokens + 1 dense text vector per item (CobraEmbedding :47-147,
interleave_seq_mask :323-377), causal post-norm TransformerDecoder used
decoder-only (:150-224; torch's cross-attention over an EMPTY memory
contributes zero but its LayerNorm still applies — replicated), per-
codebook heads with position-shifted supervision (codebook 0 predicted
from the dense position, codebook c>0 from the previous codebook position,
:417-457), dense in-batch InfoNCE masked by same-sequence (:466-495),
codebook-entropy / per-codebook-accuracy metrics (:510-517), beam-search
`generate` re-running the decoder per codebook step (:531-665), and
`beam_fusion` = beam candidates + dense nearest-neighbour with
alpha-blended scores (:679-760).

TPU redesign:
- the reference's scatter-based interleave becomes a static
  reshape: (B, T, C, D) sparse ++ (B, T, 1, D) dense -> (B, T*(C+1), D) —
  no scatter, no dynamic shapes (SURVEY.md §7 build item 8);
- the dense-InfoNCE boolean compression (cobra.py:478-479) becomes
  where-masking with a valid-row denominator — static shapes under jit;
- generation is deterministic top-k beam search, jit-friendly (static
  loop, static shapes per step). The default cached engine runs the
  decoder over the dense user-history positions ONCE per eval batch
  (`decode_prefill`, KV cached per layer at batch size B), then advances
  only the sem-id suffix per codebook step (`decode_suffix_step`) with
  the B*K beams resolved by einsum against the shared history K/V —
  O(B*T^2 + C*B*K*T) instead of the uncached O(C*B*K*T^2) full
  re-decodes (still available via use_cache=False; parity pinned by
  tests/test_decode_cache.py).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from genrec_tpu.ops.losses import cross_entropy_with_ignore
from genrec_tpu.ops.normalize import l2norm

_NEG_SIM = -1e4


class CobraOutput(NamedTuple):
    loss: jax.Array
    loss_sparse: jax.Array
    loss_dense: jax.Array
    acc_correct: jax.Array
    acc_total: jax.Array
    recall_correct: jax.Array
    recall_total: jax.Array
    vec_cos_sim: jax.Array
    codebook_entropy: jax.Array


class CobraGenerationOutput(NamedTuple):
    sem_ids: jax.Array  # (B, K, C)
    dense_vecs: jax.Array  # (B, K, D)
    scores: jax.Array  # (B, K)


class BeamFusionOutput(NamedTuple):
    item_ids: jax.Array  # (B, K)
    sem_ids: jax.Array  # (B, K, C)
    scores: jax.Array  # (B, K)


class LightT5Encoder(nn.Module):
    """Random-init text encoder: embed + post-norm transformer encoder,
    mean-pool, project, L2-normalize (reference encoder.py:15-106)."""

    n_layers: int = 1
    hidden_dim: int = 768
    output_dim: int = 768
    num_heads: int = 8
    ff_dim: int = 2048
    vocab_size: int = 32128
    max_seq_len: int = 512
    dropout: float = 0.1
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, batch_tokens, deterministic: bool = True):
        orig_3d = batch_tokens.ndim == 3
        if orig_3d:
            B, T, L = batch_tokens.shape
            flat = batch_tokens.reshape(B * T, L)
        else:
            flat = batch_tokens
            L = flat.shape[1]

        emb = self.param(
            "embedding", nn.initializers.normal(1.0), (self.vocab_size, self.hidden_dim)
        )
        pos = self.param(
            "pos_embedding", nn.initializers.normal(1.0), (self.max_seq_len, self.hidden_dim)
        )
        x = emb[flat].astype(self.dtype) + pos[None, :L].astype(self.dtype)
        pad = flat == 0

        for i in range(self.n_layers):
            x = _PostNormEncoderLayer(
                self.hidden_dim, self.num_heads, self.ff_dim, self.dropout,
                dtype=self.dtype, name=f"layer_{i}",
            )(x, pad, deterministic)
        x = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32, name="layer_norm")(x)

        mask = (~pad)[..., None].astype(jnp.float32)
        pooled = (x * mask).sum(axis=1) / jnp.maximum(mask.sum(axis=1), 1e-9)
        projected = nn.Dense(self.output_dim, dtype=self.dtype, name="proj")(pooled)
        out = l2norm(projected)
        if orig_3d:
            out = out.reshape(B, T, -1)
        return out


class _TorchMHA(nn.Module):
    """torch.nn.MultiheadAttention-equivalent self-attention (packed qkv
    projection with bias, output projection with bias, scaled dot product)."""

    dim: int
    num_heads: int
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        self.in_proj = nn.Dense(3 * self.dim, dtype=self.dtype, name="in_proj")
        self.out_proj = nn.Dense(self.dim, dtype=self.dtype, name="out_proj")
        self.attn_drop = nn.Dropout(self.dropout)

    def __call__(self, x, attn_mask=None, key_padding_mask=None, deterministic=True):
        out, _ = self._full(x, attn_mask, key_padding_mask, deterministic)
        return out

    def prefill(self, x, attn_mask=None, key_padding_mask=None):
        """Full forward that also returns (k, v) each (B, H, L, hd) for the
        incremental-decode cache."""
        return self._full(x, attn_mask, key_padding_mask, True)

    def _full(self, x, attn_mask, key_padding_mask, deterministic):
        B, L, D = x.shape
        H, hd = self.num_heads, D // self.num_heads
        qkv = self.in_proj(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        split = lambda t: t.reshape(B, L, H, hd).transpose(0, 2, 1, 3)
        q, k, v = split(q), split(k), split(v)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * (hd**-0.5)
        # Finite fill, NOT -inf: fully-masked rows (padded queries) would
        # otherwise produce NaN through the softmax GRADIENT, and NaN*0
        # poisons the whole loss even though those rows are excluded from
        # it. With -1e9 dead rows get uniform attention; their outputs only
        # feed positions the losses mask out, and for live rows
        # exp(-1e9 - max) underflows to exactly 0 — same result as -inf.
        if attn_mask is not None:
            scores = jnp.where(attn_mask[None, None], -1e9, scores)
        if key_padding_mask is not None:
            scores = jnp.where(key_padding_mask[:, None, None, :], -1e9, scores)
        attn = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        attn = self.attn_drop(attn, deterministic=deterministic)
        out = jnp.einsum("bhqk,bhkd->bhqd", attn, v)
        out = out.transpose(0, 2, 1, 3).reshape(B, L, D)
        return self.out_proj(out), (k, v)

    def decode_tree(self, x, k_pool, v_pool, block_tables, seq_lens, cache,
                    topo, base_steps):
        """Speculative tree-verification twin of `decode_paged`: one
        parallel pass over every candidate-tree node (N replaces the
        beam axis; ops/spec_tree.py holds the topology tables).

        The paged-history partial is the same `paged_attention_stats`
        read (nodes of a slot share its pages like beams do); the dense
        suffix partial runs over each node's VIRTUAL cache — the
        committed beam cache with ancestor K/V from this pass overlaid
        at the speculated slots — through `ops.paged.tree_suffix_stats`,
        whose score/mask/merge ops are the plain step's, so an accepted
        path's output is bitwise the sequential steps'. The committed
        ``cache`` is read, never written: a rejected branch leaves no
        trace. Returns (out, (k_new, v_new) per-node projections).
        """
        from genrec_tpu.ops.paged import (
            merge_attention_stats,
            paged_attention_stats,
            tree_suffix_stats,
        )
        from genrec_tpu.ops.spec_tree import tree_virtual_cache

        B, N, D = x.shape
        H, hd = self.num_heads, D // self.num_heads
        q, k_new, v_new = jnp.split(self.in_proj(x), 3, axis=-1)
        q = q.reshape(B, N, H, hd)
        k_new = k_new.reshape(B, N, H, hd)
        v_new = v_new.reshape(B, N, H, hd)
        vc_k = tree_virtual_cache(cache["k"], k_new, topo, base_steps)
        vc_v = tree_virtual_cache(cache["v"], v_new, topo, base_steps)
        acc_h, m_h, l_h = paged_attention_stats(
            q, k_pool, v_pool, block_tables, seq_lens
        )
        node_slots = base_steps[:, None] + jnp.asarray(topo.level)[None, :]
        acc_s, m_s, l_s = tree_suffix_stats(q, vc_k, vc_v, node_slots)
        out = merge_attention_stats(acc_h, m_h, l_h, acc_s, m_s, l_s)
        out = out.astype(x.dtype).reshape(B, N, D)
        return self.out_proj(out), (k_new, v_new)

    def decode_paged(self, x, k_pool, v_pool, block_tables, seq_lens, cache,
                     steps):
        """`decode` with PAGED history K/V and a per-row suffix slot.

        The history keys live in the shared page pool (read through each
        row's block-table entries, positions >= seq_lens masked); the
        suffix cache stays dense per beam and is written at the per-row
        ``steps`` slot. The paged history partial and the dense suffix
        partial merge through the flash identity into EXACTLY the dense
        path's joint softmax over [history ++ suffix].
        """
        from genrec_tpu.ops.paged import merge_attention_stats, paged_attention_stats

        B, K, D = x.shape
        H, hd = self.num_heads, D // self.num_heads
        q, k_new, v_new = jnp.split(self.in_proj(x), 3, axis=-1)
        q = q.reshape(B, K, H, hd)
        S = cache["k"].shape[2]
        hit = (jnp.arange(S)[None, :] == steps[:, None])[:, None, :, None, None]
        ck = jnp.where(hit, k_new.reshape(B, K, 1, H, hd), cache["k"])
        cv = jnp.where(hit, v_new.reshape(B, K, 1, H, hd), cache["v"])
        acc_h, m_h, l_h = paged_attention_stats(
            q, k_pool, v_pool, block_tables, seq_lens
        )
        s_suf = jnp.einsum("bkhd,bkshd->bkhs", q, ck).astype(jnp.float32) * (hd**-0.5)
        s_suf = jnp.where(
            jnp.arange(S)[None, None, None, :] > steps[:, None, None, None],
            -1e9, s_suf,
        )
        m_s = s_suf.max(axis=-1)
        e = jnp.exp(s_suf - m_s[..., None])
        l_s = e.sum(axis=-1)
        acc_s = jnp.einsum("bkhs,bkshd->bkhd", e, cv.astype(jnp.float32))
        out = merge_attention_stats(acc_h, m_h, l_h, acc_s, m_s, l_s)
        out = out.astype(x.dtype).reshape(B, K, D)
        return self.out_proj(out), {"k": ck, "v": cv}

    def decode(self, x, hist_kv, hist_pad, cache, slot: int):
        """One suffix position for K beams against the shared history K/V.

        x: (B, K, dim). hist_kv: (k, v) each (B, H, Lh, hd) — batch-sized,
        never expanded to B*K. hist_pad: (B, Lh) True = padding.
        cache {"k","v"}: (B, K, S, H, hd) suffix cache written at ``slot``
        (static). Scores over [history ++ suffix] concatenated in the same
        key order as the full forward, softmaxed jointly in fp32.
        """
        B, K, D = x.shape
        H, hd = self.num_heads, D // self.num_heads
        q, k_new, v_new = jnp.split(self.in_proj(x), 3, axis=-1)
        q = q.reshape(B, K, H, hd)
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k_new.reshape(B, K, 1, H, hd), (0, 0, slot, 0, 0)
        )
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v_new.reshape(B, K, 1, H, hd), (0, 0, slot, 0, 0)
        )
        hk, hv = hist_kv
        Lh, S = hk.shape[2], ck.shape[2]
        s_hist = jnp.einsum("bkhd,bhmd->bkhm", q, hk).astype(jnp.float32) * (hd**-0.5)
        s_hist = jnp.where(hist_pad[:, None, None, :], -1e9, s_hist)
        s_suf = jnp.einsum("bkhd,bkshd->bkhs", q, ck).astype(jnp.float32) * (hd**-0.5)
        s_suf = jnp.where(jnp.arange(S)[None, None, None, :] > slot, -1e9, s_suf)
        attn = jax.nn.softmax(
            jnp.concatenate([s_hist, s_suf], axis=-1), axis=-1
        ).astype(x.dtype)
        out = (
            jnp.einsum("bkhm,bhmd->bkhd", attn[..., :Lh], hv)
            + jnp.einsum("bkhs,bkshd->bkhd", attn[..., Lh:], cv)
        ).reshape(B, K, D)
        return self.out_proj(out), {"k": ck, "v": cv}


class _PostNormEncoderLayer(nn.Module):
    """torch nn.TransformerEncoderLayer (norm_first=False, relu)."""

    dim: int
    num_heads: int
    ff_dim: int
    dropout: float
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, key_padding_mask, deterministic):
        h = _TorchMHA(self.dim, self.num_heads, self.dropout, self.dtype, name="self_attn")(
            x, key_padding_mask=key_padding_mask, deterministic=deterministic
        )
        x = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32, name="norm1")(
            x + nn.Dropout(self.dropout)(h, deterministic=deterministic)
        ).astype(x.dtype)
        h = nn.Dense(self.ff_dim, dtype=self.dtype, name="linear1")(x)
        h = nn.Dropout(self.dropout)(nn.relu(h), deterministic=deterministic)
        h = nn.Dense(self.dim, dtype=self.dtype, name="linear2")(h)
        x = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32, name="norm2")(
            x + nn.Dropout(self.dropout)(h, deterministic=deterministic)
        ).astype(x.dtype)
        return x


class _PostNormDecoderLayer(nn.Module):
    """torch nn.TransformerDecoderLayer with EMPTY memory: the cross-attn
    term contributes zero but its add&norm still applies (cobra.py:205-216)."""

    dim: int
    num_heads: int
    ff_dim: int
    dropout: float
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        self.self_attn = _TorchMHA(
            self.dim, self.num_heads, self.dropout, self.dtype, name="self_attn"
        )
        self.norm1 = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32, name="norm1")
        self.norm2 = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32, name="norm2")
        self.norm3 = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32, name="norm3")
        self.linear1 = nn.Dense(self.ff_dim, dtype=self.dtype, name="linear1")
        self.linear2 = nn.Dense(self.dim, dtype=self.dtype, name="linear2")
        self.drop1 = nn.Dropout(self.dropout)
        self.drop2 = nn.Dropout(self.dropout)
        self.drop3 = nn.Dropout(self.dropout)

    def __call__(self, x, attn_mask, key_padding_mask, deterministic):
        h = self.self_attn(
            x, attn_mask=attn_mask, key_padding_mask=key_padding_mask,
            deterministic=deterministic,
        )
        return self._post_attn(x, h, deterministic)

    def _post_attn(self, x, h, deterministic):
        x = self.norm1(
            x + self.drop1(h, deterministic=deterministic)
        ).astype(x.dtype)
        # Cross-attention over empty memory == +0, then norm2. The (unused)
        # cross projection params still exist in torch; they are omitted
        # here deliberately — they receive no gradient either way.
        x = self.norm2(x).astype(x.dtype)
        h = self.linear1(x)
        h = self.drop2(nn.relu(h), deterministic=deterministic)
        h = self.linear2(h)
        x = self.norm3(
            x + self.drop3(h, deterministic=deterministic)
        ).astype(x.dtype)
        return x

    def prefill(self, x, attn_mask, key_padding_mask):
        h, kv = self.self_attn.prefill(
            x, attn_mask=attn_mask, key_padding_mask=key_padding_mask
        )
        return self._post_attn(x, h, True), kv

    def decode(self, x, hist_kv, hist_pad, cache, slot: int):
        h, new_cache = self.self_attn.decode(x, hist_kv, hist_pad, cache, slot)
        return self._post_attn(x, h, True), new_cache

    def decode_paged(self, x, k_pool, v_pool, block_tables, seq_lens, cache,
                     steps):
        h, new_cache = self.self_attn.decode_paged(
            x, k_pool, v_pool, block_tables, seq_lens, cache, steps
        )
        return self._post_attn(x, h, True), new_cache

    def decode_tree(self, x, k_pool, v_pool, block_tables, seq_lens, cache,
                    topo, base_steps):
        h, kv = self.self_attn.decode_tree(
            x, k_pool, v_pool, block_tables, seq_lens, cache, topo, base_steps
        )
        return self._post_attn(x, h, True), kv


class CobraDecoder(nn.Module):
    hidden_dim: int = 768
    n_layers: int = 6
    n_heads: int = 12
    ff_dim: int = 2048
    dropout: float = 0.1
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        self.layers = [
            _PostNormDecoderLayer(
                self.hidden_dim, self.n_heads, self.ff_dim, self.dropout,
                dtype=self.dtype, name=f"layer_{i}",
            )
            for i in range(self.n_layers)
        ]

    def __call__(self, tgt, tgt_key_padding_mask=None, deterministic=True):
        L = tgt.shape[1]
        causal = jnp.triu(jnp.ones((L, L), bool), k=1)
        x = tgt
        for layer in self.layers:
            x = layer(x, causal, tgt_key_padding_mask, deterministic)
        return x

    def prefill(self, tgt, tgt_key_padding_mask=None):
        """Forward over the history once, returning per-layer (k, v)."""
        L = tgt.shape[1]
        causal = jnp.triu(jnp.ones((L, L), bool), k=1)
        x, kvs = tgt, []
        for layer in self.layers:
            x, kv = layer.prefill(x, causal, tgt_key_padding_mask)
            kvs.append(kv)
        return x, kvs

    def decode(self, x, hist_kvs, hist_pad, caches, slot: int):
        """Advance one suffix position for K beams: x (B, K, dim)."""
        new_caches = []
        for layer, hkv, cache in zip(self.layers, hist_kvs, caches):
            x, nc = layer.decode(x, hkv, hist_pad, cache, slot)
            new_caches.append(nc)
        return x, new_caches

    def decode_paged(self, x, k_pools, v_pools, block_tables, seq_lens,
                     caches, steps):
        """`decode` with the per-layer history K/V read from page pools
        and a per-row suffix slot (slot-level continuous batching)."""
        new_caches = []
        for layer, kp, vp, cache in zip(self.layers, k_pools, v_pools, caches):
            x, nc = layer.decode_paged(
                x, kp, vp, block_tables, seq_lens, cache, steps
            )
            new_caches.append(nc)
        return x, new_caches

    def decode_tree(self, x, k_pools, v_pools, block_tables, seq_lens,
                    caches, topo, base_steps):
        """One parallel verification pass over every tree node, all
        layers: x (B, N, dim) -> (out, per-layer (k_new, v_new))."""
        node_kvs = []
        for layer, kp, vp, cache in zip(self.layers, k_pools, v_pools, caches):
            x, kv = layer.decode_tree(
                x, kp, vp, block_tables, seq_lens, cache, topo, base_steps
            )
            node_kvs.append(kv)
        return x, node_kvs


class CobraEmbedding(nn.Module):
    """Interleave C sparse codebook embeddings + 1 dense vector per item.

    Static-reshape interleave instead of the reference's scatter loop.
    """

    id_vocab_size: int
    n_codebooks: int = 3
    d_model: int = 768
    max_len: int = 1024
    dtype: jnp.dtype = jnp.float32

    @property
    def pad_id(self) -> int:
        return self.id_vocab_size * self.n_codebooks

    def setup(self):
        self.id_embed = self.param(
            "id_embed", nn.initializers.normal(1.0),
            (self.id_vocab_size * self.n_codebooks + 1, self.d_model),
        )
        self.type_embed = self.param(
            "type_embed", nn.initializers.normal(1.0), (2, self.d_model)
        )
        self.pos_embed = self.param(
            "pos_embed", nn.initializers.normal(1.0), (self.max_len, self.d_model)
        )

    def __call__(self, input_ids, input_vecs, mask, n_complete_items: Optional[int] = None):
        """input_ids (B, L), input_vecs (B, T, D), mask (B, L + T_complete)."""
        B, L = input_ids.shape
        C = self.n_codebooks
        T_vecs = input_vecs.shape[1]
        if n_complete_items is None:
            n_complete_items = L // C
        n_complete_tokens = n_complete_items * C

        token_type = jnp.arange(L) % C
        is_pad = input_ids == self.pad_id
        offset_ids = jnp.where(is_pad, input_ids, input_ids + token_type[None] * self.id_vocab_size)
        sparse = self.id_embed[offset_ids].astype(self.dtype)
        # Pad row is the last table row; torch padding_idx pins it to zero.
        sparse = jnp.where(is_pad[..., None], 0.0, sparse)

        chunks = []
        if n_complete_tokens > 0:
            comp = sparse[:, :n_complete_tokens].reshape(B, n_complete_items, C, -1)
            dense = input_vecs[:, :n_complete_items, None, :].astype(self.dtype)
            inter = jnp.concatenate([comp, dense], axis=2)  # (B, T, C+1, D)
            chunks.append(inter.reshape(B, n_complete_items * (C + 1), -1))
        if L - n_complete_tokens > 0:
            chunks.append(sparse[:, n_complete_tokens:])
        h = jnp.concatenate(chunks, axis=1) if len(chunks) > 1 else chunks[0]

        out_len = h.shape[1]
        type_row = jnp.concatenate(
            [
                jnp.tile(jnp.concatenate([jnp.zeros(C, jnp.int32), jnp.ones(1, jnp.int32)]), n_complete_items),
                jnp.zeros(L - n_complete_tokens, jnp.int32),
            ]
        )[:out_len]
        m = mask[..., None].astype(self.dtype)
        h = h * m
        h = h + self.pos_embed[None, :out_len].astype(self.dtype) * m
        h = h + self.type_embed[type_row][None].astype(self.dtype) * m
        return h

    def suffix_token(self, tok, slot: int, base_pos: int):
        """Embed ONE generated sem-id token per beam: tok (B, K) ints at
        suffix position ``slot`` (absolute position base_pos + slot).
        Matches __call__'s layout for appended sparse tokens: codebook
        offset slot % C, sparse type row, never padding."""
        offset = tok + (slot % self.n_codebooks) * self.id_vocab_size
        h = self.id_embed[offset].astype(self.dtype)
        h = h + self.pos_embed[base_pos + slot].astype(self.dtype)
        h = h + self.type_embed[0].astype(self.dtype)
        return h

    def suffix_token_ragged(self, tok, steps, base_pos):
        """`suffix_token` with per-row suffix slots AND per-row base
        positions: tok (B, K), steps (B,), base_pos (B,) — each row embeds
        its token at ITS history end (continuous batching mixes rows whose
        histories ended at different absolute positions)."""
        offset = tok + (steps[:, None] % self.n_codebooks) * self.id_vocab_size
        h = self.id_embed[offset].astype(self.dtype)
        pos = jnp.clip(base_pos + steps, 0, self.max_len - 1)
        h = h + self.pos_embed[pos][:, None].astype(self.dtype)
        h = h + self.type_embed[0].astype(self.dtype)
        return h

    def suffix_token_tree(self, tok, node_slots, base_pos):
        """`suffix_token_ragged` with PER-NODE suffix slots: tok (B, N),
        node_slots (B, N) — each candidate-tree node embeds its drafted
        token at its own speculated position (same per-element math, so
        an accepted node's embedding is bitwise the plain step's)."""
        offset = tok + (node_slots % self.n_codebooks) * self.id_vocab_size
        h = self.id_embed[offset].astype(self.dtype)
        pos = jnp.clip(base_pos[:, None] + node_slots, 0, self.max_len - 1)
        h = h + self.pos_embed[pos].astype(self.dtype)
        h = h + self.type_embed[0].astype(self.dtype)
        return h


def interleave_seq_mask(seq_mask, C: int, n_complete_items: Optional[int] = None):
    """(B, L) sparse mask -> (B, L + T_complete) with the dense slot after
    each complete item carrying that item's last-sparse-token mask."""
    B, L = seq_mask.shape
    if n_complete_items is None:
        n_complete_items = L // C
    n_complete_tokens = n_complete_items * C
    parts = []
    if n_complete_tokens > 0:
        comp = seq_mask[:, :n_complete_tokens].reshape(B, n_complete_items, C)
        dense = comp[:, :, C - 1 : C]  # mask of last sparse token
        parts.append(jnp.concatenate([comp, dense], axis=2).reshape(B, -1))
    if L - n_complete_tokens > 0:
        parts.append(seq_mask[:, n_complete_tokens:])
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]


class Cobra(nn.Module):
    encoder_n_layers: int = 1
    encoder_hidden_dim: int = 768
    encoder_num_heads: int = 8
    encoder_vocab_size: int = 32128
    id_vocab_size: int = 512
    n_codebooks: int = 3
    d_model: int = 768
    max_len: int = 1024
    temperature: float = 0.2
    decoder_n_layers: int = 8
    decoder_num_heads: int = 6
    decoder_dropout: float = 0.1
    dtype: jnp.dtype = jnp.float32

    @property
    def pad_id(self) -> int:
        return self.id_vocab_size * self.n_codebooks

    def setup(self):
        self.encoder = LightT5Encoder(
            n_layers=self.encoder_n_layers,
            hidden_dim=self.encoder_hidden_dim,
            output_dim=self.d_model,
            num_heads=self.encoder_num_heads,
            vocab_size=self.encoder_vocab_size,
            dtype=self.dtype,
            name="encoder",
        )
        self.cobra_emb = CobraEmbedding(
            id_vocab_size=self.id_vocab_size,
            n_codebooks=self.n_codebooks,
            d_model=self.d_model,
            max_len=self.max_len,
            dtype=self.dtype,
            name="cobra_emb",
        )
        self.decoder = CobraDecoder(
            self.d_model, n_layers=self.decoder_n_layers,
            n_heads=self.decoder_num_heads, dropout=self.decoder_dropout,
            dtype=self.dtype, name="decoder",
        )
        self.sparse_head = [
            nn.Dense(self.id_vocab_size, dtype=self.dtype, name=f"sparse_head_{c}")
            for c in range(self.n_codebooks)
        ]

    # ---- training ---------------------------------------------------------

    def __call__(self, input_ids, encoder_input_ids, deterministic=True) -> CobraOutput:
        C = self.n_codebooks
        vecs = self.encoder(encoder_input_ids, deterministic=deterministic)
        B, TC = input_ids.shape
        T = TC // C

        sparse_mask = input_ids != self.pad_id
        seq_mask = interleave_seq_mask(sparse_mask, C)
        emb = self.cobra_emb(input_ids, vecs, seq_mask)
        h = self.decoder(emb, tgt_key_padding_mask=~seq_mask, deterministic=deterministic)

        n_pos = T - 1
        loss_sparse = 0.0
        total_correct = jnp.zeros((), jnp.int32)
        total_tokens = jnp.zeros((), jnp.int32)
        all_item_correct = jnp.ones((B, n_pos), bool)
        all_valid = None
        for c in range(C):
            if c == 0:
                pos_c = jnp.arange(0, T - 1) * (C + 1) + C  # dense positions
                target_pos = jnp.arange(1, T) * C
            else:
                pos_c = jnp.arange(1, T) * (C + 1) + (c - 1)
                target_pos = jnp.arange(1, T) * C + c
            logits = self.sparse_head[c](h[:, pos_c, :]).astype(jnp.float32)
            target = input_ids[:, target_pos]
            valid = target != self.pad_id
            if all_valid is None:
                all_valid = valid
            ce, _ = cross_entropy_with_ignore(logits, target, ignore_index=self.pad_id)
            loss_sparse = loss_sparse + ce.sum() / jnp.maximum(valid.sum(), 1)

            pred1 = jnp.argmax(logits, axis=-1)
            top5 = jax.lax.top_k(logits, 5)[1]
            total_correct = total_correct + jnp.sum((pred1 == target) & valid)
            total_tokens = total_tokens + valid.sum()
            all_item_correct = all_item_correct & ((pred1 == target) | ~valid)
        loss_sparse = loss_sparse / C

        item_correct = all_item_correct & all_valid
        recall_correct = item_correct.sum()
        recall_total = all_valid.sum()

        # Dense InfoNCE — static-shape where-masking instead of boolean
        # compression (cobra.py:478-489).
        vec_pos = jnp.arange(1, T) * (C + 1) + (C - 1)
        vec_pred = h[:, vec_pos, :]
        vec_gt = jax.lax.stop_gradient(vecs[:, 1:, :])
        Q = B * (T - 1)
        valid_dense = seq_mask[:, (C + 1) :: (C + 1)].reshape(Q)
        vp = l2norm(vec_pred.reshape(Q, -1).astype(jnp.float32))
        vg = l2norm(vec_gt.reshape(Q, -1).astype(jnp.float32))

        seq_ids = jnp.repeat(jnp.arange(B), T - 1)
        same_seq = (seq_ids[None, :] == seq_ids[:, None]) & ~jnp.eye(Q, dtype=bool)
        sim = (vp @ vg.T) / self.temperature
        sim = jnp.where(same_seq, _NEG_SIM, sim)
        # Invalid columns must not act as negatives; invalid rows drop out.
        sim = jnp.where(~valid_dense[None, :] & ~jnp.eye(Q, dtype=bool), _NEG_SIM, sim)
        logz = jax.nn.logsumexp(sim, axis=-1)
        diag = jnp.diagonal(sim)
        dense_ce = (logz - diag) * valid_dense
        loss_dense = dense_ce.sum() / jnp.maximum(valid_dense.sum(), 1)

        cos = jnp.sum(vp * vg, axis=-1)
        vec_cos_sim = jnp.sum(cos * valid_dense) / jnp.maximum(valid_dense.sum(), 1)

        # Codebook usage entropy (reference hardcodes ::3; generalized to C).
        entropies = []
        for c in range(C):
            ids_c = input_ids[:, c::C]
            usage = jnp.bincount(ids_c.reshape(-1), length=self.pad_id + 1).astype(jnp.float32)
            prob = usage / jnp.maximum(usage.sum(), 1)
            entropies.append(-jnp.sum(prob * jnp.log(prob + 1e-12)))
        codebook_entropy = jnp.mean(jnp.asarray(entropies))

        return CobraOutput(
            loss=loss_sparse + loss_dense,
            loss_sparse=loss_sparse,
            loss_dense=loss_dense,
            acc_correct=total_correct,
            acc_total=total_tokens,
            recall_correct=recall_correct,
            recall_total=recall_total,
            vec_cos_sim=vec_cos_sim,
            codebook_entropy=codebook_entropy,
        )

    # ---- generation -------------------------------------------------------

    def encode_items(self, encoder_input_ids):
        return self.encoder(encoder_input_ids, deterministic=True)

    def decode_hidden(self, input_ids, vecs, n_complete_items):
        """Run the decoder over (possibly partial) sequences; returns
        (h, seq_mask)."""
        sparse_mask = input_ids != self.pad_id
        seq_mask = interleave_seq_mask(sparse_mask, self.n_codebooks, n_complete_items)
        emb = self.cobra_emb(input_ids, vecs, seq_mask, n_complete_items)
        h = self.decoder(emb, tgt_key_padding_mask=~seq_mask, deterministic=True)
        return h, seq_mask

    def decode_prefill(self, input_ids, vecs, n_complete_items):
        """`decode_hidden` over the user history ONCE per eval batch, also
        returning the per-layer K/V for cached suffix decoding."""
        sparse_mask = input_ids != self.pad_id
        seq_mask = interleave_seq_mask(sparse_mask, self.n_codebooks, n_complete_items)
        emb = self.cobra_emb(input_ids, vecs, seq_mask, n_complete_items)
        h, kvs = self.decoder.prefill(emb, tgt_key_padding_mask=~seq_mask)
        return h, seq_mask, kvs

    def decode_suffix_step(self, tok, slot, base_pos, hist_kvs, hist_pad, caches):
        """Advance the sem-id suffix by one codebook position for K beams.

        tok: (B, K) tokens chosen at the previous step; slot/base_pos are
        static ints (suffix index and history length). Returns
        (h (B, K, d_model), new_caches).
        """
        x = self.cobra_emb.suffix_token(tok, slot, base_pos)
        return self.decoder.decode(x, hist_kvs, hist_pad, caches, slot)

    def decode_suffix_step_paged(self, tok, steps, base_pos, k_pools, v_pools,
                                 block_tables, seq_lens, caches):
        """`decode_suffix_step` through the paged history pools with
        per-row suffix slots (steps) and per-row history ends (base_pos).
        """
        x = self.cobra_emb.suffix_token_ragged(tok, steps, base_pos)
        return self.decoder.decode_paged(
            x, k_pools, v_pools, block_tables, seq_lens, caches, steps
        )

    def decode_suffix_tree_paged(self, node_tok, topo, base_steps, base_pos,
                                 k_pools, v_pools, block_tables, seq_lens,
                                 caches):
        """Speculative tree verification: hidden states for EVERY
        candidate-tree node in one parallel suffix pass. ``base_steps``
        is the level-0 suffix slot (the plain step's ``steps - 1``);
        node n sits at slot base + level[n]. Returns (h (S, N, d_model),
        per-layer (k_new, v_new)); the committed caches are read only.
        """
        node_slots = base_steps[:, None] + jnp.asarray(topo.level)[None, :]
        x = self.cobra_emb.suffix_token_tree(node_tok, node_slots, base_pos)
        return self.decoder.decode_tree(
            x, k_pools, v_pools, block_tables, seq_lens, caches, topo,
            base_steps,
        )


def _constrained_logp(logits, trie, prefix_idx, step: int):
    """Log-probs over a (..., V) logit block, trie-masked when a trie is
    given: illegal continuations of ``prefix_idx`` (same leading shape)
    are -1e32 BEFORE the softmax (scores renormalize over legal codes
    only) and again AFTER (a dead beam — no legal continuation — yields
    a flat softmax that must still never win the top-k). trie=None is
    the plain log_softmax. The one definition shared by every codebook
    step of both the cached and uncached searches."""
    if trie is None:
        return jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    legal = trie.legal_mask(prefix_idx, step)
    logp = jax.nn.log_softmax(
        jnp.where(legal, logits, -1e32).astype(jnp.float32), axis=-1
    )
    return jnp.where(legal, logp, -1e32)


def cobra_generate(
    model: Cobra,
    params,
    input_ids,
    encoder_input_ids,
    n_candidates: int = 10,
    temperature: float = 1.0,
    item_vecs=None,
    use_cache: bool = True,
    trie=None,
) -> CobraGenerationOutput:
    """Deterministic top-k beam search over the C codebooks (jit-friendly,
    static shapes per step, mirroring cobra.py:531-665).

    use_cache=True (default) decodes the dense user history ONCE per eval
    batch and advances only the sem-id suffix per codebook step against
    per-layer KV caches; use_cache=False re-runs the full decoder per step
    (the original path, kept as the parity reference).

    ``trie`` (ops.trie.DenseTrie/PackedTrie over the item corpus's C-code
    tuples) constrains decoding to REAL items: each codebook step's logits
    are masked to the trie-legal continuations before the softmax (so beam
    scores renormalize over legal codes only) and again after (so a dead
    beam — one with no legal continuation — can never win the top-k).
    With trie=None the behavior is exactly the unconstrained search.
    """
    C = model.n_codebooks
    K = n_candidates
    V = model.id_vocab_size
    B = input_ids.shape[0]

    vecs = (
        item_vecs
        if item_vecs is not None
        else model.apply({"params": params}, encoder_input_ids, method=Cobra.encode_items)
    )
    T_items = vecs.shape[1]
    if use_cache and input_ids.shape[1] == C * T_items:
        return _cobra_generate_cached(
            model, params, input_ids, vecs, K, temperature, trie
        )

    beam_tokens = None  # (B, K, c)
    beam_scores = None
    prefix_idx = None  # (B, K) trie prefixes of each beam
    h_last = None
    for c in range(C):
        if c == 0:
            h, seq_mask = model.apply(
                {"params": params}, input_ids, vecs, T_items,
                method=Cobra.decode_hidden,
            )
            seq_lens = seq_mask.sum(axis=1)
            h_c = h[jnp.arange(B), seq_lens - 1]  # (B, D) last dense pos
            logits = _apply_head(model, params, 0, h_c) / temperature
            logp = _constrained_logp(logits, trie, jnp.zeros((B,), jnp.int32), 0)
            beam_scores, tok = jax.lax.top_k(logp, K)  # (B, K)
            beam_tokens = tok[..., None]  # (B, K, 1)
            if trie is not None:
                prefix_idx = trie.advance(jnp.zeros((B, K), jnp.int32), tok, 0)
            if C == 1:
                h_last = jnp.broadcast_to(h_c[:, None], (B, K, h_c.shape[-1]))
        else:
            flat_ids = jnp.concatenate(
                [
                    jnp.broadcast_to(input_ids[:, None], (B, K, input_ids.shape[1])),
                    beam_tokens,
                ],
                axis=-1,
            ).reshape(B * K, -1)
            flat_vecs = jnp.broadcast_to(
                vecs[:, None], (B, K, T_items, vecs.shape[-1])
            ).reshape(B * K, T_items, -1)
            h, seq_mask = model.apply(
                {"params": params}, flat_ids, flat_vecs, T_items,
                method=Cobra.decode_hidden,
            )
            seq_lens = seq_mask.sum(axis=1)
            h_c = h[jnp.arange(B * K), seq_lens - 1]  # (B*K, D)
            logits = _apply_head(model, params, c, h_c) / temperature
            logp = _constrained_logp(logits.reshape(B, K, V), trie, prefix_idx, c)
            combined = (beam_scores[..., None] + logp).reshape(B, K * V)
            beam_scores, idx = jax.lax.top_k(combined, K)
            parent = idx // V
            tok = idx % V
            beam_tokens = jnp.concatenate(
                [
                    jnp.take_along_axis(beam_tokens, parent[..., None], axis=1),
                    tok[..., None],
                ],
                axis=-1,
            )
            if trie is not None:
                prefix_idx = trie.advance(
                    jnp.take_along_axis(prefix_idx, parent, axis=1), tok, c
                )
            if c == C - 1:
                h_k = h_c.reshape(B, K, -1)
                h_last = jnp.take_along_axis(h_k, parent[..., None], axis=1)

    return CobraGenerationOutput(
        sem_ids=beam_tokens,
        dense_vecs=l2norm(h_last.astype(jnp.float32)),
        scores=beam_scores,
    )


def _cobra_generate_cached(
    model: Cobra, params, input_ids, vecs, K: int, temperature: float, trie=None
) -> CobraGenerationOutput:
    """KV-cached beam search: one prefill over the interleaved history at
    batch size B, then one suffix position per codebook step at (B, K).

    Semantics match the uncached path exactly, including its read position
    `h[seq_lens - 1]`: for full histories that is the newly appended beam
    token (computed incrementally); for partially-padded rows it lands
    INSIDE the causal history, where the hidden state is unaffected by
    appended tokens — so it is served from the prefill activations.
    """
    from genrec_tpu.models.t5transformer import gather_beam_caches, init_decode_caches

    C = model.n_codebooks
    V = model.id_vocab_size
    B = input_ids.shape[0]
    T_items = vecs.shape[1]

    h_pre, seq_mask, hist_kvs = model.apply(
        {"params": params}, input_ids, vecs, T_items, method=Cobra.decode_prefill
    )
    Lint = seq_mask.shape[1]
    n_valid = seq_mask.sum(axis=1)
    rows = jnp.arange(B)

    h_c = h_pre[rows, n_valid - 1]  # (B, d) last dense position
    logits = _apply_head(model, params, 0, h_c) / temperature
    logp = _constrained_logp(logits, trie, jnp.zeros((B,), jnp.int32), 0)
    beam_scores, tok = jax.lax.top_k(logp, K)
    beam_tokens = tok[..., None]  # (B, K, 1)
    prefix_idx = (
        None if trie is None else trie.advance(jnp.zeros((B, K), jnp.int32), tok, 0)
    )
    if C == 1:
        h_last = jnp.broadcast_to(h_c[:, None], (B, K, h_c.shape[-1]))
        return CobraGenerationOutput(
            sem_ids=beam_tokens,
            dense_vecs=l2norm(h_last.astype(jnp.float32)),
            scores=beam_scores,
        )

    full = n_valid == Lint  # (B,) histories with no padding
    hist_pad = ~seq_mask
    caches = init_decode_caches(
        model.decoder_n_layers, B, K, C - 1, model.decoder_num_heads,
        model.d_model, model.dtype,
    )
    h_last = None
    for c in range(1, C):
        h_new, caches = model.apply(
            {"params": params}, beam_tokens[:, :, c - 1], c - 1, Lint,
            hist_kvs, hist_pad, caches, method=Cobra.decode_suffix_step,
        )  # (B, K, d)
        pos = jnp.clip(n_valid + c - 1, 0, Lint - 1)
        h_c = jnp.where(full[:, None, None], h_new, h_pre[rows, pos][:, None, :])
        logits = _apply_head(model, params, c, h_c) / temperature
        logp = _constrained_logp(logits, trie, prefix_idx, c)  # (B, K, V)
        combined = (beam_scores[..., None] + logp).reshape(B, K * V)
        beam_scores, idx = jax.lax.top_k(combined, K)
        parent = idx // V
        tok = idx % V
        beam_tokens = jnp.concatenate(
            [
                jnp.take_along_axis(beam_tokens, parent[..., None], axis=1),
                tok[..., None],
            ],
            axis=-1,
        )
        if trie is not None:
            prefix_idx = trie.advance(
                jnp.take_along_axis(prefix_idx, parent, axis=1), tok, c
            )
        caches = gather_beam_caches(caches, parent)
        if c == C - 1:
            h_last = jnp.take_along_axis(h_c, parent[..., None], axis=1)

    return CobraGenerationOutput(
        sem_ids=beam_tokens,
        dense_vecs=l2norm(h_last.astype(jnp.float32)),
        scores=beam_scores,
    )


def _apply_head(model: Cobra, params, c: int, x):
    k = params[f"sparse_head_{c}"]
    return x @ k["kernel"] + k["bias"]


# ---- paged decode (ragged paged KV + slot-level continuous batching) --------
#
# Mirror of the TIGER section in models/tiger.py: the interleaved-history
# K/V moves into shared page pools, the suffix cache stays dense per beam,
# and the per-step body takes a PER-ROW codebook index so the serving
# engine can advance slots sitting at different steps in one fixed-shape
# call. `cobra_generate_paged` drives it in lockstep as the parity
# reference against `_cobra_generate_cached` (pinned <=1e-5).


def init_cobra_paged_state(model: Cobra, n_slots: int, beams: int):
    """Zeroed slot-major decode state (see init_tiger_paged_state)."""
    C = model.n_codebooks
    nl = model.decoder_n_layers
    H = model.decoder_num_heads
    hd = model.d_model // H
    return {
        "beam_tokens": jnp.zeros((n_slots, beams, C), jnp.int32),
        "beam_scores": jnp.zeros((n_slots, beams), jnp.float32),
        "prefix_idx": jnp.zeros((n_slots, beams), jnp.int32),
        "cache_k": jnp.zeros((n_slots, nl, beams, max(C - 1, 1), H, hd), model.dtype),
        "cache_v": jnp.zeros((n_slots, nl, beams, max(C - 1, 1), H, hd), model.dtype),
        "tail_hidden": jnp.zeros((n_slots, C, model.d_model), jnp.float32),
        "full": jnp.zeros((n_slots,), bool),
        "base_pos": jnp.zeros((n_slots,), jnp.int32),
        "h_last": jnp.zeros((n_slots, beams, model.d_model), jnp.float32),
    }


def cobra_prefill_paged(model: Cobra, params, input_ids, vecs, block_tables,
                        k_pools, v_pools, trie, n_candidates: int,
                        temperature: float = 1.0):
    """Bucketed prefill writing the interleaved-history K/V into the page
    pools, plus everything the suffix steps need per slot.

    Returns (k_pools, v_pools, init) where init holds the codebook-0 beam
    (the step-0 head reads the prefill's last dense position — no suffix
    step needed), the C prefill tail hiddens serving partially-padded
    rows' reads, the full-row flag, base_pos (= valid interleaved length;
    also the pool seq_lens), and h_last seeded for the C == 1 edge.
    """
    from genrec_tpu.ops.paged import write_pages

    C = model.n_codebooks
    B = input_ids.shape[0]
    T_items = vecs.shape[1]
    h_pre, seq_mask, hist_kvs = model.apply(
        {"params": params}, input_ids, vecs, T_items, method=Cobra.decode_prefill
    )
    k_pools = tuple(
        write_pages(pool, block_tables, kv[0]) for pool, kv in zip(k_pools, hist_kvs)
    )
    v_pools = tuple(
        write_pages(pool, block_tables, kv[1]) for pool, kv in zip(v_pools, hist_kvs)
    )
    Lint = seq_mask.shape[1]
    n_valid = seq_mask.sum(axis=1).astype(jnp.int32)
    rows = jnp.arange(B)
    tail = jnp.stack(
        [
            h_pre[rows, jnp.clip(n_valid + c - 1, 0, Lint - 1)].astype(jnp.float32)
            for c in range(C)
        ],
        axis=1,
    )  # (B, C, d): c=0 feeds the step-0 head; c>=1 serve partial rows

    logits = _apply_head(model, params, 0, tail[:, 0]) / temperature
    logp = _constrained_logp(logits, trie, jnp.zeros((B,), jnp.int32), 0)
    beam_scores, tok = jax.lax.top_k(logp, n_candidates)
    beam_tokens = jnp.zeros((B, n_candidates, C), jnp.int32)
    beam_tokens = beam_tokens.at[:, :, 0].set(tok)
    prefix_idx = (
        jnp.zeros((B, n_candidates), jnp.int32)
        if trie is None
        else trie.advance(jnp.zeros((B, n_candidates), jnp.int32), tok, 0)
    )
    init = {
        "beam_tokens": beam_tokens,
        "beam_scores": beam_scores,
        "prefix_idx": prefix_idx,
        "tail_hidden": tail,
        "full": n_valid == Lint,
        "base_pos": n_valid,
        "h_last": jnp.broadcast_to(
            tail[:, 0][:, None], (B, n_candidates, model.d_model)
        ),
    }
    return k_pools, v_pools, init


def _cobra_beam_update(model: Cobra, trie, logits_scaled, beam_tokens,
                       beam_scores, prefix_idx, steps):
    """One beam selection given this step's temperature-scaled (S, K, V)
    logits — the post-logits math of the paged suffix step, factored out
    so the speculative accept scan (`cobra_spec_tree_step`) replays the
    SAME definition per tree level. Returns (beam_tokens, beam_scores,
    prefix_idx, parent, tok)."""
    from genrec_tpu.ops.trie import advance_ragged, legal_mask_ragged

    S_, K, C = beam_tokens.shape
    V = model.id_vocab_size
    if trie is None:
        logp = jax.nn.log_softmax(logits_scaled.astype(jnp.float32), axis=-1)
    else:
        legal = legal_mask_ragged(trie, prefix_idx, steps)
        logp = jax.nn.log_softmax(
            jnp.where(legal, logits_scaled, -1e32).astype(jnp.float32), axis=-1
        )
        logp = jnp.where(legal, logp, -1e32)

    combined = (beam_scores[..., None] + logp).reshape(S_, K * V)
    new_scores, idx = jax.lax.top_k(combined, K)
    parent = idx // V
    tok = idx % V
    new_tokens = jnp.take_along_axis(beam_tokens, parent[..., None], axis=1)
    hit = jnp.arange(C)[None, None, :] == steps[:, None, None]
    new_tokens = jnp.where(hit, tok[..., None], new_tokens)
    new_prefix = (
        jnp.zeros_like(prefix_idx)
        if trie is None
        else advance_ragged(
            trie,
            jnp.take_along_axis(prefix_idx, parent, axis=1),
            tok, steps,
        )
    )
    return new_tokens, new_scores, new_prefix, parent, tok


def cobra_paged_decode_step(
    model: Cobra,
    params,
    trie,
    state: dict,
    steps,
    block_tables,
    seq_lens,
    k_pools,
    v_pools,
    temperature: float = 1.0,
):
    """One suffix codebook position for every slot; steps (S,) carries
    each row's codebook index c in [1, C-1]. Mirrors one iteration of
    `_cobra_generate_cached`'s loop with the static c replaced by the
    per-row operand: the sparse head, trie tables, suffix slot and token
    write column are all row-selected.
    """
    C = model.n_codebooks
    S_, K, _ = state["beam_tokens"].shape
    caches = [
        {"k": state["cache_k"][:, i], "v": state["cache_v"][:, i]}
        for i in range(state["cache_k"].shape[1])
    ]

    tok_prev = jnp.take_along_axis(
        state["beam_tokens"], jnp.clip(steps - 1, 0, C - 1)[:, None, None], axis=2
    )[:, :, 0]
    h_new, caches = model.apply(
        {"params": params}, tok_prev, steps - 1, state["base_pos"],
        k_pools, v_pools, block_tables, seq_lens, caches,
        method=Cobra.decode_suffix_step_paged,
    )  # (S, K, d)
    c_idx = jnp.clip(steps, 0, C - 1)
    h_tail = jnp.take_along_axis(
        state["tail_hidden"], c_idx[:, None, None], axis=1
    )[:, 0]
    h_c = jnp.where(
        state["full"][:, None, None], h_new, h_tail[:, None, :].astype(h_new.dtype)
    )

    logits = None
    for c in range(C):  # every sparse head computed, row-selected (C tiny)
        lc = _apply_head(model, params, c, h_c)
        logits = lc if logits is None else jnp.where(
            (steps == c)[:, None, None], lc, logits
        )
    logits = logits / temperature
    beam_tokens, beam_scores, prefix_idx, parent, _tok = _cobra_beam_update(
        model, trie, logits, state["beam_tokens"], state["beam_scores"],
        state["prefix_idx"], steps,
    )
    from genrec_tpu.models.t5transformer import gather_beam_caches

    caches = gather_beam_caches(caches, parent)
    h_last = jnp.take_along_axis(h_c, parent[..., None], axis=1).astype(jnp.float32)

    return {
        "beam_tokens": beam_tokens,
        "beam_scores": beam_scores,
        "prefix_idx": prefix_idx,
        "cache_k": jnp.stack([c["k"] for c in caches], axis=1),
        "cache_v": jnp.stack([c["v"] for c in caches], axis=1),
        "tail_hidden": state["tail_hidden"],
        "full": state["full"],
        "base_pos": state["base_pos"],
        "h_last": h_last,
    }


def cobra_spec_tree_step(
    model: Cobra,
    params,
    trie,
    state: dict,
    steps,
    block_tables,
    seq_lens,
    k_pools,
    v_pools,
    fanout: int = 4,
    depth: int | None = None,
    temperature: float = 1.0,
    draft_override=None,
):
    """Speculative tree decode for the COBRA suffix: commit between 1 and
    ``depth + 1`` codebook positions per slot in ONE target invocation.

    Same contract as `tiger_spec_tree_step`: draft trie-legal children
    per beam (weight-ranked; plain code order when trie is None — the
    free-decode correctness case), verify the whole tree in one parallel
    suffix pass (`Cobra.decode_suffix_tree_paged`), replay
    `_cobra_beam_update` — the plain step's own selection math — level
    by level, and accept while every selection was a drafted edge.
    Level 0 is exact, so the worst case equals plain decode step for
    step, bit for bit. Returns (new_state, accept (S,) int32).
    """
    from genrec_tpu.ops.spec_tree import (
        TreeTopology, commit_level_kv, match_drafted,
    )
    from genrec_tpu.ops.trie import advance_ragged, legal_topk_ragged

    C = model.n_codebooks
    S_, K, _ = state["beam_tokens"].shape
    if depth is None:
        depth = max(C - 2, 0)
    depth = max(min(int(depth), C - 2), 0)
    topo = TreeTopology(K, fanout, depth)
    caches = [
        {"k": state["cache_k"][:, i], "v": state["cache_v"][:, i]}
        for i in range(state["cache_k"].shape[1])
    ]

    # -- draft ---------------------------------------------------------------
    tok_prev = jnp.take_along_axis(
        state["beam_tokens"], jnp.clip(steps - 1, 0, C - 1)[:, None, None], axis=2
    )[:, :, 0]
    levels_tok = [tok_prev]
    draft_toks = []
    cur_prefix = state["prefix_idx"]  # (S, N_prev), N_0 = K
    for l in range(1, depth + 1):
        step_l = jnp.minimum(steps + (l - 1), C - 1)
        if draft_override is not None:
            d_tok = jnp.asarray(draft_override[l - 1], jnp.int32)
        elif trie is None:
            # Free decode: no legality to expand — draft the first F
            # codes (correctness-only; acceptance is incidental).
            d_tok = jnp.broadcast_to(
                jnp.arange(topo.fanouts[l - 1], dtype=jnp.int32),
                (S_, cur_prefix.shape[1], topo.fanouts[l - 1]),
            )
        else:
            d_tok, _ = legal_topk_ragged(trie, cur_prefix, step_l,
                                         topo.fanouts[l - 1])
        draft_toks.append(d_tok)
        levels_tok.append(d_tok.reshape(S_, -1))
        if trie is None:
            cur_prefix = jnp.zeros(
                (S_, d_tok.shape[1] * d_tok.shape[2]), jnp.int32)
        else:
            cur_prefix = advance_ragged(
                trie, jnp.broadcast_to(cur_prefix[..., None], d_tok.shape),
                d_tok, step_l,
            ).reshape(S_, -1)
    node_tok = jnp.concatenate(levels_tok, axis=1)  # (S, N)

    # -- verify: one parallel suffix pass over the whole tree ----------------
    h_nodes, node_kvs = model.apply(
        {"params": params}, node_tok, topo, steps - 1, state["base_pos"],
        k_pools, v_pools, block_tables, seq_lens, caches,
        method=Cobra.decode_suffix_tree_paged,
    )  # (S, N, d)
    node_steps = steps[:, None] + jnp.asarray(topo.level)[None, :]
    c_idx = jnp.clip(node_steps, 0, C - 1)
    h_tail = jnp.take_along_axis(
        state["tail_hidden"], c_idx[..., None], axis=1
    )  # (S, N, d): partial rows read their prefill tail at every level
    h_c_nodes = jnp.where(
        state["full"][:, None, None], h_nodes, h_tail.astype(h_nodes.dtype)
    )
    logits_nodes = None
    for c in range(C):  # every sparse head computed, node-selected (C tiny)
        lc = _apply_head(model, params, c, h_c_nodes)
        logits_nodes = lc if logits_nodes is None else jnp.where(
            (node_steps == c)[..., None], lc, logits_nodes
        )
    logits_nodes = logits_nodes / temperature

    # -- accept scan: replay the plain update along the drafted tree --------
    run_tokens = com_tokens = state["beam_tokens"]
    run_scores = com_scores = state["beam_scores"]
    run_prefix = com_prefix = state["prefix_idx"]
    run_ck = com_ck = [c["k"] for c in caches]
    run_cv = com_cv = [c["v"] for c in caches]
    com_h_last = state["h_last"]
    cur_local = jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32)[None], (S_, K))
    ok = jnp.ones((S_,), bool)
    accept = jnp.zeros((S_,), jnp.int32)
    for j in range(depth + 1):
        applied = ok & (steps + j <= C - 1)
        step_j = jnp.minimum(steps + j, C - 1)
        flat_idx = topo.level_offsets[j] + cur_local  # (S, K)
        logits_j = jnp.take_along_axis(logits_nodes, flat_idx[..., None], axis=1)
        new_tokens, new_scores, new_prefix, parent, sel_tok = _cobra_beam_update(
            model, trie, logits_j, run_tokens, run_scores, run_prefix, step_j,
        )
        # This level's suffix-cache slot is steps - 1 + j.
        new_ck, new_cv = commit_level_kv(
            node_kvs, run_ck, run_cv, flat_idx, parent, step_j - 1
        )
        h_c_sel = jnp.take_along_axis(h_c_nodes, flat_idx[..., None], axis=1)
        new_h_last = jnp.take_along_axis(
            h_c_sel, parent[..., None], axis=1
        ).astype(jnp.float32)
        ap2 = applied[:, None]
        ap3 = applied[:, None, None]
        ap5 = applied[:, None, None, None, None]
        com_tokens = jnp.where(ap3, new_tokens, com_tokens)
        com_scores = jnp.where(ap2, new_scores, com_scores)
        com_prefix = jnp.where(ap2, new_prefix, com_prefix)
        com_h_last = jnp.where(ap3, new_h_last, com_h_last)
        com_ck = [jnp.where(ap5, n, c) for n, c in zip(new_ck, com_ck)]
        com_cv = [jnp.where(ap5, n, c) for n, c in zip(new_cv, com_cv)]
        accept = accept + applied.astype(jnp.int32)
        if j < depth:
            parent_local = jnp.take_along_axis(cur_local, parent, axis=1)
            matched, child_f = match_drafted(draft_toks[j], parent_local, sel_tok)
            ok = applied & matched
            cur_local = parent_local * topo.fanouts[j] + child_f
            run_tokens, run_scores, run_prefix = new_tokens, new_scores, new_prefix
            run_ck, run_cv = new_ck, new_cv

    new_state = {
        "beam_tokens": com_tokens,
        "beam_scores": com_scores,
        "prefix_idx": com_prefix,
        "cache_k": jnp.stack(com_ck, axis=1),
        "cache_v": jnp.stack(com_cv, axis=1),
        "tail_hidden": state["tail_hidden"],
        "full": state["full"],
        "base_pos": state["base_pos"],
        "h_last": com_h_last,
    }
    return new_state, accept


def cobra_generate_paged(
    model: Cobra,
    params,
    input_ids,
    encoder_input_ids,
    n_candidates: int = 10,
    temperature: float = 1.0,
    item_vecs=None,
    trie=None,
    page_size: int = 8,
    kv_dtype: str = "float32",
) -> CobraGenerationOutput:
    """`cobra_generate(use_cache=True)` through the paged decode path —
    prefill into a freshly built pool, then the slot-level suffix step
    with every row in lockstep (the parity reference for serving).
    ``kv_dtype="int8"`` stores the pool quantized (ops/quant).
    """
    C = model.n_codebooks
    B = input_ids.shape[0]
    vecs = (
        item_vecs
        if item_vecs is not None
        else model.apply({"params": params}, encoder_input_ids, method=Cobra.encode_items)
    )
    T_items = vecs.shape[1]
    if input_ids.shape[1] != C * T_items:
        raise ValueError("paged decode requires complete-item histories")

    nl = model.decoder_n_layers
    H = model.decoder_num_heads
    hd = model.d_model // H
    Lint = T_items * (C + 1)
    pages_per_slot = -(-Lint // page_size)
    num_pages = 1 + B * pages_per_slot
    block_tables = jnp.asarray(
        1 + jnp.arange(B * pages_per_slot).reshape(B, pages_per_slot), jnp.int32
    )
    from genrec_tpu.ops.paged import zero_pool

    zeros = lambda: tuple(
        zero_pool(num_pages, page_size, H, hd, model.dtype, kv_dtype)
        for _ in range(nl)
    )
    k_pools, v_pools, init = cobra_prefill_paged(
        model, params, input_ids, vecs, block_tables, zeros(), zeros(),
        trie, n_candidates, temperature,
    )
    state = init_cobra_paged_state(model, B, n_candidates)
    state.update(init)
    seq_lens = init["base_pos"]
    for c in range(1, C):
        state = cobra_paged_decode_step(
            model, params, trie, state, jnp.full((B,), c, jnp.int32),
            block_tables, seq_lens, k_pools, v_pools, temperature=temperature,
        )
    return CobraGenerationOutput(
        sem_ids=state["beam_tokens"],
        dense_vecs=l2norm(state["h_last"]),
        scores=state["beam_scores"],
    )


def beam_fusion(
    model: Cobra,
    params,
    input_ids,
    encoder_input_ids,
    item_dense_vecs,
    item_sem_ids,
    n_candidates: int = 10,
    n_beam: int = 50,
    temperature: float = 1.0,
    alpha: float = 0.5,
    item_vecs=None,
    use_cache: bool = True,
    trie=None,
) -> BeamFusionOutput:
    """Beam candidates + dense nearest-neighbour, alpha-fused (cobra.py:679-760).

    The dense similarity is one (B, n_beam, D) x (D, N) matmul — pure MXU.
    """
    gen = cobra_generate(
        model, params, input_ids, encoder_input_ids,
        n_candidates=n_beam, temperature=temperature, item_vecs=item_vecs,
        use_cache=use_cache, trie=trie,
    )
    item_vecs_n = l2norm(item_dense_vecs.astype(jnp.float32))
    sim = jnp.einsum("bkd,nd->bkn", gen.dense_vecs, item_vecs_n)
    max_sim = sim.max(axis=-1)
    best_item = jnp.argmax(sim, axis=-1)  # (B, n_beam)

    beam_norm = jax.nn.softmax(gen.scores, axis=-1)
    fused = alpha * beam_norm + (1 - alpha) * (max_sim + 1) / 2
    top_scores, top_idx = jax.lax.top_k(fused, n_candidates)
    item_ids = jnp.take_along_axis(best_item, top_idx, axis=1)
    sem_ids = item_sem_ids[item_ids]
    return BeamFusionOutput(item_ids=item_ids, sem_ids=sem_ids, scores=top_scores)
