"""T5-style transformer encoder-decoder (TIGER's backbone).

Parity target: reference genrec/modules/transformer.py — per-layer T5
relative-bias self-attention (bidirectional log buckets, stored as an
(n_heads*num_buckets, 1) embedding :77-104), bias-free projections, fused
kv for self-attention (:72, 122-124), pre-norm blocks with optional
cross-attention (:256-324), T5 relu FFN, RMS norms with fp32 statistics,
additive attn-mask + boolean key-padding mask (-1e9 fill :143-151).

TPU notes: all shapes static; softmax in fp32. Every self-attention,
packed rows or not, adds ONE (1, H, Lq, Lk) bias grid per layer: 32
buckets of SLOT distance gathered from `rel_bias` and broadcast over the
batch, so the forward gather and the backward scatter touch H*Lq*Lk
values (22k at TIGER's 61 slots), never batch times that; the batch axis
of the score gradient is a dense row-sum. Packed encoder rows
(`Tiger.forward_packed`) need no per-row grid: the packer lays a segment
out contiguously, so within a segment relative distance IS slot distance,
and cross-segment pairs are masked by the caller's additive `attn_mask`
before the softmax. Do not index `rel_bias` with a tensor that has a
batch axis: a per-row grid costs a gather and a scatter-add of
rows*H*L*L values into 192 entries a layer, which was 89% of the packed
step on a v5e (PERF.md section 6). The packed decoder's cross-attention
reads that memory as the encoder left it, (R, L, d): a query batch S
times the key batch is S examples a memory row, whose queries are folded
into one (S*Lq)-long query axis against the row's K/V, so no (R*S, L, ·)
copy of the memory or its K/V is made.

Incremental decode (the KV-cached engine behind `tiger_generate`):
beam-search generation keeps all decode tensors in (B, K, ...) layout —
self-attention K/V live in a static (B, K, S, H, hd) cache written one
position per step (`decode_step`), and cross-attention K/V are projected
ONCE per eval batch from the *un-expanded* (B, Lm) encoder memory
(`precompute_cross_kv`) and attended by all K beams via einsum, so the
K-fold memory broadcast of the naive decoder never materializes. Beam
reordering is a `take_along_axis` on the cache's beam axis
(`gather_beam_caches`). Pattern proven in models/backbones/qwen.py.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from genrec_tpu.models.layers import RMSNorm
from genrec_tpu.ops.buckets import t5_relative_position_bucket

_NEG = -1e9


class T5Attention(nn.Module):
    d_model: int
    n_heads: int
    dropout: float = 0.0
    is_cross_attention: bool = False
    has_relative_bias: bool = True
    num_relative_buckets: int = 32
    max_distance: int = 128
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        dense = lambda d, name: nn.Dense(d, use_bias=False, dtype=self.dtype, name=name)
        self.q = dense(self.d_model, "q")
        if self.is_cross_attention:
            self.k = dense(self.d_model, "k")
            self.v = dense(self.d_model, "v")
        else:
            self.kv = dense(2 * self.d_model, "kv")
        self.o = dense(self.d_model, "o")
        if self.has_relative_bias and not self.is_cross_attention:
            # Same storage quirk as the reference: one scalar per
            # (head, bucket), flattened.
            self.rel_bias = self.param(
                "rel_bias",
                nn.initializers.normal(stddev=0.02),
                (self.n_heads * self.num_relative_buckets, 1),
            )
        self.attn_drop = nn.Dropout(self.dropout)

    def _position_bias(self, q_len: int, k_len: int, q_offset: int = 0):
        ctx = q_offset + jnp.arange(q_len)[:, None]
        mem = jnp.arange(k_len)[None, :]
        buckets = t5_relative_position_bucket(
            mem - ctx, self.num_relative_buckets, self.max_distance, bidirectional=True
        )  # (q, k)
        head_offset = jnp.arange(self.n_heads)[:, None, None] * self.num_relative_buckets
        idx = buckets[None] + head_offset  # (H, q, k)
        return self.rel_bias[idx, 0][None]  # (1, H, q, k)

    def __call__(
        self,
        query,
        key=None,
        value=None,
        attn_mask=None,
        key_padding_mask=None,
        deterministic: bool = True,
    ):
        B, Lq, _ = query.shape
        H, hd = self.n_heads, self.d_model // self.n_heads
        if self.is_cross_attention:
            k = self.k(key)
            v = self.v(value)
        else:
            k, v = jnp.split(self.kv(query), 2, axis=-1)
        q = self.q(query)

        # Packed rows (Tiger.forward_packed): the query batch is G times the
        # key batch, a memory row's G examples one after another. They share
        # the row's K/V as one query axis of G * Lq; key_padding_mask stays
        # per example, (B, Lk).
        Bk = k.shape[0]
        G = B // Bk
        if G * Bk != B:
            raise ValueError(f"query batch {B} is not a multiple of key batch {Bk}")
        split = lambda x: x.reshape(Bk, -1, H, hd).transpose(0, 2, 1, 3)
        q, k, v = split(q), split(k), split(v)
        Lk = k.shape[2]

        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (hd**-0.5)
        scores = scores.astype(jnp.float32)
        if self.has_relative_bias and not self.is_cross_attention:
            scores = scores + self._position_bias(Lq, Lk)
        if key_padding_mask is not None and G > 1:
            pad = key_padding_mask.reshape(Bk, 1, G, 1, Lk)
            grouped = scores.reshape(Bk, H, G, Lq, Lk)
            scores = jnp.where(pad, _NEG, grouped).reshape(scores.shape)
        elif key_padding_mask is not None:  # True = padding
            scores = jnp.where(key_padding_mask[:, None, None, :], _NEG, scores)
        if attn_mask is not None:  # additive, (Lq, Lk) or broadcastable
            scores = scores + attn_mask

        attn = jax.nn.softmax(scores, axis=-1).astype(query.dtype)
        attn = self.attn_drop(attn, deterministic=deterministic)
        out = jnp.einsum("bhqk,bhkd->bhqd", attn, v)
        out = out.transpose(0, 2, 1, 3).reshape(B, Lq, self.d_model)
        return self.o(out)

    # ---- incremental decode ------------------------------------------------

    def decode_self(self, x, cache, step: int):
        """One self-attention decode step against a static KV cache.

        x: (B, K, d_model) — the current position for each of K beams.
        cache: {"k", "v"}: (B, K, S, H, hd). ``step`` is the static write
        slot; slots > step are masked out (exp underflows to exactly 0, so
        the padded softmax matches the uncached prefix softmax).
        """
        B, K, _ = x.shape
        H, hd = self.n_heads, self.d_model // self.n_heads
        k_new, v_new = jnp.split(self.kv(x), 2, axis=-1)
        q = self.q(x).reshape(B, K, H, hd)
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k_new.reshape(B, K, 1, H, hd), (0, 0, step, 0, 0)
        )
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v_new.reshape(B, K, 1, H, hd), (0, 0, step, 0, 0)
        )
        S = ck.shape[2]
        scores = jnp.einsum("bkhd,bkshd->bkhs", q, ck) * (hd**-0.5)
        scores = scores.astype(jnp.float32)
        if self.has_relative_bias:
            # (1, H, 1, S) bias at query position ``step`` -> (1, 1, H, S).
            scores = scores + self._position_bias(1, S, q_offset=step)[:, :, 0][:, None]
        scores = jnp.where(jnp.arange(S)[None, None, None, :] > step, _NEG, scores)
        attn = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        out = jnp.einsum("bkhs,bkshd->bkhd", attn, cv).reshape(B, K, self.d_model)
        return self.o(out), {"k": ck, "v": cv}

    def decode_self_ragged(self, x, cache, steps):
        """`decode_self` with a PER-ROW step operand (steps: (B,) int32).

        Slot-level continuous batching advances rows sitting at different
        decode positions in ONE fixed-shape call, so the write slot, the
        relative-position bias and the causal mask all come from ``steps``
        instead of a static int. Row b with steps[b] == t computes exactly
        what `decode_self(..., step=t)` computes for it.
        """
        B, K, _ = x.shape
        H, hd = self.n_heads, self.d_model // self.n_heads
        k_new, v_new = jnp.split(self.kv(x), 2, axis=-1)
        q = self.q(x).reshape(B, K, H, hd)
        S = cache["k"].shape[2]
        hit = (jnp.arange(S)[None, :] == steps[:, None])[:, None, :, None, None]
        ck = jnp.where(hit, k_new.reshape(B, K, 1, H, hd), cache["k"])
        cv = jnp.where(hit, v_new.reshape(B, K, 1, H, hd), cache["v"])
        scores = jnp.einsum("bkhd,bkshd->bkhs", q, ck) * (hd**-0.5)
        scores = scores.astype(jnp.float32)
        if self.has_relative_bias:
            rel = jnp.arange(S)[None, :] - steps[:, None]  # (B, S) mem - ctx
            buckets = t5_relative_position_bucket(
                rel, self.num_relative_buckets, self.max_distance,
                bidirectional=True,
            )
            head_offset = jnp.arange(self.n_heads)[:, None] * self.num_relative_buckets
            bias = self.rel_bias[buckets[:, None, :] + head_offset[None], 0]
            scores = scores + bias[:, None]  # (B, 1, H, S)
        scores = jnp.where(
            jnp.arange(S)[None, None, None, :] > steps[:, None, None, None],
            _NEG, scores,
        )
        attn = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        out = jnp.einsum("bkhs,bkshd->bkhd", attn, cv).reshape(B, K, self.d_model)
        return self.o(out), {"k": ck, "v": cv}

    def decode_self_tree(self, x, cache, topo, steps):
        """Speculative tree-verification self-attention: one parallel
        pass over every candidate-tree node (ops/spec_tree.py).

        x: (B, N, d_model) — N tree nodes per slot replacing the K-beam
        axis. cache: the COMMITTED (B, K, S, H, hd) suffix cache (read
        only — commitment happens in the accept scan, so a rejected
        branch leaves it untouched). steps: (B,) the slots' current
        decode positions. Node n attends its root beam's committed
        prefix plus its ancestors' K/V from THIS pass, overlaid at the
        speculated slots through the static ancestor tables — the fixed
        tree-attention mask — with the same score/bias/mask/softmax ops
        as `decode_self_ragged`, so an accepted path's logits are
        bitwise the sequential plain steps'.

        Returns (out (B, N, d_model), (k_new, v_new) each (B, N, H, hd))
        — the per-node K/V the accept scan commits for accepted levels.
        """
        from genrec_tpu.ops.spec_tree import tree_virtual_cache

        B, N, _ = x.shape
        H, hd = self.n_heads, self.d_model // self.n_heads
        k_new, v_new = jnp.split(self.kv(x), 2, axis=-1)
        q = self.q(x).reshape(B, N, H, hd)
        k_new = k_new.reshape(B, N, H, hd)
        v_new = v_new.reshape(B, N, H, hd)
        S = cache["k"].shape[2]
        node_steps = steps[:, None] + jnp.asarray(topo.level)[None, :]
        vk = tree_virtual_cache(cache["k"], k_new, topo, steps)
        vv = tree_virtual_cache(cache["v"], v_new, topo, steps)
        scores = jnp.einsum("bkhd,bkshd->bkhs", q, vk) * (hd**-0.5)
        scores = scores.astype(jnp.float32)
        if self.has_relative_bias:
            rel = jnp.arange(S)[None, None, :] - node_steps[:, :, None]
            buckets = t5_relative_position_bucket(
                rel, self.num_relative_buckets, self.max_distance,
                bidirectional=True,
            )  # (B, N, S)
            head_offset = jnp.arange(self.n_heads)[:, None] * self.num_relative_buckets
            bias = self.rel_bias[
                buckets[:, :, None, :] + head_offset[None, None], 0
            ]  # (B, N, H, S)
            scores = scores + bias
        scores = jnp.where(
            jnp.arange(S)[None, None, None, :] > node_steps[:, :, None, None],
            _NEG, scores,
        )
        attn = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        out = jnp.einsum("bkhs,bkshd->bkhd", attn, vv).reshape(B, N, self.d_model)
        return self.o(out), (k_new, v_new)

    def project_kv(self, memory):
        """Cross-attention K/V from the un-expanded encoder memory, computed
        once per eval batch: (B, Lm, d) -> two (B, H, Lm, hd)."""
        B, Lm, _ = memory.shape
        H, hd = self.n_heads, self.d_model // self.n_heads
        k = self.k(memory).reshape(B, Lm, H, hd).transpose(0, 2, 1, 3)
        v = self.v(memory).reshape(B, Lm, H, hd).transpose(0, 2, 1, 3)
        return k, v

    def decode_cross_paged(self, x, k_pool, v_pool, block_tables, seq_lens):
        """`decode_cross` against PAGED K/V: the memory keys live in a
        page pool and each row reads its own pages through a block-table
        row; positions >= seq_lens[b] are masked (the serving layout's
        contiguous-valid-prefix contract replaces key_padding_mask).
        Beams share the row's pages — no K-fold gather, no remap on beam
        reorder.
        """
        B, K, _ = x.shape
        H, hd = self.n_heads, self.d_model // self.n_heads
        from genrec_tpu.ops.paged import paged_attention

        q = self.q(x).reshape(B, K, H, hd)
        out = paged_attention(q, k_pool, v_pool, block_tables, seq_lens)
        return self.o(out.reshape(B, K, self.d_model))

    def decode_cross(self, x, kv, key_padding_mask=None):
        """Cross-attention of K beams against shared cached K/V.

        x: (B, K, d_model); kv: pair of (B, H, Lm, hd);
        key_padding_mask: (B, Lm), True = padding. The einsum resolves the
        beam axis against the batch-sized memory — no K-fold broadcast.
        """
        B, K, _ = x.shape
        H, hd = self.n_heads, self.d_model // self.n_heads
        k, v = kv
        q = self.q(x).reshape(B, K, H, hd)
        scores = jnp.einsum("bkhd,bhmd->bkhm", q, k) * (hd**-0.5)
        scores = scores.astype(jnp.float32)
        if key_padding_mask is not None:
            scores = jnp.where(key_padding_mask[:, None, None, :], _NEG, scores)
        attn = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        out = jnp.einsum("bkhm,bhmd->bkhd", attn, v).reshape(B, K, self.d_model)
        return self.o(out)


class T5FeedForward(nn.Module):
    dim: int
    hidden_dim: int
    dropout: float = 0.1
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        x = nn.Dense(self.hidden_dim, use_bias=False, dtype=self.dtype, name="wi")(x)
        x = nn.relu(x)
        x = nn.Dropout(self.dropout)(x, deterministic=deterministic)
        return nn.Dense(self.dim, use_bias=False, dtype=self.dtype, name="wo")(x)


class TransformerBlock(nn.Module):
    dim: int
    num_heads: int
    dropout: float = 0.1
    ff_hidden_dim: int = 2048
    cross_attn: bool = False
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        self.self_attn = T5Attention(
            self.dim, self.num_heads, self.dropout, dtype=self.dtype, name="self_attn"
        )
        self.norm1 = RMSNorm(self.dim, name="norm1")
        self.drop1 = nn.Dropout(self.dropout)
        if self.cross_attn:
            self.cross = T5Attention(
                self.dim, self.num_heads, self.dropout,
                is_cross_attention=True, has_relative_bias=False,
                dtype=self.dtype, name="cross_attn",
            )
            self.norm_cross = RMSNorm(self.dim, name="norm_cross")
            self.drop_cross = nn.Dropout(self.dropout)
        self.ff = T5FeedForward(self.dim, self.ff_hidden_dim, self.dropout,
                                dtype=self.dtype, name="ff")
        self.norm2 = RMSNorm(self.dim, name="norm2")
        self.drop2 = nn.Dropout(self.dropout)

    def __call__(
        self,
        x,
        context=None,
        attn_mask=None,
        key_padding_mask=None,
        memory_key_padding_mask=None,
        deterministic: bool = True,
    ):
        h = self.self_attn(
            self.norm1(x),
            attn_mask=attn_mask,
            key_padding_mask=key_padding_mask,
            deterministic=deterministic,
        )
        x = x + self.drop1(h, deterministic=deterministic)
        if self.cross_attn and context is not None:
            h = self.cross(
                self.norm_cross(x), key=context, value=context,
                key_padding_mask=memory_key_padding_mask,
                deterministic=deterministic,
            )
            x = x + self.drop_cross(h, deterministic=deterministic)
        h = self.ff(self.norm2(x), deterministic=deterministic)
        return x + self.drop2(h, deterministic=deterministic)

    def decode_step(self, x, cache, cross_kv=None, memory_key_padding_mask=None,
                    step: int = 0):
        """Cached one-position decode: x (B, K, dim) -> (out, new_cache)."""
        h, new_cache = self.self_attn.decode_self(self.norm1(x), cache, step)
        x = x + h
        if self.cross_attn and cross_kv is not None:
            h = self.cross.decode_cross(
                self.norm_cross(x), cross_kv, memory_key_padding_mask
            )
            x = x + h
        h = self.ff(self.norm2(x), deterministic=True)
        return x + h, new_cache

    def decode_step_paged(self, x, cache, k_pool, v_pool, block_tables,
                          seq_lens, steps):
        """`decode_step` with per-row steps and paged cross-attention K/V."""
        h, new_cache = self.self_attn.decode_self_ragged(self.norm1(x), cache, steps)
        x = x + h
        if self.cross_attn:
            h = self.cross.decode_cross_paged(
                self.norm_cross(x), k_pool, v_pool, block_tables, seq_lens
            )
            x = x + h
        h = self.ff(self.norm2(x), deterministic=True)
        return x + h, new_cache

    def decode_step_tree(self, x, cache, k_pool, v_pool, block_tables,
                         seq_lens, topo, steps):
        """`decode_step_paged` over tree nodes: tree self-attention
        against the committed cache + in-pass ancestors; cross-attention
        reads the SAME paged pages (the node axis rides where the beam
        axis did — beams/nodes of a slot share its pages, nothing is
        remapped). Returns (out, (k_new, v_new)) per-node K/V instead of
        an updated cache — commitment is the accept scan's job."""
        h, kv = self.self_attn.decode_self_tree(self.norm1(x), cache, topo, steps)
        x = x + h
        if self.cross_attn:
            h = self.cross.decode_cross_paged(
                self.norm_cross(x), k_pool, v_pool, block_tables, seq_lens
            )
            x = x + h
        h = self.ff(self.norm2(x), deterministic=True)
        return x + h, kv


class TransformerEncoder(nn.Module):
    dim: int
    depth: int
    num_heads: int
    dropout: float = 0.1
    ff_hidden_dim: int = 2048
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        self.layers = [
            TransformerBlock(
                self.dim, self.num_heads, self.dropout,
                ff_hidden_dim=self.ff_hidden_dim, cross_attn=False,
                dtype=self.dtype, name=f"layer_{i}",
            )
            for i in range(self.depth)
        ]

    def __call__(self, src, attn_mask=None, key_padding_mask=None, deterministic=True):
        for layer in self.layers:
            src = layer(
                src, attn_mask=attn_mask, key_padding_mask=key_padding_mask,
                deterministic=deterministic,
            )
        return src


class TransformerDecoder(nn.Module):
    dim: int
    depth: int
    num_heads: int
    dropout: float = 0.1
    ff_hidden_dim: int = 2048
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        self.layers = [
            TransformerBlock(
                self.dim, self.num_heads, self.dropout,
                ff_hidden_dim=self.ff_hidden_dim, cross_attn=True,
                dtype=self.dtype, name=f"layer_{i}",
            )
            for i in range(self.depth)
        ]

    def __call__(
        self,
        tgt,
        memory,
        attn_mask=None,
        key_padding_mask=None,
        memory_key_padding_mask=None,
        deterministic=True,
    ):
        for layer in self.layers:
            tgt = layer(
                tgt, context=memory, attn_mask=attn_mask,
                key_padding_mask=key_padding_mask,
                memory_key_padding_mask=memory_key_padding_mask,
                deterministic=deterministic,
            )
        return tgt

    def precompute_cross_kv(self, memory):
        """Per-layer cross-attention K/V from the (B, Lm, d) memory — the
        once-per-eval-batch projection the uncached decoder re-ran every
        step over a K-fold-expanded memory."""
        return [layer.cross.project_kv(memory) for layer in self.layers]

    def decode_step(self, x, caches, cross_kvs, memory_key_padding_mask=None,
                    step: int = 0):
        """Advance all layers one position: x (B, K, dim) ->
        (out, new_caches)."""
        new_caches = []
        for layer, cache, ckv in zip(self.layers, caches, cross_kvs):
            x, nc = layer.decode_step(
                x, cache, ckv, memory_key_padding_mask, step=step
            )
            new_caches.append(nc)
        return x, new_caches

    def decode_step_paged(self, x, caches, k_pools, v_pools, block_tables,
                          seq_lens, steps):
        """Advance all layers one per-row position against the paged
        cross-attention pools (one (pages, page, H*hd) K and V pool per
        layer)."""
        new_caches = []
        for layer, cache, kp, vp in zip(self.layers, caches, k_pools, v_pools):
            with jax.named_scope("decoder_layer"):
                x, nc = layer.decode_step_paged(
                    x, cache, kp, vp, block_tables, seq_lens, steps
                )
            new_caches.append(nc)
        return x, new_caches

    def decode_tree(self, x, caches, k_pools, v_pools, block_tables,
                    seq_lens, topo, steps):
        """One parallel verification pass over every tree node, all
        layers: x (B, N, dim) -> (out, per-layer (k_new, v_new) node
        K/V). The committed caches are read, never written."""
        node_kvs = []
        for layer, cache, kp, vp in zip(self.layers, caches, k_pools, v_pools):
            x, kv = layer.decode_step_tree(
                x, cache, kp, vp, block_tables, seq_lens, topo, steps
            )
            node_kvs.append(kv)
        return x, node_kvs


def init_decode_caches(depth: int, batch: int, beams: int, max_len: int,
                       n_heads: int, d_model: int, dtype=jnp.float32):
    """Static per-layer self-attention KV caches, (B, K, S, H, hd)."""
    hd = d_model // n_heads
    return [
        {
            "k": jnp.zeros((batch, beams, max_len, n_heads, hd), dtype),
            "v": jnp.zeros((batch, beams, max_len, n_heads, hd), dtype),
        }
        for _ in range(depth)
    ]


def gather_beam_caches(caches, sel_parent):
    """Reorder every cache leaf along the beam axis after a beam-search
    top-k: sel_parent (B, K) indexes the surviving parents. The KV rows of
    slot s were written by the parent's prefix, so a gather keeps cache
    and beam_seqs consistent."""
    idx = sel_parent[:, :, None, None, None]
    return [
        {k: jnp.take_along_axis(v, idx, axis=1) for k, v in cache.items()}
        for cache in caches
    ]


def causal_mask(T: int) -> jax.Array:
    """Additive (T, T) mask: -inf above the diagonal."""
    return jnp.where(jnp.triu(jnp.ones((T, T), bool), k=1), _NEG, 0.0)


class TransformerEncoderDecoder(nn.Module):
    d_model: int
    nhead: int
    num_encoder_layers: int
    num_decoder_layers: int
    dim_feedforward: int = 2048
    dropout: float = 0.1
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        self.encoder = TransformerEncoder(
            self.d_model, self.num_encoder_layers, self.nhead, self.dropout,
            self.dim_feedforward, dtype=self.dtype, name="encoder",
        )
        self.decoder = TransformerDecoder(
            self.d_model, self.num_decoder_layers, self.nhead, self.dropout,
            self.dim_feedforward, dtype=self.dtype, name="decoder",
        )

    def __call__(
        self,
        src,
        tgt,
        src_key_padding_mask=None,
        memory_key_padding_mask=None,
        tgt_mask=None,
        deterministic=True,
    ):
        if tgt_mask is None:
            tgt_mask = causal_mask(tgt.shape[1])
        memory = self.encoder(
            src, key_padding_mask=src_key_padding_mask, deterministic=deterministic
        )
        return self.decoder(
            tgt, memory, attn_mask=tgt_mask,
            memory_key_padding_mask=memory_key_padding_mask,
            deterministic=deterministic,
        )
