"""Qwen2-class decoder-only LLM backbone in Flax.

The reference's LCRec/NoteLLM wrap HF `AutoModelForCausalLM` with a
Qwen2.5 backbone (lcrec.py:39-40, notellm.py:44-77; config/base.gin:19).
This is the JAX equivalent (SURVEY.md §7 hard part #2): RMSNorm ->
GQA attention with RoPE (q/k/v biased, o bias-free, Qwen2 layout) ->
SwiGLU MLP, pre-norm residuals, optional tied LM head.

Weight parity is tested against a random-init HF Qwen2ForCausalLM
(instantiated offline from config) — see tests/test_qwen.py — and
`params_from_hf_state_dict` converts real checkpoints when available.

TPU notes: static shapes, fp32 softmax/norm statistics, bf16 matmuls via
`dtype`; `jax.checkpoint`-friendly layer structure; decode uses a static
KV cache (`init_cache` + per-step `decode_step`) so generation is one
compiled while-free loop per new token.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from genrec_tpu.models.layers import RMSNorm


@dataclasses.dataclass(frozen=True)
class QwenConfig:
    vocab_size: int = 151936
    hidden_size: int = 1536
    intermediate_size: int = 8960
    num_hidden_layers: int = 28
    num_attention_heads: int = 12
    num_key_value_heads: int = 2
    max_position_embeddings: int = 4096
    rope_theta: float = 1_000_000.0
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = True
    # Mixture-of-experts (Qwen2-MoE-class): >0 replaces the dense SwiGLU
    # with `num_experts` routed SwiGLU experts (top-k, capacity-dropped).
    # The reference has no MoE anywhere (SURVEY.md §2.5: EP "absent"); this
    # is the beyond-parity path that gives the framework an expert-parallel
    # axis to scale over.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # Per-expert slot budget C = ceil(tokens/num_experts) * capacity_factor.
    # Static C keeps every shape jit-compilable; overflow tokens fall back
    # to the residual stream (their MLP delta is zero), the standard
    # Switch/GShard trade.
    # None = DROPLESS: every routed token-expert pair is computed, whatever
    # the imbalance (sorted pairs, grouped products: QwenMoEMLP._dropless).
    moe_capacity_factor: Optional[float] = 2.0
    router_aux_coef: float = 0.01
    # Width of one expert (None: intermediate_size, the Qwen2-MoE layout).
    moe_intermediate_size: Optional[int] = None
    # Renormalise the chosen gates over the chosen experts.
    norm_topk_prob: bool = True
    # Which experts THIS chip holds (expert parallelism without the
    # exchange): the router keeps `num_experts` outputs and its
    # `num_experts_per_tok`; only experts [first, first + held) have
    # weights here and add to the result. None = all of them. Dropless only.
    moe_first_expert: int = 0
    moe_experts_held: Optional[int] = None
    # Attention layout. head_dim None = hidden_size // heads (Qwen2);
    # Qwen3-class models state it (heads x head_dim != hidden_size).
    head_dim: Optional[int] = None
    attention_bias: bool = True  # q/k/v bias (Qwen2 has it, Qwen3 not)
    qk_norm: bool = False  # per-head RMSNorm on q and k before RoPE
    # Learned sparse attention (DeepSeek-Sparse-Attention indexer): >0 =
    # each query attends the `sparse_topk` keys its indexer scores
    # highest among the real keys at or before it (all of them where
    # there are no more). `sparse_chunk` is the query tile in which
    # scores and selection are computed (never block-level selection),
    # and the query tile of every attention that builds no (L, L) bias.
    sparse_topk: int = 0
    indexer_heads: int = 0
    indexer_head_dim: int = 0
    sparse_chunk: int = 512
    # Layer kinds, a layer at a time. Mixer: the 1-based layer numbers (as a
    # published ``linear_attn_config`` lists them) that run Kimi Delta
    # Attention (backbones/kda.py) or NoPE latent attention
    # (backbones/mla.py); every other layer runs the attention above. MLP:
    # with ``num_experts > 0`` the first ``first_k_dense_replace`` layers
    # keep the dense SwiGLU of ``intermediate_size``. The defaults mean
    # what every config meant before there were kinds: one mixer, one MLP.
    kda_layers: tuple = ()
    mla_layers: tuple = ()
    first_k_dense_replace: int = 0
    # KDA: heads x head size (keys and values alike).
    kda_heads: int = 0
    kda_head_dim: int = 0
    # Latent attention: the latent's rank, the key's two parts (the
    # "rope" part is carried and never rotated) and the value's width.
    # Its query tile is ``sparse_chunk``.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Router: ``softmax`` over the experts, or ``sigmoid`` scores with a
    # selection bias (enters the choice of experts, never their gates; zero
    # from the seed, no gradient) and the chosen gates scaled by
    # ``routed_scaling_factor``. ``n_shared_experts`` x the expert width is
    # one more SwiGLU every token passes through, beside the routed ones.
    moe_scoring: str = "softmax"
    routed_scaling_factor: float = 1.0
    n_shared_experts: int = 0
    # Full attention without positions (``use_rope`` False: q and k are never
    # rotated; ``rope_theta`` is then unread), and an output gate: the
    # attention's output times sigmoid(x W_g), W_g hidden -> heads x head_dim
    # without bias, element by element, before ``o_proj``.
    use_rope: bool = True
    attn_output_gate: bool = False
    # KDA: the write strength is 2 * sigmoid(.) instead of sigmoid(.), so the
    # transition I - b k k^T has the eigenvalue 1 - b in (-1, 1) along the
    # unit key (a published ``kda_allow_neg_eigval``).
    kda_neg_eigval: bool = False

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(
                self, "head_dim", self.hidden_size // self.num_attention_heads)
        if self.moe_capacity_factor is not None and (
                self.moe_experts_held is not None or self.moe_first_expert):
            raise ValueError(
                "a share of the experts (moe_experts_held) needs the dropless "
                "path: set moe_capacity_factor=None")
        if self.sparse_topk and not (self.indexer_heads and self.indexer_head_dim):
            raise ValueError("sparse_topk > 0 needs indexer_heads and indexer_head_dim")
        object.__setattr__(self, "kda_layers", tuple(self.kda_layers))
        object.__setattr__(self, "mla_layers", tuple(self.mla_layers))
        if set(self.kda_layers) & set(self.mla_layers):
            raise ValueError("a layer runs one mixer: kda_layers and mla_layers overlap")
        if self.kda_layers and not (self.kda_heads and self.kda_head_dim):
            raise ValueError("kda_layers needs kda_heads and kda_head_dim")
        if self.mla_layers and not (self.kv_lora_rank and self.qk_nope_head_dim
                                    and self.v_head_dim):
            raise ValueError("mla_layers needs kv_lora_rank, qk_nope_head_dim "
                             "and v_head_dim")
        if self.moe_scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"moe_scoring {self.moe_scoring!r}: softmax or sigmoid")

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def experts_here(self) -> int:
        return self.num_experts if self.moe_experts_held is None else self.moe_experts_held

    def mixer_kind(self, layer: int) -> str:
        """``attention`` | ``kda`` | ``mla`` of the 0-based ``layer``."""
        if layer + 1 in self.kda_layers:
            return "kda"
        return "mla" if layer + 1 in self.mla_layers else "attention"

    def mlp_kind(self, layer: int) -> str:
        """``dense`` | ``moe`` of the 0-based ``layer``."""
        dense = self.num_experts == 0 or layer < self.first_k_dense_replace
        return "dense" if dense else "moe"

    @property
    def dense_attention_layers(self) -> bool:
        """Some layer scores every key at once and needs the (L, L) bias."""
        return self.sparse_topk == 0 and any(
            self.mixer_kind(i) == "attention" for i in range(self.num_hidden_layers))


def causal_pad_bias(L: int, attention_mask=None):
    """Additive attention bias: causal triu mask plus key-padding mask
    (-1e9, never -inf: fully-masked rows would NaN-poison gradients).
    Shared by the dense forward and the pipeline-parallel stage body so
    the two paths can never drift apart."""
    bias = jnp.where(jnp.triu(jnp.ones((L, L), bool), k=1), -1e9, 0.0)[None, None]
    if attention_mask is not None:
        bias = bias + jnp.where(attention_mask[:, None, None, :] == 0, -1e9, 0.0)
    return bias


def _rope(x, positions, theta):
    """NeoX-style half-rotation RoPE. x: (B, L, H, hd), positions: (B, L)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, L, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Learned sparse attention: indexer scores -> exact top-k -> masked softmax
# ---------------------------------------------------------------------------

_NEG = -1e9  # never -inf: a fully masked (padding) query row must stay finite
_HIGHEST = jax.lax.Precision.HIGHEST


def indexer_scores(q_idx, w_idx, k_idx):
    """I[b,t,n] = sum_j w[b,t,j] * relu(q_idx[b,t,j] . k_idx[b,n]), float32
    at full precision (16 x 64 a token: small beside the attention, and a
    selection computed in bf16 flips at near ties for nothing)."""
    s = jnp.einsum("bthd,bnd->bhtn", q_idx, k_idx, precision=_HIGHEST)
    return jnp.einsum("bhtn,bth->btn", jax.nn.relu(s), w_idx, precision=_HIGHEST)


def select_topk(scores, allowed, k: int):
    """Exactly the ``k`` allowed keys of largest score in each row (all
    allowed keys where there are at most ``k``); ties go to the lowest
    index. scores (..., N) float32, allowed (..., N) bool -> bool mask.

    No sort and no (N,)-wide top_k: the k-th largest score is found by
    bisection on the scores' order-preserving integer image, one bit a
    pass (32 counting passes over the tile), and a tie at that score is
    cut at an index found the same way."""
    n = scores.shape[-1]
    if n <= k:
        return allowed
    scores = scores.astype(jnp.float32)
    scores = jnp.where(scores == 0, 0.0, scores)  # -0.0 and 0.0 are one score
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    # unsigned image; 0 is below every float's image, so a key that is not
    # allowed ranks last
    u = jnp.where(allowed, key.astype(jnp.uint32) ^ jnp.uint32(0x80000000),
                  jnp.uint32(0))

    def count(mask):
        return jnp.sum(mask, axis=-1, keepdims=True, dtype=jnp.int32)

    def value_bit(i, thr):
        cand = thr | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        return jnp.where(count(u >= cand) >= k, cand, thr)

    # thr: the largest value that at least k keys reach = the k-th largest
    thr = jax.lax.fori_loop(
        0, 32, value_bit, jnp.zeros(u.shape[:-1] + (1,), jnp.uint32))
    above = u > thr
    tie = u == thr
    need = k - count(above)  # >= 1 ties to take, lowest index first
    idx = jnp.arange(n, dtype=jnp.int32)
    nbits = max(1, (n - 1).bit_length())

    def index_bit(i, cut):
        cand = cut | (jnp.int32(1) << (jnp.int32(nbits - 1) - i))
        return jnp.where(count(tie & (idx < cand)) < need, cand, cut)

    # cut: the largest index with fewer than `need` ties before it
    cut = jax.lax.fori_loop(
        0, nbits, index_bit, jnp.zeros(u.shape[:-1] + (1,), jnp.int32))
    return (above | (tie & (idx <= cut))) & allowed


#: Keys a softmax pass reads at once. Measured on a v5e (PERF.md section 6,
#: PR 30): XLA's fused max/exp over (32, 512, n) float32 scores takes 1.2 ms
#: at n = 4,096 and 1.5 ms at 5,120, then 26 ms at 5,632 and 56 ms at 8,192
#: (the reduction stops fitting on chip). Longer key runs are merged chunk by
#: chunk with a running max and sum, which is the same softmax.
_KEY_CHUNK = 4096


def _attend_row(q, k, v, sel):
    """One row, one query tile against its keys. q (T, KV, rep, hd),
    k/v (N, KV, hd), sel (T, N) bool -> (T, KV, rep, hd). Softmax in
    float32 over the selected keys only, `_KEY_CHUNK` keys at a time."""
    hd = q.shape[-1]
    N = k.shape[0]
    m = l = acc = None
    for lo in range(0, N, _KEY_CHUNK):
        hi = min(lo + _KEY_CHUNK, N)
        s = jnp.einsum("tgrd,ngd->grtn", q, k[lo:hi],
                       preferred_element_type=jnp.float32) * (hd ** -0.5)
        s = jnp.where(sel[None, None, :, lo:hi], s, _NEG)
        m_new = s.max(axis=-1) if m is None else jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        part = jnp.einsum("grtn,ngd->grtd", p.astype(v.dtype), v[lo:hi],
                          preferred_element_type=jnp.float32)
        if m is None:
            l, acc = p.sum(axis=-1), part
        else:
            # a chunk with no selected key holds exp(0) = 1 everywhere; the
            # first chunk that has one rescales it away (exp(-1e9 - m) = 0)
            scale = jnp.exp(m - m_new)
            l = l * scale + p.sum(axis=-1)
            acc = acc * scale[..., None] + part
        m = m_new
    out = (acc / l[..., None]).astype(v.dtype)
    return out.transpose(2, 0, 1, 3)  # (g, r, t, d) -> (t, g, r, d)


def _attend_tile(q, k, v, sel):
    """One query tile of every row, a row at a time, each rematerialised
    in the backward pass: the live scores are (heads, T, N) of ONE row."""
    row = jax.checkpoint(_attend_row)
    return jax.lax.map(lambda a: row(*a), (q, k, v, sel))


def sparse_attention(q, k, v, q_idx, w_idx, k_idx, key_valid, topk: int,
                     chunk: int, q_slot=None, query_valid=None):
    """Attention over each query's top-``topk`` indexer-selected keys,
    exact, a query tile at a time: the largest temporary is the
    (heads, chunk, N) scores of ONE row, never (rows, heads, L, L).

    q (B, L, KV, rep, hd); k, v (B, N, KV, hd); q_idx (B, L, Hi, di),
    w_idx (B, L, Hi), k_idx (B, N, di) float32; key_valid (B, N) bool.
    ``q_slot`` None: the queries ARE keys 0..L-1 (training, N == L), and a
    tile reads only the keys up to its own end. Otherwise (L,) traced
    slots of the queries in a cache of N slots. Returns the output and
    (sum over real queries of kept/available keys, real queries) for the
    ``sparse_keys_kept_share`` counter (None without ``query_valid``)."""
    B, L = q.shape[:2]
    N = k.shape[1]
    outs, kept, seen = [], 0.0, 0.0
    for lo in range(0, L, chunk):
        hi = min(lo + chunk, L)
        n = min(hi, N) if q_slot is None else N
        slots = jnp.arange(lo, hi) if q_slot is None else q_slot[lo:hi]
        with jax.named_scope("indexer"):
            scores = indexer_scores(q_idx[:, lo:hi], w_idx[:, lo:hi], k_idx[:, :n])
        with jax.named_scope("sparse_select"):
            allowed = (key_valid[:, None, :n]
                       & (jnp.arange(n)[None, None, :] <= slots[None, :, None]))
            sel = checkpoint_name(select_topk(scores, allowed, topk), "sparse_sel")
            if query_valid is not None:
                avail = jnp.sum(allowed, -1).astype(jnp.float32)
                share = jnp.sum(sel, -1).astype(jnp.float32) / jnp.maximum(avail, 1.0)
                real = query_valid[:, lo:hi].astype(jnp.float32)
                kept = kept + jnp.sum(share * real)
                seen = seen + jnp.sum(real)
        with jax.named_scope("sparse_attend"):
            outs.append(checkpoint_name(
                _attend_tile(q[:, lo:hi], k[:, :n], v[:, :n], sel),
                "sparse_attn_out"))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    return out, (None if query_valid is None else (kept, seen))


def causal_attention(q, k, v, key_valid, chunk: int, q_slot=None):
    """Attention over every real key at or before the query, a query tile
    at a time through the tiled running-max softmax above: the largest
    temporary is the (heads, chunk, keys) scores of ONE row. The key width
    may differ from the value width.

    q (B, L, KV, rep, hd); k (B, N, KV, hd); v (B, N, KV, vd); key_valid
    (B, N) bool. ``q_slot`` as in `sparse_attention`. A tile's backward pass
    keeps the whole K and V of its row (one buffer for all tiles) and
    slices them inside the rematerialised row."""
    L, N = q.shape[1], k.shape[1]
    outs = []
    for lo in range(0, L, chunk):
        hi = min(lo + chunk, L)
        n = min(hi, N) if q_slot is None else N
        slots = jnp.arange(lo, hi) if q_slot is None else q_slot[lo:hi]
        allowed = (key_valid[:, None, :n]
                   & (jnp.arange(n)[None, None, :] <= slots[None, :, None]))
        row = jax.checkpoint(
            lambda qr, kr, vr, sel, n=n: _attend_row(qr, kr[:n], vr[:n], sel))
        outs.append(jax.lax.map(lambda a: row(*a), (q[:, lo:hi], k, v, allowed)))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


def gqa_decode_paged(q, k, v, cache):
    """One new token a beam against a prompt held in pages and the beam's
    own generated suffix: the serving decode of a full-attention layer.

    q (S*W, 1, H, hd), k and v (S*W, 1, KV, hd): W beams of each of S
    slots. ``cache``: ``k_pool``/``v_pool`` (pages, page, KV*hd) with the
    slots' ``block_tables`` (S, pages) and ``seq_lens`` (S,): the prompt,
    shared by a slot's beams; ``sk``/``sv`` (S, W, T, KV*hd): each beam's
    suffix, this token written at slot ``t`` (S,) and read up to it. The
    H/KV query heads of a key-value head ride the paged read's beam axis;
    the two partial softmaxes are merged into the joint one
    (`ops.paged.merge_attention_stats`). Returns (S*W, 1, H*hd) and the
    suffix with the token in it."""
    from genrec_tpu.ops.paged import NEG, merge_attention_stats, paged_attention_stats

    bt, sl, t = cache["block_tables"], cache["seq_lens"], cache["t"]
    S = bt.shape[0]
    H, hd = q.shape[2:]
    KV = k.shape[2]
    W, rep, T = q.shape[0] // S, H // KV, cache["sk"].shape[2]
    hit = (jnp.arange(T)[None, :] == t[:, None])[:, None, :, None]
    sk = jnp.where(hit, k.reshape(S, W, 1, KV * hd), cache["sk"])
    sv = jnp.where(hit, v.reshape(S, W, 1, KV * hd), cache["sv"])
    q5 = q.reshape(S, W, KV, rep, hd)
    with jax.named_scope("kv_attend"):
        # head h = g * rep + r reads key-value head g
        acc, m, l = paged_attention_stats(
            q5.transpose(0, 1, 3, 2, 4).reshape(S, W * rep, KV, hd),
            cache["k_pool"], cache["v_pool"], bt, sl)
        beams = lambda a: a.reshape((S, W, rep, KV) + a.shape[3:]).swapaxes(2, 3)
        acc, m, l = beams(acc), beams(m), beams(l)
    with jax.named_scope("suffix_attend"):
        s = jnp.einsum("swgrd,swtgd->swgrt", q5, sk.reshape(S, W, T, KV, hd),
                       preferred_element_type=jnp.float32) * (hd ** -0.5)
        s = jnp.where(jnp.arange(T) > t[:, None, None, None, None], NEG, s)
        m_s = s.max(axis=-1)
        e = jnp.exp(s - m_s[..., None])
        acc_s = jnp.einsum("swgrt,swtgd->swgrd", e,
                           sv.reshape(S, W, T, KV, hd).astype(jnp.float32))
        out = merge_attention_stats(acc, m, l, acc_s, m_s, e.sum(axis=-1))
    return out.reshape(S * W, 1, H * hd).astype(q.dtype), {"sk": sk, "sv": sv}


class QwenAttention(nn.Module):
    cfg: QwenConfig
    dtype: jnp.dtype = jnp.float32
    # Sequence parallelism: when ring_axis is set and this forward is traced
    # inside a shard_map over that mesh axis, attention runs as ring
    # attention (parallel/ring_attention.py) — K/V shards rotate via
    # ppermute, O(L_local^2) score tiles, exact result. Incompatible with
    # the decode cache (generation is not sequence-sharded).
    ring_axis: Optional[str] = None
    ring_size: int = 1

    def _indexer(self, x):
        """The indexer's queries, head weights and (one shared head of)
        keys, float32, from the layer's normed input. The selection is
        discrete: nothing here receives a gradient from the LM loss."""
        cfg = self.cfg
        Hi, di = cfg.indexer_heads, cfg.indexer_head_dim
        x = jax.lax.stop_gradient(x).astype(jnp.float32)
        dense = lambda n, name: nn.Dense(
            n, use_bias=False, dtype=jnp.float32, precision=_HIGHEST, name=name)
        q_idx = dense(Hi * di, "idx_q")(x).reshape(x.shape[:2] + (Hi, di))
        k_idx = nn.LayerNorm(epsilon=1e-6, dtype=jnp.float32,
                             name="idx_k_norm")(dense(di, "idx_k")(x))
        w_idx = dense(Hi, "idx_w")(x)
        return q_idx, w_idx, k_idx

    @nn.compact
    def __call__(self, x, positions, attn_bias, cache=None, ring_kv_valid=None,
                 key_valid=None):
        cfg = self.cfg
        B, L, _ = x.shape
        H, KV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        bias = cfg.attention_bias
        sparse = cfg.sparse_topk > 0
        q = nn.Dense(H * hd, use_bias=bias, dtype=self.dtype, name="q_proj")(x)
        k = nn.Dense(KV * hd, use_bias=bias, dtype=self.dtype, name="k_proj")(x)
        v = nn.Dense(KV * hd, use_bias=bias, dtype=self.dtype, name="v_proj")(x)
        q = q.reshape(B, L, H, hd)
        k = k.reshape(B, L, KV, hd)
        v = v.reshape(B, L, KV, hd)
        if cfg.qk_norm:
            q = RMSNorm(hd, cfg.rms_norm_eps, name="q_norm")(q)
            k = RMSNorm(hd, cfg.rms_norm_eps, name="k_norm")(k)
        if cfg.use_rope:
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
        if sparse:
            if self.ring_axis is not None:
                raise ValueError("sparse attention is not wired with ring attention")
            q_idx, w_idx, k_idx = self._indexer(x)
            if cfg.use_rope:
                q_idx = _rope(q_idx, positions, cfg.rope_theta)
                k_idx = _rope(k_idx[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
        gate = None
        if cfg.attn_output_gate:
            gate = nn.Dense(H * hd, use_bias=False, dtype=self.dtype, name="gate_proj")(x)

        new_cache = None
        q_slot = None
        paged = cache is not None and "k_pool" in cache
        if cache is not None and not paged:
            # cache: dict(k=(B, S, KV, hd), v=..., idx scalar): static-size
            # decode cache updated at position idx (sparse attention keeps
            # the indexer's keys beside K and V).
            idx = cache["idx"]
            ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, idx, 0, 0))
            cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, idx, 0, 0))
            k, v = ck, cv
            new_cache = {"k": ck, "v": cv, "idx": idx + L}
            if sparse:
                k_idx = jax.lax.dynamic_update_slice(cache["ki"], k_idx, (0, idx, 0))
                new_cache["ki"] = k_idx
            if sparse or attn_bias is None:
                q_slot = idx + jnp.arange(L)

        rep = H // KV  # GQA expansion factor
        if paged:
            # serving: the prompt in pages, the beam's own suffix beside it
            if sparse:
                raise ValueError("sparse attention has no paged decode")
            out, new_cache = gqa_decode_paged(q, k, v, cache)
        elif sparse:
            if key_valid is None:
                key_valid = jnp.ones(k.shape[:2], bool)
            key_valid = key_valid.astype(bool)
            out, kept = sparse_attention(
                q.reshape(B, L, KV, rep, hd), k, v, q_idx, w_idx, k_idx,
                key_valid, cfg.sparse_topk, cfg.sparse_chunk, q_slot=q_slot,
                query_valid=key_valid if cache is None else None)
            out = out.reshape(B, L, H * hd)
            if kept is not None:
                self.sow("counters", "sparse_keys_kept_share",
                         100.0 * kept[0] / jnp.maximum(kept[1], 1.0))
        elif self.ring_axis is not None and cache is None:
            from genrec_tpu.parallel.ring_attention import ring_attention

            # K/V rotate UNREPEATED (kv_rep expands on the local tile), so
            # ring ppermute traffic scales with KV heads, not query heads.
            out = ring_attention(
                q, k, v, axis_name=self.ring_axis, axis_size=self.ring_size,
                causal=True, kv_valid=ring_kv_valid, kv_rep=rep,
            ).reshape(B, L, H * hd)
        elif attn_bias is None:
            # No (L, L) bias was built: the caller's rows are too long for
            # one score matrix a head (a serving prefill: 6.7 GB a row at
            # 5,120 tokens), so the same softmax runs a query tile at a time.
            if key_valid is None:
                key_valid = jnp.ones(k.shape[:2], bool)
            out = causal_attention(
                q.reshape(B, L, KV, rep, hd), k, v, key_valid.astype(bool),
                cfg.sparse_chunk, q_slot=q_slot).reshape(B, L, H * hd)
        else:
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
            scores = jnp.einsum("blhd,bshd->bhls", q, k).astype(jnp.float32) * (hd**-0.5)
            scores = scores + attn_bias  # (B or 1, 1, L, S) additive
            attn = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            out = jnp.einsum("bhls,bshd->blhd", attn, v).reshape(B, L, H * hd)
        if gate is not None:
            out = (out * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(self.dtype)
        out = nn.Dense(cfg.hidden_size, use_bias=False, dtype=self.dtype, name="o_proj")(out)
        return out, new_cache


class QwenMLP(nn.Module):
    cfg: QwenConfig
    dtype: jnp.dtype = jnp.float32
    width: Optional[int] = None  # None: cfg.intermediate_size

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        width = self.width or cfg.intermediate_size
        gate = nn.Dense(width, use_bias=False, dtype=self.dtype, name="gate_proj")(x)
        up = nn.Dense(width, use_bias=False, dtype=self.dtype, name="up_proj")(x)
        return nn.Dense(cfg.hidden_size, use_bias=False, dtype=self.dtype, name="down_proj")(
            nn.silu(gate) * up
        )


def _ctx_mesh_axes() -> tuple:
    """Axis names of whichever mesh context is active — `jax.set_mesh`
    (abstract) or the legacy `with mesh:` (physical) — so sharding
    constraints no-op cleanly outside any mesh (e.g. during init)."""
    from jax.sharding import get_abstract_mesh

    axes = tuple(getattr(get_abstract_mesh(), "axis_names", ()))
    if not axes:
        try:
            from jax._src.mesh import thread_resources

            axes = tuple(thread_resources.env.physical_mesh.axis_names)
        except Exception:
            axes = ()
    return axes


class QwenMoEMLP(nn.Module):
    """Top-k routed mixture of SwiGLU experts, GShard/Switch dispatch.

    TPU-first design: routing is expressed as two dense einsums against a
    (tokens, experts, capacity) dispatch/combine tensor — static shapes,
    no gather/scatter, so XLA tiles the per-expert matmuls onto the MXU
    and, when the expert stacks are sharded over an ``expert`` mesh axis
    (parallel/shardings.moe_rules), lowers the dispatch einsum to an
    all-to-all over ICI. The fp32 router and the load-balancing auxiliary
    loss (sown into the ``losses`` collection as ``router_aux``) follow
    the Switch-Transformer formulation.
    """

    cfg: QwenConfig
    dtype: jnp.dtype = jnp.float32
    # When set, dispatched (E, C, D) activations are sharding-constrained
    # to this mesh axis so the all-to-all boundary is explicit even before
    # XLA's propagation pass.
    expert_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, token_mask=None):
        cfg = self.cfg
        E, K, D, F = (
            cfg.num_experts,
            cfg.num_experts_per_tok,
            cfg.hidden_size,
            cfg.expert_width,
        )
        B, L, _ = x.shape
        S = B * L
        xf = x.reshape(S, D)

        # Router in fp32 at full precision: tiny matmul, and bf16 logits
        # (a TPU's default for a float32 product) visibly perturb top-k
        # order at realistic expert counts.
        with jax.named_scope("moe_route"):
            logits = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                              precision=_HIGHEST, name="router")(
                xf.astype(jnp.float32)
            )
            if cfg.moe_scoring == "sigmoid":
                # The bias moves which experts are chosen, never their
                # gates; a buffer the gradient step leaves alone.
                probs = jax.nn.sigmoid(logits)
                bias = jax.lax.stop_gradient(self.param(
                    "selection_bias", nn.initializers.zeros, (E,)))
                _, eidx = jax.lax.top_k(probs + bias, K)
                gates = jnp.take_along_axis(probs, eidx, axis=-1)
            else:
                probs = jax.nn.softmax(logits, axis=-1)  # (S, E)
                gates, eidx = jax.lax.top_k(probs, K)  # (S, K)
            if cfg.norm_topk_prob:
                gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
            if cfg.routed_scaling_factor != 1.0:
                gates = gates * cfg.routed_scaling_factor

        # Padding tokens must not claim capacity slots (at tight capacity
        # factors they would evict REAL tokens' primary experts with
        # rank-0 priority) nor steer the load-balance loss.
        valid = (
            jnp.ones((S,), jnp.int32)
            if token_mask is None
            else token_mask.reshape(S).astype(jnp.int32)
        )

        held = cfg.experts_here
        w_gate = self.param("gate_proj", nn.initializers.lecun_normal(), (held, D, F))
        w_up = self.param("up_proj", nn.initializers.lecun_normal(), (held, D, F))
        w_down = self.param("down_proj", nn.initializers.lecun_normal(), (held, F, D))

        # Switch load-balance loss over VALID tokens only: E * sum_e
        # mean(router prob_e) * mean(fraction whose TOP choice is e);
        # 1.0 when uniform. Over all E router outputs: it needs no expert.
        vf = valid.astype(jnp.float32)
        nv = jnp.maximum(vf.sum(), 1.0)
        top1 = jax.nn.one_hot(eidx[:, 0], E, dtype=jnp.float32) * vf[:, None]
        aux = E * jnp.sum((probs * vf[:, None]).sum(0) / nv * (top1.sum(0) / nv))
        self.sow("losses", "router_aux", cfg.router_aux_coef * aux)

        def with_shared(y):
            """The routed result plus what every token gets alike."""
            y = y.reshape(B, L, D)
            if not cfg.n_shared_experts:
                return y
            with jax.named_scope("moe_shared"):
                return y + QwenMLP(cfg, self.dtype, cfg.n_shared_experts * F,
                                   name="shared_expert")(x)

        if cfg.moe_capacity_factor is None:
            return with_shared(
                self._dropless(xf, gates, eidx, valid, w_gate, w_up, w_down))

        C = max(1, int(-(-S // E) * cfg.moe_capacity_factor))
        expert_mask = (
            jax.nn.one_hot(eidx, E, dtype=jnp.int32) * valid[:, None, None]
        )  # (S, K, E)
        # Slot assignment: rank-k choices claim capacity only after every
        # rank-(k-1) choice (transpose K to the front before the cumsum),
        # so a token's primary expert is never evicted by another token's
        # secondary pick.
        flat = expert_mask.transpose(1, 0, 2).reshape(K * S, E)
        pos = (jnp.cumsum(flat, axis=0) * flat - 1).reshape(K, S, E).transpose(1, 0, 2)
        slot = (pos * expert_mask).sum(-1)  # (S, K)
        keep = (slot >= 0) & (slot < C) & (valid[:, None] > 0)
        slot = jnp.clip(slot, 0, C - 1)

        # Accumulate (S, E, C) dispatch/combine one rank at a time: the
        # fused 4-D (S, K, E, C) one-hot product is K x larger than the
        # routing tensors themselves and XLA does not reliably fuse it
        # away — at long-context S it alone could OOM the HBM.
        dispatch = jnp.zeros((S, E, C), x.dtype)
        combine = jnp.zeros((S, E, C), x.dtype)
        for kk in range(K):
            d = (
                jax.nn.one_hot(eidx[:, kk], E, dtype=x.dtype)
                * keep[:, kk, None].astype(x.dtype)
            )[:, :, None] * jax.nn.one_hot(slot[:, kk], C, dtype=x.dtype)[:, None, :]
            dispatch = dispatch + d
            combine = combine + gates[:, kk].astype(x.dtype)[:, None, None] * d

        expert_in = jnp.einsum("sec,sd->ecd", dispatch, xf)  # all-to-all boundary
        if self.expert_axis is not None and self.expert_axis in _ctx_mesh_axes():
            from jax.lax import with_sharding_constraint
            from jax.sharding import PartitionSpec as P

            expert_in = with_sharding_constraint(
                expert_in, P(self.expert_axis, None, None)
            )
        h = nn.silu(
            jnp.einsum("ecd,edf->ecf", expert_in, w_gate.astype(self.dtype))
        ) * jnp.einsum("ecd,edf->ecf", expert_in, w_up.astype(self.dtype))
        expert_out = jnp.einsum("ecf,efd->ecd", h, w_down.astype(self.dtype))
        return with_shared(jnp.einsum("sec,ecd->sd", combine, expert_out))

    def _dropless(self, xf, gates, eidx, valid, w_gate, w_up, w_down):
        """Every routed (token, expert) pair whose expert is held here,
        none dropped: sort the pairs by expert, one grouped product a
        projection over the experts held, weighted sum back per token.
        The pairs of absent experts sort last and are never computed:
        what those experts would have added is left out (the chip's share
        of an expert-parallel layer, without its exchange)."""
        cfg = self.cfg
        S, K = eidx.shape
        held = cfg.experts_here
        with jax.named_scope("moe_route"):
            local = eidx - cfg.moe_first_expert
            here = (local >= 0) & (local < held) & (valid[:, None] > 0)
            group = jnp.where(here, local, held).reshape(S * K)
            order = jnp.argsort(group, stable=True).astype(jnp.int32)
            inv = jnp.zeros_like(order).at[order].set(
                jnp.arange(S * K, dtype=jnp.int32), unique_indices=True)
            sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
            n_here = sizes.sum()
            n_valid = jnp.maximum(valid.sum(), 1)
            self.sow("counters", "expert_load_max_over_mean",
                     sizes.max() * held / jnp.maximum(n_here, 1).astype(jnp.float32))
            self.sow("counters", "expert_picks_here_share",
                     100.0 * n_here / (n_valid * K).astype(jnp.float32))
            self.sow("counters", "expert_pairs_per_held_expert",
                     n_here.astype(jnp.float32) / held)
        with jax.named_scope("moe_experts"):
            xs = _dispatch_rows(xf, order, inv, n_here, K)  # (S*K, D) by expert
            dot = lambda a, w: jax.lax.ragged_dot(a, w.astype(self.dtype), sizes)
            h = nn.silu(dot(xs, w_gate)) * dot(xs, w_up)
            ys = dot(h, w_down)
        with jax.named_scope("moe_combine"):
            ys = _live(ys, n_here)  # before the gates: unwritten rows may hold anything
            ys = ys * gates.reshape(S * K)[order].astype(ys.dtype)[:, None]
            return _combine_rows(ys, order, inv, K)


# The rows of a token's K pairs, in expert order, and back: a pair of
# gathers that are each other's transpose (``order`` is a permutation of the
# S*K pairs and ``inv`` its inverse), so neither direction scatters. Rows
# from ``n_here`` on belong to experts held elsewhere: a grouped product
# neither reads nor writes them, so whatever is summed back per token is
# zeroed there first (`_live`), and nothing else needs to be.


def _live(rows, n_here):
    keep = jnp.arange(rows.shape[0], dtype=jnp.int32) < n_here
    return jnp.where(keep[:, None], rows, jnp.zeros((), rows.dtype))


def _gather_pairs(xf, order, K):
    return xf[order // K]


def _sum_pairs(rows, inv, K):
    P, D = rows.shape
    return rows[inv].reshape(P // K, K, D).sum(axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _dispatch_rows(xf, order, inv, n_here, K):
    return _gather_pairs(xf, order, K)


def _dispatch_fwd(xf, order, inv, n_here, K):
    return _gather_pairs(xf, order, K), (inv, n_here)


def _dispatch_bwd(K, res, g):
    inv, n_here = res
    return _sum_pairs(_live(g, n_here), inv, K), None, None, None


_dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _combine_rows(rows, order, inv, K):
    """``rows`` already zeroed from ``n_here`` on."""
    return _sum_pairs(rows, inv, K)


def _combine_fwd(rows, order, inv, K):
    return _sum_pairs(rows, inv, K), order


def _combine_bwd(K, order, g):
    return _gather_pairs(g, order, K), None, None


_combine_rows.defvjp(_combine_fwd, _combine_bwd)


def collect_moe_aux(mutables) -> jnp.ndarray:
    """Sum every ``router_aux`` value sown during an
    ``apply(..., mutable=["losses"])`` forward (0.0 for dense models).
    Accepts any Mapping (older flax returns FrozenDict, not dict)."""
    from collections.abc import Mapping

    leaves = []

    def walk(tree):
        if isinstance(tree, Mapping):
            for k, v in tree.items():
                if k == "router_aux":
                    leaves.extend(v if isinstance(v, (tuple, list)) else [v])
                else:
                    walk(v)

    walk(mutables.get("losses", {}) if isinstance(mutables, Mapping) else {})
    return sum(leaves) if leaves else jnp.asarray(0.0)


def collect_counters(mutables) -> dict:
    """Mean over the layers of every value sown into the ``counters``
    collection during an ``apply(..., mutable=["counters"])`` forward:
    ``expert_load_max_over_mean``, ``expert_picks_here_share`` (%),
    ``expert_pairs_per_held_expert``, ``sparse_keys_kept_share`` (%),
    ``kda_state_keep_share`` (%). Empty for a dense model."""
    from collections.abc import Mapping

    found: dict = {}

    def walk(tree):
        for k, v in tree.items():
            if isinstance(v, Mapping):
                walk(v)
            else:
                found.setdefault(k, []).extend(
                    v if isinstance(v, (tuple, list)) else [v])

    if isinstance(mutables, Mapping):
        walk(mutables.get("counters", {}))
    return {k: sum(v) / len(v) for k, v in found.items()}


class QwenBlock(nn.Module):
    cfg: QwenConfig
    dtype: jnp.dtype = jnp.float32
    ring_axis: Optional[str] = None
    ring_size: int = 1
    expert_axis: Optional[str] = None
    # 0-based index in the stack: the block asks the config for its kinds
    # (`QwenConfig.mixer_kind` / `mlp_kind`).
    layer: int = 0

    @nn.compact
    def __call__(self, x, positions, attn_bias, cache=None, ring_kv_valid=None,
                 token_mask=None, key_valid=None):
        """Mixer then MLP, each composed from the layer's kinds and the
        config: dense or learned-sparse attention (``cfg.sparse_topk``),
        Kimi Delta Attention or NoPE latent attention; SwiGLU, capacity MoE
        or dropless MoE (``cfg.num_experts``, ``moe_capacity_factor``).
        ``key_valid`` (B, keys) marks the real keys for sparse and latent
        attention, which build no additive bias (``attn_bias`` is unused
        there); ``token_mask`` (B, L) the block's real tokens, for the
        experts and for the recurrent state."""
        h = RMSNorm(self.cfg.hidden_size, self.cfg.rms_norm_eps, name="input_layernorm")(x)
        h = h.astype(self.dtype)
        mixer = self.cfg.mixer_kind(self.layer)
        if mixer == "kda":
            from genrec_tpu.models.backbones.kda import KimiDeltaAttention

            h, new_cache = KimiDeltaAttention(self.cfg, self.dtype, name="kda")(
                h, token_mask, cache)
        elif mixer == "mla":
            from genrec_tpu.models.backbones.mla import LatentAttention

            h, new_cache = LatentAttention(self.cfg, self.dtype, name="mla")(
                h, key_valid, cache)
        else:
            h, new_cache = QwenAttention(
                self.cfg, self.dtype, self.ring_axis, self.ring_size, name="self_attn"
            )(h, positions, attn_bias, cache, ring_kv_valid, key_valid)
        x = x + h
        h = RMSNorm(self.cfg.hidden_size, self.cfg.rms_norm_eps, name="post_attention_layernorm")(x)
        if self.cfg.mlp_kind(self.layer) == "moe":
            x = x + QwenMoEMLP(self.cfg, self.dtype, self.expert_axis, name="moe")(
                h.astype(self.dtype), token_mask
            )
        else:
            x = x + QwenMLP(self.cfg, self.dtype, name="mlp")(h.astype(self.dtype))
        return x, new_cache


class QwenLM(nn.Module):
    cfg: QwenConfig
    dtype: jnp.dtype = jnp.float32
    # Rematerialize each block's activations in the backward pass — trades
    # FLOPs for HBM, the standard lever for 1.5B-scale training on one
    # chip (reference: gradient_checkpointing_enable, lcrec.py:42-46).
    remat: bool = False
    # Sequence parallelism: set to a mesh axis name (+ its size) and trace
    # __call__ inside a shard_map over that axis — attention becomes ring
    # attention, everything else stays local. See models/lcrec.sp_sft_loss.
    ring_axis: Optional[str] = None
    ring_size: int = 1
    # Expert parallelism: mesh axis the MoE expert stacks are sharded over
    # (only meaningful with cfg.num_experts > 0).
    expert_axis: Optional[str] = None

    def setup(self):
        self.embed_tokens = self.param(
            "embed_tokens", nn.initializers.normal(0.02),
            (self.cfg.vocab_size, self.cfg.hidden_size),
        )
        # Under sparse attention a rematerialised block keeps the selected
        # sets (a byte a causal pair) and the attention's output: its
        # backward pass then recomputes neither the indexer and the
        # selection nor a forward pass of the attention it needs only
        # tile by tile (each tile rematerialises its own scores).
        policy = (jax.checkpoint_policies.save_only_these_names(
            "sparse_sel", "sparse_attn_out") if self.cfg.sparse_topk > 0 else None)
        block_cls = (nn.remat(QwenBlock, static_argnums=(), policy=policy)
                     if self.remat else QwenBlock)
        self.blocks = [
            block_cls(
                self.cfg, self.dtype, self.ring_axis, self.ring_size,
                self.expert_axis, i, name=f"layer_{i}",
            )
            for i in range(self.cfg.num_hidden_layers)
        ]
        self.norm = RMSNorm(self.cfg.hidden_size, self.cfg.rms_norm_eps, name="norm")
        if not self.cfg.tie_word_embeddings:
            self.lm_head = self.param(
                "lm_head", nn.initializers.normal(0.02),
                (self.cfg.vocab_size, self.cfg.hidden_size),
            )

    def _head(self, h):
        w = self.embed_tokens if self.cfg.tie_word_embeddings else self.lm_head
        return h @ w.T.astype(self.dtype)

    def __call__(self, input_ids, attention_mask=None, positions=None,
                 return_hidden: bool = False, compute_logits: bool = True):
        """Full-sequence forward. attention_mask: (B, L) 1=valid.

        compute_logits=False skips the (L, vocab) LM-head matmul — the
        dominant cost for embedding-only uses (NoteLLM) where only the
        hidden states are consumed.
        """
        B, L = input_ids.shape
        if positions is None:
            # NOTE: inside a shard_map (ring_axis set) this default is the
            # LOCAL arange — sequence-parallel callers must pass global
            # positions explicitly (models/lcrec.sp_sft_loss does).
            positions = jnp.broadcast_to(jnp.arange(L), (B, L))
        if self.ring_axis is not None:
            # Causality + padding are handled inside ring attention (global
            # positions from the ring indices; kv validity rotates with the
            # blocks) — no L x L bias is ever materialized.
            bias = None
            ring_valid = (
                None if attention_mask is None else attention_mask.astype(bool)
            )
        elif not self.cfg.dense_attention_layers:
            # Selection, causality and padding are masks of a query tile
            # inside sparse_attention / causal_attention, and a recurrent
            # layer reads the token mask: no (L, L) bias either.
            bias = None
            ring_valid = None
        else:
            bias = causal_pad_bias(L, attention_mask)
            ring_valid = None

        x = self.embed_tokens[input_ids].astype(self.dtype)
        for block in self.blocks:
            x, _ = block(
                x, positions, bias, ring_kv_valid=ring_valid,
                token_mask=attention_mask, key_valid=attention_mask,
            )
        h = self.norm(x).astype(self.dtype)
        logits = self._head(h) if compute_logits else None
        if return_hidden:
            return logits, h
        return logits

    # ---- KV-cache decode ---------------------------------------------------

    def init_cache(self, batch_size: int, max_len: int):
        """Per layer, by its mixer: K and V (and, under sparse attention,
        the indexer's keys ``ki`` beside them: the selection runs over the
        cache's slots); a latent row a token (``latent``); or a
        constant-size recurrent state (``s``, ``conv``). Every layer keeps
        ``idx``, the next slot to write, and every other entry has the
        batch in front (the beam's reorder relies on it)."""
        from genrec_tpu.models.backbones.kda import init_kda_cache
        from genrec_tpu.models.backbones.mla import init_mla_cache

        cfg = self.cfg
        kv = (batch_size, max_len, cfg.num_key_value_heads, cfg.head_dim)

        def layer(kind):
            if kind == "kda":
                return init_kda_cache(cfg, batch_size)
            if kind == "mla":
                return init_mla_cache(cfg, batch_size, max_len, self.dtype)
            c = {"k": jnp.zeros(kv, self.dtype), "v": jnp.zeros(kv, self.dtype),
                 "idx": jnp.asarray(0, jnp.int32)}
            if cfg.sparse_topk > 0:
                c["ki"] = jnp.zeros(
                    (batch_size, max_len, cfg.indexer_head_dim), jnp.float32)
            return c

        return [layer(cfg.mixer_kind(i)) for i in range(cfg.num_hidden_layers)]

    def decode_step(self, input_ids, positions, caches, pad_mask):
        """Advance by input_ids.shape[1] tokens against a static cache.

        pad_mask: (B, S) 1 = valid cache slot (after this step's write).
        Returns (logits_at_last, new_caches).
        """
        B, L = input_ids.shape
        S = pad_mask.shape[1]
        bias = None
        if self.cfg.dense_attention_layers:
            # Bias over cache slots: mask invalid slots; also causal within
            # the newly-written block.
            slot = jnp.arange(S)[None, None, None, :]
            write_pos = caches[0]["idx"] + jnp.arange(L)
            causal = jnp.where(slot > write_pos[None, None, :, None], -1e9, 0.0)
            bias = causal + jnp.where(pad_mask[:, None, None, :] == 0, -1e9, 0.0)

        x = self.embed_tokens[input_ids].astype(self.dtype)
        # Validity of the CURRENT block's tokens (pad_mask covers cache
        # slots): without it, prefilling a padded prompt would let pad
        # tokens claim MoE capacity that training denies them.
        token_mask = jax.lax.dynamic_slice_in_dim(
            pad_mask, caches[0]["idx"], L, axis=1
        )
        new_caches = []
        for block, cache in zip(self.blocks, caches):
            x, nc = block(x, positions, bias, cache, token_mask=token_mask,
                          key_valid=pad_mask)
            new_caches.append(nc)
        h = self.norm(x).astype(self.dtype)
        return self._head(h)[:, -1, :], new_caches


    # ---- serving through pages (models/lcrec.py) ---------------------------

    def prefill_cached(self, input_ids, attention_mask):
        """A left-padded prompt against fresh caches of exactly its length:
        `decode_step` from slot 0 with the head on the last position only.
        Returns (logits (B, V), caches): by layer K and V (B, L, KV, hd) of
        every prompt slot, or a KDA layer's end state and its convolutions'
        last inputs. No (L, L) bias is built, whatever the layers: a prompt
        is as long as the history bucket, so a full-attention layer runs
        `causal_attention`'s query tiles (of ``sparse_chunk``) over the mask."""
        B, L = input_ids.shape
        positions = jnp.maximum(jnp.cumsum(attention_mask, axis=1) - 1, 0)
        x = self.embed_tokens[input_ids].astype(self.dtype)
        new_caches = []
        for block, cache in zip(self.blocks, self.init_cache(B, L)):
            x, nc = block(x, positions, None, cache, token_mask=attention_mask,
                          key_valid=attention_mask)
            new_caches.append(nc)
        h = self.norm(x[:, -1:]).astype(self.dtype)
        return self._head(h)[:, 0, :], new_caches

    def decode_paged(self, input_ids, positions, caches, token_mask):
        """One token a row against per-layer serving caches: a full-attention
        layer's `gqa_decode_paged` cache, a KDA layer's ``s``/``conv``.
        input_ids, positions, token_mask (N, 1) -> (logits (N, V), caches)."""
        x = self.embed_tokens[input_ids].astype(self.dtype)
        new_caches = []
        for block, cache in zip(self.blocks, caches):
            x, nc = block(x, positions, None, cache, token_mask=token_mask)
            new_caches.append(nc)
        h = self.norm(x).astype(self.dtype)
        return self._head(h)[:, -1, :], new_caches


def params_from_hf_state_dict(sd: dict, cfg: QwenConfig) -> dict:
    """Convert an HF Qwen2ForCausalLM state dict (numpy arrays) into this
    module's param tree. Dense models only: HF Qwen2-MoE checkpoints use
    per-expert ``mlp.experts.*`` keys this converter does not map yet."""
    if cfg.num_experts > 0:
        raise NotImplementedError(
            "params_from_hf_state_dict maps dense Qwen2 checkpoints; "
            "MoE (cfg.num_experts > 0) key mapping is not implemented"
        )
    lin = lambda p, bias: (
        {"kernel": sd[p + ".weight"].T, "bias": sd[p + ".bias"]}
        if bias
        else {"kernel": sd[p + ".weight"].T}
    )
    params = {
        "embed_tokens": sd["model.embed_tokens.weight"],
        "norm": {"weight": sd["model.norm.weight"]},
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = sd["lm_head.weight"]
    for i in range(cfg.num_hidden_layers):
        p = f"model.layers.{i}"
        params[f"layer_{i}"] = {
            "self_attn": {
                "q_proj": lin(f"{p}.self_attn.q_proj", True),
                "k_proj": lin(f"{p}.self_attn.k_proj", True),
                "v_proj": lin(f"{p}.self_attn.v_proj", True),
                "o_proj": lin(f"{p}.self_attn.o_proj", False),
            },
            "mlp": {
                "gate_proj": lin(f"{p}.mlp.gate_proj", False),
                "up_proj": lin(f"{p}.mlp.up_proj", False),
                "down_proj": lin(f"{p}.mlp.down_proj", False),
            },
            "input_layernorm": {"weight": sd[f"{p}.input_layernorm.weight"]},
            "post_attention_layernorm": {"weight": sd[f"{p}.post_attention_layernorm.weight"]},
        }
    return params
