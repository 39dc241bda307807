"""Kimi Delta Attention (KDA): a gated delta-rule linear-attention mixer with
a PER-CHANNEL forget gate (Kimi Linear, arXiv:2510.26692).
With ``QwenConfig.kda_neg_eigval`` the write strength below is 2 * sigmoid
instead of sigmoid: b_t in (0, 2), so I - b_t k_t k_t^T has the eigenvalue
1 - b_t in (-1, 1) along the unit key. Nothing else changes, in the
recurrent step and the chunked form alike.

One head keeps a state S (K x V, float32), zero before the row:

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T,   o_t = S_t^T q_t

with a_t = exp(g_t) in (0, 1)^K the forget gate of each key channel and b_t in
(0, 1) the write strength. `kda_recurrent_step` is that line, one token (the
decode cache's step). `kda_chunked` is the same recurrence regrouped a chunk
of C tokens at a time, no term dropped and no clamp on g. With G_i the
running sum of g inside the chunk and S_0 the state it starts from,

    S_t = Diag(a_t) S_{t-1} + k_t u_t^T,  u_t = b_t (v_t - S_{t-1}^T (a_t * k_t))
    A_ij = b_i sum_c k_ic k_jc exp(G_ic - G_jc)            (j < i)
    (I + A) U = b * (V - (K * exp(G)) S_0)                 (unit lower triangular)
    o_i = S_0^T (q_i * exp(G_i)) + sum_{j <= i} u_j sum_c q_ic k_jc exp(G_ic - G_jc)
    S_C = Diag(exp(G_C)) S_0 + sum_j (k_j * exp(G_C - G_j)) u_j^T

The per-channel decay between two tokens of a chunk enters only as the
PAIRWISE difference exp(G_i - G_j), j <= i, which never passes 1: the
factorised form (k_i exp(G_i)) . (k_j exp(-G_j)) that a scalar gate allows
overflows float32 here, where -G passes 88 inside 64 tokens. The pairwise
tensor is (C, C, K) a head, which is why the chunk is small.

Only the last three lines read S_0, so `kda_chunked` runs a row in GROUPS of
m chunks, three stages a group (`_group`), all float32:

1. `_state_free`, a chunk at a time: G, the pairwise sums for the rows k (A
   before b) and q (the q-k products of the o line), the right-hand side
   b * [V, K * exp(G)], q * exp(G), k * exp(G_C - G), exp(G_C). The decay
   exp(G_i - G_j) times k_j, (C, C, K) a row and head, is summed against
   k_i and q_i in the fusion that makes it; written out it would be 67 MB a
   chunk at Kimi-Linear's training shape (B 1, H 32, K 128; 8.6 GB over a
   row's 128 chunks) and 268 MB a chunk at Solar-Open2's two-row prefill
   (B 2, H 64), so this stage stays a loop over chunks and never sees a row.
2. ONE `solve_triangular` over the m * B * H systems (I + A) X = rhs, 64 x 64
   with 256 right-hand columns. On the TPU the solve inverts the block in a
   kernel that lays the BATCH along the 128 lanes, so a call on one chunk's
   32 heads costs what a call on 128 systems costs (86 us: a row's 4,096
   systems take 11.0 ms in 128 calls and 2.7 ms in 32). `_GROUP_BYTES`, the
   (C, C, K) decay a group may stand for, is set to what makes 128 systems
   at C 64, K 128, whatever B and H: 4 chunks a group at Kimi's shape, 2 and
   1 at Solar's one- and two-row prefill. Larger groups read slower on the
   chip.
3. `_state_step`, a chunk at a time, all that reads the state: u = X_v - X_k S,
   o = (q * exp(G)) S + qk u, S' = S * exp(G_C) + k_end^T u. Four products
   and two elementwise lines; no exponential and no solve.

A group hands from stage to stage the pairwise sums (m B H 2C C), the
right-hand side and X (m B H C 2K each), q * exp(G) and k_end (m B H C K
each): 29 MB at Kimi's shape and at both of Solar's. Held for a whole row
they are 940 MB at Kimi's shape (2.35 GB at Solar's two-row 1,024-item
bucket) and a backward pass keeps them, with cotangents as large beside
them; that form was built and its step did not fit the chip (PERF.md
section 6, PR 37). The group loop is rematerialised instead, as the chunk
loop was, and keeps the state at each group's start (67 MB a layer at Kimi's
shape, where the chunk loop kept 268).
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from genrec_tpu.models.backbones.qwen import QwenConfig

_HIGHEST = jax.lax.Precision.HIGHEST
_CHUNK = 64  # tokens a chunk: the pairwise decay is (C, C, K) a head
_GROUP_BYTES = 1 << 28  # float32 (C, C, K) decay a group of chunks: 128 systems a solve at K = 128
_CONV_KERNEL = 4  # the published short_conv_kernel_size


def causal_conv(u, w, prefix=None):
    """Depthwise causal convolution, one filter a channel, no bias:
    c_t = sum_j w_j u_{t-(k-1)+j}. u (B, L, C), w (k, C); ``prefix``
    (B, k-1, C) holds the inputs before the block (zeros at a row's start).
    Returns c and the last k-1 inputs (the next block's prefix)."""
    k, L = w.shape[0], u.shape[1]
    if prefix is None:
        prefix = jnp.zeros((u.shape[0], k - 1, u.shape[2]), u.dtype)
    ext = jnp.concatenate([prefix, u], axis=1)
    c = sum(w[j] * ext[:, j:j + L] for j in range(k))
    return c, ext[:, L:]


def unit_vector(x):
    """x / |x| over the last axis; the zero vector (padding) stays zero."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def forget_gate(f, a_log, dt_bias):
    """g = -exp(A_log) * softplus(f + dt_bias) <= 0, one for every head AND
    channel. f (B, L, H, K), a_log (H,), dt_bias (H, K)."""
    return -jnp.exp(a_log)[:, None] * jax.nn.softplus(f + dt_bias)


def kda_recurrent_step(q, k, v, g, b, s):
    """One token of the recurrence. q, k, g (B, H, K); v (B, H, V);
    b (B, H); s (B, H, K, V) float32 -> (o (B, H, V), s)."""
    s = s * jnp.exp(g)[..., None]
    u = b[..., None] * (v - jnp.einsum("bhk,bhkv->bhv", k, s, precision=_HIGHEST))
    s = s + k[..., None] * u[..., None, :]
    return jnp.einsum("bhk,bhkv->bhv", q, s, precision=_HIGHEST), s


def _state_free(x):
    """Stage 1's loop body, one chunk, every row and head at once: all that
    does not read the state. q, k, g (B, C, H, K); v (B, C, H, V); b (B, C, H)
    -> heads first: the pairwise sums (B, H, 2C, C), the right-hand side
    (B, H, C, V + K), q * exp(G) and k decayed to the chunk's end (B, H, C, K),
    exp(G_C) (B, H, K, 1)."""
    q, k, v, g, b = x
    C = q.shape[1]
    G = jnp.cumsum(g, axis=1)
    # sum_c row_ic k_jc exp(G_ic - G_jc) for j <= i, the rows being k then q.
    # The (C, C, K) decay times k_j is made once and summed against both; above
    # the diagonal the difference is positive and never exponentiated
    i, j = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    decay = jnp.exp(jnp.where((j <= i)[None, :, :, None, None],
                              G[:, :, None] - G[:, None], -jnp.inf))
    kd = decay * k[:, None]  # (B, C, C, H, K)
    pair = jnp.concatenate([jnp.sum(r[:, :, None] * kd, axis=-1) for r in (k, q)],
                           axis=1)  # (B, 2C, C, H)
    eG = jnp.exp(G)
    heads_first = lambda a: a.transpose(0, 2, 1, 3)  # (B, H, C, .)
    rhs = b.transpose(0, 2, 1)[..., None] * jnp.concatenate(
        [heads_first(v), heads_first(k * eG)], axis=-1)
    k_end = heads_first(k * jnp.exp(G[:, -1:] - G))  # decayed to the chunk's end
    return (pair.transpose(0, 3, 1, 2), rhs, heads_first(q * eG), k_end,
            heads_first(eG[:, -1:]).swapaxes(-1, -2))


def _state_step(s, x):
    """Stage 3's loop body, one chunk: all that reads the state. s (B, H, K, V);
    sol (B, H, C, V + K) the solve's [X_v, X_k]; qk (B, H, C, C); qe, k_end
    (B, H, C, K); eGC (B, H, K, 1) -> s and o (B, C, H, V)."""
    sol, qk, qe, k_end, eGC = x
    V = s.shape[-1]
    mm = lambda a, b_: jnp.matmul(a, b_, precision=_HIGHEST)
    u = sol[..., :V] - mm(sol[..., V:], s)  # (B, H, C, V)
    o = mm(qe, s) + mm(qk, u)
    return s * eGC + mm(k_end.swapaxes(-1, -2), u), o.transpose(0, 2, 1, 3)


def _group(s, x):
    """One group of m chunks: stage 1 a chunk at a time, stage 2 over all m
    at once, stage 3 a chunk at a time. s (B, H, K, V); q, k, g
    (m, B, C, H, K); v (m, B, C, H, V); b (m, B, C, H) -> s and o
    (m, B, C, H, V)."""
    *_, b = x
    pair, rhs, qe, k_end, eGC = jax.lax.map(jax.checkpoint(_state_free), x)
    C = rhs.shape[-2]
    # A_ij = b_i sum_c k_ic k_jc ...: scaled HERE, so that b's gradient reads
    # the group's own pair and stage 1's backward need not sum it again
    A = pair[..., :C, :] * b.swapaxes(2, 3)[..., None]
    # (I + A) X = rhs, every chunk, row and head one batch axis: the diagonal
    # is taken as 1, and neither it nor what lies above it is read
    sol = jax.scipy.linalg.solve_triangular(
        A.reshape((-1, C, C)), rhs.reshape((-1,) + rhs.shape[-2:]),
        lower=True, unit_diagonal=True).reshape(rhs.shape)
    return jax.lax.scan(_state_step, s, (sol, pair[..., C:, :], qe, k_end, eGC))


def kda_chunked(q, k, v, g, b, chunk: int = _CHUNK, s0=None):
    """The recurrence over a whole block of L tokens, ``chunk`` at a time, in
    groups of chunks sized by `_GROUP_BYTES` (the module docstring's three
    stages), each group rematerialised in the backward pass (what it keeps
    is the state at each group's start). q, k, g (B, L, H, K); v (B, L, H, V);
    b (B, L, H); all float32, g = 0 and b = 0 at padding (the state passes
    through). Returns o (B, L, H, V) and the final state (B, H, K, V)."""
    B, L, H, K = q.shape
    V = v.shape[-1]
    if s0 is None:
        s0 = jnp.zeros((B, H, K, V), jnp.float32)
    n = -(-L // chunk)
    # chunks whose pairwise decay (C, C, K float32 a row and head) fits the budget
    groups = -(-n // max(1, _GROUP_BYTES // (4 * B * H * chunk * chunk * K)))
    m = -(-n // groups)

    def grouped(a):
        a = jnp.pad(a, [(0, 0), (0, groups * m * chunk - L)] + [(0, 0)] * (a.ndim - 2))
        a = a.reshape((B, groups, m, chunk) + a.shape[2:])
        return jnp.moveaxis(a, 0, 2)  # (groups, m, B, chunk, ...)

    s, o = jax.lax.scan(jax.checkpoint(_group), s0,
                        tuple(grouped(a) for a in (q, k, v, g, b)))
    o = jnp.moveaxis(o.reshape((groups * m, B, chunk, H, V)), 0, 1)  # (B, n, C, H, V)
    return o.reshape((B, -1, H, V))[:, :L], s


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """Inverse softplus of a log-uniform step in [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, np.log(1e-3), np.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


# The elementwise chains around the scan, each rematerialised: what a layer's
# backward pass keeps of them is their bf16 inputs (the projections' outputs),
# not the float32 intermediates (0.4 GB each at 8,192 x 12,288).


@functools.partial(jax.checkpoint, static_argnums=(3,))
def _conv_heads(u, w, prefix, head_dim: int):
    """Projection outputs (B, L, 3HK) -> q, k, v (B, L, H, K) float32: causal
    convolution, SiLU, and the unit norm of q (scaled) and k."""
    c, conv = causal_conv(u.astype(jnp.float32), w, prefix)
    q, k, v = (a.reshape(a.shape[:2] + (-1, head_dim))
               for a in jnp.split(jax.nn.silu(c), 3, axis=-1))
    return unit_vector(q) * head_dim ** -0.5, unit_vector(k), v, conv


@functools.partial(jax.checkpoint, static_argnums=(5,))
def _gates(f, a_log, dt_bias, b, m, b_scale: float = 1.0):
    """Forget gate g (B, L, H, K) and write strength (B, L, H), float32,
    both zero at padding. f (B, L, HK), b (B, L, H) pre-activations;
    ``b_scale`` 2 lets the transition's eigenvalue along the key go
    negative (``QwenConfig.kda_neg_eigval``)."""
    f = f.astype(jnp.float32).reshape(f.shape[:2] + dt_bias.shape)
    g = forget_gate(f, a_log, dt_bias) * m[:, :, None, None]
    b = jax.nn.sigmoid(b.astype(jnp.float32)) * m[:, :, None]
    return g, b if b_scale == 1.0 else b * b_scale


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def _gated_norm(o, gate, weight, eps: float, dtype):
    """RMSNorm over each head's channels (one learned scale), times the
    sigmoid output gate. o (B, L, H, K) float32, gate (B, L, HK)."""
    from genrec_tpu.ops.normalize import rms_norm

    o = rms_norm(o, weight, eps).reshape(gate.shape)
    return (o * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dtype)


class _Weight(nn.Module):
    """A norm's learned scale as a leaf named ``weight``."""

    dim: int

    @nn.compact
    def __call__(self):
        return self.param("weight", nn.initializers.ones, (self.dim,))


class KimiDeltaAttention(nn.Module):
    """The KDA mixer of one layer. ``token_mask`` (B, L) marks the real
    tokens: padding writes nothing and forgets nothing. ``cache``:
    ``s`` (B, H, K, K) float32 state, ``conv`` (B, kernel-1, 3HK) the last
    inputs of the convolutions, ``idx`` the tokens seen: constant size."""

    cfg: QwenConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, token_mask=None, cache=None):
        cfg = self.cfg
        B, L, D = x.shape
        H, K, kw = cfg.kda_heads, cfg.kda_head_dim, _CONV_KERNEL
        m = (jnp.ones((B, L), jnp.float32) if token_mask is None
             else token_mask.astype(jnp.float32))
        x = x * m[..., None].astype(x.dtype)
        dense = lambda n, name, bias=False: nn.Dense(
            n, use_bias=bias, dtype=self.dtype, name=name)

        with jax.named_scope("kda_proj"):
            u = jnp.concatenate(
                [dense(H * K, n)(x) for n in ("q_proj", "k_proj", "v_proj")], axis=-1)
        with jax.named_scope("kda_conv"):
            w = jnp.concatenate(
                [self.param(n, nn.initializers.lecun_normal(), (kw, H * K))
                 for n in ("q_conv", "k_conv", "v_conv")], axis=-1)
            q, k, v, conv = _conv_heads(
                u, w, None if cache is None else cache["conv"], K)
        with jax.named_scope("kda_gate"):
            g, b = _gates(dense(H * K, "f_b_proj")(dense(K, "f_a_proj")(x)),
                          self.param("A_log", _a_log_init, (H,)),
                          self.param("dt_bias", _dt_bias_init, (H, K)),
                          dense(H, "b_proj")(x), m,
                          2.0 if cfg.kda_neg_eigval else 1.0)
            if cache is None:
                keep = jnp.sum(jnp.exp(g) * m[:, :, None, None])
                self.sow("counters", "kda_state_keep_share",
                         100.0 * keep / jnp.maximum(m.sum() * H * K, 1.0))
        with jax.named_scope("kda_scan"):
            s0 = None if cache is None else cache["s"]
            if cache is not None and L == 1:
                o, s = kda_recurrent_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                          b[:, 0], s0)
                o = o[:, None]
            else:
                o, s = kda_chunked(q, k, v, g, b, s0=s0)
        with jax.named_scope("kda_out"):
            gate = dense(H * K, "g_b_proj", bias=True)(dense(K, "g_a_proj")(x))
            y = dense(D, "o_proj")(_gated_norm(
                o, gate, _Weight(K, name="o_norm")(), cfg.rms_norm_eps, self.dtype))
        new_cache = None
        if cache is not None:
            new_cache = {"s": s, "conv": conv, "idx": cache["idx"] + L}
        return y, new_cache


def init_kda_cache(cfg: QwenConfig, batch_size: int):
    H, K = cfg.kda_heads, cfg.kda_head_dim
    return {"s": jnp.zeros((batch_size, H, K, K), jnp.float32),
            "conv": jnp.zeros((batch_size, _CONV_KERNEL - 1, 3 * H * K),
                              jnp.float32),
            "idx": jnp.asarray(0, jnp.int32)}
