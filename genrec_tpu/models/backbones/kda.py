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


def _chunk(s, x):
    """One chunk, every row and head at once. s (B, H, K, V); q, k, g
    (B, C, H, K); v (B, C, H, V); b (B, C, H)."""
    q, k, v, g, b = x
    C = q.shape[1]
    G = jnp.cumsum(g, axis=1)
    # sum_c row_ic k_jc exp(G_ic - G_jc) for j <= i, the rows being k then q:
    # one pass over the (2C, C, K) pairs; above the diagonal the difference
    # is positive and never exponentiated
    rows = jnp.concatenate([k, q], axis=1)
    diff = jnp.concatenate([G, G], axis=1)[:, :, None] - G[:, None]
    i, j = jnp.arange(2 * C)[:, None] % C, jnp.arange(C)[None, :]
    decay = jnp.exp(jnp.where((j <= i)[None, :, :, None, None], diff, -jnp.inf))
    pair = jnp.sum(rows[:, :, None] * decay * k[:, None], axis=-1)  # (B, 2C, C, H)
    pair = pair.transpose(0, 3, 1, 2)  # (B, H, 2C, C)
    bh = b.transpose(0, 2, 1)  # (B, H, C)
    A = jnp.tril(pair[:, :, :C], -1) * bh[..., None]
    qk = jnp.tril(pair[:, :, C:])
    eG = jnp.exp(G)
    heads_first = lambda a: a.transpose(0, 2, 1, 3)  # (B, H, C, .)
    rhs = bh[..., None] * jnp.concatenate(
        [heads_first(v), heads_first(k * eG)], axis=-1)
    sol = jax.scipy.linalg.solve_triangular(
        jnp.eye(C, dtype=A.dtype) + A, rhs, lower=True, unit_diagonal=True)
    V = v.shape[-1]
    mm = lambda a, b_: jnp.matmul(a, b_, precision=_HIGHEST)
    u = sol[..., :V] - mm(sol[..., V:], s)  # (B, H, C, V)
    o = mm(heads_first(q * eG), s) + mm(qk, u)
    k_end = heads_first(k * jnp.exp(G[:, -1:] - G))  # decayed to the chunk's end
    s = s * heads_first(eG[:, -1:]).swapaxes(-1, -2) + mm(k_end.swapaxes(-1, -2), u)
    return s, o.transpose(0, 2, 1, 3)


def kda_chunked(q, k, v, g, b, chunk: int = _CHUNK, s0=None):
    """The recurrence over a whole block of L tokens, ``chunk`` at a time,
    the chunk loop rematerialised in the backward pass (what it keeps is
    the state at each chunk's start). q, k, g (B, L, H, K); v (B, L, H, V);
    b (B, L, H); all float32, g = 0 and b = 0 at padding (the state passes
    through). Returns o (B, L, H, V) and the final state (B, H, K, V)."""
    B, L, H, K = q.shape
    if s0 is None:
        s0 = jnp.zeros((B, H, K, v.shape[-1]), jnp.float32)
    n = -(-L // chunk)

    def chunks(a):
        a = jnp.pad(a, [(0, 0), (0, n * chunk - L)] + [(0, 0)] * (a.ndim - 2))
        return jnp.moveaxis(a.reshape((B, n, chunk) + a.shape[2:]), 1, 0)

    s, o = jax.lax.scan(jax.checkpoint(_chunk), s0,
                        tuple(chunks(a) for a in (q, k, v, g, b)))
    return jnp.moveaxis(o, 0, 1).reshape((B, n * chunk) + o.shape[3:])[:, :L], s


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
