"""Plain reference of TIGER (arXiv:2305.05065) as the repo's gin sizes it.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision: no kernels, no cache, no packing, no batching tricks.
It imports nothing of ``genrec_tpu`` and takes only what the benchmark made
from the seed: the parameter tree (by the names the model publishes), the
raw examples or requests, and the catalog's sem-id table.

Architecture, as published and as the reference repo has it: a T5-style
encoder-decoder over the flattened (item, codebook) token stream with a
hashed user token in front; RMS norms without mean or bias; bias-free
projections; per-layer bidirectional log-bucket relative bias on
self-attention (32 buckets, max distance 128); ReLU feed-forward; decoder
started from a learned BOS; one output head over codebook*depth+1 tokens;
loss = per-sequence SUM of token cross-entropy, mean over sequences.
Departures from the paper are the reference repo's own (no final layer norm,
the unused position tables), noted where they occur.

``mode`` selects the arithmetic: ``"f32"`` is the reference; ``"fp8"`` is the
contract's lower-precision control (the step below the bf16 the
configuration states): every matrix-product operand is rounded to
float8_e4m3fn first. ``"bf16"`` rounds them to bfloat16 instead: the
configuration's own precision in the reference's arithmetic, a witness of what
rounding alone moves (PERF.md). Neither is ever used to judge a run.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e9
NUM_BUCKETS = 32
MAX_DISTANCE = 128
TEMPERATURE = 0.2  # the served beam scores log_softmax(logits / 0.2)


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------


def _q(x, mode):
    if mode == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if mode == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def mm(a, b, mode, spec=None):
    a, b = _q(a, mode), _q(b, mode)
    if spec is None:
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def rms(x, w, eps=1e-6):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rel_buckets(q_pos, k_pos):
    """T5 bidirectional bucket of key_pos - query_pos (int arrays)."""
    rel = np.asarray(k_pos)[None, :] - np.asarray(q_pos)[:, None]
    ret = -rel
    n = NUM_BUCKETS // 2
    sign = (ret < 0).astype(np.int64)
    ret = np.abs(ret)
    max_exact = n // 2
    small = ret < max_exact
    inc = (
        np.log(ret.astype(np.float32) / max_exact + 1e-6)
        / math.log(MAX_DISTANCE / max_exact) * (n - max_exact)
    ).astype(np.int64)
    large = max_exact + np.minimum(inc, n - max_exact - 1)
    return np.where(small, ret, large) + sign * n


def _bias(rel_bias, n_heads, q_pos, k_pos):
    b = rel_buckets(q_pos, k_pos)  # (q, k)
    idx = b[None] + (np.arange(n_heads) * NUM_BUCKETS)[:, None, None]
    return rel_bias[:, 0][idx]  # (H, q, k)


def dropout(x, rate, key):
    if key is None or rate == 0.0:
        return x
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0)


class _Keys:
    """Dropout keys in call order; None when the pass is deterministic."""

    def __init__(self, key):
        self.key = key

    def next(self):
        if self.key is None:
            return None
        self.key, sub = jax.random.split(self.key)
        return sub


def attention(p, xq, xkv, n_heads, mask, bias, mode, rate, keys, cross):
    """mask: (..., q, k) bool, True = may attend."""
    d = xq.shape[-1]
    hd = d // n_heads
    q = mm(xq, p["q"]["kernel"], mode)
    if cross:
        k = mm(xkv, p["k"]["kernel"], mode)
        v = mm(xkv, p["v"]["kernel"], mode)
    else:
        kv = mm(xkv, p["kv"]["kernel"], mode)
        k, v = kv[..., :d], kv[..., d:]
    split = lambda t: t.reshape(t.shape[0], t.shape[1], n_heads, hd)
    q, k, v = split(q), split(k), split(v)
    s = mm(q, k, mode, "bqhd,bkhd->bhqk") * hd ** -0.5
    if bias is not None:
        s = s + bias[None]
    s = jnp.where(mask[:, None], s, NEG)
    a = jax.nn.softmax(s, axis=-1)
    a = dropout(a, rate, keys.next())
    o = mm(a, v, mode, "bhqk,bkhd->bqhd").reshape(xq.shape[0], xq.shape[1], d)
    return mm(o, p["o"]["kernel"], mode)


def ffn(p, x, mode, rate, keys):
    h = jax.nn.relu(mm(x, p["wi"]["kernel"], mode))
    h = dropout(h, rate, keys.next())
    return mm(h, p["wo"]["kernel"], mode)


def block(p, x, n_heads, self_mask, self_bias, mode, rate, keys,
          memory=None, mem_mask=None):
    xn = rms(x, p["norm1"]["weight"])
    h = attention(p["self_attn"], xn, xn, n_heads, self_mask, self_bias,
                  mode, rate, keys, cross=False)
    x = x + dropout(h, rate, keys.next())
    if memory is not None:
        h = attention(p["cross_attn"], rms(x, p["norm_cross"]["weight"]),
                      memory, n_heads, mem_mask, None, mode, rate, keys,
                      cross=True)
        x = x + dropout(h, rate, keys.next())
    h = ffn(p["ff"], rms(x, p["norm2"]["weight"]), mode, rate, keys)
    return x + dropout(h, rate, keys.next())


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def encode(params, cfg, user, hist, n_tok, mode="f32", rate=0.0, keys=None):
    """user (N,), hist (N, L) flattened sem ids (type = pos % depth),
    n_tok (N,) valid history tokens -> memory (N, 1+L, d), valid (N, 1+L)."""
    keys = keys or _Keys(None)
    H = cfg["num_heads"]
    cb, depth = cfg["codebook_size"], cfg["sem_id_dim"]
    N, L = hist.shape
    types = jnp.arange(L) % depth
    sem = params["sem_id_embedding"]["embedding"][types[None] * cb + hist]
    usr = params["user_id_embedding"]["embedding"][
        user % cfg["num_user_embeddings"]]
    x = jnp.concatenate([usr[:, None], sem], axis=1)  # (N, 1+L, e)
    valid = jnp.arange(1 + L)[None] < (1 + n_tok)[:, None]
    x = rms(x, params["norm_context"]["weight"])
    x = dropout(x, rate, keys.next())
    x = mm(x, params["in_proj_context"]["kernel"], mode)
    pos = np.arange(1 + L)
    enc = params["transformer"]["encoder"]
    mask = jnp.broadcast_to(valid[:, None, :], (N, 1 + L, 1 + L))
    for i in range(cfg["n_layers"] // 2):
        lp = enc[f"layer_{i}"]
        bias = _bias(lp["self_attn"]["rel_bias"], H, pos, pos)
        x = block(lp, x, H, mask, bias, mode, rate, keys)
    return x, valid


def decode(params, cfg, memory, valid, dec_tokens, mode="f32", rate=0.0,
           keys=None):
    """dec_tokens (N, T) sem codes fed after BOS (type = position) ->
    logits (N, T+1, V) at BOS and after each fed token."""
    keys = keys or _Keys(None)
    H = cfg["num_heads"]
    cb = cfg["codebook_size"]
    N, T = dec_tokens.shape
    e = params["bos_embedding"].shape[0]
    x = jnp.broadcast_to(params["bos_embedding"], (N, 1, e))
    if T:
        tok = params["sem_id_embedding"]["embedding"][
            jnp.arange(T)[None] * cb + dec_tokens]
        x = jnp.concatenate([x, tok], axis=1)
    x = rms(x, params["norm"]["weight"])
    x = dropout(x, rate, keys.next())
    x = mm(x, params["in_proj"]["kernel"], mode)
    pos = np.arange(T + 1)
    causal = jnp.asarray(pos[None, :] <= pos[:, None])
    self_mask = jnp.broadcast_to(causal[None], (N, T + 1, T + 1))
    mem_mask = jnp.broadcast_to(valid[:, None, :], (N, T + 1, valid.shape[1]))
    dec = params["transformer"]["decoder"]
    for i in range(cfg["n_layers"] // 2):
        lp = dec[f"layer_{i}"]
        bias = _bias(lp["self_attn"]["rel_bias"], H, pos, pos)
        x = block(lp, x, H, self_mask, bias, mode, rate, keys,
                  memory=memory, mem_mask=mem_mask)
    # No final layer norm before the head: the reference repo has none.
    return mm(x, params["output_head"]["kernel"], mode)


# ---------------------------------------------------------------------------
# training: loss, gradients, clipped AdamW
# ---------------------------------------------------------------------------


def batch_loss_sum(params, cfg, block_, mode, rate, key):
    """SUM over the block's real examples of the per-sequence token-sum CE."""
    keys = _Keys(key)
    memory, valid = encode(params, cfg, block_["user"], block_["hist"],
                           block_["n_tok"], mode, rate, keys)
    depth, cb = cfg["sem_id_dim"], cfg["codebook_size"]
    # The decoder is fed all `depth` target codes (the last one's output
    # is unused by the loss); feeding depth-1 gives the same three logits.
    logits = decode(params, cfg, memory, valid, block_["target"][:, :depth - 1],
                    mode, rate, keys)
    gold = jnp.arange(depth)[None] * cb + block_["target"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, gold[..., None], axis=-1)[..., 0]
    per_seq = jnp.sum(logz - picked, axis=1)
    return jnp.sum(per_seq * block_["real"])


def loss_and_grads(params, cfg, examples, mode="f32", key=None,
                   block_rows=2048):
    """Mean loss over the examples and its gradient, in blocks of rows."""
    rate = float(cfg["dropout"]) if key is not None else 0.0
    n = len(examples["user"])
    fn = _grad_fn(_freeze(cfg), mode, rate)
    total, grads = 0.0, None
    for i, start in enumerate(range(0, n, block_rows)):
        blk = {k: np.asarray(v[start:start + block_rows])
               for k, v in examples.items()}
        pad = block_rows - len(blk["user"])
        blk["real"] = np.concatenate(
            [np.ones(len(blk["user"]), np.float32), np.zeros(pad, np.float32)])
        if pad:
            for k in ("user", "hist", "n_tok", "target"):
                blk[k] = np.concatenate(
                    [blk[k], np.zeros((pad,) + blk[k].shape[1:], blk[k].dtype)])
        sub = None if key is None else jax.random.fold_in(key, i)
        if sub is None:
            sub = jax.random.key(0)  # unused at rate 0
        l, g = fn(params, blk, sub)
        total = total + l
        grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
    scale = 1.0 / n
    return total * scale, jax.tree_util.tree_map(lambda g: g * scale, grads)


@functools.lru_cache(maxsize=None)
def _grad_fn(cfg_items, mode, rate):
    cfg = dict(cfg_items)
    return jax.jit(jax.value_and_grad(
        lambda p, b, k: batch_loss_sum(p, cfg, b, mode, rate, k)))


def lr_at(opt, count):
    """Linear warm-up from 0 then cosine decay (HF semantics)."""
    warm, total, base = opt["warmup_steps"], opt["total_steps"], opt["learning_rate"]
    if count < warm:
        return base * count / max(1.0, warm)
    progress = (count - warm) / max(1.0, total - warm)
    return base * max(0.0, 0.5 * (1.0 + math.cos(math.pi * progress)))


def clip(grads, max_norm):
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gnorm, 1e-6))
    return jax.tree_util.tree_map(lambda g: g * scale, grads), gnorm


def adamw_step(params, grads, state, opt):
    """One AdamW update (decoupled weight decay), count starting at 0."""
    b1, b2, eps = 0.9, 0.999, 1e-8
    count = state["count"]
    lr = lr_at(opt, count)
    t = count + 1
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                                state["mu"], grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                                state["nu"], grads)
    def upd(p, m, v):
        mhat = m / (1 - b1 ** t)
        vhat = v / (1 - b2 ** t)
        return p - lr * (mhat / (jnp.sqrt(vhat) + eps) + opt["weight_decay"] * p)
    new = jax.tree_util.tree_map(upd, params, mu, nu)
    return new, {"count": t, "mu": mu, "nu": nu}


def train_steps(params, cfg, opt, batches, mode="f32", key=None,
                block_rows=2048):
    """Follow the first steps: per step the loss; the first CLIPPED gradient
    (what the optimizer gets); the parameters after the last step."""
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    state = {"count": 0, "mu": zeros, "nu": zeros}
    losses, first_grad = [], None
    p = params
    for i, ex in enumerate(batches):
        sub = None if key is None else jax.random.fold_in(key, 1000 + i)
        loss, grads = loss_and_grads(p, cfg, ex, mode, sub, block_rows)
        grads, _ = clip(grads, opt["clip_norm"])
        if first_grad is None:
            first_grad = grads
        p, state = adamw_step(p, grads, state, opt)
        losses.append(float(loss))
    return {"losses": losses, "first_grad": first_grad, "params": p}


# ---------------------------------------------------------------------------
# serving: teacher-forced scores of served beams, and what the beam must hold
# ---------------------------------------------------------------------------


class Catalog:
    """The reference's own view of the catalog: which codes may follow
    which prefix, and which item a full tuple names."""

    def __init__(self, item_sem_ids: np.ndarray, codebook_size: int):
        ids = np.asarray(item_sem_ids, np.int64)
        self.cb = codebook_size
        self.depth = ids.shape[1]
        self.item_of = {tuple(int(c) for c in t): i for i, t in enumerate(ids)}
        self.children: list[dict] = [dict() for _ in range(self.depth)]
        for t in ids:
            for d in range(self.depth):
                self.children[d].setdefault(tuple(int(c) for c in t[:d]),
                                            set()).add(int(t[d]))

    def legal(self, prefix) -> np.ndarray:
        m = np.zeros(self.cb, bool)
        kids = self.children[len(prefix)].get(tuple(int(c) for c in prefix))
        if kids:
            m[list(kids)] = True
        return m


def served_logps(params, cfg, catalog: Catalog, user, hist, n_tok, beams,
                 mode="f32"):
    """For each request and each served beam: the masked, tempered
    log-softmax rows along the beam's own path.

    beams: (N, K, depth) served sem ids. Returns logp (N, K, depth, cb):
    row [n, k, d] is log_softmax(legal-masked logits / T) after the beam's
    first d codes; illegal codes hold -inf."""
    N, K, depth = beams.shape
    cb = cfg["codebook_size"]
    memory, valid = _encode_jit(params, _freeze(cfg), user, hist, n_tok, mode)
    mem = jnp.repeat(memory, K, axis=0)
    val = jnp.repeat(valid, K, axis=0)
    flat = jnp.asarray(beams.reshape(N * K, depth))
    logits = _decode_jit(params, _freeze(cfg), mem, val, flat[:, :depth - 1], mode)
    logits = np.asarray(logits, np.float32).reshape(N, K, depth, -1)
    out = np.full((N, K, depth, cb), -np.inf, np.float32)
    for n in range(N):
        for k in range(K):
            for d in range(depth):
                legal = catalog.legal(beams[n, k, :d])
                if not legal.any():
                    continue
                row = logits[n, k, d, d * cb:(d + 1) * cb].astype(np.float64)
                z = np.where(legal, row / TEMPERATURE, -np.inf)
                z = z - z.max()
                out[n, k, d] = z - np.log(np.exp(z).sum())
    return out


def _freeze(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))


def _encode_impl(params, cfg_items, user, hist, n_tok, mode):
    return encode(params, dict(cfg_items), user, hist, n_tok, mode)


def _decode_impl(params, cfg_items, memory, valid, toks, mode):
    return decode(params, dict(cfg_items), memory, valid, toks, mode)


_encode_jit = jax.jit(_encode_impl, static_argnums=(1, 5))
_decode_jit = jax.jit(_decode_impl, static_argnums=(1, 5))
