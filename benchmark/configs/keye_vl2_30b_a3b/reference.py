"""Plain reference of Keye-VL-2.0-30B-A3B's language model, as cut in
``config.json``: forward, SFT loss, gradients and clipped AdamW steps.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision: no kernels, no cache, no sorted routing, no grouped
product. It imports nothing of ``genrec_tpu`` and takes only what the
benchmark made from the seed: the parameter tree (by the names the program
publishes) and the raw padded rows. It is given the same SHARE of the
deployment as the program: which experts are held, which vocabulary rows.

The layer, for one row of hidden states x_t (left-padded, ``attention_mask``
marks the real tokens):

1. h = RMSNorm(x); q = h W_q (32 x 128), k = h W_k, v = h W_v (4 x 128), no
   bias; per-head RMSNorm on q and k; RoPE (NeoX halves, theta 1e7).
2. Indexer: qI = h W_qI (16 x 64), kI = LayerNorm(h W_kI) (one head of 64),
   w = h W_w (16); RoPE on qI and kI;
   I[t,s] = sum_j w[t,j] relu(qI[t,j] . kI[s]) for real s <= t.
3. S_t = the ``topk`` keys of largest I[t,.] among the real s <= t (all of
   them where there are no more); ties: lowest index first (``lax.top_k``).
4. o[t,h] = softmax over s in S_t of q[t,h] . k[s, h // 8] / sqrt(128), times
   v; x = x + concat_h(o) W_o.
5. u = RMSNorm(x); p = softmax(u W_r) over all 128 router outputs; T = top 8;
   g_e = p_e / sum_{e' in T} p_e'; y = sum over e in T HELD HERE of
   g_e W_down,e (silu(W_gate,e u) * W_up,e u); x = x + y. Every expert held is
   applied to every token and masked: nothing is dropped.
6. RMSNorm, untied head over the vocabulary slice; cross-entropy over the
   labelled positions (logits at t predict the label at t + 1), plus the
   Switch load-balance term over all 128 router outputs and the real tokens.

Departures from the published description, each also in ``config.json``:

- No vision tower: the catalog row gives it no widths, and LCRec's prompts are
  text and codebook tokens.
- M-RoPE (sections [16, 24, 24]) as ordinary RoPE over all 64 frequency
  pairs: for text the three position ids are equal.
- No indexer alignment loss (it belongs to DSA's pre-training recipe): the
  selection is discrete, so the indexer's leaves get NO gradient from the LM
  loss, exactly zero here, and move by weight decay alone.
- What the experts held elsewhere would add is left out, here as in the
  program: the partial result goes on to the next layer.
- Assumed, the row having no key for them: the q/k head norm (the Qwen3-MoE
  convention), the LayerNorm on kI, RoPE on the indexer's heads and w from the
  hidden state (DeepSeek-V3.2-Exp's published DSA), ``q_chunk_size`` /
  ``kv_chunk_size`` read as a tiling and not as block-level selection (the
  selection is per query token), the load-balance coefficient 0.001.

``mode`` selects the arithmetic: ``"f32"`` is the reference; ``"fp8"`` is the
contract's lower-precision control (the step below the bf16 the configuration
states): every matrix-product operand is rounded to float8_e4m3fn first;
``"bf16"`` rounds them to bfloat16, a witness of what rounding alone moves.
Neither is ever used to judge a run.

Memory, on the chip after the program's state is freed: the whole step is one
gradient call with each layer rematerialised, attention row by row and query
block by query block, the head row by row; the first gradient waits on the
host while the later steps run.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e9
HIGHEST = jax.lax.Precision.HIGHEST


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
        return jnp.matmul(a, b, precision=HIGHEST)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def layer_norm(x, scale, bias, eps=1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def rope(x, positions, theta):
    """NeoX halves. x (L, heads, hd), positions (L,)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def share_of(cfg) -> tuple[int, int]:
    """(first expert held, experts held) of the configuration as run."""
    return int(cfg.get("first_expert", 0)), int(cfg["num_experts"])


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------


def select(scores, allowed, topk):
    """(T, N) indexer scores, (T, N) bool -> (T, N) bool: the ``topk``
    allowed keys of largest score, lowest index first among equals."""
    T, N = scores.shape
    if N <= topk:
        return allowed
    scores = jnp.where(scores == 0, 0.0, scores)  # -0.0 and 0.0 are one score
    _, idx = jax.lax.top_k(jnp.where(allowed, scores, -jnp.inf), topk)
    hit = jnp.zeros((T, N), bool).at[jnp.arange(T)[:, None], idx].set(True)
    return hit & allowed


def attention_row(p, cfg, h, positions, valid, mode, q_block):
    """One row. h (L, D) normed input -> (L, D) attention output (before
    the residual) and the selected sets (L, L) bool."""
    L = h.shape[0]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    sa = cfg["sa_config"]
    Hi, di, topk = sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    q = mm(h, p["q_proj"]["kernel"], mode).reshape(L, H, hd)
    k = mm(h, p["k_proj"]["kernel"], mode).reshape(L, KV, hd)
    v = mm(h, p["v_proj"]["kernel"], mode).reshape(L, KV, hd)
    q = rope(rms(q, p["q_norm"]["weight"], eps), positions, theta)
    k = rope(rms(k, p["k_norm"]["weight"], eps), positions, theta)
    hs = jax.lax.stop_gradient(h)  # the selection is discrete
    qi = rope(mm(hs, p["idx_q"]["kernel"], mode).reshape(L, Hi, di),
              positions, theta)
    ki = layer_norm(mm(hs, p["idx_k"]["kernel"], mode),
                    p["idx_k_norm"]["scale"], p["idx_k_norm"]["bias"])
    ki = rope(ki[:, None, :], positions, theta)[:, 0]
    w = mm(hs, p["idx_w"]["kernel"], mode)
    slots = jnp.arange(L)
    rep = H // KV

    @jax.checkpoint
    def block(qb, qib, wb, tb):
        # qb (T, H, hd), qib (T, Hi, di), wb (T, Hi), tb (T,) query slots
        s_idx = mm(qib, ki, mode, "thd,nd->htn")
        scores = mm(jax.nn.relu(s_idx), wb, mode, "htn,th->tn")
        allowed = valid[None, :] & (slots[None, :] <= tb[:, None])
        sel = select(scores, allowed, topk)
        s = mm(qb.reshape(-1, KV, rep, hd), k, mode, "tgrd,ngd->grtn") * hd ** -0.5
        s = jnp.where(sel[None, None], s, NEG)
        a = jax.nn.softmax(s, axis=-1)
        o = mm(a, v, mode, "grtn,ngd->tgrd").reshape(-1, H * hd)
        return o, sel

    nb = -(-L // q_block)
    pad = nb * q_block - L
    blocked = lambda a: jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
        (nb, q_block) + a.shape[1:])
    o, sel = jax.lax.map(lambda a: block(*a),
                         (blocked(q), blocked(qi), blocked(w), blocked(slots)))
    o = o.reshape(nb * q_block, H * hd)[:L]
    sel = sel.reshape(nb * q_block, L)[:L]
    return mm(o, p["o_proj"]["kernel"], mode), sel


def route(p, cfg, u, mode):
    """u (S, D) -> router probabilities (S, E), chosen experts (S, K) and
    their gates (S, K), over ALL published router outputs."""
    probs = jax.nn.softmax(mm(u, p["router"]["kernel"], mode), axis=-1)
    gates, eidx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return probs, eidx, gates


def experts(p, cfg, u, eidx, gates, valid, mode, share=None):
    """The held experts' part of the expert layer's result. u (S, D),
    valid (S,) bool. A loop over the experts held: every one is applied to
    every token, then masked by whether the token chose it."""
    first, held = share if share is not None else share_of(cfg)

    @jax.checkpoint
    def one(j, w_gate, w_up, w_down):
        g = jnp.sum(jnp.where(eidx == first + j, gates, 0.0), axis=-1)
        g = jnp.where(valid, g, 0.0)
        a, b = mm(u, w_gate, mode), mm(u, w_up, mode)
        return g[:, None] * mm(jax.nn.silu(a) * b, w_down, mode)

    y, _ = jax.lax.scan(
        lambda y, a: (y + one(*a), None), jnp.zeros_like(u),
        (jnp.arange(held), p["gate_proj"], p["up_proj"], p["down_proj"]))
    return y


def switch_aux(probs, eidx, valid, n_experts):
    """E * sum_e mean(p_e) * mean(top choice is e), over the real tokens."""
    vf = valid.astype(jnp.float32)
    nv = jnp.maximum(vf.sum(), 1.0)
    top1 = jax.nn.one_hot(eidx[:, 0], n_experts, dtype=jnp.float32) * vf[:, None]
    return n_experts * jnp.sum((probs * vf[:, None]).sum(0) / nv * (top1.sum(0) / nv))


def layer(p, cfg, x, positions, valid, mode="f32", share=None, q_block=256):
    """x (B, L, D) -> (x, load-balance term, selected sets (B, L, L))."""
    eps = cfg["rms_norm_eps"]
    B, L, D = x.shape
    h = rms(x, p["input_layernorm"]["weight"], eps)
    o, sel = jax.lax.map(
        lambda a: attention_row(p["self_attn"], cfg, a[0], a[1], a[2], mode,
                                min(q_block, L)),
        (h, positions, valid))
    x = x + o
    u = rms(x, p["post_attention_layernorm"]["weight"], eps).reshape(B * L, D)
    flat_valid = valid.reshape(B * L)
    probs, eidx, gates = route(p["moe"], cfg, u, mode)
    y = experts(p["moe"], cfg, u, eidx, gates, flat_valid, mode, share)
    aux = switch_aux(probs, eidx, flat_valid, probs.shape[-1])
    return x + y.reshape(B, L, D), aux, sel


# ---------------------------------------------------------------------------
# the model and its loss
# ---------------------------------------------------------------------------


def hidden(params, cfg, input_ids, attention_mask, positions=None, mode="f32",
           q_block=256):
    """Final normed hidden states (B, L, D) and the summed load-balance
    terms. ``positions`` default to the slot index, as the program's
    training forward numbers them (RoPE is relative)."""
    B, L = input_ids.shape
    valid = jnp.asarray(attention_mask).astype(bool)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(L), (B, L))
    x = params["embed_tokens"][input_ids]
    aux = 0.0
    for i in range(cfg["num_hidden_layers"]):
        step = jax.checkpoint(
            lambda lp, x: layer(lp, cfg, x, positions, valid, mode,
                                q_block=q_block)[:2])
        x, a = step(params[f"layer_{i}"], x)
        aux = aux + a
    return rms(x, params["norm"]["weight"], cfg["rms_norm_eps"]), aux


def logits_of(params, h, mode="f32"):
    return mm(h, params["lm_head"].T, mode)


def forward(params, cfg, input_ids, attention_mask, positions=None, mode="f32",
            q_block=256):
    """Logits (B, L, V) over the vocabulary slice: the full forward the
    cached prefill-then-decode is compared with."""
    h, _ = hidden(params, cfg, input_ids, attention_mask, positions, mode, q_block)
    return logits_of(params, h, mode)


def batch_loss(params, cfg, batch, mode="f32", q_block=256):
    """Mean cross-entropy over the labelled positions of the batch plus the
    load-balance term. batch: input_ids, attention_mask, labels (B, L),
    labels -100 where not a target."""
    h, aux = hidden(params, cfg, batch["input_ids"], batch["attention_mask"],
                    None, mode, q_block)
    labels = batch["labels"][:, 1:]

    @jax.checkpoint
    def row(hr, lr):
        z = logits_of(params, hr, mode)
        logz = jax.nn.logsumexp(z, axis=-1)
        gold = jnp.take_along_axis(z, jnp.maximum(lr, 0)[:, None], axis=-1)[:, 0]
        on = lr != -100
        return jnp.sum(jnp.where(on, logz - gold, 0.0)), jnp.sum(on)

    ce, n = jax.lax.map(lambda a: row(*a), (h[:, :-1], labels))
    return ce.sum() / jnp.maximum(n.sum(), 1) + cfg["router_aux_coef"] * aux


def _freeze(cfg):
    def fz(v):
        if isinstance(v, dict):
            return tuple(sorted((k, fz(x)) for k, x in v.items()
                                if isinstance(x, (int, float, str, bool, dict))))
        return v
    return fz({k: v for k, v in cfg.items()
               if isinstance(v, (int, float, str, bool)) or k == "sa_config"})


def _thaw(items):
    return {k: (dict(v) if isinstance(v, tuple) else v) for k, v in items}


@functools.lru_cache(maxsize=None)
def _grad_fn(cfg_items, mode, q_block):
    cfg = _thaw(cfg_items)
    return jax.jit(jax.value_and_grad(
        lambda p, b: batch_loss(p, cfg, b, mode, q_block)))


def loss_and_grads(params, cfg, batch, mode="f32"):
    q_block = int(cfg.get("assumed", {}).get("reference_query_block", 256))
    batch = {k: jnp.asarray(batch[k]) for k in ("input_ids", "attention_mask", "labels")}
    return _grad_fn(_freeze(cfg), mode, q_block)(params, batch)


# ---------------------------------------------------------------------------
# training: clipped AdamW (the trainer's: warm-up from 0, cosine, clip 1.0)
# ---------------------------------------------------------------------------


def lr_at(opt, count):
    """Linear warm-up from 0 then cosine decay (HF semantics)."""
    warm, total, base = opt["warmup_steps"], opt["total_steps"], opt["learning_rate"]
    if count < warm:
        return base * count / max(1.0, warm)
    progress = (count - warm) / max(1.0, total - warm)
    return base * max(0.0, 0.5 * (1.0 + math.cos(math.pi * progress)))


@jax.jit
def _clip(grads, max_norm):
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gnorm, 1e-6))
    return jax.tree_util.tree_map(lambda g: g * scale, grads)


def _adamw(p, m, v, g, lr, t, decay):
    b1, b2, eps = 0.9, 0.999, 1e-8
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mhat = m / (1 - b1 ** t)
    vhat = v / (1 - b2 ** t)
    return p - lr * (mhat / (jnp.sqrt(vhat) + eps) + decay * p), m, v


# a leaf at a time, the moments updated in place (and, after the first step,
# the weights): the whole tree is never held twice
_adamw_first = jax.jit(_adamw, donate_argnums=(1, 2, 3))
_adamw_next = jax.jit(_adamw, donate_argnums=(0, 1, 2))


def train_steps(params, cfg, opt, batches, mode="f32", key=None, block_rows=None):
    """Follow the first steps: per step the loss; the first CLIPPED gradient
    (what the optimizer gets), kept on the host; the parameters after the
    last step. A step is ONE gradient call over the whole batch (the
    load-balance term is a statistic of the batch), so ``block_rows`` must
    cover it; ``key`` is unused (no dropout)."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    mu = [jnp.zeros_like(x) for x in leaves]
    nu = [jnp.zeros_like(x) for x in leaves]
    losses, first_grad = [], None
    for i, batch in enumerate(batches):
        if block_rows is not None and block_rows < len(batch["input_ids"]):
            raise ValueError("the reference takes a step's rows in one block")
        p = jax.tree_util.tree_unflatten(treedef, leaves)
        loss, grads = loss_and_grads(p, cfg, batch, mode)
        del p
        grads = _clip(grads, float(opt["clip_norm"]))
        if first_grad is None:
            first_grad = jax.tree_util.tree_map(np.asarray, grads)
        lr, t = lr_at(opt, i), i + 1
        step = _adamw_first if i == 0 else _adamw_next
        g_leaves = jax.tree_util.tree_leaves(grads)
        del grads
        for j in range(len(leaves)):
            leaves[j], mu[j], nu[j] = step(
                leaves[j], mu[j], nu[j], g_leaves[j], lr, t,
                float(opt["weight_decay"]))
            g_leaves[j] = None
        losses.append(float(loss))
    return {"losses": losses, "first_grad": first_grad,
            "params": jax.tree_util.tree_unflatten(treedef, leaves)}
