"""Plain reference of Kimi-Linear-48B-A3B-Instruct's language model, as cut
in ``config.json``: forward, SFT loss, gradients and clipped AdamW steps.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision: no kernels, no cache, no chunk algebra, no sorted
routing, no grouped product. It imports nothing of ``genrec_tpu`` and takes
only what the benchmark made from the seed: the parameter tree (by the names
the program publishes) and the raw padded rows. It is given the same SHARE of
the deployment as the program: which experts are held, which vocabulary rows.

A row of hidden states x_t, left-padded, m_t in {0, 1} marks the real tokens;
RMSNorm has eps 1e-5 throughout.

KDA mixer (layers in ``linear_attn_config.kda_layers``). h = RMSNorm(x),
zeroed where m_t = 0.
1. q~ = h W_q, k~ = h W_k, v~ = h W_v (32 x 128 each, no bias); a causal
   depthwise convolution of kernel 4 a channel, no bias, as four shifted
   products (inputs before the row are zero), then SiLU.
2. Per head, q and k L2-normalised over their 128; q scaled by 128^-0.5.
3. g_t = -exp(A_log) * softplus(W_f2 (W_f1 h_t) + dt_bias), one for every head
   and CHANNEL; a_t = exp(g_t); b_t = sigmoid(h_t W_b) a head. At padding
   g_t = 0 and b_t = 0.
4. A state S (128 x 128, float32) a head, zero before the row, TOKEN BY TOKEN:
   S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T; o_t = S_t^T q_t.
   A two-level ``lax.scan`` whose outer level is rematerialised (8,192 saved
   states of 32 x 128 x 128 float32 would be 17 GB; 128 + 64 are 0.4 GB).
5. y_t = W_o [RMSNorm_head(o_t) * sigmoid(W_g2 (W_g1 h_t) + bias)]; x = x + y.

MLA mixer, no positions (layers in ``full_attn_layers``). h = RMSNorm(x);
q_t = h W_q (32 x 192); [c_t ; r_t] = h W_kva (512 + 64), c = RMSNorm(c);
[kc ; v] = c W_kvb (32 x (128 + 128)); k = [kc ; r_t], r_t shared by the
heads; NOTHING is rotated; softmax over the real s <= t of q . k / sqrt(192),
full score rows a query block; x = x + concat(o) W_o.

MLP. The first ``first_k_dense_replace`` layers: a dense SwiGLU. The others:
s = sigmoid(u W_r) over all 256 router outputs; T = the 8 largest of s + bias;
w_e = 2.446 * s_e / sum_{T} s; y = sum over e in T HELD HERE of w_e E_e(u)
+ E_shared(u). Every held expert is applied to every token and masked.
After the last layer RMSNorm, the untied head over the vocabulary slice,
cross-entropy over the labelled positions (logits at t predict t + 1).

Departures from the published description, each also in ``config.json``:
5 of 27 layers (1-5 in the published order); 8 of 256 experts held, what the
248 held elsewhere would add is left out, here as in the program; the
vocabulary slice; the selection bias is a buffer that stays at its seed
value (zero): no gradient, no load-balance update, and no Switch term
(``router_aux_coef`` 0.0); ``model_max_length`` 1,048,576 is kept while rows
are 8,192. Assumed (the catalog row names the layer, ``head_dim`` 128,
``num_heads`` 32, ``short_conv_kernel_size`` 4; the rest follows the technical
report, arXiv:2510.26692, and its public implementation's layout): the
rank-128 gate projections and where their bias sits, SiLU after the
convolutions, L2-normalised q and k, the sigmoid gate inside the output norm.

``mode`` selects the arithmetic: ``"f32"`` is the reference; ``"fp8"`` is the
contract's lower-precision control (the step below the bf16 the configuration
states): every operand of a product the configuration computes in bf16 (the
projections, the latent attention, the experts, the head) is rounded to
float8_e4m3fn first; the recurrence and the gates stay float32, as the
configuration states them; ``"bf16"`` rounds those operands to bfloat16, a
witness of what rounding alone moves. Neither is ever used to judge a run.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e9
HIGHEST = jax.lax.Precision.HIGHEST
SCAN_BLOCK = 64


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


def unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def swiglu(p, u, mode):
    a, b = mm(u, p["gate_proj"]["kernel"], mode), mm(u, p["up_proj"]["kernel"], mode)
    return mm(jax.nn.silu(a) * b, p["down_proj"]["kernel"], mode)


def share_of(cfg) -> tuple[int, int]:
    """(first expert held, experts held) of the configuration as run."""
    return int(cfg.get("first_expert", 0)), int(cfg["num_experts"])


def mixer_kind(cfg, layer: int) -> str:
    """``kda`` or ``mla`` of the 0-based layer, by the published lists."""
    lac = cfg["linear_attn_config"]
    if layer + 1 in lac["kda_layers"]:
        return "kda"
    if layer + 1 in lac["full_attn_layers"]:
        return "mla"
    raise ValueError(f"layer {layer + 1} is in neither published list")


# ---------------------------------------------------------------------------
# Kimi Delta Attention
# ---------------------------------------------------------------------------


def conv4(u, w):
    """Causal depthwise convolution as shifted products. u (L, C), w (k, C):
    c_t = sum_j w_j u_{t-(k-1)+j}, inputs before the row zero."""
    k, L = w.shape[0], u.shape[0]
    ext = jnp.concatenate([jnp.zeros((k - 1, u.shape[1]), u.dtype), u], axis=0)
    return sum(w[j] * ext[j:j + L] for j in range(k))


def kda_gates(p, cfg, h, m, mode):
    """g (L, H, K) <= 0 and b (L, H), zero at padding."""
    lac = cfg["linear_attn_config"]
    H, K = lac["num_heads"], lac["head_dim"]
    f = mm(mm(h, p["f_a_proj"]["kernel"], mode), p["f_b_proj"]["kernel"], mode)
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        f.reshape(-1, H, K) + p["dt_bias"])
    b = jax.nn.sigmoid(mm(h, p["b_proj"]["kernel"], mode))
    return g * m[:, None, None], b * m[:, None]


def delta_rule(q, k, v, g, b, s0=None):
    """The recurrence, token by token. q, k, g (L, H, K); v (L, H, V);
    b (L, H) -> o (L, H, V) and the final state (H, K, V)."""
    L, H, K = q.shape

    def token(S, x):
        qt, kt, vt, gt, bt = x
        S = S * jnp.exp(gt)[..., None]
        u = bt[:, None] * (vt - jnp.einsum("hk,hkv->hv", kt, S, precision=HIGHEST))
        S = S + kt[..., None] * u[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", qt, S, precision=HIGHEST)

    block = jax.checkpoint(lambda S, xs: jax.lax.scan(token, S, xs))
    n = -(-L // SCAN_BLOCK)
    blocks = lambda a: jnp.pad(
        a, [(0, n * SCAN_BLOCK - L)] + [(0, 0)] * (a.ndim - 1)).reshape(
            (n, SCAN_BLOCK) + a.shape[1:])
    if s0 is None:
        s0 = jnp.zeros((H, K, v.shape[-1]), jnp.float32)
    S, o = jax.lax.scan(block, s0, tuple(blocks(a) for a in (q, k, v, g, b)))
    return o.reshape((n * SCAN_BLOCK,) + o.shape[2:])[:L], S


def kda_row(p, cfg, h, valid, mode, scalar_gate=False):
    """One row. h (L, D) normed input -> (L, D) mixer output (before the
    residual) and the final state. ``scalar_gate`` (a test's control, never
    a judge): the gate averaged over its channels, one decay a head."""
    lac = cfg["linear_attn_config"]
    H, K = lac["num_heads"], lac["head_dim"]
    L = h.shape[0]
    m = valid.astype(jnp.float32)
    h = h * m[:, None]
    heads = lambda a: a.reshape(L, H, K)
    q, k, v = (heads(jax.nn.silu(conv4(mm(h, p[n + "_proj"]["kernel"], mode),
                                       p[n + "_conv"])))
               for n in ("q", "k", "v"))
    q, k = unit(q) * K ** -0.5, unit(k)
    g, b = kda_gates(p, cfg, h, m, mode)
    if scalar_gate:
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    o, S = delta_rule(q, k, v, g, b)
    gate = jax.nn.sigmoid(
        mm(mm(h, p["g_a_proj"]["kernel"], mode), p["g_b_proj"]["kernel"], mode)
        + p["g_b_proj"]["bias"])
    o = rms(o, p["o_norm"]["weight"], cfg["rms_norm_eps"]).reshape(L, H * K) * gate
    return mm(o, p["o_proj"]["kernel"], mode), S


# ---------------------------------------------------------------------------
# latent attention, no positions
# ---------------------------------------------------------------------------


def mla_row(p, cfg, h, valid, mode, q_block):
    """One row. h (L, D) normed input -> (L, D) attention output."""
    L = h.shape[0]
    H = cfg["num_attention_heads"]
    dn, dr, dv, r = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"], cfg["kv_lora_rank"])
    q = mm(h, p["q_proj"]["kernel"], mode).reshape(L, H, dn + dr)
    ckr = mm(h, p["kv_a_proj"]["kernel"], mode)
    c = rms(ckr[:, :r], p["kv_a_norm"]["weight"], cfg["rms_norm_eps"])
    kv = mm(c, p["kv_b_proj"], mode).reshape(L, H, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(ckr[:, None, r:], (L, H, dr))], axis=-1)
    v = kv[..., dn:]
    slots = jnp.arange(L)

    @jax.checkpoint
    def block(qb, tb):
        s = mm(qb, k, mode, "thd,nhd->htn") * (dn + dr) ** -0.5
        allowed = valid[None, :] & (slots[None, :] <= tb[:, None])
        a = jax.nn.softmax(jnp.where(allowed[None], s, NEG), axis=-1)
        return mm(a, v, mode, "htn,nhd->thd").reshape(-1, H * dv)

    nb = -(-L // q_block)
    pad = nb * q_block - L
    blocked = lambda a: jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
        (nb, q_block) + a.shape[1:])
    o = jax.lax.map(lambda a: block(*a), (blocked(q), blocked(slots)))
    return mm(o.reshape(nb * q_block, H * dv)[:L], p["o_proj"]["kernel"], mode)


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------


def route(p, cfg, u, mode):
    """u (S, D) -> sigmoid scores (S, E), chosen experts (S, K) and their
    gates (S, K), over ALL published router outputs. The bias enters the
    choice, not the gates."""
    scores = jax.nn.sigmoid(mm(u, p["router"]["kernel"], mode))
    _, eidx = jax.lax.top_k(scores + jax.lax.stop_gradient(p["selection_bias"]),
                            cfg["num_experts_per_token"])
    gates = jnp.take_along_axis(scores, eidx, axis=-1)
    if cfg.get("moe_renormalize", True):
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return scores, eidx, gates * cfg["routed_scaling_factor"]


def experts(p, cfg, u, eidx, gates, valid, mode, share=None):
    """The held experts' part of the routed result. u (S, D), valid (S,)
    bool. A loop over the experts held: every one is applied to every
    token, then masked by whether the token chose it."""
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


def moe(p, cfg, u, valid, mode, share=None):
    """Routed part of the experts held here plus the shared expert."""
    _, eidx, gates = route(p, cfg, u, mode)
    return (experts(p, cfg, u, eidx, gates, valid, mode, share)
            + swiglu(p["shared_expert"], u, mode))


# ---------------------------------------------------------------------------
# the layer, the model and its loss
# ---------------------------------------------------------------------------


def layer(p, cfg, i, x, valid, mode="f32", share=None, q_block=256,
          scalar_gate=False):
    """Layer ``i`` (0-based). x (B, L, D) -> x."""
    eps = cfg["rms_norm_eps"]
    B, L, D = x.shape
    h = rms(x, p["input_layernorm"]["weight"], eps)
    if mixer_kind(cfg, i) == "kda":
        row = lambda a: kda_row(p["kda"], cfg, a[0], a[1], mode, scalar_gate)[0]
    else:
        row = lambda a: mla_row(p["mla"], cfg, a[0], a[1], mode, min(q_block, L))
    x = x + jax.lax.map(row, (h, valid))
    u = rms(x, p["post_attention_layernorm"]["weight"], eps).reshape(B * L, D)
    if i < cfg["first_k_dense_replace"]:
        y = swiglu(p["mlp"], u, mode)
    else:
        y = moe(p["moe"], cfg, u, valid.reshape(B * L), mode, share)
    return x + y.reshape(B, L, D)


def hidden(params, cfg, input_ids, attention_mask, mode="f32", q_block=256,
           scalar_gate=False):
    """Final normed hidden states (B, L, D)."""
    valid = jnp.asarray(attention_mask).astype(bool)
    x = params["embed_tokens"][input_ids]
    for i in range(cfg["num_hidden_layers"]):
        step = jax.checkpoint(
            lambda lp, x, i=i: layer(lp, cfg, i, x, valid, mode, q_block=q_block,
                                     scalar_gate=scalar_gate))
        x = step(params[f"layer_{i}"], x)
    return rms(x, params["norm"]["weight"], cfg["rms_norm_eps"])


def logits_of(params, h, mode="f32"):
    return mm(h, params["lm_head"].T, mode)


def forward(params, cfg, input_ids, attention_mask, mode="f32", q_block=256):
    """Logits (B, L, V) over the vocabulary slice: the full forward the
    cached prefill-then-decode is compared with."""
    return logits_of(params, hidden(params, cfg, input_ids, attention_mask,
                                    mode, q_block), mode)


def batch_loss(params, cfg, batch, mode="f32", q_block=256, scalar_gate=False):
    """Mean cross-entropy over the labelled positions of the batch. batch:
    input_ids, attention_mask, labels (B, L), labels -100 where not a target."""
    h = hidden(params, cfg, batch["input_ids"], batch["attention_mask"], mode,
               q_block, scalar_gate)
    labels = batch["labels"][:, 1:]

    @jax.checkpoint
    def row(hr, lr):
        z = logits_of(params, hr, mode)
        logz = jax.nn.logsumexp(z, axis=-1)
        gold = jnp.take_along_axis(z, jnp.maximum(lr, 0)[:, None], axis=-1)[:, 0]
        on = lr != -100
        return jnp.sum(jnp.where(on, logz - gold, 0.0)), jnp.sum(on)

    ce, n = jax.lax.map(lambda a: row(*a), (h[:, :-1], labels))
    return ce.sum() / jnp.maximum(n.sum(), 1)


#: what of the configuration's file the model reads
MODEL_KEYS = (
    "hidden_size", "num_hidden_layers", "num_attention_heads", "rms_norm_eps",
    "linear_attn_config", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "first_k_dense_replace", "num_experts", "first_expert",
    "num_experts_per_token", "moe_renormalize", "routed_scaling_factor")


@functools.lru_cache(maxsize=None)
def _grad_fn(cfg_json, mode, q_block):
    cfg = json.loads(cfg_json)
    return jax.jit(jax.value_and_grad(
        lambda p, b: batch_loss(p, cfg, b, mode, q_block)))


def loss_and_grads(params, cfg, batch, mode="f32"):
    q_block = int(cfg.get("assumed", {}).get("reference_query_block", 256))
    batch = {k: jnp.asarray(batch[k]) for k in ("input_ids", "attention_mask", "labels")}
    model = json.dumps({k: cfg[k] for k in MODEL_KEYS if k in cfg}, sort_keys=True)
    return _grad_fn(model, mode, q_block)(params, batch)


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


# A leaf at a time. Between steps the two moments wait on the HOST: with them
# on the device (4.9 GB beside 2.4 GB of weights) the gradient program (2.4 GB
# of gradients, 5.3 GB of temporaries) does not load on a 16 GB chip.
_adamw_first = jax.jit(_adamw, donate_argnums=(1, 2))  # the caller keeps its weights
_adamw_next = jax.jit(_adamw, donate_argnums=(0, 1, 2))


def train_steps(params, cfg, opt, batches, mode="f32", key=None, block_rows=None):
    """Follow the first steps: per step the loss; the first CLIPPED gradient
    (what the optimizer gets), kept on the host; the parameters after the
    last step. A step is ONE gradient call over the whole batch, so
    ``block_rows`` must cover it; ``key`` is unused (no dropout)."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    mu = [np.zeros(x.shape, np.float32) for x in leaves]
    nu = [np.zeros(x.shape, np.float32) for x in leaves]
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
            leaves[j], m, v = step(
                leaves[j], jnp.asarray(mu[j]), jnp.asarray(nu[j]), g_leaves[j],
                lr, t, float(opt["weight_decay"]))
            mu[j], nu[j] = np.asarray(m), np.asarray(v)
            g_leaves[j] = None
        losses.append(float(loss))
    return {"losses": losses, "first_grad": first_grad,
            "params": jax.tree_util.tree_unflatten(treedef, leaves)}
