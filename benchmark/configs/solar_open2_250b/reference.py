"""Plain reference of Solar-Open2-250B's language model, as cut in
``config.json``: the forward pass over a prompt and a forced continuation.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision: no kernels, no cache, no pages, no chunk algebra, no
sorted routing, no grouped product, no batching of requests. It imports
nothing of ``genrec_tpu`` and takes only what the benchmark made from the
seed: the parameter tree (by the names the program publishes; bfloat16 leaves
are read as the float32 numbers they hold) and raw left-padded rows. It is
given the same SHARE of the deployment as the program: which experts are
held, which vocabulary rows.

A row of hidden states x_t, left-padded, m_t in {0, 1} marks the real tokens;
RMSNorm has eps 1e-5 throughout. One period is four layers: full attention,
then three of Kimi Delta Attention.

Full attention, no positions (layers in ``gqa_layers``, 0-based).
h = RMSNorm(x); q = h W_q (64 x 128), k = h W_k, v = h W_v (8 x 128), no
bias, no q/k norm, NOTHING is rotated (``use_rope`` false); query head
8 g + r reads key-value head g; softmax over the real s <= t of
q . k / sqrt(128), full score rows a query block; the heads' outputs,
concatenated, are multiplied element by element by sigmoid(h W_g)
(W_g 4,096 -> 8,192, ``use_gqa_gate``) and then projected: x = x + (o * gate) W_o.

KDA mixer (every other layer). h = RMSNorm(x), zeroed where m_t = 0.
1. q~ = h W_q, k~ = h W_k, v~ = h W_v (64 x 128 each, no bias); a causal
   depthwise convolution of kernel 4 a channel, no bias, as four shifted
   products (inputs before the row are zero), then SiLU.
2. Per head, q and k L2-normalised over their 128; q scaled by 128^-0.5.
3. g_t = -exp(A_log) * softplus(W_f2 (W_f1 h_t) + dt_bias), one for every
   head and CHANNEL (``kda_use_full_proj`` false: W_f1 4,096 -> 128, W_f2
   128 -> 8,192); a_t = exp(g_t); b_t = 2 * sigmoid(h_t W_b) a head
   (``kda_allow_neg_eigval``: I - b k k^T has the eigenvalue 1 - b in (-1, 1)
   along the unit key). At padding g_t = 0 and b_t = 0.
4. A state S (128 x 128, float32) a head, zero before the row, TOKEN BY TOKEN:
   S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T; o_t = S_t^T q_t.
5. y_t = W_o [RMSNorm_head(o_t) * sigmoid(W_g2 (W_g1 h_t) + bias)]; x = x + y.

MLP, every layer (``first_k_dense_replace`` 0). s = sigmoid(u W_r) over all
320 router outputs; T = the 8 largest of s + bias; w_e = s_e / sum_{T} s
(``norm_topk_prob``, ``routed_scaling_factor`` 1); y = sum over e in T HELD
HERE of w_e E_e(u) + E_shared(u). Every held expert is applied to every token
and masked: a loop over the experts. After the last layer RMSNorm and the
untied head over the vocabulary slice.

Departures from the published description, each also in ``config.json``:
4 of 48 layers (layers 0-3); 40 of 320 experts held, what the 280 held
elsewhere would add is left out, here as in the program; the vocabulary
slice; the selection bias is a buffer at its seed value (zero). Assumed (the
catalog row gives the keys; the rest follows the family's public
implementation): the router's sigmoid scoring with a selection-only bias, the
gate's width and place, no q/k norm or bias in the full layer, the KDA layout
of ``kimi_linear_48b_a3b`` with the low-rank gates.

``mode`` selects the arithmetic: ``"f32"`` is the reference; ``"fp8"`` is the
contract's lower-precision control (the step below the bfloat16 the
configuration states): every operand of a product the configuration computes
in bf16 (the projections, the attention, the experts, the head) is rounded to
float8_e4m3fn first; the recurrence, the gates and the router stay float32, as
the configuration states them; ``"bf16"`` rounds those operands to bfloat16, a
witness of what rounding alone moves. ``variant`` names a STRUCTURAL control,
one departure from the equations above: ``"beta_single"`` (b = sigmoid, not
doubled), ``"no_gate"`` (the full layer's output gate dropped),
``"state_bf16"`` (the recurrent state rounded to bfloat16 after every token).
No control is ever used to judge a run.

Beside the forward pass: `end_states` (the KDA layers' states after a prompt's
last token: what a serving system's retained snapshot is held to) and
`beam_search` (the plain beam over the catalog's trie, a full forward a row a
step: how a control answers a prompt itself).
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e9
HIGHEST = jax.lax.Precision.HIGHEST
SCAN_BLOCK = 64


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------


def f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def to_bf16(x):
    """Round to bfloat16's 8 bits of mantissa, as an operation the compiler
    keeps: a cast down and up again is a pair it may drop (on the chip it
    does: `xla_allow_excess_precision`), and a control that rounds nothing
    reads as the reference itself."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _q(x, mode):
    if mode == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if mode == "bf16":
        return to_bf16(x)
    return x


def mm(a, b, mode, spec=None):
    a, b = _q(f32(a), mode), _q(f32(b), mode)
    if spec is None:
        return jnp.matmul(a, b, precision=HIGHEST)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * f32(w)


def unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def swiglu(p, u, mode):
    a, b = mm(u, p["gate_proj"]["kernel"], mode), mm(u, p["up_proj"]["kernel"], mode)
    return mm(jax.nn.silu(a) * b, p["down_proj"]["kernel"], mode)


def share_of(cfg) -> tuple[int, int]:
    """(first expert held, experts held) of the configuration as run."""
    return int(cfg.get("first_expert", 0)), int(cfg["n_routed_experts"])


def mixer_kind(cfg, layer: int) -> str:
    """``attention`` or ``kda`` of the 0-based layer, by the published list."""
    return "attention" if layer in cfg["gqa_layers"] else "kda"


# ---------------------------------------------------------------------------
# Kimi Delta Attention, negative eigenvalues allowed
# ---------------------------------------------------------------------------


def conv4(u, w):
    """Causal depthwise convolution as shifted products. u (L, C), w (k, C):
    c_t = sum_j w_j u_{t-(k-1)+j}, inputs before the row zero."""
    w = f32(w)
    k, L = w.shape[0], u.shape[0]
    ext = jnp.concatenate([jnp.zeros((k - 1, u.shape[1]), u.dtype), u], axis=0)
    return sum(w[j] * ext[j:j + L] for j in range(k))


def kda_gates(p, cfg, h, m, mode, variant=None):
    """g (L, H, K) <= 0 and b (L, H) in (0, 2), zero at padding."""
    lac = cfg["linear_attn_config"]
    H, K = lac["num_heads"], lac["head_dim"]
    f = mm(mm(h, p["f_a_proj"]["kernel"], mode), p["f_b_proj"]["kernel"], mode)
    g = -jnp.exp(f32(p["A_log"]))[:, None] * jax.nn.softplus(
        f.reshape(-1, H, K) + f32(p["dt_bias"]))
    b = jax.nn.sigmoid(mm(h, p["b_proj"]["kernel"], mode))
    if cfg["kda_allow_neg_eigval"] and variant != "beta_single":
        b = 2.0 * b
    return g * m[:, None, None], b * m[:, None]


def delta_rule(q, k, v, g, b, variant=None):
    """The recurrence, token by token. q, k, g (L, H, K); v (L, H, V);
    b (L, H) -> o (L, H, V) and the final state (H, K, V)."""
    L, H, K = q.shape

    def token(S, x):
        qt, kt, vt, gt, bt = x
        S = S * jnp.exp(gt)[..., None]
        u = bt[:, None] * (vt - jnp.einsum("hk,hkv->hv", kt, S, precision=HIGHEST))
        S = S + kt[..., None] * u[:, None, :]
        if variant == "state_bf16":
            S = to_bf16(S)
        return S, jnp.einsum("hk,hkv->hv", qt, S, precision=HIGHEST)

    n = -(-L // SCAN_BLOCK)
    blocks = lambda a: jnp.pad(
        a, [(0, n * SCAN_BLOCK - L)] + [(0, 0)] * (a.ndim - 1)).reshape(
            (n, SCAN_BLOCK) + a.shape[1:])
    S, o = jax.lax.scan(
        lambda S, xs: jax.lax.scan(token, S, xs),
        jnp.zeros((H, K, v.shape[-1]), jnp.float32),
        tuple(blocks(a) for a in (q, k, v, g, b)))
    return o.reshape((n * SCAN_BLOCK,) + o.shape[2:])[:L], S


def kda_row(p, cfg, h, valid, mode, variant=None):
    """One row. h (L, D) normed input -> (L, D) mixer output (before the
    residual) and the state after the row's last token (H, K, V)."""
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
    g, b = kda_gates(p, cfg, h, m, mode, variant)
    o, S = delta_rule(q, k, v, g, b, variant)
    gate = jax.nn.sigmoid(
        mm(mm(h, p["g_a_proj"]["kernel"], mode), p["g_b_proj"]["kernel"], mode)
        + f32(p["g_b_proj"]["bias"]))
    o = rms(o, p["o_norm"]["weight"], cfg["rms_norm_eps"]).reshape(L, H * K) * gate
    return mm(o, p["o_proj"]["kernel"], mode), S


# ---------------------------------------------------------------------------
# full attention: grouped queries, no positions, an output gate
# ---------------------------------------------------------------------------


def gqa_row(p, cfg, h, valid, mode, q_block, variant=None):
    """One row. h (L, D) normed input -> (L, D) attention output."""
    L = h.shape[0]
    H, G, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    rep = H // G
    q = mm(h, p["q_proj"]["kernel"], mode).reshape(L, G, rep, hd)
    k = mm(h, p["k_proj"]["kernel"], mode).reshape(L, G, hd)
    v = mm(h, p["v_proj"]["kernel"], mode).reshape(L, G, hd)
    slots = jnp.arange(L)

    def block(qb, tb):
        s = mm(qb, k, mode, "tgrd,ngd->grtn") * hd ** -0.5
        allowed = valid[None, :] & (slots[None, :] <= tb[:, None])
        a = jax.nn.softmax(jnp.where(allowed[None, None], s, NEG), axis=-1)
        return mm(a, v, mode, "grtn,ngd->tgrd").reshape(-1, H * hd)

    nb = -(-L // q_block)
    pad = nb * q_block - L
    blocked = lambda a: jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
        (nb, q_block) + a.shape[1:])
    o = jax.lax.map(lambda a: block(*a), (blocked(q), blocked(slots)))
    o = o.reshape(nb * q_block, H * hd)[:L]
    if cfg["use_gqa_gate"] and variant != "no_gate":
        o = o * jax.nn.sigmoid(mm(h, p["gate_proj"]["kernel"], mode))
    return mm(o, p["o_proj"]["kernel"], mode)


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------


def route(p, cfg, u):
    """u (S, D) -> chosen experts (S, K) and their gates (S, K), over ALL
    published router outputs, in float32 whatever the mode (the
    configuration states the router so). The bias enters the choice, not
    the gates."""
    scores = jax.nn.sigmoid(mm(u, p["router"]["kernel"], "f32"))
    _, eidx = jax.lax.top_k(scores + f32(p["selection_bias"]),
                            cfg["num_experts_per_tok"])
    gates = jnp.take_along_axis(scores, eidx, axis=-1)
    if cfg["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return eidx, gates * cfg["routed_scaling_factor"]


def experts(p, cfg, u, eidx, gates, valid, mode, share=None):
    """The held experts' part of the routed result. u (S, D), valid (S,)
    bool. A loop over the experts held: every one is applied to every
    token, then masked by whether the token chose it."""
    first, held = share if share is not None else share_of(cfg)

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
    eidx, gates = route(p, cfg, u)
    return (experts(p, cfg, u, eidx, gates, valid, mode, share)
            + swiglu(p["shared_expert"], u, mode))


# ---------------------------------------------------------------------------
# the layer and the model
# ---------------------------------------------------------------------------


def layer(p, cfg, i, x, valid, mode="f32", share=None, q_block=256,
          variant=None):
    """Layer ``i`` (0-based) over ONE row. x (L, D) -> x and, of a KDA
    layer, its state after the row's last token (else None)."""
    eps = cfg["rms_norm_eps"]
    L = x.shape[0]
    h = rms(x, p["input_layernorm"]["weight"], eps)
    S = None
    if mixer_kind(cfg, i) == "kda":
        y, S = kda_row(p["kda"], cfg, h, valid, mode, variant)
    else:
        y = gqa_row(p["self_attn"], cfg, h, valid, mode, min(q_block, L), variant)
    x = x + y
    u = rms(x, p["post_attention_layernorm"]["weight"], eps)
    return x + moe(p["moe"], cfg, u, valid, mode, share), S


def hidden_row(params, cfg, ids, mask, mode="f32", share=None, q_block=256,
               variant=None):
    """Final normed hidden states (L, D) of one left-padded row, and the
    KDA layers' states after its last token, in layer order."""
    valid = jnp.asarray(mask).astype(bool)
    x = f32(params["embed_tokens"][ids])
    states = []
    for i in range(cfg["num_hidden_layers"]):
        x, S = layer(params[f"layer_{i}"], cfg, i, x, valid, mode, share,
                     q_block, variant)
        if S is not None:
            states.append(S)
    return rms(x, params["norm"]["weight"], cfg["rms_norm_eps"]), states


def forward(params, cfg, input_ids, attention_mask, mode="f32", share=None,
            q_block=256, variant=None):
    """Logits (B, L, V) over the vocabulary slice, a row at a time: the full
    forward the paged prefill-then-decode is compared with."""
    def row(a):
        h, _ = hidden_row(params, cfg, a[0], a[1], mode, share, q_block, variant)
        return mm(h, f32(params["lm_head"]).T, mode)

    return jax.lax.map(row, (jnp.asarray(input_ids), jnp.asarray(attention_mask)))


#: what of the configuration's file the model reads
MODEL_KEYS = (
    "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "rms_norm_eps", "linear_attn_config",
    "gqa_layers", "use_gqa_gate", "kda_allow_neg_eigval", "n_routed_experts",
    "first_expert", "num_experts_per_tok", "norm_topk_prob",
    "routed_scaling_factor", "base_vocab", "codebook_size", "sem_id_dim")


@functools.lru_cache(maxsize=None)
def _tail_fn(cfg_json, mode, q_block, variant):
    cfg = json.loads(cfg_json)
    C, K, base = cfg["sem_id_dim"], cfg["codebook_size"], cfg["base_vocab"]

    def rows(params, ids, mask):
        def row(a):
            h, _ = hidden_row(params, cfg, a[0], a[1], mode, None, q_block, variant)
            z = mm(h[-C:], f32(params["lm_head"]).T, mode)  # (C, V)
            logp = jax.nn.log_softmax(z, axis=-1)
            return jnp.stack([logp[d, base + d * K: base + (d + 1) * K]
                              for d in range(C)])
        return jax.lax.map(row, (ids, mask))

    return jax.jit(rows)


@functools.lru_cache(maxsize=None)
def _state_fn(cfg_json, mode, q_block, variant):
    cfg = json.loads(cfg_json)
    return jax.jit(lambda params, ids, mask: jnp.stack(
        hidden_row(params, cfg, ids, mask, mode, None, q_block, variant)[1]))


def _model_json(cfg) -> tuple:
    q_block = int(cfg.get("assumed", {}).get("reference_query_block", 256))
    return json.dumps({k: cfg[k] for k in MODEL_KEYS if k in cfg},
                      sort_keys=True), q_block


def end_states(params, cfg, prompt, mode="f32", variant=None, pad_to=None):
    """The KDA layers' recurrent states after a prompt's last token, in layer
    order: (KDA layers, H, K, V) float32. What a serving system must hold
    for the prompt's continuation, and what a retained snapshot is."""
    prompt = np.asarray(prompt, np.int32)
    Lp = int(pad_to(len(prompt))) if pad_to is not None else len(prompt)
    ids = np.zeros(Lp, np.int32)
    mask = np.zeros(Lp, np.int32)
    ids[Lp - len(prompt):] = prompt
    mask[Lp - len(prompt):] = 1
    model, q_block = _model_json(cfg)
    return np.asarray(_state_fn(model, mode, q_block, variant)(
        params, jnp.asarray(ids), jnp.asarray(mask)))


def continuation_logps(params, cfg, ids, mask, mode="f32", variant=None):
    """For left-padded rows ``prompt ++ the first C - 1 codes of a forced
    continuation``: the log-probabilities (softmax over the whole vocabulary
    slice) of each codebook's K tokens at the C positions that predict the
    continuation's codes. ids, mask (N, L) -> (N, C, K) float32."""
    model, q_block = _model_json(cfg)
    fn = _tail_fn(model, mode, q_block, variant)
    return np.asarray(fn(params, jnp.asarray(ids), jnp.asarray(mask)))


class Catalog:
    """The item catalog as the reference needs it: the legal children of every
    prefix, and the item of every complete tuple."""

    def __init__(self, item_sem_ids, codebook_size: int):
        ids = np.asarray(item_sem_ids, np.int64)
        self.depth = ids.shape[1]
        self.codebook_size = int(codebook_size)
        self.item_of = {tuple(int(c) for c in t): i for i, t in enumerate(ids)}
        self.children: dict = {}
        for t in self.item_of:
            for d in range(self.depth):
                self.children.setdefault(t[:d], set()).add(t[d])

    def legal(self, prefix) -> np.ndarray:
        out = np.zeros(self.codebook_size, bool)
        out[list(self.children.get(tuple(int(c) for c in prefix), ()))] = True
        return out


def served_logps(params, cfg, catalog: Catalog, prompts, beams, mode="f32",
                 variant=None, pad_to=None):
    """Per request and served beam, the trie-masked log-probabilities of every
    code along the beam. ``prompts``: a list of 1-D token arrays; ``beams``
    (N, W, C) codes. Rows of one request run one after another (no batching
    across requests; a request's rows share one compiled shape, padded on the
    left to ``pad_to(len)``). Returns (N, W, C, K), -inf at illegal children."""
    C, K, base = cfg["sem_id_dim"], cfg["codebook_size"], cfg["base_vocab"]
    N, W, _ = beams.shape
    out = np.full((N, W, C, K), -np.inf, np.float32)
    offs = base + np.arange(C - 1) * K
    for n in range(N):
        prompt = np.asarray(prompts[n], np.int32)
        L = len(prompt) + C - 1
        Lp = int(pad_to(len(prompt))) + C - 1 if pad_to is not None else L
        ids = np.zeros((W, Lp), np.int32)
        mask = np.zeros((W, Lp), np.int32)
        for w in range(W):
            ids[w, Lp - L:] = np.concatenate([prompt, beams[n, w, :C - 1] + offs])
            mask[w, Lp - L:] = 1
        logp = continuation_logps(params, cfg, ids, mask, mode, variant)
        for w in range(W):
            for d in range(C):
                legal = catalog.legal(beams[n, w, :d])
                out[n, w, d, legal] = logp[w, d, legal]
    return out


def beam_search(params, cfg, catalog: Catalog, prompt, width: int, mode="f32",
                variant=None, pad_to=None):
    """The plain beam over the catalog's trie, one code a step: every live
    prefix is extended by each of its legal children, scored by the sum of
    the log-probabilities (softmax over the whole vocabulary slice) along it,
    and the best ``width`` are kept. A step's rows are ``prompt ++ prefix``
    filled up to the served rows' length with code 0 (no earlier position
    sees the fill: the layers are causal), ``width`` rows every step, so they
    share the compiled shape of `served_logps`. Returns (beams (width, C),
    scores (width,)), best first: what a serving system's answer is
    compared with when THIS arithmetic stands in its place."""
    C, K, base = cfg["sem_id_dim"], cfg["codebook_size"], cfg["base_vocab"]
    prompt = np.asarray(prompt, np.int32)
    L = len(prompt) + C - 1
    Lp = int(pad_to(len(prompt))) + C - 1 if pad_to is not None else L
    offs = base + np.arange(C - 1) * K
    live = [((), 0.0)]
    for d in range(C):
        ids = np.zeros((width, Lp), np.int32)
        mask = np.zeros((width, Lp), np.int32)
        for w in range(width):
            codes = np.zeros(C - 1, np.int64)
            codes[:d] = live[min(w, len(live) - 1)][0][:C - 1]
            ids[w, Lp - L:] = np.concatenate([prompt, codes + offs])
            mask[w, Lp - L:] = 1
        logp = continuation_logps(params, cfg, ids, mask, mode, variant)
        grown = [(prefix + (int(c),), score + float(logp[w, d, c]))
                 for w, (prefix, score) in enumerate(live)
                 for c in np.nonzero(catalog.legal(prefix))[0]]
        live = sorted(grown, key=lambda b: -b[1])[:width]
    return (np.array([b for b, _ in live], np.int64),
            np.array([s for _, s in live], np.float64))
