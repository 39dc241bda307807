"""LCRec: LLM-based recommendation with collaborative semantics
(arXiv:2311.09049, ICDE 2024).

Parity target: reference genrec/models/lcrec.py — Qwen-class causal-LM
backbone (:39-40), `<Ci_j>` codebook special tokens appended to the vocab
with embedding resize (:48-60), SFT tokenization with prompt masking
(:88-112, labels -100 on prompt/pad), batched constrained beam search
(:164-243) driven by per-step allowed-token sets
(lcrec_trainer.py:87-128's ConstrainedDecodingHelper).

TPU redesign: because codebook tokens are appended as CONTIGUOUS vocab
ranges, the per-step constraint is a static slice — step c scores only
logits[base + c*K : base + (c+1)*K] — so the whole beam search compiles to
one jitted program over a shared KV cache (prompt encoded once, beams
share it) with no per-token host callback.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from genrec_tpu.models.backbones.qwen import QwenConfig, QwenLM


class LCRecGenerationOutput(NamedTuple):
    sem_ids: jax.Array  # (B, W, C) codebook indices (not token ids)
    log_probas: jax.Array  # (B, W)


def extend_vocab(
    cfg: QwenConfig,
    params,
    num_codebooks: int,
    codebook_size: int,
    key,
    base: int | None = None,
    pad_to: int = 1,
    min_rows: int = 0,
):
    """Append num_codebooks*codebook_size codebook tokens to the vocab.

    Mirrors `add_codebook_tokens` + `resize_token_embeddings`
    (lcrec.py:48-60): new embedding rows are drawn from the backbone's
    init distribution; token id of <Cc_k> = base + c*K + k.

    ``base`` defaults to cfg.vocab_size (append at the end). HF
    checkpoints often PAD the model vocab past len(tokenizer); their
    added-token ids start at len(tokenizer) < vocab_size, so the caller
    passes that id as ``base`` — rows in [base, base+n) are (re)initialized
    in place and the table only grows by what doesn't already fit.

    ``pad_to`` rounds the final vocab up to a multiple (tensor-parallel
    degree), so the embedding/lm_head rows stay shardable; the zero pad
    rows are never tokenizer-reachable and generation masks them via
    ``valid_vocab``. ``min_rows`` is a floor on the final row count (one
    chip's slice of a vocabulary-parallel head, held at its real size).
    Returns (new_cfg, new_params, base).
    """
    import dataclasses

    n_new = num_codebooks * codebook_size
    if base is None:
        base = cfg.vocab_size
    if base > cfg.vocab_size:
        raise ValueError(f"base {base} beyond model vocab {cfg.vocab_size}")
    need = base + n_new
    total = max(cfg.vocab_size, need, min_rows)
    total = -(-total // pad_to) * pad_to
    grow = max(0, total - cfg.vocab_size)
    new_cfg = dataclasses.replace(cfg, vocab_size=total)
    k1, k2 = jax.random.split(key)
    params = dict(params)

    def extended(table, k):
        rows = 0.02 * jax.random.normal(k, (n_new, table.shape[1]), table.dtype)
        if grow:
            table = jnp.concatenate(
                [table, jnp.zeros((grow, table.shape[1]), table.dtype)], axis=0
            )
        return jax.lax.dynamic_update_slice(table, rows, (base, 0))

    params["embed_tokens"] = extended(params["embed_tokens"], k1)
    if not cfg.tie_word_embeddings:
        params["lm_head"] = extended(params["lm_head"], k2)
    return new_cfg, params, base


def sft_loss(model: QwenLM, params, input_ids, attention_mask, labels,
             valid_vocab: int | None = None, use_fused_ce: bool = False,
             with_metrics: bool = False):
    """Causal-LM CE with -100-masked labels (HF convention: logits at t
    predict labels at t+1; reference lcrec_trainer.py uses model(labels=...)).
    ``valid_vocab`` masks vocab pad rows out of the softmax (TP padding).

    ``use_fused_ce`` routes the head through kernels/fused_ce.py: the
    (B, L, V) logits never materialize — at Qwen vocab scale (~150k) that
    is the single largest activation of the SFT step. Exact same loss;
    the valid_vocab mask becomes a row-slice of the head weights (a
    never-computed logit == a -inf-masked one).

    ``with_metrics`` returns (loss, metrics): the step's ``real_tokens``
    and the backbone's counters (`qwen.collect_counters`: expert load,
    picks on the experts held, keys the sparse selection kept)."""
    from genrec_tpu.models.backbones.qwen import collect_counters, collect_moe_aux
    from genrec_tpu.ops.losses import cross_entropy_with_ignore, mask_vocab_logits

    apply_kwargs = {}
    if use_fused_ce:
        apply_kwargs = dict(return_hidden=True, compute_logits=False)
    # The router's load-balance loss (sown by each QwenMoEMLP) and the
    # counters are dropped silently without mutable=; a dense backbone sows
    # neither.
    out, mut = model.apply(
        {"params": params}, input_ids, attention_mask=attention_mask,
        mutable=["losses", "counters"], **apply_kwargs,
    )
    aux = collect_moe_aux(mut) if model.cfg.num_experts > 0 else 0.0
    metrics = {"real_tokens": jnp.sum(attention_mask).astype(jnp.float32),
               **jax.lax.stop_gradient(collect_counters(mut))}
    done = lambda ce: (ce + aux, metrics) if with_metrics else ce + aux

    if use_fused_ce:
        from genrec_tpu.kernels.fused_ce import fused_ce_mean_loss

        _, h = out
        w = (
            params["embed_tokens"]
            if model.cfg.tie_word_embeddings
            else params["lm_head"]
        ).astype(model.dtype)
        if valid_vocab is not None:
            w = w[:valid_vocab]
        return done(fused_ce_mean_loss(
            h[:, :-1, :], w, labels[:, 1:], ignore_index=-100
        ))

    logits = mask_vocab_logits(out, valid_vocab)
    per_tok, valid = cross_entropy_with_ignore(
        logits[:, :-1, :], labels[:, 1:], ignore_index=-100
    )
    return done(per_tok.sum() / jnp.maximum(valid.sum(), 1))


def make_tp_sharded_fused_sft_loss(model: QwenLM, mesh, valid_vocab: int):
    """SFT loss with the fused CE running vocab-SHARDED over the "model"
    mesh axis (tensor parallelism).

    The backbone runs under GSPMD auto-sharding (qwen_rules constraints,
    as the plain tp path does); only the head CE enters a shard_map region:
    each model shard runs the dense fused kernel over its (Vpad/tp, d)
    slice of the head with offset-mapped targets, and the per-shard online
    softmax accumulators merge with one pmax + two psums
    (kernels/fused_ce.sharded_fused_linear_ce — a global-level custom_vjp
    whose fwd AND bwd each run their own primal-only shard_map). This is
    the configuration the dense fused path must refuse (a pallas_call is
    not GSPMD-partitionable over the vocab dim); inside shard_map the
    kernel only ever sees per-device local shapes, so no GSPMD
    partitioning of the Mosaic call is needed. Loss matches the replicated
    fused path to fp32 rounding; reference semantics as in sft_loss (ref
    lcrec_trainer.py SFT step with -100-masked labels).
    """
    from genrec_tpu.kernels.fused_ce import sharded_fused_linear_ce

    d = model.cfg.hidden_size

    def ce(h, w, t):
        # Global arrays: rows shard over "data", head rows over "model".
        return sharded_fused_linear_ce(
            h.reshape(-1, d), w.astype(model.dtype), t.reshape(-1),
            mesh, "model", "data", -100, valid_vocab,
        )

    def loss_fn(params, batch):
        input_ids = batch["input_ids"]
        attention_mask = batch["attention_mask"]
        labels = batch["labels"]
        if model.cfg.num_experts > 0:
            from genrec_tpu.models.backbones.qwen import collect_moe_aux

            out, mut = model.apply(
                {"params": params}, input_ids, attention_mask=attention_mask,
                mutable=["losses"], return_hidden=True, compute_logits=False,
            )
            aux = collect_moe_aux(mut)
        else:
            out = model.apply(
                {"params": params}, input_ids, attention_mask=attention_mask,
                return_hidden=True, compute_logits=False,
            )
            aux = 0.0
        _, h = out
        w = (
            params["embed_tokens"]
            if model.cfg.tie_word_embeddings
            else params["lm_head"]
        )
        t = labels[:, 1:]
        per_row = ce(h[:, :-1, :], w, t)
        valid = (t.reshape(-1) != -100).astype(jnp.float32)
        return per_row.sum() / jnp.maximum(valid.sum(), 1.0) + aux

    return loss_fn


def make_sp_sft_loss(
    cfg: QwenConfig,
    mesh,
    sp_axis: str = "sp",
    dtype=jnp.float32,
    remat: bool = False,
    valid_vocab: int | None = None,
):
    """Sequence-parallel SFT: the token dim is sharded over ``sp_axis`` and
    attention runs as ring attention (parallel/ring_attention.py) inside a
    shard_map — each device holds L/N tokens, K/V shards rotate over ICI,
    no L x L score matrix ever materializes. This is the long-context
    training path the reference lacks entirely (SURVEY.md §5.7).

    Labels are pre-shifted on the host (labels[t] <- labels[t+1]) so the
    next-token alignment never crosses a shard boundary; the masked-CE
    sum/count reduce with psum over (sp, data).

    Returns (model, loss_fn) where loss_fn(params, batch) -> scalar and
    batch carries input_ids / attention_mask / labels of shape (B, L) with
    L divisible by the sp size (and B by the data size).
    """
    import functools

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from genrec_tpu.ops.losses import cross_entropy_with_ignore

    n = mesh.shape[sp_axis]
    batch_axis = "data" if "data" in mesh.axis_names else None
    model = QwenLM(cfg, dtype=dtype, remat=remat, ring_axis=sp_axis, ring_size=n)
    spec = P(batch_axis, sp_axis)
    reduce_axes = (sp_axis,) + ((batch_axis,) if batch_axis else ())

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), spec, spec, spec, spec),
        out_specs=P(),
    )
    def _body(params, input_ids, attention_mask, positions, shifted_labels):
        from genrec_tpu.ops.losses import mask_vocab_logits

        logits = model.apply(
            {"params": params}, input_ids,
            attention_mask=attention_mask, positions=positions,
        )
        logits = mask_vocab_logits(logits, valid_vocab)
        per_tok, valid = cross_entropy_with_ignore(
            logits, shifted_labels, ignore_index=-100
        )
        s = jax.lax.psum(jnp.sum(per_tok), reduce_axes)
        v = jax.lax.psum(jnp.sum(valid), reduce_axes)
        return s / jnp.maximum(v, 1)

    def loss_fn(params, batch):
        ids = batch["input_ids"]
        am = batch["attention_mask"]
        labels = batch["labels"]
        B, L = ids.shape
        if L % n:
            raise ValueError(f"sequence length {L} not divisible by sp={n}")
        # Global left-pad-aware positions, computed BEFORE sharding.
        positions = jnp.maximum(jnp.cumsum(am, axis=1) - 1, 0)
        shifted = jnp.concatenate(
            [labels[:, 1:], jnp.full((B, 1), -100, labels.dtype)], axis=1
        )
        return _body(params, ids, am, positions, shifted)

    return model, loss_fn


def generate_greedy(
    model: QwenLM,
    params,
    input_ids,
    attention_mask,
    max_new_tokens: int,
    eos_id: int,
    max_cache: int | None = None,
    valid_vocab: int | None = None,
):
    """Unconstrained greedy decode with a KV cache (the reference's
    index2item eval path: `generate(..., do_sample=False)` without the
    prefix constraint, lcrec_trainer.py:215-227).

    ``valid_vocab`` masks logits at ids >= it: HF checkpoints pad the
    MODEL vocab past the tokenizer, and those live padding rows would
    otherwise be argmax-able ids the tokenizer cannot decode.

    Fully jittable: the decode loop is a lax.scan over max_new_tokens
    steps; rows that emit EOS keep emitting EOS. Returns (B, max_new)
    token ids."""
    B, L = input_ids.shape
    S = max_cache or (L + max_new_tokens)
    positions = jnp.maximum(jnp.cumsum(attention_mask, axis=1) - 1, 0)

    caches = model.apply({"params": params}, B, S, method=QwenLM.init_cache)
    pad = jnp.concatenate(
        [attention_mask, jnp.zeros((B, S - L), attention_mask.dtype)], axis=1
    )
    logits, caches = model.apply(
        {"params": params}, input_ids, positions, caches, pad,
        method=QwenLM.decode_step,
    )
    next_pos = positions[:, -1] + 1  # (B,)

    vocab_mask = None
    if valid_vocab is not None:
        vocab_mask = jnp.arange(logits.shape[-1]) < valid_vocab

    def body(carry, step):
        logits, caches, pad, done = carry
        logits = logits.astype(jnp.float32)
        if vocab_mask is not None:
            logits = jnp.where(vocab_mask, logits, -jnp.inf)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        tok = jnp.where(done, eos_id, tok)
        done = done | (tok == eos_id)
        slot = jnp.arange(S)[None, :]
        write_at = caches[0]["idx"].astype(jnp.int32)
        pad = jnp.where(slot == write_at, 1, pad)
        logits, caches = model.apply(
            {"params": params}, tok[:, None], (next_pos + step)[:, None],
            caches, pad, method=QwenLM.decode_step,
        )
        return (logits, caches, pad, done), tok

    done0 = jnp.zeros((B,), bool)
    _, toks = jax.lax.scan(
        body, (logits, caches, pad, done0), jnp.arange(max_new_tokens)
    )
    return toks.T  # (B, max_new)


# ---------------------------------------------------------------------------
# The beam over the codebook cascade: one definition for the dense generate
# loop below and the paged decode step (`lcrec_paged_decode_step`).
# ---------------------------------------------------------------------------


def first_beams(trie, logp_w, W: int, C: int):
    """The beams after code 0, from the prompt's last position. logp_w
    (B, K): log-probabilities of the first codebook's slice. All beams are
    identical before it, so the best ``W`` codes of the ONE row open them;
    with W > K only K distinct first codes exist and the rest start at -inf
    (displaced by real candidates at the next code). Returns beam_tokens
    (B, W, C), beam_scores (B, W) and beam_rank (B, W): each beam's trie rank
    (0 without a trie). Dead beams carry the trie's sentinel rank, whose
    legal mask is all False: their scores stay -inf from the step that
    killed them."""
    B, K = logp_w.shape
    if trie is not None:
        logp_w = jnp.where(trie.legal_mask(jnp.zeros((B,), jnp.int32), 0),
                           logp_w, -jnp.inf)
    W0 = min(W, K)
    scores, toks = jax.lax.top_k(logp_w, W0)
    if W0 < W:
        scores = jnp.concatenate(
            [scores, jnp.full((B, W - W0), -jnp.inf)], axis=1)
        toks = jnp.concatenate(
            [toks, jnp.zeros((B, W - W0), toks.dtype)], axis=1)
    beam_tokens = jnp.zeros((B, W, C), jnp.int32).at[:, :, 0].set(toks)
    beam_rank = jnp.zeros((B, W), jnp.int32)
    if trie is not None:
        beam_rank = trie.advance(beam_rank, toks.astype(jnp.int32), 0)
    return beam_tokens, scores, beam_rank


def beam_step(trie, logp_w, beam_tokens, beam_scores, beam_rank, step):
    """Extend every beam by code ``step`` and keep the best W of the W * K
    candidates. logp_w (B, W, K): each beam's log-probabilities over the
    codebook's slice. ``step`` is one code for the whole batch (an int: the
    dense loop) or a code a row ((B,) int32: serving slots, each at its own).
    Returns beam_tokens, beam_scores, beam_rank and ``parent`` (B, W): the
    beam each survivor extends, which its caches must follow."""
    from genrec_tpu.ops.trie import advance_ragged, legal_mask_ragged

    B, W, K = logp_w.shape
    ragged = not isinstance(step, int)
    if trie is not None:
        legal = (legal_mask_ragged(trie, beam_rank, step) if ragged
                 else trie.legal_mask(beam_rank, step))
        logp_w = jnp.where(legal, logp_w, -jnp.inf)
    combined = (beam_scores[..., None] + logp_w).reshape(B, W * K)
    beam_scores, idx = jax.lax.top_k(combined, W)
    parent, tok = idx // K, idx % K
    beam_tokens = jnp.take_along_axis(beam_tokens, parent[..., None], axis=1)
    if ragged:
        hit = jnp.arange(beam_tokens.shape[-1])[None, None, :] == step[:, None, None]
        beam_tokens = jnp.where(hit, tok[..., None], beam_tokens)
    else:
        beam_tokens = beam_tokens.at[:, :, step].set(tok)
    if trie is not None:
        rank = jnp.take_along_axis(beam_rank, parent, axis=1)
        tok32 = tok.astype(jnp.int32)
        beam_rank = (advance_ragged(trie, rank, tok32, step) if ragged
                     else trie.advance(rank, tok32, step))
    return beam_tokens, beam_scores, beam_rank, parent


def generate_topk_constrained(
    model: QwenLM,
    params,
    input_ids,
    attention_mask,
    base_vocab: int,
    num_codebooks: int,
    codebook_size: int,
    beam_width: int = 10,
    temperature: float = 1.0,
    max_cache: int | None = None,
    trie=None,
):
    """Constrained beam search over the codebook-token cascade.

    The prompt (left-padded via attention_mask) is encoded once per batch
    row into a KV cache; the cache is then broadcast across beams and C
    decode steps run with the static per-step vocabulary slice. Fully
    jittable (static shapes, no host callbacks).

    ``trie`` (optional, DenseTrie/PackedTrie/TensorTrie interface)
    restricts every step to corpus-valid sem-id tuples: each beam tracks
    its prefix rank through ``trie.advance`` and the step's codebook
    slice is masked with ``trie.legal_mask`` before top-k, so every
    surviving beam is a complete catalog item. With ``trie=None`` the
    search is exactly the unconstrained cascade.
    """
    B, L = input_ids.shape
    W = beam_width
    K = codebook_size
    C = num_codebooks
    S = max_cache or (L + C)

    # Positions must be left-pad-aware (HF convention).
    positions = jnp.maximum(jnp.cumsum(attention_mask, axis=1) - 1, 0)

    caches = model.apply({"params": params}, B, S, method=QwenLM.init_cache)
    pad = jnp.concatenate(
        [attention_mask, jnp.zeros((B, S - L), attention_mask.dtype)], axis=1
    )
    logits, caches = model.apply(
        {"params": params}, input_ids, positions, caches, pad,
        method=QwenLM.decode_step,
    )

    def map_cache(fn, c):
        # every entry but the write cursor has the batch in front (K, V
        # and, under sparse attention, the indexer's keys)
        return {name: a if name == "idx" else fn(a) for name, a in c.items()}

    caches = [map_cache(lambda a: jnp.repeat(a, W, axis=0), c) for c in caches]
    pad_bw = jnp.repeat(pad, W, axis=0)
    next_pos = positions[:, -1] + 1  # (B,)

    for c in range(C):
        lo = base_vocab + c * K
        logp = jax.nn.log_softmax(
            logits.astype(jnp.float32) / temperature, axis=-1
        )
        logp_w = jax.lax.dynamic_slice_in_dim(logp, lo, K, axis=1)
        if c == 0:
            beam_tokens, beam_scores, beam_rank = first_beams(
                trie, logp_w, W, C)
        else:
            beam_tokens, beam_scores, beam_rank, parent = beam_step(
                trie, logp_w.reshape(B, W, K), beam_tokens, beam_scores,
                beam_rank, c)
            # Reorder caches to follow the selected parents.
            flat_parent = (parent + jnp.arange(B)[:, None] * W).reshape(B * W)
            caches = [map_cache(lambda a: a[flat_parent], cc) for cc in caches]
        if c < C - 1:
            # Feed the chosen tokens and advance the cache one step.
            tok_ids = (beam_tokens[:, :, c] + base_vocab + c * K).reshape(B * W, 1)
            step_pos = (next_pos[:, None] + c).repeat(W, axis=0).reshape(B * W, 1)
            slot = jnp.arange(S)[None, :]
            write_at = (caches[0]["idx"]).astype(jnp.int32)
            pad_bw = jnp.where(slot == write_at, 1, pad_bw)
            logits, caches = model.apply(
                {"params": params}, tok_ids, step_pos, caches, pad_bw,
                method=QwenLM.decode_step,
            )

    return LCRecGenerationOutput(sem_ids=beam_tokens, log_probas=beam_scores)


# ---------------------------------------------------------------------------
# Serving through pages (serving/heads.LCRecGenerativeHead's paged protocol):
# the prompt's K and V of every full-attention layer live in the engine's
# page pool, shared by a slot's beams; what a beam owns lives in its slot's
# row: the K and V of its few generated tokens, and the recurrent state and
# convolution tails of every KDA layer. Layers of other kinds (latent or
# sparse attention) have no paged form.
# ---------------------------------------------------------------------------


def paged_layer_kinds(cfg: QwenConfig) -> tuple:
    """The mixer of each layer, refusing what has no paged form."""
    kinds = tuple(cfg.mixer_kind(i) for i in range(cfg.num_hidden_layers))
    if cfg.sparse_topk > 0 or "mla" in kinds:
        raise ValueError(
            "the paged LCRec path holds full-attention K/V pages and KDA "
            "states; sparse (indexer) and latent attention layers have no "
            "page row yet")
    return kinds


def lcrec_paged_state_zeros(model: QwenLM, n_slots: int, beams: int,
                            num_codebooks: int) -> dict:
    """The zeroed slot-major decode state, a flat dict. A row's leaves:

    - ``beam_seqs`` (W, C), ``beam_logps`` (W,), ``beam_rank`` (W,): the beam;
    - ``parent`` (W,): the beam each beam extended at the last step. The
      per-beam leaves below are stored as the step computed them and follow
      ``parent`` when the NEXT step reads them, so a reorder moves a state
      once (on its way into the recurrence), not twice;
    - a KDA layer i: ``kda_s{i}`` (W, H, K, K) float32 and ``kda_conv{i}``
      (W, 3, 3HK) float32, a beam's own; ``kda_s0_{i}`` (H, K, K) and
      ``kda_conv0_{i}`` (3, 3HK), the prompt's end state, which the prefill
      (or a warm admit) writes and the slot's first step hands every beam;
    - a full-attention layer j: ``suf_k{j}``, ``suf_v{j}`` (W, C-1, KV*hd),
      the K and V of the beam's generated tokens."""
    from genrec_tpu.models.backbones.kda import _CONV_KERNEL

    cfg = model.cfg
    W, T = beams, max(num_codebooks - 1, 1)
    state = {
        "beam_seqs": jnp.zeros((n_slots, W, num_codebooks), jnp.int32),
        "beam_logps": jnp.zeros((n_slots, W), jnp.float32),
        "beam_rank": jnp.zeros((n_slots, W), jnp.int32),
        "parent": jnp.zeros((n_slots, W), jnp.int32),
    }
    H, K = cfg.kda_heads, cfg.kda_head_dim
    for i, kind in enumerate(paged_layer_kinds(cfg)):
        if kind == "kda":
            conv = (_CONV_KERNEL - 1, 3 * H * K)
            state[f"kda_s{i}"] = jnp.zeros((n_slots, W, H, K, K), jnp.float32)
            state[f"kda_conv{i}"] = jnp.zeros((n_slots, W) + conv, jnp.float32)
            state[f"kda_s0_{i}"] = jnp.zeros((n_slots, H, K, K), jnp.float32)
            state[f"kda_conv0_{i}"] = jnp.zeros((n_slots,) + conv, jnp.float32)
        else:
            kv = (n_slots, W, T, cfg.num_key_value_heads * cfg.head_dim)
            state[f"suf_k{i}"] = jnp.zeros(kv, model.dtype)
            state[f"suf_v{i}"] = jnp.zeros(kv, model.dtype)
    return state


def lcrec_prefill_paged(model: QwenLM, params, trie, input_ids, attention_mask,
                        block_tables, k_pools, v_pools, base_vocab: int,
                        num_codebooks: int, codebook_size: int,
                        beam_width: int, temperature: float = 1.0):
    """The left-padded prompt through every layer, its full-attention K and
    V WRITTEN into the page pools through the batch's block tables (a row's
    real tokens first: nothing here depends on a position once K is made),
    the beams opened from the last position's trie-masked log-probabilities.

    Returns (k_pools, v_pools, init, counters). ``init``: a row a request of
    the leaves a slot starts from (``beam_*``, ``kda_s0_*``, ``kda_conv0_*``):
    what a bind writes and a prefix entry snapshots. ``counters``: the
    backbone's (`qwen.collect_counters`), e.g. the real token-expert pairs a
    held expert saw in this launch."""
    from genrec_tpu.models.backbones.qwen import collect_counters
    from genrec_tpu.ops.paged import write_pages

    kinds = paged_layer_kinds(model.cfg)
    B, L = input_ids.shape
    (logits, caches), mut = model.apply(
        {"params": params}, input_ids, attention_mask,
        method=QwenLM.prefill_cached, mutable=["counters"])
    # real tokens first: page rows past a slot's length are never read
    n_pad = L - jnp.sum(attention_mask, axis=1).astype(jnp.int32)
    src = (jnp.arange(L)[None, :] + n_pad[:, None]) % L
    front = lambda a: jnp.take_along_axis(a, src[:, :, None, None], axis=1)
    k_pools, v_pools = list(k_pools), list(v_pools)
    init, page_layer = {}, 0
    for i, (kind, c) in enumerate(zip(kinds, caches)):
        if kind == "kda":
            init[f"kda_s0_{i}"] = c["s"]
            init[f"kda_conv0_{i}"] = c["conv"]
        else:
            k_pools[page_layer] = write_pages(
                k_pools[page_layer], block_tables, jnp.moveaxis(front(c["k"]), 1, 2))
            v_pools[page_layer] = write_pages(
                v_pools[page_layer], block_tables, jnp.moveaxis(front(c["v"]), 1, 2))
            page_layer += 1
    with jax.named_scope("beam_update"):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32) / temperature, axis=-1)
        logp_w = jax.lax.dynamic_slice_in_dim(logp, base_vocab, codebook_size, axis=1)
        seqs, scores, rank = first_beams(trie, logp_w, beam_width, num_codebooks)
    init.update(beam_seqs=seqs, beam_logps=scores, beam_rank=rank)
    return tuple(k_pools), tuple(v_pools), init, collect_counters(mut)


def lcrec_paged_decode_step(model: QwenLM, params, trie, state: dict, steps,
                            block_tables, seq_lens, k_pools, v_pools,
                            base_vocab: int, codebook_size: int,
                            temperature: float = 1.0) -> dict:
    """Advance every slot by one code: slot s, at ``steps[s]`` = c in
    1..C-1, feeds each beam's code c-1 through the layers (a full-attention
    layer reads the prompt's pages and the beam's suffix, a KDA layer
    advances the beam's own state) and extends the beams by code c, with the
    trie mask and beam update of `generate_topk_constrained`. Rows at step 0
    (free slots) take no expert, write no state and are never read.

    Returns the leaves it changed (`lcrec_paged_state_zeros`); the prompt's
    end states ``kda_s0_*``/``kda_conv0_*`` are read-only."""
    kinds = paged_layer_kinds(model.cfg)
    S, W, C = state["beam_seqs"].shape
    K = codebook_size
    live = steps >= 1
    c_in = jnp.clip(steps - 1, 0, C - 1)  # the code fed; its suffix slot too
    c_out = jnp.clip(steps, 0, C - 1)  # the code this step decides
    first = steps == 1  # the beams still share the prompt's end state
    parent = state["parent"]

    def own(leaf, shared=None):
        """A per-beam leaf (S, W, ...) as this step's beams see it: gathered
        by the last step's ``parent``; at a slot's first step the prompt's,
        to all."""
        idx = parent.reshape((S, W) + (1,) * (leaf.ndim - 2))
        out = jnp.take_along_axis(leaf, idx, axis=1)
        if shared is not None:
            pick = first.reshape((S,) + (1,) * (leaf.ndim - 1))
            out = jnp.where(pick, shared[:, None], out)
        return out

    rows = lambda a: a.reshape((S * W,) + a.shape[2:])  # a row a beam

    caches, page_layer = [], 0
    for i, kind in enumerate(kinds):
        if kind == "kda":
            caches.append({
                "s": rows(own(state[f"kda_s{i}"], state[f"kda_s0_{i}"])),
                "conv": rows(own(state[f"kda_conv{i}"], state[f"kda_conv0_{i}"])),
                "idx": jnp.zeros((), jnp.int32)})
        else:
            caches.append({
                "k_pool": k_pools[page_layer], "v_pool": v_pools[page_layer],
                "block_tables": block_tables, "seq_lens": seq_lens, "t": c_in,
                "sk": own(state[f"suf_k{i}"]), "sv": own(state[f"suf_v{i}"])})
            page_layer += 1

    tok = jnp.take_along_axis(state["beam_seqs"], c_in[:, None, None], axis=2)[..., 0]
    tok_ids = (tok + base_vocab + c_in[:, None] * K).reshape(S * W, 1)
    beams = lambda a: jnp.repeat(a, W, axis=0)[:, None]
    logits, caches = model.apply(
        {"params": params}, tok_ids, beams(seq_lens + c_in), caches,
        beams(live.astype(jnp.int32)), method=QwenLM.decode_paged)

    with jax.named_scope("beam_update"):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32) / temperature, axis=-1)
        lo = base_vocab + c_out * K
        logp_w = jax.vmap(lambda row, at: jax.lax.dynamic_slice(row, (at,), (K,)))(
            logp, jnp.repeat(lo, W)).reshape(S, W, K)
        seqs, scores, rank, parent = beam_step(
            trie, logp_w, state["beam_seqs"], state["beam_logps"],
            state["beam_rank"], c_out)
    out = {"beam_seqs": seqs, "beam_logps": scores, "beam_rank": rank,
           "parent": parent}
    for i, (kind, c) in enumerate(zip(kinds, caches)):
        if kind == "kda":
            out[f"kda_s{i}"] = c["s"].reshape(state[f"kda_s{i}"].shape)
            out[f"kda_conv{i}"] = c["conv"].reshape(state[f"kda_conv{i}"].shape)
        else:
            out[f"suf_k{i}"], out[f"suf_v{i}"] = c["sk"], c["sv"]
    return out
