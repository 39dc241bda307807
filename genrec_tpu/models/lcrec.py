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

    beam_tokens = jnp.zeros((B, W, C), jnp.int32)
    beam_scores = jnp.full((B, W), -jnp.inf).at[:, 0].set(0.0)
    # Per-beam trie rank of the emitted prefix; root rank is 0. Dead
    # beams carry the trie's sentinel rank, whose legal_mask is all
    # False — their scores stay -inf from the step that killed them.
    beam_rank = jnp.zeros((B, W), jnp.int32)

    for c in range(C):
        lo = base_vocab + c * K
        logp = jax.nn.log_softmax(
            logits.astype(jnp.float32) / temperature, axis=-1
        )
        logp_w = jax.lax.dynamic_slice_in_dim(logp, lo, K, axis=1)
        if c == 0:
            if trie is not None:
                root = jnp.zeros((B,), jnp.int32)
                logp_w = jnp.where(
                    trie.legal_mask(root, 0), logp_w, -jnp.inf
                )
            # First step: all beams identical; expand from the B-row
            # logits. With beam_width > codebook_size only K distinct
            # first tokens exist — fill the rest with -inf beams (they
            # are displaced by real W*K candidates at step 1).
            W0 = min(W, K)
            scores, toks = jax.lax.top_k(logp_w, W0)  # (B, W0)
            if W0 < W:
                scores = jnp.concatenate(
                    [scores, jnp.full((B, W - W0), -jnp.inf)], axis=1
                )
                toks = jnp.concatenate(
                    [toks, jnp.zeros((B, W - W0), toks.dtype)], axis=1
                )
            beam_scores = scores
            beam_tokens = beam_tokens.at[:, :, 0].set(toks)
            if trie is not None:
                beam_rank = trie.advance(
                    jnp.zeros((B, W), jnp.int32), toks.astype(jnp.int32), 0
                )
        else:
            logp_w = logp_w.reshape(B, W, K)
            if trie is not None:
                logp_w = jnp.where(
                    trie.legal_mask(beam_rank, c), logp_w, -jnp.inf
                )
            combined = (beam_scores[..., None] + logp_w).reshape(B, W * K)
            beam_scores, idx = jax.lax.top_k(combined, W)
            parent = idx // K
            tok = idx % K
            beam_tokens = jnp.take_along_axis(beam_tokens, parent[..., None], axis=1)
            beam_tokens = beam_tokens.at[:, :, c].set(tok)
            if trie is not None:
                beam_rank = trie.advance(
                    jnp.take_along_axis(beam_rank, parent, axis=1),
                    tok.astype(jnp.int32), c,
                )
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
