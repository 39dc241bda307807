"""TIGER: generative retrieval over semantic IDs (arXiv:2305.05065).

Parity target: reference genrec/models/tiger.py — encoder-decoder over the
flattened (item, codebook) token stream with a prepended hashed user token
(:166-173), SemIdEmbedding offset by token type, BOS-started decoder, flat
vocab = num_item_embeddings*sem_id_dim + 1 with a single output head
(:146-147), loss = per-sequence SUM of token CE then batch mean (:232-240).
The unused-but-present parameters of the reference (pos_embedding,
decoder_pos_embedding, out_proj — their additions are commented out in the
reference forward :173-176, 181-183) are kept for a matching param surface.

Generation — the north-star redesign (SURVEY.md §7 hard part #1): the
reference's CPU defaultdict trie + per-(batch, beam) Python masking/rerank
loops (tiger.py:341-447) become ONE jitted program: dense prefix-legality
gathers (ops/trie.py), Gumbel-top-k sampling without replacement (exactly
`torch.multinomial(probs, KK)`'s distribution), and vectorized
sort-based beam dedup. No host sync inside the decode loop.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from genrec_tpu.models.embeddings import SemIdEmbedding, UserIdEmbedding
from genrec_tpu.ops.losses import cross_entropy_with_ignore
from genrec_tpu.models.layers import RMSNorm
from genrec_tpu.models.t5transformer import (
    TransformerEncoderDecoder,
    causal_mask,
    gather_beam_caches,
    init_decode_caches,
)


class TigerOutput(NamedTuple):
    logits: jax.Array
    loss: Optional[jax.Array]


class TigerGenerationOutput(NamedTuple):
    sem_ids: jax.Array  # (B, K, D)
    log_probas: jax.Array  # (B, K)


class TigerPackedOutput(NamedTuple):
    per_example_loss: jax.Array  # (R, S) token-sum CE per segment
    loss: Optional[jax.Array]  # mean over valid segments
    real_tokens: jax.Array  # scalar: non-pad encoder slots in the batch


class Tiger(nn.Module):
    embedding_dim: int
    attn_dim: int
    dropout: float
    num_heads: int
    n_layers: int
    num_item_embeddings: int
    num_user_embeddings: int
    sem_id_dim: int
    max_pos: int = 2048
    dtype: jnp.dtype = jnp.float32
    # Round the output-head vocab (and sem-id table rows) up to a multiple
    # so tensor parallelism can shard them: the natural flat vocab
    # num_item_embeddings*sem_id_dim + 1 is odd, which at any even tp
    # degree forced the headline sharding rules into replication fallback.
    # Padded logit slots are masked to -1e9 so softmax/decode never see
    # them; padded embedding rows are never indexed.
    pad_vocab_to: int = 1

    @property
    def vocab_size(self) -> int:
        return self.num_item_embeddings * self.sem_id_dim + 1

    @property
    def padded_vocab_size(self) -> int:
        m = max(self.pad_vocab_to, 1)
        return -(-self.vocab_size // m) * m

    def _mask_pad_logits(self, logits):
        if self.padded_vocab_size == self.vocab_size:
            return logits
        live = jnp.arange(self.padded_vocab_size) < self.vocab_size
        return jnp.where(live, logits, -1e9)

    def setup(self):
        normal = nn.initializers.normal(stddev=1.0)
        self.bos_embedding = self.param("bos_embedding", normal, (self.embedding_dim,))
        self.norm = RMSNorm(self.embedding_dim, name="norm")
        self.norm_context = RMSNorm(self.embedding_dim, name="norm_context")
        self.drop = nn.Dropout(self.dropout)
        self.sem_id_embedding = SemIdEmbedding(
            self.num_item_embeddings, self.sem_id_dim, self.embedding_dim,
            dtype=self.dtype, rows_multiple=self.pad_vocab_to,
            name="sem_id_embedding",
        )
        self.user_id_embedding = UserIdEmbedding(
            self.num_user_embeddings, self.embedding_dim,
            dtype=self.dtype, name="user_id_embedding",
        )
        # Present in the reference but unused by its forward (additions
        # commented out); kept for parameter-surface parity.
        self.pos_embedding = self.param("pos_embedding", normal, (self.max_pos, self.embedding_dim))
        self.decoder_pos_embedding = self.param(
            "decoder_pos_embedding", normal, (self.sem_id_dim, self.embedding_dim)
        )
        dense = lambda d, name: nn.Dense(d, use_bias=False, dtype=self.dtype, name=name)
        self.in_proj = dense(self.attn_dim, "in_proj")
        self.in_proj_context = dense(self.attn_dim, "in_proj_context")
        self.out_proj = dense(self.embedding_dim, "out_proj")  # unused, parity
        self.transformer = TransformerEncoderDecoder(
            d_model=self.attn_dim,
            nhead=self.num_heads,
            num_encoder_layers=self.n_layers // 2,
            num_decoder_layers=self.n_layers // 2,
            dim_feedforward=1024,
            dropout=self.dropout,
            dtype=self.dtype,
            name="transformer",
        )
        self.output_head = dense(self.padded_vocab_size, "output_head")

    # ---- shared pieces -----------------------------------------------------

    def _encoder_input(self, user_input_ids, item_input_ids, token_type_ids, seq_mask):
        if user_input_ids.ndim == 1:
            user_input_ids = user_input_ids[:, None]
        user_emb = self.user_id_embedding(user_input_ids)  # (B, 1, D)
        item_emb = self.sem_id_embedding(item_input_ids, token_type_ids)
        enc = jnp.concatenate([user_emb, item_emb], axis=1)
        pad = jnp.concatenate(
            [jnp.zeros((seq_mask.shape[0], 1), bool), seq_mask == 0], axis=1
        )  # True = padding; user token always valid
        return enc, pad

    def _decoder_input(self, B, target_input_ids, target_token_type_ids):
        bos = jnp.broadcast_to(
            self.bos_embedding.astype(self.dtype), (B, 1, self.embedding_dim)
        )
        if target_input_ids is None or target_input_ids.shape[1] == 0:
            return bos
        tgt = self.sem_id_embedding(target_input_ids, target_token_type_ids)
        return jnp.concatenate([bos, tgt], axis=1)

    # ---- training forward --------------------------------------------------

    def __call__(
        self,
        user_input_ids,
        item_input_ids,
        token_type_ids,
        target_input_ids,
        target_token_type_ids,
        seq_mask,
        deterministic: bool = True,
    ) -> TigerOutput:
        if seq_mask is None:
            seq_mask = jnp.ones_like(item_input_ids)
        B = item_input_ids.shape[0]
        enc, pad = self._encoder_input(user_input_ids, item_input_ids, token_type_ids, seq_mask)
        dec = self._decoder_input(B, target_input_ids, target_token_type_ids)
        enc = self.in_proj_context(self.drop(self.norm_context(enc), deterministic=deterministic))
        dec = self.in_proj(self.drop(self.norm(dec), deterministic=deterministic))

        out = self.transformer(
            enc, dec,
            src_key_padding_mask=pad,
            memory_key_padding_mask=pad,
            deterministic=deterministic,
        )
        logits = self._mask_pad_logits(self.output_head(out))  # (B, T+1, V)
        loss = None
        if target_input_ids is not None and target_input_ids.shape[1] == self.sem_id_dim:
            target_vocab = target_token_type_ids * self.num_item_embeddings + target_input_ids
            # ignore_index=-1: vocab id 0 is a real token here, nothing is masked.
            per_tok, _ = cross_entropy_with_ignore(
                logits[:, :-1, :], target_vocab, ignore_index=-1
            )
            # Per-sequence SUM over tokens, then batch mean (tiger.py:232-240).
            loss = jnp.mean(jnp.sum(per_tok, axis=1))
        return TigerOutput(logits=logits, loss=loss)

    # ---- packed-sequence training ------------------------------------------

    def forward_packed(
        self,
        item_input_ids,
        token_type_ids,
        user_token_ids,
        user_mask,
        segment_ids,
        positions,
        target_ids,
        segment_valid,
        deterministic: bool = True,
    ) -> TigerPackedOutput:
        """Training forward over PACKED encoder rows.

        Multiple (user, history) examples share one encoder row: each
        segment starts with its user token (``user_mask`` marks the slot,
        ``user_token_ids`` carries the hashed id there), followed by the
        flattened sem-id history. Encoder self-attention is restricted to
        same-segment pairs, and every layer adds the ONE (1, H, L, L)
        relative-bias grid of slot distances that the unpacked forward
        builds, broadcast over rows. That is exact because of the packer's
        contract (`data.batching.pack_examples`): a segment's slots are one
        contiguous run numbered ``arange(n)``, so for a same-segment pair
        relative distance is slot distance, ``positions[k] - positions[q]
        == k - q``, and a T5 bias reads nothing but that difference;
        cross-segment pairs are masked before the softmax whatever bias
        they got. So each segment's encoder output equals the unpacked
        forward's exactly. ``positions`` is NOT read: it stays in the
        signature for the callers that pass it by position (the trainer,
        the benchmark's adapter). Rows laid out any other way (a segment
        split or interleaved) would need the per-row bias back. Decoders
        stay per example — (R*S, D+1) rows cross-attending into their own
        segment of the packed memory via a per-example memory mask.

        Shapes: token operands (R, L); ``target_ids`` (R, S, D);
        ``segment_valid`` (R, S) with S = max segments per row. Loss is the
        reference per-sequence token-sum CE averaged over VALID segments —
        identical to the unpacked batch mean over the same examples.
        """
        R, L = item_input_ids.shape
        item_emb = self.sem_id_embedding(item_input_ids, token_type_ids)
        user_emb = self.user_id_embedding(user_token_ids)
        enc = jnp.where(user_mask[..., None] == 1, user_emb, item_emb)
        pad = segment_ids == 0  # True = padding slot
        cross = segment_ids[:, :, None] != segment_ids[:, None, :]
        seg_mask = jnp.where(cross, -1e9, 0.0)[:, None]  # additive (R,1,L,L)
        enc = self.in_proj_context(
            self.drop(self.norm_context(enc), deterministic=deterministic)
        )
        with jax.named_scope("encoder"):
            memory = self.transformer.encoder(
                enc, attn_mask=seg_mask, key_padding_mask=pad,
                deterministic=deterministic,
            )

        _, S, D = target_ids.shape
        N = R * S
        tgt_flat = target_ids.reshape(N, D)
        tgt_types = jnp.broadcast_to(jnp.arange(D), (N, D))
        dec = self._decoder_input(N, tgt_flat, tgt_types)
        dec = self.in_proj(self.drop(self.norm(dec), deterministic=deterministic))
        # Per-example memory: segment s of row r, selected by mask. The
        # repeat is decoder-side only (N ≈ examples, same as the unpacked
        # decoder batch) — the packed ENCODER ran R rows, which is the win.
        mem = jnp.repeat(memory, S, axis=0)  # (N, L, attn_dim)
        seg_of = jnp.tile(jnp.arange(1, S + 1), R)  # (N,)
        mem_pad = jnp.repeat(segment_ids, S, axis=0) != seg_of[:, None]
        with jax.named_scope("decoder"):
            out = self.transformer.decoder(
                dec, mem,
                attn_mask=causal_mask(dec.shape[1]),
                memory_key_padding_mask=mem_pad,
                deterministic=deterministic,
            )
        with jax.named_scope("loss"):
            logits = self._mask_pad_logits(self.output_head(out))
            target_vocab = tgt_types * self.num_item_embeddings + tgt_flat
            per_tok, _ = cross_entropy_with_ignore(
                logits[:, :-1, :], target_vocab, ignore_index=-1
            )
            seq_loss = per_tok.sum(axis=1).reshape(R, S)
            valid = segment_valid.astype(jnp.float32)
            loss = (seq_loss * valid).sum() / jnp.maximum(valid.sum(), 1.0)
        return TigerPackedOutput(
            per_example_loss=seq_loss, loss=loss,
            real_tokens=jnp.sum(segment_ids != 0),
        )

    # ---- generation --------------------------------------------------------

    def encode_context(self, user_input_ids, item_input_ids, token_type_ids, seq_mask):
        with jax.named_scope("encoder"):
            enc, pad = self._encoder_input(
                user_input_ids, item_input_ids, token_type_ids, seq_mask)
            enc = self.in_proj_context(self.norm_context(enc))
            memory = self.transformer.encoder(
                enc, key_padding_mask=pad, deterministic=True)
        return memory, pad

    def decode_step(self, memory, memory_pad, tgt_ids, tgt_type):
        """Logits at the last position given the (possibly empty) prefix."""
        B = memory.shape[0]
        dec = self._decoder_input(B, tgt_ids, tgt_type)
        dec = self.in_proj(self.norm(dec))
        out = self.transformer.decoder(
            dec, memory,
            attn_mask=causal_mask(dec.shape[1]),
            memory_key_padding_mask=memory_pad,
            deterministic=True,
        )
        logits = self._mask_pad_logits(self.output_head(out))
        return logits[:, -1, :].astype(jnp.float32)

    # ---- KV-cached incremental generation ----------------------------------

    def encode_for_decode(self, user_input_ids, item_input_ids, token_type_ids, seq_mask):
        """Encoder pass + once-per-batch cross-attention K/V projection.

        Returns (cross_kvs, pad) with everything batch-sized (B, not B*K):
        the decode steps resolve the beam axis by einsum instead of
        broadcasting the memory K-fold into HBM.
        """
        memory, pad = self.encode_context(
            user_input_ids, item_input_ids, token_type_ids, seq_mask
        )
        cross_kvs = self.transformer.decoder.precompute_cross_kv(memory)
        return cross_kvs, pad

    def decode_step_cached(self, last_tok, caches, cross_kvs, memory_pad, step: int):
        """Logits for decode position ``step`` given only the PREVIOUS
        token (None at step 0 = BOS), against the KV caches.

        last_tok: (B, K) int or None. Returns (logits (B, K, V) fp32,
        new_caches). Position-wise pieces (embedding, norm, in_proj,
        output head) match the uncached `decode_step` exactly; attention
        reads the cache instead of re-running the prefix.
        """
        B = memory_pad.shape[0]
        K = caches[0]["k"].shape[1]
        if last_tok is None:
            x = jnp.broadcast_to(
                self.bos_embedding.astype(self.dtype), (B, K, self.embedding_dim)
            )
        else:
            tok_type = jnp.full_like(last_tok, step - 1)
            x = self.sem_id_embedding(last_tok, tok_type)
        x = self.in_proj(self.norm(x))
        x, new_caches = self.transformer.decoder.decode_step(
            x, caches, cross_kvs, memory_key_padding_mask=memory_pad, step=step
        )
        logits = self._mask_pad_logits(self.output_head(x))
        return logits.astype(jnp.float32), new_caches

    def decode_tree_paged(self, node_tok, topo, steps, caches, k_pools,
                          v_pools, block_tables, seq_lens):
        """Speculative tree verification: logits for EVERY candidate-tree
        node in one parallel decoder pass (ops/spec_tree.py).

        node_tok: (S, N) — level-major flat node inputs: level-0 nodes
        carry each beam's last committed token (exactly the plain step's
        input; BOS where the slot is at step 0), level-l nodes carry the
        drafted step-(t+l-1) candidates. Each node's logits are computed
        with the same per-element ops as `decode_step_paged` would use
        at its step, so an accepted path is bitwise the sequential plain
        steps. Returns (logits (S, N, V) fp32, per-layer (k_new, v_new))
        — the committed caches in ``caches`` are read, never written.
        """
        S_, N = node_tok.shape
        node_steps = steps[:, None] + jnp.asarray(topo.level)[None, :]
        bos = jnp.broadcast_to(
            self.bos_embedding.astype(self.dtype), (S_, N, self.embedding_dim)
        )
        tok_type = jnp.clip(node_steps - 1, 0, self.sem_id_dim - 1)
        emb = self.sem_id_embedding(node_tok, tok_type)
        x = jnp.where((node_steps == 0)[..., None], bos, emb)
        x = self.in_proj(self.norm(x))
        x, node_kvs = self.transformer.decoder.decode_tree(
            x, caches, k_pools, v_pools, block_tables, seq_lens, topo, steps
        )
        logits = self._mask_pad_logits(self.output_head(x))
        return logits.astype(jnp.float32), node_kvs

    def decode_step_paged(self, last_tok, caches, k_pools, v_pools,
                          block_tables, seq_lens, steps):
        """`decode_step_cached` over PAGED cross-attention K/V with a
        per-row step operand — the slot-level continuous-batching decode:
        every row advances one position, rows may sit at different steps.

        last_tok: (S, K) int32; rows with steps[s] == 0 ignore it and
        start from BOS. caches: per-layer dense suffix caches (S, K,
        sem_id_dim, H, hd) — tiny, per-beam; the big history K/V stays in
        the shared pools, read through block_tables/seq_lens.
        """
        S_, K = last_tok.shape
        bos = jnp.broadcast_to(
            self.bos_embedding.astype(self.dtype), (S_, K, self.embedding_dim)
        )
        tok_type = jnp.broadcast_to(
            jnp.clip(steps - 1, 0, self.sem_id_dim - 1)[:, None], (S_, K)
        )
        emb = self.sem_id_embedding(last_tok, tok_type)
        x = jnp.where((steps == 0)[:, None, None], bos, emb)
        x = self.in_proj(self.norm(x))
        x, new_caches = self.transformer.decoder.decode_step_paged(
            x, caches, k_pools, v_pools, block_tables, seq_lens, steps
        )
        logits = self._mask_pad_logits(self.output_head(x))
        return logits.astype(jnp.float32), new_caches


def _dedup_top_k(scores, keys, k):
    """Per-row: keep the best-scoring instance of each key, return top-k.

    scores, keys: (M,). Returns (top_scores, top_idx) with duplicates of a
    key reduced to its best instance (vectorized replacement for the
    reference's per-batch Python dedup loop, tiger.py:396-447).
    """
    with jax.named_scope("beam_dedup_top_k"):
        order = jnp.lexsort((-scores, keys))  # sort by key, best score first
        ks = keys[order]
        first = jnp.concatenate([jnp.ones((1,), bool), ks[1:] != ks[:-1]])
        keep = jnp.zeros_like(first).at[order].set(first)
        masked = jnp.where(keep, scores, -jnp.inf)
        top_scores, top_idx = jax.lax.top_k(masked, k)
    return top_scores, top_idx


def tiger_generate(
    model: Tiger,
    params,
    trie,
    user_input_ids,
    item_input_ids,
    token_type_ids,
    seq_mask,
    rng: jax.Array,
    temperature: float = 0.2,
    n_top_k_candidates: int = 10,
    sample_factor: int = 6,
    deterministic: bool = False,
    use_cache: bool = True,
) -> TigerGenerationOutput:
    """Trie-constrained beam search, fully on device and jit-friendly.

    Matches the reference's procedure (tiger.py:312-452): at each of
    sem_id_dim steps sample KK = K*sample_factor candidates WITHOUT
    replacement from softmax(masked_logits / temperature) (Gumbel-top-k ==
    multinomial without replacement), accumulate log-probs, dedup by full
    sequence, keep top K. With deterministic=True the sampling noise is
    dropped (pure beam search).

    use_cache=True (default) runs the KV-cached incremental engine:
    self-attention appends one position per step, cross-attention K/V are
    projected once from the batch-sized memory, and beam reorders gather
    the cache — O(1) attention per step instead of re-running the whole
    prefix over a K-fold-expanded memory. Both paths share the sampling /
    dedup loop below, so their outputs are identical up to float
    association (parity pinned by tests/test_decode_cache.py).
    """
    B = item_input_ids.shape[0]
    K = n_top_k_candidates
    Kcb = model.num_item_embeddings
    D = model.sem_id_dim
    KK = min(K * sample_factor, Kcb)

    if use_cache:
        cross_kvs, pad = model.apply(
            {"params": params}, user_input_ids, item_input_ids, token_type_ids,
            seq_mask, method=Tiger.encode_for_decode,
        )
        caches = init_decode_caches(
            len(cross_kvs), B, K, D, model.num_heads, model.attn_dim, model.dtype
        )
    else:
        memory, pad = model.apply(
            {"params": params}, user_input_ids, item_input_ids, token_type_ids,
            seq_mask, method=Tiger.encode_context,
        )
        Lm = memory.shape[1]
        memory = jnp.broadcast_to(memory[:, None], (B, K, Lm, memory.shape[-1])).reshape(B * K, Lm, -1)
        pad = jnp.broadcast_to(pad[:, None], (B, K, Lm)).reshape(B * K, Lm)

    beam_seqs = jnp.zeros((B, K, D), jnp.int32)
    beam_logps = jnp.zeros((B, K), jnp.float32)
    prefix_idx = jnp.zeros((B, K), jnp.int32)

    for step in range(D):
        if use_cache:
            last_tok = None if step == 0 else beam_seqs[:, :, step - 1]
            logits, caches = model.apply(
                {"params": params}, last_tok, caches, cross_kvs, pad, step,
                method=Tiger.decode_step_cached,
            )
            logits = logits.reshape(B * K, -1)
        else:
            if step == 0:
                tgt_ids, tgt_type = None, None
            else:
                tgt_ids = beam_seqs[:, :, :step].reshape(B * K, step)
                tgt_type = jnp.broadcast_to(jnp.arange(step), (B * K, step))
            logits = model.apply(
                {"params": params}, memory, pad, tgt_ids, tgt_type,
                method=Tiger.decode_step,
            )  # (B*K, V)
        window = jax.lax.dynamic_slice_in_dim(logits, step * Kcb, Kcb, axis=1)
        legal = trie.legal_mask(prefix_idx.reshape(B * K), step)  # (B*K, Kcb)
        masked = jnp.where(legal, window, -1e32)
        logp = jax.nn.log_softmax(masked / temperature, axis=-1)

        if deterministic:
            perturbed = logp
        else:
            rng, sub = jax.random.split(rng)
            perturbed = logp + jax.random.gumbel(sub, logp.shape)
        _, cand_tok = jax.lax.top_k(perturbed, KK)  # (B*K, KK)
        cand_logp = jnp.take_along_axis(logp, cand_tok, axis=1)
        # Candidates drawn from dead/illegal slots must never win.
        cand_legal = jnp.take_along_axis(legal, cand_tok, axis=1)
        cand_logp = jnp.where(cand_legal, cand_logp, -1e32)

        total = (beam_logps.reshape(B * K, 1) + cand_logp).reshape(B, K * KK)
        toks = cand_tok.reshape(B, K * KK)
        parents = jnp.broadcast_to(jnp.arange(K)[:, None], (K, KK)).reshape(1, K * KK)
        parents = jnp.broadcast_to(parents, (B, K * KK))

        # Dedup key = packed candidate sequence (parent prefix advanced).
        parent_prefix = jnp.take_along_axis(prefix_idx, parents, axis=1)
        keys = parent_prefix * Kcb + toks
        top_scores, top_idx = jax.vmap(lambda s, c: _dedup_top_k(s, c, K))(total, keys)

        sel_parent = jnp.take_along_axis(parents, top_idx, axis=1)  # (B, K)
        sel_tok = jnp.take_along_axis(toks, top_idx, axis=1)
        beam_seqs = jnp.take_along_axis(beam_seqs, sel_parent[..., None], axis=1)
        beam_seqs = beam_seqs.at[:, :, step].set(sel_tok)
        sel_prefix = jnp.take_along_axis(prefix_idx, sel_parent, axis=1)
        prefix_idx = trie.advance(sel_prefix, sel_tok, step)
        beam_logps = top_scores
        if use_cache:
            caches = gather_beam_caches(caches, sel_parent)

    return TigerGenerationOutput(sem_ids=beam_seqs, log_probas=beam_logps)


# ---- paged decode (ragged paged KV + slot-level continuous batching) --------
#
# The serving engine keeps the decode heads' history K/V in a shared page
# pool (serving/kv_pool.py) and advances up to max_slots requests — each
# possibly at a DIFFERENT decode step — in one fixed-shape call. The step
# below is that call's body; `tiger_generate_paged` drives it with all
# rows in lockstep as the parity reference against the dense-cache
# `tiger_generate` (pinned <=1e-5 in tests/test_paged_parity.py).


def init_tiger_paged_state(model: Tiger, n_slots: int, beams: int,
                           draft_hint: bool = False):
    """Zeroed slot-major decode state. cache_k/cache_v stack the per-layer
    suffix caches on axis 1 so the whole state is a flat dict of arrays
    (the engine scatters admitted rows into it host-side).
    ``draft_hint=True`` (speculative engines) adds the per-slot step-0
    logit window the prefill computes for the drafter."""
    nl = model.n_layers // 2
    H = model.num_heads
    hd = model.attn_dim // H
    D = model.sem_id_dim
    state = {
        "beam_seqs": jnp.zeros((n_slots, beams, D), jnp.int32),
        "beam_logps": jnp.zeros((n_slots, beams), jnp.float32),
        "prefix_idx": jnp.zeros((n_slots, beams), jnp.int32),
        "cache_k": jnp.zeros((n_slots, nl, beams, D, H, hd), model.dtype),
        "cache_v": jnp.zeros((n_slots, nl, beams, D, H, hd), model.dtype),
    }
    if draft_hint:
        state["logits0"] = jnp.zeros(
            (n_slots, model.num_item_embeddings), jnp.float32
        )
    return state


def _tiger_beam_update(model: Tiger, trie, logits, beam_seqs, beam_logps,
                       prefix_idx, steps, rng, temperature: float,
                       sample_factor: int):
    """One constrained-beam selection given this step's (S, K, V) logits
    — the post-logits math of the paged decode step, factored out so the
    speculative accept scan (`tiger_spec_tree_step`) replays the SAME
    definition per tree level: spec == plain is structural, not a
    parallel implementation kept in sync by hand.

    Returns (beam_seqs, beam_logps, prefix_idx, sel_parent, sel_tok).
    """
    from genrec_tpu.ops.trie import advance_ragged, legal_mask_ragged

    with jax.named_scope("beam_update"):
        S_, K, D = beam_seqs.shape
        Kcb = model.num_item_embeddings
        KK = min(K * sample_factor, Kcb)
        flat = logits.reshape(S_ * K, -1)
        window = jax.vmap(
            lambda row, st: jax.lax.dynamic_slice(row, (st * Kcb,), (Kcb,))
        )(flat, jnp.repeat(steps, K))  # per-row vocab window at its own step
        legal = legal_mask_ragged(trie, prefix_idx, steps).reshape(S_ * K, Kcb)
        masked = jnp.where(legal, window, -1e32)
        logp = jax.nn.log_softmax(masked / temperature, axis=-1)

        perturbed = logp if rng is None else logp + jax.random.gumbel(rng, logp.shape)
        _, cand_tok = jax.lax.top_k(perturbed, KK)
        cand_logp = jnp.take_along_axis(logp, cand_tok, axis=1)
        cand_legal = jnp.take_along_axis(legal, cand_tok, axis=1)
        cand_logp = jnp.where(cand_legal, cand_logp, -1e32)

        total = (beam_logps.reshape(S_ * K, 1) + cand_logp).reshape(S_, K * KK)
        toks = cand_tok.reshape(S_, K * KK)
        parents = jnp.broadcast_to(jnp.arange(K)[:, None], (K, KK)).reshape(1, K * KK)
        parents = jnp.broadcast_to(parents, (S_, K * KK))

        parent_prefix = jnp.take_along_axis(prefix_idx, parents, axis=1)
        keys = parent_prefix * Kcb + toks
        top_scores, top_idx = jax.vmap(lambda s, c: _dedup_top_k(s, c, K))(total, keys)

        sel_parent = jnp.take_along_axis(parents, top_idx, axis=1)  # (S, K)
        sel_tok = jnp.take_along_axis(toks, top_idx, axis=1)
        new_seqs = jnp.take_along_axis(beam_seqs, sel_parent[..., None], axis=1)
        hit = jnp.arange(D)[None, None, :] == steps[:, None, None]
        new_seqs = jnp.where(hit, sel_tok[..., None], new_seqs)
        sel_prefix = jnp.take_along_axis(prefix_idx, sel_parent, axis=1)
        new_prefix = advance_ragged(trie, sel_prefix, sel_tok, steps)
    return new_seqs, top_scores, new_prefix, sel_parent, sel_tok


def tiger_paged_decode_step(
    model: Tiger,
    params,
    trie,
    state: dict,
    steps,
    block_tables,
    seq_lens,
    k_pools,
    v_pools,
    rng=None,
    temperature: float = 0.2,
    sample_factor: int = 6,
):
    """Advance every slot one constrained-beam position (per-slot steps).

    Mirrors one iteration of `tiger_generate`'s loop exactly, with the
    static ``step`` replaced by the (S,) ``steps`` operand: the vocab
    window, trie tables and cache write slot are all row-selected.
    rng=None is deterministic pure beam search (the serving default);
    passing a key reproduces the Gumbel-top-k sampling path.
    Inactive/garbage rows (the engine's free slots) compute harmlessly —
    nothing here reduces across rows.
    """
    S_, K, D = state["beam_seqs"].shape
    caches = [
        {"k": state["cache_k"][:, i], "v": state["cache_v"][:, i]}
        for i in range(state["cache_k"].shape[1])
    ]

    last_tok = jnp.take_along_axis(
        state["beam_seqs"], jnp.clip(steps - 1, 0, D - 1)[:, None, None], axis=2
    )[:, :, 0]
    logits, caches = model.apply(
        {"params": params}, last_tok, caches, k_pools, v_pools,
        block_tables, seq_lens, steps, method=Tiger.decode_step_paged,
    )  # (S, K, V)
    beam_seqs, beam_logps, prefix_idx, sel_parent, _ = _tiger_beam_update(
        model, trie, logits, state["beam_seqs"], state["beam_logps"],
        state["prefix_idx"], steps, rng, temperature, sample_factor,
    )
    caches = gather_beam_caches(caches, sel_parent)

    return {
        "beam_seqs": beam_seqs,
        "beam_logps": beam_logps,
        "prefix_idx": prefix_idx,
        "cache_k": jnp.stack([c["k"] for c in caches], axis=1),
        "cache_v": jnp.stack([c["v"] for c in caches], axis=1),
    }


def tiger_spec_tree_step(
    model: Tiger,
    params,
    trie,
    state: dict,
    steps,
    block_tables,
    seq_lens,
    k_pools,
    v_pools,
    fanout: int = 4,
    depth: int | None = None,
    temperature: float = 0.2,
    sample_factor: int = 6,
    draft_override=None,
):
    """Speculative tree decode: commit between 1 and ``depth + 1``
    constrained-beam positions per slot in ONE target-model invocation.

    Draft: per beam, the top-``fanout`` trie-legal continuations ranked
    by the trie's draft weights (`ops.trie.legal_topk_ragged`), expanded
    ``depth`` levels into a static-topology tree. Verify: one parallel
    decoder pass over every node (`Tiger.decode_tree_paged`) — level 0
    is the current step's own forward, always exact. Accept: replay the
    plain beam update (`_tiger_beam_update`, the same definition the
    plain step runs) level by level on the verified logits; a level
    commits only while every selected (parent, token) pair was a drafted
    tree edge, so the result equals running the plain step accept-many
    times, bit for bit, and the drafter-disagrees worst case commits
    exactly 1 (plain decode's rate — never slower in steps, never
    different in output).

    Deterministic beams only (the serving contract): sampling would need
    per-level rngs that the plain path draws sequentially.
    ``draft_override`` (tests) replaces the drafter's level-l candidate
    arrays, e.g. to force full rejection.

    Returns (new_state, accept (S,) int32 codes committed per slot).
    """
    from genrec_tpu.ops.spec_tree import (
        TreeTopology, commit_level_kv, match_drafted,
    )
    from genrec_tpu.ops.trie import advance_ragged, legal_topk_ragged

    S_, K, D = state["beam_seqs"].shape
    if depth is None:
        depth = D - 1
    depth = max(min(int(depth), D - 1), 0)
    topo = TreeTopology(K, fanout, depth)
    caches = [
        {"k": state["cache_k"][:, i], "v": state["cache_v"][:, i]}
        for i in range(state["cache_k"].shape[1])
    ]

    # -- draft the candidate tree (trie gathers only — no model work) --------
    last_tok = jnp.take_along_axis(
        state["beam_seqs"], jnp.clip(steps - 1, 0, D - 1)[:, None, None], axis=2
    )[:, :, 0]
    levels_tok = [last_tok]  # level-0 inputs == the plain step's inputs
    draft_toks = []
    cur_prefix = state["prefix_idx"]  # (S, N_prev), N_0 = K
    for l in range(1, depth + 1):
        step_l = jnp.minimum(steps + (l - 1), D - 1)  # clip: overdeep levels
        if draft_override is not None:                # are never accepted
            d_tok = jnp.asarray(draft_override[l - 1], jnp.int32)
        else:
            d_tok, _ = legal_topk_ragged(trie, cur_prefix, step_l,
                                         topo.fanouts[l - 1])
            if l == 1 and "logits0" in state:
                # Step-0 drafting from the model's OWN prefill-computed
                # logits (see tiger_prefill_paged): the root codebook's
                # branching carries no popularity signal, but the top-F
                # of the step-0 window covers the verified beam almost
                # surely. Rows past step 0 keep the trie-weight draft.
                _, hint = jax.lax.top_k(state["logits0"],
                                        topo.fanouts[0])  # (S, F1)
                d_tok = jnp.where(
                    (steps == 0)[:, None, None],
                    jnp.broadcast_to(hint[:, None, :], d_tok.shape
                                     ).astype(jnp.int32),
                    d_tok,
                )
        draft_toks.append(d_tok)  # (S, N_{l-1}, F)
        levels_tok.append(d_tok.reshape(S_, -1))
        cur_prefix = advance_ragged(
            trie, jnp.broadcast_to(cur_prefix[..., None], d_tok.shape),
            d_tok, step_l,
        ).reshape(S_, -1)
    node_tok = jnp.concatenate(levels_tok, axis=1)  # (S, N)

    # -- verify: one parallel pass over the whole tree -----------------------
    logits_all, node_kvs = model.apply(
        {"params": params}, node_tok, topo, steps, caches, k_pools, v_pools,
        block_tables, seq_lens, method=Tiger.decode_tree_paged,
    )  # (S, N, V), per-layer (k_new, v_new)

    # -- accept scan: replay the plain update along the drafted tree --------
    run_seqs = com_seqs = state["beam_seqs"]
    run_logps = com_logps = state["beam_logps"]
    run_prefix = com_prefix = state["prefix_idx"]
    run_ck = com_ck = [c["k"] for c in caches]
    run_cv = com_cv = [c["v"] for c in caches]
    cur_local = jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32)[None], (S_, K))
    ok = jnp.ones((S_,), bool)
    accept = jnp.zeros((S_,), jnp.int32)
    for j in range(depth + 1):
        applied = ok & (steps + j <= D - 1)  # (S,) — per-slot acceptance
        step_j = jnp.minimum(steps + j, D - 1)
        flat_idx = topo.level_offsets[j] + cur_local  # (S, K) node ids
        logits_j = jnp.take_along_axis(logits_all, flat_idx[..., None], axis=1)
        new_seqs, new_logps, new_prefix, sel_parent, sel_tok = _tiger_beam_update(
            model, trie, logits_j, run_seqs, run_logps, run_prefix, step_j,
            None, temperature, sample_factor,
        )
        new_ck, new_cv = commit_level_kv(
            node_kvs, run_ck, run_cv, flat_idx, sel_parent, step_j
        )
        ap2 = applied[:, None]
        ap5 = applied[:, None, None, None, None]
        com_seqs = jnp.where(applied[:, None, None], new_seqs, com_seqs)
        com_logps = jnp.where(ap2, new_logps, com_logps)
        com_prefix = jnp.where(ap2, new_prefix, com_prefix)
        com_ck = [jnp.where(ap5, n, c) for n, c in zip(new_ck, com_ck)]
        com_cv = [jnp.where(ap5, n, c) for n, c in zip(new_cv, com_cv)]
        accept = accept + applied.astype(jnp.int32)
        if j < depth:
            parent_local = jnp.take_along_axis(cur_local, sel_parent, axis=1)
            matched, child_f = match_drafted(draft_toks[j], parent_local, sel_tok)
            ok = applied & matched
            cur_local = parent_local * topo.fanouts[j] + child_f
            run_seqs, run_logps, run_prefix = new_seqs, new_logps, new_prefix
            run_ck, run_cv = new_ck, new_cv

    new_state = {
        "beam_seqs": com_seqs,
        "beam_logps": com_logps,
        "prefix_idx": com_prefix,
        "cache_k": jnp.stack(com_ck, axis=1),
        "cache_v": jnp.stack(com_cv, axis=1),
    }
    return new_state, accept


def tiger_prefill_paged(model: Tiger, params, user_input_ids, item_input_ids,
                        token_type_ids, seq_mask, block_tables,
                        k_pools, v_pools, trie=None, draft_hint: bool = False):
    """Bucketed prefill that writes its cross-attention K/V straight into
    the page pools. Returns (k_pools, v_pools, seq_lens, extras) —
    seq_lens is the per-row valid KV length (user token + real sem-id
    tokens), which assumes the serving layout's CONTIGUOUS valid prefix
    in seq_mask. Rows padded beyond their page allocation scatter into
    the reserved null page (block-table entry 0) and are never read
    unmasked.

    ``draft_hint=True`` (the speculative engine) additionally runs the
    single BOS decoder position against the fresh encoder memory and
    returns ``extras["logits0"]`` — the trie-masked step-0 vocab window.
    That is the "head's own logits" drafter signal: TIGER's step-0
    branching is the whole root codebook, where popularity ranking has
    no model signal, but the model's OWN step-0 scores drafted at
    prefill cover the verified step-0 beam almost surely (a near-free
    extra decode position amortized into the prefill pass; it only needs
    to RANK candidates, so dense-vs-paged float association is
    harmless).
    """
    from genrec_tpu.ops.paged import write_pages

    cross_kvs, pad = model.apply(
        {"params": params}, user_input_ids, item_input_ids, token_type_ids,
        seq_mask, method=Tiger.encode_for_decode,
    )
    seq_lens = (~pad).sum(axis=1).astype(jnp.int32)
    extras = {}
    if draft_hint:
        B = pad.shape[0]
        caches = init_decode_caches(
            len(cross_kvs), B, 1, model.sem_id_dim, model.num_heads,
            model.attn_dim, model.dtype,
        )
        logits, _ = model.apply(
            {"params": params}, None, caches, cross_kvs, pad, 0,
            method=Tiger.decode_step_cached,
        )  # (B, 1, V)
        window = logits[:, 0, : model.num_item_embeddings]
        if trie is not None:
            legal = trie.legal_mask(jnp.zeros((B,), jnp.int32), 0)
            window = jnp.where(legal, window, -jnp.inf)
        extras["logits0"] = window.astype(jnp.float32)
    k_pools = tuple(
        write_pages(pool, block_tables, kv[0]) for pool, kv in zip(k_pools, cross_kvs)
    )
    v_pools = tuple(
        write_pages(pool, block_tables, kv[1]) for pool, kv in zip(v_pools, cross_kvs)
    )
    return k_pools, v_pools, seq_lens, extras


def tiger_generate_paged(
    model: Tiger,
    params,
    trie,
    user_input_ids,
    item_input_ids,
    token_type_ids,
    seq_mask,
    rng: jax.Array,
    temperature: float = 0.2,
    n_top_k_candidates: int = 10,
    sample_factor: int = 6,
    deterministic: bool = False,
    page_size: int = 8,
    kv_dtype: str = "float32",
) -> TigerGenerationOutput:
    """`tiger_generate` through the paged decode path: prefill into a
    freshly built page pool (contiguous block tables) and run the
    slot-level decode step with every row in lockstep. The parity
    reference for serving, which composes the same pieces with a real
    allocator and per-slot steps. Requires seq_mask rows to be contiguous
    valid prefixes (the serving layout). ``kv_dtype="int8"`` stores the
    pool quantized (ops/quant) — the int8-vs-fp32 parity reference
    tests/test_quantized.py pins.
    """
    B = item_input_ids.shape[0]
    K = n_top_k_candidates
    D = model.sem_id_dim
    nl = model.n_layers // 2
    H = model.num_heads
    hd = model.attn_dim // H
    Lm = seq_mask.shape[1] + 1  # + user token
    pages_per_slot = -(-Lm // page_size)
    num_pages = 1 + B * pages_per_slot  # page 0 = reserved null page
    block_tables = jnp.asarray(
        1 + jnp.arange(B * pages_per_slot).reshape(B, pages_per_slot), jnp.int32
    )
    from genrec_tpu.ops.paged import zero_pool

    zeros = lambda: tuple(
        zero_pool(num_pages, page_size, H, hd, model.dtype, kv_dtype)
        for _ in range(nl)
    )
    k_pools, v_pools, seq_lens, _ = tiger_prefill_paged(
        model, params, user_input_ids, item_input_ids, token_type_ids,
        seq_mask, block_tables, zeros(), zeros(),
    )

    state = init_tiger_paged_state(model, B, K)
    for step in range(D):
        sub = None
        if not deterministic:
            rng, sub = jax.random.split(rng)
        state = tiger_paged_decode_step(
            model, params, trie, state, jnp.full((B,), step, jnp.int32),
            block_tables, seq_lens, k_pools, v_pools, rng=sub,
            temperature=temperature, sample_factor=sample_factor,
        )
    return TigerGenerationOutput(
        sem_ids=state["beam_seqs"], log_probas=state["beam_logps"]
    )
