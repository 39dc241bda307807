"""Model FLOPs and kernel bytes of TIGER, from shapes alone.

Counted: the matrix products the architecture REQUIRES (2 FLOPs a
multiply-add). Not counted: recomputation, dropout, norms, softmax,
optimizer arithmetic, embedding lookups, padding, the fourth decoder
position the training forward computes and throws away.
"""

from __future__ import annotations


def _attn_proj(d):          # q, k, v, o of one attention, per token
    return 2 * 4 * d * d


def encoder_token(cfg, seg_len: float) -> float:
    """Forward FLOPs of one encoder token in a segment of ``seg_len``."""
    d, f, e = cfg["attn_dim"], cfg["ffn_dim"], cfg["embedding_dim"]
    layers = cfg["n_layers"] // 2
    per_layer = _attn_proj(d) + 2 * 2 * seg_len * d + 2 * 2 * d * f
    return 2 * e * d + layers * per_layer


def cross_kv_token(cfg) -> float:
    """Projecting one memory token to K and V in every decoder layer."""
    d = cfg["attn_dim"]
    return (cfg["n_layers"] // 2) * 2 * 2 * d * d


def decoder_token(cfg, self_len: float, mem_len: float) -> float:
    """Forward FLOPs of one decoder position (cross K/V not included)."""
    d, f, e = cfg["attn_dim"], cfg["ffn_dim"], cfg["embedding_dim"]
    layers = cfg["n_layers"] // 2
    vocab = cfg["codebook_size"] * cfg["sem_id_dim"] + 1
    per_layer = (_attn_proj(d) + 2 * 2 * self_len * d       # self-attention
                 + 2 * 2 * d * d + 2 * 2 * mem_len * d      # cross q, o, scores
                 + 2 * 2 * d * f)
    return 2 * e * d + layers * per_layer + 2 * d * vocab


def train_example(cfg, enc_tokens: float) -> float:
    """Forward + backward FLOPs of one training example whose encoder
    stream (user token + history) is ``enc_tokens`` long."""
    depth = cfg["sem_id_dim"]
    fwd = enc_tokens * (encoder_token(cfg, enc_tokens) + cross_kv_token(cfg))
    fwd += sum(decoder_token(cfg, t + 1, enc_tokens) for t in range(depth))
    return 3.0 * fwd


def serve_prefill(cfg, enc_tokens: float) -> float:
    return enc_tokens * (encoder_token(cfg, enc_tokens) + cross_kv_token(cfg))


def serve_decode(cfg, enc_tokens: float, beams: int) -> float:
    """All decode steps of one request: ``beams`` beams, ``depth`` codes."""
    depth = cfg["sem_id_dim"]
    return beams * sum(decoder_token(cfg, t + 1, enc_tokens) for t in range(depth))


def paged_attention_call(cfg, kv_tokens: float, beams: int) -> tuple[float, float]:
    """(FLOPs, bytes) one live slot needs from ONE layer's paged-attention
    call: its beams' queries against ``kv_tokens`` cached K and V rows, read
    once in the pool's 2-byte type."""
    d = cfg["attn_dim"]
    flops = 2 * 2 * beams * kv_tokens * d
    bytes_ = 2 * kv_tokens * d * 2 + 2 * beams * d * 2
    return flops, bytes_


def paged_layers(cfg) -> int:
    """Layers whose K/V live in the page pool (the decoder's cross-attention)."""
    return cfg["n_layers"] // 2


def kv_tokens(cfg, n_items: int) -> int:
    """KV rows a request holds in the pool: user token + its history's codes."""
    return 1 + min(int(n_items), cfg["max_items"]) * cfg["sem_id_dim"]
