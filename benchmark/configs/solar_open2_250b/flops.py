"""Model FLOPs and kernel bytes of the cut Solar-Open2 language model served
through the LCRec head, from shapes alone.

Counted: what the layers' equations REQUIRE (2 FLOPs a multiply-add),
whatever the implementation spends: the full layer's projections (its output
gate among them) and the CAUSAL keys only (t + 1 a query); the KDA
projections, its convolutions' taps and the recurrence at 6 x 128 x 128 a head
a token (decay, read, write, output: not the chunked form's pairwise decay or
its triangular solve); the router over its 320 outputs, the experts HELD here
only (``num_experts_per_tok x held / published`` picks a token, the uniform
expectation; the measured count is the ``expert_pairs_per_held_expert``
counter) and the shared expert; the head at the ONE prompt position that is
read and at every decoded position. Not counted: norms, softmax, gates'
elementwise arithmetic, the selection, the trie, the beam, embedding lookups,
padding, the H-fold over-compute of the paged kernel's block-diagonal query.

``harness/readers.py`` hands ``serve_prefill`` and ``serve_decode`` a token
count of ``1 + items x sem_id_dim``. For TIGER the 1 is the user token. This
head's prompt has no such token: a prompt is ``items x sem_id_dim`` codebook
tokens and nothing else, so both functions take the 1 off again.
"""

from __future__ import annotations


def gqa_token(cfg) -> float:
    """Forward FLOPs of one token in the full layer outside its keys."""
    d, h, g, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["num_key_value_heads"], cfg["head_dim"])
    gate = d * h * hd if cfg["use_gqa_gate"] else 0
    return 2 * (d * h * hd + 2 * d * g * hd + gate + h * hd * d)


def gqa_pair(cfg) -> float:
    """One query against one key in every head: the score and the value."""
    return 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]


def kda_token(cfg) -> float:
    """Forward FLOPs of one token in one KDA mixer."""
    d, lac = cfg["hidden_size"], cfg["linear_attn_config"]
    h, k, taps = lac["num_heads"], lac["head_dim"], lac["short_conv_kernel_size"]
    proj = 2 * 4 * d * h * k                # q, k, v, o
    low_rank = 2 * 2 * (d * k + k * h * k)  # forget gate and output gate
    return proj + low_rank + 2 * d * h + 2 * taps * 3 * h * k + 6 * k * k * h


def mlp_token(cfg) -> float:
    """Forward FLOPs of one token in a layer's experts."""
    d = cfg["hidden_size"]
    expert = 2 * 3 * d * cfg["moe_intermediate_size"]
    picks = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
             / cfg["n_routed_experts_published"])
    return (2 * d * cfg["n_routed_experts_published"]
            + (picks + cfg["n_shared_experts"]) * expert)


def _layers(cfg) -> tuple[int, int]:
    """(full layers, KDA layers) among the layers kept."""
    n = cfg["num_hidden_layers"]
    full = sum(1 for i in range(n) if i in cfg["gqa_layers"])
    return full, n - full


def token(cfg) -> float:
    """Forward FLOPs of one token through every layer, keys not included."""
    full, kda = _layers(cfg)
    return (full * gqa_token(cfg) + kda * kda_token(cfg)
            + (full + kda) * mlp_token(cfg))


def head_position(cfg) -> float:
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def serve_prefill(cfg, enc_tokens: float) -> float:
    """The prompt of ``enc_tokens - 1`` tokens (module docstring) through
    the layers, and the head at its last position."""
    n = max(enc_tokens - 1, 0.0)
    full, _ = _layers(cfg)
    return n * token(cfg) + full * gqa_pair(cfg) * n * (n + 1) / 2 + head_position(cfg)


def serve_decode(cfg, enc_tokens: float, beams: int) -> float:
    """All decode steps of one request: ``beams`` beams, one token a step
    for the codes after the first (which the prefill's position resolves)."""
    n = max(enc_tokens - 1, 0.0)
    full, _ = _layers(cfg)
    steps = cfg["sem_id_dim"] - 1
    per_step = sum(token(cfg) + full * gqa_pair(cfg) * (n + t + 1) + head_position(cfg)
                   for t in range(steps))
    return beams * per_step


def paged_attention_call(cfg, kv_tokens: float, beams: int) -> tuple[float, float]:
    """(FLOPs, bytes) one live slot needs from ONE layer's paged-attention
    call: its beams' 64 query heads against ``kv_tokens`` cached K and V rows
    of 8 heads, each row read ONCE for the 8 query heads of its group, in
    the pool's 2-byte type; the queries in and the outputs back."""
    h, g, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    flops = 2 * 2 * beams * h * kv_tokens * hd
    bytes_ = 2 * kv_tokens * g * hd * 2 + 2 * beams * h * hd * 2
    return flops, bytes_


def paged_layers(cfg) -> int:
    """Layers whose K/V live in the page pool: the full-attention ones."""
    return _layers(cfg)[0]


def kv_tokens(cfg, n_items: int) -> int:
    """KV rows a request holds in the pool: its history's codebook tokens."""
    return max(min(int(n_items), cfg["max_items"]) * cfg["sem_id_dim"], 1)
