"""Model FLOPs of the cut Keye-VL-2.0 language model, from shapes alone.

Counted: the matrix products the layer's equations REQUIRE (2 FLOPs a
multiply-add), whatever the implementation spends: attention over the
SELECTED keys only (min(t + 1, topk) a query), the experts HELD here only
(``num_experts_per_tok x held / published`` picks a token, the uniform
expectation; the measured share is the ``expert_picks_here_share`` counter),
the head over the labelled positions. The indexer (projections, and a score
for every key at or before the query) is counted forward only: nothing
flows back through the selection. Not counted: recomputation, norms,
softmax, RoPE, the selection itself, optimizer arithmetic, embedding
lookups, padding.
"""

from __future__ import annotations


def dense_token(cfg) -> float:
    """Forward FLOPs of one token in one layer outside attention's keys:
    q/k/v/o, the router, and its picks on the experts held."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    proj = 2 * (2 * d * h * hd + 2 * d * kv * hd)
    router = 2 * d * cfg["num_experts_published"]
    picks = cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["num_experts_published"]
    expert = 2 * 3 * d * cfg["moe_intermediate_size"]
    return proj + router + picks * expert


def selected_keys(cfg, n: float) -> float:
    """Sum over the n queries of a row of the keys each attends."""
    k = cfg["sa_config"]["topk"]
    if n <= k:
        return n * (n + 1) / 2
    return k * (k + 1) / 2 + (n - k) * k


def indexer_row(cfg, n: float) -> float:
    """The indexer over a row of n real tokens, one layer, forward."""
    sa, d = cfg["sa_config"], cfg["hidden_size"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    proj = 2 * d * (hi * di + di + hi)
    return n * proj + (2 * hi * di + 2 * hi) * n * (n + 1) / 2


def train_example(cfg, enc_tokens: float) -> float:
    """Forward + backward FLOPs of one row whose real tokens number
    ``enc_tokens + sem_id_dim`` (instruction, history, target). Affine in
    the row's length past ``topk`` but for the indexer's scores, so the
    mean length stands for the rows (a slight undercount)."""
    n = enc_tokens + cfg["sem_id_dim"]
    layers = cfg["num_hidden_layers"]
    attend = 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]
    labelled = max(n - cfg["instruction_tokens"] - cfg["sem_id_dim"], 0.0)
    head = labelled * 2 * cfg["hidden_size"] * cfg["vocab_size"]
    fwd = layers * (n * dense_token(cfg) + attend * selected_keys(cfg, n)) + head
    return 3.0 * fwd + layers * indexer_row(cfg, n)
