"""Model FLOPs of the cut Kimi-Linear language model, from shapes alone.

Counted: what the layers' equations REQUIRE (2 FLOPs a multiply-add),
whatever the implementation spends: the KDA projections, its convolutions'
taps and the recurrence at 6 x 128 x 128 a head a token (decay, read, write,
output: not the chunked form's pairwise decay or its triangular solve); the
latent attention's projections and the CAUSAL keys only (t + 1 a query); the
experts HELD here only (``num_experts_per_token x held / published`` picks a
token, the uniform expectation; the measured share is the
``expert_picks_here_share`` counter) and the shared expert; the head over the
labelled positions. Not counted: recomputation, norms, softmax, gates'
elementwise arithmetic, the selection, optimizer arithmetic, embedding
lookups, padding.
"""

from __future__ import annotations


def kda_token(cfg) -> float:
    """Forward FLOPs of one token in one KDA mixer."""
    d, lac = cfg["hidden_size"], cfg["linear_attn_config"]
    h, k, taps = lac["num_heads"], lac["head_dim"], lac["short_conv_kernel_size"]
    proj = 2 * 4 * d * h * k                # q, k, v, o
    low_rank = 2 * 2 * (d * k + k * h * k)  # forget gate and output gate
    return proj + low_rank + 2 * d * h + 2 * taps * 3 * h * k + 6 * k * k * h


def mla_token(cfg) -> float:
    """Forward FLOPs of one token in the latent attention outside its keys."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv, r = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"], cfg["kv_lora_rank"])
    return 2 * (d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d)


def mla_pair(cfg) -> float:
    """One query against one key: the score and the weighted value."""
    return 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])


def mlp_token(cfg, layer: int) -> float:
    """Forward FLOPs of one token in the 0-based layer's MLP."""
    d = cfg["hidden_size"]
    if layer < cfg["first_k_dense_replace"]:
        return 2 * 3 * d * cfg["intermediate_size"]
    expert = 2 * 3 * d * cfg["moe_intermediate_size"]
    picks = (cfg["num_experts_per_token"] * cfg["num_experts"]
             / cfg["num_experts_published"])
    return (2 * d * cfg["num_experts_published"]
            + (picks + cfg["num_shared_experts"]) * expert)


def forward_row(cfg, n: float) -> float:
    """Forward FLOPs of the layers over a row of n real tokens."""
    lac = cfg["linear_attn_config"]
    total = 0.0
    for i in range(cfg["num_hidden_layers"]):
        total += n * mlp_token(cfg, i)
        if i + 1 in lac["kda_layers"]:
            total += n * kda_token(cfg)
        else:
            total += n * mla_token(cfg) + mla_pair(cfg) * n * (n + 1) / 2
    return total


def train_example(cfg, enc_tokens: float) -> float:
    """Forward + backward FLOPs of one row whose real tokens number
    ``enc_tokens + sem_id_dim`` (instruction, history, target). Quadratic in
    the row's length through the latent attention's causal keys, so the mean
    length stands for the rows with a slight undercount."""
    n = enc_tokens + cfg["sem_id_dim"]
    labelled = max(n - cfg["instruction_tokens"] - cfg["sem_id_dim"], 0.0)
    head = labelled * 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return 3.0 * (forward_row(cfg, n) + head)
