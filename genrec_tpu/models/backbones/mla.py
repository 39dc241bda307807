"""Multi-head latent attention with NO positional encoding (the full-attention
layers of Kimi Linear, ``mla_use_nope``): keys and values are expanded from a
low-rank latent row a token, nothing is rotated, and order reaches the layer
only through what the recurrent layers before it wrote into the stream.

    q_t = h W_q                                  (heads x (nope + rope-part))
    [c_t ; r_t] = h W_kva,  c = RMSNorm(c)       (kv_lora_rank + rope-part)
    [kc_{t,n} ; v_{t,n}] = c_t W_kvb             (heads x (nope + v))
    k_{t,n} = [kc_{t,n} ; r_t]                   (r_t shared by all heads)
    o_{t,n} = softmax_{s <= t, real}(q_{t,n} . k_{s,n} / sqrt(nope + rope-part)) v_{s,n}

The softmax runs through `qwen.causal_attention`, the tiled running-max
routine sparse attention uses, with "every real key at or before the query"
as its set: never a (heads, L, L) tensor. The cache holds the latent row
(kv_lora_rank + rope-part numbers a token), not K and V.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from genrec_tpu.models.backbones.qwen import QwenConfig, causal_attention
from genrec_tpu.models.layers import RMSNorm


class LatentAttention(nn.Module):
    cfg: QwenConfig
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, key_valid=None, cache=None):
        cfg = self.cfg
        B, L, D = x.shape
        H = cfg.num_attention_heads
        dn, dr, dv, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                         cfg.v_head_dim, cfg.kv_lora_rank)
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=self.dtype, name=name)
        with jax.named_scope("mla_latent"):
            q = dense(H * (dn + dr), "q_proj")(x).reshape(B, L, H, 1, dn + dr)
            ckr = dense(r + dr, "kv_a_proj")(x)
            latent = jnp.concatenate(
                [RMSNorm(r, cfg.rms_norm_eps, name="kv_a_norm")(ckr[..., :r]),
                 ckr[..., r:]], axis=-1)
            w_kvb = self.param("kv_b_proj", nn.initializers.lecun_normal(),
                               (r, H * (dn + dv))).astype(self.dtype)

        def expand(rows):
            """Latent rows (..., N, r + dr) -> k (..., N, H, dn + dr), v (..., N, H, dv)."""
            kv = (rows[..., :r] @ w_kvb).reshape(rows.shape[:-1] + (H, dn + dv))
            shared = jnp.broadcast_to(rows[..., None, r:], kv.shape[:-1] + (dr,))
            return jnp.concatenate([kv[..., :dn], shared], axis=-1), kv[..., dn:]

        if key_valid is None:
            key_valid = jnp.ones((B, L if cache is None else cache["latent"].shape[1]), bool)
        key_valid = key_valid.astype(bool)
        new_cache = None
        with jax.named_scope("mla_attend"):
            if cache is None:
                k, v = expand(latent)
                out = causal_attention(q, k, v, key_valid, cfg.sparse_chunk)
            else:
                idx = cache["idx"]
                rows = jax.lax.dynamic_update_slice(
                    cache["latent"], latent.astype(cache["latent"].dtype), (0, idx, 0))
                new_cache = {"latent": rows, "idx": idx + L}
                q_slot = idx + jnp.arange(L)

                def one_row(a):
                    # a row at a time: K and V of the whole cache are
                    # expanded for ONE row, never for the batch of beams
                    qr, lr, vr = a
                    k, v = expand(lr)
                    return causal_attention(qr[None], k[None], v[None], vr[None],
                                            cfg.sparse_chunk, q_slot=q_slot)[0]

                out = jax.lax.map(one_row, (q, rows, key_valid))
        return dense(D, "o_proj")(out.reshape(B, L, H * dv)), new_cache


def init_mla_cache(cfg: QwenConfig, batch_size: int, max_len: int, dtype):
    return {"latent": jnp.zeros(
                (batch_size, max_len, cfg.kv_lora_rank + cfg.qk_rope_head_dim), dtype),
            "idx": jnp.asarray(0, jnp.int32)}
