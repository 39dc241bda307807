"""Position / time bucketing functions shared by TIGER and HSTU.

Parity targets:
- T5 bidirectional log-bucket rel-position (reference
  genrec/modules/transformer.py:13-41, note the ``+1e-6`` inside the log
  and the ``-relative_positions`` sign flip),
- HSTU causal rel-position bucketing (reference genrec/models/hstu.py:300-328),
- HSTU temporal log2 bucketing of |timestamp diffs| (hstu.py:369-398).

All are small integer-producing functions used to index learned bias
tables; computed on device so bias lookups fuse into attention.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def t5_relative_position_bucket(
    relative_positions: jax.Array,
    num_buckets: int = 32,
    max_distance: int = 128,
    bidirectional: bool = True,
) -> jax.Array:
    """T5 bucketing of ``key_pos - query_pos`` grids (int array in/out)."""
    ret = -relative_positions
    if bidirectional:
        num_buckets //= 2
        sign = (ret < 0).astype(jnp.int32)
        ret = jnp.abs(ret)
    else:
        ret = jnp.maximum(ret, 0)

    max_exact = num_buckets // 2
    is_small = ret < max_exact
    # The log-scaled increment is clamped BEFORE adding max_exact
    # (reference transformer.py:31-35), capping buckets at num_buckets-1.
    increment = (
        jnp.log(ret.astype(jnp.float32) / max_exact + 1e-6)
        / math.log(max_distance / max_exact)
        * (num_buckets - max_exact)
    ).astype(jnp.int32)
    large_val = max_exact + jnp.minimum(increment, num_buckets - max_exact - 1)

    ret = jnp.where(is_small, ret, large_val)
    if bidirectional:
        ret = ret + sign * num_buckets
    return ret


def hstu_position_bucket(
    relative_position: jax.Array,
    num_buckets: int = 32,
    max_distance: int = 128,
) -> jax.Array:
    """HSTU causal bucketing of ``query_pos - key_pos`` (clamped to >= 0)."""
    rp = jnp.maximum(relative_position, 0)
    max_exact = num_buckets // 2
    is_small = rp < max_exact
    # log(0) at rp=0 is safe: that branch is only selected when rp>=max_exact.
    large = max_exact + (
        jnp.log(jnp.maximum(rp, 1).astype(jnp.float32) / max_exact)
        / math.log(max_distance / max_exact)
        * (num_buckets - max_exact)
    ).astype(jnp.int32)
    large = jnp.minimum(large, num_buckets - 1)
    return jnp.where(is_small, rp, large)


def hstu_log_bucket(time_diff: jax.Array, num_buckets: int = 64) -> jax.Array:
    """log2 bucketing of |timestamp differences|: floor(ln(max(1,|d|))/ln 2)."""
    abs_diff = jnp.maximum(jnp.abs(time_diff), 1).astype(jnp.float32)
    buckets = (jnp.log(abs_diff) / 0.693).astype(jnp.int32)
    return jnp.clip(buckets, 0, num_buckets - 1)
