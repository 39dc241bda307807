"""Parameter-sharding rule sets: tensor parallelism as a framework feature.

A rule set maps param-path substrings to PartitionSpecs over the ("data",
"model") mesh; `shard_params` applies them with divisibility guards (axes
that don't divide the tp degree stay replicated). The TIGER rules shard
what dominates its memory/FLOPs: the flat vocab output head, the sem-id
embedding rows, and the FFN hidden dim. Gradients/optimizer states follow
automatically (optax init inherits placements).
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# A rule: (path-substring predicate, axis index to shard, mesh axis name).
Rule = tuple[Callable[[str], bool], int, str]


def tiger_rules(model_axis: str = "model") -> Sequence[Rule]:
    return (
        (lambda p: "output_head" in p and p.endswith("kernel"), 1, model_axis),
        (lambda p: "sem_id_embedding" in p, 0, model_axis),
        (lambda p: "ff" in p and "wi" in p and p.endswith("kernel"), 1, model_axis),
        (lambda p: "ff" in p and "wo" in p and p.endswith("kernel"), 0, model_axis),
    )


def qwen_rules(model_axis: str = "model") -> Sequence[Rule]:
    """Megatron-style: column-parallel q/k/v/gate/up, row-parallel o/down,
    vocab-sharded embedding + head."""
    col = ("q_proj", "k_proj", "v_proj", "gate_proj", "up_proj")
    row = ("o_proj", "down_proj")
    return (
        (lambda p: any(c in p for c in col) and p.endswith("kernel"), 1, model_axis),
        (lambda p: any(r in p for r in row) and p.endswith("kernel"), 0, model_axis),
        (lambda p: p.endswith("embed_tokens") or p.endswith("lm_head"), 0, model_axis),
    )


def moe_rules(expert_axis: str = "expert") -> Sequence[Rule]:
    """Expert parallelism for the Qwen MoE blocks: the stacked per-expert
    SwiGLU weights (E, D, F)/(E, F, D) shard on dim 0 over the expert
    axis; the router stays replicated (it is tiny and every device needs
    the full routing distribution to build its dispatch mask)."""
    stacks = ("gate_proj", "up_proj", "down_proj")
    return (
        (
            lambda p: "moe" in p and any(s in p for s in stacks) and "router" not in p,
            0,
            expert_axis,
        ),
    )


def retrieval_rules(model_axis: str = "model") -> Sequence[Rule]:
    """Serving-retrieval sharding: the tied item-embedding table (the only
    big tensor in SASRec/HSTU) sharded by ROWS (items) over the model
    axis, so the last-hidden scoring matmul h @ emb.T shards the item
    axis and `item_topk` merges per-shard top-k — the full (B, V) score
    matrix never lives on one device. The substring match (not endswith)
    also places the quantized runtime operand's leaves — its int8 data
    (V, d) and fp32 scale (V,) both shard dim 0, which the ndim guard in
    ``param_specs`` handles per leaf."""
    return ((lambda p: "item_embedding" in p, 0, model_axis),)


def serve_rules(model_axis: str = "model") -> Sequence[Rule]:
    """Tensor-parallel SERVING operands (the ServingEngine/DecodeWorker
    ``mesh=`` knob): everything fat a serving host holds resident.

    - the retrieval item table, by rows (``retrieval_rules`` — the
      substring match also places the int8 ``QuantizedTable`` runtime
      operand's two leaves, so ``item_topk``'s shard_map two-stage top-k
      reads its slice in place);
    - TIGER's flat vocab output head and sem-id embedding rows, the two
      generative-serving params that grow with the catalog.

    Attention/FFN kernels stay replicated: serving shards the KV page
    BANK over its head axis instead (``kv_pool_sharding``), which is
    where paged-decode memory actually lives. Unmatched leaves replicate
    over the whole mesh (``param_specs`` fallback), so one rule set
    serves mixed retrieval+generative heads."""
    return (
        *retrieval_rules(model_axis),
        (lambda p: "output_head" in p and p.endswith("kernel"), 1, model_axis),
        (lambda p: "sem_id_embedding" in p, 0, model_axis),
    )


def kv_pool_sharding(mesh: Mesh, n_heads: int, model_axis: str = "model"):
    """Per-leaf placement for a KV page bank's pools: (num_pages,
    page_size, n_heads * head_dim) leaves shard their merged LAST axis
    over ``model_axis``. The axis is head-major, so under the
    divisibility required below a shard holds whole heads — paged
    attention is embarrassingly parallel across heads, so the bank
    splits n-fold with zero cross-device traffic inside the attention
    read — and every other leaf (int8 per-row scale planes, which span
    heads) replicates.

    Returns None when the mesh cannot shard the head axis (no such axis,
    degree 1, or non-divisible n_heads): the caller keeps the pool
    unsharded rather than silently replicating a "sharded" bank."""
    if model_axis not in mesh.shape:
        return None
    n = mesh.shape[model_axis]
    if n <= 1 or n_heads % n != 0:
        return None

    def place(leaf):
        if getattr(leaf, "ndim", 0) == 3:
            return NamedSharding(mesh, P(None, None, model_axis))
        return NamedSharding(mesh, P())

    return place


def _score_items(h, emb):
    """fp32 (B, V) scores of last-hiddens against a table (or shard).

    A ``QuantizedTable`` dequantizes AT SCORE: ``(h @ data.T) * scale``
    equals ``h @ (data * scale[:, None]).T`` exactly in fp32, so the
    resident operand stays int8 and accumulation stays fp32. Detected
    structurally (``.data``/``.scale``) — parallel is L0 and must not
    import ``ops.quant``; any 2-leaf (rows, row-scales) container works.
    """
    if hasattr(emb, "scale"):
        return (h @ emb.data.astype(jnp.float32).T) * emb.scale[None, :]
    return (h @ emb.T).astype(jnp.float32)


def item_topk(h, item_emb, k: int, *, mesh: Mesh | None = None,
              model_axis: str = "model"):
    """Top-k items from last-hidden states: (B, d) x (V, d) -> scores/ids
    (B, k), fp32, with the pad row (item id 0) excluded.

    ``item_emb`` is a plain (V, d) table or an int8
    ``ops.quant.QuantizedTable`` (dequant-at-score, identical outputs up
    to quantization error — the recall floor tests/test_quantized.py
    pins).

    With a mesh whose ``model_axis`` divides V, runs as a shard_map over
    the item axis: each device scores and top-k's only ITS slice of the
    table, then the (B, k*n_shards) locals merge with one small top-k —
    per-device score memory drops n_shards-fold. Otherwise (mesh=None,
    degree 1, or non-divisible V) the plain single-device computation.
    """
    quantized = hasattr(item_emb, "scale")
    V = item_emb.shape[0]
    k = min(k, V)

    def plain(h, emb):
        scores = _score_items(h, emb)
        scores = scores.at[:, 0].set(-jnp.inf)
        return jax.lax.top_k(scores, k)

    if mesh is None or model_axis not in mesh.shape:
        return plain(h, item_emb)
    n = mesh.shape[model_axis]
    if n <= 1 or V % n != 0 or V // n < k:
        return plain(h, item_emb)
    # in_specs must mirror the arg pytrees: a QuantizedTable operand is
    # a 2-leaf pytree — data rows and their scales shard dim 0 together
    # (built via type(item_emb) so the class arrives with the operand).
    emb_spec = (
        type(item_emb)(P(model_axis, None), P(model_axis))
        if quantized else P(model_axis, None)
    )

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(), emb_spec),
        out_specs=(P(None, model_axis), P(None, model_axis)),
    )
    def local_topk(h, emb_shard):
        offset = jax.lax.axis_index(model_axis) * emb_shard.shape[0]
        scores = _score_items(h, emb_shard)
        ids = offset + jnp.arange(emb_shard.shape[0])
        scores = jnp.where(ids[None, :] == 0, -jnp.inf, scores)
        s, i = jax.lax.top_k(scores, k)
        return s, i + offset

    s, i = local_topk(h, item_emb)  # (B, k*n) each
    s_top, sel = jax.lax.top_k(s, k)
    return s_top, jnp.take_along_axis(i, sel, axis=1)


def param_specs(params, rules: Sequence[Rule], mesh: Mesh, log_fn=None):
    """PartitionSpec tree for ``params`` under ``rules`` (replicated where
    no rule matches or the axis doesn't divide the mesh axis size).

    ``log_fn`` (e.g. logger.info) reports every rule-matched leaf that had
    to FALL BACK to replication because of divisibility — silent fallback
    otherwise hides that "tensor parallelism" sharded nothing (TIGER's
    default flat vocab 256*3+1 = 769 is odd, so the vocab rules skip at
    any even tp degree)."""

    def spec_of(path, leaf):
        p = "/".join(str(getattr(k, "key", k)) for k in path)
        for pred, axis, mesh_axis in rules:
            if pred(p) and leaf.ndim > axis:
                if leaf.shape[axis] % mesh.shape[mesh_axis] == 0:
                    out = [None] * leaf.ndim
                    out[axis] = mesh_axis
                    return P(*out)
                if log_fn is not None:
                    log_fn(
                        f"sharding rule matched {p} but dim {axis} "
                        f"({leaf.shape[axis]}) is not divisible by "
                        f"{mesh_axis}={mesh.shape[mesh_axis]}; replicating"
                    )
        return P()

    return jax.tree_util.tree_map_with_path(spec_of, params)


def shard_params(mesh: Mesh, params, rules: Sequence[Rule], log_fn=None):
    specs = param_specs(params, rules, mesh, log_fn=log_fn)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )


def make_place_state(mesh: Mesh, rules: Sequence[Rule] | None, log_fn=None):
    """One placement function used at TrainState creation AND on resume, so
    a restored run keeps the same layout. With ``rules`` it shards (adam
    mu/nu mirror the param paths, so the substring rules place them
    identically); with ``rules=None`` it replicates."""
    from genrec_tpu.parallel.mesh import replicate

    if rules is None:
        return lambda s: replicate(mesh, s)
    return lambda s: shard_params(mesh, s, rules, log_fn=log_fn)
