"""Pipeline-parallel causal-LM SFT loss for the Qwen backbone.

This is the MODEL-SPECIFIC half of the pipeline-parallelism story: it
closes over `QwenBlock` and the loss ops, builds the per-stage apply
function, and runs the generic GPipe schedule that lives (model-free) in
`parallel/pipeline.py` (`stack_layer_params` / `stacked_param_specs` +
the ppermute tick loop below). It used to live inside parallel/ — the
`parallel -> models/ops` layering debt graftlint's baseline carried;
moving the model-aware builder up to models/ (L3 may import L0 and L2)
retires those suppressions and leaves parallel/ model-free.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from genrec_tpu.parallel.pipeline import stack_layer_params, stacked_param_specs


def make_pp_sft_loss(
    cfg,
    mesh,
    pipe_axis: str = "pipe",
    n_micro: int | None = None,
    dtype=jnp.float32,
    remat: bool = False,
    valid_vocab: int | None = None,
    tp_rules=None,
    log_fn=None,
):
    """Pipeline-parallel causal-LM SFT loss for the Qwen backbone.

    Returns loss_fn(params, batch) taking the NORMAL QwenLM param tree and
    a batch of input_ids / attention_mask / labels (B, L); B must divide
    by n_micro (and by the "data" axis when present), n_layers by the pipe
    size. The block stack runs under shard_map over ``pipe_axis`` with
    ppermute-forwarded activations; embed / norm / head run outside.

    ``tp_rules`` (e.g. shardings.qwen_rules()) enables the 3-axis
    dp x tp x pp layout: the shard_map goes manual over ONLY pipe/data
    (JAX 0.9 ``axis_names``) while the "model" axis stays auto — XLA's
    SPMD partitioner Megatron-shards the per-stage block matmuls from the
    sharding constraints this function places on the stacked params, and
    the out-of-pipeline embed/head matmuls likewise. No hand-written
    model-axis collectives: the scan/ppermute schedule is identical to
    the 1-axis pipeline.
    """
    from genrec_tpu.models.backbones.qwen import QwenBlock
    from genrec_tpu.ops.losses import cross_entropy_with_ignore

    S = mesh.shape[pipe_axis]
    if cfg.num_hidden_layers % S:
        raise ValueError(
            f"n_layers {cfg.num_hidden_layers} not divisible by pipe={S}"
        )
    if len({(cfg.mixer_kind(i), cfg.mlp_kind(i))
            for i in range(cfg.num_hidden_layers)}) > 1:
        raise ValueError("the pipeline stacks ONE block over every layer: a "
                         "config whose layers differ in kind is not wired")
    M = n_micro or S
    batch_axis = "data" if "data" in mesh.axis_names else None
    block = QwenBlock(cfg, dtype)

    # Manual collective axes; any OTHER mesh axis (model) stays auto so
    # XLA can tensor-shard the in-stage compute.
    manual = frozenset({pipe_axis} | ({batch_axis} if batch_axis else set()))

    # x: (M, Bm, L, D) microbatched activations; masks/positions likewise.
    x_spec = P(None, batch_axis, None, None)
    m_spec = P(None, batch_axis, None)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(pipe_axis), x_spec, m_spec, m_spec),
        out_specs=x_spec,
        axis_names=manual,
    )
    def _pp_blocks(stacked, x, positions, attention_mask):
        from genrec_tpu.models.backbones.qwen import causal_pad_bias

        stage = jax.lax.axis_index(pipe_axis)
        L = x.shape[2]

        def stage_apply(h, pos, am):
            bias = causal_pad_bias(L, am)

            def body(h, p):
                h, _ = block.apply({"params": p}, h, pos, bias)
                return h, None

            if remat:
                # gradient_checkpointing: store only each layer's input.
                body = jax.checkpoint(body)
            h, _ = jax.lax.scan(body, h, stacked)
            return h

        # Initial carries must be marked varying over the pipe axis (the
        # loop body makes them so via stage-dependent writes).
        buf = jax.lax.pcast(jnp.zeros_like(x[0]), (pipe_axis,), to="varying")
        outs = jax.lax.pcast(jnp.zeros_like(x), (pipe_axis,), to="varying")
        fwd = [(i, i + 1) for i in range(S - 1)]

        def tick(carry, t):
            buf, outs = carry
            mi = jnp.clip(t, 0, M - 1)  # stage 0 feeds microbatch t
            inp = jnp.where(
                stage == 0, jax.lax.dynamic_index_in_dim(x, mi, 0, False), buf
            )
            # Every stage processes the microbatch whose index is t-stage
            # (garbage outside [0, M); masked on write / never forwarded).
            mj = jnp.clip(t - stage, 0, M - 1)
            pos = jax.lax.dynamic_index_in_dim(positions, mj, 0, False)
            am = jax.lax.dynamic_index_in_dim(attention_mask, mj, 0, False)
            h = stage_apply(inp, pos, am)
            nxt = jax.lax.ppermute(h, pipe_axis, fwd)
            write = jnp.clip(t - (S - 1), 0, M - 1)
            valid = (stage == S - 1) & (t >= S - 1)
            cur = jax.lax.dynamic_index_in_dim(outs, write, 0, False)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(valid, h, cur), write, 0
            )
            return (nxt, outs), None

        (_, outs), _ = jax.lax.scan(
            tick, (buf, outs), jnp.arange(M + S - 1)
        )
        # Only the last stage holds real outputs; replicate via psum.
        outs = jnp.where(stage == S - 1, outs, 0.0)
        return jax.lax.psum(outs, pipe_axis)

    def loss_fn(params, batch):
        ids = batch["input_ids"]
        am = batch["attention_mask"]
        labels = batch["labels"]
        B, L = ids.shape
        if B % M:
            raise ValueError(f"batch {B} not divisible by n_micro {M}")
        Bm = B // M
        rest, stacked = stack_layer_params(params, cfg.num_hidden_layers)
        # Pin the stacked layout: layers over pipe, and (with tp_rules)
        # Megatron dims over the model axis — the constraint is what the
        # auto-axis partitioner propagates into the per-stage matmuls.
        specs = stacked_param_specs(stacked, tp_rules, pipe_axis, mesh, log_fn)
        stacked = jax.tree_util.tree_map(
            lambda x, s: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, s)
            ),
            stacked, specs,
        )
        positions = jnp.maximum(jnp.cumsum(am, axis=1) - 1, 0)

        x = rest["embed_tokens"][ids].astype(dtype)
        h = _pp_blocks(
            stacked,
            x.reshape(M, Bm, L, -1),
            positions.reshape(M, Bm, L),
            am.reshape(M, Bm, L),
        ).reshape(B, L, -1)

        # Final norm + head outside the pipeline (replicated weights).
        from genrec_tpu.ops.normalize import rms_norm

        h = rms_norm(h, rest["norm"]["weight"], cfg.rms_norm_eps).astype(dtype)
        w = (
            rest["embed_tokens"]
            if cfg.tie_word_embeddings
            else rest["lm_head"]
        )
        from genrec_tpu.ops.losses import mask_vocab_logits

        logits = mask_vocab_logits(h @ w.T.astype(dtype), valid_vocab)
        per_tok, valid = cross_entropy_with_ignore(
            logits[:, :-1, :], labels[:, 1:], ignore_index=-100
        )
        return per_tok.sum() / jnp.maximum(valid.sum(), 1)

    return loss_fn
