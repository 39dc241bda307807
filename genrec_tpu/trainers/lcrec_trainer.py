"""LCRec trainer (parity target: reference genrec/trainers/lcrec_trainer.py).

Epoch loop, AdamW + cosine schedule, optional LoRA (:306-315), SFT with
prompt-masked labels, constrained beam-10 generation eval producing
per-codebook + exact-match + TopK metrics (:131-267), eval_only mode
(:358-364). The constrained decode is the jitted cascade of
models/lcrec.py instead of an HF prefix_allowed_tokens_fn host callback.

The "amazon" dataset path expects a local HF Qwen checkpoint + tokenizer
(zero-egress environments use the synthetic path, which exercises the
identical code on a tiny random-init backbone).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax

from genrec_tpu import configlib
from genrec_tpu.core.harness import jit_train_step, make_train_step
from genrec_tpu.core.logging import Tracker, setup_logger
from genrec_tpu.core.profiling import ProfileWindow
from genrec_tpu.core.lora import lora_init, lora_merge, lora_param_count
from genrec_tpu.core.state import TrainState
from genrec_tpu.data.batching import (
    batch_iterator,
    prefetch_eval_batches,
    prefetch_to_device,
)
from genrec_tpu.data.lcrec_tasks import synthetic_lcrec_data
from genrec_tpu.models.backbones.qwen import QwenConfig, QwenLM
from genrec_tpu.models.lcrec import (
    extend_vocab,
    generate_greedy,
    generate_topk_constrained,
    sft_loss,
)
from genrec_tpu.ops.metrics import TopKAccumulator
from genrec_tpu.ops.schedules import cosine_schedule_with_warmup
from genrec_tpu.parallel import distributed_init, get_mesh


def make_generate_fn(model, base_vocab, num_codebooks, codebook_size, beam_width, max_cache):
    @jax.jit
    def gen(params, batch):
        out = generate_topk_constrained(
            model, params, batch["input_ids"], batch["attention_mask"],
            base_vocab, num_codebooks, codebook_size,
            beam_width=beam_width, max_cache=max_cache,
        )
        return out.sem_ids

    return gen


def evaluate_item2index(gen_fn, params, arrays, batch_size, mesh, num_codebooks):
    """Greedy constrained item->index over the item set: exact-match +
    per-codebook accuracy (reference lcrec_trainer.py:193-213)."""
    from genrec_tpu.parallel import metric_allreduce

    correct = np.zeros(num_codebooks)
    exact = 0
    total = 0
    # Eval uses the same prefetching iterator as the train loop so host
    # batching + H2D transfer overlap the previous batch's generate.
    for sharded, host, valid in prefetch_eval_batches(
        batch_iterator(arrays, batch_size), mesh
    ):
        top = np.asarray(gen_fn(params, sharded))  # (B, W, C)
        n = int(valid.sum())
        pred = top[:n, 0, :]
        target = host["target_ids"][:n]
        correct += (pred == target).sum(axis=0)
        exact += int((pred == target).all(axis=1).sum())
        total += n
    s = metric_allreduce(
        {"correct": list(correct), "exact": float(exact), "total": float(total)}
    )
    out = {"item2index_exact": s["exact"] / max(s["total"], 1)}
    out.update(
        {
            f"item2index_c{c}": s["correct"][c] / max(s["total"], 1)
            for c in range(num_codebooks)
        }
    )
    return out


def evaluate_index2item(free_fn, params, arrays, target_texts, batch_size, mesh, tok):
    """Unconstrained index->item: generated text must contain the target
    title (reference lcrec_trainer.py:215-227)."""
    from genrec_tpu.parallel import metric_allreduce

    match = 0
    total = 0
    offset = 0
    for sharded, valid in prefetch_to_device(batch_iterator(arrays, batch_size), mesh):
        toks = np.asarray(free_fn(params, sharded))  # (B, T)
        n = int(valid.sum())
        for i in range(n):
            tgt = target_texts[offset + i].strip().lower()
            gen = tok.decode(toks[i]).strip().lower()
            if tgt and gen and tgt in gen:
                match += 1
        total += n
        offset += n
    s = metric_allreduce({"match": float(match), "total": float(total)})
    return {"index2item_match": s["match"] / max(s["total"], 1)}


def evaluate(gen_fn, params, arrays, batch_size, mesh, num_codebooks):
    from genrec_tpu.parallel import metric_allreduce

    acc = TopKAccumulator(ks=(1, 5, 10))
    cb_correct = np.zeros(num_codebooks)
    cb_total = 0
    for sharded, host, valid in prefetch_eval_batches(
        batch_iterator(arrays, batch_size), mesh
    ):
        top = np.asarray(gen_fn(params, sharded))
        n = int(valid.sum())
        target = host["target_ids"][:n]
        acc.accumulate(jnp.asarray(target), jnp.asarray(top[:n]))
        top1 = top[:n, 0, :]
        for c in range(num_codebooks):
            cb_correct[c] += (top1[:, c] == target[:, c]).sum()
        cb_total += n
    m = acc.reduce(cross_process=True)
    # Codebook counters must be summed across hosts too, same scope as
    # the TopK metrics.
    cb = metric_allreduce({"correct": list(cb_correct), "total": float(cb_total)})
    m.update(
        {
            f"codebook_acc_{c}": cb["correct"][c] / max(cb["total"], 1)
            for c in range(num_codebooks)
        }
    )
    return m


def backbone_config(*, n_layers, num_heads, num_kv_heads, moe_dropless=False,
                    **widths) -> QwenConfig:
    """The random-init backbone's `QwenConfig` (untied head) from
    `train()`'s scalar arguments, which a gin file binds: three of them
    renamed, ``moe_dropless`` for ``moe_capacity_factor=None``, the rest
    (``vocab_size``, ``hidden_size``, ``head_dim``, ``sparse_topk``, ...)
    under `QwenConfig`'s own names."""
    if moe_dropless:
        widths["moe_capacity_factor"] = None
    return QwenConfig(
        num_hidden_layers=n_layers, num_attention_heads=num_heads,
        num_key_value_heads=num_kv_heads, tie_word_embeddings=False, **widths)


def make_dense_sft_loss(model, live_vocab: int, use_fused_ce: bool):
    """`train()`'s data-parallel loss: (params, batch) -> (loss, metrics),
    metrics = the step's real tokens and the backbone's counters."""
    return lambda p, batch: sft_loss(
        model, p, batch["input_ids"], batch["attention_mask"],
        batch["labels"], valid_vocab=live_vocab, use_fused_ce=use_fused_ce,
        with_metrics=True,
    )


def make_sft_step(loss_and_metrics, optimizer):
    """`train()`'s jitted step over a (params, batch) -> (loss, metrics)."""
    return jit_train_step(make_train_step(
        lambda p, batch, step_rng: loss_and_metrics(p, batch), optimizer,
        clip_norm=1.0, name="lcrec_train_step"))


@configlib.configurable
def train(
    epochs=4,
    batch_size=8,
    learning_rate=3e-4,
    num_warmup_steps=20,
    weight_decay=0.01,
    num_codebooks=3,
    codebook_size=8,
    beam_width=10,
    max_text_len=96,
    use_lora=False,
    gradient_checkpointing=False,
    # Fused full-softmax CE over the LM head (kernels/fused_ce.py): the
    # (B, L, vocab) logits — the largest activation of the SFT step at
    # real Qwen vocab (~150k) — never materialize. Exact same loss.
    # auto = on when on TPU; dense (non-sp/pp) loss path only.
    use_fused_ce="auto",
    # >1: shard the token dim over an "sp" mesh axis and train with ring
    # attention (long-context path; max_text_len must divide by it).
    sequence_parallel=1,
    # >1: GPipe pipeline parallelism over a "pipe" mesh axis — the block
    # stack is stage-sharded, activations ppermute between stages
    # (parallel/pipeline.py). n_layers must divide by it.
    pipeline_parallel=1,
    pp_microbatches=None,
    # >1: Megatron-style tensor parallelism over a "model" mesh axis
    # (parallel/shardings.qwen_rules: column q/k/v/gate/up, row o/down,
    # vocab-sharded embedding/head where divisible).
    tensor_parallel=1,
    lora_rank=8,
    lora_alpha=16.0,
    lora_targets=("q_proj", "v_proj"),
    # >0: replace the dense SwiGLU with a routed mixture of experts
    # (backbones.qwen.QwenMoEMLP — beyond-parity, reference has no MoE).
    num_experts=0,
    num_experts_per_tok=2,
    # >1: shard the expert stacks over an "expert" mesh axis
    # (parallel/shardings.moe_rules); requires num_experts % it == 0.
    expert_parallel=1,
    # Backbone (synthetic default: tiny random-init Qwen).
    pretrained_path=None,
    hidden_size=64,
    intermediate_size=128,
    n_layers=2,
    num_heads=4,
    num_kv_heads=2,
    # Attention layout of the random-init backbone (Qwen3-class models
    # state head_dim, drop the q/k/v bias and norm q and k per head).
    head_dim=None,
    attention_bias=True,
    qk_norm=False,
    rope_theta=10000.0,
    # Expert layer beyond the capacity path: one expert's width, the gate
    # renormalisation, DROPLESS routing (every routed pair computed), and
    # the share of the experts this chip holds of an expert-parallel
    # deployment (router and experts-per-token as published; no exchange).
    moe_intermediate_size=None,
    norm_topk_prob=True,
    moe_dropless=False,
    moe_first_expert=0,
    moe_experts_held=None,
    router_aux_coef=0.01,
    # >0: learned sparse attention (an indexer picks each query's top-k
    # keys; backbones.qwen.sparse_attention).
    sparse_topk=0,
    indexer_heads=0,
    indexer_head_dim=0,
    sparse_chunk=512,
    rms_norm_eps=1e-6,
    # Layer kinds (1-based layer numbers, as a published
    # ``linear_attn_config`` lists them): layers that run Kimi Delta
    # Attention (backbones/kda.py: heads x head size) or NoPE latent attention
    # (backbones/mla.py); every other layer runs the attention above. With
    # experts, the first ``first_k_dense_replace`` layers keep the dense MLP.
    kda_layers=(),
    mla_layers=(),
    first_k_dense_replace=0,
    kda_heads=0,
    kda_head_dim=0,
    kv_lora_rank=0,
    qk_nope_head_dim=0,
    qk_rope_head_dim=0,
    v_head_dim=0,
    # Router scores (``softmax`` | ``sigmoid`` with a selection bias), the
    # scale on the chosen gates, and shared experts beside the routed ones.
    moe_scoring="softmax",
    routed_scaling_factor=1.0,
    n_shared_experts=0,
    # >0: rows of the embedding and the head held here (a vocabulary slice
    # plus the codebook tokens); rows past the live vocabulary are inert.
    vocab_rows=0,
    dataset="synthetic",
    dataset_folder="dataset/amazon",
    split="beauty",
    sem_ids_path=None,
    # History window for the task prompts (reference lcrec max_seq_len,
    # amazon_lcrec.py:183 — caps every seqrec/fusionseqrec/itemsearch
    # history and the eval history); amazon dataset path only.
    max_history=20,
    # Training samples drawn per user per task stream (our sampler's
    # budget knob; the reference generates per-position samples and caps
    # with max_train_samples instead).
    samples_per_user=2,
    # Sampling weights over data.lcrec_tasks.TASKS (seqrec, item2index,
    # index2item, fusionseqrec, itemsearch, preferenceobtain); None = the
    # reference's default mix. The debug config pins seqrec-only, matching
    # reference AmazonLCRecDataset.enabled_tasks=["seqrec"].
    task_weights=None,
    eval_item_tasks=True,
    eval_items_limit=256,
    index2item_max_new=16,
    do_eval=True,
    eval_only=False,
    # Debug fast mode (reference lcrec_trainer.py:283, 327-333 /
    # lcrec_debug.gin): 0 = no limit.
    max_train_samples=0,
    max_eval_samples=0,
    resume_from_checkpoint=False,
    # True: final evals use the best-valid-Recall@10 weights (the
    # sasrec/hstu reference protocol). False: final-epoch weights — the
    # reference LCRec protocol (lcrec_trainer.py:426-431 saves final only,
    # no best tracking); the parity harness uses False.
    test_on_best=True,
    eval_every_epoch=2,
    eval_batch_size=16,
    save_dir_root="out/lcrec",
    save_every_epoch=10,
    wandb_logging=False,
    wandb_project="lcrec_training",
    wandb_log_interval=50,
    amp=True,
    mixed_precision_type="bf16",
    profile_steps=0,
    seed=0,
):
    distributed_init()
    logger = setup_logger(save_dir_root)
    tracker = Tracker(wandb_logging, wandb_project, save_dir=save_dir_root)
    chosen = [n for n in (sequence_parallel, pipeline_parallel, tensor_parallel,
                          expert_parallel)
              if n > 1]
    # Wired composition #1: tensor x expert parallelism for MoE runs
    # (dp x model x expert — the standard MoE-LLM layout: attention
    # Megatron-sharded, expert stacks expert-sharded; the rule sets match
    # disjoint param paths so they concatenate).
    tp_ep_combo = (
        tensor_parallel > 1 and expert_parallel > 1 and num_experts > 0
        and sequence_parallel == 1 and pipeline_parallel == 1
    )
    # Wired composition #2 — dp x tp x pp: the standard dense-LLM pod
    # layout. The pipeline
    # shard_map goes manual over pipe/data only; the model axis stays
    # auto and XLA Megatron-shards the per-stage matmuls from the
    # qwen_rules constraints (parallel/pipeline.py make_pp_sft_loss).
    tp_pp_combo = (
        tensor_parallel > 1 and pipeline_parallel > 1
        and sequence_parallel == 1 and expert_parallel == 1
        and num_experts == 0
    )
    if len(chosen) > 1 and not (tp_ep_combo or tp_pp_combo):
        raise ValueError("pick ONE of sequence_parallel / pipeline_parallel / "
                         "tensor_parallel / expert_parallel per run (wired "
                         "compositions: tensor_parallel x expert_parallel "
                         "with num_experts>0, and tensor_parallel x "
                         "pipeline_parallel for the dense stack)")
    if num_experts > 0 and (sequence_parallel > 1 or pipeline_parallel > 1):
        # sp/pp run the blocks inside shard_map and do not collect the
        # sown router-aux loss. Refuse rather than quietly degrade.
        raise ValueError("num_experts>0 is wired for dp / expert_parallel / "
                         "tensor_parallel x expert_parallel runs only")
    if num_experts > 0 and tensor_parallel > 1 and expert_parallel == 1:
        # tp's qwen_rules match Dense kernels only, so the dominant
        # (E, D, F) expert stacks would silently stay replicated.
        raise ValueError("MoE with tensor_parallel needs expert_parallel>1 "
                         "too (else the expert stacks stay replicated)")
    if expert_parallel > 1 and use_lora:
        # Same reasoning as tensor_parallel+LoRA below: the trainable tree
        # is the adapters, moe_rules match nothing in it, and the expert
        # axis would just eat devices from data parallelism.
        raise ValueError("expert_parallel with use_lora is not wired; "
                         "run LoRA data-parallel")
    if expert_parallel > 1 and (
        num_experts <= 0 or num_experts % expert_parallel
    ):
        raise ValueError(
            f"expert_parallel={expert_parallel} needs num_experts>0 "
            f"divisible by it (got {num_experts})"
        )
    if tensor_parallel > 1 and use_lora:
        # The LoRA step rebuilds the merged tree per step from replicated
        # base_params, so TP would shard nothing (no memory benefit) while
        # the model axis still eats devices from data parallelism. Refuse
        # rather than silently run at 1/tp throughput.
        raise ValueError("tensor_parallel with use_lora is not wired; "
                         "run LoRA data-parallel (it is already memory-light)")
    if sparse_topk > 0 and (sequence_parallel > 1 or pipeline_parallel > 1
                            or tensor_parallel > 1):
        # Ring attention and the pipeline stage body build their own
        # attention; qwen_rules know nothing of the indexer's leaves.
        raise ValueError("sparse_topk>0 (indexer-selected attention) is wired "
                         "for data-parallel runs only, not sequence_parallel / "
                         "pipeline_parallel / tensor_parallel")
    if (kda_layers or mla_layers) and (
            sequence_parallel > 1 or pipeline_parallel > 1 or tensor_parallel > 1):
        # Ring attention and the pipeline stage body build their own
        # attention; qwen_rules know nothing of these mixers' leaves; a
        # recurrent state does not cross a sequence shard.
        raise ValueError("kda_layers / mla_layers (Kimi Delta Attention, latent "
                         "attention) are wired for data-parallel runs only, not "
                         "sequence_parallel / pipeline_parallel / tensor_parallel")
    if (kda_layers or mla_layers) and use_lora:
        raise ValueError("LoRA on a backbone with kda_layers / mla_layers is "
                         "not wired (its targets would match the new mixers' "
                         "q_proj / k_proj / v_proj leaves)")
    if n_shared_experts and expert_parallel > 1:
        raise ValueError("n_shared_experts with expert_parallel is not wired "
                         "(moe_rules shard the routed stacks only)")
    if moe_experts_held is not None and (expert_parallel > 1 or not moe_dropless):
        raise ValueError("moe_experts_held (one chip's share of the experts) "
                         "needs moe_dropless=True and runs without an exchange: "
                         "expert_parallel must stay 1")
    if use_lora and num_experts > 0 and any(
            t in ("gate_proj", "up_proj", "down_proj", "router")
            for t in lora_targets):
        raise ValueError("LoRA on the experts or the router is not wired "
                         "(stacked (E, D, F) weights have no adapter); keep "
                         "lora_targets to the attention projections")
    if tp_ep_combo:
        from genrec_tpu.parallel import make_mesh

        mesh = make_mesh(
            {"data": -1, "model": tensor_parallel, "expert": expert_parallel}
        )
        logger.info(f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}")
    elif tp_pp_combo:
        from genrec_tpu.parallel import make_mesh

        mesh = make_mesh(
            {"data": -1, "model": tensor_parallel, "pipe": pipeline_parallel}
        )
        logger.info(f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}")
    elif chosen:
        from genrec_tpu.parallel import make_mesh

        axis = (
            ("sp", sequence_parallel) if sequence_parallel > 1
            else ("pipe", pipeline_parallel) if pipeline_parallel > 1
            else ("expert", expert_parallel) if expert_parallel > 1
            else ("model", tensor_parallel)
        )
        mesh = make_mesh({"data": -1, axis[0]: axis[1]})
        logger.info(f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}")
    else:
        mesh = get_mesh()
    compute_dtype = jnp.bfloat16 if (amp and mixed_precision_type == "bf16") else jnp.float32

    rng = jax.random.key(seed)
    init_rng, vocab_rng, state_rng = jax.random.split(rng, 3)

    def random_init_config(vocab_size, max_pos):
        return backbone_config(
            vocab_size=vocab_size, max_position_embeddings=max_pos,
            hidden_size=hidden_size, intermediate_size=intermediate_size,
            n_layers=n_layers, num_heads=num_heads, num_kv_heads=num_kv_heads,
            head_dim=head_dim, attention_bias=attention_bias, qk_norm=qk_norm,
            rope_theta=rope_theta, num_experts=num_experts,
            num_experts_per_tok=num_experts_per_tok,
            moe_intermediate_size=moe_intermediate_size,
            norm_topk_prob=norm_topk_prob, moe_dropless=moe_dropless,
            moe_first_expert=moe_first_expert,
            moe_experts_held=moe_experts_held,
            router_aux_coef=router_aux_coef, sparse_topk=sparse_topk,
            indexer_heads=indexer_heads, indexer_head_dim=indexer_head_dim,
            sparse_chunk=sparse_chunk, rms_norm_eps=rms_norm_eps,
            kda_layers=tuple(kda_layers), mla_layers=tuple(mla_layers),
            first_k_dense_replace=first_k_dense_replace, kda_heads=kda_heads,
            kda_head_dim=kda_head_dim, kv_lora_rank=kv_lora_rank,
            qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            moe_scoring=moe_scoring,
            routed_scaling_factor=routed_scaling_factor,
            n_shared_experts=n_shared_experts,
        )

    # None = each data source's default mix.
    tw_extra = {} if task_weights is None else {"task_weights": tuple(task_weights)}
    if dataset == "synthetic":
        data, tok = synthetic_lcrec_data(
            codebook_size=codebook_size, num_codebooks=num_codebooks, seed=seed,
            **tw_extra,
        )
        data.max_len = max_text_len
        # Backbone vocab covers words only; codebook tokens are appended by
        # extend_vocab below, exactly like the HF resize path.
        cfg = random_init_config(
            tok.base_vocab, max_text_len + num_codebooks + 1)
        model0 = QwenLM(cfg, dtype=compute_dtype, remat=gradient_checkpointing,
                        expert_axis="expert" if expert_parallel > 1 else None)
        params = model0.init(init_rng, jnp.zeros((1, 4), jnp.int32))["params"]
    else:
        # Real-data path (reference amazon_lcrec.py:164-676): sequences +
        # meta text from the Amazon dump, sem ids from the RQ-VAE artifact,
        # HF tokenizer when pretrained_path provides one (WordTokenizer
        # fallback otherwise).
        from genrec_tpu.data.lcrec_tasks import amazon_lcrec_data

        if sem_ids_path is None:
            raise ValueError("amazon LCRec needs sem_ids_path (RQ-VAE artifact)")
        hf_tok = None
        if pretrained_path:
            from transformers import AutoTokenizer

            hf_tok = AutoTokenizer.from_pretrained(pretrained_path)
        data, tok = amazon_lcrec_data(
            dataset_folder, split, sem_ids_path,
            tokenizer=hf_tok, max_len=max_text_len,
            max_history=max_history, seed=seed, **tw_extra,
        )
        num_codebooks = int(data.sem_ids.shape[1])
        codebook_size = int(tok.codebook_size)
        max_pos = max_text_len + max(num_codebooks, index2item_max_new) + 1

        hf_config = os.path.join(pretrained_path or "", "config.json")
        if num_experts > 0 and pretrained_path and os.path.exists(hf_config):
            raise ValueError(
                "num_experts>0 with a full HF checkpoint is not wired "
                "(params_from_hf_state_dict maps dense Qwen2 only)"
            )
        if pretrained_path and os.path.exists(hf_config):
            # Full local checkpoint: convert torch weights into the flax
            # tree (backbones.qwen.params_from_hf_state_dict).
            import json as _json

            with open(hf_config) as f:
                hc = _json.load(f)
            cfg = QwenConfig(
                vocab_size=hc["vocab_size"],
                hidden_size=hc["hidden_size"],
                intermediate_size=hc["intermediate_size"],
                num_hidden_layers=hc["num_hidden_layers"],
                num_attention_heads=hc["num_attention_heads"],
                num_key_value_heads=hc.get(
                    "num_key_value_heads", hc["num_attention_heads"]
                ),
                max_position_embeddings=max(
                    max_pos, hc.get("max_position_embeddings", max_pos)
                ),
                rope_theta=hc.get("rope_theta", 1e6),
                rms_norm_eps=hc.get("rms_norm_eps", 1e-6),
                tie_word_embeddings=hc.get("tie_word_embeddings", True),
            )
            from transformers import AutoModelForCausalLM

            from genrec_tpu.models.backbones.qwen import params_from_hf_state_dict

            hf_model = AutoModelForCausalLM.from_pretrained(pretrained_path)
            sd = {k: v.numpy() for k, v in hf_model.state_dict().items()}
            del hf_model
            params = params_from_hf_state_dict(sd, cfg)
            params = jax.tree_util.tree_map(jnp.asarray, params)
            logger.info(f"loaded HF backbone from {pretrained_path}")
        else:
            # Tokenizer-only dir (or none): random-init backbone at the
            # configured dims, vocab sized to the tokenizer.
            cfg = random_init_config(tok.base_vocab, max_pos)
        model0 = QwenLM(cfg, dtype=compute_dtype, remat=gradient_checkpointing,
                        expert_axis="expert" if expert_parallel > 1 else None)
        params = (
            params
            if pretrained_path and os.path.exists(hf_config)
            else model0.init(init_rng, jnp.zeros((1, 4), jnp.int32))["params"]
        )

    # Append codebook special tokens (resize_token_embeddings equivalent).
    # base = first codebook-token id: the tokenizer's, when it has one (HF
    # models pad vocab past len(tokenizer), so cfg.vocab_size can differ).
    # Pad embed_tokens/lm_head rows to a multiple of lcm(8, tp): divisible
    # by the actual TP degree (including non-power-of-2 meshes) so the
    # qwen_rules vocab sharding never silently falls back to replication,
    # AND independent of tensor_parallel for every tp dividing 8, so a
    # checkpoint trained at one such degree restores/eval_only's at
    # another (pad rows are masked out of the loss by valid_vocab and out
    # of generation by valid_vocab/allowed slices).
    import math

    cfg, params, base_vocab = extend_vocab(
        cfg, params, num_codebooks, codebook_size, vocab_rng,
        base=getattr(tok, "base_vocab", None),
        pad_to=math.lcm(8, max(tensor_parallel, 1)), min_rows=vocab_rows,
    )
    # remat mirrors the reference's gradient_checkpointing_enable (lcrec.py:42-46).
    model = QwenLM(cfg, dtype=compute_dtype, remat=gradient_checkpointing,
                   expert_axis="expert" if expert_parallel > 1 else None)
    # Ids >= live_vocab are pad rows (TP padding / HF resize padding):
    # masked out of the SFT softmax and of generation argmax, so they stay
    # inert and tp>1 losses match tp=1 exactly.
    live_vocab = base_vocab + num_codebooks * codebook_size
    logger.info(
        f"vocab {base_vocab} + {num_codebooks * codebook_size} codebook tokens"
        + (f" (+{cfg.vocab_size - live_vocab} pad)" if cfg.vocab_size > live_vocab else "")
    )

    train_arrays = data.train_arrays(samples_per_user=samples_per_user)
    valid_arrays = data.eval_arrays("valid")
    test_arrays = data.eval_arrays("test")
    if max_train_samples > 0:
        train_arrays = {k: v[:max_train_samples] for k, v in train_arrays.items()}
        logger.info(f"limited train samples to {len(train_arrays['input_ids'])}")
    if max_eval_samples > 0:
        valid_arrays = {k: v[:max_eval_samples] for k, v in valid_arrays.items()}
        test_arrays = {k: v[:max_eval_samples] for k, v in test_arrays.items()}
        logger.info(f"limited eval samples to {len(valid_arrays['input_ids'])}")

    steps_per_epoch = max(1, len(train_arrays["input_ids"]) // batch_size)
    schedule = cosine_schedule_with_warmup(
        learning_rate, num_warmup_steps, epochs * steps_per_epoch
    )
    optimizer = optax.adamw(schedule, weight_decay=weight_decay)

    if sequence_parallel > 1:
        # Ring-attention loss over the sp-sharded token dim; generation
        # (KV-cache decode) stays on the plain model — same param tree.
        from genrec_tpu.models.lcrec import make_sp_sft_loss

        if max_text_len % sequence_parallel:
            raise ValueError(
                f"max_text_len {max_text_len} must divide by "
                f"sequence_parallel {sequence_parallel}"
            )
        _, sp_loss = make_sp_sft_loss(
            cfg, mesh, dtype=compute_dtype, remat=gradient_checkpointing,
            valid_vocab=live_vocab,
        )
        base_loss = lambda p, batch: (sp_loss(p, batch), {})
    elif pipeline_parallel > 1:
        from genrec_tpu.models.pp_sft import make_pp_sft_loss
        from genrec_tpu.parallel.shardings import qwen_rules as _qr

        pp_loss = make_pp_sft_loss(
            cfg, mesh, n_micro=pp_microbatches, dtype=compute_dtype,
            remat=gradient_checkpointing, valid_vocab=live_vocab,
            tp_rules=_qr() if tp_pp_combo else None, log_fn=logger.info,
        )
        base_loss = lambda p, batch: (pp_loss(p, batch), {})
    else:
        if tensor_parallel > 1:
            # Vocab-sharded head: the dense fused kernel cannot be
            # GSPMD-partitioned over the vocab dim, so fused CE routes
            # through shard_map over the model axis instead (per-device
            # pallas_calls, per-shard softmax stats merged with pmax/psum).
            # Auto therefore needs no single-chip gate here — shard_map
            # never asks GSPMD to split the Mosaic call.
            if use_fused_ce == "auto":
                from genrec_tpu.kernels.policy import auto_sharded_fused_ce

                use_fused_ce = auto_sharded_fused_ce()
            if use_fused_ce:
                from genrec_tpu.models.lcrec import (
                    make_tp_sharded_fused_sft_loss,
                )

                tp_loss = make_tp_sharded_fused_sft_loss(
                    model, mesh, valid_vocab=live_vocab
                )
                base_loss = lambda p, batch: (tp_loss(p, batch), {})
            else:
                base_loss = make_dense_sft_loss(model, live_vocab, False)
        else:
            if use_fused_ce == "auto":
                from genrec_tpu.kernels.policy import auto_fused_ce

                use_fused_ce = auto_fused_ce(tensor_parallel)
            base_loss = make_dense_sft_loss(model, live_vocab, bool(use_fused_ce))

    if use_lora:
        lora = lora_init(params, jax.random.fold_in(rng, 7), lora_rank, tuple(lora_targets))
        logger.info(f"LoRA: {lora_param_count(lora)} trainable params")
        base_params = params

        def loss_fn(lp, batch):
            return base_loss(
                lora_merge(base_params, lp, lora_alpha, lora_rank), batch)

        trainable = lora
        params_of = lambda tp: lora_merge(base_params, tp, lora_alpha, lora_rank)
    else:
        loss_fn = base_loss
        trainable = params
        params_of = lambda tp: tp

    step_fn = make_sft_step(loss_fn, optimizer)
    from genrec_tpu.parallel.shardings import make_place_state, moe_rules, qwen_rules

    rules = (
        tuple(qwen_rules()) + tuple(moe_rules()) if tp_ep_combo
        else qwen_rules() if tensor_parallel > 1
        else moe_rules() if expert_parallel > 1
        else None
    )
    place_state = make_place_state(mesh, rules, log_fn=logger.info)
    state = place_state(TrainState.create(trainable, optimizer, state_rng))
    gen_fn = make_generate_fn(
        model, base_vocab, num_codebooks, codebook_size, beam_width,
        max_cache=max_text_len + num_codebooks + 1,
    )
    if eval_item_tasks:
        # item2index (greedy constrained) + index2item (unconstrained)
        # evaluation over the item set (reference lcrec_trainer.py:193-227).
        i2i_arrays = data.item2index_eval_arrays(eval_items_limit)
        idx2i_arrays, idx2i_texts = data.index2item_eval_arrays(eval_items_limit)
        greedy_fn = make_generate_fn(
            model, base_vocab, num_codebooks, codebook_size, 1,
            max_cache=max_text_len + num_codebooks + 1,
        )
        free_fn = jax.jit(
            lambda p, b: generate_greedy(
                model, p, b["input_ids"], b["attention_mask"],
                index2item_max_new, tok.eos_id,
                max_cache=max_text_len + index2item_max_new,
                # Keep argmax off live HF vocab-padding rows the tokenizer
                # cannot decode.
                valid_vocab=tok.vocab_size,
            )
        )

    from genrec_tpu.core.checkpoint import BestTracker, CheckpointManager, save_params
    from genrec_tpu.core.fault_tolerance import restore_for_eval
    from genrec_tpu.core.preemption import PreemptionGuard
    from genrec_tpu.trainers.packed_loop import PackedTrainLoop

    ckpt = CheckpointManager(os.path.join(save_dir_root, "checkpoints")) if save_dir_root else None
    prof = ProfileWindow(
        os.path.join(save_dir_root, "profile") if save_dir_root else "",
        profile_steps,
    )
    guard = PreemptionGuard(logger)
    loop = PackedTrainLoop(
        logger=logger, tracker=tracker, prof=prof, mesh=mesh,
        guard=guard, ckpt=ckpt,
        rows_per_step=batch_size, row_len=max_text_len, seed=seed,
        pack_sequences=False, train_arrays=train_arrays,
        wandb_log_interval=wandb_log_interval,
        save_dir_root=save_dir_root,
    )

    # eval_only restores the latest checkpoint (the reference loads a
    # trained model for eval_only, lcrec_trainer.py:358-364) WITHOUT the
    # exact-resume preconditions — a pure evaluation consumes no training
    # data, so a different data seed or a pre-PR4 record must not refuse;
    # resume picks up mid-training through the step-granular resume point.
    start_epoch, start_batch, global_step = 0, 0, 0
    if eval_only:
        state, ckpt_step = restore_for_eval(
            ckpt, state, place_state, logger=logger  # keep the TP layout
        )
        if ckpt_step is None:
            logger.warning("eval_only without a checkpoint: evaluating the INITIAL model")
    elif resume_from_checkpoint:
        state, start_epoch, start_batch, global_step = loop.resume(
            state, place_state  # restored runs keep the TP layout
        )

    if eval_only:
        m = evaluate(gen_fn, params_of(state.params), valid_arrays, eval_batch_size, mesh, num_codebooks)
        logger.info("eval_only " + ", ".join(f"{k}={v:.4f}" for k, v in m.items()))
        loop.shutdown()
        return m, m

    best = BestTracker(save_dir_root)
    for epoch in range(start_epoch, epochs):
        res = loop.run_epoch(
            state, step_fn, epoch, global_step,
            start_batch=start_batch if epoch == start_epoch else 0,
        )
        state, global_step = res.state, res.global_step
        if res.preempted:
            # SIGTERM/SIGINT grace window: the loop already wrote a
            # durable mid-epoch resume point (even mid-FINAL-epoch — the
            # hole the old epoch-granular guard left open); exit cleanly
            # so the scheduler restarts us with resume_from_checkpoint.
            loop.shutdown(preempted_epoch=epoch)
            return {}, {}

        if ckpt is not None and (epoch + 1) % save_every_epoch == 0:
            # Epoch-boundary resume point: cursor = (next epoch, batch 0).
            loop.save(state, epoch=epoch + 1, next_batch=0,
                      global_step=global_step)

        if do_eval and (epoch + 1) % eval_every_epoch == 0:
            m = evaluate(gen_fn, params_of(state.params), valid_arrays, eval_batch_size, mesh, num_codebooks)
            logger.info(
                f"epoch {epoch} valid " + ", ".join(f"{k}={v:.4f}" for k, v in m.items())
            )
            tracker.log({"epoch": epoch, **{f"eval/{k}": v for k, v in m.items()}})
            best.update(m["Recall@10"], state.params)

    # Unconditional final resume point: closes the old hole where a
    # save_every_epoch cadence never firing left a completed run with
    # NOTHING on disk to resume from.
    loop.save(state, epoch=epochs, next_batch=0, global_step=global_step)
    final_trainable = (
        best.best_params(like=state.params) if test_on_best else None
    )
    if final_trainable is None:
        final_trainable = state.params
    final_params = params_of(final_trainable)
    valid_metrics = evaluate(gen_fn, final_params, valid_arrays, eval_batch_size, mesh, num_codebooks)
    test_metrics = evaluate(gen_fn, final_params, test_arrays, eval_batch_size, mesh, num_codebooks)
    if eval_item_tasks:
        test_metrics.update(
            evaluate_item2index(
                greedy_fn, final_params, i2i_arrays, eval_batch_size, mesh,
                num_codebooks,
            )
        )
        test_metrics.update(
            evaluate_index2item(
                free_fn, final_params, idx2i_arrays, idx2i_texts,
                eval_batch_size, mesh, tok,
            )
        )
    logger.info("test " + ", ".join(f"{k}={v:.4f}" for k, v in test_metrics.items()))
    tracker.log({f"test/{k}": v for k, v in test_metrics.items()})
    if save_dir_root:
        # Best tracker stores the TRAINABLE tree (lora or full); persist the
        # merged model too for direct consumption.
        save_params(os.path.join(save_dir_root, "final_model"), final_params)
    loop.shutdown()
    return valid_metrics, test_metrics


if __name__ == "__main__":
    configlib.parse_config()
    train()
