"""Adapter: builds the system under test for ``kimi_linear_48b_a3b`` from a seed.

The one file of the configuration that imports the program. It wires what
``genrec_tpu.trainers.lcrec_trainer.train`` wires, through that trainer's own
seams: ``backbone_config`` (the ``QwenConfig`` of a random-init backbone from
the trainer's scalar arguments, layer kinds among them), ``make_dense_sft_loss``
(``sft_loss`` with the step's counters), ``make_sft_step``
(``jit_train_step(make_train_step(...))``, clip 1.0) and
``PackedTrainLoop(pack_sequences=False)``. Weights and rows are made here from
the seed, never by the program's initialisers or datasets, so the plain
reference gets the same tree and the same raw rows. Rows and the train entry
are laid out as ``keye_vl2_30b_a3b/adapter.py`` lays them out (a configuration
keeps its own files: nothing is shared between the two directories).

The imports below are at the top on purpose: a checkout of the program that
lacks these mixers fails here, at once, before anything is built or compiled.
"""

from __future__ import annotations

import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
import optax

from genrec_tpu.models.backbones import kda, mla  # noqa: F401  (fail at once without them)
from genrec_tpu.models.backbones.qwen import QwenLM
from genrec_tpu.trainers.lcrec_trainer import (
    backbone_config,
    make_dense_sft_loss,
    make_sft_step,
)


def model_config(cfg: dict, share: tuple[int, int] | None = None):
    """The program's ``QwenConfig`` of the configuration as run. ``share``
    (first expert, experts held) overrides the file's (for the test that
    ties the share to the model)."""
    lac = cfg["linear_attn_config"]
    n = cfg["num_hidden_layers"]
    first, held = share if share is not None else (
        int(cfg.get("first_expert", 0)), int(cfg["num_experts"]))
    return backbone_config(
        vocab_size=cfg["vocab_size"],
        max_position_embeddings=cfg["model_max_length"],
        hidden_size=cfg["hidden_size"], intermediate_size=cfg["intermediate_size"],
        n_layers=n, num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        attention_bias=False, rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=cfg["rms_norm_eps"],
        kda_layers=tuple(i for i in lac["kda_layers"] if i <= n),
        mla_layers=tuple(i for i in lac["full_attn_layers"] if i <= n),
        kda_heads=lac["num_heads"], kda_head_dim=lac["head_dim"],
        kv_lora_rank=cfg["kv_lora_rank"], qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        sparse_chunk=cfg["assumed"]["attention_query_tile"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        num_experts=cfg["num_experts_published"],
        num_experts_per_tok=cfg["num_experts_per_token"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        norm_topk_prob=cfg["moe_renormalize"], moe_dropless=True,
        moe_first_expert=first, moe_experts_held=held,
        moe_scoring=cfg["moe_router_activation_func"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        n_shared_experts=cfg["num_shared_experts"],
        router_aux_coef=cfg["router_aux_coef"],
    )


def _model(cfg: dict):
    return QwenLM(model_config(cfg), dtype=jnp.dtype(cfg["compute_dtype"]),
                  remat=True)


def param_shapes(cfg: dict):
    model = _model(cfg)
    return jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 4), jnp.int32))["params"],
        jax.random.key(0))


def make_params(cfg: dict, seed: int):
    """The whole tree in one jitted call on the device, float32. By the
    model's own distributions (normal(0.02) head, fan-in scaled normal for
    projections, convolution filters and expert stacks, ones for norm
    scales, zeros for biases and the selection bias, ``A_log`` the log of
    uniform(1, 16), ``dt_bias`` the inverse softplus of a log-uniform step in
    [1e-3, 1e-1], so that a channel forgets between 0.1% and 80% a token as
    in a trained model) but for the embedding rows, which are UNIT normal, as
    ``keye_vl2_30b_a3b`` has them and for its reason: at 0.02 the layers'
    outputs swamp a token's own row and every token routes alike (PERF.md
    section 6, PR 30)."""
    shapes = param_shapes(cfg)
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]

    def init(key):
        out = []
        for i, (path, sd) in enumerate(leaves):
            name = str(path[-1].key)
            k = jax.random.fold_in(key, i)
            if name in ("weight", "scale"):
                v = jnp.ones(sd.shape, jnp.float32)
            elif name in ("bias", "selection_bias"):
                v = jnp.zeros(sd.shape, jnp.float32)
            elif name == "A_log":
                v = jnp.log(jax.random.uniform(k, sd.shape, jnp.float32, 1.0, 16.0))
            elif name == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, sd.shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
                v = dt + jnp.log(-jnp.expm1(-dt))
            elif name == "embed_tokens":
                v = jax.random.normal(k, sd.shape, jnp.float32)
            elif name == "lm_head":
                v = 0.02 * jax.random.normal(k, sd.shape, jnp.float32)
            else:  # (in, out) kernels, (taps, channels) filters, (experts, in, out) stacks
                v = jax.random.normal(k, sd.shape, jnp.float32) / np.sqrt(sd.shape[-2])
            out.append(v)
        return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(shapes), out)

    return jax.jit(init)(jax.random.key(seed % (2**31 - 1)))


# ---------------------------------------------------------------------------
# rows: lifelong histories as LCRec's seqrec task lays them out
# ---------------------------------------------------------------------------


def row_items(cfg: dict, traffic: dict) -> np.ndarray:
    """Items (history + target) of each row of the corpus. Token lengths are
    log-normal, clipped, ONE fixed draw from the mix's ``base_seed``: every
    run sees the same real tokens an epoch."""
    spec = traffic["history_tokens"]
    rng = np.random.default_rng([int(spec["base_seed"]), 31])
    tokens = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"],
                               int(traffic["corpus_rows"])))
    tokens = np.clip(tokens, spec["min"], min(spec["max"], traffic["row_len"]))
    return ((tokens - cfg["instruction_tokens"]) // cfg["sem_id_dim"]).astype(np.int64)


def make_rows(cfg: dict, traffic: dict, seed: int) -> dict:
    """The corpus in the LCRec loop's fixed padded layout (left-padded,
    ``pack_sequences=False``): ``instruction_tokens`` text ids, then the
    history's items oldest first, 5 codebook tokens each, then the target's
    5. Labels on every codebook token from the second item on."""
    rng = np.random.default_rng([seed, 12])
    L, D = int(traffic["row_len"]), cfg["sem_id_dim"]
    n_instr, base, cb = cfg["instruction_tokens"], cfg["base_vocab"], cfg["codebook_size"]
    items = row_items(cfg, traffic)
    n = len(items)
    p = np.arange(1, base + 1, dtype=np.float64) ** -float(traffic["instruction_zipf_a"])
    ids = np.zeros((n, L), np.int32)
    mask = np.zeros((n, L), np.int32)
    labels = np.full((n, L), -100, np.int32)
    offsets = base + np.arange(D) * cb
    for r in range(n):
        codes = rng.integers(0, cb, (int(items[r]), D)) + offsets
        row = np.concatenate([rng.choice(base, n_instr, p=p / p.sum()),
                              codes.reshape(-1)]).astype(np.int32)
        pad = L - len(row)
        ids[r, pad:] = row
        mask[r, pad:] = 1
        labels[r, pad + n_instr + D:] = row[n_instr + D:]
    return {"input_ids": ids, "attention_mask": mask, "labels": labels,
            "example_id": np.arange(n, dtype=np.int32),
            "segment_valid": np.ones(n, np.int32)}


def reference_examples(rows: dict, ids, cfg) -> dict:
    """The raw rows ``ids``, as the reference takes them."""
    ids = np.asarray(ids)
    return {k: rows[k][ids] for k in ("input_ids", "attention_mask", "labels")}


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


class _Tracker:
    """What ``PackedTrainLoop`` logs to: keeps every step's loss."""

    def __init__(self):
        self.step_losses: list[float] = []

    def log(self, payload: dict) -> None:
        if "train/loss" in payload and "global_step" in payload:
            self.step_losses.append(float(payload["train/loss"]))

    def finish(self) -> None:
        pass


def make_step(cfg: dict):
    """(model, optimizer, jitted step), as ``lcrec_trainer.train`` wires
    them: AdamW on the trainer's warm-up + cosine schedule, the trainer's
    own loss and step, the fused head loss where its policy turns it on."""
    from genrec_tpu.kernels.policy import auto_fused_ce
    from genrec_tpu.ops.schedules import cosine_schedule_with_warmup

    opt = cfg["optimizer"]
    model = _model(cfg)
    schedule = cosine_schedule_with_warmup(
        opt["learning_rate"], opt["warmup_steps"], opt["total_steps"])
    optimizer = optax.adamw(schedule, weight_decay=opt["weight_decay"])

    def loss(params, batch):
        # the trainer's "auto", asked as the step is traced (so that a
        # compile rehearsal steered to the chip's branch takes it too)
        return make_dense_sft_loss(model, cfg["vocab_size"], auto_fused_ce(1))(
            params, batch)

    return model, optimizer, make_sft_step(loss, optimizer)


def train_shapes(cfg: dict, traffic: dict, chips: int = 1):
    """Shapes of the step's arguments at the cell's size (for the compile
    rehearsal: no arrays are made)."""
    from genrec_tpu.core.state import TrainState

    _, optimizer, _ = make_step(cfg)
    state = jax.eval_shape(
        lambda: TrainState.create(
            jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                                   param_shapes(cfg)),
            optimizer, jax.random.key(0)))
    R, L = int(traffic["rows_per_step_per_chip"]) * chips, int(traffic["row_len"])
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    batch = {k: i32(R, L) for k in ("input_ids", "attention_mask", "labels")}
    batch.update(example_id=i32(R), segment_valid=i32(R))
    return state, batch


class TrainEntry:
    """The object set-up builds once and the window then drives."""

    def __init__(self, cfg, traffic, seed, rows, mesh, tracer=None):
        from genrec_tpu.core.profiling import ProfileWindow
        from genrec_tpu.core.state import TrainState
        from genrec_tpu.parallel.shardings import make_place_state
        from genrec_tpu.trainers.packed_loop import PackedTrainLoop

        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.examples = rows
        chips = mesh.devices.size
        self.rows_per_step = int(traffic["rows_per_step_per_chip"]) * chips
        self.row_len = int(traffic["row_len"])
        self.repack_seconds = 0.0  # this loop does not repack
        self.repack_spans: list = []
        self.on_step = None
        self._row_tokens = rows["attention_mask"].sum(axis=1)
        self.tracker = _Tracker()
        self.logger = logging.getLogger("benchmark.train")
        self.logger.setLevel(logging.INFO)  # the loop's epoch lines, on stderr
        self.loop = PackedTrainLoop(
            logger=self.logger, tracker=self.tracker,
            prof=ProfileWindow("", 0), mesh=mesh, guard=None, ckpt=None,
            rows_per_step=self.rows_per_step, row_len=self.row_len, seed=seed,
            pack_sequences=False, train_arrays=rows,
            wandb_log_interval=int(cfg["optimizer"]["log_interval"]),
            save_dir_root=None, step_hook=self._hook, tracer=tracer,
        )
        self.model, optimizer, self.step_fn = make_step(cfg)
        place_state = make_place_state(mesh, None, log_fn=self.logger.info)
        params = make_params(cfg, seed)
        self.params0 = jax.tree_util.tree_map(jnp.copy, params)
        self.state = place_state(TrainState.create(
            params, optimizer, jax.random.key(seed % (2**31 - 1))))
        self.epoch = 0
        self.global_step = 0
        self.snapshots: dict[int, object] = {}
        self.snap_at: dict[int, object] = {}
        self.steps_done = 0

    # -- the hook runs after every step, before the state is donated again --

    def _hook(self, state, epoch, consumed, global_step):
        self.steps_done += 1
        if self.on_step is not None:
            self.on_step()
        fn = self.snap_at.get(global_step)
        if fn is not None:
            self.snapshots[global_step] = fn(state)

    def _batches(self, arrays, epoch):
        from genrec_tpu.data.batching import batch_iterator

        return batch_iterator(arrays, self.rows_per_step, shuffle=True,
                              seed=self.seed, epoch=epoch, drop_last=True)

    def first_batches(self, n: int) -> list[dict]:
        """The host batches of the first ``n`` steps: the same call
        ``run_epoch`` makes."""
        it = self._batches(self.examples, 0)
        return [next(it)[0] for _ in range(n)]

    def run_epoch(self, max_steps=None, start_batch: int = 0):
        res = self.loop.run_epoch(
            self.state, self.step_fn, self.epoch, self.global_step,
            start_batch=start_batch, max_steps=max_steps,
        )
        self.state, self.global_step = res.state, res.global_step
        # the caller keeps the result through the reference's run: it must
        # not hold 9.7 GB of state alive on the device
        return dataclasses.replace(res, state=None)

    def epoch_tokens(self, n_batches: int) -> tuple[int, int]:
        """(real tokens, row slots) of the ``n_batches`` steps the current
        epoch ran, counted here from the rows handed to the loop."""
        it = self._batches({"row": np.arange(len(self._row_tokens))}, self.epoch)
        tokens = sum(int(self._row_tokens[next(it)[0]["row"]].sum())
                     for _ in range(n_batches))
        return tokens, n_batches * self.rows_per_step * self.row_len

    def mean_history_tokens(self) -> float:
        """Mean real tokens of a row before its target's codes (what the
        train kind adds ``sem_id_dim`` to for the tokens of an example)."""
        return float(self._row_tokens.mean()) - self.cfg["sem_id_dim"]

    def close(self):
        """Free the device for the reference: the state, the window's copy
        of the starting weights, and the step's executable, which holds its
        temporaries reserved for as long as it is loaded."""
        self.state = self.params0 = None
        self.snapshots.clear()
        self.step_fn = self.loop = self.model = None
        jax.clear_caches()


def build_train(cfg: dict, traffic: dict, seed: int, chips: int,
                tracer=None) -> TrainEntry:
    from genrec_tpu.parallel import make_mesh

    mesh = make_mesh({"data": chips}, devices=jax.devices()[:chips])
    return TrainEntry(cfg, traffic, seed, make_rows(cfg, traffic, seed), mesh,
                      tracer=tracer)
