"""Adapter: builds the system under test for ``tiger_amazon`` from a seed.

This is the one file of the configuration that imports the program. It
repeats the wiring of ``genrec_tpu.trainers.tiger_trainer.train`` (that
function is 300 lines with no seam: see PERF.md, list for the tracing issue)
and of ``chip_smoke.phase_serve``, and hands back the entries the measured
windows drive: ``PackedTrainLoop.run_epoch`` with the jitted step, and a
started ``ServingEngine``. Weights and data are made here from the seed,
never by the program's own initialisers or datasets, so the plain reference
gets the same tree and the same raw examples without taking anything the
program computed.
"""

from __future__ import annotations

import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax


def _model(cfg: dict):
    from genrec_tpu.models.tiger import Tiger

    return Tiger(
        embedding_dim=cfg["embedding_dim"], attn_dim=cfg["attn_dim"],
        dropout=cfg["dropout"], num_heads=cfg["num_heads"],
        n_layers=cfg["n_layers"], num_item_embeddings=cfg["codebook_size"],
        num_user_embeddings=cfg["num_user_embeddings"],
        sem_id_dim=cfg["sem_id_dim"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
    )


def param_shapes(cfg: dict):
    model = _model(cfg)
    L = cfg["max_items"] * cfg["sem_id_dim"]
    D = cfg["sem_id_dim"]
    z = lambda *s: jnp.zeros(s, jnp.int32)
    return jax.eval_shape(
        lambda k: model.init(k, z(1), z(1, L), z(1, L), z(1, D), z(1, D),
                             jnp.ones((1, L), jnp.int32))["params"],
        jax.random.key(0),
    )


def make_params(cfg: dict, seed: int):
    """The whole tree in one jitted call on the device, float32 (the type
    the program trains and serves from). Distributions follow the model's
    own initialisers: unit normal tables, 0.02 relative bias, ones for
    norms, fan-in scaled normal for projections."""
    shapes = param_shapes(cfg)
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]

    def init(key):
        out = []
        for i, (path, sd) in enumerate(leaves):
            name = str(path[-1].key)
            k = jax.random.fold_in(key, i)
            if name == "weight":
                v = jnp.ones(sd.shape, jnp.float32)
            elif name == "kernel":
                v = jax.random.normal(k, sd.shape, jnp.float32) / np.sqrt(sd.shape[0])
            elif name == "rel_bias":
                v = 0.02 * jax.random.normal(k, sd.shape, jnp.float32)
            else:  # embedding tables, BOS, the unused position tables
                v = jax.random.normal(k, sd.shape, jnp.float32)
            out.append(v)
        return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(shapes), out)

    return jax.jit(init)(jax.random.key(seed % (2**31 - 1)))


def make_catalog(cfg: dict, seed: int) -> np.ndarray:
    """``catalog_items`` unique sem-id tuples drawn from the seed."""
    rng = np.random.default_rng([seed, 11])
    cb, depth, n = cfg["codebook_size"], cfg["sem_id_dim"], cfg["assumed"]["catalog_items"]
    codes = rng.choice(cb ** depth, size=n, replace=False)
    out = np.zeros((n, depth), np.int64)
    for d in reversed(range(depth)):
        out[:, d] = codes % cb
        codes = codes // cb
    return out


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


class _Tracker:
    """What ``PackedTrainLoop`` logs to: keeps every step's loss (the gin
    logs every step, ``wandb_log_interval = 1``)."""

    def __init__(self):
        self.step_losses: list[float] = []

    def log(self, payload: dict) -> None:
        if "train/loss" in payload and "global_step" in payload:
            self.step_losses.append(float(payload["train/loss"]))

    def finish(self) -> None:
        pass


def make_step(cfg: dict):
    """(model, optimizer, jitted step), wired as ``tiger_trainer.train``
    wires them: ``jit_train_step(make_train_step(loss_fn, adamw, clip))``
    over ``Tiger.forward_packed``."""
    from genrec_tpu.core.harness import jit_train_step, make_train_step
    from genrec_tpu.models.tiger import Tiger
    from genrec_tpu.ops.schedules import cosine_schedule_with_warmup

    opt = cfg["optimizer"]
    model = _model(cfg)
    schedule = cosine_schedule_with_warmup(
        opt["learning_rate"], opt["warmup_steps"], opt["total_steps"])
    optimizer = optax.adamw(schedule, weight_decay=opt["weight_decay"])

    def loss_fn(params, batch, step_rng):
        out = model.apply(
            {"params": params},
            batch["item_input_ids"], batch["token_type_ids"],
            batch["user_token_ids"], batch["user_mask"],
            batch["segment_ids"], batch["positions"],
            batch["target_ids"], batch["segment_valid"],
            deterministic=False, rngs={"dropout": step_rng},
            method=Tiger.forward_packed,
        )
        return out.loss, {"real_tokens": out.real_tokens.astype(jnp.float32)}

    step_fn = jit_train_step(
        make_train_step(loss_fn, optimizer, accum_steps=1,
                        clip_norm=opt["clip_norm"]))
    return model, optimizer, step_fn


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
    R = int(traffic["rows_per_step_per_chip"]) * chips
    L = 1 + cfg["max_items"] * cfg["sem_id_dim"]
    S, D = int(traffic["pack_max_segments"]), cfg["sem_id_dim"]
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    batch = {k: i32(R, L) for k in (
        "item_input_ids", "token_type_ids", "user_token_ids", "user_mask",
        "segment_ids", "positions")}
    batch.update(target_ids=i32(R, S, D), example_id=i32(R, S),
                 segment_valid=i32(R, S))
    return state, batch


class TrainEntry:
    """The object set-up builds once and the window then drives."""

    def __init__(self, cfg, traffic, seed, examples, mesh, tracer=None):
        from genrec_tpu.core.profiling import ProfileWindow
        from genrec_tpu.core.state import TrainState
        from genrec_tpu.data.batching import pack_examples
        from genrec_tpu.parallel.shardings import make_place_state
        from genrec_tpu.trainers.packed_loop import PackedTrainLoop

        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.examples = examples
        self.mesh = mesh
        opt = cfg["optimizer"]
        chips = mesh.devices.size
        self.rows_per_step = int(traffic["rows_per_step_per_chip"]) * chips
        self.row_len = 1 + cfg["max_items"] * cfg["sem_id_dim"]
        self.repack_seconds = 0.0
        self.repack_spans: list = []  # (t0, t1) on time.monotonic
        self.on_step = None
        self._row_tokens = None
        self._row_epoch = -1

        def repack(epoch: int):
            t0 = time.monotonic()
            out = pack_examples(
                examples, row_len=self.row_len,
                segment_keys=("target_ids", "example_id"),
                max_segments=int(traffic["pack_max_segments"]),
                seed=(seed, epoch),
            )
            t1 = time.monotonic()
            self.repack_seconds += t1 - t0
            self.repack_spans.append((t0, t1))
            arrays = out[0]
            # Real tokens of each packed row: its encoder tokens and the
            # target codes of its segments (counted here, from the arrays
            # handed to the loop, not read from the program's own count).
            self._row_tokens = (
                (arrays["segment_ids"] != 0).sum(axis=1)
                + cfg["sem_id_dim"] * arrays["segment_valid"].sum(axis=1)
            )
            self._row_epoch = epoch
            return out

        self.tracker = _Tracker()
        self.logger = logging.getLogger("benchmark.train")
        self.loop = PackedTrainLoop(
            logger=self.logger, tracker=self.tracker,
            prof=ProfileWindow("", 0), mesh=mesh, guard=None, ckpt=None,
            rows_per_step=self.rows_per_step, row_len=self.row_len, seed=seed,
            pack_sequences=True, repack=repack, tokens_scale=1.0,
            wandb_log_interval=int(opt["log_interval"]),
            save_dir_root=None, step_hook=self._hook, tracer=tracer,
        )
        self.pack_report = self.loop.pack_report  # packs epoch 0 (as train())
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

    def first_batches(self, n: int) -> list[dict]:
        """The host batches of the first ``n`` steps: the same call
        ``run_epoch`` makes, on the epoch-0 pack the loop holds."""
        from genrec_tpu.data.batching import batch_iterator

        it = batch_iterator(
            self.loop._arrays_for(0), self.rows_per_step, shuffle=True,
            seed=self.seed, epoch=0, drop_last=True,
        )
        return [next(it)[0] for _ in range(n)]

    def run_epoch(self, max_steps=None, start_batch: int = 0):
        res = self.loop.run_epoch(
            self.state, self.step_fn, self.epoch, self.global_step,
            start_batch=start_batch, max_steps=max_steps,
        )
        self.state, self.global_step = res.state, res.global_step
        return res

    def epoch_tokens(self, n_batches: int) -> tuple[int, int]:
        """(real tokens, row slots) of the ``n_batches`` steps the current
        epoch ran: the rows come from the same ``batch_iterator`` call."""
        from genrec_tpu.data.batching import batch_iterator

        if self._row_epoch != self.epoch:
            raise RuntimeError("token count asked for an epoch not packed")
        it = batch_iterator(
            {"row": np.arange(len(self._row_tokens))}, self.rows_per_step,
            shuffle=True, seed=self.seed, epoch=self.epoch, drop_last=True,
        )
        tokens = 0
        for _ in range(n_batches):
            tokens += int(self._row_tokens[next(it)[0]["row"]].sum())
        slots = n_batches * self.rows_per_step * (
            self.row_len + self.cfg["sem_id_dim"] * int(self.traffic["pack_max_segments"]))
        return tokens, slots

    def mean_history_tokens(self) -> float:
        """Mean encoder tokens of an example (user token + history)."""
        return float(np.mean([len(e["item_input_ids"]) for e in self.examples]))

    def close(self):
        self.state = None
        self.params0 = None
        self.snapshots.clear()


def make_examples(cfg: dict, traffic: dict, seed: int, item_sem_ids: np.ndarray):
    """Raw variable-length training examples in the packer's format
    (``TigerSeqData.train_examples``), plus ``example_id`` so that the
    comparison can name the examples of a batch without reading the
    packer's layout. Lengths come from the traffic's generator."""
    from benchmark.harness.traffic import history_lengths

    rng = np.random.default_rng([seed, 12])
    n = int(traffic["corpus_examples"])
    D = cfg["sem_id_dim"]
    lens = history_lengths(traffic["history_lengths"], n, cfg["max_items"], seed)
    n_items = len(item_sem_ids)
    sem = item_sem_ids.astype(np.int32)
    total = int(lens.sum())
    items = rng.integers(0, n_items, total)
    targets = sem[rng.integers(0, n_items, n)]
    users = rng.integers(0, traffic["n_users"], n) % cfg["num_user_embeddings"]
    flat = sem[items].reshape(-1)  # all histories' tokens, concatenated
    types_row = np.tile(np.arange(D, dtype=np.int32), cfg["max_items"])
    out = []
    cursor = 0
    for i in range(n):
        m = int(lens[i]) * D
        ids = np.zeros(1 + m, np.int32)
        ids[1:] = flat[cursor:cursor + m]
        cursor += m
        types = np.zeros(1 + m, np.int32)
        types[1:] = types_row[:m]
        utok = np.zeros(1 + m, np.int32)
        utok[0] = users[i]
        umask = np.zeros(1 + m, np.int32)
        umask[0] = 1
        out.append({
            "item_input_ids": ids, "token_type_ids": types,
            "user_token_ids": utok, "user_mask": umask,
            "target_ids": targets[i], "example_id": np.int32(i),
        })
    return out


def reference_examples(examples, ids, cfg) -> dict:
    """The raw examples ``ids`` in the reference's plain padded layout."""
    L = cfg["max_items"] * cfg["sem_id_dim"]
    n = len(ids)
    hist = np.zeros((n, L), np.int32)
    n_tok = np.zeros(n, np.int32)
    user = np.zeros(n, np.int32)
    target = np.zeros((n, cfg["sem_id_dim"]), np.int32)
    for j, i in enumerate(ids):
        ex = examples[int(i)]
        m = len(ex["item_input_ids"]) - 1
        hist[j, :m] = ex["item_input_ids"][1:]
        n_tok[j] = m
        user[j] = ex["user_token_ids"][0]
        target[j] = ex["target_ids"]
    return {"user": user, "hist": hist, "n_tok": n_tok, "target": target}


def build_train(cfg: dict, traffic: dict, seed: int, chips: int,
                tracer=None) -> TrainEntry:
    from genrec_tpu.parallel import make_mesh

    mesh = make_mesh({"data": chips}, devices=jax.devices()[:chips])
    catalog = make_catalog(cfg, seed)
    examples = make_examples(cfg, traffic, seed, catalog)
    return TrainEntry(cfg, traffic, seed, examples, mesh, tracer=tracer)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def build_serve(cfg: dict, traffic: dict, seed: int, tracer=None):
    """A started, warmed engine with one paged TIGER head."""
    from genrec_tpu.serving import BucketLadder, PagedConfig, ServingEngine
    from genrec_tpu.serving.heads import TigerGenerativeHead

    a = cfg["assumed"]["serve"]
    model = _model(cfg)
    catalog = make_catalog(cfg, seed)
    params = make_params(cfg, seed)
    head = TigerGenerativeHead(model, catalog, top_k=cfg["assumed"]["beam"])
    kv_tokens = head.paged_kv_tokens(cfg["max_items"], cfg["max_items"])
    paged = PagedConfig(
        max_slots=a["max_slots"], page_size=a["page_size"],
        pages_per_slot=-(-kv_tokens // a["page_size"]),
        num_pages=a["num_pages"],
    )
    engine = ServingEngine(
        [head], params, paged=True, paged_config=paged,
        ladder=BucketLadder(tuple(a["batch_buckets"]), tuple(a["history_buckets"])),
        max_batch=a["max_batch"], max_wait_ms=a["max_wait_ms"],
        prefix_cache=True, prefix_cache_entries=a["prefix_cache_entries"],
        handle_signals=False, tracer=tracer,
    )
    engine.start()
    return engine, head, params, catalog


def make_request(head_name: str, user_id: int, history):
    from genrec_tpu.serving import Request

    return Request(head=head_name, user_id=int(user_id),
                   history=np.asarray(history, np.int64))
