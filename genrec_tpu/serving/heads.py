"""Engine heads: how each model family answers a padded micro-batch.

A head owns a model + its item-corpus tables and exposes four hooks the
engine composes:

- ``make_batch(reqs, B, L)``: pad a list of requests into device arrays
  at the (B, L) bucket — fewer rows than B are zero/pad-filled, histories
  longer than L keep their newest items;
- ``make_fn(B, L)``: the pure function (params, *batch) -> outputs that
  the engine AOT-compiles once per bucket;
- ``finalize(outputs, reqs)``: host-side split of the batch outputs into
  per-request payloads;
- ``on_params(params)``: refresh derived tables after a hot reload (the
  COBRA head re-encodes its item tower here).

Two families:

- **Generative** (TIGER, COBRA): trie-constrained KV-cached beam search —
  legal-item masking is fused into every decode step, so each emitted
  sem-id tuple is a REAL item and maps back to an item id through the
  corpus lookup ("Vectorizing the Trie", arxiv 2602.22647: the mask must
  live on-accelerator or the decode loop syncs to host every step). The
  corpus lives in a `catalog.CatalogSnapshot` and its trie is a
  `catalog.TensorTrie` RUNTIME OPERAND: `runtime_operands()` threads the
  trie tensors between params and the batch in every compiled call, so
  one executable serves any same-rung catalog snapshot and the engine
  hot-swaps catalogs between micro-batches exactly like params
  (`set_catalog`, `Response.catalog_version`).
- **Retrieval** (SASRec, HSTU): `last_hidden` (one position, not the full
  sequence) scored against the tied item-embedding table through
  `parallel.shardings.item_topk`, which shards the item axis when the
  engine runs on a mesh.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from genrec_tpu.catalog import CatalogSnapshot


class Head:
    """Interface + shared history padding helpers.

    Heads with ``supports_paged = True`` additionally implement the paged
    decode protocol (ragged paged KV + slot-level continuous batching —
    the engine's `_PagedRunner` composes these):

    - ``paged_layout() -> (n_layers, n_heads, head_dim, dtype)``: the
      per-layer page-pool geometry;
    - ``paged_kv_tokens(n_items, L_bucket) -> int``: KV tokens a request
      occupies after prefill at history bucket L (page allocation +
      seq_lens);
    - ``paged_init_step`` / ``paged_total_steps``: a slot enters decode at
      init_step and finishes when its step counter reaches total_steps;
    - ``paged_state_zeros(n_slots)``: the slot-major decode-state dict,
      zeroed; `serving/slots.SlotTable` places it on the device and keeps
      it there between steps;
    - ``paged_result_leaves``: the state leaves ``paged_finalize`` reads,
      the only ones a decode step brings back to the host;
    - ``paged_init_leaves``: the state leaves the prefill's ``init`` (and
      so a warm admit or a handoff) may carry, the only ones a bind
      stages from the host; every other leaf of a bound row is zeroed on
      the device;
    - ``make_prefill_paged_fn(B, L)``: compiled per (batch, history)
      bucket — signature (params, *runtime_operands, *batch,
      block_tables, k_pools, v_pools): runs the encoder/prefill, WRITES
      its K/V into the pools through the batch's block tables, returns
      (k_pools, v_pools, init) with init rows scattered into admitted
      slots;
    - ``make_decode_paged_fn()``: compiled ONCE at max_slots — signature
      (params, *runtime_operands, state, steps, block_tables, seq_lens,
      k_pools, v_pools): advances every slot one step (per-slot step
      operands);
    - ``paged_finalize(state_row, req)``: slot state -> response payload.

    A head's page layers are a SUBSET of its layers: ``paged_layout``
    describes only the layers that have keys (the LCRec head's
    full-attention layers; its KDA layers have none). What the other
    layers carry from token to token lives in the slot's row:
    ``paged_recurrent_leaves`` names those state leaves (a constant-size
    recurrent state a beam, and the prompt's end state a slot that the
    prefill hands over in ``init`` and a prefix entry snapshots), so the
    engine can count their bytes. ``paged_prefill_counters`` names keys of
    the prefill's ``init`` that are one number a LAUNCH, not rows of slot
    state: the runner takes them out before the bind (and reads them only
    with the tracer on). ``paged_check_options`` refuses, by name, engine
    options the head's paged path does not implement.

    Catalog heads additionally thread their trie through
    ``runtime_operands()`` (the engine inserts it between params and the
    batch in every compiled call), so the corpus swaps without a
    recompile.
    """

    name: str
    top_k: int
    generative = False
    supports_paged = False
    #: Heads whose corpus is a swappable CatalogSnapshot (set_catalog /
    #: runtime_operands / catalog_version below).
    supports_catalog = False
    #: Paged heads that additionally implement speculative tree decode
    #: (docs/SERVING.md "Speculative decoding"): ``spec_depth`` levels
    #: speculated past the always-exact root step, verified through
    #: ``make_spec_decode_paged_fn(fanout)`` — signature identical to
    #: the plain decode fn but returning (state, accept (S,) int32).
    #: ``enable_spec_drafting()`` is called by the runner BEFORE state /
    #: prefill compilation so the head can extend both with drafter
    #: hints (TIGER's prefill-computed step-0 logits).
    supports_spec = False
    #: State leaves that hold recurrent (non-KV) state, and keys of the
    #: prefill's ``init`` that are launch-level counters (class docstring).
    paged_recurrent_leaves: tuple = ()
    paged_prefill_counters: tuple = ()

    def paged_check_options(self, *, kv_dtype: str = "float32",
                            spec_decode: bool = False, mesh=None,
                            handoff: bool = False) -> None:
        """Raise ValueError naming the first engine option this head's
        paged path does not implement. Default: everything goes."""

    @property
    def spec_depth(self) -> int:
        return 0

    def enable_spec_drafting(self) -> None:
        return None

    def make_spec_decode_paged_fn(self, fanout: int):
        raise NotImplementedError(f"head {self.name!r} has no speculative decode")

    def on_params(self, params) -> None:  # derived-table refresh hook
        del params

    #: Mesh the serving runtime committed this head's operands to (the
    #: ServingEngine/DecodeWorker ``mesh=`` knob) — remembered so catalog
    #: swaps and hot reloads re-place the refreshed operand.
    _serve_mesh = None
    _serve_model_axis = "model"

    def place_operands(self, mesh, model_axis: str = "model") -> None:
        """Commit runtime operands to ``mesh``: catalog tries REPLICATE
        (every device needs the full constraint set — the trie is tiny
        next to the tables that actually shard), RetrievalHead row-shards
        its quantized scoring table. Mesh-lowered executables require
        committed operands (aot.sds_tree carries NamedSharding into the
        lowering), so this runs before warmup compiles anything."""
        self._serve_mesh = mesh
        self._serve_model_axis = model_axis
        self._place_trie()

    def _place_trie(self) -> None:
        trie = getattr(self, "trie", None)
        if trie is None or self._serve_mesh is None:
            return
        from jax.sharding import NamedSharding, PartitionSpec

        self.trie = jax.device_put(
            trie, NamedSharding(self._serve_mesh, PartitionSpec())
        )

    def runtime_operands(self) -> tuple:
        """Device-side catalog operands threaded between ``params`` and
        the batch in EVERY compiled call — runtime arguments, never
        closure constants (graftlint's constant_bake rule is the guard).
        Catalog heads return ``(trie,)``; others return ``()``."""
        return ()

    @property
    def catalog_version(self) -> Optional[str]:
        return None

    def set_catalog(self, snapshot) -> None:
        raise NotImplementedError(f"head {self.name!r} has no swappable catalog")

    def validate_snapshot(self, snapshot) -> None:
        raise NotImplementedError(f"head {self.name!r} has no swappable catalog")

    def snapshot_operands(self, snapshot) -> tuple:
        """The runtime-operand tuple ``snapshot`` would install — the
        aval source for the engine's staging path (rung-change detection
        + AOT catalog precompile, engine.stage_catalog). Default: the
        snapshot's device trie, matching every trie-operand head; heads
        whose catalog installs a different operand (NoteLLM's scoring
        bank) override so a bank-rung change is detected and precompiled
        exactly like a trie-rung change."""
        return (snapshot.device_trie(),)

    def validate(self, req) -> None:
        """Reject malformed requests AT SUBMIT TIME, so the error goes to
        the one bad caller — not (via the batch-failure path) to every
        innocent request co-batched with it. Negative ids would silently
        wrap through numpy/jnp indexing; ids past the corpus/vocab are
        silently CLAMPED by jax's out-of-bounds gather — both would make
        the engine answer confidently from the wrong history."""
        h = np.asarray(req.history, np.int64).reshape(-1)
        if h.size and h.min() < 0:
            raise ValueError(f"negative item ids in request history: {h[h < 0][:5]}")
        hi = self.max_item_id()
        if hi is not None and h.size and h.max() > hi:
            raise ValueError(
                f"request history ids exceed the corpus (max valid id {hi}): "
                f"{h[h > hi][:5]}"
            )

    def max_item_id(self):
        """Largest valid history item id, or None when unknown."""
        return None

    def natural_len(self, req) -> int:
        return len(req.history)

    # ---- cross-request prefix cache (paged heads; engine._PagedRunner) ----

    def prefix_key_tokens(self, req, max_history: int):
        """Token-aligned key of the request's EFFECTIVE history — exactly
        what this head's prefill would encode (bucket-clipped to the
        newest ``max_history`` items, dead ids dropped the same way
        make_batch drops them, plus any per-request conditioning like
        TIGER's user token). Two requests with equal keys are guaranteed
        to prefill IDENTICAL page content, which is what makes a
        full-key prefix-cache hit numerically exact. None = this head
        does not participate in the prefix cache."""
        del req, max_history
        return None

    def paged_warm_state(self, init, n_tokens: int, L_bucket: int):
        """Slot-state rows a warm (prefix-cache) admission restores in
        place of running the prefill executable. ``init`` is the donor's
        post-prefill row snapshot (None when prefill leaves state
        zeroed); heads override to patch the few fields that depend on
        the admission-time bucket rather than the history (COBRA's
        ``full`` flag)."""
        del n_tokens, L_bucket
        return init

    def dummy_request(self, length: int = 1):
        from genrec_tpu.serving.types import Request

        return Request(head=self.name, history=np.zeros(length, np.int64))

    def make_batch(self, reqs, B: int, L: int):
        raise NotImplementedError

    def make_fn(self, B: int, L: int):
        raise NotImplementedError

    def finalize(self, outputs, reqs) -> list[dict]:
        raise NotImplementedError


def _clip_history(history, L: int) -> np.ndarray:
    """Newest-L items of a history (the informative tail). Id-range
    checks happen in Head.validate at submit time; the batch path only
    backstops against wrap-around indexing."""
    h = np.asarray(history, np.int64).reshape(-1)
    if len(h) and h.min() < 0:
        raise ValueError(f"negative item ids in request history: {h[h < 0][:5]}")
    return h[-L:] if len(h) > L else h


class _CorpusLookup:
    """sem-id tuple -> corpus item id, for mapping generative beams back
    to servable items. Constrained decoding guarantees every tuple is in
    the corpus; -1 (never expected) would flag a constraint violation.
    The underlying dict is the snapshot's cached ``item_index()`` —
    built once per snapshot, on the staging thread when the catalog is
    hot-swapped."""

    def __init__(self, snapshot):
        self._map = snapshot.item_index()

    def __call__(self, tuples: np.ndarray) -> np.ndarray:
        return np.asarray(
            [self._map.get(tuple(int(c) for c in t), -1) for t in tuples], np.int64
        )


class TigerGenerativeHead(Head):
    """TIGER beam search through the PR-1 KV-cached engine, trie-masked.

    The corpus comes either as a prebuilt ``catalog=`` CatalogSnapshot or
    as a raw ``item_sem_ids`` (N, D) table (wrapped into a snapshot);
    requests carry item ids indexing it. The snapshot's TensorTrie is the
    head's single runtime operand — the compiled executables never bake
    it, so `set_catalog` swaps the corpus without recompiling (same-rung
    snapshots; a rung change is precompiled AOT by the engine's staging
    path). Beam search is deterministic (pure beam, no Gumbel sampling)
    so identical requests get identical answers.
    """

    generative = True
    supports_catalog = True

    def __init__(self, model, item_sem_ids: Optional[np.ndarray] = None,
                 top_k: int = 10, name: str = "tiger", catalog=None):
        self.model = model
        self.name = name
        self.top_k = top_k
        if catalog is None:
            if item_sem_ids is None:
                raise ValueError("need item_sem_ids or catalog=")
            catalog = CatalogSnapshot.build(
                np.asarray(item_sem_ids, np.int64), model.num_item_embeddings
            )
        self.validate_snapshot(catalog)
        self.set_catalog(catalog)

    def validate_snapshot(self, snapshot) -> None:
        if snapshot.depth != self.model.sem_id_dim:
            raise ValueError(
                f"catalog depth {snapshot.depth} != model sem_id_dim "
                f"{self.model.sem_id_dim}"
            )
        if snapshot.codebook_size != self.model.num_item_embeddings:
            raise ValueError(
                f"catalog codebook {snapshot.codebook_size} != model "
                f"num_item_embeddings {self.model.num_item_embeddings}"
            )

    def prepare_snapshot(self, snapshot) -> None:
        """Staging-thread hook (engine.stage_catalog): warm the cached
        device trie + item index so the batcher's set_catalog is pure
        pointer swaps — no host->device upload, no O(N) Python on the
        hot path."""
        snapshot.device_trie()
        snapshot.item_index()

    def set_catalog(self, snapshot) -> None:
        """Swap the whole corpus atomically (called by the engine's
        batcher BETWEEN micro-batches / after slot drain): trie operand,
        id-range validation bound, and the beam -> item-id lookup. All
        derived artifacts are snapshot-cached (prepare_snapshot warms
        them on the staging thread)."""
        self.catalog = snapshot
        self.item_sem_ids = snapshot.item_sem_ids
        self.trie = snapshot.device_trie()
        self._place_trie()  # keep the operand on the serving mesh
        self._lookup = _CorpusLookup(snapshot)

    @property
    def catalog_version(self) -> Optional[str]:
        return self.catalog.version

    def runtime_operands(self) -> tuple:
        return (self.trie,)

    def max_item_id(self):
        return len(self.item_sem_ids) - 1

    def make_batch(self, reqs, B: int, L: int):
        D = self.model.sem_id_dim
        ids = np.zeros((B, L * D), np.int32)
        mask = np.zeros((B, L * D), np.int32)
        user = np.zeros((B,), np.int32)
        for i, r in enumerate(reqs):
            # Items past the live corpus are DROPPED, not indexed:
            # validate() checked ids at submit time, but a hot swap to a
            # SMALLER catalog can land while a request is queued — a
            # removed item simply vanishes from the history instead of
            # IndexError-failing the whole co-batched micro-batch.
            h = _clip_history(r.history, L)
            h = h[h < len(self.item_sem_ids)]
            if len(h):
                ids[i, : len(h) * D] = self.item_sem_ids[h].reshape(-1)
                mask[i, : len(h) * D] = 1
            user[i] = int(r.user_id) % self.model.num_user_embeddings
        types = np.tile(np.arange(D, dtype=np.int32), (B, L))
        return (jnp.asarray(user), jnp.asarray(ids), jnp.asarray(types),
                jnp.asarray(mask))

    def make_fn(self, B: int, L: int):
        from genrec_tpu.models.tiger import tiger_generate

        def fn(params, trie, user, ids, types, mask):
            # The trie is a runtime OPERAND (catalog.TensorTrie pytree),
            # threaded by the engine — never closed over, never baked.
            out = tiger_generate(
                self.model, params, trie, user, ids, types, mask,
                jax.random.key(0), n_top_k_candidates=self.top_k,
                deterministic=True, use_cache=True,
            )
            return out.sem_ids, out.log_probas

        return fn

    def finalize(self, outputs, reqs) -> list[dict]:
        sem_ids, logp = outputs
        return [
            dict(items=self._lookup(sem_ids[i]), scores=np.asarray(logp[i]),
                 sem_ids=np.asarray(sem_ids[i]))
            for i in range(len(reqs))
        ]

    # ---- paged decode protocol ---------------------------------------------

    supports_paged = True
    supports_spec = True

    @property
    def spec_depth(self) -> int:
        # Root level is exact; everything past it is speculated — a
        # fresh slot can finish its whole tuple in one verify call.
        return self.model.sem_id_dim - 1

    def enable_spec_drafting(self) -> None:
        """Runner hook (BEFORE paged_state_zeros / prefill compiles):
        extend the prefill with the step-0 logit window and the slot
        state with its per-slot row — the drafter's root-step signal
        (popularity ranking has no model signal at the root codebook)."""
        self._spec_draft_hint = True

    @property
    def paged_init_step(self) -> int:
        return 0

    @property
    def paged_total_steps(self) -> int:
        return self.model.sem_id_dim

    paged_result_leaves = ("beam_seqs", "beam_logps")

    @property
    def paged_init_leaves(self) -> tuple:
        # The plain prefill leaves the state zeroed; the speculative one
        # hands the drafter its step-0 logit window.
        return ("logits0",) if getattr(self, "_spec_draft_hint", False) else ()

    def paged_layout(self):
        m = self.model
        return m.n_layers // 2, m.num_heads, m.attn_dim // m.num_heads, m.dtype

    def paged_kv_tokens(self, n_items: int, L_bucket: int) -> int:
        # user token + D sem-id tokens per (bucket-clipped) history item
        return 1 + min(int(n_items), L_bucket) * self.model.sem_id_dim

    def paged_state_zeros(self, n_slots: int) -> dict:
        from genrec_tpu.models.tiger import init_tiger_paged_state

        return init_tiger_paged_state(
            self.model, n_slots, self.top_k,
            draft_hint=getattr(self, "_spec_draft_hint", False),
        )

    def make_prefill_paged_fn(self, B: int, L: int):
        from genrec_tpu.models.tiger import tiger_prefill_paged

        del B, L  # shapes come from make_batch/block_tables
        draft_hint = getattr(self, "_spec_draft_hint", False)

        def fn(params, trie, user, ids, types, mask, block_tables,
               k_pools, v_pools):
            # TIGER's plain prefill is trie-free; the operand rides the
            # uniform paged signature (params, *operands, *batch, ...)
            # and jit prunes the unused arg. The SPECULATIVE prefill
            # reads it: the step-0 draft window is trie-masked.
            k_pools, v_pools, _, extras = tiger_prefill_paged(
                self.model, params, user, ids, types, mask, block_tables,
                k_pools, v_pools, trie=trie, draft_hint=draft_hint,
            )
            return k_pools, v_pools, extras

        return fn

    def make_spec_decode_paged_fn(self, fanout: int):
        from genrec_tpu.models.tiger import tiger_spec_tree_step

        def fn(params, trie, state, steps, block_tables, seq_lens,
               k_pools, v_pools):
            # Deterministic beams only — the same serving contract as
            # the plain step; one topology (fanout x spec_depth) per
            # engine rung, compiled at warmup.
            return tiger_spec_tree_step(
                self.model, params, trie, state, steps, block_tables,
                seq_lens, k_pools, v_pools, fanout=fanout,
                depth=self.spec_depth,
            )

        return fn

    def make_decode_paged_fn(self):
        from genrec_tpu.models.tiger import tiger_paged_decode_step

        def fn(params, trie, state, steps, block_tables, seq_lens,
               k_pools, v_pools):
            # Deterministic pure beam (the serving contract: identical
            # requests get identical answers), same as the dense make_fn.
            return tiger_paged_decode_step(
                self.model, params, trie, state, steps, block_tables,
                seq_lens, k_pools, v_pools, rng=None,
            )

        return fn

    def paged_finalize(self, row: dict, req) -> dict:
        sem = np.asarray(row["beam_seqs"])
        return dict(items=self._lookup(sem), scores=np.asarray(row["beam_logps"]),
                    sem_ids=sem)

    def prefix_key_tokens(self, req, max_history: int):
        """TIGER's prefill is user-conditioned (the user token is encoder
        position 0) and the encoder is BIDIRECTIONAL — the cross-attention
        K/V of a history prefix changes when items are appended — so the
        key carries the user id and only a FULL-key match is reusable
        (the engine's one admissible tier anyway)."""
        h = _clip_history(req.history, max_history)
        h = h[h < len(self.item_sem_ids)]  # same drop rule as make_batch
        return (int(req.user_id) % self.model.num_user_embeddings,
                *(int(x) for x in h))


class CobraGenerativeHead(Head):
    """COBRA cached beam search, trie-masked, over a precomputed item tower.

    The sparse side of each history item comes from the catalog's
    ``item_sem_ids`` (N, C); the dense side from per-item vectors, which
    are CATALOG artifacts: either snapshot-held (``item_vecs`` — the
    catalog pipeline precomputed the tower, reused unchanged across
    params-only hot reloads) or encoded HERE from the snapshot's
    ``item_text_tokens``, exactly ONCE per catalog version — a params
    reload with an unchanged catalog keeps the tower (the PR-5 behavior
    of re-encoding the whole corpus on every params reload is retired;
    ``tower_encodes`` counts the real encodes for tests/metrics).
    """

    generative = True
    supports_catalog = True

    def __init__(self, model, item_sem_ids: Optional[np.ndarray] = None,
                 item_vecs: Optional[np.ndarray] = None,
                 item_text_tokens: Optional[np.ndarray] = None,
                 top_k: int = 10, name: str = "cobra", catalog=None):
        self.model = model
        self.name = name
        self.top_k = top_k
        self._encode = None
        self._last_params = None
        self._vecs_version = None  # catalog version the tower was encoded for
        self._prepared_tower = None  # (version, vecs) from prepare_snapshot
        self.tower_encodes = 0
        if catalog is None:
            if item_sem_ids is None:
                raise ValueError("need item_sem_ids or catalog=")
            catalog = CatalogSnapshot.build(
                np.asarray(item_sem_ids, np.int64), model.id_vocab_size,
                item_vecs=item_vecs, item_text_tokens=item_text_tokens,
            )
        self.validate_snapshot(catalog)
        self.set_catalog(catalog)

    def validate_snapshot(self, snapshot) -> None:
        if snapshot.depth != self.model.n_codebooks:
            raise ValueError(
                f"catalog depth {snapshot.depth} != model n_codebooks "
                f"{self.model.n_codebooks}"
            )
        if snapshot.codebook_size != self.model.id_vocab_size:
            raise ValueError(
                f"catalog codebook {snapshot.codebook_size} != model "
                f"id_vocab_size {self.model.id_vocab_size}"
            )
        if snapshot.item_vecs is None and snapshot.item_text_tokens is None:
            raise ValueError(
                "COBRA catalog snapshot needs item_vecs or item_text_tokens "
                "(the dense item tower has to come from somewhere)"
            )
        cur = getattr(self, "item_vecs", None)
        if cur is not None and snapshot.item_vecs is not None and (
            snapshot.item_vecs.shape[-1] != cur.shape[-1]
        ):
            raise ValueError(
                f"snapshot tower dim {snapshot.item_vecs.shape[-1]} != "
                f"serving tower dim {cur.shape[-1]} — batch avals would drift"
            )

    def prepare_snapshot(self, snapshot) -> None:
        """Staging-thread hook (engine.stage_catalog): warm the device
        trie + item index, and encode the dense tower for a TEXT-only
        snapshot BEFORE the swap is staged — the batcher's set_catalog
        is a pure pointer swap; the hot path never compiles, uploads,
        or encodes a corpus."""
        snapshot.device_trie()
        snapshot.item_index()
        if snapshot.item_vecs is not None or self._last_params is None:
            return
        self._prepared_tower = (
            snapshot.version,
            self._encode_text(self._last_params, snapshot),
        )

    def set_catalog(self, snapshot) -> None:
        self.catalog = snapshot
        self.item_sem_ids = snapshot.item_sem_ids
        self.trie = snapshot.device_trie()
        self._place_trie()  # keep the operand on the serving mesh
        self._lookup = _CorpusLookup(snapshot)
        if snapshot.item_vecs is not None:
            # Snapshot-held tower: reused as-is until the NEXT catalog
            # version, including across params-only hot reloads.
            self.item_vecs = np.asarray(snapshot.item_vecs)
            self._vecs_version = snapshot.version
        elif self._prepared_tower is not None and (
            self._prepared_tower[0] == snapshot.version
        ):
            # Tower encoded ahead of time by prepare_snapshot (the
            # engine staging path).
            self.item_vecs = self._prepared_tower[1]
            self._vecs_version = snapshot.version
            self._prepared_tower = None
        elif self._last_params is not None:
            # Direct set_catalog without staging (tests, bootstrap):
            # encode inline — caller's thread, not the hot path.
            self._encode_tower(self._last_params)
        else:
            # Before the first on_params: the engine's start() delivers
            # params to every head before compiling anything.
            self.item_vecs = None
            self._vecs_version = None

    @property
    def catalog_version(self) -> Optional[str]:
        return self.catalog.version

    def runtime_operands(self) -> tuple:
        return (self.trie,)

    def max_item_id(self):
        return len(self.item_sem_ids) - 1

    def on_params(self, params) -> None:
        """Params (re)load hook. The item tower is a CATALOG artifact:
        it re-encodes only when the catalog version actually changed
        (or was never encoded), never on a params-only reload."""
        self._last_params = params
        if self._vecs_version == self.catalog.version:
            return
        self._encode_tower(params)

    def _encode_text(self, params, snapshot) -> np.ndarray:
        """One full-corpus tower encode from ``snapshot``'s item text."""
        from genrec_tpu.models.cobra import Cobra

        if snapshot.item_text_tokens is None:
            raise ValueError(
                f"catalog {snapshot.version} carries no item_vecs and no "
                "item_text_tokens — cannot build the dense item tower"
            )
        if self._encode is None:
            self._encode = jax.jit(
                lambda p, t: self.model.apply(
                    {"params": p}, t, method=Cobra.encode_items
                )
            )
        self.tower_encodes += 1
        return np.asarray(
            self._encode(params, jnp.asarray(snapshot.item_text_tokens))
        )

    def _encode_tower(self, params) -> None:
        self.item_vecs = self._encode_text(params, self.catalog)
        self._vecs_version = self.catalog.version

    def make_batch(self, reqs, B: int, L: int):
        C = self.model.n_codebooks
        d = self.item_vecs.shape[-1]
        ids = np.full((B, L * C), self.model.pad_id, np.int32)
        vecs = np.zeros((B, L, d), self.item_vecs.dtype)
        for i, r in enumerate(reqs):
            # Drop items removed by a shrinking hot swap (see the TIGER
            # make_batch note): never index past the live corpus.
            h = _clip_history(r.history, L)
            h = h[h < len(self.item_sem_ids)]
            if len(h):
                ids[i, : len(h) * C] = self.item_sem_ids[h].reshape(-1)
                vecs[i, : len(h)] = self.item_vecs[h]
        return jnp.asarray(ids), jnp.asarray(vecs)

    def make_fn(self, B: int, L: int):
        from genrec_tpu.models.cobra import cobra_generate

        def fn(params, trie, ids, vecs):
            out = cobra_generate(
                self.model, params, ids, None, n_candidates=self.top_k,
                temperature=1.0, item_vecs=vecs, use_cache=True,
                trie=trie,
            )
            return out.sem_ids, out.scores

        return fn

    def finalize(self, outputs, reqs) -> list[dict]:
        sem_ids, scores = outputs
        return [
            dict(items=self._lookup(sem_ids[i]), scores=np.asarray(scores[i]),
                 sem_ids=np.asarray(sem_ids[i]))
            for i in range(len(reqs))
        ]

    # ---- paged decode protocol ---------------------------------------------

    supports_paged = True
    supports_spec = True

    @property
    def spec_depth(self) -> int:
        # Codebook 0 resolves at prefill; the first suffix step is the
        # exact root, the remaining C-2 codebooks are speculated.
        return max(self.model.n_codebooks - 2, 0)

    def make_spec_decode_paged_fn(self, fanout: int):
        from genrec_tpu.models.cobra import cobra_spec_tree_step

        def fn(params, trie, state, steps, block_tables, seq_lens,
               k_pools, v_pools):
            return cobra_spec_tree_step(
                self.model, params, trie, state, steps, block_tables,
                seq_lens, k_pools, v_pools, fanout=fanout,
                depth=self.spec_depth, temperature=1.0,
            )

        return fn

    @property
    def paged_init_step(self) -> int:
        # Codebook 0 resolves AT PREFILL (the step-0 head reads the
        # history's last dense position); suffix steps cover 1..C-1.
        return 1

    @property
    def paged_total_steps(self) -> int:
        return self.model.n_codebooks

    paged_result_leaves = ("beam_tokens", "beam_scores")
    #: What `cobra_prefill_paged` returns as ``init``.
    paged_init_leaves = ("beam_tokens", "beam_scores", "prefix_idx",
                         "tail_hidden", "full", "base_pos", "h_last")

    def paged_layout(self):
        m = self.model
        return (
            m.decoder_n_layers, m.decoder_num_heads,
            m.d_model // m.decoder_num_heads, m.dtype,
        )

    def paged_kv_tokens(self, n_items: int, L_bucket: int) -> int:
        # C sparse + 1 dense token per (bucket-clipped) history item
        return min(int(n_items), L_bucket) * (self.model.n_codebooks + 1)

    def paged_state_zeros(self, n_slots: int) -> dict:
        from genrec_tpu.models.cobra import init_cobra_paged_state

        return init_cobra_paged_state(self.model, n_slots, self.top_k)

    def make_prefill_paged_fn(self, B: int, L: int):
        from genrec_tpu.models.cobra import cobra_prefill_paged

        del B, L

        def fn(params, trie, ids, vecs, block_tables, k_pools, v_pools):
            # COBRA resolves codebook 0 AT prefill, so the trie operand
            # is live here (unlike TIGER's trie-free prefill).
            return cobra_prefill_paged(
                self.model, params, ids, vecs, block_tables, k_pools, v_pools,
                trie, self.top_k, temperature=1.0,
            )

        return fn

    def make_decode_paged_fn(self):
        from genrec_tpu.models.cobra import cobra_paged_decode_step

        def fn(params, trie, state, steps, block_tables, seq_lens,
               k_pools, v_pools):
            return cobra_paged_decode_step(
                self.model, params, trie, state, steps, block_tables,
                seq_lens, k_pools, v_pools, temperature=1.0,
            )

        return fn

    def paged_finalize(self, row: dict, req) -> dict:
        sem = np.asarray(row["beam_tokens"])
        return dict(items=self._lookup(sem), scores=np.asarray(row["beam_scores"]),
                    sem_ids=sem)

    def prefix_key_tokens(self, req, max_history: int):
        """COBRA keys on the effective item history alone (no user
        conditioning in the decoder input). The decoder is causal, but
        prefill ALSO resolves the codebook-0 beam from the last dense
        position — a grown history needs that head re-run — so, like
        TIGER, only a full-key match is admissible."""
        h = _clip_history(req.history, max_history)
        h = h[h < len(self.item_sem_ids)]  # same drop rule as make_batch
        return tuple(int(x) for x in h)

    def paged_warm_state(self, init, n_tokens: int, L_bucket: int):
        """Everything cobra_prefill_paged returns is bucket-independent
        for the valid positions (causal decoder + pad masking) EXCEPT
        ``full`` — "did the row fill its prefill bucket" — which must be
        judged against the ADMISSION-time bucket (what a cold engine
        serving this request solo would use), not the donor's possibly
        larger co-batched one. The length side comes from the donor's
        ``base_pos`` (prefill's pad-masked n_valid), NOT from
        ``n_tokens``: natural_len counts history ids that make_batch
        DROPS (dead ids after a shrinking catalog swap), and prefill's
        own full flag compared the effective length."""
        del n_tokens
        patched = dict(init)
        patched["full"] = np.asarray(
            int(init["base_pos"]) == L_bucket * (self.model.n_codebooks + 1)
        )
        return patched


class RetrievalHead(Head):
    """SASRec/HSTU: right-aligned history -> last_hidden -> sharded top-k.

    Histories are RIGHT-aligned (newest item in slot L-1, zeros pad the
    left) so the model's last position is the prediction point — the same
    layout the SASRec eval path uses. ``use_timestamps=True`` (HSTU with
    temporal bias) batches each request's timestamps alongside.

    ``quantized=True`` scores against an int8 per-row-quantized copy of
    the tied item-embedding table (the largest operand at catalog scale)
    instead of the fp32 rows in ``params``: ``on_params`` builds the
    ``ops.quant.QuantizedTable`` ONCE per params version and threads it
    as a runtime operand (never a closure constant), and ``item_topk``
    dequantizes at score time with fp32 accumulation. The fp32 table
    stays untouched in ``params`` (it is tied into the input-embedding
    path and the hot-reload aval check).
    """

    def __init__(self, name: str, model, top_k: int = 10,
                 use_timestamps: bool = False, mesh=None,
                 model_axis: str = "model", quantized: bool = False):
        self.name = name
        self.model = model
        self.top_k = top_k
        self.use_timestamps = use_timestamps
        self.mesh = mesh
        self.model_axis = model_axis
        self.quantized = bool(quantized)
        self._qtable = None
        # SASRec/HSTU position tables are sized max_seq_len: a history
        # bucket past it would crash the warmup trace with an opaque
        # broadcast error, so buckets clamp here (the over-long tail is
        # truncated to the newest items, same as the ladder contract).
        self._max_len = int(getattr(model, "max_seq_len", 0)) or None

    def on_params(self, params) -> None:
        """Refresh the quantized scoring table — once per params version
        (start and every hot reload), not per batch."""
        if self.quantized:
            from genrec_tpu.models.embeddings import quantize_item_table

            self._qtable = quantize_item_table(params["item_embedding"])
            self._place_qtable()

    def place_operands(self, mesh, model_axis: str = "model") -> None:
        """Engine/worker mesh knob: adopt the mesh for ``item_topk``'s
        shard_map (when the head wasn't constructed with one) and
        row-shard the quantized table — both int8 data rows and their
        fp32 scales split dim 0 over the model axis, the PR 16 2-leaf
        operand landing sharded in place."""
        super().place_operands(mesh, model_axis)
        if self.mesh is None:
            self.mesh = mesh
            self.model_axis = model_axis
        self._place_qtable()

    def _place_qtable(self) -> None:
        mesh = self._serve_mesh
        if mesh is None or self._qtable is None:
            return
        from jax.sharding import NamedSharding, PartitionSpec as P

        axis = self._serve_model_axis
        qt = self._qtable
        n = mesh.shape.get(axis, 1)
        if n > 1 and qt.data.shape[0] % n == 0:
            spec = type(qt)(P(axis, None), P(axis))
        else:  # non-divisible vocab: replicate, same as param_specs
            spec = type(qt)(P(), P())
        self._qtable = jax.device_put(
            qt, jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), spec)
        )

    def runtime_operands(self) -> tuple:
        if not self.quantized:
            return ()
        if self._qtable is None:
            raise RuntimeError(
                f"head {self.name!r} is quantized but has no table yet; "
                "on_params(params) must run before compilation"
            )
        return (self._qtable,)

    def max_item_id(self):
        return int(self.model.num_items)

    def _clamp(self, L: int) -> int:
        return min(L, self._max_len) if self._max_len else L

    def make_batch(self, reqs, B: int, L: int):
        L = self._clamp(L)
        ids = np.zeros((B, L), np.int32)
        ts = np.zeros((B, L), np.int32) if self.use_timestamps else None
        for i, r in enumerate(reqs):
            h = _clip_history(r.history, L)
            if len(h):
                ids[i, L - len(h):] = h
                if ts is not None and r.timestamps is not None:
                    t = np.asarray(r.timestamps, np.int64).reshape(-1)[-len(h):]
                    ts[i, L - len(t):] = t
        out = (jnp.asarray(ids),)
        if ts is not None:
            out = out + (jnp.asarray(ts),)
        return out

    def make_fn(self, B: int, L: int):
        from genrec_tpu.parallel.shardings import item_topk

        del L  # shapes come from make_batch (same clamp)
        model = self.model

        def fn(params, *rest):
            if self.quantized:  # runtime operand rides ahead of the batch
                table, rest = rest[0], rest[1:]
            else:
                table = params["item_embedding"]
            ids = rest[0]
            if self.use_timestamps:
                h = model.apply(
                    {"params": params}, ids, rest[1], method=type(model).last_hidden
                )
            else:
                h = model.apply(
                    {"params": params}, ids, method=type(model).last_hidden
                )
            return item_topk(
                h.astype(jnp.float32), table, self.top_k,
                mesh=self.mesh, model_axis=self.model_axis,
            )

        return fn

    def finalize(self, outputs, reqs) -> list[dict]:
        scores, items = outputs
        return [
            dict(items=np.asarray(items[i]), scores=np.asarray(scores[i]),
                 sem_ids=None)
            for i in range(len(reqs))
        ]


class LCRecGenerativeHead(Head):
    """LCRec constrained beam search over the extended-vocab LLM.

    Requests carry ITEM ids into the catalog; ``make_batch`` maps each
    history item to its D codebook tokens (``base_vocab + c*K + code``,
    the ``extend_vocab`` layout) and LEFT-pads the prompt — the KV-cached
    decode reads the last position, so the newest item must sit at the
    right edge (models/lcrec.py's HF left-pad convention). Decoding runs
    ``generate_topk_constrained`` with the snapshot's TensorTrie as a
    runtime operand: every emitted tuple is a corpus item, mapped back to
    an item id through ``_CorpusLookup`` exactly like TIGER/COBRA.

    Two paths. The dense bucket path (``make_fn``): one executable a
    (batch, history) bucket runs the whole generate loop over a dense
    cache. The PAGED path (``supports_paged``, for a backbone whose
    mixers are full attention and KDA): the prompt's K and V of the
    full-attention layers go to the engine's page pool, shared by a
    slot's beams; each KDA layer's recurrent state and convolution tails
    live in the slot's row, a beam's own, beside the K and V of its few
    generated tokens (`models/lcrec.lcrec_prefill_paged`,
    `lcrec_paged_decode_step`). Code 0 is resolved AT PREFILL from the
    prompt's last position (COBRA's pattern: ``paged_init_step`` 1), so a
    request takes C - 1 decode steps, one model pass each, none wasted.
    The paged path refuses int8 pages, speculative decode, a mesh and the
    disaggregated hand-off (`paged_check_options`).
    """

    generative = True
    supports_catalog = True

    def __init__(self, model, base_vocab: int, num_codebooks: int,
                 codebook_size: int, item_sem_ids: Optional[np.ndarray] = None,
                 top_k: int = 10, name: str = "lcrec", catalog=None):
        self.model = model
        self.name = name
        self.top_k = top_k
        self.base_vocab = int(base_vocab)
        self.num_codebooks = int(num_codebooks)
        self.codebook_size = int(codebook_size)
        cfg = getattr(model, "cfg", None)
        if cfg is not None and (
            self.base_vocab + self.num_codebooks * self.codebook_size
            > cfg.vocab_size
        ):
            raise ValueError(
                f"codebook region [{self.base_vocab}, "
                f"{self.base_vocab + self.num_codebooks * self.codebook_size})"
                f" exceeds model vocab {cfg.vocab_size}"
            )
        # Position table bound: a prompt is L*C tokens + C decode steps.
        max_pos = int(getattr(cfg, "max_position_embeddings", 0) or 0)
        self._max_len = (
            max(1, (max_pos - self.num_codebooks) // self.num_codebooks)
            if max_pos else None
        )
        if catalog is None:
            if item_sem_ids is None:
                raise ValueError("need item_sem_ids or catalog=")
            catalog = CatalogSnapshot.build(
                np.asarray(item_sem_ids, np.int64), self.codebook_size
            )
        self.validate_snapshot(catalog)
        self.set_catalog(catalog)

    def validate_snapshot(self, snapshot) -> None:
        if snapshot.depth != self.num_codebooks:
            raise ValueError(
                f"catalog depth {snapshot.depth} != head num_codebooks "
                f"{self.num_codebooks}"
            )
        if snapshot.codebook_size != self.codebook_size:
            raise ValueError(
                f"catalog codebook {snapshot.codebook_size} != head "
                f"codebook_size {self.codebook_size}"
            )

    def prepare_snapshot(self, snapshot) -> None:
        snapshot.device_trie()
        snapshot.item_index()

    def set_catalog(self, snapshot) -> None:
        self.catalog = snapshot
        self.item_sem_ids = snapshot.item_sem_ids
        self.trie = snapshot.device_trie()
        self._place_trie()
        self._lookup = _CorpusLookup(snapshot)

    @property
    def catalog_version(self) -> Optional[str]:
        return self.catalog.version

    def runtime_operands(self) -> tuple:
        return (self.trie,)

    def max_item_id(self):
        return len(self.item_sem_ids) - 1

    def _clamp(self, L: int) -> int:
        return min(L, self._max_len) if self._max_len else L

    def make_batch(self, reqs, B: int, L: int):
        L = self._clamp(L)
        C = self.num_codebooks
        tok_base = self.base_vocab + np.arange(C, dtype=np.int64) * self.codebook_size
        ids = np.zeros((B, L * C), np.int32)
        mask = np.zeros((B, L * C), np.int32)
        for i, r in enumerate(reqs):
            # Same shrink-swap drop rule as TIGER: a queued request may
            # reference items a smaller hot-swapped catalog removed.
            h = _clip_history(r.history, L)
            h = h[h < len(self.item_sem_ids)]
            if len(h):
                toks = (self.item_sem_ids[h] + tok_base).reshape(-1)
                ids[i, L * C - len(toks):] = toks
                mask[i, L * C - len(toks):] = 1
        # Degenerate rows (emptied history, B-padding): one attended
        # position keeps the softmax over attention weights finite.
        mask[:, -1] = 1
        return jnp.asarray(ids), jnp.asarray(mask)

    def make_fn(self, B: int, L: int):
        from genrec_tpu.models.lcrec import generate_topk_constrained

        L = self._clamp(L)
        C = self.num_codebooks

        def fn(params, trie, ids, mask):
            out = generate_topk_constrained(
                self.model, params, ids, mask, self.base_vocab, C,
                self.codebook_size, beam_width=self.top_k,
                max_cache=L * C + C, trie=trie,
            )
            return out.sem_ids, out.log_probas

        return fn

    def finalize(self, outputs, reqs) -> list[dict]:
        sem_ids, logp = outputs
        return [
            dict(items=self._lookup(sem_ids[i]), scores=np.asarray(logp[i]),
                 sem_ids=np.asarray(sem_ids[i]))
            for i in range(len(reqs))
        ]

    # ---- paged decode protocol ---------------------------------------------

    @property
    def supports_paged(self) -> bool:
        """Whether every mixer of the backbone has a paged form (a head
        over latent or sparse attention keeps the dense bucket path)."""
        try:
            self._paged_kinds()
        except ValueError:
            return False
        return True

    def paged_check_options(self, *, kv_dtype: str = "float32",
                            spec_decode: bool = False, mesh=None,
                            handoff: bool = False) -> None:
        refused = {
            "kv_dtype='int8'": kv_dtype == "int8",
            "spec_decode": bool(spec_decode),
            "mesh": mesh is not None,
            "the disaggregated hand-off": bool(handoff),
        }
        for option, asked in refused.items():
            if asked:
                raise ValueError(
                    f"head {self.name!r}: the paged LCRec path does not "
                    f"implement {option} (its slot rows hold a recurrent "
                    "state a beam beside the KV pages); serve it with that "
                    "option off, or on the dense bucket path (paged=False)")

    @property
    def paged_init_step(self) -> int:
        return 1  # code 0 resolves at prefill

    @property
    def paged_total_steps(self) -> int:
        return self.num_codebooks

    paged_result_leaves = ("beam_seqs", "beam_logps")
    #: The backbone's counters the prefill hands out beside its rows.
    paged_prefill_counters = ("expert_pairs_per_held_expert",)

    def _paged_kinds(self) -> tuple:
        from genrec_tpu.models.lcrec import paged_layer_kinds

        return paged_layer_kinds(self.model.cfg)

    @property
    def paged_recurrent_leaves(self) -> tuple:
        return tuple(
            f"{leaf}{i}" for i, kind in enumerate(self._paged_kinds())
            if kind == "kda"
            for leaf in ("kda_s", "kda_conv", "kda_s0_", "kda_conv0_"))

    @property
    def paged_init_leaves(self) -> tuple:
        return ("beam_seqs", "beam_logps", "beam_rank") + tuple(
            leaf for leaf in self.paged_recurrent_leaves if "0_" in leaf)

    def paged_layout(self):
        """The layers that HAVE pages: the full-attention ones."""
        cfg = self.model.cfg
        n = sum(kind == "attention" for kind in self._paged_kinds())
        if n == 0:
            raise ValueError(
                f"head {self.name!r}: no full-attention layer, so nothing "
                "to page; serve a pure-recurrent backbone on the dense path")
        return n, cfg.num_key_value_heads, cfg.head_dim, self.model.dtype

    def paged_kv_tokens(self, n_items: int, L_bucket: int) -> int:
        # C codebook tokens an item; an emptied history attends one pad token
        return max(min(int(n_items), self._clamp(L_bucket)) * self.num_codebooks, 1)

    def paged_state_zeros(self, n_slots: int) -> dict:
        from genrec_tpu.models.lcrec import lcrec_paged_state_zeros

        return lcrec_paged_state_zeros(
            self.model, n_slots, self.top_k, self.num_codebooks)

    def make_prefill_paged_fn(self, B: int, L: int):
        from genrec_tpu.models.lcrec import lcrec_prefill_paged

        del B, L  # shapes come from make_batch/block_tables

        def fn(params, trie, ids, mask, block_tables, k_pools, v_pools):
            k_pools, v_pools, init, counters = lcrec_prefill_paged(
                self.model, params, trie, ids, mask, block_tables, k_pools,
                v_pools, self.base_vocab, self.num_codebooks,
                self.codebook_size, self.top_k)
            init.update({k: counters[k] for k in self.paged_prefill_counters
                         if k in counters})
            return k_pools, v_pools, init

        return fn

    def make_decode_paged_fn(self):
        from genrec_tpu.models.lcrec import lcrec_paged_decode_step

        def fn(params, trie, state, steps, block_tables, seq_lens,
               k_pools, v_pools):
            return lcrec_paged_decode_step(
                self.model, params, trie, state, steps, block_tables,
                seq_lens, k_pools, v_pools, self.base_vocab,
                self.codebook_size)

        return fn

    def paged_finalize(self, row: dict, req) -> dict:
        sem = np.asarray(row["beam_seqs"])
        return dict(items=self._lookup(sem), scores=np.asarray(row["beam_logps"]),
                    sem_ids=sem)

    def prefix_key_tokens(self, req, max_history: int):
        """The effective item history alone (no user conditioning). The
        layers are causal, but the prefill also opens the beams from the
        last position and ends the recurrent states there: a grown history
        needs both again, so only a full-key match is admissible (an
        incremental prefill FROM the snapshot is ROADMAP M7)."""
        h = _clip_history(req.history, self._clamp(max_history))
        h = h[h < len(self.item_sem_ids)]  # same drop rule as make_batch
        return tuple(int(x) for x in h)


class NoteLLMRetrievalHead(Head):
    """NoteLLM Query2Embedding retrieval: ``[EMB]`` hidden -> item top-k.

    Requests carry query TOKEN ids (``Request.history`` is the tokenized
    query); ``make_batch`` appends the ``[EMB]`` special token after the
    clipped query and the compiled fn reads its L2-normalized hidden
    state (``query2embedding_forward``), then scores it against the
    catalog's precomputed item-note embeddings through the same sharded
    ``item_topk`` path the SASRec/HSTU heads use.

    The item bank is a CATALOG artifact and a RUNTIME OPERAND: snapshot
    ``item_vecs`` (N, d) padded to a ``capacity_for`` rung as an
    AUGMENTED (cap, d+1) fp32 table — row i+1 carries item i plus a bias
    column of 0, pad rows carry a -1e9 bias, and the query side appends a
    1 — so pad rows can never win top-k through the UNCHANGED item_topk
    kernel, and same-rung catalog swaps are pure operand changes (a rung
    change is AOT-precompiled by the engine staging path via
    ``snapshot_operands``). Row 0 is the pad row item_topk always masks;
    returned row r maps to item r-1.
    """

    supports_catalog = True

    #: Bias given to pad rows (and earned by none of the real rows, whose
    #: scores are cosine-bounded): a pad row can never reach the top-k.
    _PAD_BIAS = -1e9

    def __init__(self, model, emb_token_id: int,
                 item_sem_ids: Optional[np.ndarray] = None,
                 item_vecs: Optional[np.ndarray] = None,
                 codebook_size: Optional[int] = None,
                 top_k: int = 10, name: str = "notellm", catalog=None,
                 mesh=None, model_axis: str = "model"):
        self.model = model
        self.name = name
        self.top_k = top_k
        self.emb_token_id = int(emb_token_id)
        self.mesh = mesh
        self.model_axis = model_axis
        self._bank = None          # live augmented device bank
        self._bank_cache: dict = {}  # version -> augmented bank (staging)
        cfg = getattr(model, "cfg", None)
        max_pos = int(getattr(cfg, "max_position_embeddings", 0) or 0)
        self._max_len = max(1, max_pos - 1) if max_pos else None
        if catalog is None:
            if item_sem_ids is None or item_vecs is None:
                raise ValueError("need (item_sem_ids, item_vecs) or catalog=")
            item_sem_ids = np.asarray(item_sem_ids, np.int64)
            if codebook_size is None:
                codebook_size = int(item_sem_ids.max()) + 1
            catalog = CatalogSnapshot.build(
                item_sem_ids, codebook_size, item_vecs=np.asarray(item_vecs)
            )
        self.validate_snapshot(catalog)
        self.set_catalog(catalog)

    def validate_snapshot(self, snapshot) -> None:
        if snapshot.item_vecs is None:
            raise ValueError(
                "NoteLLM catalog snapshot needs item_vecs (the precomputed "
                "item-note embeddings — the retrieval bank has to come from "
                "somewhere)"
            )
        cfg = getattr(self.model, "cfg", None)
        d = int(snapshot.item_vecs.shape[-1])
        if cfg is not None and d != cfg.hidden_size:
            raise ValueError(
                f"snapshot item_vecs dim {d} != model hidden_size "
                f"{cfg.hidden_size}"
            )
        cur = getattr(self, "catalog", None)
        if cur is not None and d != int(cur.item_vecs.shape[-1]):
            raise ValueError(
                f"snapshot item_vecs dim {d} != serving bank dim "
                f"{int(cur.item_vecs.shape[-1])} — operand avals would drift"
            )

    def _augmented_bank(self, snapshot) -> np.ndarray:
        """(cap, d+1) fp32: row i+1 = [item_vecs[i], 0]; row 0 (the pad
        row item_topk masks) and capacity-padding rows get the -1e9 bias
        column. ``capacity_for`` rungs keep the aval stable across
        same-size snapshots."""
        from genrec_tpu.catalog.tensor_trie import capacity_for

        vecs = np.asarray(snapshot.item_vecs, np.float32)
        n, d = vecs.shape
        cap = capacity_for(n + 1)
        bank = np.zeros((cap, d + 1), np.float32)
        bank[1:n + 1, :d] = vecs
        bank[0, d] = self._PAD_BIAS
        bank[n + 1:, d] = self._PAD_BIAS
        return bank

    def prepare_snapshot(self, snapshot) -> None:
        """Staging-thread hook: build + upload the augmented bank ahead
        of the swap, so set_catalog is a pointer swap on the batcher."""
        snapshot.device_trie()
        if snapshot.version not in self._bank_cache:
            self._bank_cache[snapshot.version] = jnp.asarray(
                self._augmented_bank(snapshot)
            )

    def snapshot_operands(self, snapshot) -> tuple:
        """The engine's staging aval source: the bank this snapshot would
        install (NOT the trie — a bank-rung change must be detected and
        precompiled even when the trie rung is unchanged)."""
        self.prepare_snapshot(snapshot)
        return (self._bank_cache[snapshot.version],)

    def set_catalog(self, snapshot) -> None:
        self.catalog = snapshot
        bank = self._bank_cache.get(snapshot.version)
        if bank is None:
            bank = jnp.asarray(self._augmented_bank(snapshot))
        self._bank = bank
        self._bank_cache = {snapshot.version: bank}
        self._place_bank()

    def place_operands(self, mesh, model_axis: str = "model") -> None:
        super().place_operands(mesh, model_axis)
        if self.mesh is None:
            self.mesh = mesh
            self.model_axis = model_axis
        self._place_bank()

    def _place_bank(self) -> None:
        if self._bank is None or self._serve_mesh is None:
            return
        from jax.sharding import NamedSharding, PartitionSpec

        # Replicated, like the trie: item_topk's shard_map re-partitions
        # the rows itself when the mesh path is taken.
        self._bank = jax.device_put(
            self._bank, NamedSharding(self._serve_mesh, PartitionSpec())
        )

    @property
    def catalog_version(self) -> Optional[str]:
        return self.catalog.version

    def runtime_operands(self) -> tuple:
        return (self._bank,)

    def max_item_id(self):
        # History ids are query TOKEN ids: anything below the [EMB]
        # token (appended by make_batch, never by the caller) is legal.
        return self.emb_token_id - 1

    def _clamp(self, L: int) -> int:
        return min(L, self._max_len) if self._max_len else L

    def make_batch(self, reqs, B: int, L: int):
        L = self._clamp(L)
        ids = np.zeros((B, L + 1), np.int32)
        mask = np.zeros((B, L + 1), np.int32)
        emb_idx = np.zeros((B, 1), np.int32)
        for i, r in enumerate(reqs):
            h = _clip_history(r.history, L)
            ids[i, :len(h)] = h
            ids[i, len(h)] = self.emb_token_id
            mask[i, :len(h) + 1] = 1
            emb_idx[i, 0] = len(h)
        # B-padding rows keep their defaults: [EMB] at position 0 with
        # mask zeroed elsewhere — ids[i, 0] must still be the token the
        # row reads, so stamp it for the unfilled rows too.
        for i in range(len(reqs), B):
            ids[i, 0] = self.emb_token_id
            mask[i, 0] = 1
        return jnp.asarray(ids), jnp.asarray(mask), jnp.asarray(emb_idx)

    def make_fn(self, B: int, L: int):
        from genrec_tpu.models.notellm import query2embedding_forward
        from genrec_tpu.parallel.shardings import item_topk

        del B, L  # shapes come from make_batch (same clamp)

        def fn(params, bank, ids, mask, emb_idx):
            out = query2embedding_forward(
                self.model, params, ids, mask, emb_idx,
                tau=jnp.float32(0.0), return_loss=False,
            )
            emb = out.sentence_embedding  # (B, d) fp32, L2-normalized
            ones = jnp.ones((emb.shape[0], 1), emb.dtype)
            return item_topk(
                jnp.concatenate([emb, ones], axis=1), bank, self.top_k,
                mesh=self.mesh, model_axis=self.model_axis,
            )

        return fn

    def finalize(self, outputs, reqs) -> list[dict]:
        scores, rows = outputs
        out = []
        for i in range(len(reqs)):
            s = np.asarray(scores[i])
            r = np.asarray(rows[i])
            # Rows that only the pad bias could fill (top_k > n_items)
            # report item -1, never a phantom id.
            items = np.where(s < self._PAD_BIAS / 2, -1, r - 1)
            out.append(dict(items=items, scores=s, sem_ids=None))
        return out


# ---------------------------------------------------------------------------
# graftlint compile manifest (scripts/graftlint.py, docs/ANALYSIS.md)
# ---------------------------------------------------------------------------

from genrec_tpu.analysis.manifest import BuiltEntry, register_entry


def _tiny_tiger_head():
    """CI-shape TIGER head + params for the serving manifest entries."""
    from genrec_tpu.models.tiger import Tiger

    rng = np.random.default_rng(7)
    valid = np.unique(rng.integers(0, 8, (20, 3)), axis=0)
    model = Tiger(embedding_dim=16, attn_dim=32, dropout=0.0, num_heads=4,
                  n_layers=2, num_item_embeddings=8, num_user_embeddings=20,
                  sem_id_dim=3, max_pos=64)
    B, L, D = 2, 4, 3
    params = model.init(
        jax.random.key(0), jnp.zeros((B,), jnp.int32),
        jnp.zeros((B, L * D), jnp.int32), jnp.zeros((B, L * D), jnp.int32),
        jnp.zeros((B, D), jnp.int32), jnp.zeros((B, D), jnp.int32),
        jnp.ones((B, L * D), jnp.int32),
    )["params"]
    return TigerGenerativeHead(model, valid, top_k=4), params, B, L


@register_entry("serve/tiger_generate_dense", tags=("serving", "generative"))
def _graftlint_dense_entry() -> BuiltEntry:
    """The dense whole-generate executable, jitted exactly like
    ServingEngine._compile: (params, trie-operand, *batch). The trie is a
    catalog.TensorTrie RUNTIME OPERAND — the debt this entry used to
    baseline (dense legality tables baked as pred[64,8] literals) is
    retired, and the tight 256 B threshold now ASSERTS no catalog-sized
    literal creeps back in (at CI shapes the old bake was 512 B, so the
    threshold still bites — the same self-test discipline as the
    check_*_hlo regexes)."""
    head, params, B, L = _tiny_tiger_head()
    fn = jax.jit(head.make_fn(B, L))
    args = (params, *head.runtime_operands(),
            *head.make_batch([head.dummy_request()], B, L))
    return BuiltEntry(fn=fn, args=args, max_const_bytes=256)


@register_entry("serve/tiger_paged_decode_step", tags=("serving", "paged"))
def _graftlint_paged_decode_entry() -> BuiltEntry:
    """The collapsed-shape paged decode step, jitted like
    serving/slots.SlotTable.compile on TPU (donation on; production only
    disables it on CPU to silence the no-op warning). The slot-state
    operand is replaced by the step's output every step — undonated it
    would double-buffer the whole slot table. The trie rides as a
    runtime operand at argnum 1 (catalog.TensorTrie) — NOT donated, it
    survives across every step — and the 256 B constant threshold now
    asserts the old baked-table debt stays retired."""
    from genrec_tpu.serving.aot import paged_decode_donate_argnums
    from genrec_tpu.serving.kv_pool import KVPagePool, PagedConfig

    head, params, _B, _L = _tiny_tiger_head()
    cfg = PagedConfig(max_slots=4, page_size=8, pages_per_slot=2)
    pool = KVPagePool(cfg, *head.paged_layout())
    S = cfg.max_slots
    state = head.paged_state_zeros(S)
    # Same donate argnums production compiles (engine shares the
    # function); donation is requested unconditionally here because the
    # audit reads the declaration, which CPU lowering preserves.
    fn = jax.jit(head.make_decode_paged_fn(),
                 donate_argnums=paged_decode_donate_argnums(
                     len(head.runtime_operands())))
    args = (
        params, *head.runtime_operands(), state,
        jnp.zeros((S,), jnp.int32),
        jnp.zeros((S, cfg.pages_per_slot), jnp.int32),
        jnp.zeros((S,), jnp.int32),
        pool.k_pools, pool.v_pools,
    )
    # expect_donated stays a LITERAL, independent of the shared function:
    # it states which buffers are dead (a fact about step(), which keeps
    # the output in the input's place: params 0, trie 1, slot state 2),
    # so a paged_decode_donate_argnums that stops naming the state fails
    # the audit instead of both sides silently agreeing on "no donation".
    return BuiltEntry(fn=fn, args=args, expect_donated=(2,),
                      max_const_bytes=256)
