"""TensorTrie: the legal-item trie as a device-resident RUNTIME OPERAND.

The `ops/trie` representations (DenseTrie boolean tables, PackedTrie
sorted-key arrays) are correct and fast — but every serving executable
that closes over one bakes the tables in as XLA literals: a catalog
change recompiles every bucket, executable size scales with the corpus,
and graftlint's `constant_bake` rule carried the debt as two baseline
suppressions. "Vectorizing the Trie" (PAPERS.md, arxiv 2602.22647) gives
the fix: flatten the trie into plain int32 tensors and pass them as
runtime ARGUMENTS, with gather/segment ops replacing pointer chasing, so
ONE compiled executable serves any catalog snapshot.

Encoding — a rank-based child CSR, one row per depth:

- ``keys``    (D, C) int32 — step t's sorted unique ``parent_rank * K +
  code`` pairs (the CSR values, parent recoverable as ``key // K``),
  padded to the static capacity C with ``PAD_KEY`` (int32 max, sorting
  above every real key so binary search ignores the padding);
- ``offsets`` (D, C+1) int32 — the CSR row index: node p's children at
  step t occupy ``keys[t, offsets[t, p]:offsets[t, p+1]]``. Derived
  from ``keys`` at build time; the legal mask and the draft weights
  read a node's children through it (``_children``), and ``n_nodes``
  per step is ``offsets[t, -1]``.

A prefix is represented by its RANK among the sorted valid prefixes of
that length (exactly PackedTrie's representation, so the two agree
rank-for-rank along every valid path); the dead-prefix sentinel is the
static capacity C, which has no children and whose candidate keys
exceed every storable key. ``legal_mask`` reads each prefix node's
child segment (at most K keys from ``offsets[t, p]``: one gather of
nodes x K values, no search per candidate code); ``advance`` is one
``searchsorted`` probe a node. No host sync, no Python loops, and the
ragged variants index the PER-ROW step directly (``keys[steps, ...]``)
instead of the compute-all-depths row-select the heterogeneous-shape
tries need.

Capacity ladder: C is padded UP to a static rung (geometric, x4 from
``MIN_CAPACITY``) so catalog snapshots of similar size share an aval —
swapping them into a compiled executable is a pure operand change.
Growth past a rung changes the aval and is the ONLY recompile, done AOT
on the serving engine's staging thread (serving/catalog.py).

TensorTrie is registered as a jax pytree (arrays are children,
``codebook_size`` is static aux data), so it can be passed straight
through ``jax.jit`` boundaries, lowered from ShapeDtypeStructs, and
duck-types the DenseTrie/PackedTrie interface (``legal_mask`` /
``advance`` / ``depth`` / ``codebook_size``) everywhere the models
already take a ``trie`` argument.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: Padding key: int32 max sorts above every real key (< (C+1) * K, checked
#: at build), so searchsorted over a padded row never lands on padding,
#: and no segment reaches it (every ``offsets`` entry is <= n_keys).
PAD_KEY = np.iinfo(np.int32).max

#: Smallest capacity rung. Rungs grow geometrically (x4): snapshots whose
#: node counts land in the same rung share an executable.
MIN_CAPACITY = 64
CAPACITY_GROWTH = 4


def capacity_for(n_nodes: int) -> int:
    """The static capacity rung covering ``n_nodes`` trie nodes."""
    c = MIN_CAPACITY
    while c < n_nodes:
        c *= CAPACITY_GROWTH
    return c


def _per_row(steps: jax.Array, prefix_idx: jax.Array) -> jax.Array:
    """(S,) steps shaped to broadcast against (S, ...) prefixes."""
    return steps.reshape(steps.shape + (1,) * (prefix_idx.ndim - 1))


@jax.tree_util.register_pytree_node_class
class TensorTrie:
    """Flat tensor trie over sem-id tuples of depth D, codebook size K.

    ``keys``/``offsets`` may be numpy arrays, jax arrays, tracers, or
    ShapeDtypeStructs — the same object flows from the snapshot builder
    through ``jax.jit`` lowering into the compiled call.
    """

    def __init__(self, keys, offsets, codebook_size: int, weights=None):
        self.keys = keys          # (D, C) int32, per-row sorted, PAD_KEY-padded
        self.offsets = offsets    # (D, C+1) int32 CSR row index
        self.codebook_size = int(codebook_size)
        # Per-node draft weight, aligned with ``keys``: by default the
        # number of complete legal tuples below each node (leaf counts —
        # the corpus-popularity signal the speculative drafter ranks
        # trie-legal children by, ops/spec_tree.py). ``build`` can
        # aggregate per-item scores instead (e.g. retrieval-head item
        # scores mapped through the corpus index). Zeros when the
        # builder has no signal: the drafter then ranks by code order.
        if weights is None:
            weights = np.zeros(np.shape(keys), np.float32)
        self.weights = weights    # (D, C) float32, 0 on padding rows

    # -- pytree protocol (arrays are leaves, K is static) --------------------

    def tree_flatten(self):
        return (self.keys, self.offsets, self.weights), (self.codebook_size,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        keys, offsets, weights = children
        return cls(keys, offsets, aux[0], weights)

    @property
    def depth(self) -> int:
        return int(self.keys.shape[0])

    @property
    def capacity(self) -> int:
        return int(self.keys.shape[1])

    # -- build ---------------------------------------------------------------

    @classmethod
    def build(cls, valid_ids: np.ndarray, codebook_size: int,
              capacity: int | None = None,
              item_weights: np.ndarray | None = None) -> "TensorTrie":
        """Flatten (N, D) legal tuples into the padded runtime encoding.

        ``capacity`` pins an explicit rung (it must cover the widest
        step); by default the smallest ladder rung covering the catalog
        is used, so same-rung snapshots share executables.

        ``item_weights`` (N,) optionally scores each tuple (e.g. a
        retrieval head's item scores through the corpus index); each
        trie node's draft weight is the SUM over the tuples below it.
        Default: every tuple weighs 1, so node weight == leaf count
        (corpus popularity), the zero-cost drafter signal.
        """
        valid_ids = np.asarray(valid_ids, np.int64)
        if valid_ids.ndim != 2 or valid_ids.size == 0:
            raise ValueError(f"need a (N, D) tuple table, got {valid_ids.shape}")
        N, D = valid_ids.shape
        K = int(codebook_size)
        if valid_ids.min() < 0 or valid_ids.max() >= K:
            raise ValueError(f"sem-id codes outside [0, {K})")
        w_items = (
            np.ones(N, np.float64) if item_weights is None
            else np.asarray(item_weights, np.float64).reshape(N)
        )
        step_keys, step_weights = [], []
        rank = np.zeros(N, np.int64)
        for t in range(D):
            k = rank * K + valid_ids[:, t]
            uniq = np.unique(k)
            step_keys.append(uniq)
            rank = np.searchsorted(uniq, k)
            # Node weight = sum of item weights below the node (leaf
            # count under the default all-ones weighting).
            step_weights.append(
                np.bincount(rank, weights=w_items, minlength=len(uniq))
            )
        n_max = max(len(u) for u in step_keys)
        C = capacity_for(n_max) if capacity is None else int(capacity)
        if C < n_max:
            raise ValueError(f"capacity {C} < {n_max} trie nodes at the widest step")
        # The dead-prefix sentinel C must still produce int32 candidate
        # keys below PAD_KEY: (C + 1) * K is the largest candidate formed.
        if (C + 1) * K > PAD_KEY:
            max_c = PAD_KEY // K - 1
            rung = MIN_CAPACITY
            while rung * CAPACITY_GROWTH <= max_c:
                rung *= CAPACITY_GROWTH
            raise ValueError(
                f"capacity {C} x codebook {K} overflows int32 keys: the "
                f"largest candidate key (C + 1) * K = {(C + 1) * K} exceeds "
                f"PAD_KEY = {PAD_KEY}. The largest usable capacity for this "
                f"codebook is {max_c} (ladder rung {rung}); rebuild with "
                f"capacity <= {rung} (which must still cover the widest "
                "step), shrink the catalog snapshot, or wait for wider "
                "(int64) trie keys — tracked on the ROADMAP."
            )
        keys = np.full((D, C), PAD_KEY, np.int32)
        offsets = np.zeros((D, C + 1), np.int32)
        weights = np.zeros((D, C), np.float32)
        for t, uniq in enumerate(step_keys):
            keys[t, : len(uniq)] = uniq
            weights[t, : len(uniq)] = step_weights[t]
            # CSR row starts: node p's children begin where key p*K would
            # insert. Rows past the real node count collapse to empty
            # segments at n_keys (PAD_KEY sorts above every probe).
            offsets[t] = np.searchsorted(uniq, np.arange(C + 1) * K)
        return cls(keys, offsets, K, weights)

    def device(self) -> "TensorTrie":
        """The same trie with its tensors as jax device arrays."""
        return TensorTrie(
            jnp.asarray(self.keys), jnp.asarray(self.offsets),
            self.codebook_size, jnp.asarray(self.weights),
        )

    def n_nodes(self) -> list[int]:
        """Real (unpadded) node count per step — build-time stats only."""
        return [int(np.asarray(self.offsets[t, -1])) for t in range(self.depth)]

    # -- the decode-loop interface (DenseTrie/PackedTrie-compatible) ---------

    def legal_mask(self, prefix_idx: jax.Array, step: int) -> jax.Array:
        """prefix_idx: (...,) ranks -> (..., K) bool of legal next codes."""
        with jax.named_scope("trie_legal_mask"):
            _, hit = self._children(prefix_idx, step)
            return hit.any(axis=-2)

    def advance(self, prefix_idx: jax.Array, token: jax.Array, step: int) -> jax.Array:
        """Rank of the extended prefix; dead/illegal -> sentinel capacity."""
        return self._advance_row(self.keys[step], prefix_idx, token)

    def legal_mask_ragged(self, prefix_idx: jax.Array, steps: jax.Array) -> jax.Array:
        """Per-row step operand: prefix_idx (S, ...) + steps (S,) ->
        (S, ..., K). The uniform (D, C) layout lets ``steps`` index the
        tensors directly, in place of the compute-all-depths select that
        `ops/trie.legal_mask_ragged` needs for heterogeneous tables."""
        with jax.named_scope("trie_legal_mask_ragged"):
            _, hit = self._children(prefix_idx, _per_row(steps, prefix_idx))
            return hit.any(axis=-2)

    def advance_ragged(self, prefix_idx: jax.Array, token: jax.Array,
                       steps: jax.Array) -> jax.Array:
        with jax.named_scope("trie_advance_ragged"):
            row_keys = self.keys[steps]
            return jax.vmap(self._advance_row)(row_keys, prefix_idx, token)

    def child_weights_ragged(self, prefix_idx: jax.Array,
                             steps: jax.Array) -> jax.Array:
        """Draft weight of every candidate child code, per-row step:
        prefix_idx (S, ...) + steps (S,) -> (S, ..., K) float32 — the
        node weight of the extended prefix where it is legal, 0 where it
        is not (the speculative drafter masks illegal codes itself).
        The same segment read as `legal_mask_ragged`, plus the weights
        at the children's positions."""
        with jax.named_scope("trie_child_weights_ragged"):
            step = _per_row(steps, prefix_idx)
            pos, hit = self._children(prefix_idx, step)
            w = jnp.asarray(self.weights)[step[..., None], pos]  # (S, ..., K)
            # A code has at most one child, so the sum holds one term.
            return jnp.where(hit, w[..., None], 0.0).sum(axis=-2)

    # -- a node's children, through the CSR offsets --------------------------

    def _children(self, prefix_idx: jax.Array, step):
        """The child segment of every prefix node: ``step`` is a static
        int or an int32 array that broadcasts against ``prefix_idx``.
        Returns ``pos`` (..., K) int32, positions in the step's key row,
        and ``hit`` (..., K, K) bool: ``hit[..., j, c]`` is True where
        the node has a j-th child AND that child carries code c (a node
        has at most K children, one a code). A rank outside [0, C) — the
        dead sentinel C, a free slot's leftover — has no children.

        The K positions are computed indices, clipped to the row: a
        K-wide slice from ``lo`` would clamp its START near the row's
        end and shift silently. Which of them are the node's is decided
        by ``j < n``, never by the key's value."""
        K, C = self.codebook_size, self.capacity
        keys, offsets = jnp.asarray(self.keys), jnp.asarray(self.offsets)
        live = (prefix_idx >= 0) & (prefix_idx < C)
        p = jnp.where(live, prefix_idx, 0)
        lo = offsets[step, p]
        n = jnp.where(live, offsets[step, p + 1] - lo, 0)
        j = jnp.arange(K, dtype=jnp.int32)
        pos = jnp.minimum(lo[..., None] + j, C - 1)
        code = keys[jnp.expand_dims(step, -1), pos] - p[..., None] * K
        hit = (j < n[..., None])[..., None] & (code[..., None] == j)
        return pos, hit

    def _advance_row(self, row_keys: jax.Array, prefix_idx: jax.Array,
                     token: jax.Array) -> jax.Array:
        C = row_keys.shape[0]
        key = prefix_idx * self.codebook_size + token
        pos = jnp.clip(jnp.searchsorted(row_keys, key), 0, C - 1)
        return jnp.where(row_keys[pos] == key, pos, C).astype(jnp.int32)

    # -- misc ----------------------------------------------------------------

    def aval_signature(self) -> tuple:
        """The shape/dtype facts that decide executable compatibility: a
        snapshot whose trie matches this signature swaps into a compiled
        executable as a pure operand change (no recompile)."""
        return (
            tuple(int(s) for s in self.keys.shape),
            tuple(int(s) for s in self.offsets.shape),
            tuple(int(s) for s in self.weights.shape),
            self.codebook_size,
        )

    def __repr__(self) -> str:  # pragma: no cover - debugging nicety
        return (
            f"TensorTrie(depth={self.keys.shape[0]}, "
            f"capacity={self.keys.shape[1]}, K={self.codebook_size})"
        )
