"""int8 symmetric quantization containers for the serving memory path.

Every resident byte of the decode path is a param operand, a KV page, or
a handoff payload; quantizing them is the serving-density lever (half
the page bytes ~= double the resident streams at a fixed HBM budget,
and a 2-4x smaller disagg wire payload — the compact-KV movement that
makes disaggregated prefill/decode cheap, cf. TPLA, arxiv 2508.15881).

Two containers, both REGISTERED PYTREES so they flow through every
existing compile/donate/ledger surface unchanged:

- ``QuantizedKVPool``: one decode layer's K or V page pool as int8
  ``data`` (num_pages, page_size, heads * head_dim) plus fp32 per-
  page-row ``scale`` (num_pages, page_size) — one scale per resident
  token position, reduced over the merged feature axis. Page granularity
  means a COW page share carries its scales for free (they live at the
  same page index), and the disagg gather/scatter moves (data, scale)
  rows together.
- ``QuantizedTable``: a 2-D parameter table (e.g. a retrieval head's
  item-embedding matrix) as int8 ``data`` (V, d) plus fp32 per-row
  ``scale`` (V,) — dequant-at-score keeps fp32 accumulation while the
  resident operand is one byte per element.

Being pytrees is the whole trick: ``serving.aot.sds_tree`` (tree_map)
turns them into ShapeDtypeStruct skeletons for AOT lowering,
``obs.memory.tree_nbytes`` (tree_leaves) prices them at their REAL
bytes (int8 data + fp32 scale) for the HBM ledger, and jit donation
donates both leaves — no signature changes anywhere pools or tables
already flow. ``tree_unflatten`` must therefore accept arbitrary leaf
types (SDS, tracers) without validation.

Quantization is symmetric: ``scale = max|x| / 127`` per row (clamped
away from zero so all-zero rows round-trip to exact zeros), ``data =
round(x / scale)`` clipped to [-127, 127], dequant ``data * scale`` in
fp32. The dequant happens AFTER the gather/slice in every consumer so
no fp32 upcast of a whole pool is ever materialized (pinned by
scripts/check_quant_hlo.py against the optimized HLO).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Smallest admissible scale: keeps x / scale finite for all-zero rows
# (they quantize to zeros and dequantize to exact zeros).
_EPS = 1e-12

KV_DTYPES = ("float32", "int8")


def quantize_symmetric(x: jax.Array, reduce_axes) -> tuple[jax.Array, jax.Array]:
    """int8-quantize ``x`` with one scale per kept index.

    ``reduce_axes``: the axes folded into each scale (``(-1,)`` for
    per-token KV rows over the merged heads * head_dim axis and for
    per-row table quantization). Returns (data int8, scale fp32) where scale's
    shape is ``x`` with the reduced axes removed.
    """
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=reduce_axes)
    scale = jnp.maximum(amax, _EPS) / 127.0
    expand = jnp.expand_dims(scale, reduce_axes)
    data = jnp.clip(jnp.round(x / expand), -127, 127).astype(jnp.int8)
    return data, scale


@jax.tree_util.register_pytree_node_class
class QuantizedKVPool:
    """One layer's K or V page pool, int8 data + per-page-row scales.

    Drop-in pytree replacement for the ``(P, page, H*hd)`` pool array
    inside ``KVPagePool.k_pools`` / ``v_pools``; ``ops/paged.py``
    dispatches on it (quantize on write, dequant after gather / inside
    the Pallas kernel). Leaves: ``data`` int8 (P, page, H*hd),
    ``scale`` fp32 (P, page).
    """

    __slots__ = ("data", "scale")

    def __init__(self, data, scale):
        self.data = data
        self.scale = scale

    def tree_flatten(self):
        return (self.data, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        # No validation: leaves may be ShapeDtypeStructs (AOT lowering),
        # tracers (inside jit), or donated buffers.
        return cls(*children)

    @classmethod
    def zeros(cls, shape) -> "QuantizedKVPool":
        """Fresh all-zero pool of geometry ``shape`` = (P, page, H*hd).
        Scales init to 1 so a never-written page dequantizes to zeros
        (page 0, the reserved null page, is read masked anyway)."""
        return cls(
            jnp.zeros(shape, jnp.int8),
            jnp.ones(shape[:2], jnp.float32),
        )

    # -- geometry mirrors (the few array attributes pool consumers read)
    @property
    def shape(self):
        return self.data.shape

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def nbytes(self) -> int:
        return int(self.data.size * self.data.dtype.itemsize
                   + self.scale.size * self.scale.dtype.itemsize)

    def dequantize(self) -> jax.Array:
        """Full fp32 pool — test/debug only; runtime consumers dequant
        AFTER gathering (see module docstring)."""
        return self.data.astype(jnp.float32) * self.scale[:, :, None]

    # -- row movement (disagg transport gather/scatter, COW shares) ----
    def take_rows(self, pages: jax.Array) -> tuple[jax.Array, jax.Array]:
        """(data[pages], scale[pages]) — the wire payload of a page run."""
        return self.data[pages], self.scale[pages]

    def put_rows(self, pages: jax.Array, data: jax.Array,
                 scale: jax.Array) -> "QuantizedKVPool":
        """Functional scatter of quantized rows (and their scales) into
        ``pages`` — the receiving side of a serialized handoff."""
        return QuantizedKVPool(
            self.data.at[pages].set(data.astype(jnp.int8)),
            self.scale.at[pages].set(scale.astype(jnp.float32)),
        )

    def __repr__(self):
        return f"QuantizedKVPool(data={self.data!r}, scale={self.scale!r})"


@jax.tree_util.register_pytree_node_class
class QuantizedTable:
    """A 2-D table as int8 ``data`` (V, d) + fp32 per-row ``scale`` (V,).

    The retrieval heads' item-embedding operand: built once per catalog
    / params version (``from_array``), scored via dequant-at-score in
    ``parallel.shardings.item_topk`` (``(h @ data.T) * scale`` — exactly
    ``h @ (data * scale[:, None]).T`` in fp32).
    """

    __slots__ = ("data", "scale")

    def __init__(self, data, scale):
        self.data = data
        self.scale = scale

    def tree_flatten(self):
        return (self.data, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @classmethod
    def from_array(cls, table) -> "QuantizedTable":
        """Quantize a (V, d) fp table per-row (symmetric int8)."""
        data, scale = quantize_symmetric(jnp.asarray(table), (-1,))
        return cls(data, scale)

    @property
    def shape(self):
        return self.data.shape

    @property
    def nbytes(self) -> int:
        return int(self.data.size * self.data.dtype.itemsize
                   + self.scale.size * self.scale.dtype.itemsize)

    def dequantize(self) -> jax.Array:
        return self.data.astype(jnp.float32) * self.scale[:, None]

    def __repr__(self):
        return f"QuantizedTable(data={self.data!r}, scale={self.scale!r})"
