"""AOT-lowering helpers shared by the serving engine and the disagg
workers/transports.

These rules are load-bearing compile discipline, so they live in exactly
one place:

- ``sds_tree``: pytree -> ShapeDtypeStructs, lowering without live
  buffers;
- ``donate_argnums``: the backend donation policy — CPU has no buffer
  donation, and donating there only emits a per-call warning;
- ``paged_decode_donate_argnums``: which operand of the paged decode
  step is dead after the call;
- ``named``: the name an executable carries into a device profile.
"""

from __future__ import annotations


def named(fn, name: str):
    """``fn`` under a stable name of its own, so that the compiled
    executable is `jit_<name>` on a device profile's `XLA Modules` line
    (and in every op's scope path) and not `jit_fn`. The name must not
    hold the substring `paged`: a profile reader finds the paged-attention
    kernel's custom calls by that word in their scope path, and an
    executable's name is in every path."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def donate_argnums(*argnums):
    """``argnums`` where the backend supports donation, ``()`` on CPU."""
    import jax

    return argnums if jax.default_backend() != "cpu" else ()


def paged_decode_donate_argnums(n_operands: int) -> tuple:
    """Argnums the paged decode step donates: the slot state, which is
    dead after every call (the table the executable returns takes its
    place on the device). The paged signature is (params, *catalog operands, state,
    ...), so the state's index follows the head's operand count — a fixed
    index would donate params or a trie for a head with none or two. The
    operands (catalog.TensorTrie) are threaded, NOT donated: they survive
    every step and are swapped only by set_catalog. Shared with the
    graftlint manifest entry in serving/heads.py so the donation audit
    audits the SAME argnums production compiles."""
    return (1 + n_operands,)


def sds_tree(tree):
    """Pytree -> ShapeDtypeStructs for AOT lowering without live buffers.

    Leaves already committed to a mesh (`NamedSharding` — the
    ServingEngine/DecodeWorker ``mesh=`` knob places params, quantized
    tables, and KV page banks this way) keep their sharding on the
    struct, so the lowered executable expects exactly the placement the
    live operand has. Host numpy / single-device leaves lower unplaced,
    as before — nothing changes for a meshless engine."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    def cvt(x):
        sharding = getattr(x, "sharding", None)
        if isinstance(sharding, NamedSharding):
            return jax.ShapeDtypeStruct(
                jnp.shape(x), jnp.result_type(x), sharding=sharding
            )
        return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x))

    return jax.tree_util.tree_map(cvt, tree)
