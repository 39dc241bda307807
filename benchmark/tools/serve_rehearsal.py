"""Compile a paged serving cell's largest prefill bucket and largest decode
rung at the real sizes for a DESCRIBED v5e chip (no chip attached) and print
``memory_analysis()`` beside the resident bytes (weights, slot table, page
pool): settles ``assumed.serve`` before any chip call. Run by hand with
``JAX_PLATFORMS=cpu``:

    python3 -m benchmark.tools.serve_rehearsal solar_open2_serve_lifelong [batch [items [slots]]]

No array of the cell's size is made: parameters, pools and the slot table
are shapes. The catalog (and so the trie operand's shape) is the real one.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

from benchmark.tools.compile_rehearsal import analysis, describe_chip, with_sharding


def _nbytes(tree) -> int:
    import jax
    import numpy as np

    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


def rehearse(cell, B: int, L: int, S: int, one_chip) -> dict:
    from unittest import mock

    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg, adapter = cell.config, cell.adapter
    head = adapter.make_head(cfg, adapter.make_catalog(cfg, 0))
    paged = adapter.paged_config(cfg, head)
    layers, heads, hd, dtype = head.paged_layout()
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    shapes = adapter.param_shapes(cfg)
    f32_leaves = ("A_log", "dt_bias")  # as the adapter's make_params keeps them
    params = jax.tree_util.tree_map_with_path(
        lambda path, s: sds(s.shape, jnp.float32 if str(path[-1].key) in f32_leaves
                            else jnp.dtype(cfg["param_dtype"])), shapes)
    trie = with_sharding(jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), head.trie), one_chip)
    pool = tuple(sds((paged.num_pages, paged.page_size, heads * hd), dtype)
                 for _ in range(layers))
    table = with_sharding(
        jax.eval_shape(lambda: head.paged_state_zeros(paged.max_slots)), one_chip)
    C = cfg["sem_id_dim"]
    out = {"resident": {"weights": _nbytes(params), "slot_table": _nbytes(table),
                        "page_pool": 2 * _nbytes(pool), "trie": _nbytes(trie),
                        "recurrent_state": sum(
                            _nbytes(table[k]) for k in head.paged_recurrent_leaves)}}
    # The program asks jax.default_backend() whether the paged kernel is on
    # (TPU only); the rehearsal steers it to the chip's branch.
    with mock.patch.object(jax, "default_backend", return_value="tpu"):
        n = 2 + 2  # params, trie, ids, mask
        prefill = jax.jit(head.make_prefill_paged_fn(B, L),
                          donate_argnums=(n + 1, n + 2))
        out[f"prefill_b{B}_l{L}"] = analysis(prefill.lower(
            params, trie, sds((B, L * C), np.int32), sds((B, L * C), np.int32),
            sds((B, paged.pages_per_slot), np.int32), pool, pool).compile())

        fn = head.make_decode_paged_fn()

        def step(params, trie, table, vectors, k_pools, v_pools):
            rows = fn(params, trie, {k: v[:S] for k, v in table.items()},
                      vectors[:, 0], vectors[:, 2:], vectors[:, 1], k_pools, v_pools)
            table = {k: jax.lax.dynamic_update_slice_in_dim(v, rows[k], 0, axis=0)
                     if k in rows else v for k, v in table.items()}
            return table, {k: rows[k] for k in head.paged_result_leaves}

        decode = jax.jit(step, donate_argnums=(2,))
        out[f"decode_s{S}"] = analysis(decode.lower(
            params, trie, table, sds((S, 2 + paged.pages_per_slot), np.int32),
            pool, pool).compile())
    return out


def main(argv) -> int:
    import json

    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    from benchmark.harness.spec import Spec

    cell = Spec().cell(argv[0])
    if cell.kind_name != "serve":
        raise SystemExit(f"{cell.name}: not a serving cell")
    a = cell.config["assumed"]["serve"]
    B = int(argv[1]) if len(argv) > 1 else max(a["batch_buckets"])
    L = int(argv[2]) if len(argv) > 2 else max(a["history_buckets"])
    S = int(argv[3]) if len(argv) > 3 else a["max_slots"]
    for name, v in rehearse(cell, B, L, S, describe_chip()).items():
        print(name, json.dumps(v), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
