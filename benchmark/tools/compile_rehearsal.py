"""Compile a training cell's step at the real sizes for a DESCRIBED v5e chip
(no chip attached) and print ``memory_analysis()``: settles rows_per_step
before any chip call. Run by hand with ``JAX_PLATFORMS=cpu``:

    python3 -m benchmark.tools.compile_rehearsal tiger_train_packed [rows ...]
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def describe_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def with_sharding(tree, sharding):
    import jax

    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree)


def analysis(compiled) -> dict:
    m = compiled.memory_analysis()
    out = {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes")}
    out["total_bytes"] = (out["argument_size_in_bytes"] + out["output_size_in_bytes"]
                          + out["temp_size_in_bytes"] - out["alias_size_in_bytes"])
    return out


def train_step(cell, rows: int, one_chip) -> dict:
    traffic = dict(cell.traffic, rows_per_step_per_chip=rows)
    _, _, step_fn = cell.adapter.make_step(cell.config)
    state, batch = cell.adapter.train_shapes(cell.config, traffic, 1)
    state, batch = with_sharding(state, one_chip), with_sharding(batch, one_chip)
    # The program asks jax.default_backend() which RNG to draw dropout from
    # (rbg on a TPU, threefry elsewhere); the rehearsal steers it to the
    # chip's branch, or it would compile a program the chip never runs.
    from unittest import mock

    import jax

    with mock.patch.object(jax, "default_backend", return_value="tpu"):
        lowered = step_fn.lower(state, batch)
    return analysis(lowered.compile())


def main(argv) -> int:
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    from benchmark.harness.spec import Spec

    cell = Spec().cell(argv[0])
    one_chip = describe_chip()
    if cell.kind_name != "train":
        # A serving cell's memory is its page pool: pages x page bytes from
        # the config's arithmetic, checked on the chip (PERF.md section 4).
        raise SystemExit(f"{cell.name}: only training steps are rehearsed here")
    rows = [int(r) for r in argv[1:]] or [
        int(cell.traffic["rows_per_step_per_chip"])]
    for r in rows:
        print(r, "rows:", train_step(cell, r, one_chip), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
