"""The v5e compile rehearsal, kept small: the training cell's step compiles
for a DESCRIBED v5e chip (no chip attached) on the chip's own RNG branch. The
real sizes are run by hand (``python3 -m benchmark.tools.compile_rehearsal``);
their figures are in PERF.md. One file, topology inside a fixture."""

import pytest


@pytest.fixture(scope="module")
def one_chip():
    import jax

    from benchmark.tools import compile_rehearsal

    try:
        sharding = compile_rehearsal.describe_chip()
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield sharding
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def test_train_step_compiles_for_v5e(one_chip):
    from benchmark.harness.spec import Spec
    from benchmark.tools import compile_rehearsal

    cell = Spec().cell("tiger_train_packed")
    small = compile_rehearsal.train_step(cell, 8, one_chip)
    # Weights, gradients and both Adam moments of 15.4M float32 parameters
    # are donated in and come back out.
    assert small["alias_size_in_bytes"] > 3 * 4 * cell.config["parameters"]
    assert 0 < small["temp_size_in_bytes"] < 2**30
