"""Test harness: force an 8-device virtual CPU platform before JAX import.

The reference has no tests at all (SURVEY.md §4); here every distributed
code path is exercised on a faked 8-device host mesh so CI needs no TPU.
"""

import os

# Tests always run on the virtual 8-device CPU mesh, whatever the session
# environment names.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from genrec_tpu.parallel.mesh import enable_compile_cache  # noqa: E402

# The same persistent compile cache every entry point uses, on for the whole
# session: engines and trainers rebuilt test after test recompile identical
# programs, which then load from the cache instead.
enable_compile_cache()


@pytest.fixture(autouse=True, scope="session")
def _pallas_interpret_mode():
    """The suite runs the Pallas kernels through the interpreter, and asks
    for that here by name: no wrapper infers it from the backend."""
    from genrec_tpu.kernels.policy import interpret_mode

    with interpret_mode():
        yield


@pytest.fixture(autouse=True)
def _fresh_config():
    """Isolate configlib global state between tests."""
    from genrec_tpu.configlib import clear_bindings
    from genrec_tpu.configlib.parser import clear_macros

    yield
    clear_bindings()
    clear_macros()


@pytest.fixture
def rng():
    return np.random.default_rng(0)
