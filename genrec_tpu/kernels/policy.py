"""Central policy for the Pallas kernels: when "auto" turns one on, and
the one place that decides whether a kernel runs compiled or interpreted.

Every trainer exposing a three-state kernel flag ("auto" / True / False)
resolves "auto" through this module. Every kernel wrapper resolves its
``interpret`` argument through `resolve_interpret`, so a process that
meant to be on the chip and is not fails instead of silently running the
Pallas interpreter.
"""

from __future__ import annotations

import contextlib

import jax

# Process-wide on purpose: the wrappers are reached from inside flax
# modules under jit, where no caller-owned object could be threaded to
# them. Only `interpret_mode()` sets it.
_interpret_requested = False


@contextlib.contextmanager
def interpret_mode():
    """Run every Pallas wrapper entered inside this context through the
    Pallas interpreter. Something a caller asks for by name — the test
    suite (tests/conftest.py), ``preflight --interpret``,
    ``chip_smoke.py --rehearse`` — never something inferred from the
    backend."""
    global _interpret_requested
    prev, _interpret_requested = _interpret_requested, True
    try:
        yield
    finally:
        _interpret_requested = prev


def resolve_interpret(interpret: bool, kernel: str) -> bool:
    """The wrappers' ``interpret`` decision. True when the caller passed
    ``interpret=True`` or `interpret_mode()` is active; False on a TPU;
    anywhere else an error, because Mosaic compiles only for TPU."""
    if interpret or _interpret_requested:
        return True
    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"Pallas kernel {kernel} called on backend {backend!r} without "
            "interpret mode: Mosaic compiles only on TPU. Pass "
            "interpret=True or enter genrec_tpu.kernels.policy."
            "interpret_mode() if the interpreter is what you want."
        )
    return False


def auto_fused_ce(tensor_parallel: int = 1) -> bool:
    """"auto" policy for the fused linear+CE kernel (kernels/fused_ce.py).

    On for single-chip TPU runs only: the dense kernel under multi-chip
    GSPMD has not been checked on more than one chip, and
    tensor_parallel > 1 vocab-shards the head, which the dense kernel
    cannot partition over (the sharded path is kernels/fused_ce.py
    sharded_fused_linear_ce, wired separately by the trainers).
    """
    return (
        jax.default_backend() == "tpu"
        and jax.device_count() == 1
        and tensor_parallel == 1
    )


def auto_pallas_attention() -> bool:
    """"auto" policy for the fused HSTU attention kernel (fwd + bwd)."""
    return jax.default_backend() == "tpu"


def auto_paged_attention() -> bool:
    """"auto" policy for the paged decode-attention kernel
    (kernels/paged_attention.py). TPU-only: off-TPU the serving engine and
    the parity tests run the pure-JAX gather fallback in ops/paged.py,
    which is the numerics contract the kernel is pinned against."""
    return jax.default_backend() == "tpu"


def auto_sharded_fused_ce() -> bool:
    """"auto" policy for the vocab-SHARDED fused CE (LCRec tp>1 head,
    kernels/fused_ce.sharded_fused_linear_ce). No single-chip gate:
    shard_map hands each device a local pallas_call, so GSPMD never has
    to partition the Mosaic call."""
    return jax.default_backend() == "tpu"
