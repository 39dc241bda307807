"""The look for a chip, the compile cache, and the device's memory."""

from __future__ import annotations

import os
import sys


def checkout_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache, through the program's own
    ``parallel.mesh.enable_compile_cache`` (the one place in the tree that
    names a cache directory): ``<checkout>/.jax_compile_cache``, a fixed
    path inside the checkout (the path is part of the cache's key), or
    where ``JAX_COMPILATION_CACHE_DIR`` says."""
    from genrec_tpu.parallel.mesh import enable_compile_cache as enable

    return enable()


def require_chips(n: int) -> dict:
    """The device as JAX reports it; exits non-zero, printing no result,
    when there is no TPU or fewer chips than the cell asks for."""
    import jax

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if dev["platform"] != "tpu" or dev["count"] < n:
        sys.stderr.write(
            f"benchmark: needs {n} TPU chip(s), found platform "
            f"{dev['platform']!r} ({dev['kind']} x{dev['count']}); "
            "the benchmark measures on the chip and does no work elsewhere\n")
        raise SystemExit(3)
    return dev


def memory_peak_bytes() -> int:
    """Peak bytes occupied on the fullest chip (0 where the backend does not
    report it, as on the CPU). The TPU allocator counts live buffers under
    ``peak_bytes_in_use`` and what compiled programs reserve for their
    temporaries under ``peak_bytes_reserved``; the chip's memory holds both
    (its ``largest_free_block_bytes`` is the limit less their sum), so the
    peak is their sum."""
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def bytes_limit() -> int:
    import jax

    stats = jax.local_devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit", 0))
