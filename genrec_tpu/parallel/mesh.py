"""Device mesh + sharding utilities.

The design (SURVEY.md §2.5): a named `jax.sharding.Mesh` whose axes carry the
parallelism strategy — "data" for DP (the only strategy the reference has),
with room for "model" (TP), "pipe" (PP) and "seq" (SP) axes that the
reference lacks entirely. Params are replicated (or sharded on "model"),
batches sharded on "data"; XLA emits the gradient psum over ICI from the
sharded jit — no NCCL/MPI analog exists anywhere in this stack.
"""

from __future__ import annotations

import os
from typing import Any, Mapping

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pin_platform(platform: str) -> None:
    """Programmatic platform pin (``--platform`` flags, parity/profile
    runners): the same thing ``JAX_PLATFORMS`` does, from code. Must run
    before first device use."""
    jax.config.update("jax_platforms", platform)


#: The in-checkout compile cache, used when JAX_COMPILATION_CACHE_DIR does
#: not place one. Fixed and derived from the package location: the path is
#: part of the cache key, so a directory that moves never hits.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_compile_cache",
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return the directory in use. Every process entry calls this (trainers
    through `distributed_init`).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the directory is the
    environment's to choose — JAX reads the variable itself and no code
    sets another. Otherwise the cache is `COMPILE_CACHE_DIR`. Either way
    the two thresholds drop to zero, so the small AOT bucket executables
    of the serving ladder are kept too. The compile tap is registered here
    as well (`obs.CompileEvents`), so it sees every load and compile of
    the process from its entry on."""
    from genrec_tpu.obs import CompileEvents

    CompileEvents.ensure()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


def device_summary() -> dict:
    """The device as JAX reports it — stamped on every measurement."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def require_tpu(who: str) -> dict:
    """First act of the entry points that measure on the chip (bench.py,
    chip_smoke.py, the kernel preflight): the device summary on a TPU, a
    non-zero exit naming what was found anywhere else. They do no work
    and print no number off the chip."""
    dev = device_summary()
    if dev["platform"] != "tpu":
        raise SystemExit(
            f"{who}: found platform {dev['platform']!r} "
            f"({dev['kind']} x{dev['count']}), not 'tpu'; "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}. "
            "This entry point runs on the chip and does no work elsewhere."
        )
    return dev


def distributed_init(initialization_timeout: int | None = None) -> None:
    """Initialize multi-host JAX if launched in a multi-process environment,
    and turn on the persistent compile cache (`enable_compile_cache`).

    Replaces `Accelerator(...)` process-group setup (reference
    tiger_trainer.py:124-128). Single-process runs skip the distributed
    part, so trainers call this unconditionally, first.

    The `jax.distributed.initialize` call runs with an explicit
    ``initialization_timeout`` (``GENREC_DIST_INIT_TIMEOUT`` seconds,
    default 300) and a missing/late host surfaces as an actionable error
    naming the coordinator address, this process's id, and the expected
    process count — not JAX's bare hang-then-stack-trace.
    """
    enable_compile_cache()
    if int(os.environ.get("JAX_PROCESS_COUNT", "1")) > 1 or "JAX_COORDINATOR_ADDRESS" in os.environ:
        timeout = (
            initialization_timeout
            if initialization_timeout is not None
            else int(os.environ.get("GENREC_DIST_INIT_TIMEOUT", "300"))
        )
        coordinator = os.environ.get("JAX_COORDINATOR_ADDRESS", "<env-detected>")
        process_id = os.environ.get("JAX_PROCESS_ID", "<env-detected>")
        process_count = os.environ.get("JAX_PROCESS_COUNT", "<env-detected>")
        # jax reads JAX_COORDINATOR_ADDRESS itself but fills process
        # count/id only from cluster auto-detection (SLURM, GKE) —
        # env-var-driven fleets must pass them explicitly or initialize
        # fails instantly with "Number of processes must be defined".
        kwargs: dict = {"initialization_timeout": timeout}
        if "JAX_PROCESS_COUNT" in os.environ:
            kwargs["num_processes"] = int(os.environ["JAX_PROCESS_COUNT"])
        if "JAX_PROCESS_ID" in os.environ:
            kwargs["process_id"] = int(os.environ["JAX_PROCESS_ID"])
        # An UNREACHABLE coordinator must be caught HERE: past this
        # point the XLA distributed client LOG(FATAL)s the whole process
        # on its registration deadline (no Python exception to wrap), so
        # non-coordinator processes retry a plain TCP connect against
        # the same deadline first and fail with an actionable error.
        if (
            coordinator != "<env-detected>"
            and kwargs.get("process_id", 0) != 0
        ):
            # One budget overall: the connect wait and initialize share
            # the deadline, so a slow coordinator cannot stretch the
            # operator's wait to 2x the configured timeout.
            kwargs["initialization_timeout"] = _await_coordinator(
                coordinator, timeout, process_id, process_count
            )
        try:
            jax.distributed.initialize(**kwargs)
        except Exception as e:
            # Only timeout-shaped failures get the missing-host
            # narrative; anything else (double initialize, bad flag) is
            # instant and must not send the operator chasing networking.
            msg = str(e).lower()
            if not any(t in msg for t in ("deadline", "timed out", "timeout")):
                raise
            raise RuntimeError(
                _init_failure_message(timeout, coordinator, process_id,
                                      process_count)
            ) from e


def _init_failure_message(timeout, coordinator, process_id, process_count):
    return (
        f"jax.distributed.initialize() failed after {timeout}s "
        f"(coordinator {coordinator}, process id {process_id} of "
        f"{process_count} expected). Most likely one host never "
        "started or cannot reach the coordinator: check that every "
        "worker launched, that JAX_COORDINATOR_ADDRESS is routable "
        "from all hosts, and that JAX_PROCESS_COUNT matches the "
        "actual fleet size. Raise GENREC_DIST_INIT_TIMEOUT for "
        "slow-provisioning fleets."
    )


def _await_coordinator(coordinator: str, timeout: int,
                       process_id, process_count) -> int:
    """Retry a bare TCP connect to the coordinator until it accepts or
    the initialization deadline passes (workers legitimately start
    before the coordinator — refused connects keep retrying). Returns
    the whole seconds REMAINING of ``timeout`` (at least 1) for the
    caller to hand to `jax.distributed.initialize`."""
    import socket
    import time

    host, _, port = coordinator.rpartition(":")
    if not host or not port.isdigit():
        # A malformed address is a config error, not a timeout: fail
        # instantly with the same actionable narrative instead of a raw
        # int() traceback from the connect loop.
        raise RuntimeError(
            f"JAX_COORDINATOR_ADDRESS {coordinator!r} is not host:port. "
            + _init_failure_message(timeout, coordinator, process_id,
                                    process_count)
        )
    deadline = time.monotonic() + timeout
    while True:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            raise RuntimeError(
                _init_failure_message(timeout, coordinator, process_id,
                                      process_count)
            )
        try:
            with socket.create_connection(
                (host, int(port)), timeout=min(5.0, remaining)
            ):
                return max(1, int(deadline - time.monotonic()))
        except OSError:
            time.sleep(min(0.5, max(0.0, deadline - time.monotonic())))


def make_mesh(shape: Mapping[str, int] | None = None, devices=None) -> Mesh:
    """Build a named mesh. ``shape`` maps axis name -> size; one axis may be
    -1 (inferred). Default: all devices on a single "data" axis."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if not shape:
        shape = {"data": n}
    names = list(shape.keys())
    sizes = list(shape.values())
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        sizes[sizes.index(-1)] = n // known
    total = int(np.prod(sizes))
    if total != n:
        raise ValueError(f"mesh shape {dict(zip(names, sizes))} != {n} devices")
    dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, tuple(names))


def get_mesh(data_axis: str = "data") -> Mesh:
    """The default 1-axis data-parallel mesh over every local device."""
    return make_mesh({data_axis: len(jax.devices())})


def shard_batch(mesh: Mesh, batch: Any, axis: str = "data") -> Any:
    """Place a host batch pytree with its leading dim sharded over ``axis``.

    Single-process: a plain device_put of the global array. Multi-host:
    every process holds the same GLOBAL batch (batch_iterator's (seed,
    epoch)-deterministic shuffle guarantees it) and
    `jax.make_array_from_process_local_data(..., global_shape)` uploads
    only this process's addressable shards — no cross-host transfer of
    array contents, the TPU-native analog of the reference's
    `Accelerator(split_batches=True)` per-rank loader split (SURVEY.md
    §5.8).
    """
    multi = jax.process_count() > 1

    def place(x):
        x = np.asarray(x)
        spec = P(axis, *([None] * (x.ndim - 1)))
        sharding = NamedSharding(mesh, spec)
        if multi:
            return jax.make_array_from_process_local_data(
                sharding, x, global_shape=x.shape
            )
        return jax.device_put(x, sharding)

    return jax.tree_util.tree_map(place, batch)


def replicate(mesh: Mesh, tree: Any) -> Any:
    """Fully replicate a pytree (params/opt state) across the mesh."""
    sharding = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding), tree)


def to_host(x) -> np.ndarray:
    """Materialize a (possibly globally-sharded) device array on every
    host. Single-process: plain np.asarray. Multi-host: np.asarray on an
    array spanning non-addressable devices raises, so gather the global
    value via process_allgather instead."""
    if jax.process_count() == 1 or getattr(x, "is_fully_addressable", True):
        return np.asarray(x)
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(x, tiled=True))


def metric_allreduce(tree: Any) -> Any:
    """Sum metric scalars across processes (reference `accelerator.reduce`
    sum-gather, sasrec_trainer.py:75-82). Within one process the devices
    already reduced via the sharded jit; this covers multi-host."""
    if jax.process_count() == 1:
        return tree
    from jax.experimental import multihost_utils

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    stacked = np.asarray([float(v) for v in leaves], np.float64)
    summed = multihost_utils.process_allgather(stacked).sum(axis=0)
    return jax.tree_util.tree_unflatten(treedef, [float(v) for v in summed])


def barrier(name: str = "barrier") -> None:
    """Cross-host sync point (reference `accelerator.wait_for_everyone`)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)


def allgather_host_ints(values) -> np.ndarray:
    """Gather a small per-process int vector from every process.

    Returns a ``(process_count, len(values))`` int64 array whose row p is
    process p's vector — the communication primitive under checkpoint
    consensus (each host contributes its locally-valid checkpoint steps)
    and preemption agreement. Every process must call this in lockstep
    with an equal-length vector. Single-process: a trivial (1, N) reshape,
    no collective.
    """
    row = np.asarray(list(values), np.int64).reshape(-1)
    if jax.process_count() == 1:
        return row[None, :]
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(row))


def any_across_processes(flag: bool) -> bool:
    """True iff ``flag`` is True on AT LEAST one process.

    The multi-host preemption agreement primitive: every host polls its
    local PreemptionGuard but acts only on the fleet-wide OR, so all hosts
    write their preemption resume point at the SAME global step instead of
    forking (one host checkpointing step N while another runs on to N+1
    would deadlock the next collective and fork the saved state).
    Single-process: returns ``flag`` with no collective.
    """
    if jax.process_count() == 1:
        return bool(flag)
    return bool(allgather_host_ints([1 if flag else 0]).max())
