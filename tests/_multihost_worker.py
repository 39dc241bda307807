"""Worker process for tests/test_multihost.py (not a pytest module).

Runs as 1 of 2 jax.distributed processes, each with 4 virtual CPU
devices -> an 8-device global mesh, and exercises the multi-host-only
branches the single-process suite cannot reach. Scenarios (argv[4],
default "base"):

- ``base``: parallel.mesh.shard_batch's
  make_array_from_process_local_data upload, metric_allreduce /
  to_host / barrier / allgather_host_ints / any_across_processes,
  TopKAccumulator.reduce(cross_process=True), and orbax save/restore of
  a NON-ADDRESSABLE (cross-process data-sharded) array.
- ``commit``: coordinated commit under a host lost MID-SAVE. Both
  processes contribute shards of a cross-process-sharded array to a
  shared-directory save; a chaos plan SIGKILLs process 1 after its
  snapshot, while the commit is in flight. Process 0's bounded commit
  barrier errors instead of hanging, and the step must NEVER gain a
  commit marker — no host can ever restore a half-written checkpoint.
  (Process 1 never prints; the parent asserts it died by SIGKILL.)

Prints MULTIHOST_OK on success; any assertion kills the process and the
parent test fails on the exit code.
"""

import os
import sys


def _scenario_base(process_id: int, ckpt_dir: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from genrec_tpu.parallel import (
        allgather_host_ints,
        any_across_processes,
        get_mesh,
        metric_allreduce,
        replicate,
        shard_batch,
        to_host,
    )

    mesh = get_mesh()

    # --- shard_batch: the make_array_from_process_local_data branch.
    # Every process holds the same GLOBAL batch (the trainers' contract);
    # each uploads only its addressable shards.
    batch = {"x": np.arange(16, dtype=np.float32).reshape(8, 2)}
    sharded = shard_batch(mesh, batch)
    assert sharded["x"].shape == (8, 2)
    assert not sharded["x"].is_fully_addressable

    # A jitted global reduction over the cross-process array.
    total = jax.jit(lambda b: jnp.sum(b["x"]))(sharded)
    assert float(total) == float(np.arange(16).sum()), float(total)

    # --- to_host on a non-addressable array (process_allgather path).
    back = to_host(sharded["x"])
    np.testing.assert_array_equal(back, batch["x"])

    # --- metric_allreduce: per-process partial sums -> global sums.
    got = metric_allreduce({"n": 1.0 + process_id, "s": 10.0})
    assert got["n"] == 3.0, got  # 1 + 2
    assert got["s"] == 20.0, got

    # --- the checkpoint-consensus / preemption-agreement primitives.
    rows = allgather_host_ints([process_id * 10, 7])
    np.testing.assert_array_equal(rows, [[0, 7], [10, 7]])
    assert any_across_processes(process_id == 1)  # one host's flag -> all
    assert not any_across_processes(False)

    # --- TopKAccumulator.reduce(cross_process=True): processes accumulate
    # DIFFERENT batches; the reduced metrics must reflect both.
    from genrec_tpu.ops.metrics import TopKAccumulator

    acc = TopKAccumulator(ks=(1,))
    if process_id == 0:
        actual = jnp.asarray([[7]])
        top = jnp.asarray([[[7]]])  # hit
    else:
        actual = jnp.asarray([[7]])
        top = jnp.asarray([[[3]]])  # miss
    acc.accumulate(actual=actual, top_k=top)
    m = acc.reduce(cross_process=True)
    assert abs(m["Recall@1"] - 0.5) < 1e-6, m  # 1 hit / 2 samples globally

    # --- orbax save/restore of a non-addressable array via the one
    # CheckpointManager all trainers use.
    from genrec_tpu.core.checkpoint import CheckpointManager

    state = {
        "w": replicate(mesh, jnp.full((4,), 3.0)),
        "data_sharded": sharded["x"],
    }
    mgr = CheckpointManager(ckpt_dir)
    mgr.save(0, state)
    mgr.wait()
    like = {
        "w": replicate(mesh, jnp.zeros((4,))),
        "data_sharded": shard_batch(mesh, {"x": np.zeros((8, 2), np.float32)})["x"],
    }
    restored = mgr.restore(like)
    np.testing.assert_array_equal(to_host(restored["w"]), np.full((4,), 3.0))
    np.testing.assert_array_equal(to_host(restored["data_sharded"]), batch["x"])
    mgr.close()


def _scenario_commit(process_id: int, ckpt_dir: str) -> None:
    """Process 1 dies (SIGKILL) mid-save of a cross-process-sharded
    array: the step must never gain a commit marker anywhere."""
    import jax.numpy as jnp
    import numpy as np

    from genrec_tpu.core import chaos
    from genrec_tpu.core.checkpoint import _COMMIT_MARKER, CheckpointManager
    from genrec_tpu.parallel import get_mesh, replicate, shard_batch

    mesh = get_mesh()
    sharded = shard_batch(
        mesh, {"x": np.arange(16, dtype=np.float32).reshape(8, 2)}
    )
    state = {"w": replicate(mesh, jnp.full((4,), 3.0)), "xs": sharded["x"]}
    # Bounded commit barrier: the lost host must surface as an error on
    # the survivor within seconds, not orbax's 10-minute default.
    mgr = CheckpointManager(ckpt_dir, commit_timeout_secs=20)
    mgr.save(1, state)
    mgr.wait()  # a known-good committed step first

    with chaos.inject(
        chaos.ChaosPlan(die_in_save_at_step=2, only_process=1)
    ):
        mgr.save(2, state)  # process 1 never returns from this call

    assert process_id == 0, "process 1 should have died in save"
    try:
        mgr.wait()
        raise SystemExit("commit completed with a dead peer — marker race")
    except SystemExit:
        raise
    except Exception as e:  # barrier timeout / peer-failure error
        print(f"commit blocked as expected: {type(e).__name__}", flush=True)
    marker = os.path.join(ckpt_dir, "2", _COMMIT_MARKER)
    assert not os.path.exists(marker), "half-written step gained a marker"
    # The previous committed step is untouched.
    assert os.path.exists(os.path.join(ckpt_dir, "1", _COMMIT_MARKER))


def main(coordinator: str, process_id: int, ckpt_dir: str,
         scenario: str = "base") -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = [
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count")
    ]
    flags.append("--xla_force_host_platform_device_count=4")
    os.environ["XLA_FLAGS"] = " ".join(flags)

    import jax

    jax.config.update("jax_platforms", "cpu")
    # Cross-process computations on the CPU backend need an explicit
    # collectives implementation (the default errors with "Multiprocess
    # computations aren't implemented on the CPU backend").
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator, num_processes=2, process_id=process_id
    )
    assert jax.process_count() == 2, jax.process_count()
    assert jax.device_count() == 8, jax.device_count()
    assert jax.local_device_count() == 4

    fn = {
        "base": _scenario_base,
        "commit": _scenario_commit,
    }[scenario]
    fn(process_id, ckpt_dir)

    if scenario == "commit":
        # Process 1 is dead: an end-of-test barrier would hang, and the
        # distributed client's shutdown may block on the lost peer too.
        print(f"MULTIHOST_OK {process_id}", flush=True)
        os._exit(0)
    from genrec_tpu.parallel import barrier

    barrier("multihost-test-done")
    print(f"MULTIHOST_OK {process_id}", flush=True)


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), sys.argv[3],
         sys.argv[4] if len(sys.argv) > 4 else "base")
