"""Central Pallas auto-enable policy (kernels/policy.py)."""

import jax

from genrec_tpu.kernels import policy


def test_cpu_backend_disables_all_autos():
    # conftest pins the cpu backend, so every auto resolves False here.
    assert jax.default_backend() == "cpu"
    assert policy.auto_fused_ce() is False
    assert policy.auto_fused_ce(tensor_parallel=2) is False
    assert policy.auto_pallas_attention() is False
    assert policy.auto_paged_attention() is False
    assert policy.auto_sharded_fused_ce() is False


def test_no_environment_switch_moves_a_tpu_run_off_its_kernels(monkeypatch):
    """The old GENREC_TPU_DISABLE_PALLAS kill-switch is gone: on a TPU the
    autos answer from the backend and nothing else."""
    assert not hasattr(policy, "pallas_disabled")
    monkeypatch.setattr(policy.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(policy.jax, "device_count", lambda: 1)
    monkeypatch.setenv("GENREC_TPU_DISABLE_PALLAS", "1")
    assert policy.auto_fused_ce() is True
    assert policy.auto_pallas_attention() is True
    assert policy.auto_paged_attention() is True
    assert policy.auto_sharded_fused_ce() is True


def test_dense_auto_requires_single_chip_and_tp1(monkeypatch):
    # Simulate a TPU backend: the dense kernel additionally requires a
    # single device and tensor_parallel == 1 (docs/training.md policy);
    # the sharded variant requires neither.
    monkeypatch.setattr(policy.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(policy.jax, "device_count", lambda: 1)
    assert policy.auto_fused_ce() is True
    assert policy.auto_fused_ce(tensor_parallel=2) is False
    monkeypatch.setattr(policy.jax, "device_count", lambda: 8)
    assert policy.auto_fused_ce() is False
    assert policy.auto_pallas_attention() is True
    assert policy.auto_sharded_fused_ce() is True
