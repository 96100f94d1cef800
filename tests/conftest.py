"""Shared fixtures and the ONE place the 8-device host platform is forced.

Several suites (elastic, MoE expert parallelism, parallel strategies,
mesh-native commit) need a real multi-device ``jax.Mesh``, which on CPU
hosts means ``--xla_force_host_platform_device_count=8``.  JAX pins the
device count at backend initialisation, so the flag must be in the
environment BEFORE anything imports jax — pytest imports this conftest
ahead of every test module, making it the single reliable hook.  Tests
that spawn subprocess workers inherit the flag through the environment;
an already-forced count (e.g. a CI job exporting its own XLA_FLAGS) is
left untouched.
"""
import os

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

import pytest


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running exhaustive checks")


@pytest.fixture(scope="session")
def pallas_interpret() -> bool:
    """Platform-detected Pallas execution mode for kernel tests: compiled
    on a real accelerator backend, ``interpret=True`` on CPU hosts (same
    kernel body, run by the Pallas interpreter — numerics identical).
    Pallas TPU kernels compile only against a TPU backend."""
    import jax
    return jax.default_backend() == "cpu"


@pytest.fixture(scope="session")
def host_devices_8():
    """The 8 forced host devices.  Skips (instead of mysteriously failing
    mesh construction) when a jax backend was already live before this
    conftest could force the count — e.g. pytest run from a process that
    imported jax first, or an environment pinning a smaller force."""
    import jax
    if jax.device_count() < 8:
        pytest.skip(
            "needs 8 host devices but the jax backend initialised with "
            f"{jax.device_count()} — conftest.py could not force "
            "--xla_force_host_platform_device_count=8 (backend already "
            "live or XLA_FLAGS pinned elsewhere)")
    return jax.devices()
