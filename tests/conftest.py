"""Test harness config.

Runs the whole suite on the JAX CPU backend with 8 virtual devices — the
in-process analog of the reference's ``test.MustRunCluster(t, 3)``
(test/pilosa.go:343): multi-device semantics without TPU hardware.
Must run before any jax import.
"""

import os

# Force (not setdefault: the environment may select an accelerator, and
# a test run must never take the chip) the CPU backend with 8 virtual
# devices for all tests.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Opt-in runtime lock-order witness (analysis/witness.py): installed
# HERE — after jax (its internal locks are not ours to audit) and
# before any pilosa_tpu module is imported by test collection — so
# every lock the product creates during the suite is witnessed. CI
# wires PILOSA_TPU_WITNESS=1 into the overload/chaos jobs.
_witness = None
if os.environ.get("PILOSA_TPU_WITNESS") == "1":
    from pilosa_tpu.analysis import witness as _witness_mod  # noqa: E402

    _witness = _witness_mod.install()


@pytest.fixture(scope="session", autouse=True)
def _lock_order_witness():
    """Fail the session if the suite ever acquired two lock sites in
    both orders — a latent deadlock even when this run got lucky."""
    yield
    if _witness is not None:
        _witness.check()


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process fault tests (tens of seconds)")
