"""Test configuration.

Tests run on CPU with 8 virtual XLA devices so the multi-chip sharding path
(nomad_tpu.parallel) is exercised without TPU hardware.  The platform is
forced through the environment AND jax.config: a machine that has an
accelerator would otherwise pick it (no backend is initialized yet at
conftest time).  Real-TPU behavior is covered by chip_smoke.py.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-second wall-clock test; excluded from tier-1 "
        "(pytest -m 'not slow') and run by the dedicated CI stages "
        "(scripts/ci.sh chaos stage, or -m slow)")
