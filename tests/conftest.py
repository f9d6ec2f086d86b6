"""Test bootstrap: force an 8-virtual-device CPU topology BEFORE jax
initializes, so sharding/mesh tests run without TPU hardware
(the reference's analogue is backend-parametrized AcceleratedTest,
veles/tests/accelerated_test.py)."""

import os
import sys

# Must happen before jax (or anything importing jax) initializes a
# backend: the tests run on the CPU whatever the machine holds.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running end-to-end tests excluded from tier-1")


@pytest.fixture(autouse=True)
def _reset_prng():
    """Deterministic generators + clean resilience state per test."""
    import veles_tpu.prng as prng
    import veles_tpu.resilience as resilience
    prng.reset()
    resilience.reset()
    yield
    prng.reset()
    resilience.reset()


@pytest.fixture
def f32_precision():
    """Pins the activation stream to f32 (precision_level 1) for
    closed-form math tests whose tolerances bf16 cannot meet; the
    default (level 0 = bf16 activations) is restored afterwards."""
    from veles_tpu.config import root
    prev = getattr(root.common.engine, "precision_level", None)
    root.common.engine.precision_level = 1
    yield
    if prev is None:
        root.common.engine.precision_level = 0
    else:
        root.common.engine.precision_level = prev
