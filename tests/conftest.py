"""Test config: force an 8-device virtual CPU platform so distributed tests
exercise real mesh sharding without TPU hardware (SURVEY.md §4 takeaway:
host-platform fake devices replace the reference's subprocess-per-GPU
harness).

The tests always run on the CPU, whatever is attached: the platform is
pinned through both the environment and jax.config before the first
backend is instantiated.  The chip is reached by ``chip_smoke.py`` only.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    # tier-2 marker: multi-process gang tests (launcher + TCPStore
    # rendezvous of jax-importing workers).  On throttled-CPU containers
    # the simultaneous worker imports routinely blow the 60s rendezvous
    # barrier, so these are excluded from the tier-1 sweep
    # (-m 'not slow', see ROADMAP.md) and run explicitly via -m slow.
    config.addinivalue_line(
        "markers",
        "slow: multi-process gang integration tests (tier-2; -m slow)")


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle

    paddle.seed(1234)
    np.random.seed(1234)
    yield


@pytest.fixture
def cpu_mesh8():
    """8-device CPU mesh for sharding tests."""
    import jax

    devs = jax.devices("cpu")
    assert len(devs) >= 8, "conftest must force 8 host devices"
    return devs
