# NOTE: deliberately NO XLA_FLAGS / device-count forcing here — smoke tests
# and benchmarks must see the single real CPU device.  Multi-device tests
# spawn subprocesses that set JAX_PLATFORMS=cpu and
# --xla_force_host_platform_device_count themselves.
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.RandomState(0)
