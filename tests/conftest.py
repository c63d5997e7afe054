"""Force an 8-device CPU mesh before JAX initializes.

The SURVEY test strategy (§4): JAX CPU multi-device exercises the same
shard_map/collective code paths a TPU pod uses. Must run before `import jax`
anywhere, hence top of conftest.
"""

import os
import re

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags)
os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

assert jax.default_backend() == "cpu" and len(jax.devices()) == 8, \
    "expected 8 virtual CPU devices for mesh tests"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    import jax
    return jax.make_mesh((8,), ("parts",))


@pytest.fixture(scope="session")
def mesh4():
    import jax
    return jax.make_mesh((4,), ("parts",))


@pytest.fixture
def rng():
    return np.random.default_rng(0)
