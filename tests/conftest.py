import os

# Tests run on the real single CPU device by default; the host-mesh tests
# that need several devices spawn with their own XLA_FLAGS via subprocess,
# EXCEPT the in-process mesh tests below which require the flag before jax
# imports — so set a modest 8-device count for the whole test session.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np
import pytest


@pytest.fixture(scope="session")
def host_mesh():
    from repro.launch.mesh import make_mesh
    return make_mesh((2, 4), ("data", "model"))


@pytest.fixture(scope="session")
def pod_mesh():
    from repro.launch.mesh import make_mesh
    return make_mesh((2, 2, 2), ("pod", "data", "model"))
