"""Test config: force an 8-device virtual CPU platform so mesh/sharding tests
run without TPUs (SURVEY.md §4 'fake device' lesson — the reference uses a
fake CPU custom-device plugin; we use XLA host platform device_count).

JAX_PLATFORMS=cpu is set here, before jax is first imported, so the tests
never touch an accelerator: on a machine with a chip, a chip belongs to one
process at a time, and the suite runs in several workers.
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8 " +
                      os.environ.get("XLA_FLAGS", ""))
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seeded():
    import paddle_tpu as pt
    pt.seed(2024)
    np.random.seed(2024)
    yield
