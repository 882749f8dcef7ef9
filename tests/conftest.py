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


@pytest.fixture(scope="session")
def wide_model():
    """head_dim 128 with 8 KV heads (hidden 1024 over 8 heads), two layers:
    the smallest model whose pool takes the decode kernel AND the one-launch
    row write (a row of 8 heads is a whole sublane tile), i.e. the read
    both serving cells and every real model take, where the tiny head_dim
    16 models of tier-1 take the gather. Two layers, so that a one-layer
    speculative draft is weaker than its target. The interpreted kernels
    compile slowly on the CPU, so everything about it stays tiny."""
    import jax
    from paddle_tpu.models.llama import LlamaConfig, llama_init_params
    cfg = LlamaConfig.tiny(num_hidden_layers=2, hidden_size=1024,
                           num_attention_heads=8, num_key_value_heads=8,
                           max_position_embeddings=128)
    return cfg, llama_init_params(cfg, jax.random.PRNGKey(3))


@pytest.fixture(params=["paged", "kernel"])
def served(request, small_model, wide_model):
    """(cfg, params, read): a model for each read of the default
    ``kv_layout="paged"``: the requesting file's ``small_model`` (head_dim
    16: ``stats["kv_read"] == "gather"``) and ``wide_model`` (``"kernel"``).
    Engines built from it assert ``eng.stats["kv_read"] == read``."""
    if request.param == "kernel":
        return (*wide_model, "kernel")
    return (*small_model, "gather")


@pytest.fixture()
def no_automatic_collection():
    """A collection of generation 1 or 2 is a span (`gc.collect`): a test
    that counts the ring's spans exactly holds automatic collections off,
    from a fresh ring on. `gc.collect()` still runs, hook and all."""
    import gc
    from paddle_tpu.observability import spans
    was = gc.isenabled()
    gc.disable()
    spans.reset()
    yield
    if was:
        gc.enable()
