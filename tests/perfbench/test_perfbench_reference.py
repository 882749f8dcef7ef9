"""The plain reference against the program at tiny sizes on the CPU, and
the int8 control against the reference."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import perfbench
from perfbench.families import llama
from perfbench.weights import make_weights

ref = llama.reference()

HERE = os.path.dirname(perfbench.__file__)


def tiny(name):
    with open(os.path.join(HERE, "configs", "rehearse", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module", params=["internlm2-1.8b", "mistral-7b.l4"])
def model(request):
    cfg = tiny(request.param)
    return cfg, make_weights(cfg, 5), llama.llama_config(cfg, 64)


def test_reference_imports_nothing_of_the_program():
    for mod in ("ref/llama.py", "arith.py", "gen.py", "stats.py", "trace.py"):
        with open(os.path.join(HERE, mod)) as f:
            src = f.read()
        assert "import paddle_tpu" not in src and "from paddle_tpu" not in src


def test_forward_logits_match_the_program(model):
    from paddle_tpu.models.llama import llama_forward
    cfg, w, lc = model
    toks = np.random.RandomState(0).randint(1, cfg["vocab_size"], 48)
    want, _ = llama_forward(w, jnp.asarray(toks[None], jnp.int32), lc,
                            remat=False)
    best, at, first = ref.served_logits(
        w, jnp.asarray(toks, jnp.int32), jnp.int32(0),
        jnp.asarray(toks, jnp.int32), cfg=ref.hashable(cfg), dot="f32", n=48)
    want = np.asarray(want[0], np.float32)
    np.testing.assert_allclose(np.asarray(best), want.max(-1), atol=2e-5)
    np.testing.assert_allclose(np.asarray(at),
                               want[np.arange(48), toks], atol=2e-5)
    assert (np.asarray(first) == want.argmax(-1)).mean() > 0.95


def test_loss_and_gradients_match_the_program(model):
    from paddle_tpu.models.llama import llama_loss
    cfg, w, lc = model
    rs = np.random.RandomState(1)
    toks = jnp.asarray(rs.randint(1, cfg["vocab_size"], (2, 32)), jnp.int32)
    labs = jnp.asarray(rs.randint(1, cfg["vocab_size"], (2, 32)), jnp.int32)
    want_l, want_g = jax.value_and_grad(
        lambda p: llama_loss(p, toks, labs, lc, remat=False))(w)
    got_l, got_g = ref.loss_and_grads(w, toks, labs, cfg=ref.hashable(cfg),
                                      dot="f32")
    assert float(got_l) == pytest.approx(float(want_l), rel=1e-5)
    for k in want_g:
        a, b = np.asarray(got_g[k]), np.asarray(want_g[k])
        assert np.abs(a - b).max() <= 1e-4 * np.abs(b).max() + 1e-8, k


def test_adamw_leaf_is_adamw():
    hp = (3e-4, 0.9, 0.999, 1e-8, 0.1)
    p = jnp.asarray([1.0, -2.0, 0.5], jnp.float32)
    g1 = jnp.asarray([0.1, -0.2, 0.0], jnp.float32)
    g2 = jnp.asarray([-0.3, 0.1, 0.2], jnp.float32)
    p0 = np.asarray(p)
    p1 = np.asarray(ref.adamw_leaf(p, (g1,), jnp.int32(1), hp=hp))
    want1 = p0 - 3e-4 * np.asarray(g1) / (np.abs(g1) + 1e-8) - 3e-5 * p0
    np.testing.assert_allclose(p1, want1, rtol=1e-6)
    m = 0.9 * 0.1 * np.asarray(g1) + 0.1 * np.asarray(g2)
    v = 0.999 * 0.001 * np.asarray(g1) ** 2 + 0.001 * np.asarray(g2) ** 2
    want2 = p1 - 3e-4 * (m / (1 - 0.81)) / (np.sqrt(v / (1 - 0.999 ** 2))
                                            + 1e-8) - 3e-5 * p1
    p2 = np.asarray(ref.adamw_leaf(jnp.asarray(p1), (g1, g2), jnp.int32(2),
                                   hp=hp))
    np.testing.assert_allclose(p2, want2, rtol=1e-5)


def test_int8_control_departs_from_the_reference(model):
    cfg, w, _ = model
    rs = np.random.RandomState(2)
    toks = jnp.asarray(rs.randint(1, cfg["vocab_size"], (2, 32)), jnp.int32)
    l32, g32 = ref.loss_and_grads(w, toks, toks, cfg=ref.hashable(cfg),
                                  dot="f32")
    l8, g8 = ref.loss_and_grads(w, toks, toks, cfg=ref.hashable(cfg),
                                dot="int8")
    assert np.isfinite(float(l8)) and float(l8) != float(l32)
    rel = [float(jnp.linalg.norm(g8[k] - g32[k]) / jnp.linalg.norm(g32[k]))
           for k in g32]
    assert 1e-3 < float(np.median(rel)) < 0.3   # rounding, not another model


def test_weights_come_from_the_seed_alone():
    cfg = tiny("mistral-7b.l4")
    a, b, c = make_weights(cfg, 7), make_weights(cfg, 7), make_weights(cfg, 8)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["wq"], c["wq"])
    big = make_weights(cfg, 2 ** 31 + 5)
    assert not np.array_equal(big["wq"], make_weights(cfg, 5)["wq"])
