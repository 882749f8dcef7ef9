"""The dropless expert layer (ops/moe_dropless.py) against the plain reference
(perfbench/ref/exaone_moe.py) at tiny float32 widths: even and skewed
routing, an expert that takes most tokens and one that takes none, the
shares of all devices adding up to the uncut layer, the selection bias, the
counts, and what LlamaConfig says of a spec it cannot hold."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models.llama import LlamaConfig, layer_params_at, \
    llama_init_params, split_layer_params
from paddle_tpu.ops.moe_dropless import moe_dropless, route
from perfbench.ref import exaone_moe as ref

D, FE, E, K = 64, 32, 16, 4


def spec(**kw):
    d = dict(hidden_size=D, intermediate_size=96, num_hidden_layers=2,
             mlp_layer_types=("dense", "sparse"), num_experts=E,
             num_experts_per_tok=K, moe_intermediate_size=FE,
             num_shared_experts=1, scoring_func="sigmoid",
             norm_topk_prob=True, routed_scaling_factor=2.5)
    d.update(kw)
    return LlamaConfig.tiny(**d)


def weights(cfg, seed=0, skew=None):
    """The sparse layer's parameters [no stack dim], float32, with a bias
    drawn at a tenth of the scores' spread; `skew` = (crowded, empty):
    router columns that draw most tokens, and none."""
    p = llama_init_params(cfg, jax.random.PRNGKey(seed))
    lp = layer_params_at(split_layer_params(p)[0], cfg, 1)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 100))
    lp["gate_w"] = jax.random.normal(k1, (D, E), jnp.float32) * 0.2
    lp["gate_bias"] = jax.random.normal(k2, (E,), jnp.float32) * 0.03
    if skew:
        crowded, empty = skew
        lp["gate_bias"] = lp["gate_bias"].at[crowded].set(3.0) \
            .at[empty].set(-3.0)
    return lp


def ref_cfg(cfg, held):
    return {"k": K, "scoring": cfg.scoring_func,
            "norm_topk": cfg.norm_topk_prob,
            "scale": cfg.routed_scaling_factor,
            "shared": cfg.num_shared_experts, "held": held}


def ref_layer(g, lp, cfg, held):
    """The reference's sparse FFN over the experts `held` of lp's E."""
    first, count = held
    p = {k: v[None] for k, v in lp.items()}
    for k in ("moe_w_gate", "moe_w_up", "moe_w_down"):
        p[k] = p[k][:, first:first + count]
    return ref.sparse_ffn(g, p, jnp.int32(0), ref_cfg(cfg, held),
                          ref.f32_dot)[0]


def tokens(n, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, D), jnp.float32)


EXPERT_LEAVES = ("moe_w_gate", "moe_w_up", "moe_w_down")


def held_slice(lp, first, count):
    out = dict(lp)
    for k in EXPERT_LEAVES:
        out[k] = lp[k][first:first + count]
    return out


def layer_of_one(g, lp, cfg, **kw):
    """The layer as the engine calls it: the expert weights stacked over the
    sparse layers (here a stack of this one), and its place in the stack."""
    stacked = {**lp, **{k: lp[k][None] for k in EXPERT_LEAVES}}
    return moe_dropless(g, stacked, cfg, 0, **kw)


# a float32 product on the CPU against the reference's HIGHEST: the same
# arithmetic in another order, a few ulp of sums over 64 and 32 terms
TOL = dict(rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("n", [1, 7, 40, 300])
@pytest.mark.parametrize("held", [(0, E), (4, 4), (12, 4)])
def test_layer_agrees_with_the_reference(n, held):
    cfg = spec(experts_held=held)
    lp = weights(spec())
    g = tokens(n)
    y, counts = layer_of_one(g, held_slice(lp, *held), cfg)
    np.testing.assert_allclose(y, ref_layer(g, lp, cfg, held), **TOL)
    experts, _ = route(g, lp["gate_w"], lp["gate_bias"], cfg)
    here = np.bincount(np.asarray(experts).ravel(), minlength=E)
    first, count = held
    assert counts[:count].tolist() == here[first:first + count].tolist()
    assert int(counts.sum()) == n * K       # the rest went elsewhere


@pytest.mark.parametrize("n", [5, 64, 600])
def test_skewed_routing_one_expert_crowded_one_empty(n):
    """No capacity: the crowded expert takes every token, the empty one
    none, and the result is still the reference's."""
    held = (4, 8)
    cfg = spec(experts_held=held)
    lp = weights(spec(), skew=(6, 9))
    g = tokens(n, seed=2)
    y, counts = layer_of_one(g, held_slice(lp, *held), cfg)
    assert int(counts[6 - 4]) == n and int(counts[9 - 4]) == 0
    np.testing.assert_allclose(y, ref_layer(g, lp, cfg, held), **TOL)


def test_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """Four devices hold four experts each: their routed parts and the
    shared expert counted ONCE are the reference's layer with all 16 held
    (the cut of a configuration leaves out what the absent shares add, in
    program and reference alike)."""
    lp = weights(spec(), seed=3)
    g = tokens(50, seed=4)
    whole = ref_layer(g, lp, spec(), (0, E))
    shared = ref.swiglu(g, lp["shared_w_gate"], lp["shared_w_up"],
                        lp["shared_w_down"], ref.f32_dot)
    parts, seen = [], 0
    for first in range(0, E, 4):
        cfg = spec(experts_held=(first, 4))
        y, counts = layer_of_one(g, held_slice(lp, first, 4), cfg)
        parts.append(y - shared)            # every device computes shared
        seen += int(counts[:4].sum())
    assert seen == 50 * K                   # every assignment lands once
    np.testing.assert_allclose(sum(parts) + shared, whole,
                               rtol=5e-5, atol=5e-6)
    # and one share alone is NOT the layer
    assert float(jnp.max(jnp.abs(parts[0] + shared - whole))) > 1e-3


def test_selection_bias_changes_selections_and_not_weights():
    cfg = spec()
    lp = weights(cfg, seed=5)
    g = tokens(200, seed=6)
    with_b, w_b = route(g, lp["gate_w"], lp["gate_bias"], cfg)
    without, _ = route(g, lp["gate_w"], jnp.zeros(E), cfg)
    changed = int(jnp.sum(jnp.sort(with_b, -1) != jnp.sort(without, -1)))
    assert 0 < changed < with_b.size // 2
    # the weight is the score, never score + bias: 2.5 x normalised
    np.testing.assert_allclose(w_b.sum(-1), 2.5, rtol=1e-6)
    y_b, _ = layer_of_one(g, lp, cfg)
    y_0, _ = layer_of_one(g, dict(lp, gate_bias=jnp.zeros(E)), cfg)
    assert float(jnp.max(jnp.abs(y_b - y_0))) > 1e-3
    np.testing.assert_allclose(y_b, ref_layer(g, lp, cfg, (0, E)), **TOL)


def test_unreal_tokens_route_nowhere():
    cfg = spec(experts_held=(0, 8))
    lp = held_slice(weights(spec()), 0, 8)
    g = tokens(12)
    valid = jnp.arange(12) < 5
    y, counts = layer_of_one(g, lp, cfg, valid=valid)
    y5, counts5 = layer_of_one(g[:5], lp, cfg)
    assert counts.tolist() == counts5.tolist() and int(counts.sum()) == 5 * K
    np.testing.assert_allclose(y[:5], y5, **TOL)


@pytest.mark.parametrize("kw", [dict(scoring_func="softmax"),
                                dict(norm_topk_prob=False),
                                dict(num_shared_experts=0),
                                dict(num_shared_experts=2)])
def test_the_routers_published_forms(kw):
    cfg = spec(**kw)
    lp = weights(cfg, seed=7)
    g = tokens(33, seed=8)
    y, _ = layer_of_one(g, lp, cfg)
    np.testing.assert_allclose(y, ref_layer(g, lp, cfg, (0, E)), **TOL)


def test_stacked_weights_are_read_in_place():
    """The expert weights stay stacked over the sparse layers and the
    products read this layer's groups only: a layer inside a stack of two
    gives what it gives as a stack of one."""
    cfg = spec(num_hidden_layers=3, mlp_layer_types=("dense", "sparse",
                                                     "sparse"))
    p = llama_init_params(cfg, jax.random.PRNGKey(9))
    layer_p = split_layer_params(p)[0]
    g = tokens(20, seed=10)
    for layer in (1, 2):
        lp = layer_params_at(layer_p, cfg, layer)
        alone, c1 = layer_of_one(g, lp, cfg)
        stacked = {**lp, **{k: layer_p[k] for k in EXPERT_LEAVES}}
        y, c2 = moe_dropless(g, stacked, cfg, layer - 1)
        assert c1.tolist() == c2.tolist()
        np.testing.assert_array_equal(y, alone)


def test_the_router_scores_its_input_before_the_rounding():
    """The layer takes the float32 normed stream: the router scores it as it
    is, the experts take it rounded to the compute dtype. Here a token whose
    8th and 9th experts the bfloat16 rounding would swap keeps the float32
    selection."""
    cfg = spec(dtype=jnp.bfloat16)
    lp = weights(spec(), seed=11)
    g = tokens(20000, seed=12)
    as_f32, _ = route(g, lp["gate_w"], lp["gate_bias"], cfg)
    rounded, _ = route(g.astype(jnp.bfloat16), lp["gate_w"],
                       lp["gate_bias"], cfg)
    swapped = np.flatnonzero(np.any(
        np.sort(as_f32, -1) != np.sort(rounded, -1), axis=-1))
    assert 0 < len(swapped) < 2000
    y, counts = layer_of_one(g[swapped[:16]], lp, cfg)
    assert y.dtype == jnp.bfloat16
    here = [np.bincount(np.asarray(sel)[swapped[:16]].ravel(), minlength=E)
            for sel in (as_f32, rounded)]
    assert counts[:E].tolist() == here[0].tolist() != here[1].tolist()


# ------------------------------------------------- what a spec may state

BAD = [
    (dict(layer_types=("full_attention",)), "each 'full_attention', "
     "'linear_attention' or 'sliding_attention'"),
    (dict(layer_types=("window",) * 4), "num_hidden_layers=4 entries"),
    (dict(layer_types=("sliding_attention",) * 4), "sliding_window >= 1"),
    (dict(mlp_layer_types=("dense",)), "each 'dense' or 'sparse'"),
    (dict(mlp_layer_types=("sparse",) * 4), "num_experts_per_tok=2 of "
     "num_experts=0"),
    (dict(layer_types=("full_attention",) * 4, num_experts=4),
     "served by the dropless layer only"),
    (dict(num_experts=8, experts_held=(6, 4)), "no range of the 8 experts"),
    (dict(scoring_func="tanh"), "'softmax' or 'sigmoid'"),
    (dict(rope_layer_types=("sliding_attention",)), "go with a layer "
     "pattern"),
    (dict(layer_types=("full_attention",) * 4,
          rope_layer_types=("linear_attention",)), "has no rotation"),
]


@pytest.mark.parametrize("kw,why", BAD, ids=lambda x: str(x)[:28])
def test_config_errors_name_the_kinds(kw, why):
    with pytest.raises(ValueError, match=why):
        LlamaConfig.tiny(**kw)


def test_three_kinds_answer_for_their_state():
    cfg = LlamaConfig.tiny(
        num_hidden_layers=8, layer_types=("sliding_attention",) * 3
        + ("full_attention",) + ("linear_attention", "sliding_attention",
                                 "sliding_attention", "full_attention"),
        sliding_window=8, linear_num_key_heads=2, linear_num_value_heads=2,
        linear_key_head_dim=8, linear_value_head_dim=16,
        mlp_layer_types=("dense",) + ("sparse",) * 7, num_experts=8,
        num_experts_per_tok=2, experts_held=(2, 4))
    assert (cfg.num_kv_layers, cfg.num_linear_layers, cfg.num_sliding_layers,
            cfg.num_attn_layers) == (2, 1, 5, 7)
    assert cfg.kind_index(3) == ("full_attention", 0)
    assert cfg.kind_index(6) == ("sliding_attention", 4)
    assert cfg.kind_index(4) == ("linear_attention", 0)
    assert [cfg.attn_index(i) for i in (0, 3, 5, 7)] == [0, 3, 4, 6]
    assert cfg.ffn_index(0) == ("dense", 0) and cfg.ffn_index(5) == (
        "sparse", 4)
    assert cfg.held == (2, 4) and cfg.is_recurrent and cfg.has_ring
    ring = 2 * 8 * 2 * 16 * 4               # K and V, 8 rows, 2 heads of 16
    assert cfg.ring_shapes(3)["win_k"] == ((3, 8, 2, 16), jnp.float32)
    lin = dataclasses.replace(cfg, layer_types=("linear_attention",) * 8)
    assert cfg.state_bytes_per_request() == 5 * ring \
        + lin.state_bytes_per_request() // 8
    assert "recurrent state" in cfg.slot_state and "ring" in cfg.slot_state
    assert LlamaConfig.tiny().slot_state == ""
    assert cfg.rotates("full_attention") and dataclasses.replace(
        cfg, rope_layer_types=("sliding_attention",)).rotates(
            "full_attention") is False
