"""The paged burst takes the projections whose output is split into heads at
once a layer at a time (ISSUE 35): the same tokens as on the layer stacks,
for a model of one layer kind, a hybrid and an expert spec at tiny float32
sizes on the CPU; `layer_params_at` on tuples and stacks alike; what the
engine says of it (`stats["burst_weights"]`, the `serve.init` span) where it
places and where it does not (quantized weights, a mesh, the dense layout);
and the ask itself (`burst_for_layouts`), which only a TPU engine makes,
driven here on the CPU, where the compiler has no layout to choose."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference import ContinuousBatcher
from paddle_tpu.inference.replica import _spec_config, build_batcher, \
    build_params
from paddle_tpu.models.llama import (LlamaConfig, heads_at_once_leaves,
                                     layer_params_at, llama_init_params,
                                     split_layer_params)
from paddle_tpu.models.llama_paged import burst_for_layouts, \
    per_layer_weights
from paddle_tpu.observability import spans
from perfbench import harness as hs
from perfbench.families import exaone_moe
from perfbench.weights import make_weights
from tests.test_hybrid_serving import CONFIG as HYBRID

BATCHER = dict(max_batch=3, max_len=64, page_size=8, prompt_buckets=[16, 32],
               burst=4)
NAMES = {"uniform": ["wq", "wk", "wv"], "hybrid": ["wv", "lin_wg"],
         "expert": ["wq", "wk", "wv"]}


def _model(case):
    """(LlamaConfig, params, a builder of engines on them)."""
    if case == "uniform":
        cfg = LlamaConfig.tiny(dtype=jnp.float32)
        params = llama_init_params(cfg, jax.random.PRNGKey(3))
        return cfg, params, lambda **kw: ContinuousBatcher(
            cfg, params, **{**BATCHER, **kw})
    if case == "hybrid":
        spec = json.loads(json.dumps({"config": HYBRID, "seed": 1}))
        params = build_params(spec)
    else:
        with open(os.path.join(hs.HERE, "configs", "rehearse",
                               "k-exaone-236b.l8e16.json")) as f:
            tiny = json.load(f)
        spec = {"config": exaone_moe.model_spec(tiny, BATCHER["max_len"])}
        params = make_weights(tiny, 7)
    return _spec_config(spec), params, lambda **kw: build_batcher(
        {**spec, "batcher": {**BATCHER, **kw}}, params=params)


def _serve(eng, seed=0, lens=((5, 9), (20, 12), (30, 6), (9, 20))):
    rng = np.random.RandomState(seed)
    rids = [eng.add_request(rng.randint(1, 250, n).tolist(),
                            max_new_tokens=m) for n, m in lens]
    out = eng.run()
    return [out[r] for r in rids]


@pytest.fixture(scope="module", params=["uniform", "hybrid", "expert"])
def model(request):
    return (request.param,) + _model(request.param)


def test_tokens_on_per_layer_leaves_equal_those_on_the_stacks(model):
    case, cfg, params, build = model
    eng = build()
    handed = {k for k, v in eng._burst_params.items() if isinstance(v, tuple)}
    assert handed == set(NAMES[case]) == set(eng.stats["burst_weights"]["names"])
    walked = cfg.layer_types is not None or cfg.mlp_layer_types is not None
    # a pattern's prefill walks by index and takes the same leaves: one form
    assert (eng._params is eng._burst_params) == walked
    stacks = build()
    stacks._params = stacks._burst_params = params     # as before ISSUE 35
    assert _serve(eng) == _serve(stacks)
    assert eng.stats["decode_steps"] == stacks.stats["decode_steps"] > 0


def test_layer_params_at_answers_tuples_and_stacks_alike(model):
    _, cfg, params, _ = model
    stacked = split_layer_params(params)[0]
    tuples = split_layer_params(per_layer_weights(params, cfg))[0]
    assert any(isinstance(v, tuple) for v in tuples.values())
    for layer in range(cfg.num_hidden_layers):
        a = layer_params_at(stacked, cfg, layer)
        b = layer_params_at(tuples, cfg, layer)
        assert a.keys() == b.keys()
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


def test_the_ask_on_the_cpu_names_no_layout_and_its_program_serves(model):
    """What a TPU engine does at load, driven by hand: the burst compiled
    with the per-layer leaves' layouts left to the compiler is the program
    of that block table, and on the CPU it asks for nothing."""
    _, cfg, params, build = model
    eng, plain = build(), build()
    P = eng._page_buckets[-1]
    program = burst_for_layouts(
        per_layer_weights(params, cfg), eng._cache, eng.B, P,
        params["embed_tokens"].sharding, **eng._burst_static)
    asked = program.input_formats[0][0]
    placed = per_layer_weights(params, cfg, asked)
    for name in eng.stats["burst_weights"]["names"]:
        assert len(asked[name]) == len(placed[name]) == params[name].shape[0]
        assert all(a.format.layout == params[name][0].format.layout
                   for a in placed[name])
    eng._burst_params, eng._burst_programs = placed, {P: program}
    long = ((30, 30), (25, 35))         # contexts that reach the widest table
    assert _serve(eng, lens=long) == _serve(plain, lens=long)
    assert P in eng.stats["page_buckets_used"]


@pytest.mark.parametrize("case,config,names", [
    ("one kind", dict(), ("wq", "wk", "wv")),
    ("per-head QK-norm", dict(qk_norm_per_head=True), ("wq", "wk", "wv")),
    ("QK-norm over the whole projection", dict(qk_norm=True), ("wv",)),
    ("hybrid", HYBRID, ("wv", "lin_wg")),
])
def test_the_rule_names_the_projections_split_into_heads_at_once(case, config,
                                                                 names):
    cfg = _spec_config({"config": config}) if case == "hybrid" \
        else LlamaConfig.tiny(**config)
    assert heads_at_once_leaves(cfg) == names


def test_stat_and_serve_init_span_say_what_was_handed_over():
    cfg, params, build = _model("uniform")
    mark = len(spans.records())
    eng = build()
    bw = eng.stats["burst_weights"]
    nbytes = sum(params[k].nbytes for k in ("wq", "wk", "wv"))
    assert bw == {"leaves": 3 * cfg.num_hidden_layers, "bytes": nbytes,
                  "names": ["wq", "wk", "wv"], "relaid": 0, "relaid_bytes": 0,
                  "relaid_names": [], "note": bw["note"]}
    assert "no compiler to ask" in bw["note"] and eng._burst_programs == {}
    init = [s for s in spans.records()[mark:] if s.name == "serve.init"][-1]
    assert init.args == {"per_layer_mb": round(nbytes / 1e6, 3),
                         "relaid_mb": 0.0}


@pytest.mark.parametrize("why,kw,env", [
    ("quantized weights", dict(precision="int8"), {}),
    ("a serving mesh", dict(), {"PADDLE_SERVE_MESH_MODEL": "2"}),
    ("kv_layout='dense'", dict(kv_layout="dense"), {}),
])
def test_an_engine_that_does_not_place_says_so_and_runs_as_before(
        monkeypatch, why, kw, env):
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    cfg, params, build = _model("uniform")
    mark = len(spans.records())
    eng = build(**kw)
    bw = eng.stats["burst_weights"]
    assert why in bw["note"] and bw["leaves"] == bw["relaid"] == 0
    assert bw["names"] == [] and eng._burst_params is eng._params
    assert not any(isinstance(v, tuple) for v in eng._params.values())
    init = [s for s in spans.records()[mark:] if s.name == "serve.init"][-1]
    assert init.args == {"per_layer_mb": 0.0, "relaid_mb": 0.0}
    out = _serve(eng)
    for k in env:
        monkeypatch.delenv(k)
    if "precision" not in kw:       # same arithmetic: the placed engine's
        assert out == _serve(build())
    assert [len(o) for o in out] == [9, 12, 6, 20]


def test_annotate_adds_to_the_innermost_open_span_only():
    spans.annotate(lost=1)              # outside any span: nothing
    mark = len(spans.records())

    @spans.traced("outer.fn", cat="user", fixed=1)
    def fn():
        with spans.span("inner", cat="user"):
            spans.annotate(inner=2)
        spans.annotate(learned=3)

    fn()
    fn()                                # the decorator's own args stay its own
    got = {s.name: s.args for s in spans.records()[mark:]}
    assert got["inner"] == {"inner": 2}
    assert got["outer.fn"] == {"fixed": 1, "learned": 3}


def test_a_one_kind_prefill_handed_per_layer_leaves_walks_to_the_same_pages():
    """`llama_paged_prefill_slot` scans a model of one layer kind only when
    every leaf is a stack; handed tuples it walks by index as a pattern's
    does (measured on the chip in PR 35: 1 % more tokens a second, 14 s more
    set-up, so the engine hands such a prefill the stacks)."""
    from paddle_tpu.models.llama_paged import init_paged_kv_cache, \
        llama_paged_prefill_slot
    cfg, params, _ = _model("uniform")
    toks = jnp.asarray(np.random.RandomState(1).randint(1, 250, 16),
                       jnp.int32)
    pages = jnp.asarray([3, 1], jnp.int32)

    def run(p):
        return llama_paged_prefill_slot(
            p, init_paged_kv_cache(cfg, 6, 8, max_batch=2), toks, pages,
            jnp.int32(11), jax.random.PRNGKey(0), config=cfg)

    (first, cache), (first_t, cache_t) = run(params), run(
        per_layer_weights(params, cfg))
    assert int(first) == int(first_t)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(cache_t)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
