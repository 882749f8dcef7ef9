"""A model spec with a layer pattern (gated delta-rule layers beside a
full-attention layer) served by ContinuousBatcher(kv_layout="paged"), at
tiny float32 sizes on the CPU: the engine's tokens through prefill and
decode against the plain reference's full forward (perfbench/ref/
olmo_hybrid.py, which imports nothing of the program), with slot re-use,
preemption and both prompt buckets; every refusal by name; and a spec
without a pattern untouched."""
import dataclasses
import json
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.inference import ContinuousBatcher
from paddle_tpu.inference.replica import (_spec_config, build_batcher,
                                          build_params)
from paddle_tpu.models.llama import (LlamaConfig, llama_forward,
                                     llama_init_params)
from paddle_tpu.observability import metrics, spans
from perfbench.ref import olmo_hybrid as ref

CONFIG = dict(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4,
    head_dim=16, max_position_embeddings=256, rms_norm_eps=1e-6,
    rope_theta=None, qk_norm=True, norm_placement="post",
    layer_types=["linear_attention"] * 3 + ["full_attention"],
    linear_num_key_heads=4, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, dtype="float32")
BATCHER = dict(max_batch=3, max_len=96, page_size=8, prompt_buckets=[16, 32],
               burst=4)
TOL = 2e-4      # float32 end to end: the served token is the reference's


def spec(**batcher):
    # through JSON: what a fleet replica is started from
    return json.loads(json.dumps({"config": CONFIG, "seed": 1,
                                  "batcher": {**BATCHER, **batcher}}))


@pytest.fixture(scope="module")
def params():
    """The seeded tree, with every gain and gate parameter moved off its
    neutral value so that each one is seen, and the embedding at unit scale
    (at 64 wide, rows of deviation 0.02 would leave every mixer's output
    under the norms' eps, where it hardly matters what a mixer computes)."""
    p = build_params(spec())
    p["embed_tokens"] = p["embed_tokens"] * 50.0
    key = jax.random.PRNGKey(7)
    for i, name in enumerate(sorted(p)):
        if p[name].dtype == jnp.float32 and name in (
                "ln1", "ln2", "norm", "q_norm", "k_norm", "lin_norm",
                "lin_A_log", "lin_dt_bias"):
            scale = 0.5 if name in ("lin_A_log", "lin_dt_bias") else 0.05
            p[name] = p[name] + scale * jax.random.normal(
                jax.random.fold_in(key, i), p[name].shape, jnp.float32)
    return p


def gaps(params, finished):
    """By how much each served token's logit lies under the reference's
    best at its position, at the worst, over the requests."""
    rcfg = ref.hashable(CONFIG)
    worst = 0.0
    for req in finished:
        plen, n = len(req.prompt), len(req.out)
        toks = np.zeros(96, np.int32)
        toks[:plen + n] = list(req.prompt) + list(req.out)
        picks = np.zeros(48, np.int32)
        picks[:n] = req.out
        best, at, _ = ref.served_logits(
            params, jnp.asarray(toks), jnp.int32(plen - 1),
            jnp.asarray(picks), cfg=rcfg, dot="f32", n=48)
        worst = max(worst, float(jnp.max((best - at)[:n])))
    return worst


def serve(eng, lengths, seed=0):
    r = np.random.default_rng(seed)
    rids = [eng.add_request(r.integers(1, 256, n).tolist(), max_new_tokens=m)
            for n, m in lengths]
    while eng.pending:
        eng.step()
    done = eng.take_finished()
    assert sorted(done) == rids
    assert all(done[rid].reason == "complete" and len(done[rid].out) == m
               for rid, (_, m) in zip(rids, lengths))
    return [done[rid] for rid in rids]


MIX = [(5, 9), (20, 7), (31, 12), (9, 5), (17, 20), (32, 3), (1, 6), (16, 11)]


def test_engine_serves_the_references_tokens_with_slot_reuse(params):
    """8 requests over 3 slots, prompts in both buckets: a slot's state is
    overwritten by its next prefill, and the cache holds pools for the one
    full layer and a state for each of the three linear ones."""
    eng = build_batcher(spec(), params=params)
    assert {k: len(v) for k, v in eng._cache.items()} == {
        "k": 1, "v": 1, "state": 3, "conv": 3}
    assert eng._cache["state"][0].shape == (3, 4, 16, 8)
    assert eng._cache["state"][0].dtype == jnp.float32
    assert eng._cache["conv"][0].shape == (3, 3, 2 * 4 * 8 + 4 * 16)
    finished = serve(eng, MIX)
    assert gaps(params, finished) < TOL
    assert eng._cache["state"][0].dtype == jnp.float32  # and stays so
    assert eng.stats["prefills"] == len(MIX) and eng.pages_in_use == 0
    assert eng.stats["state_bytes"] == 3 * 3 * (4 * 16 * 8 * 4 + 3 * 128 * 4)


def test_reference_sees_a_wrong_state(params):
    """The comparison can tell: the same requests judged with another
    model's parameters read far over the tolerance."""
    eng = build_batcher(spec(), params=params)
    finished = serve(eng, MIX[:3])
    other = dict(params, lin_wo=-params["lin_wo"])
    assert gaps(other, finished) > 50 * TOL


def test_preemption_restarts_a_request_from_a_fresh_state(params):
    """A pool too small for three long requests: the youngest is preempted,
    prefilled again into whatever slot is free, and its tokens are the
    reference's all the same."""
    lengths = [(31, 40), (30, 40), (29, 40)]
    small = build_batcher(spec(num_pages=21), params=params)
    finished = serve(small, lengths)
    assert small.stats["preemptions"] >= 1
    assert gaps(params, finished) < TOL
    roomy = serve(build_batcher(spec(), params=params), lengths)
    assert [q.out for q in finished] == [q.out for q in roomy]


def test_pages_and_budget_count_the_full_layers_only():
    from paddle_tpu.inference.paging import pages_for_budget
    from paddle_tpu.models.llama_paged import (page_bytes,
                                               paged_kv_bytes_per_token)
    cfg = _spec_config(spec())
    assert (cfg.num_kv_layers, cfg.num_linear_layers) == (1, 3)
    assert page_bytes(cfg, 8) == 2 * 1 * 8 * 4 * 16 * 4
    assert paged_kv_bytes_per_token(cfg, 5, 8) == 5 * page_bytes(cfg, 8)
    eng = build_batcher(spec(pool_hbm_bytes=40 * page_bytes(cfg, 8)))
    assert eng._alloc.num_pages == pages_for_budget(
        40 * page_bytes(cfg, 8), page_bytes(cfg, 8)) == 40
    uniform = dataclasses.replace(cfg, layer_types=None)
    assert page_bytes(uniform, 8) == 4 * page_bytes(cfg, 8)


def test_state_is_reported_on_the_span_and_the_gauge(params):
    eng = build_batcher(spec(), params=params)
    per_slot = eng.stats["state_bytes"] // 3
    assert per_slot == _spec_config(spec()).state_bytes_per_request()
    t0 = time.time_ns()     # the spans' clock: the ring holds older ones
    serve(eng, [(9, 12), (12, 12)])
    live = [s.args["state"] for s in spans.records()
            if s.name == "serve.dispatch_burst" and s.t0_ns >= t0]
    assert 2 * per_slot in live and set(live) <= {0, per_slot, 2 * per_slot}
    assert "serve.state_mb_held" in metrics.snapshot()["gauges"]


REFUSED = [
    (dict(prefix_cache_pages=4), "a shared page holds K/V rows"),
    (dict(kv_layout="ragged"), "unknown kv_layout 'ragged'"),
    (dict(kv_layout="dense"), "only the default kv_layout='paged'"),
    (dict(kv_dtype="int8"), "quantized K/V pages beside"),
    (dict(spec_decode=True), "cannot be rewound out of a recurrent state"),
]


@pytest.mark.parametrize("kw,why", REFUSED, ids=lambda x: str(x)[:24])
def test_what_cannot_hold_for_a_state_is_refused_by_name(kw, why):
    with pytest.raises(ValueError, match=why):
        build_batcher(spec(**kw))


@pytest.mark.parametrize("env,why", [
    ("PADDLE_SERVE_MESH_MODEL", "the per-slot state has no sharding rule"),
    ("PADDLE_PREFIX_CACHE_PAGES", "a shared page holds K/V rows"),
    ("PADDLE_SPEC_DECODE", "cannot be rewound out of a recurrent state"),
    ("PADDLE_SERVE_KV_DTYPE", "quantized K/V pages beside")])
def test_a_fleet_wide_knob_is_refused_too(monkeypatch, env, why):
    monkeypatch.setenv(env, {"PADDLE_SERVE_KV_DTYPE": "int8",
                             "PADDLE_SPEC_DECODE": "1"}.get(env, "2"))
    with pytest.raises(ValueError, match=why):
        build_batcher(spec())


@pytest.mark.parametrize("kw", [dict(prefill_only=True),
                                dict(kv_import={"tlen": 3, "n_pages": 1})])
def test_disaggregated_requests_are_refused_by_name(kw):
    eng = build_batcher(spec())
    with pytest.raises(ValueError, match="carries K/V pages only"):
        eng.add_request([1, 2, 3], max_new_tokens=4, **kw)


def test_paths_of_one_layer_kind_say_so():
    cfg = _spec_config(spec())
    p = llama_init_params(cfg)
    with pytest.raises(NotImplementedError, match="layer pattern"):
        llama_forward(p, jnp.zeros((1, 8), jnp.int32), cfg)
    with pytest.raises(ValueError, match="num_hidden_layers=4 entries"):
        LlamaConfig.tiny(layer_types=("full_attention",))
    with pytest.raises(ValueError, match="more value heads than key heads"):
        LlamaConfig.tiny(layer_types=("linear_attention",) * 4,
                         linear_num_key_heads=2, linear_num_value_heads=4,
                         linear_key_head_dim=8, linear_value_head_dim=8)


# ------------------------------------------- a spec without a pattern

def test_a_stated_head_dim_is_the_derived_one():
    a, b = LlamaConfig.tiny(), LlamaConfig.tiny(head_dim=16)
    assert a == b and hash(a) == hash(b) and a.head_dim == 16
    assert (a.num_kv_layers, a.is_recurrent, a.state_bytes_per_request()) \
        == (4, False, 0)
    assert dataclasses.replace(a, num_hidden_layers=2).head_dim == 16


def test_no_pattern_is_the_llama_programs_bit_for_bit():
    """The pattern walk with every layer FULL computes what the Llama
    programs compute: a decode step's logits and pools bit for bit (both
    unrolled), a prefill's to float32 round-off (a scan against a walk);
    and the engine's tokens are llama_generate's, as ever."""
    from paddle_tpu.models.llama_decode import llama_generate
    from paddle_tpu.models.llama_paged import (_paged_decode_step_slots,
                                               init_paged_kv_cache,
                                               llama_paged_prefill_slot)
    cfg = LlamaConfig.tiny()
    walk = dataclasses.replace(cfg, layer_types=("full_attention",) * 4)
    p = llama_init_params(cfg, jax.random.PRNGKey(3))
    toks = jnp.asarray(np.random.default_rng(1).integers(1, 256, 16),
                       jnp.int32)
    pages = jnp.asarray([3, 1], jnp.int32)
    out = {}
    for name, c in (("llama", cfg), ("walk", walk)):
        cache = init_paged_kv_cache(c, 6, 8, max_batch=2)
        first, cache = llama_paged_prefill_slot(
            p, cache, toks, pages, jnp.int32(13), jax.random.PRNGKey(0),
            config=c, slot=jnp.int32(0))
        bt = jnp.asarray([[3, 1], [0, 0]], jnp.int32)
        logits, cache2 = _paged_decode_step_slots(
            p, jax.tree.map(jnp.copy, cache), bt,
            jnp.asarray([13, 0], jnp.int32), jnp.asarray([first, 0]), c)
        out[name] = (first, cache, logits, cache2)
    assert int(out["llama"][0]) == int(out["walk"][0])
    for a, b in zip(jax.tree.leaves(out["llama"][1]),
                    jax.tree.leaves(out["walk"][1])):
        np.testing.assert_allclose(a, b, atol=1e-6)
    # the decode step from ONE cache: bit for bit
    cache = out["llama"][1]
    bt = jnp.asarray([[3, 1], [0, 0]], jnp.int32)
    args = (bt, jnp.asarray([13, 0], jnp.int32),
            jnp.asarray([int(out["llama"][0]), 0], jnp.int32))
    la, ca = _paged_decode_step_slots(p, cache, *args, cfg)
    lb, cb = _paged_decode_step_slots(p, cache, *args, walk)
    np.testing.assert_array_equal(la, lb)
    for a, b in zip(jax.tree.leaves(ca), jax.tree.leaves(cb)):
        np.testing.assert_array_equal(a, b)

    eng = ContinuousBatcher(cfg, p, max_batch=2, max_len=64, page_size=8,
                            prompt_buckets=(16,), burst=4)
    prompt = [int(t) for t in toks[:11]]
    rid = eng.add_request(prompt, max_new_tokens=9)
    got = eng.run()[rid]
    want = llama_generate(p, jnp.asarray([prompt], jnp.int32), cfg, 9)[0]
    assert list(got) == [int(t) for t in want]
    assert "state" not in eng._cache and eng.stats["state_bytes"] == 0


# ------------------------------------------ a pool whose rows are padded

def test_a_pool_of_12_kv_heads_is_padded_where_the_kernel_reads():
    """12 KV heads x 128: the decode kernel reads the pool, whose rows are
    padded to 16 heads (whole sublane tiles: llama_paged.pool_kv_heads, the
    one place that decides it) for the two programs the default layout runs
    alone; the tokens are llama_generate's. Every other reader of such a
    pool is refused by name; quantized pages (the gather reads them) keep
    the model's own geometry."""
    from paddle_tpu.models.llama_decode import llama_generate
    from paddle_tpu.models.llama_paged import page_bytes, pool_kv_heads
    cfg = LlamaConfig.tiny(hidden_size=1536, num_attention_heads=12,
                           num_key_value_heads=12, num_hidden_layers=2)
    assert (pool_kv_heads(cfg), pool_kv_heads(cfg, "int8"),
            pool_kv_heads(cfg, None, mesh=object())) == (16, 12, 12)
    p = llama_init_params(cfg, jax.random.PRNGKey(5))
    kw = dict(max_batch=2, max_len=64, page_size=8, prompt_buckets=(16,),
              burst=4)
    eng = ContinuousBatcher(cfg, p, **kw)
    assert eng._cache["k"][0].shape == (17, 8, 16, 128)
    assert eng.stats["kv_read"] == "kernel"
    assert eng._page_bytes == page_bytes(cfg, 8) \
        == 2 * 2 * 8 * 16 * 128 * jnp.dtype(cfg.dtype).itemsize
    r = np.random.default_rng(2)
    prompts = [r.integers(1, 256, n).tolist() for n in (11, 16, 5)]
    rids = [eng.add_request(q, max_new_tokens=9) for q in prompts]
    got = eng.run()
    for rid, q in zip(rids, prompts):
        want = llama_generate(p, jnp.asarray([q], jnp.int32), cfg, 9)[0]
        assert list(got[rid]) == [int(t) for t in want]
    with pytest.raises(ValueError, match="pads KV heads 12 -> 16"):
        eng.add_request([1, 2, 3], max_new_tokens=4, prefill_only=True)
    for refused in (dict(prefix_cache_pages=8), dict(spec_decode=True)):
        with pytest.raises(ValueError, match="pads KV heads 12 -> 16"):
            ContinuousBatcher(cfg, p, **refused, **kw)
    assert ContinuousBatcher(cfg, p, kv_dtype="int8",
                             **kw)._cache["k"][0].shape[2] == 12
