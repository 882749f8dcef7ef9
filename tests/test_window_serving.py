"""A spec with window (SLIDING) layers and expert (SPARSE) FFNs served by the
default ContinuousBatcher(kv_layout="paged"), built from the JSON spec as a
fleet replica builds it: prefill then decode through ring and pages against
the plain reference's full forward, on logits, for contexts under, at and
several times the window; what the engine holds and counts; what it refuses
for a ring; and the flash forward under a window."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.replica import build_batcher
from paddle_tpu.observability import metrics, spans
from paddle_tpu.ops import flash_attention as fa
from perfbench import check, harness as hs
from perfbench.families import exaone_moe as fam
from perfbench.weights import make_weights

W = 8       # the tiny spec's window


def tiny(**kw):
    with open(os.path.join(hs.HERE, "configs", "rehearse",
                           "k-exaone-236b.l8e16.json")) as f:
        return {**json.load(f), **kw}


def engine(cfg, seed=7, **kw):
    settings = dict(kv_layout="paged", max_batch=3, max_len=64, page_size=8,
                    prompt_buckets=[16, 32], burst=4)
    settings.update(kw)
    weights = make_weights(cfg, seed)
    return fam.engine(cfg, {"engine": settings}, weights), weights


def serve(eng, lens, seed=0):
    rng = np.random.RandomState(seed)
    prompts = {}
    for n, m in lens:
        p = rng.randint(1, 256, n).tolist()
        prompts[eng.add_request(p, max_new_tokens=m)] = p
    out = eng.run()
    return [{"prompt": prompts[r], "out": out[r]} for r in sorted(out)]


# float32 on the CPU, the program's products against the reference's
# HIGHEST: the served token is the reference's best or within rounding of
# it (PR 30's hybrid reads under 2e-4 the same way)
GAP = 2e-4

# (prompt, new tokens): the context ends under the window, at it, and at
# several times it with a ring that has wrapped (first in the prompt, then
# in the decode steps too)
CONTEXTS = [(3, 4), (5, 3), (W, 1), (W - 1, 2), (W, W), (20, 30), (30, 30),
            (2, 40)]


@pytest.fixture(scope="module")
def served():
    cfg = tiny()
    eng, weights = engine(cfg)
    return cfg, weights, eng, serve(eng, CONTEXTS)


@pytest.mark.parametrize("i", range(len(CONTEXTS)),
                         ids=[f"{p}+{n}" for p, n in CONTEXTS])
def test_engine_agrees_with_the_reference_on_logits(served, i):
    cfg, weights, _, reqs = served
    assert (len(reqs[i]["prompt"]), len(reqs[i]["out"])) == CONTEXTS[i]
    gaps = check.served_gaps(weights, cfg, [reqs[i]], pad_tokens=16,
                             pad_outputs=8)
    assert gaps["tokens"] == CONTEXTS[i][1] and gaps["served"] <= GAP


def test_what_the_engine_holds_and_counts(served):
    cfg, _, eng, reqs = served
    cache = eng._cache
    assert len(cache["k"]) == len(cache["v"]) == 1      # the FULL layer
    assert len(cache["win_k"]) == len(cache["win_v"]) == 3
    assert cache["win_k"][0].shape == (3, W, 2, 16)
    assert cache["moe_counts"][0].shape == (2, 4 + 1)
    ring = 3 * 2 * W * 2 * 16 * 4
    assert eng.stats["state_bytes"] == 3 * ring == 3 * fam.state_bytes(cfg, 4)
    assert eng.pages_in_use == 0
    tokens = sum(len(r["prompt"]) + len(r["out"]) - 1 for r in reqs)
    seen = np.asarray(jax.device_get(cache["moe_counts"][0]))
    # every real token made 4 assignments in each of the 3 sparse layers
    assert int(seen.sum()) == tokens * 4 * 3
    assert eng.stats["moe_expert_tokens"] == seen[:, :-1].sum(0).tolist()
    assert min(eng.stats["moe_expert_tokens"]) > 0
    snap = metrics.snapshot()["counters"]
    assert snap["serve.moe_assignments_total"] >= int(seen.sum())
    assert 0 < snap["serve.moe_assignments_local"] \
        < snap["serve.moe_assignments_total"]
    assert "serve.state_mb_held" in metrics.snapshot()["gauges"]


def test_dispatch_span_carries_state_and_the_bursts_assignments():
    eng, _ = engine(tiny(), seed=11)
    t0 = spans.now_ns()
    serve(eng, [(6, 9), (12, 9)], seed=1)
    args = [s.args for s in spans.records()
            if s.name == "serve.dispatch_burst" and s.t0_ns >= t0]
    ring = eng.stats["state_bytes"] // 3
    assert {a["state"] for a in args} <= {0, ring, 2 * ring}
    busy = [a for a in args if a.get("moe_local")]
    assert busy and all(a["moe_max"] <= a["moe_local"] <= 2 * 4 * 3 * 4
                        for a in busy)
    # a burst of 4 steps x 2 slots x 3 layers x 4 assignments, a quarter
    # of the experts held
    assert sum(a["moe_local"] for a in busy) > 0


REFUSED = [
    (dict(prefix_cache_pages=4), "a shared page holds K/V rows"),
    (dict(kv_layout="dense"), "only the default kv_layout='paged'"),
    (dict(kv_dtype="int8"), "quantized K/V pages beside"),
    (dict(spec_decode=True), "has overwritten the oldest row of a ring"),
]


@pytest.mark.parametrize("kw,why", REFUSED, ids=lambda x: str(x)[:24])
def test_what_cannot_hold_for_a_ring_is_refused_by_name(kw, why):
    with pytest.raises(ValueError, match=why) as e:
        engine(tiny(), **kw)
    assert "ring of K/V rows" in str(e.value)


@pytest.mark.parametrize("env", ["PADDLE_SERVE_MESH_MODEL",
                                 "PADDLE_PREFIX_CACHE_PAGES",
                                 "PADDLE_SPEC_DECODE"])
def test_a_fleet_wide_knob_is_refused_for_a_ring_too(monkeypatch, env):
    monkeypatch.setenv(env, {"PADDLE_SPEC_DECODE": "1"}.get(env, "2"))
    with pytest.raises(ValueError, match="ring of K/V rows"):
        engine(tiny())


@pytest.mark.parametrize("kw", [dict(prefill_only=True),
                                dict(kv_import={"tlen": 3, "n_pages": 1})])
def test_disaggregated_requests_are_refused_for_a_ring(kw):
    eng, _ = engine(tiny())
    with pytest.raises(ValueError, match="carries K/V pages only"):
        eng.add_request([1, 2, 3], max_new_tokens=4, **kw)


def test_experts_without_a_ring_are_refused_what_only_uniform_programs_run():
    """All layers full attention, the FFNs by kind: still only the two
    default programs walk it."""
    cfg = tiny(layer_types=["full_attention"] * 4)
    with pytest.raises(ValueError, match="per-layer FFN kind"):
        engine(cfg, prefix_cache_pages=4)
    eng, weights = engine(cfg)
    reqs = serve(eng, [(5, 6), (20, 9)])
    assert check.served_gaps(weights, cfg, reqs, pad_tokens=16,
                             pad_outputs=8)["served"] <= GAP
    assert eng.stats["state_bytes"] == 0


def test_a_preempted_request_restarts_into_its_ring():
    """A pool too small for three long requests: the youngest is preempted
    and served again from scratch, its next prefill overwriting the ring."""
    cfg = tiny()
    eng, weights = engine(cfg, num_pages=10)
    reqs = serve(eng, [(14, 30), (12, 30), (10, 30)], seed=3)
    assert eng.stats["preemptions"] >= 1
    assert check.served_gaps(weights, cfg, reqs, pad_tokens=16,
                             pad_outputs=8)["served"] <= GAP


def test_the_pool_kernels_read_and_write_a_ring_they_can():
    """head_dim 128 and 8 KV heads: the ring is a pool of one page a slot
    that `paged_decode_attention` reads and `paged_kv_scatter` writes
    (interpreted here), and the engine still serves the reference's
    tokens."""
    cfg = tiny(hidden_size=256, num_attention_heads=8, num_key_value_heads=8,
               head_dim=128, num_hidden_layers=2, sliding_window=16,
               layer_types=["sliding_attention", "full_attention"],
               mlp_layer_types=["dense", "sparse"])
    eng, weights = engine(cfg, max_batch=2, prompt_buckets=[32])
    assert eng.stats["kv_read"] == "kernel"
    reqs = serve(eng, [(20, 20), (5, 14)], seed=5)
    assert check.served_gaps(weights, cfg, reqs, pad_tokens=16,
                             pad_outputs=8)["served"] <= GAP


# ------------------------------------------ the flash forward under a window

def masked(q, k, v, window):
    L, S = q.shape[1], k.shape[1]
    s = jnp.einsum("blhd,bshd->bhls", q, k) / np.sqrt(q.shape[-1])
    i = jnp.arange(L)[:, None] + S - L
    j = jnp.arange(S)[None, :]
    seen = (j <= i) & (j > i - window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhls,bshd->blhd", p, v)


def qkv(L, S, H=2, D=128, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (1, L, H, D), dtype),
            jax.random.normal(ks[1], (1, S, H, D), dtype),
            jax.random.normal(ks[2], (1, S, H, D), dtype))


@pytest.mark.parametrize("L,S,window,bq,bk", [
    (512, 512, 128, 128, 128),      # the cell's window over four tiles
    (512, 512, 128, 256, 128),      # a q block over several kv tiles
    (384, 384, 100, 128, 128),      # a window that is no tile
    (256, 256, 1, 128, 128),        # every query sees itself alone
    (256, 512, 200, 128, 256),      # L < S: bottom-right aligned
    (256, 256, 1000, 128, 128),     # a window wider than the sequence
])
def test_flash_forward_with_a_window_is_masked_attention(L, S, window, bq,
                                                         bk):
    q, k, v = qkv(L, S)
    out, lse = fa._flash_fwd_impl(q, k, v, True, bq, bk, interpret=True,
                                  window=window)
    np.testing.assert_allclose(out, masked(q, k, v, window),
                               rtol=2e-5, atol=2e-5)
    assert np.isfinite(np.asarray(lse)).all()
    np.testing.assert_allclose(fa._fa_reference(q, k, v, True, window),
                               masked(q, k, v, window), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_no_window_is_the_forward_as_it_was_bit_for_bit(dtype):
    """`window=None` runs the loop from tile 0 under the causal mask alone:
    the same bits as the forward of `_flash_fwd_bwd` (the path every other
    caller takes), and as a window so wide that it bounds nothing."""
    q, k, v = qkv(512, 512, dtype=dtype)
    base, lse = fa._flash_fwd_impl(q, k, v, True, 128, 128, interpret=True)
    plain = fa._flash_fwd_bwd(q, k, v, True, 128, 128, True)
    wide, lse_w = fa._flash_fwd_impl(q, k, v, True, 128, 128, interpret=True,
                                     window=4096)
    assert np.array_equal(np.asarray(base, np.float32),
                          np.asarray(plain, np.float32))
    assert np.array_equal(np.asarray(base, np.float32),
                          np.asarray(wide, np.float32))
    assert np.array_equal(np.asarray(lse), np.asarray(lse_w))


def test_a_window_is_forward_only_and_needs_causal():
    q, k, v = qkv(128, 128)
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda a: fa._flash_fwd_window(a, k, v, 64, 128, 128,
                                                True).sum())(q)
    with pytest.raises(ValueError, match="causal=True"):
        fa.flash_attention_raw(q, k, v, causal=False, window=64)
    # off the TPU the entry takes the XLA reference, window and all
    np.testing.assert_allclose(
        fa.flash_attention_raw(q, k, v, causal=True, window=64),
        masked(q, k, v, 64), rtol=2e-5, atol=2e-5)


def test_first_tile_of_a_window():
    import numpy as _np
    f = lambda qi, w: int(fa._kv_first_tile(_np.int32(qi), 512, 512, 2048,
                                            2048, w))
    assert [f(i, None) for i in range(4)] == [0, 0, 0, 0]
    # q block i starts at row 512 i and sees from column 512 i - 127 on
    assert [f(i, 128) for i in range(4)] == [0, 0, 1, 2]
    assert [f(i, 1) for i in range(4)] == [0, 1, 2, 3]
    assert [f(i, 1024) for i in range(4)] == [0, 0, 0, 1]
