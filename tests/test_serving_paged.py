"""Paged KV-cache serving (PR 3 tentpole).

The contracts under test:
  * EQUIVALENCE — the paged ContinuousBatcher (block-table pool,
    models/llama_paged.py) is token-identical to BOTH the dense-slot
    batcher and per-request ``llama_generate`` at temperature=0, across
    mixed prompt lengths, staggered admission/retirement, page-pool
    stalls, and mid-flight preemption.
  * MEMORY — cache HBM is ``num_pages × page_size`` rows, decoupled from
    ``max_batch × max_len``: a paged engine admits MORE concurrent
    requests than the dense layout could at an equal row budget, and a
    starved pool queues (and preempts) instead of crashing.
  * INVENTORY — compiled executables stay O(prompt buckets + page
    buckets), independent of request count (measured off the jit caches).
  * RESILIENCE — PADDLE_CHAOS faults at serve.admit / serve.burst retire
    requests with partial output; the scheduler never wedges.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.distributed.resilience import chaos
from paddle_tpu.inference import ContinuousBatcher
from paddle_tpu.inference.paging import (PageAllocator, default_page_buckets,
                                         pages_for)
from paddle_tpu.models.llama import LlamaConfig, llama_init_params
from paddle_tpu.models.llama_decode import llama_generate


@pytest.fixture(scope="module")
def small_model():
    cfg = LlamaConfig.tiny(num_hidden_layers=2, max_position_embeddings=128)
    params = llama_init_params(cfg, jax.random.PRNGKey(3))
    return cfg, params


def _reference_generate(cfg, params, prompt, n):
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None, :])
    out = llama_generate(params, toks, cfg, n, temperature=0.0)
    return [int(t) for t in np.asarray(out)[0]]


def _engine(cfg, params, **kw):
    kw.setdefault("max_batch", 3)
    kw.setdefault("max_len", 96)
    kw.setdefault("prompt_buckets", (8, 16, 32))
    kw.setdefault("burst", 4)
    kw.setdefault("page_size", 8)
    return ContinuousBatcher(cfg, params, **kw)


def _mixed_requests(cfg, seed, spec):
    rng = np.random.RandomState(seed)
    return [(rng.randint(1, cfg.vocab_size, n).tolist(), m) for n, m in spec]


# --------------------------------------------------------------- allocator
class TestPageAllocator:
    def test_all_or_nothing_and_reuse(self):
        a = PageAllocator(5)          # pages 1..4 usable, 0 scratch
        assert a.usable == 4 and a.free_pages == 4
        got = a.alloc(3)
        assert len(got) == 3 and 0 not in got
        assert a.alloc(2) is None     # only 1 left: untouched
        assert a.free_pages == 1
        a.free(got[:2])
        assert a.free_pages == 3 and a.pages_in_use == 1

    def test_invalid_frees_raise(self):
        a = PageAllocator(4)
        with pytest.raises(ValueError):
            a.free([0])               # scratch page is never allocatable
        with pytest.raises(ValueError):
            a.free([9])
        pages = a.alloc(2)
        a.free(pages)
        with pytest.raises(RuntimeError):
            a.free(pages)             # double free overflows the pool

    def test_default_page_buckets(self):
        assert default_page_buckets(12) == (1, 2, 4, 8, 12)
        assert default_page_buckets(8) == (1, 2, 4, 8)
        assert pages_for(0, 8) == 0
        assert pages_for(1, 8) == 1
        assert pages_for(8, 8) == 1
        assert pages_for(9, 8) == 2


# ------------------------------------------------------------- equivalence
class TestPagedEquivalence:
    SPEC = [(5, 7), (13, 3), (29, 12), (8, 1), (20, 6), (11, 9), (4, 8)]

    def test_paged_matches_dense_and_generate(self, small_model):
        """7 mixed requests through 3 slots: admission and retirement are
        staggered by construction. Paged output == dense output ==
        llama_generate, token for token."""
        cfg, params = small_model
        reqs = _mixed_requests(cfg, 11, self.SPEC)
        outs = {}
        for layout in ("paged", "dense"):
            eng = _engine(cfg, params, kv_layout=layout)
            rids = [eng.add_request(p, max_new_tokens=m) for p, m in reqs]
            res = eng.run()
            outs[layout] = [res[r] for r in rids]
        for (p, m), paged, dense in zip(reqs, outs["paged"], outs["dense"]):
            ref = _reference_generate(cfg, params, p, m)
            assert paged == ref, (len(p), m)
            assert dense == ref, (len(p), m)

    def test_eos_retirement_paged(self, served):
        """EOS inside a burst: the slot freezes for the burst's remaining
        steps (its frozen row write lands in its own page, through the
        per-slot loop or, on the kernel read, paged_kv_scatter)."""
        cfg, params, read = served
        rng = np.random.RandomState(3)
        prompt = rng.randint(1, cfg.vocab_size, 6).tolist()
        ref = _reference_generate(cfg, params, prompt, 20)
        # the first token past the prefill's that the stream has not
        # emitted before (random weights repeat themselves)
        k = next(i for i in range(2, 20) if ref[i] not in ref[:i])
        eng = _engine(cfg, params, eos_id=ref[k])
        assert eng.stats["kv_read"] == read
        rid = eng.add_request(prompt, max_new_tokens=20)
        out = eng.run()
        assert out[rid] == ref[:k + 1]
        # pages freed with the slot: pool is empty again
        assert eng.pages_in_use == 0

    def test_slot_and_page_reuse_after_retire(self, served):
        """One slot forces full reuse; the second prompt is shorter, so its
        block table must not expose the previous occupant's pages."""
        cfg, params, read = served
        rng = np.random.RandomState(7)
        eng = _engine(cfg, params, max_batch=1)
        assert eng.stats["kv_read"] == read
        long_p = rng.randint(1, cfg.vocab_size, 30).tolist()
        short_p = rng.randint(1, cfg.vocab_size, 4).tolist()
        r1 = eng.add_request(long_p, max_new_tokens=8)
        assert eng.run()[r1] == _reference_generate(cfg, params, long_p, 8)
        r2 = eng.add_request(short_p, max_new_tokens=10)
        assert eng.run()[r2] == _reference_generate(cfg, params, short_p, 10)


# ----------------------------------------------------- memory / admission
class TestPagedMemory:
    def test_hbm_decoupled_from_max_batch(self, small_model):
        """Equal KV row budget: dense fits 2 slots × 96 rows = 192 rows; a
        paged pool of 24×8 = 192 rows (+scratch) serves SIX concurrent
        short requests — admission is bounded by live tokens, not by
        worst-case slots."""
        cfg, params = small_model
        reqs = _mixed_requests(cfg, 23, [(6, 6)] * 6)
        eng = _engine(cfg, params, max_batch=6, num_pages=25, page_size=8)
        pool_rows = (25 - 1) * 8
        dense_rows_2slots = 2 * 96
        assert pool_rows <= dense_rows_2slots
        rids = [eng.add_request(p, max_new_tokens=m) for p, m in reqs]
        out = eng.run()
        assert eng.stats["max_concurrent"] == 6   # > the 2 dense slots
        assert eng.stats["preemptions"] == 0      # live tokens fit easily
        for rid, (p, m) in zip(rids, reqs):
            assert out[rid] == _reference_generate(cfg, params, p, m)

    def test_pool_exhaustion_queues_not_crashes(self, small_model):
        """A pool that can hold ~1.5 requests' worth of pages: admission
        stalls (requests stay QUEUED), growth preempts, and every request
        still completes token-exact."""
        cfg, params = small_model
        reqs = _mixed_requests(cfg, 31, [(9, 20), (9, 20), (9, 20), (5, 12)])
        # worst case per request: ceil(29/8) = 4 pages; usable = 6
        eng = _engine(cfg, params, num_pages=7, page_size=8)
        rids = [eng.add_request(p, max_new_tokens=m) for p, m in reqs]
        out = eng.run()
        assert eng.stats["admission_stalls"] >= 1
        for rid, (p, m) in zip(rids, reqs):
            assert out[rid] == _reference_generate(cfg, params, p, m)
        assert eng.pages_in_use == 0              # everything returned

    def test_midflight_preemption_is_exact(self, small_model):
        """Both requests admit cheaply (short prompts) but grow long: the
        pool runs dry mid-flight, the youngest slot is preempted back to
        the queue, and its regenerated output is still exact."""
        cfg, params = small_model
        reqs = _mixed_requests(cfg, 37, [(5, 30), (5, 30)])
        # each needs ceil(35/8) = 5 pages eventually; usable = 7 < 10
        eng = _engine(cfg, params, num_pages=8, page_size=8, burst=8)
        rids = [eng.add_request(p, max_new_tokens=m) for p, m in reqs]
        out = eng.run()
        assert eng.stats["preemptions"] >= 1
        for rid, (p, m) in zip(rids, reqs):
            assert out[rid] == _reference_generate(cfg, params, p, m)

    def test_enqueue_time_rejections(self, small_model):
        cfg, params = small_model
        eng = _engine(cfg, params)
        with pytest.raises(ValueError):
            eng.add_request(list(range(1, 40)), max_new_tokens=2)  # > bucket
        with pytest.raises(ValueError):
            eng.add_request([1, 2], max_new_tokens=200)  # > max_len budget
        with pytest.raises(ValueError):
            eng.add_request([1, 2], max_new_tokens=0)    # no silent extras
        with pytest.raises(ValueError):
            eng.add_request([1, 2], max_new_tokens=-3)
        # paged: a request whose pages can never exist is rejected at
        # enqueue, not queued forever
        tiny_pool = _engine(cfg, params, num_pages=3, page_size=8)
        with pytest.raises(ValueError):
            tiny_pool.add_request(list(range(1, 30)), max_new_tokens=40)
        assert tiny_pool.pending == 0


# ------------------------------------------------------ executable bounds
class TestExecutableInventory:
    def test_compile_count_is_o_buckets_not_o_requests(self, small_model):
        """12 requests of varied lengths/budgets through a fresh engine:
        the jit caches must grow by at most one burst per page bucket used
        and one prefill per prompt bucket used — never per request."""
        from paddle_tpu.models.llama_paged import (llama_paged_decode_burst,
                                                   llama_paged_prefill_slot)
        cfg, params = small_model
        spec = [(4, 5), (7, 9), (12, 4), (18, 7), (25, 11), (30, 3),
                (5, 8), (14, 6), (22, 9), (9, 5), (28, 7), (6, 10)]
        reqs = _mixed_requests(cfg, 41, spec)
        b0 = llama_paged_decode_burst._cache_size()
        p0 = llama_paged_prefill_slot._cache_size()
        eng = _engine(cfg, params)
        rids = [eng.add_request(p, max_new_tokens=m) for p, m in reqs]
        out = eng.run()
        assert len(out) == len(reqs)
        new_bursts = llama_paged_decode_burst._cache_size() - b0
        new_prefills = llama_paged_prefill_slot._cache_size() - p0
        # deltas are ≤ the bucket counts (warm jit caches from earlier
        # tests can only make them smaller — never per-request growth)
        assert new_bursts <= len(eng.stats["page_buckets_used"]) \
            <= len(eng._page_buckets)
        assert new_prefills <= len(eng._buckets)
        # and the outputs stayed correct while we were counting
        p, m = reqs[0]
        assert out[rids[0]] == _reference_generate(cfg, params, p, m)

    def test_decode_bench_paged_smoke(self):
        """Tier-1 smoke for benchmarks/decode_bench.py --paged (CPU tiny
        config): always emits the JSON payload, and the measured
        executable inventory respects the O(buckets) bound."""
        from benchmarks import decode_bench
        from paddle_tpu.models.llama_paged import (llama_paged_decode_burst,
                                                   llama_paged_prefill_slot)
        b0 = llama_paged_decode_burst._cache_size()
        p0 = llama_paged_prefill_slot._cache_size()
        payload = decode_bench.main(["--paged", "6", "3", "8"])
        assert payload["metric"] == "llama_paged_decode_tokens_per_sec"
        assert payload["value"] > 0
        assert payload["kv_read_bytes_per_token"] <= \
            payload["kv_read_bytes_per_token_dense"]
        delta_burst = llama_paged_decode_burst._cache_size() - b0
        delta_prefill = llama_paged_prefill_slot._cache_size() - p0
        assert delta_burst <= len(payload["config"]["page_buckets"])
        assert delta_prefill <= len(payload["config"]["prompt_buckets"])
        # absolute counts land in the JSON for the standalone bench run
        assert set(payload["executables"]) == {"paged_burst", "paged_prefill"}


# ------------------------------------------------------------------ chaos
def _chaos_engine(layout, small_model, wide_model, **kw):
    """An engine for a chaos case: ``"kernel"`` is the default layout on
    conftest's wide_model, whose bursts read through the decode kernel and
    write through paged_kv_scatter."""
    cfg, params = wide_model if layout == "kernel" else small_model
    eng = _engine(cfg, params, **kw,
                  kv_layout="dense" if layout == "dense" else "paged")
    assert eng.stats["kv_read"] == ("gather" if layout == "paged"
                                    else layout)
    return cfg, params, eng


class TestServingChaos:
    @pytest.mark.parametrize("layout", ["paged", "kernel", "dense"])
    def test_admit_fault_retires_request_not_scheduler(self, small_model,
                                                       wide_model, layout):
        """serve.admit:1 — the FIRST admission faults: that request
        finishes with empty (partial) output; every other request is
        exact; the queue fully drains."""
        cfg, params, eng = _chaos_engine(layout, small_model, wide_model)
        reqs = _mixed_requests(cfg, 51, [(6, 5), (10, 7), (15, 4)])
        rids = [eng.add_request(p, max_new_tokens=m) for p, m in reqs]
        with chaos.inject("serve.admit:1"):
            out = eng.run()
        assert len(out) == 3
        assert out[rids[0]] == []                 # retired with partial out
        assert eng.stats["chaos_retired"] == 1
        for rid, (p, m) in zip(rids[1:], reqs[1:]):
            assert out[rid] == _reference_generate(cfg, params, p, m)
        if layout != "dense":
            assert eng.pages_in_use == 0

    @pytest.mark.parametrize("layout", ["paged", "kernel", "dense"])
    def test_burst_fault_retires_active_with_partial_output(self, small_model,
                                                            wide_model,
                                                            layout):
        """serve.burst:1 — the first burst faults: the active requests
        retire with whatever tokens they have (at least the prefill
        token), later requests serve exactly, nothing wedges."""
        cfg, params, eng = _chaos_engine(layout, small_model, wide_model,
                                         max_batch=2)
        reqs = _mixed_requests(cfg, 53, [(6, 8), (10, 8), (15, 5), (8, 6)])
        rids = [eng.add_request(p, max_new_tokens=m) for p, m in reqs]
        with chaos.inject("serve.burst:1"):
            out = eng.run()
        assert len(out) == 4                      # queue fully drained
        assert eng.stats["chaos_retired"] >= 1
        # every output is a PREFIX of the exact reference (partial, never
        # wrong), and at least one later request completed exactly
        exact = 0
        for rid, (p, m) in zip(rids, reqs):
            ref = _reference_generate(cfg, params, p, m)
            assert out[rid] == ref[:len(out[rid])], rid
            exact += out[rid] == ref
        assert exact >= 1
        if layout != "dense":
            assert eng.pages_in_use == 0


# -------------------------------------------------------------- telemetry
def test_paged_serving_publishes_metrics(small_model):
    from paddle_tpu.observability import metrics
    cfg, params = small_model
    reqs = _mixed_requests(cfg, 61, [(6, 6), (12, 8)])
    before_tokens = metrics.counter("serve.tokens").value
    eng = _engine(cfg, params)
    for p, m in reqs:
        eng.add_request(p, max_new_tokens=m)
    eng.run()
    snap = metrics.snapshot()
    assert snap["counters"]["serve.tokens"] - before_tokens == \
        sum(m for _, m in reqs)
    assert "serve.pages_in_use" in snap["gauges"]
    assert snap["gauges"]["serve.pages_in_use"] == 0.0  # all freed
    assert snap["gauges"]["serve.kv_read_mb_per_tok"] > 0
    assert snap["histograms"]["serve.burst_time_s"]["count"] >= 1
