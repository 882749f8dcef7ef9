"""Copy-on-write prefix-shared KV pages (ISSUE 13 tentpole).

The contracts under test:
  * REFCOUNTS — ``PageAllocator`` counts holders per page: a shared page
    is accounted ONCE in free_pages/pages_in_use however many block
    tables map it, recycles at zero, and double frees still raise.
  * CACHE — ``inference/prefix_cache.py`` indexes FULL prompt pages by
    chained blake2b hashes, verifies tokens on match, LRU-evicts idle
    entries under its capacity, and reclaims them on allocator pressure.
  * PARITY — a prefix-shared serve is temp=0 token-identical to an
    unshared serve AND ``llama_generate`` on BOTH reads of the default
    layout (the gather, and the decode kernel on ``conftest.wide_model``),
    through suffix-only prefill, full-prefix decode-resume,
    COW-triggering writes, and mid-flight preemption of a sharing slot.
  * CAPACITY — a common system prompt admits ≥2× the concurrent
    requests at equal ``pool_hbm_bytes`` vs ``PADDLE_PREFIX_CACHE_PAGES=0``,
    and hits pay suffix-only prefill (token-count + executable
    accounting) — composing with quantized (int8) pages.
  * RESILIENCE — chaos at ``serve.prefix_hash`` (lookup fault → plain
    unshared admit) and ``serve.prefix_evict`` (eviction races a hit →
    entry survives) leaves served tokens identical to fault-free.
  * DISAGG — /kv_transfer probe + ``transfer.slice_blob`` ship only the
    pages the decode pool does not already hold shared; the tail page
    always travels and a racing eviction sheds into re-prefill.
"""
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.distributed.resilience import chaos
from paddle_tpu.inference import ContinuousBatcher
from paddle_tpu.inference.paging import PageAllocator, pages_for
from paddle_tpu.inference.prefix_cache import PrefixCache, chain_hashes
from paddle_tpu.models.llama import LlamaConfig, llama_init_params
from paddle_tpu.models.llama_decode import llama_generate
from paddle_tpu.observability import metrics

PS = 8


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
    kw.setdefault("page_size", PS)
    return ContinuousBatcher(cfg, params, **kw)


def _sys_reqs(cfg, seed=11, sys_pages=2, tails=(5, 3, 0, 9, 1)):
    """A common system prompt of ``sys_pages`` FULL pages plus per-request
    tails (tail 0 = the full-prefix duplicate that resumes without any
    prefill)."""
    rng = np.random.RandomState(seed)
    sysp = rng.randint(1, cfg.vocab_size, sys_pages * PS).tolist()
    reqs = [(sysp + rng.randint(1, cfg.vocab_size, n).tolist(), 6 + n % 5)
            for n in tails]
    return sysp, reqs


def _serve(eng, reqs, stagger=False):
    if not stagger:
        rids = [eng.add_request(p, max_new_tokens=m) for p, m in reqs]
        out = eng.run()
        return [out[r] for r in rids]
    rids, pend, outs = [], list(reqs), {}
    while pend or eng.pending:
        if pend:
            p, m = pend.pop(0)
            rids.append(eng.add_request(p, max_new_tokens=m))
        eng.step()
    outs = eng.take_finished()
    return [outs[r].out for r in rids]


# ------------------------------------------------------- allocator refcounts
class TestAllocatorRefcounts:
    def test_share_free_lifecycle(self):
        a = PageAllocator(6)
        got = a.alloc(2)
        assert a.free_pages == 3 and a.pages_in_use == 2
        a.share(got)                      # second holder per page
        assert a.free_pages == 3          # shared pages count ONCE
        assert all(a.refcount(p) == 2 for p in got)
        a.free(got)                       # first holder lets go
        assert a.free_pages == 3 and a.pages_in_use == 2
        a.free(got)                       # last holder: recycle
        assert a.free_pages == 5 and a.pages_in_use == 0
        with pytest.raises(RuntimeError):
            a.free(got)                   # double free still loud

    def test_share_unallocated_raises(self):
        a = PageAllocator(4)
        with pytest.raises(ValueError):
            a.share([1])                  # never allocated
        with pytest.raises(ValueError):
            a.share([0])                  # scratch is never shareable
        got = a.alloc(1)
        a.share(got, n=3)
        assert a.refcount(got[0]) == 4


# ------------------------------------------------------------ cache mechanics
class TestPrefixCacheUnit:
    def test_chain_hashes_page_granular_and_chained(self):
        toks = list(range(1, 25))         # 3 full pages at PS=8
        hs = chain_hashes(toks, PS)
        assert len(hs) == 3
        assert hs == chain_hashes(toks + [99, 98], PS)[:3]  # tail-invariant
        # a change in page 0 reaches EVERY later chain hash
        other = [7] + toks[1:]
        assert all(x != y for x, y in zip(hs, chain_hashes(other, PS)))
        # deterministic across calls/processes (blake2b, not hash())
        assert hs == chain_hashes(list(toks), PS)

    def test_match_insert_refcounts(self):
        a = PageAllocator(10)
        c = PrefixCache(a, PS, capacity_pages=8)
        toks = list(range(1, 17))         # 2 full pages
        pages = a.alloc(2)
        assert c.insert(toks, pages) == 2
        assert all(a.refcount(p) == 2 for p in pages)   # owner + cache
        a.free(pages)                      # owner retires: cache holds on
        assert all(a.refcount(p) == 1 for p in pages)
        assert c.evictable_pages() == 2
        got, matched = c.match(toks + [40, 41])
        assert got == pages and matched == 16
        assert all(a.refcount(p) == 2 for p in pages)   # cache + new holder
        # different prefix: miss, no refs taken
        none, m0 = c.match([5] * 20)
        assert none == [] and m0 == 0
        # partial: only page 0 of a half-matching prompt
        half = toks[:8] + [3] * 8
        got2, m2 = c.match(half)
        assert got2 == pages[:1] and m2 == 8
        a.free(got + got2)

    def test_verification_rejects_token_mismatch(self):
        a = PageAllocator(6)
        c = PrefixCache(a, PS, capacity_pages=4)
        toks = list(range(1, 9))
        pages = a.alloc(1)
        c.insert(toks, pages)
        # simulate a (cosmically unlikely) chain collision: same key,
        # different stored tokens — the exact-token compare refuses it
        key = chain_hashes(toks, PS)[0]
        c._entries[key]["tokens"] = tuple([9] * 8)
        got, m = c.match(toks)
        assert got == [] and m == 0

    def test_lru_cap_and_busy_entries_survive(self):
        a = PageAllocator(12)
        c = PrefixCache(a, PS, capacity_pages=2)
        p1 = a.alloc(1)
        c.insert(list(range(1, 9)), p1)
        a.free(p1)                         # idle (cache-only)
        p2 = a.alloc(1)
        c.insert(list(range(11, 19)), p2)  # BUSY: owner still holds p2
        p3 = a.alloc(1)
        c.insert(list(range(21, 29)), p3)  # over cap: evicts idle p1
        assert c.cached_pages == 2
        assert c.match(list(range(1, 9)) + [1])[0] == []     # p1 gone
        assert c.match(list(range(11, 19)) + [1])[0] == p2   # busy survived
        a.free(p2)

    def test_lru_evicts_chain_tail_first(self):
        """Within one chain the ROOT page is the most recently used, so
        eviction eats chains from the TAIL: the surviving prefix stays
        matchable instead of stranding unreachable descendants that
        still pin pages."""
        a = PageAllocator(8)
        c = PrefixCache(a, PS, capacity_pages=8)
        toks = list(range(1, 17))
        pages = a.alloc(2)
        c.insert(toks, pages)
        a.free(pages)                      # both idle
        assert c.reclaim(1) == 1           # evicts the TAIL entry
        got, m = c.match(toks)
        assert got == pages[:1] and m == 8  # root still hits
        a.free(got)

    def test_reclaim_bounded_by_idle(self):
        a = PageAllocator(12)
        c = PrefixCache(a, PS, capacity_pages=8)
        pages = a.alloc(3)
        c.insert(list(range(1, 25)), pages)
        a.free(pages[:2])                  # 2 idle, 1 busy
        free0 = a.free_pages
        assert c.reclaim(5) == 2           # only the idle ones
        assert a.free_pages == free0 + 2
        assert c.cached_pages == 1
        a.free(pages[2:])

    def test_chaos_evict_spares_entries(self):
        a = PageAllocator(8)
        c = PrefixCache(a, PS, capacity_pages=8)
        pages = a.alloc(2)
        c.insert(list(range(1, 17)), pages)
        a.free(pages)
        with chaos.inject("serve.prefix_evict:1+"):
            assert c.reclaim(2) == 0       # every eviction raced a "hit"
        assert c.cached_pages == 2
        assert c.reclaim(2) == 2           # chaos off: reclaim proceeds


# ------------------------------------------------------------------- parity
class TestPrefixParity:
    def test_shared_matches_unshared_and_generate(self, served):
        """The acceptance pin: shared-prompt traffic (suffix hits AND a
        full-prefix resume) is token-identical to an unshared serve and
        to llama_generate, staggered admissions included."""
        cfg, params, read = served
        _, reqs = _sys_reqs(cfg)
        base = _serve(_engine(cfg, params), reqs, stagger=True)
        eng = _engine(cfg, params, prefix_cache_pages=64)
        assert eng.stats["kv_read"] == read
        shared = _serve(eng, reqs, stagger=True)
        assert shared == base
        assert eng.stats["prefix_hits"] >= 3
        assert eng.stats.get("prefix_resumes", 0) >= 1   # the tail-0 dup
        assert eng.stats.get("cow_copies", 0) >= 1       # its tail page
        for out, (p, m) in zip(shared, reqs):
            assert out == _reference_generate(cfg, params, p, m)

    def test_preemption_of_sharing_slot_is_exact(self, served):
        """Pool runs dry mid-flight while slots share a prefix: the
        youngest sharing slot preempts back to the queue, re-matches on
        re-admit, and its regenerated output is exact."""
        cfg, params, read = served
        rng = np.random.RandomState(41)
        sysp = rng.randint(1, cfg.vocab_size, 2 * PS).tolist()
        reqs = [(sysp + rng.randint(1, cfg.vocab_size, 3).tolist(), 26)
                for _ in range(2)]
        # each grows to ceil((19+26)/8) = 6 pages; 2 shared + 2×4 private
        # at peak > usable 8 → someone preempts
        eng = _engine(cfg, params, num_pages=9, burst=8,
                      prefix_cache_pages=64)
        assert eng.stats["kv_read"] == read
        warm = (sysp + [5], 4)             # populate the index first
        outs = _serve(eng, [warm] + reqs)
        assert eng.stats["preemptions"] >= 1
        assert eng.stats["prefix_hits"] >= 2
        for out, (p, m) in zip(outs, [warm] + reqs):
            assert out == _reference_generate(cfg, params, p, m)

    def test_cow_write_leaves_sharers_untouched(self, served):
        """Two identical full-page prompts decode concurrently: the
        second resumes on shared pages, COWs its tail page, and BOTH
        streams stay exact — the write never leaks into the shared
        original."""
        cfg, params, read = served
        rng = np.random.RandomState(43)
        p = rng.randint(1, cfg.vocab_size, 2 * PS).tolist()
        eng = _engine(cfg, params, prefix_cache_pages=64)
        assert eng.stats["kv_read"] == read
        ref = _reference_generate(cfg, params, p, 10)
        r1 = eng.add_request(p, max_new_tokens=10)
        eng.run()
        cow0 = eng.stats.get("cow_copies", 0)
        r2 = eng.add_request(p, max_new_tokens=10)
        r3 = eng.add_request(p, max_new_tokens=10)
        out = eng.run()
        assert eng.stats["cow_copies"] >= cow0 + 2
        assert eng.stats.get("prefix_resumes", 0) >= 2
        fin = {**{r1: ref}, **out}
        assert fin[r2] == ref and fin[r3] == ref

    def test_exact_fit_resume_drops_cache_ref_not_livelock(self, served):
        """A worst-case-sized pool (usable == the request's page bill)
        with a full-prefix resume: the COW copy has NO free page to land
        in and the shared pages' only other holder is the cache itself —
        the zero-copy fallback drops the cache reference (page becomes
        private, entry evicted) instead of preempting the slot forever."""
        cfg, params, read = served
        rng = np.random.RandomState(67)
        p = rng.randint(1, cfg.vocab_size, 2 * PS).tolist()
        ref = _reference_generate(cfg, params, p, 8)
        # worst = pages_for(16 + 8) = 3 == usable (num_pages 4)
        eng = _engine(cfg, params, num_pages=4, burst=8,
                      prefix_cache_pages=8)
        assert eng.stats["kv_read"] == read
        r1 = eng.add_request(p, max_new_tokens=8)
        out1 = eng.run()[r1]
        r2 = eng.add_request(p, max_new_tokens=8)
        out2 = eng.run()[r2]
        assert out1 == ref and out2 == ref
        assert eng.stats.get("prefix_resumes", 0) == 1
        assert eng.stats.get("cow_copies", 0) == 0   # zero-copy fallback
        assert eng.stats["preemptions"] == 0

    @pytest.mark.parametrize("spec", ["serve.prefix_hash:1+",
                                      "serve.prefix_hash:2",
                                      "serve.prefix_evict:1+"])
    def test_chaos_on_equals_fault_free(self, small_model, spec):
        """Chaos at the prefix sites degrades (miss / spared eviction),
        never diverges: chaos-on tokens == fault-free tokens."""
        cfg, params = small_model
        _, reqs = _sys_reqs(cfg, seed=13)
        base = _serve(_engine(cfg, params), reqs)
        with chaos.inject(spec):
            eng = _engine(cfg, params, prefix_cache_pages=16)
            got = _serve(eng, reqs)
        assert got == base

    def test_kernel_read_chaos_hash_fault_free(self, wide_model):
        cfg, params = wide_model
        _, reqs = _sys_reqs(cfg, seed=17)
        base = _serve(_engine(cfg, params), reqs)
        with chaos.inject("serve.prefix_hash:1+"):
            eng = _engine(cfg, params, prefix_cache_pages=16)
            assert eng.stats["kv_read"] == "kernel"
            got = _serve(eng, reqs)
        assert got == base


# ------------------------------------------------------------------ capacity
class TestCapacityAndSkippedPrefill:
    def _concurrency(self, cfg, params, cache_pages, budget, kv_dtype=None):
        kw = {"kv_dtype": kv_dtype} if kv_dtype else {}
        eng = _engine(cfg, params, max_batch=8, pool_hbm_bytes=budget,
                      prompt_buckets=(8, 16, 32, 64),
                      prefix_cache_pages=cache_pages, **kw)
        rng = np.random.RandomState(47)
        sysp = rng.randint(1, cfg.vocab_size, 4 * PS).tolist()
        warm = eng.add_request(sysp + [3], max_new_tokens=2)
        eng.run()
        reqs = [(sysp + rng.randint(1, cfg.vocab_size, 2).tolist(), 6)
                for _ in range(8)]
        outs = _serve(eng, reqs)
        for out, (p, m) in zip(outs, reqs):
            assert out == _reference_generate(cfg, params, p, m)
        return eng.stats["max_concurrent"]

    def test_2x_admissions_at_equal_hbm(self, small_model):
        """THE capacity acceptance pin: a common system prompt admits
        ≥2× the concurrent requests at the SAME pool_hbm_bytes once the
        prefix cache is on (each shared admit pays only its suffix
        pages)."""
        cfg, params = small_model
        from paddle_tpu.models.llama_paged import page_bytes
        budget = 14 * page_bytes(cfg, PS)   # 13 usable pages
        base = self._concurrency(cfg, params, 0, budget)
        shared = self._concurrency(cfg, params, 64, budget)
        assert shared >= 2 * base, (shared, base)

    def test_quantized_pages_compose(self, small_model):
        """ISSUE 10 compose: shared pages stay in the pool dtype (int8
        payload + f32 scales — capacity is multiplicative), the sharing
        ratio holds on a quantized pool, and greedy outputs agree with
        the unshared quantized serve."""
        cfg, params = small_model
        from paddle_tpu.models.llama_paged import page_bytes
        budget = 14 * page_bytes(cfg, PS, "int8")
        base = self._concurrency(cfg, params, 0, budget, kv_dtype="int8")
        shared = self._concurrency(cfg, params, 64, budget,
                                   kv_dtype="int8")
        assert shared >= 2 * base, (shared, base)
        # pool stays quantized with sharing on
        eng = _engine(cfg, params, kv_dtype="int8", prefix_cache_pages=16)
        assert eng._cache["k"][0].dtype == jnp.int8
        assert "k_scale" in eng._cache

    def test_suffix_only_prefill_accounting(self, small_model):
        """The prefill-skip acceptance pin, by token-count AND executable
        accounting: warm hits share every full prefix page (tokens
        shared == hits × prefix), marginal pages stay at the suffix
        size, the suffix executable exists, the full-prefill executable
        compiles NOTHING new on the warm pass, and a full-prefix resume
        runs no prefill at all."""
        cfg, params = small_model
        from paddle_tpu.models.llama_paged import (
            llama_paged_prefill_slot, llama_paged_prefill_suffix)
        rng = np.random.RandomState(53)
        sysp = rng.randint(1, cfg.vocab_size, 2 * PS).tolist()
        eng = _engine(cfg, params, prefix_cache_pages=64)
        _serve(eng, [(sysp + [7, 8, 9], 4)])          # cold: populates
        full0 = llama_paged_prefill_slot._cache_size()
        suf0 = llama_paged_prefill_suffix._cache_size()
        pf0 = eng.stats["prefills"]
        _serve(eng, [(sysp + [5, 6], 4), (sysp + [1, 2, 3, 4], 4)])
        assert eng.stats["prefix_hits"] == 2
        assert eng.stats["prefix_tokens_shared"] == 2 * len(sysp)
        # marginal pages: ONE suffix page per shared admit here
        assert eng.stats["prefix_marginal_pages"] == 2
        assert llama_paged_prefill_suffix._cache_size() >= max(1, suf0)
        assert llama_paged_prefill_slot._cache_size() == full0
        # full-prefix duplicate: prefill SKIPPED entirely
        _serve(eng, [(list(sysp), 4)])
        assert eng.stats["prefills"] == pf0 + 2       # resume added none
        assert eng.stats.get("prefix_resumes", 0) == 1

    def test_prefill_skipped_seconds_estimate(self, small_model):
        """slo.prefill_skipped_s accumulates once an unshared prefill has
        seeded the EMA and hits start landing."""
        cfg, params = small_model
        c0 = metrics.counter("slo.prefill_skipped_s").value
        h0 = metrics.counter("serve.prefix_hits").value
        eng = _engine(cfg, params, prefix_cache_pages=64)
        rng = np.random.RandomState(59)
        sysp = rng.randint(1, cfg.vocab_size, 2 * PS).tolist()
        _serve(eng, [(sysp + [4, 5], 4)])             # unshared: seeds EMA
        _serve(eng, [(sysp + [6, 7], 4)])             # hit: estimate lands
        assert metrics.counter("serve.prefix_hits").value == h0 + 1
        assert metrics.counter("slo.prefill_skipped_s").value > c0


# ----------------------------------------------------------- engine contracts
class TestEngineContracts:
    def test_env_flag_enables_cache(self, small_model, monkeypatch):
        cfg, params = small_model
        monkeypatch.setenv("PADDLE_PREFIX_CACHE_PAGES", "12")
        eng = _engine(cfg, params)
        assert eng._prefix is not None
        monkeypatch.setenv("PADDLE_PREFIX_CACHE_PAGES", "0")
        assert _engine(cfg, params)._prefix is None

    def test_dense_layout_refuses_prefix_cache(self, small_model):
        cfg, params = small_model
        with pytest.raises(ValueError):
            ContinuousBatcher(cfg, params, kv_layout="dense",
                              prefix_cache_pages=8)

    def test_health_and_admin_surfaces(self, small_model):
        cfg, params = small_model
        eng = _engine(cfg, params, prefix_cache_pages=16)
        h = eng.health_summary()
        assert h["prefix_sharing"] is True and h["evictable_pages"] == 0
        a = eng.admin_summary()
        assert a["prefix"]["cached_pages"] == 0
        off = _engine(cfg, params)
        assert off.health_summary()["prefix_sharing"] is False
        assert off.admin_summary()["prefix"] is None


# ------------------------------------------------------------- disagg compose
class TestDisaggCompose:
    def _blob(self, cfg, params, prompt, kv_dtype=None):
        kw = {"kv_dtype": kv_dtype} if kv_dtype else {}
        pre = _engine(cfg, params, **kw)
        rid = pre.add_request(prompt, max_new_tokens=8, prefill_only=True)
        pre.run()
        return pre.export_kv(rid)

    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    def test_sliced_transfer_token_identical(self, small_model, kv_dtype):
        """The wire-shrink acceptance: install #1 populates the decode
        pool's cache; the probe then slices transfer #2 to the unshared
        remainder — fewer wire bytes, same tokens, exact vs generate
        (unquantized) / vs the full install (quantized)."""
        cfg, params = small_model
        from paddle_tpu.inference.disagg.transfer import (
            check_blob_geometry, slice_blob)
        rng = np.random.RandomState(61)
        prompt = rng.randint(1, cfg.vocab_size, 2 * PS + 3).tolist()
        blob = self._blob(cfg, params, prompt, kv_dtype)
        kw = {"kv_dtype": kv_dtype} if kv_dtype else {}
        dec = _engine(cfg, params, prefix_cache_pages=32, **kw)
        r1 = dec.add_request(prompt, max_new_tokens=8,
                             kv_import=dict(blob))
        out1 = dec.run()[r1]
        k = dec.prefix_probe(prompt)
        assert k == 2                      # both full pages now cached
        sliced = slice_blob(blob, k)
        assert sliced["n_pages"] == 1 and sliced["from_page"] == 2
        assert sliced["wire_bytes"] < blob["wire_bytes"] / 2
        check_blob_geometry(sliced, cfg, PS)   # the /kv_transfer 400 gate
        r2 = dec.add_request(prompt, max_new_tokens=8, kv_import=sliced)
        out2 = dec.run()[r2]
        assert out2 == out1
        if kv_dtype is None:
            assert out1 == _reference_generate(cfg, params, prompt, 8)

    def test_slice_blob_geometry_contracts(self, small_model):
        cfg, params = small_model
        from paddle_tpu.inference.disagg.transfer import (
            check_blob_geometry, slice_blob, wire_breakdown)
        prompt = list(range(1, 2 * PS + 4))
        blob = self._blob(cfg, params, prompt)
        with pytest.raises(ValueError):
            slice_blob(blob, 3)            # must leave the tail page
        s = slice_blob(blob, 1)
        assert s["wire_bytes"] == wire_breakdown(
            cfg, 2, PS, None)["wire_bytes"]
        assert len(s["data"]) == s["wire_bytes"]
        # a from_page past the prompt's pages is refused at the boundary
        bad = dict(s, from_page=5, n_pages=1)
        with pytest.raises(ValueError):
            check_blob_geometry(bad, cfg, PS)

    def test_prefix_gone_sheds_not_errors(self, small_model):
        """A sliced blob whose shared prefix evicted between probe and
        install retires reason='shed' (the router re-prefills) — never a
        client-visible error, never a dead serve loop."""
        cfg, params = small_model
        from paddle_tpu.inference.disagg.transfer import slice_blob
        prompt = list(range(1, 2 * PS + 4))
        blob = self._blob(cfg, params, prompt)
        sliced = slice_blob(blob, 2)
        dec = _engine(cfg, params, prefix_cache_pages=32)  # cache EMPTY
        rid = dec.add_request(prompt, max_new_tokens=6, kv_import=sliced)
        while dec.pending:
            dec.step()
        req = dec.take_finished()[rid]
        assert req.reason == "shed" and req.out == []
        assert dec.pages_in_use == 0       # nothing leaked

    def test_replica_probe_handler(self, small_model, tmp_path):
        """The /kv_transfer probe branch: prefix pages offered by a
        decode replica, 0 from a cache-less one, 400 from the prefill
        pool."""
        cfg, params = small_model
        from paddle_tpu.distributed.fleet.elastic import FileRegistry
        from paddle_tpu.inference.replica import ReplicaServer
        reg = FileRegistry(str(tmp_path), "t", ttl=5.0)
        prompt = list(range(1, 2 * PS + 2))
        dec = _engine(cfg, params, prefix_cache_pages=32)
        rep = ReplicaServer(dec, reg, "d0", role="decode")
        rep._admin.start()   # handlers only; no serve loop, no heartbeat
        try:
            code, body = rep._h_kv_transfer({"probe": True,
                                             "prompt": prompt})
            assert code == 200 and body["from_page"] == 0
            r = dec.add_request(prompt, max_new_tokens=4)
            dec.run()
            code, body = rep._h_kv_transfer({"probe": True,
                                             "prompt": prompt})
            assert code == 200 and body["from_page"] == 2
            code, _ = rep._h_kv_transfer({"probe": True})
            assert code == 400
            pre = ReplicaServer(_engine(cfg, params), reg, "p0",
                                role="prefill")
            pre._admin.start()
            try:
                code, body = pre._h_kv_transfer({"probe": True,
                                                 "prompt": prompt})
                assert code == 400
            finally:
                pre._admin.stop()
        finally:
            rep._admin.stop()

    def test_router_transfer_slices_in_hand_blob(self, small_model,
                                                 monkeypatch):
        """_try_transfer probes a prefix-sharing decode handle and ships
        the in-hand blob SLICED to the unshared remainder; a probe
        hiccup or a non-sharing handle ships the full blob; the slice is
        capped one page below the blob (the tail page always travels)."""
        cfg, params = small_model
        from paddle_tpu.inference.disagg.coordinator import DisaggRouter
        from paddle_tpu.inference.disagg.transfer import (slice_blob,
                                                          unpack_frame)
        from paddle_tpu.inference.router import _Handle, RoutedRequest

        prompt = list(range(1, 2 * PS + 4))
        blob = self._blob(cfg, params, prompt)

        class _Reg:
            def alive_nodes(self):
                return []

            def info(self, node):
                return {}

        def run_one(sharing, post_fn):
            router = DisaggRouter(_Reg())
            req = RoutedRequest(rid=1, prompt=prompt, max_new_tokens=4,
                                trace_id=0)
            req.trace_id = router.slo.on_enqueue(req.rid)
            router._requests[req.rid] = req
            req.kv = dict(blob)       # full blob in hand (data carried)
            req.stage = "transfer"
            h = _Handle(id="serve.d0", endpoint="http://x", role="decode",
                        prefix_sharing=sharing, free_pages=64, ready=True)
            router._handles[h.id] = h
            posted = {}
            monkeypatch.setattr(router, "_post", post_fn)
            monkeypatch.setattr(
                router, "_post_bytes",
                lambda ep, path, data, timeout=None:
                    (posted.__setitem__("data", data) or (200,
                                                          {"ok": True})))
            monkeypatch.setattr(
                router, "_get_bytes",
                lambda *a, **k: pytest.fail("fetched with blob in hand"))
            assert router._try_transfer(req) == "routed"
            hdr, payload = unpack_frame(posted["data"])
            skipped = router.xfer_pages_skipped
            router.close()
            return hdr["kv"], payload, skipped

        kvh, payload, skipped = run_one(
            True, lambda *a, **k: (200, {"from_page": 2}))
        assert skipped == 2 and kvh["n_pages"] == 1
        assert kvh["from_page"] == 2
        assert payload == slice_blob(blob, 2)["data"]
        # probe says everything cached: still capped at n-1
        kvh, payload, skipped = run_one(
            True, lambda *a, **k: (200, {"from_page": 9}))
        assert skipped == 2 and kvh["n_pages"] == 1
        # probe transport fault: full blob ships
        kvh, payload, skipped = run_one(True, lambda *a, **k: (0, {}))
        assert skipped == 0 and kvh["n_pages"] == 3
        assert payload == blob["data"]
        # non-sharing handle: no probe at all
        kvh, payload, skipped = run_one(
            False, lambda *a, **k: pytest.fail("probed"))
        assert skipped == 0 and payload == blob["data"]


# ------------------------------------------------------------------- bench
class TestBenchPrefix:
    def test_serving_bench_prefix_subobject(self, monkeypatch, capsys):
        """PADDLE_PREFIX_CACHE_PAGES>0 populates the schema-checked
        `prefix` sub-object on serving_bench's JSON line (warm hit rate
        100%, marginal pages below the full-prompt bill); the line
        itself survives any drill failure (never JSON-less)."""
        from benchmarks import serving_bench
        monkeypatch.setenv("SERVING_TRAIN_STEPS", "0")
        monkeypatch.setenv("PADDLE_PREFIX_CACHE_PAGES", "48")
        monkeypatch.delenv("PADDLE_SERVE_REPLICAS", raising=False)
        monkeypatch.delenv("PADDLE_SERVE_DISAGG", raising=False)
        monkeypatch.setattr(sys, "argv", ["serving_bench.py", "2", "3", "4"])
        rc = serving_bench.main()
        out = capsys.readouterr().out
        line = next(ln for ln in out.splitlines() if ln.startswith("{"))
        doc = json.loads(line)
        assert rc == 0
        p = doc["prefix"]
        assert set(p) >= {"cache_pages", "hit_rate", "pages_shared",
                          "marginal_pages_per_shared_admit",
                          "ttft_p50_shared_s", "ttft_p50_unshared_s"}
        assert p["hit_rate"] == 1.0        # warm pass: every admit hits
        assert p["pages_shared"] > 0
        assert p["marginal_pages_per_shared_admit"] is not None
        # suffix pages only — below the full prompt's 4-5 page bill
        assert p["marginal_pages_per_shared_admit"] < 3
        assert p["ttft_p50_shared_s"] > 0 and p["ttft_p50_unshared_s"] > 0
