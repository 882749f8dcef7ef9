"""Speculative decoding on the paged serving engine (ISSUE 14).

The contracts under test:
  * ACCEPT-PREFIX — the pure walk emits exactly what plain greedy decode
    would: full accept (+bonus), full reject (correction only), mid
    reject, eos/limit freeze mid-segment.
  * PARITY — a spec-enabled ContinuousBatcher is token-identical to the
    plain engine and to per-request ``llama_generate`` at temperature 0
    on BOTH reads of the default layout (the gather, and the decode kernel
    on ``conftest.wide_model``), across staggered admission,
    mid-flight preemption, and prefix-cache-shared pages (the verify
    write COWs a shared tail page, never truncates it in place).
  * THROUGHPUT SHAPE — the self-draft (draft == target) accepts 100%
    deterministically, so tokens-per-slot-launch lands near k+1 — the
    measurable scheduling win the TPU window will cash in.
  * INVENTORY — ONE verify executable covers every per-slot proposal
    count (q_len is traced): a whole mixed-workload spec serve adds at
    most {verify, draft} singles — no per-k bucket grid.
  * CHAOS — serve.spec_verify faults fall back to the plain path for
    that burst: chaos-on == fault-free tokens, fallback counted.
  * GATING — dense layout / temperature > 0 / k < 1 silently build a
    plain engine (spec is an optimization, never a mode).
  * BENCH — PADDLE_SPEC_DECODE=1 populates the schema-checked `spec`
    sub-object on serving_bench and decode_bench JSON lines (null-off is
    pinned in tests/test_ragged_attention.py).
"""
import json
import sys

import jax
import numpy as np
import pytest

from paddle_tpu.distributed.resilience import chaos
from paddle_tpu.inference import ContinuousBatcher
from paddle_tpu.inference.speculative import (SpeculativeDecoder,
                                              accept_prefix,
                                              draft_from_target)
from paddle_tpu.models.llama import LlamaConfig, llama_init_params
from paddle_tpu.models.llama_decode import llama_generate


@pytest.fixture(scope="module")
def small_model():
    # same config/params/engine geometry as tests/test_ragged_attention.py
    # so the gather/dense/generate executables are shared across files —
    # only the draft and verify executables are new compiles here
    cfg = LlamaConfig.tiny(num_hidden_layers=2, max_position_embeddings=128)
    params = llama_init_params(cfg, jax.random.PRNGKey(3))
    return cfg, params


def _reference_generate(cfg, params, prompt, n):
    import jax.numpy as jnp
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


# ------------------------------------------------------------ accept walk
class TestAcceptPrefix:
    def test_full_accept_emits_bonus(self):
        emitted, acc, done = accept_prefix(
            [5, 6, 7], [5, 6, 7, 9], pos=10, limit=100, eos_id=-1)
        assert emitted == [5, 6, 7, 9] and acc == 3 and not done

    def test_full_reject_emits_correction_only(self):
        emitted, acc, done = accept_prefix(
            [5, 6, 7], [8, 1, 2, 3], pos=10, limit=100, eos_id=-1)
        assert emitted == [8] and acc == 0 and not done

    def test_mid_reject(self):
        emitted, acc, done = accept_prefix(
            [5, 6, 7], [5, 9, 1, 2], pos=10, limit=100, eos_id=-1)
        assert emitted == [5, 9] and acc == 1 and not done

    def test_eos_freezes_mid_segment(self):
        # the accepted eos is emitted then the slot is done — the
        # rejected tail (and even a matching one) never leaks past it
        emitted, acc, done = accept_prefix(
            [5, 2, 7], [5, 2, 7, 9], pos=10, limit=100, eos_id=2)
        assert emitted == [5, 2] and done
        # a CORRECTION token can be the eos too
        emitted, acc, done = accept_prefix(
            [5, 6], [2, 6, 9], pos=10, limit=100, eos_id=2)
        assert emitted == [2] and acc == 0 and done

    def test_limit_matches_plain_decode_arithmetic(self):
        # plain decode from pos freezes when new_pos >= limit: from
        # pos=10, limit=12 exactly two tokens can be emitted
        emitted, acc, done = accept_prefix(
            [5, 6, 7], [5, 6, 7, 9], pos=10, limit=12, eos_id=-1)
        assert emitted == [5, 6] and done

    def test_no_proposals_is_a_plain_decode_step(self):
        emitted, acc, done = accept_prefix(
            [], [4], pos=3, limit=100, eos_id=-1)
        assert emitted == [4] and acc == 0 and not done


# ------------------------------------------------------------- draft model
class TestDraftModel:
    def test_truncated_draft_slices_layers(self, small_model):
        cfg, params = small_model
        dparams, dcfg = draft_from_target(params, cfg, 1)
        assert dcfg.num_hidden_layers == 1
        assert dparams["wq"].shape[0] == 1          # stacked dim sliced
        assert dparams["embed_tokens"] is params["embed_tokens"]
        # self-draft: the tree rides through UNSLICED
        sparams, scfg = draft_from_target(params, cfg, cfg.num_hidden_layers)
        assert sparams is params
        assert scfg.num_hidden_layers == cfg.num_hidden_layers

    def test_int8_draft_builds(self, small_model):
        cfg, params = small_model
        spec = SpeculativeDecoder(cfg, params, max_batch=2, max_len=96,
                                  prompt_buckets=(8, 16, 32), k=2,
                                  draft_layers=1, precision="int8")
        assert spec._dequant is not None
        with pytest.raises(ValueError):
            SpeculativeDecoder(cfg, params, max_batch=2, max_len=96,
                               prompt_buckets=(8,), k=2,
                               precision="fp7-nonsense")


# ----------------------------------------------------------------- parity
class TestSpecServingParity:
    SPEC = [(5, 7), (13, 3), (29, 12), (8, 1), (20, 6), (11, 9), (4, 8)]

    def test_spec_matches_plain_and_generate(self, served):
        """7 mixed requests through 3 slots with a REAL (weaker,
        1-layer) draft: rejections and corrections happen, tokens don't
        change — spec == plain == llama_generate."""
        cfg, params, read = served
        reqs = _mixed_requests(cfg, 11, self.SPEC)
        eng = _engine(cfg, params, spec_decode=True, spec_k=3,
                      spec_draft_layers=1)
        assert eng._spec is not None and eng.stats["kv_read"] == read
        rids = [eng.add_request(p, max_new_tokens=m) for p, m in reqs]
        out = eng.run()
        assert eng.stats.get("spec_steps", 0) >= 1
        for rid, (p, m) in zip(rids, reqs):
            assert out[rid] == _reference_generate(cfg, params, p, m), \
                (read, len(p), m)
        assert eng.stats["spec_accepted"] < eng.stats["spec_proposed"]
        assert eng.pages_in_use == 0
        assert eng.admin_summary()["spec"]["k"] == 3

    def test_self_draft_full_accept(self, served):
        """draft == target proposes exactly the target's continuation:
        acceptance is 100% deterministically and every verify launch
        emits its whole segment — tokens per (slot, launch) > 1, the
        speculation win in launch units."""
        cfg, params, read = served
        reqs = _mixed_requests(cfg, 23, [(6, 12), (9, 16), (14, 10)])
        eng = _engine(cfg, params, spec_decode=True, spec_k=3,
                      spec_draft_layers=cfg.num_hidden_layers)
        assert eng.stats["kv_read"] == read
        rids = [eng.add_request(p, max_new_tokens=m) for p, m in reqs]
        out = eng.run()
        st = eng.stats
        assert st["spec_proposed"] > 0
        assert st["spec_accepted"] == st["spec_proposed"]
        assert st["spec_emitted"] / st["spec_slot_launches"] > 1.0
        for rid, (p, m) in zip(rids, reqs):
            assert out[rid] == _reference_generate(cfg, params, p, m)

    def test_midflight_preemption_is_exact(self, served):
        """Pool runs dry mid-flight under speculation: youngest slot
        preempted back to the queue (draft state invalidated with it),
        output still exact."""
        cfg, params, read = served
        reqs = _mixed_requests(cfg, 37, [(5, 30), (5, 30)])
        eng = _engine(cfg, params, num_pages=8, burst=8, spec_decode=True,
                      spec_k=3, spec_draft_layers=1)
        assert eng.stats["kv_read"] == read
        rids = [eng.add_request(p, max_new_tokens=m) for p, m in reqs]
        out = eng.run()
        assert eng.stats["preemptions"] >= 1
        assert eng.stats.get("spec_steps", 0) >= 1
        for rid, (p, m) in zip(rids, reqs):
            assert out[rid] == _reference_generate(cfg, params, p, m)
        assert eng.pages_in_use == 0

    def test_cow_on_prefix_shared_page(self, served):
        """The reject-on-COW-shared-page case: a full-prefix cache hit
        resumes decode INSIDE a shared tail page, so the verify's first
        write would land in a page other holders map — the growth sweep
        copies it private first (cow_copies moves), the cache entry
        survives, and a THIRD serve of the same prompt still hits.
        Tokens exact throughout, including the rejected-tail rewind."""
        cfg, params, read = served
        rng = np.random.RandomState(61)
        prompt = rng.randint(1, cfg.vocab_size, 16).tolist()  # 2 pages
        eng = _engine(cfg, params, spec_decode=True, spec_k=3,
                      spec_draft_layers=1, prefix_cache_pages=16)
        assert eng.stats["kv_read"] == read
        ref = _reference_generate(cfg, params, prompt, 8)
        r1 = eng.add_request(prompt, max_new_tokens=8)
        assert eng.run()[r1] == ref
        r2 = eng.add_request(prompt, max_new_tokens=8)   # full-prefix hit
        assert eng.run()[r2] == ref
        assert eng.stats.get("prefix_resumes", 0) >= 1
        assert eng.stats.get("cow_copies", 0) >= 1
        r3 = eng.add_request(prompt, max_new_tokens=8)   # cache intact
        assert eng.run()[r3] == ref
        assert eng.stats.get("prefix_hits", 0) >= 2
        assert eng.pages_in_use == eng._prefix.cached_pages

    def test_quantized_pages_compose(self, small_model):
        """Speculation over int8 KV pages: both the verify writes and
        reads go through the quantized pool — spec == plain quantized
        serve, token for token."""
        cfg, params = small_model
        reqs = _mixed_requests(cfg, 43, [(6, 8), (12, 6), (9, 10)])
        outs = {}
        for spec_on in (False, True):
            eng = _engine(cfg, params, kv_dtype="int8",
                          spec_decode=spec_on, spec_k=3,
                          spec_draft_layers=1)
            rids = [eng.add_request(p, max_new_tokens=m) for p, m in reqs]
            out = eng.run()
            outs[spec_on] = [out[r] for r in rids]
            if spec_on:
                assert eng.stats.get("spec_steps", 0) >= 1
        assert outs[True] == outs[False]


# ----------------------------------------------------------------- gating
class TestSpecGates:
    def test_dense_layout_degrades_silently(self, small_model):
        cfg, params = small_model
        eng = _engine(cfg, params, kv_layout="dense", spec_decode=True)
        assert eng._spec is None
        assert eng.admin_summary()["spec"] is None

    def test_temperature_degrades_silently(self, small_model):
        cfg, params = small_model
        eng = _engine(cfg, params, temperature=0.7, spec_decode=True)
        assert eng._spec is None

    def test_bad_k_degrades_silently(self, small_model):
        cfg, params = small_model
        eng = _engine(cfg, params, spec_decode=True, spec_k=0)
        assert eng._spec is None

    def test_env_flag_enables(self, small_model, monkeypatch):
        cfg, params = small_model
        monkeypatch.setenv("PADDLE_SPEC_DECODE", "1")
        monkeypatch.setenv("PADDLE_SPEC_K", "2")
        monkeypatch.setenv("PADDLE_SPEC_DRAFT_LAYERS", "1")
        eng = _engine(cfg, params)
        assert eng._spec is not None and eng._spec.k == 2
        assert eng._spec.draft_layers == 1
        monkeypatch.setenv("PADDLE_SPEC_DECODE", "0")
        assert _engine(cfg, params)._spec is None


# -------------------------------------------------------------- inventory
class TestSpecExecutableInventory:
    def test_verify_is_one_executable(self):
        """COLD config (unique to this test): a whole spec serve with
        mixed prompt lengths, budgets, limit-capped tails, full accepts
        and rejections compiles at most ONE verify executable a page
        bucket and ONE draft burst — per-slot proposal counts ride in the
        traced n_tok, not shapes (the no-per-k-bucket-grid bound)."""
        from paddle_tpu.inference.speculative import draft_spec_burst
        from paddle_tpu.models.llama_paged import llama_paged_verify
        cfg = LlamaConfig.tiny(num_hidden_layers=2, vocab_size=249,
                               max_position_embeddings=128)
        params = llama_init_params(cfg, jax.random.PRNGKey(7))
        reqs = _mixed_requests(cfg, 43, [(4, 5), (14, 16), (28, 10),
                                         (9, 14), (20, 18), (6, 9),
                                         (5, 12)])
        v0 = llama_paged_verify._cache_size()
        d0 = draft_spec_burst._cache_size()
        eng = _engine(cfg, params, spec_decode=True, spec_k=3,
                      spec_draft_layers=1)
        rids = [eng.add_request(p, max_new_tokens=m) for p, m in reqs]
        out = eng.run()
        assert eng.stats.get("spec_steps", 0) >= 2
        assert llama_paged_verify._cache_size() - v0 \
            <= len(eng._page_buckets)
        assert draft_spec_burst._cache_size() - d0 <= 1
        # a second engine, same config+k: everything is already compiled
        v1 = llama_paged_verify._cache_size()
        eng2 = _engine(cfg, params, spec_decode=True, spec_k=3,
                       spec_draft_layers=1)
        r2 = [eng2.add_request(p, max_new_tokens=m) for p, m in reqs]
        out2 = eng2.run()
        assert llama_paged_verify._cache_size() == v1
        assert [out[r] for r in rids] == [out2[r] for r in r2]


# ------------------------------------------------------------------ chaos
class TestSpecChaos:
    def test_chaos_on_equals_fault_free(self, served):
        """serve.spec_verify faulted: that burst serves through the
        plain path — degraded throughput, identical tokens, fallback
        counted, scheduler never wedges."""
        cfg, params, read = served
        reqs = _mixed_requests(cfg, 51, [(6, 8), (12, 6), (9, 10)])

        def serve(chaos_spec):
            eng = _engine(cfg, params, spec_decode=True, spec_k=3,
                          spec_draft_layers=1)
            assert eng.stats["kv_read"] == read
            rids = [eng.add_request(p, max_new_tokens=m) for p, m in reqs]
            if chaos_spec:
                with chaos.inject(chaos_spec):
                    out = eng.run()
            else:
                out = eng.run()
            return [out[r] for r in rids], eng
        base, _ = serve(None)
        faulted, eng = serve("serve.spec_verify:1")
        assert faulted == base
        assert eng.stats.get("spec_fallbacks", 0) == 1


# ------------------------------------------------------------------ bench
class TestBenchSpec:
    def test_serving_bench_spec_subobject(self, monkeypatch, capsys):
        """PADDLE_SPEC_DECODE=1 populates the schema-checked `spec`
        sub-object (accept rate, tokens per slot-launch, draft overhead,
        spec-vs-plain ratio) on serving_bench's JSON line; the self-draft
        makes the accept rate a deterministic 1.0 and tokens_per_launch
        > 1 — the acceptance-criteria shape. Null-off is pinned in
        tests/test_ragged_attention.py."""
        from benchmarks import serving_bench
        monkeypatch.setenv("SERVING_TRAIN_STEPS", "0")
        monkeypatch.setenv("PADDLE_SPEC_DECODE", "1")
        monkeypatch.setenv("PADDLE_SPEC_K", "3")
        monkeypatch.setenv("PADDLE_SPEC_DRAFT_LAYERS", "2")  # self-draft
        monkeypatch.delenv("PADDLE_SERVE_REPLICAS", raising=False)
        monkeypatch.delenv("PADDLE_SERVE_DISAGG", raising=False)
        monkeypatch.delenv("PADDLE_PREFIX_CACHE_PAGES", raising=False)
        monkeypatch.setattr(sys, "argv", ["serving_bench.py", "2", "3", "4"])
        rc = serving_bench.main()
        out = capsys.readouterr().out
        line = next(ln for ln in out.splitlines() if ln.startswith("{"))
        doc = json.loads(line)
        assert rc == 0
        s = doc["spec"]
        assert s and "error" not in s, s
        assert set(s) >= {"k", "draft_layers", "spec_steps", "accept_rate",
                          "accept_rate_p50", "tokens_per_launch",
                          "draft_overhead_frac", "tokens_per_sec",
                          "spec_vs_plain_ratio", "parity"}
        assert s["parity"] is True
        assert s["k"] == 3 and s["draft_layers"] == 2
        assert s["accept_rate"] == 1.0          # self-draft: deterministic
        assert s["tokens_per_launch"] > 1       # the acceptance shape
        assert 0.0 <= s["draft_overhead_frac"] <= 1.0
        assert s["spec_vs_plain_ratio"] > 0

    def test_decode_bench_spec_subobject(self, monkeypatch):
        from benchmarks import decode_bench
        monkeypatch.setenv("PADDLE_SPEC_DECODE", "1")
        monkeypatch.setenv("PADDLE_SPEC_K", "3")
        monkeypatch.setenv("PADDLE_SPEC_DRAFT_LAYERS", "2")
        payload = decode_bench.main(["--paged", "4", "3", "8"])
        s = payload["spec"]
        assert s and "error" not in s, s
        assert s["parity"] is True and s["tokens_per_launch"] > 1
        assert s["accept_rate"] == 1.0
