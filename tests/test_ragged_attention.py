"""The paged pool's Pallas kernels (ops/ragged_attention.py) and the default
``kv_layout="paged"`` engine on them (ISSUE 28; one paged layout: ISSUE 32).

The contracts under test:
  * KERNEL PARITY (interpret mode on CPU): the decode read
    (``paged_decode_attention``, online softmax over chunks of live pages)
    agrees with a float32 full-softmax reference within rounding;
    ``paged_kv_scatter`` is bitwise the ``dynamic_update_slice`` loop it
    replaces.
  * PAGED ENGINE ON THE KERNEL: a default-layout engine whose pool
    ``paged_kv_read`` takes reads through the decode kernel
    (``stats["kv_read"] == "kernel"``), token-identical to its gather
    twin, the dense layout and ``llama_generate`` at temperature=0,
    through a preemption and chaos; quantized pages, head_dim 64 and a
    sharded pool keep the gather. ``kv_layout="ragged"`` is an unknown
    layout and ``PADDLE_RAGGED_ATTN`` is not a flag.
  * INVENTORY: the default layout compiles one prefill per prompt bucket
    used and one burst per page bucket used (jit-cache deltas on a cold
    config).
  * BENCH CONTRACT: ``decode_bench --paged`` and ``serving_bench`` JSON
    lines carry their sub-objects, never exit JSON-less.
  * SHARDING: a pool sharded P(None, None, "model", None) over 2 of the
    forced CPU host devices serves token-identically to the unsharded
    pool, through the gather, with the pool really on both devices.
"""
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.distributed.resilience import chaos
from paddle_tpu.inference import ContinuousBatcher
from paddle_tpu.models.llama import LlamaConfig, llama_init_params
from paddle_tpu.models.llama_decode import llama_generate
from paddle_tpu.ops import ragged_attention as ra


@pytest.fixture(scope="module")
def small_model():
    # deliberately the same config/params/engine geometry as
    # tests/test_serving_paged.py: the gather/dense/generate executables
    # are shared across the two files
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


# ----------------------------------------------------------------- kernel
def _full_softmax_reference(q, kp, vp, bt, kv_lens):
    """float32 attention of each slot's decode row over rows < kv_len of
    its pages, one full-width softmax a head: no kernel, no chunks."""
    B, _, H, hd = q.shape
    KV = kp.shape[2]
    q, kp, vp = (np.asarray(a, np.float32) for a in (q, kp, vp))
    out = np.zeros((B, 1, H, hd), np.float32)
    for b in range(B):
        n = int(kv_lens[b])
        rows_k = kp[np.asarray(bt[b])].reshape(-1, KV, hd)[:n]
        rows_v = vp[np.asarray(bt[b])].reshape(-1, KV, hd)[:n]
        for h in range(H):
            kh = h // (H // KV)
            logits = rows_k[:, kh] @ q[b, 0, h] / np.float32(np.sqrt(hd))
            probs = np.exp(logits - logits.max())
            out[b, 0, h] = (probs / probs.sum()) @ rows_v[:, kh]
    return out


# decode-body cases at page_size 8, a 4-page table (32 rows), chunks of
# 2 pages: kv_len per slot. "done": a retired slot, its table row all
# scratch and its frozen pos left behind, reads scratch rows harmlessly
_PS, _PMAX = 8, 4
DECODE_CASES = {
    "one_row": [1],
    "one_short_of_a_page": [_PS - 1],
    "exactly_a_page": [_PS],
    "a_page_plus_one": [_PS + 1],
    "a_chunk_plus_one": [2 * _PS + 1],
    "full_bucket": [_PS * _PMAX],
    "mixed_lengths": [3, _PS * _PMAX, 2 * _PS, 19],
    "done_slot_all_scratch": [13, 22],
}

# the copy pipeline carried across slots (ISSUE 33), at page_size 8, an
# 8-page table (64 rows), chunks of 2 pages = 16 rows: (q_len, kv_len) per
# slot. kv_len 1-16 is one chunk, 17-32 two, 33-48 three, 49-64 four; a
# slot's first chunk lands in the buffer half the chunks before it left
_CHAIN_PMAX, _CHAIN_KV = 8, 2
CHAIN_CASES = {
    "odd_then_even_chunk_counts": [(1, 40), (1, 30), (1, 20), (1, 64)],
    "even_then_odd_chunk_counts": [(1, 30), (1, 40), (1, 10), (1, 50)],
    "odd_counts_throughout": [(1, 5), (1, 40), (1, 16), (1, 33)],
    "one_chunk_slot_between_long_ones": [(1, 64), (1, 9), (1, 60)],
    "one_chunk_slots_only": [(1, 3), (1, 16), (1, 9), (1, 1)],
    "no_context_first": [(1, 0), (1, 40), (1, 20)],
    "no_context_in_the_middle": [(1, 40), (1, 0), (1, 20)],
    "no_context_last": [(1, 40), (1, 20), (1, 0)],
    "two_without_context_in_a_row": [(1, 40), (1, 0), (1, 0), (1, 20)],
    "no_query_first": [(0, 33), (1, 40), (1, 20)],
    "no_query_in_the_middle": [(1, 40), (0, 33), (1, 20)],
    "no_query_last": [(1, 40), (1, 20), (0, 33)],
    "no_query_after_a_one_chunk_slot": [(1, 7), (0, 64), (1, 48)],
    "exact_multiples_of_a_chunk": [(1, 16), (1, 32), (1, 48), (1, 64)],
    "every_slot_without_context": [(1, 0), (1, 0), (1, 0)],
    "every_slot_without_a_query": [(0, 40), (0, 9), (0, 64)],
    "a_single_slot": [(1, 50)],
}


def _chain_launch(case, dtype, interpret, monkeypatch):
    """(out, reference, live slots) of one launch of CHAIN_CASES[case]:
    every slot's pages its own, rows past kv_len in a live page poisoned
    with NaN; 2 pages a chunk, so the launch itself (no jit cache: the
    chunk's size is read where the launch is built)."""
    KV, H, hd = _CHAIN_KV, 4, 128
    monkeypatch.setattr(ra, "_DECODE_CHUNK_ROWS", 2 * _PS * KV)
    rng = np.random.RandomState(len(case))
    q_lens, lens = (np.array(a, np.int32) for a in zip(*CHAIN_CASES[case]))
    B = len(lens)
    npool = B * _CHAIN_PMAX + 1
    kp = rng.randn(npool, _PS, KV, hd).astype(np.float32)
    vp = rng.randn(npool, _PS, KV, hd).astype(np.float32)
    bt = rng.permutation(np.arange(1, npool)).reshape(
        B, _CHAIN_PMAX).astype(np.int32)
    for b in range(B):
        if lens[b] % _PS:
            last = bt[b, (lens[b] - 1) // _PS]
            kp[last, lens[b] % _PS:] = vp[last, lens[b] % _PS:] = np.nan
    dt = jnp.dtype(dtype)
    q = jnp.asarray(rng.randn(B, 1, H, hd), dt)
    kp, vp = jnp.asarray(kp, dt), jnp.asarray(vp, dt)
    out = np.asarray(ra.paged_decode_attention.__wrapped__(
        q, kp, vp, jnp.asarray(bt), jnp.asarray(q_lens), jnp.asarray(lens),
        interpret=interpret), np.float32)
    live = (q_lens > 0) & (lens > 0)
    ref = _full_softmax_reference(q, kp, vp, bt, np.where(live, lens, 1))
    return out, ref, live


class TestPagedKernels:
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("case", sorted(DECODE_CASES))
    def test_decode_rows_match_full_softmax_reference(self, case, dtype,
                                                      monkeypatch):
        """A geometry the decode kernel takes (head_dim 128):
        chunks of live pages under an online softmax against ONE
        full-width float32 softmax. Not bitwise (ISSUE 28 ended that: the
        summation order differs); the tolerance is stated from the dtype:
        float32 reassociates <= 32 terms of magnitude <= 1 a few times
        (32 * eps, with room: 1e-5); bfloat16 also rounds K, V, q and
        the probabilities to 8 bits (2^-8 each on O(1) values: 3e-2).
        Dead rows of a live page are poisoned with NaN: they must not
        leak through the mask."""
        KV, H, hd, npool = 2, 4, 128, 24
        lens = np.array(DECODE_CASES[case], np.int32)
        B = len(lens)
        rng = np.random.RandomState(len(case))
        kp = rng.randn(npool, _PS, KV, hd).astype(np.float32)
        vp = rng.randn(npool, _PS, KV, hd).astype(np.float32)
        bt = np.zeros((B, _PMAX), np.int32)
        pages = iter(rng.permutation(np.arange(1, npool)))
        for b in range(B):
            for j in range(-(-int(lens[b]) // _PS)):
                bt[b, j] = next(pages)
            last, row = bt[b, (lens[b] - 1) // _PS], lens[b] % _PS
            if row:                      # rows past kv_len in a live page
                kp[last, row:] = vp[last, row:] = np.nan
        if case == "done_slot_all_scratch":
            bt[1] = 0
            kp[0], vp[0] = rng.randn(_PS, KV, hd), rng.randn(_PS, KV, hd)
        dt = jnp.dtype(dtype)
        q = jnp.asarray(rng.randn(B, 1, H, hd), dt)
        kp, vp = jnp.asarray(kp, dt), jnp.asarray(vp, dt)
        assert ra.decode_supported(hd, KV, _PS)
        # 2 pages a chunk: the module constant is read where the launch
        # is built, so call the launch itself (no jit cache in between)
        monkeypatch.setattr(ra, "_DECODE_CHUNK_ROWS", 2 * _PS * KV)
        out = np.asarray(ra.paged_decode_attention.__wrapped__(
            q, kp, vp, jnp.asarray(bt), jnp.ones(B, jnp.int32),
            jnp.asarray(lens), interpret=True), np.float32)
        ref = _full_softmax_reference(q, kp, vp, bt, lens)
        atol = 1e-5 if dtype == "float32" else 3e-2
        np.testing.assert_allclose(out, ref, rtol=0, atol=atol)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("case", sorted(CHAIN_CASES))
    def test_chain_across_slots_matches_full_softmax_reference(
            self, case, dtype, monkeypatch):
        """The copies run one chunk ahead ACROSS slots (ISSUE 33): a slot's
        last chunk is computed with the next slot's first in flight, in
        the buffer half the launch's running chunk count says. Every way a
        boundary can fall (odd and even chunk counts in both orders, a
        one-chunk slot, slots without context or without a query first,
        last and in between, whole chunks, nothing to read at all) against
        the float32 reference at the tolerances of the test above; a slot
        that reads nothing returns exact zeros."""
        out, ref, live = _chain_launch(case, dtype, True, monkeypatch)
        assert (out[~live] == 0).all()
        atol = 1e-5 if dtype == "float32" else 3e-2
        np.testing.assert_allclose(out[live], ref[live], rtol=0, atol=atol)

    @pytest.mark.parametrize("mode", ["eager", "on_wait"])
    @pytest.mark.parametrize("case", sorted(CHAIN_CASES))
    def test_chain_pairs_every_started_copy_with_a_wait(self, case, mode,
                                                        monkeypatch, capfd):
        """Under the TPU interpreter, which keeps the DMA semaphores'
        counts and fills fresh VMEM with NaN. ``eager``: a copy signals its
        semaphore when it is started, so a chunk started twice (by the
        slot before AND by its own slot) or never awaited leaves a count
        behind, which the interpreter reports at the kernel's exit.
        ``on_wait``: a copy moves its bytes only when it is awaited, so a
        chunk that is computed was awaited by the slot that computes it."""
        from jax.experimental.pallas import tpu as pltpu
        for interpret in (True,          # a wait that nothing started makes
                          # the TPU interpreter wait for ever: the plain one,
                          # where it reads a chunk nobody copied, goes first
                          pltpu.InterpretParams(dma_execution_mode=mode)):
            pltpu.reset_tpu_interpret_mode_state()
            out, ref, live = _chain_launch(case, "float32", interpret,
                                           monkeypatch)
            assert "non-zero count" not in capfd.readouterr().out
            assert (out[~live] == 0).all()
            np.testing.assert_allclose(out[live], ref[live], rtol=0,
                                       atol=1e-5)

    @pytest.mark.parametrize("n,r,dtype", [(3, 1, "float32"),
                                           (2, 4, "float32"),
                                           (3, 1, "bfloat16")])
    def test_scatter_writes_rows_in_place_of_update_slices(self, n, r,
                                                           dtype):
        """paged_kv_scatter == the dynamic_update_slice loop it replaces,
        bitwise: r rows of item i into page pages[i] from row rows[i] on
        (a decode step: r = 1; a prefill: whole pages), K and V in one
        launch, every other row of the pool untouched."""
        N, ps, KV, hd = 12, 4, 8, 128
        rng = np.random.RandomState(n + r)
        dt = jnp.dtype(dtype)
        kp = jnp.asarray(rng.randn(N, ps, KV, hd), dt)
        vp = jnp.asarray(rng.randn(N, ps, KV, hd), dt)
        ks = jnp.asarray(rng.randn(n, r, KV, hd), dt)
        vs = jnp.asarray(rng.randn(n, r, KV, hd), dt)
        pages = np.array([5, 2, 9][:n], np.int32)
        rows = np.array([3, 0, 1][:n] if r == 1 else [0] * n, np.int32)
        assert ra.scatter_supported(hd, KV, ps)
        ko, vo = ra.paged_kv_scatter(kp, vp, ks, vs, jnp.asarray(pages),
                                     jnp.asarray(rows), interpret=True)
        want_k, want_v = kp, vp
        for i in range(n):
            at = (int(pages[i]), int(rows[i]), 0, 0)
            want_k = jax.lax.dynamic_update_slice(want_k, ks[i][None], at)
            want_v = jax.lax.dynamic_update_slice(want_v, vs[i][None], at)
        assert (np.asarray(ko) == np.asarray(want_k)).all()
        assert (np.asarray(vo) == np.asarray(want_v)).all()

    @pytest.mark.parametrize("geometry,takes", [
        (dict(head_dim=128, kv_heads=8, page_size=16), True),
        (dict(head_dim=128, kv_heads=32, page_size=16), True),
        (dict(head_dim=128, kv_heads=4, page_size=16), False),
        (dict(head_dim=128, kv_heads=1, page_size=8), False),
        (dict(head_dim=64, kv_heads=8, page_size=16), False),
        (dict(head_dim=128, kv_heads=8, page_size=16,
              kv_dtype="fp8"), False)])
    def test_scatter_supported(self, geometry, takes):
        assert ra.scatter_supported(**geometry) is takes

    def test_decode_body_skips_slots_without_a_query(self, monkeypatch):
        """q_len 0 (a slot that takes no query this launch): exact zeros,
        and its neighbours are untouched by it, also where it follows a
        slot of two chunks (which then hands it no chunk: the slot after
        the skipped one starts its own)."""
        KV, H, hd, npool = 2, 4, 128, 12
        rng = np.random.RandomState(5)
        kp = jnp.asarray(rng.randn(npool, _PS, KV, hd), jnp.float32)
        vp = jnp.asarray(rng.randn(npool, _PS, KV, hd), jnp.float32)
        q = jnp.asarray(rng.randn(4, 1, H, hd), jnp.float32)
        bt = rng.randint(1, npool, (4, _PMAX)).astype(np.int32)
        lens = np.array([9, 17, 30, 32], np.int32)
        ref = _full_softmax_reference(q, kp, vp, bt, lens)
        monkeypatch.setattr(ra, "_DECODE_CHUNK_ROWS", 2 * _PS * KV)
        for q_lens in ([1, 0, 1, 1], [1, 1, 0, 1], [1, 1, 1, 0]):
            out = np.asarray(ra.paged_decode_attention.__wrapped__(
                q, kp, vp, jnp.asarray(bt), jnp.asarray(q_lens, jnp.int32),
                jnp.asarray(lens), interpret=True))
            took = np.array(q_lens) > 0
            assert (out[~took] == 0).all()
            np.testing.assert_allclose(out[took], ref[took], rtol=0,
                                       atol=1e-5)

    @pytest.mark.parametrize("geometry,takes", [
        (dict(head_dim=128, kv_heads=8, page_size=16), True),   # batch cell
        (dict(head_dim=128, kv_heads=32, page_size=16), True),  # chip_smoke
        (dict(head_dim=256, kv_heads=4, page_size=16), True),
        (dict(head_dim=128, kv_heads=12, page_size=16), True),
        (dict(head_dim=128, kv_heads=1, page_size=8), True),
        (dict(head_dim=64, kv_heads=8, page_size=16), False),
        (dict(head_dim=16, kv_heads=2, page_size=8), False),    # tier-1 tiny
        (dict(head_dim=128, kv_heads=4, page_size=1), False),
        (dict(head_dim=128, kv_heads=8, page_size=16,
              kv_dtype="int8"), False)])
    def test_decode_supported_says_what_the_compiler_says(self, geometry,
                                                          takes):
        """The decode kernel's rule has no backend and no max_len in it
        (tests/test_tpu_compile.py compiles a case on each side)."""
        assert ra.decode_supported(**geometry) is takes


# ---------------------------------------------------------------- serving
class TestServingParity:
    SPEC = [(5, 7), (13, 3), (29, 12), (8, 1), (20, 6), (11, 9), (4, 8)]

    def test_paged_matches_dense_and_generate(self, served, monkeypatch):
        """7 mixed requests through 3 slots, admissions landing between
        decoding bursts: the default layout on its read == the dense
        layout == llama_generate, token for token; and where the read is
        the kernel's, == its gather twin (the same model and pool, the
        choice of ``paged_kv_read`` overruled here in the test)."""
        from paddle_tpu.models import llama_paged
        cfg, params, read = served
        reqs = _mixed_requests(cfg, 11, self.SPEC)

        def serve(want, **kw):
            eng = _engine(cfg, params, **kw)
            assert eng.stats["kv_read"] == want
            rids = [eng.add_request(p, max_new_tokens=m) for p, m in reqs]
            res = eng.run()
            return [res[r] for r in rids]

        outs = [serve(read), serve("dense", kv_layout="dense")]
        if read == "kernel":
            monkeypatch.setattr(llama_paged, "paged_kv_read",
                                lambda *a, **kw: "gather")
            outs.append(serve("gather"))
        ref = [_reference_generate(cfg, params, p, m) for p, m in reqs]
        for out in outs:
            assert out == ref


# ------------------------------------------- paged engine on the kernel
@pytest.fixture(scope="module")
def kernel_run(wide_model):
    """ONE default-layout engine run on the kernel read, through a
    mid-flight preemption (8 pages for two 35-row contexts); the tests
    below each look at one side of it."""
    from paddle_tpu.observability import metrics, spans
    cfg, params = wide_model
    reqs = _mixed_requests(cfg, 37, [(5, 30), (5, 30)])
    eng = _engine(cfg, params, max_batch=2, prompt_buckets=(8,), burst=8,
                  num_pages=8, page_buckets=(12,))
    rids = [eng.add_request(p, max_new_tokens=m) for p, m in reqs]
    seq0 = max((sp.seq for sp in spans.records()), default=0)
    out = eng.run()
    bursts = [sp for sp in spans.records(since=seq0)
              if sp.name == "serve.dispatch_burst"]
    return dict(eng=eng, cfg=cfg, params=params, reqs=reqs, rids=rids,
                out=out, bursts=bursts,
                gauge=metrics.gauge("serve.kv_read_mb_per_tok").value)


class TestPagedEngineOnKernel:
    def test_tokens_exact_through_preemption(self, kernel_run):
        """float32, head_dim 128, kv_layout="paged" (the default): the
        burst reads through the interpreted decode body and the greedy
        tokens equal llama_generate's, across a preemption."""
        r = kernel_run
        assert r["eng"].stats["kv_read"] == "kernel"
        assert r["eng"].stats["preemptions"] >= 1
        for rid, (p, m) in zip(r["rids"], r["reqs"]):
            assert r["out"][rid] == _reference_generate(
                r["cfg"], r["params"], p, m)
        assert r["eng"].pages_in_use == 0

    def test_rows_are_written_by_one_launch_a_layer(self, wide_model):
        """Where the read is the kernel's and a row is whole tiles, the
        burst and the prefill hold no per-slot / per-page
        dynamic_update_slice of the pool: one paged_kv_scatter a layer."""
        from paddle_tpu.models.llama_paged import (init_paged_kv_cache,
                                                   llama_paged_decode_burst,
                                                   llama_paged_prefill_slot)
        cfg, params = wide_model
        B, P, ps = 2, 4, 8
        cache = init_paged_kv_cache(cfg, 12, ps)
        vec = jnp.zeros(B, jnp.int32)
        burst = llama_paged_decode_burst.lower(
            params, cache, jnp.zeros((B, P), jnp.int32), vec, vec,
            jnp.zeros(B, bool), vec + 9, jnp.int32(0),
            jax.random.PRNGKey(0), config=cfg, n=2).as_text()
        prefill = llama_paged_prefill_slot.lower(
            params, cache, jnp.zeros(16, jnp.int32),
            jnp.zeros(2, jnp.int32), jnp.int32(9), jax.random.PRNGKey(0),
            config=cfg).as_text()
        pool = "x".join(str(d) for d in cache["k"][0].shape)
        for text in (burst, prefill):
            assert "paged_kv_scatter" in text
            assert not [ln for ln in text.splitlines()
                        if "dynamic_update_slice" in ln and pool in ln]

    def test_dispatch_span_says_which_read(self, kernel_run):
        """The engagement counter's second home: every
        serve.dispatch_burst span carries kv_read."""
        assert kernel_run["bursts"]
        assert all(sp.args.get("kv_read") == "kernel"
                   for sp in kernel_run["bursts"])

    def test_gauge_bills_live_rows_not_the_bucket(self, kernel_run):
        """serve.kv_read_mb_per_tok follows the live pages where the
        kernel reads: under the 12-page bucket's bill, and a whole number
        of pages a slot at most max_len long."""
        from paddle_tpu.models.llama_paged import (page_bytes,
                                                   paged_kv_bytes_per_token)
        cfg = kernel_run["cfg"]
        bucket = paged_kv_bytes_per_token(cfg, 12, 8) / 1e6
        one_page = page_bytes(cfg, 8) / 1e6
        assert one_page <= kernel_run["gauge"] <= 5 * one_page < bucket

    def test_the_tiny_configs_of_tier1_keep_the_gather(self, small_model):
        cfg, params = small_model
        eng = _engine(cfg, params)
        assert eng.stats["kv_read"] == "gather"
        eng.add_request([1, 2, 3], max_new_tokens=2)
        from paddle_tpu.observability import spans
        seq0 = max((sp.seq for sp in spans.records()), default=0)
        eng.run()
        last = [sp for sp in spans.records(since=seq0)
                if sp.name == "serve.dispatch_burst"][-1]
        assert last.args["kv_read"] == "gather"

    @pytest.mark.parametrize("engine_kw,read", [
        (dict(), "kernel"),
        (dict(kv_dtype="int8"), "gather"),
        (dict(kv_layout="dense"), "dense")])
    def test_engine_selection(self, wide_model, engine_kw, read):
        """Selection by what the engine can see in its pool."""
        cfg, params = wide_model
        assert _engine(cfg, params, **engine_kw).stats["kv_read"] == read

    def test_ragged_is_an_unknown_layout(self, wide_model):
        """One paged layout (ISSUE 32): the name raises like any other."""
        cfg, params = wide_model
        with pytest.raises(ValueError, match="unknown kv_layout 'ragged'"):
            _engine(cfg, params, kv_layout="ragged")
        with pytest.raises(ValueError, match="unknown kv_layout 'pagd'"):
            _engine(cfg, params, kv_layout="pagd")

    def test_the_gather_switch_is_not_declared(self, wide_model,
                                               monkeypatch):
        """The read is chosen from the pool's geometry alone: no flag
        (the name was PADDLE_RAGGED_ATTN) is declared, none is read."""
        from paddle_tpu.utils import env_flags
        assert not env_flags.declared("PADDLE_RAGGED_ATTN")
        cfg, params = wide_model
        monkeypatch.setenv("PADDLE_RAGGED_ATTN", "0")
        assert _engine(cfg, params).stats["kv_read"] == "kernel"

    @pytest.mark.parametrize("cfg_kw,page_size,kv_dtype,mesh,read", [
        # the batch cell's geometry (InternLM2-1.8B: 16 x 128, 8 KV heads)
        (dict(hidden_size=2048, num_attention_heads=16,
              num_key_value_heads=8), 16, None, None, "kernel"),
        (dict(hidden_size=2048, num_attention_heads=16,
              num_key_value_heads=8), 16, "int8", None, "gather"),
        (dict(hidden_size=2048, num_attention_heads=16,
              num_key_value_heads=8), 16, "fp8", None, "gather"),
        (dict(hidden_size=2048, num_attention_heads=16,
              num_key_value_heads=8), 16, None, "a mesh", "gather"),
        (dict(hidden_size=1024, num_attention_heads=16,
              num_key_value_heads=8), 16, None, None, "gather"),  # hd 64
        (dict(), 8, None, None, "gather")])                      # hd 16
    def test_paged_kv_read(self, cfg_kw, page_size, kv_dtype, mesh, read):
        from paddle_tpu.models.llama_paged import paged_kv_read
        cfg = LlamaConfig.tiny(**cfg_kw)
        assert paged_kv_read(cfg, page_size, kv_dtype, mesh) == read


def _selects_over(text: str, shape: tuple) -> list:
    """stablehlo.select lines over operands whose shape starts with
    `shape` (``stablehlo.select %p, %a, %b : tensor<..xi1>, tensor<..>``)."""
    dims = "x".join(str(d) for d in shape) + "x"
    return [ln for ln in text.splitlines()
            if "stablehlo.select" in ln and f", tensor<{dims}" in ln]


class TestGatherThatStays:
    def test_int8_burst_holds_no_select_over_the_gathered_rows(
            self, small_model):
        """The gather keeps serving quantized pools; its takes clip (the
        block table is in bounds by construction), so the lowered burst
        has no fill select over the gathered payloads or scales — the
        operation that was 53 % of the batch cell's device time."""
        from paddle_tpu.models.llama_paged import (init_paged_kv_cache,
                                                   llama_paged_decode_burst)
        cfg, params = small_model
        B, P, ps = 3, 4, 8
        gathered = (B, P, ps, cfg.num_key_value_heads)
        cache = init_paged_kv_cache(cfg, 16, ps, kv_dtype="int8")
        vec = jnp.zeros(B, jnp.int32)
        text = llama_paged_decode_burst.lower(
            params, cache, jnp.zeros((B, P), jnp.int32), vec, vec,
            jnp.zeros(B, bool), vec + 9, jnp.int32(0),
            jax.random.PRNGKey(0), config=cfg, n=2,
            kv_dtype="int8").as_text()
        assert "stablehlo.gather" in text
        assert _selects_over(text, gathered) == []
        # the detector sees the select that the default mode adds
        fill = jax.jit(lambda pool, t: jnp.take(pool, t, axis=0)).lower(
            cache["k"][0], jnp.zeros((B, P), jnp.int32)).as_text()
        assert _selects_over(fill, gathered)


# -------------------------------------------------------------- inventory
class TestExecutableInventory:
    def test_one_program_a_bucket_used(self):
        """COLD config (unique to this test): a mixed workload compiles
        one prefill per prompt bucket used and one burst per page bucket
        used, whatever the request count, prompt mix and admission order;
        a second engine on the same config compiles nothing."""
        from paddle_tpu.models.llama_paged import (llama_paged_decode_burst,
                                                   llama_paged_prefill_slot)
        cfg = LlamaConfig.tiny(num_hidden_layers=2, vocab_size=250,
                               max_position_embeddings=128)
        params = llama_init_params(cfg, jax.random.PRNGKey(7))
        spec = [(4, 5), (14, 6), (28, 10), (9, 4), (20, 8), (6, 9)]
        reqs = _mixed_requests(cfg, 43, spec)
        prompt_buckets_used = {min(b for b in (8, 16, 32) if b >= n)
                               for n, _ in spec}

        def serve():
            b0 = llama_paged_decode_burst._cache_size()
            p0 = llama_paged_prefill_slot._cache_size()
            eng = _engine(cfg, params)
            rids = [eng.add_request(p, max_new_tokens=m) for p, m in reqs]
            out = eng.run()
            return (eng, [out[r] for r in rids],
                    llama_paged_decode_burst._cache_size() - b0,
                    llama_paged_prefill_slot._cache_size() - p0)

        eng, out, bursts, prefills = serve()
        assert len(prompt_buckets_used) == 3
        assert prefills == len(prompt_buckets_used)
        assert bursts == len(eng.stats["page_buckets_used"]) >= 2
        _, again, bursts, prefills = serve()
        assert (bursts, prefills) == (0, 0) and again == out


# ------------------------------------------------------------------ chaos
class TestChaosOnKernel:
    """Faults that land MID-SERVE where the bursts read through the decode
    kernel and write through ``paged_kv_scatter``: slots are decoding, rows
    of several bursts are in the pool (tests/test_serving_paged.py holds
    the first-admission and first-burst faults on every read)."""

    def test_later_admit_fault_retires_that_request_only(self, wide_model):
        cfg, params = wide_model
        reqs = _mixed_requests(cfg, 51, [(6, 5), (10, 7), (15, 4), (9, 6)])
        eng = _engine(cfg, params, max_batch=2)
        assert eng.stats["kv_read"] == "kernel"
        rids = [eng.add_request(p, max_new_tokens=m) for p, m in reqs]
        with chaos.inject("serve.admit:3"):     # a slot freed mid-serve
            out = eng.run()
        assert out[rids[2]] == [] and eng.stats["chaos_retired"] == 1
        for i in (0, 1, 3):
            p, m = reqs[i]
            assert out[rids[i]] == _reference_generate(cfg, params, p, m)
        assert eng.pages_in_use == 0

    def test_later_burst_fault_keeps_what_was_decoded(self, wide_model):
        cfg, params = wide_model
        reqs = _mixed_requests(cfg, 53, [(6, 12), (10, 12), (15, 5), (8, 6)])
        eng = _engine(cfg, params, max_batch=2)
        assert eng.stats["kv_read"] == "kernel"
        rids = [eng.add_request(p, max_new_tokens=m) for p, m in reqs]
        with chaos.inject("serve.burst:2"):     # the second burst
            out = eng.run()
        assert len(out) == 4 and eng.stats["chaos_retired"] >= 1
        refs = [_reference_generate(cfg, params, p, m) for p, m in reqs]
        for rid, ref in zip(rids, refs):
            assert out[rid] == ref[:len(out[rid])], rid
        # the first burst's tokens survived the fault, the later
        # requests were served whole
        assert 1 < len(out[rids[0]]) < 12 and 1 < len(out[rids[1]]) < 12
        assert [out[r] for r in rids[2:]] == refs[2:]
        assert eng.pages_in_use == 0


# ---------------------------------------------------------- bench contract
class TestBenchContract:
    def test_paged_kv_bytes_live_length_fix(self, small_model):
        """bytes follow LIVE length where the kernel reads, bucket width
        where the gather does."""
        from paddle_tpu.models.llama_paged import paged_kv_bytes_per_token
        cfg, _ = small_model
        bucket = paged_kv_bytes_per_token(cfg, 8, 8)          # 64 rows
        live = paged_kv_bytes_per_token(cfg, 8, 8, live_tokens=17)  # 3 pages
        assert live == paged_kv_bytes_per_token(cfg, 3, 8)
        assert live < bucket
        assert paged_kv_bytes_per_token(cfg, 8, 8, live_tokens=0) == 0

    def test_decode_bench_paged_line(self):
        """decode_bench --paged lands its line with the measured
        executable inventory and the quant sub-object, on the CPU
        fallback path (tier-1) exactly as on TPU."""
        from benchmarks import decode_bench
        payload = decode_bench.main(["--paged", "6", "3", "8"])
        # ISSUE 14: spec sub-object is null with PADDLE_SPEC_DECODE off
        # (the populated schema is pinned in tests/test_speculative.py)
        assert payload["spec"] is None
        assert "ragged" not in payload
        assert payload["kv_read_bytes_per_token"] <= \
            payload["kv_read_bytes_per_token_dense"]
        assert payload["executables"]["paged_burst"] >= 1
        assert payload["executables"]["paged_prefill"] >= 1
        # ISSUE 10: the quant sub-object rides the same JSON line
        q = payload["quant"]
        assert set(q) >= {"kv_dtype", "kv_read_bytes_per_token",
                          "kv_read_bytes_per_token_bf16",
                          "capacity_ratio_vs_bf16", "token_agreement"}
        assert q["kv_read_bytes_per_token"] < \
            q["kv_read_bytes_per_token_bf16"]
        assert q["capacity_ratio_vs_bf16"] > 1.0
        assert 0.0 <= q["token_agreement"] <= 1.0

    def test_serving_bench_line(self, monkeypatch, capsys):
        """serving_bench's JSON line: every feature's sub-object null
        while its flag is off, the quant sub-object always there."""
        from benchmarks import serving_bench
        monkeypatch.setenv("SERVING_TRAIN_STEPS", "0")
        monkeypatch.delenv("PADDLE_SERVE_REPLICAS", raising=False)
        monkeypatch.delenv("PADDLE_SERVE_DISAGG", raising=False)
        monkeypatch.delenv("PADDLE_PREFIX_CACHE_PAGES", raising=False)
        monkeypatch.delenv("PADDLE_SPEC_DECODE", raising=False)
        monkeypatch.setattr(sys, "argv", ["serving_bench.py", "2", "3", "4"])
        rc = serving_bench.main()
        out = capsys.readouterr().out
        line = next(ln for ln in out.splitlines() if ln.startswith("{"))
        doc = json.loads(line)
        assert rc == 0
        # single-process run: the ISSUE-9 fleet sub-object is null (the
        # populated schema is pinned in tests/test_serving_fleet.py), and
        # so is the ISSUE-11 disagg sub-object (populated schema pinned
        # in tests/test_disagg_serving.py)
        assert doc["fleet_serve"] is None
        assert doc["disagg"] is None
        # ISSUE 13: the prefix sub-object is null with the cache off (the
        # populated schema is pinned in tests/test_prefix_cache.py)
        assert doc["prefix"] is None
        # ISSUE 14: spec sub-object null with PADDLE_SPEC_DECODE off —
        # dashboards must distinguish 'off' from 'zero accepts' (the
        # populated schema is pinned in tests/test_speculative.py)
        assert doc["spec"] is None
        assert "ragged" not in doc
        assert doc["paged_vs_dense_divergent_requests"] == 0
        # ISSUE 10: quant sub-object (kv_dtype, bytes vs bf16, capacity
        # ratio, agreement rate) always present on the serving line
        q = doc["quant"]
        assert set(q) >= {"kv_dtype", "tokens_per_sec",
                          "kv_read_bytes_per_token",
                          "kv_read_bytes_per_token_bf16",
                          "capacity_ratio_vs_bf16", "token_agreement"}
        assert q["capacity_ratio_vs_bf16"] > 1.0

    def test_serving_bench_never_jsonless(self, monkeypatch, capsys):
        """An exploding bench still prints a machine-readable error line
        (the bench contract) — forced by an impossible argv."""
        from benchmarks import serving_bench
        monkeypatch.setattr(sys, "argv", ["serving_bench.py", "not-an-int"])
        rc = serving_bench.main()
        doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rc == 1 and "error" in doc


# --------------------------------------------------------------- sharding
class TestShardedPagePool:
    def test_kv_pool_pspec(self):
        from paddle_tpu.parallel.sharding import kv_pool_pspec, serving_mesh
        assert tuple(kv_pool_pspec()) == (None, None, "model", None)
        assert serving_mesh(0) is None and serving_mesh(1) is None

    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    def test_sharded_pool_serves_token_identical(self, small_model,
                                                 monkeypatch, kv_dtype):
        """The pool (and a quantized pool's scales) sharded along KV heads
        over 2 of the forced CPU host devices: the default layout reads
        through the gather, which GSPMD partitions, token-identical to the
        unsharded run, and the pool's buffers really live on both."""
        cfg, params = small_model           # 2 KV heads
        reqs = _mixed_requests(cfg, 5, [(5, 6), (13, 4)])

        def serve(shard):
            monkeypatch.setenv("PADDLE_SERVE_MESH_MODEL", "2" if shard
                               else "0")
            eng = _engine(cfg, params, kv_dtype=kv_dtype)
            assert eng.stats["kv_read"] == "gather"
            rids = [eng.add_request(p, max_new_tokens=m) for p, m in reqs]
            res = eng.run()
            pools = [eng._cache[k][0] for k in sorted(eng._cache)]
            return ([res[r] for r in rids],
                    {len(a.sharding.device_set) for a in pools})

        base, base_devices = serve(False)
        sharded, sharded_devices = serve(True)
        assert sharded == base
        assert (base_devices, sharded_devices) == ({1}, {2})
