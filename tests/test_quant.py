"""Block-wise quantized numerics (ISSUE 10): paddle_tpu/quant/.

The contracts under test:
  * CODEC — symmetric int8 / fp8-e4m3 block codecs round-trip EXACTLY
    where the values are representable (on-grid blocks, zeros), never
    produce NaN (fp8 saturates before casting), jit cleanly.
  * QUANTIZED ALLREDUCE — the EQuARX shape behind
    ``distributed/collective.py::all_reduce``
    (``PADDLE_QUANT_ALLREDUCE=int8|fp8``): every rank ends
    bitwise-identical, results track the fp32 sum/mean tightly, the fp
    path stays BITWISE when the flag is off, small/non-float payloads
    never take the quantized wire, and a REAL 12-step data-parallel
    training run's loss trajectory stays within a bounded δ of fp32 sync
    for int8 AND fp8 — with chaos at ``quant.allreduce`` (per-call
    fallback to full precision) inside the same envelope.
  * QUANTIZED KV PAGES — ``kv_dtype=int8|fp8`` serving on TRAINED
    weights: greedy token agreement ≥99% vs the full-precision engine
    (a quantized pool reads through the XLA gather), across staggered
    admission and mid-flight preemption; one-step decode
    logits within a bounded δ; the fp path is byte-identical (no scale
    pools, tokens == llama_generate); and an equal page-pool HBM budget
    admits ≥1.8× the live tokens of bf16 pages (the capacity
    acceptance).
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu.distributed as dist
import paddle_tpu.distributed.collective as coll
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed.resilience import chaos
from paddle_tpu.inference import ContinuousBatcher
from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.models.llama_decode import llama_generate
from paddle_tpu.observability import metrics
from paddle_tpu.quant import codec as qcodec
from paddle_tpu.quant.allreduce import quantized_all_reduce, wire_bytes
from paddle_tpu.utils.jax_compat import shard_map

N_DEV = 4


@pytest.fixture(scope="module")
def dp_world():
    """A 4-device data-parallel world (the tier-1 CPU platform forces 8
    host devices; same set_mesh idiom as tests/test_collective.py)."""
    mesh = dist.set_mesh(dist.ProcessMesh(np.arange(N_DEV), ["dp"]))
    group = dist.new_group(axis_name="dp", mesh=mesh)
    return mesh, group


@pytest.fixture(scope="module")
def trained_model():
    """Trained tiny weights (the serving_bench recipe): ~120 steps on the
    Zipf-Markov corpus peak the logits so greedy agreement is a real
    assertion, not a bf16 tie-break lottery. Same geometry as
    tests/test_ragged_attention.py so full-precision serving executables
    are shared across files."""
    from paddle_tpu.io.token_loader import synthetic_corpus
    from paddle_tpu.models import LlamaTrainStep
    from paddle_tpu.optimizer import AdamW
    cfg = LlamaConfig.tiny(num_hidden_layers=2, max_position_embeddings=128)
    corpus = np.asarray(synthetic_corpus(100_000, vocab_size=256, seed=7))
    step = LlamaTrainStep(
        cfg, optimizer=AdamW(learning_rate=3e-4, weight_decay=0.1,
                             moment_dtype=jnp.bfloat16), remat=True, seed=0)
    B, T = 2, 64
    span = B * (T + 1)
    for i in range(120):
        off = (i * span) % (len(corpus) - span - 1)
        chunk = corpus[off:off + span].reshape(B, T + 1)
        step(chunk[:, :-1].astype(np.int32), chunk[:, 1:].astype(np.int32))
    return cfg, step.params, corpus


def _engine(cfg, params, **kw):
    kw.setdefault("max_batch", 3)
    kw.setdefault("max_len", 96)
    kw.setdefault("prompt_buckets", (8, 16, 32))
    kw.setdefault("burst", 4)
    kw.setdefault("page_size", 8)
    return ContinuousBatcher(cfg, params, **kw)


def _corpus_requests(corpus, n, seed):
    rng = np.random.RandomState(seed)
    reqs = []
    for _ in range(n):
        tl = int(rng.choice([5, 9, 14, 21]))
        off = int(rng.randint(0, len(corpus) - tl - 1))
        prompt = [int(t) or 1 for t in corpus[off:off + tl]]
        reqs.append((prompt, int(rng.choice([4, 6, 9]))))
    return reqs


def _serve(cfg, params, reqs, kv_dtype="", **kw):
    eng = _engine(cfg, params, kv_dtype=kv_dtype, **kw)
    rids = [eng.add_request(p, max_new_tokens=m) for p, m in reqs]
    out = eng.run()
    return eng, [out[r] for r in rids]


def _agreement(outs, base):
    tok = sum(len(b) for b in base)
    same = sum(int(a == b) for o, bb in zip(outs, base)
               for a, b in zip(o, bb))
    return same / max(1, tok)


# ------------------------------------------------------------------ codec
class TestCodec:
    def test_int8_on_grid_roundtrip_exact(self):
        # every block carries a ±127 element, so scale == s exactly and
        # all values sit on scale × [-127, 127]
        rng = np.random.RandomState(0)
        k = rng.randint(-127, 128, (6, 32)).astype(np.float32)
        k[:, 0] = 127.0
        x = k * 0.125
        q, s = qcodec.quantize_lastdim(jnp.asarray(x), "int8")
        assert q.dtype == jnp.int8 and s.shape == (6,)
        rt = np.asarray(qcodec.dequantize_lastdim(q, s))
        assert (rt == x).all()

    def test_fp8_representable_roundtrip_exact(self):
        x = np.asarray([[0.0, 1.0, 2.0, 448.0],
                        [-448.0, 0.5, 3.5, -12.0]], np.float32)
        q, s = qcodec.quantize_lastdim(jnp.asarray(x), "fp8")
        assert q.dtype == jnp.float8_e4m3fn
        rt = np.asarray(qcodec.dequantize_lastdim(q, s))
        assert (rt == x).all()

    @pytest.mark.parametrize("mode", ["int8", "fp8"])
    def test_zero_blocks_roundtrip_exact(self, mode):
        q, s = qcodec.quantize_lastdim(jnp.zeros((3, 16)), mode)
        assert (np.asarray(qcodec.dequantize_lastdim(q, s)) == 0).all()

    def test_fp8_saturates_never_nan(self):
        # a bare float8 astype maps overflow to NaN on this jax; the
        # codec must clip first — and huge magnitudes must survive
        x = jnp.asarray([[1e30, -1e30, 1.0, 0.0]])
        q, s = qcodec.quantize_lastdim(x, "fp8")
        rt = np.asarray(qcodec.dequantize_lastdim(q, s))
        assert not np.isnan(rt).any()
        assert np.abs(rt).max() <= 1e30 * 1.001

    def test_jittable_and_dequant_dtype(self):
        f = jax.jit(lambda a: qcodec.quantize_lastdim(a, "int8"))
        q, s = f(jnp.ones((4, 8), jnp.bfloat16))
        out = qcodec.dequantize_lastdim(q, s, jnp.bfloat16)
        assert out.dtype == jnp.bfloat16


# ------------------------------------------------------ quantized allreduce
class TestQuantizedAllReduce:
    def _sync(self, mesh, fn, x):
        return np.asarray(shard_map(fn, mesh.jax_mesh,
                                    in_specs=(P("dp"),),
                                    out_specs=P("dp"))(jnp.asarray(x)))

    def test_tracks_fp32_and_ranks_bitwise_identical(self, dp_world):
        mesh, _ = dp_world
        rng = np.random.RandomState(1)
        g = rng.randn(N_DEV, 1000).astype(np.float32)

        def fp(a):
            return jax.lax.pmean(a[0], "dp")[None]

        ref = self._sync(mesh, fp, g)
        for mode, tol in (("int8", 2e-2), ("fp8", 8e-2)):
            def qn(a, mode=mode):
                return quantized_all_reduce(a[0], "dp", N_DEV, mode,
                                            block=128, average=True)[None]

            out = self._sync(mesh, qn, g)
            # every rank dequantizes the SAME gathered payload: replicas
            # cannot drift apart
            for r in range(1, N_DEV):
                assert (out[r] == out[0]).all()
            scale = np.abs(ref[0]).max()
            assert np.abs(out[0] - ref[0]).max() <= tol * scale, mode

    def test_sum_mode(self, dp_world):
        mesh, _ = dp_world
        g = np.ones((N_DEV, 512), np.float32)

        def qn(a):
            return quantized_all_reduce(a[0], "dp", N_DEV, "int8",
                                        block=64)[None]

        out = self._sync(mesh, qn, g)
        np.testing.assert_allclose(out[0], 4.0, rtol=1e-2)

    def test_api_opt_in_and_bitwise_off(self, dp_world, monkeypatch):
        """Through the PUBLIC all_reduce: int8 engages the quantized wire
        (counted), and with the flag off the result is BITWISE the
        pre-quant psum path."""
        mesh, group = dp_world
        rng = np.random.RandomState(2)
        g = rng.randn(N_DEV, 2048).astype(np.float32)

        def api(a):
            t = Tensor(a[0])
            coll.all_reduce(t, op=coll.ReduceOp.AVG, group=group)
            return t._value[None]

        def fp(a):
            return jax.lax.pmean(a[0], "dp")[None]

        ref = self._sync(mesh, fp, g)
        monkeypatch.setenv("PADDLE_QUANT_ALLREDUCE", "0")
        off = self._sync(mesh, api, g)
        assert (off == ref).all()          # bitwise: the fp path is intact
        calls0 = metrics.counter("quant.allreduce_calls").value
        monkeypatch.setenv("PADDLE_QUANT_ALLREDUCE", "int8")
        on = self._sync(mesh, api, g)
        assert metrics.counter("quant.allreduce_calls").value == calls0 + 1
        assert not (on == ref).all()       # really took the quantized wire
        assert np.abs(on[0] - ref[0]).max() <= 2e-2 * np.abs(ref[0]).max()

    def test_small_and_nonfloat_payloads_stay_fp(self, dp_world,
                                                 monkeypatch):
        """A barrier's scalar (and any int payload) must never pay scale
        overhead for zero wire win — the gate keeps them on the fp path
        with no quant.allreduce chaos hit."""
        mesh, group = dp_world
        monkeypatch.setenv("PADDLE_QUANT_ALLREDUCE", "int8")
        calls0 = metrics.counter("quant.allreduce_calls").value

        def scalar(a):
            t = Tensor(a[0, 0])
            coll.all_reduce(t, group=group)
            return t._value[None, None]

        out = self._sync(mesh, scalar, np.ones((N_DEV, 1), np.float32))
        assert out[0, 0] == 4.0

        def ints(a):
            t = Tensor(a[0].astype(jnp.int32))
            coll.all_reduce(t, group=group)
            return t._value[None].astype(jnp.float32)

        out = self._sync(mesh, ints, np.ones((N_DEV, 4096), np.float32))
        assert (out[0] == 4).all()
        assert metrics.counter("quant.allreduce_calls").value == calls0

    def test_unknown_mode_raises(self, dp_world, monkeypatch):
        mesh, group = dp_world
        monkeypatch.setenv("PADDLE_QUANT_ALLREDUCE", "int4")
        with pytest.raises(ValueError, match="int4"):
            def api(a):
                t = Tensor(a[0])
                coll.all_reduce(t, group=group)
                return t._value[None]

            self._sync(mesh, api, np.ones((N_DEV, 2048), np.float32))

    def test_wire_bytes_accounting(self):
        w = wire_bytes(1 << 20, 4, "int8", block=256)
        # 1B payload + 4B/256 scale vs 4B fp32 ≈ 0.254×
        assert 0.24 <= w["wire_ratio"] <= 0.27
        assert w["wire_bytes_per_rank"] < w["fp32_wire_bytes_per_rank"] / 3
        w8 = wire_bytes(1 << 20, 4, "fp8", block=256)
        assert w8["wire_bytes_per_rank"] == w["wire_bytes_per_rank"]


# ------------------------------------------- DP loss-trajectory acceptance
class TestDataParallelLossTrajectory:
    """The ISSUE-10 allreduce acceptance: a REAL 12-step data-parallel
    training run (per-rank grads, AVG gradient sync through the public
    all_reduce, SGD update) — quantized sync's loss trajectory within a
    bounded δ of fp32 sync, chaos-on included; fp path bitwise."""

    STEPS = 12
    LR = 0.05
    D, H = 32, 16
    # measured max rel δ on this drill: int8 ≈ 9e-5, fp8 ≈ 4.2e-4 —
    # bounds give ~50× headroom while still rejecting a broken codec
    # (a zeroed/garbled sync diverges by >1e-1 within a few steps)
    DELTA = {"int8": 5e-3, "fp8": 2e-2}

    @pytest.fixture(scope="class")
    def drill_data(self):
        rng = np.random.RandomState(0)
        X = rng.randn(8 * N_DEV, self.D).astype(np.float32)
        Wt = rng.randn(self.D, self.H).astype(np.float32)
        Y = (X @ Wt + 0.1 * rng.randn(8 * N_DEV, self.H)).astype(np.float32)
        return X, Y

    def _loss(self, w, x, y):
        return jnp.mean((x @ w - y) ** 2)

    def _run(self, mesh, group, X, Y, jit: bool):
        """12 data-parallel steps; ``jit=False`` re-traces the shard_map
        each step so the quant.allreduce chaos site fires PER CALL (the
        jitted variant hits it once at trace time)."""
        def grads(w, xb, yb):
            g = jax.grad(self._loss)(w, xb, yb)
            t = Tensor(g)
            coll.all_reduce(t, op=coll.ReduceOp.AVG, group=group)
            return t._value[None]

        sm = shard_map(grads, mesh.jax_mesh,
                       in_specs=(P(), P("dp"), P("dp")), out_specs=P("dp"))
        stepfn = jax.jit(sm) if jit else sm
        w = jnp.zeros((self.D, self.H), jnp.float32)
        losses = []
        for _ in range(self.STEPS):
            gs = np.asarray(stepfn(w, jnp.asarray(X), jnp.asarray(Y)))
            for r in range(1, N_DEV):      # DP invariant: no replica drift
                assert (gs[r] == gs[0]).all()
            w = w - self.LR * jnp.asarray(gs[0])
            losses.append(float(self._loss(w, jnp.asarray(X),
                                           jnp.asarray(Y))))
        return np.asarray(losses)

    def test_bounded_delta_int8_fp8_and_bitwise_fp(self, dp_world,
                                                   drill_data, monkeypatch):
        mesh, group = dp_world
        X, Y = drill_data
        monkeypatch.setenv("PADDLE_QUANT_BLOCK", "64")
        monkeypatch.setenv("PADDLE_QUANT_ALLREDUCE", "0")
        fp = self._run(mesh, group, X, Y, jit=True)
        assert fp[-1] < fp[0]              # the drill actually trains
        for mode in ("int8", "fp8"):
            monkeypatch.setenv("PADDLE_QUANT_ALLREDUCE", mode)
            traj = self._run(mesh, group, X, Y, jit=True)
            delta = np.max(np.abs(traj - fp) / np.abs(fp))
            assert 0 < delta <= self.DELTA[mode], (mode, delta)
            # 0 < delta: the quantized wire really engaged — a silently
            # disabled path would pass any bound

    def test_chaos_fallback_stays_in_envelope(self, dp_world, drill_data,
                                              monkeypatch):
        """chaos==fault-free per the quantized discipline: an injected
        quant.allreduce fault degrades THAT step's sync to full precision
        — the run completes inside the same bounded-δ acceptance vs fp32
        that the fault-free quantized run passes, and the fallback is
        counted."""
        mesh, group = dp_world
        X, Y = drill_data
        monkeypatch.setenv("PADDLE_QUANT_BLOCK", "64")
        monkeypatch.setenv("PADDLE_QUANT_ALLREDUCE", "0")
        fp = self._run(mesh, group, X, Y, jit=True)
        monkeypatch.setenv("PADDLE_QUANT_ALLREDUCE", "int8")
        fb0 = metrics.counter("quant.allreduce_fallbacks").value
        with chaos.inject("quant.allreduce:5"):
            traj = self._run(mesh, group, X, Y, jit=False)  # per-call hits
        assert metrics.counter("quant.allreduce_fallbacks").value == fb0 + 1
        delta = np.max(np.abs(traj - fp) / np.abs(fp))
        assert delta <= self.DELTA["int8"], delta

    def test_fp_path_ignores_armed_chaos_bitwise(self, dp_world, drill_data,
                                                 monkeypatch):
        """With quantization OFF the chaos site is never reached (the env
        gate precedes it): an armed quant.allreduce spec changes nothing,
        bitwise — the fp discipline of the chaos contract."""
        mesh, group = dp_world
        X, Y = drill_data
        monkeypatch.setenv("PADDLE_QUANT_ALLREDUCE", "0")
        fp = self._run(mesh, group, X, Y, jit=True)
        with chaos.inject("quant.allreduce:1"):
            fp_chaos = self._run(mesh, group, X, Y, jit=True)
            assert chaos.hit_counts().get("quant.allreduce", 0) == 0
        assert (fp == fp_chaos).all()

    def test_site_registered(self):
        assert "quant.allreduce" in chaos.SITES


# --------------------------------------------------- quantized KV pages
class TestQuantKVPages:
    @pytest.mark.parametrize("dt", ["int8", "fp8"])
    def test_greedy_agreement(self, trained_model, dt):
        """int8 and fp8 pages vs the full-precision engine on TRAINED
        weights, staggered admission (6 requests over 3 slots): ≥99%
        greedy token agreement."""
        cfg, params, corpus = trained_model
        reqs = _corpus_requests(corpus, 6, seed=11)
        _, base = _serve(cfg, params, reqs)
        eng, quant = _serve(cfg, params, reqs, kv_dtype=dt)
        assert eng.stats["kv_read"] == "gather"
        assert _agreement(quant, base) >= 0.99

    @pytest.mark.parametrize("dt", ["int8", "fp8"])
    def test_midflight_preemption_quantized(self, trained_model, dt):
        """A pool sized to force mid-flight preemption (the PR-8 recipe:
        two 30-token budgets over 7 usable pages) with quantized pages:
        preemption fires, everything completes, agreement holds —
        requantization after a preempted restart does not corrupt
        neighbours."""
        cfg, params, corpus = trained_model
        reqs = [([int(t) or 1 for t in corpus[o:o + 5]], 30)
                for o in (40, 200)]
        _, base = _serve(cfg, params, reqs, num_pages=8, burst=8)
        eng, outs = _serve(cfg, params, reqs, kv_dtype=dt, num_pages=8,
                           burst=8)
        assert eng.stats["preemptions"] >= 1
        assert _agreement(outs, base) >= 0.99
        assert eng.pages_in_use == 0   # clean drain

    @pytest.mark.parametrize("quant,bound", [("int8", 1e-2), ("fp8", 5e-2)])
    def test_bounded_logit_delta_one_step(self, trained_model, quant, bound):
        """Prefill the same prompt into quantized and full-precision
        pools, take ONE decode step: max |Δlogit| bounded (measured:
        int8 ≈ 8e-4, fp8 ≈ 5e-3 on a ~1.1 logit range — bounds ~10×)."""
        from paddle_tpu.models.llama_paged import (
            _paged_decode_step_slots, init_paged_kv_cache,
            llama_paged_prefill_slot)
        cfg, params, corpus = trained_model
        prompt = np.asarray([int(t) or 1 for t in corpus[100:116]], np.int32)
        outs = {}
        for dt in (None, quant):
            cache = init_paged_kv_cache(cfg, 13, 8, kv_dtype=dt)
            first, cache = llama_paged_prefill_slot(
                params, cache, jnp.asarray(prompt),
                jnp.asarray([1, 2], jnp.int32), jnp.int32(16),
                jax.random.PRNGKey(0), config=cfg, kv_dtype=dt)
            bt = np.zeros((1, 4), np.int32)
            bt[0, :3] = [1, 2, 3]
            logits, _ = _paged_decode_step_slots(
                params, cache, jnp.asarray(bt),
                jnp.asarray([16], jnp.int32),
                jnp.asarray([int(first)], jnp.int32), cfg, kv_dtype=dt)
            outs[dt] = np.asarray(logits)
        d = np.abs(outs[quant] - outs[None]).max()
        assert 0 < d <= bound, d
        assert outs[quant].argmax() == outs[None].argmax()

    def test_fp_path_byte_identical_when_off(self, trained_model,
                                             monkeypatch):
        """kv_dtype off == the pre-quant engine: no scale pools exist,
        pool dtype is the model dtype, and greedy tokens equal
        per-request llama_generate exactly."""
        monkeypatch.delenv("PADDLE_SERVE_KV_DTYPE", raising=False)
        cfg, params, corpus = trained_model
        reqs = _corpus_requests(corpus, 3, seed=31)
        eng, outs = _serve(cfg, params, reqs)
        assert eng._kv_dtype is None
        assert "k_scale" not in eng._cache
        assert eng._cache["k"][0].dtype == cfg.dtype
        for (p, m), o in zip(reqs, outs):
            ref = llama_generate(params, jnp.asarray(
                np.asarray(p, np.int32)[None, :]), cfg, m, temperature=0.0)
            assert o == [int(t) for t in np.asarray(ref)[0]]

    def test_env_opt_in_and_validation(self, trained_model, monkeypatch):
        cfg, params, _ = trained_model
        monkeypatch.setenv("PADDLE_SERVE_KV_DTYPE", "int8")
        eng = _engine(cfg, params, kv_layout="paged", kv_dtype=None)
        assert eng._kv_dtype == "int8"
        assert eng._cache["k"][0].dtype == jnp.int8
        assert eng._cache["k_scale"][0].dtype == jnp.float32
        # the dense baseline ignores the fleet-wide env knob...
        dense = _engine(cfg, params, kv_layout="dense")
        assert dense._kv_dtype is None
        # ...but rejects an explicit request, and typos fail loudly
        with pytest.raises(ValueError, match="dense"):
            _engine(cfg, params, kv_layout="dense", kv_dtype="int8")
        with pytest.raises(ValueError, match="int9"):
            _engine(cfg, params, kv_layout="paged", kv_dtype="int9")

    def test_quantized_accounting_gauges(self, trained_model):
        """serve.kv_read_mb_per_tok reflects the quantized (smaller)
        read: int8 pages bill below the full-precision serve."""
        from paddle_tpu.models.llama_paged import paged_kv_bytes_per_token
        cfg, _, _ = trained_model
        full = paged_kv_bytes_per_token(cfg, 4, 8)
        q = paged_kv_bytes_per_token(cfg, 4, 8, kv_dtype="int8")
        assert q < full
        # live-token form agrees with the page form at page boundaries
        assert paged_kv_bytes_per_token(
            cfg, 0, 8, live_tokens=32, kv_dtype="int8") == q


# ----------------------------------------------------- capacity acceptance
class TestCapacityAtEqualHBM:
    """The ISSUE-10 acceptance: quantized pages admit ≥1.8× the live
    tokens of bf16 pages at an EQUAL page-pool HBM budget. Pure
    allocator/accounting math — admission is gated by free pages, so
    usable pages × page_size IS the admissible live-token capacity."""

    CFG = dict(hidden_size=64, num_attention_heads=1, num_key_value_heads=1,
               num_hidden_layers=2, dtype=jnp.bfloat16)  # head_dim 64

    @pytest.mark.parametrize("dt", ["int8", "fp8"])
    def test_equal_budget_admits_1p8x_live_tokens(self, dt):
        from paddle_tpu.models.llama_paged import page_bytes
        cfg = LlamaConfig.tiny(**self.CFG)
        ps = 8
        budget = 48 * page_bytes(cfg, ps)      # a 48-page bf16 pool
        bf16 = _engine(cfg, params=None, kv_layout="paged",
                       pool_hbm_bytes=budget)
        quant = _engine(cfg, params=None, kv_layout="paged",
                        kv_dtype=dt, pool_hbm_bytes=budget)
        ratio = (quant._alloc.usable * ps) / (bf16._alloc.usable * ps)
        assert ratio >= 1.8, ratio
        # and in admitted-request terms: concurrent 16-token contexts
        from paddle_tpu.inference.paging import pages_for
        per_req = pages_for(16, ps)
        assert quant._alloc.usable // per_req \
            >= 1.8 * (bf16._alloc.usable // per_req)

    def test_pool_budget_knob_validation(self):
        cfg = LlamaConfig.tiny(**self.CFG)
        with pytest.raises(ValueError, match="not both"):
            _engine(cfg, params=None, kv_layout="paged",
                    pool_hbm_bytes=1 << 20, num_pages=8)

    def test_page_bytes_scale_overhead_accounting(self):
        """page_bytes carries the f32-scale overhead honestly: the ratio
        is 2·hd/(hd+4), ≈1.88 at head_dim 64, ≈1.94 at 128 — NOT a flat
        2× (the README documents when the trade is worth it)."""
        from paddle_tpu.models.llama_paged import page_bytes
        cfg = LlamaConfig.tiny(**self.CFG)
        ratio = page_bytes(cfg, 8) / page_bytes(cfg, 8, kv_dtype="int8")
        assert abs(ratio - 2 * 64 / 68) < 1e-6
