"""Disaggregated prefill/decode serving (ISSUE 11 tentpole).

The contracts under test:
  * WIRE — KV pages serialize into the quant-codec wire format (int8/fp8
    payload + f32 block scales, f32 fallback) and install bit-exact when
    pools match; the quantized wire ships ≤ 0.30× the f32 bytes at both
    scale granularities, and the page granularity
    (PADDLE_SERVE_KV_SCALE_GRAN=page) cuts scale bytes ~page_size× at a
    measured, pinned greedy-agreement cost.
  * HANDOFF — a prefill_only request parks its pages (reason
    "prefilled"), export_kv frees them, a kv_import admit installs them
    into ANOTHER engine's pool, and the decode stream is token-identical
    to llama_generate at temp=0 on both read paths and quantized pools.
  * ROLES — the lease payload and /health carry the replica role;
    DisaggRouter routes the prompt stage to the prefill pool and
    transfers to the decode pool; unified (unset) keeps base routing.
  * PRESSURE — admission's second dimension: the decode boundary rejects
    on pool pressure (free pages vs the transfer's page demand) with its
    OWN retry-after arithmetic, distinct from the queue dimension's.
  * CHAOS — serve.page_xfer (transfer faulted → re-prefill, never lost)
    and serve.prefill_dead (failover deferred one tick, never lost) keep
    chaos-on disagg serving token-identical to fault-free.
  * DRILL — ≥2 prefill + ≥2 decode subprocess replicas behind the
    router: fault-free, SIGKILL of a prefill replica mid-pass, and
    SIGKILL of a decode replica post-handoff all complete token-identical
    with trace ids preserved and per-stage slo.* histograms populated.
"""
import json
import sys
import time

import jax
import numpy as np
import pytest

from paddle_tpu.distributed.fleet import elastic as el
from paddle_tpu.distributed.resilience import chaos
from paddle_tpu.inference import (AdmissionPolicy, AdmissionReject,
                                  ContinuousBatcher, DisaggRouter, Router,
                                  ServingFleet)
from paddle_tpu.inference.disagg.transfer import (install_pages,
                                                  serialize_pages,
                                                  wire_breakdown,
                                                  wire_ratio_vs_f32)
from paddle_tpu.inference.replica import ReplicaServer, normalize_role
from paddle_tpu.inference.router import RoutedRequest
from paddle_tpu.models.llama import LlamaConfig, llama_init_params
from paddle_tpu.models.llama_decode import llama_generate
from paddle_tpu.models.llama_paged import (gather_pages,
                                           init_paged_kv_cache)
from paddle_tpu.observability import metrics
from paddle_tpu.quant.codec import normalize_scale_gran

# same tiny model discipline as tests/test_serving_fleet.py: every
# replica (in-process or subprocess) builds identical weights from SPEC
SPEC = {
    "config": {"vocab_size": 256, "hidden_size": 64,
               "intermediate_size": 128, "num_hidden_layers": 2,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "max_position_embeddings": 128, "dtype": "float32"},
    "seed": 3,
    "batcher": {"max_batch": 3, "max_len": 96, "prompt_buckets": [8, 16, 32],
                "burst": 4, "page_size": 8},
}

# head_dim 32 (128 / 4 heads): the wire-ratio acceptance number is a
# deployment claim, and at hd 16 a per-row f32 scale eats the payload win
WIDE_CFG_KW = dict(vocab_size=256, hidden_size=128, intermediate_size=256,
                   num_hidden_layers=2, num_attention_heads=4,
                   num_key_value_heads=2, max_position_embeddings=128)


@pytest.fixture(scope="module")
def small_model():
    cfg = LlamaConfig.tiny(num_hidden_layers=2, max_position_embeddings=128)
    params = llama_init_params(cfg, jax.random.PRNGKey(3))
    return cfg, params


@pytest.fixture(scope="module")
def hd32_model():
    import jax.numpy as jnp
    cfg = LlamaConfig(dtype=jnp.float32, **WIDE_CFG_KW)
    params = llama_init_params(cfg, jax.random.PRNGKey(3))
    return cfg, params


def _engine(cfg, params, **kw):
    base = dict(SPEC["batcher"])
    base["prompt_buckets"] = tuple(base["prompt_buckets"])
    base.update(kw)
    return ContinuousBatcher(cfg, params, **base)


def _reference(cfg, params, prompt, n):
    import jax.numpy as jnp
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None, :])
    out = llama_generate(params, toks, cfg, n, temperature=0.0)
    return [int(t) for t in np.asarray(out)[0]]


def _prompts(n, seed=0, lo=4, hi=20):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 256, int(m)).tolist()
            for m in rng.randint(lo, hi, n)]


def _handoff(cfg, params, reqs, kv_dtype=None, scale_gran=None,
             kv_read="gather", **kw):
    """prefill_only on engine A → export → kv_import on engine B →
    decoded outputs, in request order."""
    pre = _engine(cfg, params, kv_dtype=kv_dtype, **kw)
    dec = _engine(cfg, params, kv_dtype=kv_dtype, **kw)
    assert pre.stats["kv_read"] == dec.stats["kv_read"] == kv_read
    rids = [pre.add_request(p, max_new_tokens=m, prefill_only=True)
            for p, m in reqs]
    pre.run()
    blobs = {r: pre.export_kv(r, scale_gran=scale_gran) for r in rids}
    assert pre.parked_count == 0 and pre.pages_in_use == 0
    drids = [dec.add_request(p, max_new_tokens=m, kv_import=blobs[r])
             for r, (p, m) in zip(rids, reqs)]
    dout = dec.run()
    assert dec.pages_in_use == 0
    return [dout[r] for r in drids], blobs


class _DisaggReplicas:
    """In-process mixed-pool harness: role-tagged ReplicaServers over one
    FileRegistry (threads, not processes — the subprocess path is the
    drill)."""

    def __init__(self, tmp_path, cfg, params, roles, ttl=1.5, **engine_kw):
        self.registry = el.FileRegistry(str(tmp_path), "fleet", ttl=ttl)
        self.reps = []
        for i, role in enumerate(roles):
            eng = _engine(cfg, params, admission=AdmissionPolicy(),
                          **engine_kw)
            self.reps.append(ReplicaServer(eng, self.registry, f"r{i}",
                                           role=role).start())

    def stop(self):
        for rep in self.reps:
            rep.stop()


# ------------------------------------------------------- binary framing

class TestBinaryFrame:
    """ISSUE 12 satellite: the transfer wire is a length-prefixed binary
    frame — payload bytes ship verbatim (the old base64-JSON encoding
    paid 4/3× transport on every hop) and check_blob_geometry keeps its
    no-decode validation contract against the raw byte count."""

    def test_frame_roundtrip_bit_identical_install(self, small_model):
        from paddle_tpu.inference.disagg.transfer import (blob_meta,
                                                          pack_frame,
                                                          unpack_frame)
        cfg, params = small_model
        pre = _engine(cfg, params, kv_layout="paged", kv_dtype="int8")
        rid = pre.add_request(_prompts(1, seed=3)[0], max_new_tokens=4,
                              prefill_only=True)
        pre.run()
        blob = pre.export_kv(rid)
        frame = pack_frame({"kv": blob_meta(blob), "rid": 7},
                           blob["data"])
        header, payload = unpack_frame(frame)
        assert header["rid"] == 7
        assert payload == bytes(blob["data"])          # verbatim bytes
        rebuilt = dict(header["kv"], data=payload)
        dec = _engine(cfg, params, kv_layout="paged", kv_dtype="int8")
        dst_ids = list(range(1, 1 + blob["n_pages"]))
        a = install_pages(dec._cache, cfg, dst_ids, blob, "int8")
        b = install_pages(dec._cache, cfg, dst_ids, rebuilt, "int8")
        for leaf in ("k", "v", "k_scale", "v_scale"):
            for la, lb in zip(a[leaf], b[leaf]):
                assert np.array_equal(np.asarray(la), np.asarray(lb))

    def test_transport_cost_is_wire_bytes_plus_small_header(
            self, small_model):
        """The ~33% cut, pinned: frame transport == wire_bytes + a small
        constant header, where base64-JSON paid ceil(4/3×) plus JSON
        dressing."""
        from paddle_tpu.inference.disagg.transfer import (blob_meta,
                                                          pack_frame)
        cfg, params = small_model
        pre = _engine(cfg, params, kv_layout="paged")
        rid = pre.add_request(_prompts(1, seed=4, lo=16, hi=17)[0],
                              max_new_tokens=4, prefill_only=True)
        pre.run()
        blob = pre.export_kv(rid)
        frame = pack_frame({"kv": blob_meta(blob)}, blob["data"])
        overhead = len(frame) - blob["wire_bytes"]
        assert 0 < overhead < 512, overhead
        base64_cost = -(-blob["wire_bytes"] * 4 // 3)  # what the old wire paid
        assert len(frame) < 0.80 * base64_cost

    def test_bad_frames_answer_400_at_the_wire(self, small_model,
                                               tmp_path):
        from paddle_tpu.inference.disagg.transfer import (blob_meta,
                                                          pack_frame)
        cfg, params = small_model
        eng = _engine(cfg, params, kv_layout="paged",
                      admission=AdmissionPolicy())
        rep = ReplicaServer(eng, el.FileRegistry(str(tmp_path), "f",
                                                 ttl=5), "r0")
        code, ans = rep._h_kv_transfer(b"not a frame at all")
        assert code == 400 and "bad frame" in ans["reason"]
        pre = _engine(cfg, params, kv_layout="paged")
        rid = pre.add_request(_prompts(1, seed=5)[0],
                              max_new_tokens=4, prefill_only=True)
        pre.run()
        blob = pre.export_kv(rid)
        frame = pack_frame(
            {"kv": blob_meta(blob), "rid": 1, "prompt": [1] * 8,
             "max_new_tokens": 2, "router": "t"}, blob["data"])
        code, ans = rep._h_kv_transfer(frame[: len(frame) // 2])
        assert code == 400, ans   # truncated payload: byte-count gate

    def test_mid_body_death_is_transient_wire_noise(self):
        """A replica SIGKILLed while streaming a multi-MB /kv_blob frame
        surfaces as http.client.IncompleteRead (HTTPException, not
        OSError) — it must classify transient so the fetch degrades to
        re-prefill instead of crashing the router's poll loop."""
        import http.client

        from paddle_tpu.inference.router import _transient_send
        assert _transient_send(http.client.IncompleteRead(b"partial"))
        assert _transient_send(http.client.BadStatusLine("x"))
        assert not _transient_send(TypeError("our bug"))

    def test_frame_store_reexport_keeps_live_frame(self, small_model,
                                                   tmp_path):
        """A re-prefill landing on the same replica overwrites its frame
        IN PLACE: a duplicate eviction-order entry would otherwise evict
        the live replacement when the stale entry aged out — 404 → a
        wasted third prompt pass."""
        from paddle_tpu.inference.replica import _KV_FRAME_KEEP
        cfg, params = small_model
        eng = _engine(cfg, params, kv_layout="paged")
        rep = ReplicaServer(eng, el.FileRegistry(str(tmp_path), "f",
                                                 ttl=5), "r0")
        key = ("rt", 1)
        rep._store_frame(key, b"first")
        rep._store_frame(key, b"second")           # re-export, same rid
        assert list(rep._kv_frame_order).count(key) == 1
        for i in range(_KV_FRAME_KEEP - 1):        # age the store
            rep._store_frame(("rt", 100 + i), b"x")
        assert rep._kv_frames.get(key) == b"second"
        rep._store_frame(("rt", 999), b"x")        # now key is oldest
        assert key not in rep._kv_frames
        assert len(rep._kv_frames) == _KV_FRAME_KEEP

    def test_fetch_blob_uses_result_source_after_mark_dead(
            self, small_model, tmp_path):
        """The falsely-suspected-prefill salvage: by the time the late
        'prefilled' result arrives, _mark_dead deleted the handle — the
        frame fetch must go to the endpoint the result CAME from, not
        through the routing table."""
        cfg, params = small_model
        fleet = _DisaggReplicas(tmp_path, cfg, params, ["prefill"])
        try:
            rep = fleet.reps[0]
            router = DisaggRouter(fleet.registry)
            # a parked frame on the replica under this router's namespace
            code, ans = rep._h_enqueue(
                {"rid": 5, "prompt": _prompts(1, seed=9)[0],
                 "max_new_tokens": 4, "router": router._rid_ns,
                 "prefill_only": True})
            assert code == 200, ans
            deadline = time.time() + 30
            while (router._rid_ns, 5) not in rep._kv_frames:
                assert time.time() < deadline, "frame never exported"
                time.sleep(0.05)
            with rep._lk:
                meta = next(r["kv"] for r in rep._results
                            if r["rid"] == 5)
            req = RoutedRequest(5, [1, 2], 4, trace_id=0)
            req.replica = "serve.gone"   # handle already swept (no entry)
            blob = router._fetch_blob(req, meta, src=rep.endpoint)
            assert blob is not None and blob["data"], "salvage fetch died"
            assert len(blob["data"]) == meta["wire_bytes"]
            # and without src (pre-fix path) the handle miss returns None
            assert router._fetch_blob(req, meta, src=None) is None
        finally:
            fleet.stop()


# ------------------------------------------------------------ wire format

class TestTransferWire:
    def test_quantized_wire_ratio_both_grans(self, hd32_model):
        """Acceptance: the quantized page transfer ships ≤ 0.30× the f32
        byte count for the same live tokens, at BOTH scale
        granularities (payload itemsize + scale overhead)."""
        cfg, _ = hd32_model
        for dt in ("int8", "fp8"):
            for gran in ("row", "page"):
                r = wire_ratio_vs_f32(cfg, 8, dt, gran)
                assert r <= 0.30, (dt, gran, r)
        # page granularity is strictly cheaper than row granularity
        assert wire_ratio_vs_f32(cfg, 8, "fp8", "page") \
            < wire_ratio_vs_f32(cfg, 8, "fp8", "row")

    def test_page_gran_scale_bytes_page_size_x_fewer(self, hd32_model):
        cfg, _ = hd32_model
        row = wire_breakdown(cfg, 4, 8, "fp8", "row")
        page = wire_breakdown(cfg, 4, 8, "fp8", "page")
        assert row["scale_bytes"] == 8 * page["scale_bytes"]  # page_size×
        assert row["payload_bytes"] == page["payload_bytes"]
        assert wire_breakdown(cfg, 4, 8, None)["scale_bytes"] == 0

    def test_scale_gran_parser(self):
        assert normalize_scale_gran("") == "row"
        assert normalize_scale_gran(None) == "row"
        assert normalize_scale_gran("Page") == "page"
        with pytest.raises(ValueError):
            normalize_scale_gran("pge")

    def test_roundtrip_unquantized_bitwise(self, small_model):
        """f32 fallback wire: pool rows survive serialize→install
        bit-for-bit (f32 pool values round-trip exactly through the f32
        wire)."""
        cfg, _ = small_model
        rng = np.random.RandomState(0)
        src = init_paged_kv_cache(cfg, 6, 8)
        src = {k: tuple(v + rng.standard_normal(v.shape).astype(np.float32)
                        for v in bufs) for k, bufs in src.items()}
        ids = [2, 4, 1]
        blob = serialize_pages(cfg, src, ids, tlen=20, first=7,
                               kv_dtype=None)
        dst = init_paged_kv_cache(cfg, 6, 8)
        dst = install_pages(dst, cfg, [1, 3, 5], blob, None)
        got = gather_pages(dst, [1, 3, 5])
        want = gather_pages(src, ids)
        for leaf in ("k", "v"):
            for g, w in zip(got[leaf], want[leaf]):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_roundtrip_quantized_row_verbatim(self, small_model):
        """Row-granular quantized wire: payload AND scale pools land
        bit-identical in the destination — the disagg token-identity
        guarantee for quantized fleets."""
        import jax.numpy as jnp
        cfg, params = small_model
        eng = _engine(cfg, params, kv_dtype="int8")
        rid = eng.add_request(_prompts(1, seed=5, lo=12, hi=13)[0],
                              max_new_tokens=4, prefill_only=True)
        eng.run()
        pages = list(eng._parked[rid]["pages"])
        want = gather_pages(eng._cache, pages)
        blob = eng.export_kv(rid)
        assert blob["kv_dtype"] == "int8" and blob["scale_gran"] == "row"
        dst = init_paged_kv_cache(cfg, 8, 8, kv_dtype="int8")
        dst_ids = list(range(1, 1 + blob["n_pages"]))
        dst = install_pages(dst, cfg, dst_ids, blob, "int8")
        got = gather_pages(dst, dst_ids)
        for leaf in ("k", "v", "k_scale", "v_scale"):
            for g, w in zip(got[leaf], want[leaf]):
                np.testing.assert_array_equal(
                    np.asarray(g).view(np.uint8),
                    np.asarray(w).view(np.uint8))

    def test_geometry_mismatch_refused(self, small_model, hd32_model):
        cfg, params = small_model
        wcfg, _ = hd32_model
        eng = _engine(cfg, params)
        rid = eng.add_request([5, 6, 7, 8], max_new_tokens=4,
                              prefill_only=True)
        eng.run()
        blob = eng.export_kv(rid)
        dst = init_paged_kv_cache(wcfg, 6, 8)
        with pytest.raises(ValueError, match="does not fit this pool"):
            install_pages(dst, wcfg, [1], blob, None)


# --------------------------------------------------------- engine handoff

class TestBatcherHandoff:
    @pytest.mark.parametrize("read,kv_dtype", [
        ("paged", None), ("kernel", None), ("paged", "int8")])
    def test_handoff_token_identical(self, small_model, wide_model, read,
                                     kv_dtype):
        """The disagg core invariant: prefill on engine A + decode on
        engine B from transferred pages == llama_generate, where the
        decode steps read through the gather AND through the kernel
        (conftest.wide_model: the pages a prefill's paged_kv_scatter
        wrote, installed into another pool), full-precision AND quantized
        pools (bit-exact row-granular wire)."""
        cfg, params = wide_model if read == "kernel" else small_model
        reqs = list(zip(_prompts(4, seed=1), (6, 9, 5, 12)))
        outs, blobs = _handoff(
            cfg, params, reqs, kv_dtype=kv_dtype,
            kv_read="kernel" if read == "kernel" else "gather")
        for out, (p, m) in zip(outs, reqs):
            assert out == _reference(cfg, params, p, m)
        assert all(b["kv_dtype"] == kv_dtype for b in blobs.values())

    def test_prefilled_reason_and_parking(self, served):
        cfg, params, read = served
        eng = _engine(cfg, params)
        assert eng.stats["kv_read"] == read
        p = _prompts(1, seed=2)[0]
        rid = eng.add_request(p, max_new_tokens=8, prefill_only=True)
        out = eng.run()
        assert out[rid] and len(out[rid]) == 1      # exactly the first token
        assert eng.parked_count == 1
        assert eng.pages_in_use > 0                 # parked pages still held
        blob = eng.export_kv(rid)
        assert eng.parked_count == 0 and eng.pages_in_use == 0
        assert blob["tlen"] == len(p) and blob["first"] == out[rid][0]
        with pytest.raises(KeyError):
            eng.export_kv(rid)                      # one exit per park

    def test_prefill_only_no_decode_needed_completes(self, served):
        """mnt == 1: the prefill token IS the whole request — reason
        "complete", nothing parks (the router skips the decode stage)."""
        cfg, params, read = served
        eng = _engine(cfg, params)
        assert eng.stats["kv_read"] == read
        rid = eng.add_request(_prompts(1, seed=3)[0], max_new_tokens=1,
                              prefill_only=True)
        out = eng.run()
        assert len(out[rid]) == 1
        # no park, pool clean: reason was "complete" (nothing to export)
        assert eng.parked_count == 0 and eng.pages_in_use == 0
        with pytest.raises(KeyError):
            eng.export_kv(rid)

    def test_drop_parked_frees(self, small_model):
        cfg, params = small_model
        eng = _engine(cfg, params)
        rid = eng.add_request(_prompts(1, seed=4)[0], max_new_tokens=8,
                              prefill_only=True)
        eng.run()
        assert eng.drop_parked(rid) == 1
        assert eng.pages_in_use == 0

    def test_disagg_needs_paged_pool(self, small_model):
        cfg, params = small_model
        dense = _engine(cfg, params, kv_layout="dense")
        with pytest.raises(ValueError, match="paged"):
            dense.add_request([1, 2, 3], max_new_tokens=4,
                              prefill_only=True)
        eng = _engine(cfg, params)
        rid = eng.add_request([1, 2, 3, 4], max_new_tokens=4,
                              prefill_only=True)
        eng.run()
        blob = eng.export_kv(rid)
        with pytest.raises(ValueError, match="paged"):
            dense.add_request([1, 2, 3, 4], max_new_tokens=4,
                              kv_import=blob)
        with pytest.raises(ValueError, match="prompt"):
            eng.add_request([1, 2, 3], max_new_tokens=4, kv_import=blob)

    def test_page_gran_cost_measured_and_pinned(self, hd32_model):
        """The ISSUE 11 satellite's accuracy pin: the page-granular wire
        re-quantizes (row scales → page blocks → row scales), so its
        decode may diverge from the bit-exact row wire — measured here
        and bounded. Row-granular transfer is the exact baseline: its
        outputs equal a never-transferred quantized serve."""
        cfg, params = hd32_model
        reqs = list(zip(_prompts(4, seed=6, lo=5, hi=16), (8, 8, 8, 8)))
        row_out, _ = _handoff(cfg, params, reqs, kv_dtype="fp8",
                              scale_gran="row")
        page_out, blobs = _handoff(cfg, params, reqs, kv_dtype="fp8",
                                   scale_gran="page")
        # the coarse wire really engaged: page-gran scale bytes are
        # page_size× fewer than the row wire would carry
        for b in blobs.values():
            assert b["scale_gran"] == "page"
            assert b["scale_bytes"] * SPEC["batcher"]["page_size"] == \
                wire_breakdown(cfg, b["n_pages"], b["page_size"], "fp8",
                               "row")["scale_bytes"]
        # never-transferred quantized baseline == row-granular transfer
        base = _engine(cfg, params, kv_dtype="fp8")
        brids = [base.add_request(p, max_new_tokens=m) for p, m in reqs]
        bout = base.run()
        assert [bout[r] for r in brids] == row_out
        # measured agreement of the requantized wire, pinned: fixed
        # seeds make this deterministic (measured 0.875–1.0 per request)
        toks_total = agree = 0
        for ro, po in zip(row_out, page_out):
            toks_total += len(ro)
            agree += sum(a == b for a, b in zip(ro, po))
        assert agree / toks_total >= 0.8, (agree, toks_total)


# ------------------------------------------------------- roles + pressure

class TestRolesAndPressure:
    def test_role_parser(self):
        assert normalize_role("") == "unified"
        assert normalize_role(None) == "unified"
        assert normalize_role("Prefill") == "prefill"
        with pytest.raises(ValueError):
            normalize_role("prefil")

    def test_lease_and_health_carry_role(self, small_model, tmp_path):
        cfg, params = small_model
        fleet = _DisaggReplicas(tmp_path, cfg, params,
                                ["prefill", "decode"])
        try:
            pre, dec = fleet.reps
            assert pre.role == "prefill" and dec.role == "decode"
            assert fleet.registry.info(pre.replica_id)["role"] == "prefill"
            assert pre._health()["role"] == "prefill"
            h = dec._health()
            # the two-dimensional pressure surface (acceptance): queue
            # depth AND decode-pool page state on one probe
            for k in ("queue_depth", "free_pages", "queued_kv_pages",
                      "parked"):
                assert k in h, h
            # default role is unified — single-pool deployments never set
            # the flag and the lease says so
            eng = _engine(cfg, params)
            uni = ReplicaServer(eng, fleet.registry, "r9")
            assert uni.role == "unified"
            assert uni._lease_info()["role"] == "unified"
        finally:
            fleet.stop()

    def test_disagg_router_routes_by_role(self, small_model, tmp_path):
        """Prompt stage lands ONLY on the prefill replica, decode only on
        the decode replica — visible in each engine's own counters."""
        cfg, params = small_model
        fleet = _DisaggReplicas(tmp_path, cfg, params,
                                ["prefill", "decode"])
        try:
            router = DisaggRouter(fleet.registry)
            reqs = list(zip(_prompts(3, seed=7), (5, 8, 4)))
            rids = [router.submit(p, m) for p, m in reqs]
            out = router.wait(rids, timeout=60)
            for rid, (p, m) in zip(rids, reqs):
                assert out[rid] == _reference(cfg, params, p, m)
            pre_stats = fleet.reps[0]._b.stats
            dec_stats = fleet.reps[1]._b.stats
            assert pre_stats["prefills"] == 3
            assert pre_stats.get("kv_installs", 0) == 0
            assert dec_stats["prefills"] == 0
            assert dec_stats.get("kv_installs", 0) == 3
            s = router.summary()
            assert s["transfers"] == 3
            assert router.xfer_bytes_total > 0
            router.close()
        finally:
            fleet.stop()

    def test_base_router_ignores_roles(self, small_model, tmp_path):
        """The satellite's back-compat half: a plain Router over
        role-tagged replicas filters nothing (role=None) — candidate
        selection only specializes when a disagg stage asks."""
        cfg, params = small_model
        fleet = _DisaggReplicas(tmp_path, cfg, params,
                                ["prefill", "decode"])
        try:
            router = Router(fleet.registry)
            router.refresh(force=True)
            cands = router._candidates()
            assert {h.role for h in cands} == {"prefill", "decode"}
            # and the role filter itself: prefill stage excludes decode
            assert {h.role for h in router._candidates(role="prefill")} \
                == {"prefill"}
            router.close()
        finally:
            fleet.stop()

    def test_decide_pages_distinct_hint(self):
        """The second admission dimension computes its OWN retry-after:
        one service time (pages free when a request retires), not the
        queue dimension's depth-in-waves × p50."""
        pol = AdmissionPolicy(max_queue=8)
        hists = {"slo.e2e_s": {"p50": 2.0, "p95": 3.0}}
        assert pol.decide_pages(10, 4, hists) is None       # pages fit
        assert pol.decide_pages(None, 4, hists) is None     # dense pool
        d = pol.decide_pages(3, 4, hists)
        assert d["reason"] == "pool_pressure"
        assert d["retry_after_s"] == pytest.approx(2.0)     # ONE wave
        q = pol.retry_after(7, 4, hists)
        assert q == pytest.approx(4.0)                      # 2 waves × p50
        assert d["retry_after_s"] != q

    def test_kv_transfer_pool_pressure_429(self, small_model, tmp_path):
        """A page-starved decode replica answers /kv_transfer with 429
        pool_pressure + a computed hint — admission's second dimension at
        the HTTP boundary."""
        cfg, params = small_model
        pre = _engine(cfg, params)
        rid = pre.add_request(_prompts(1, seed=8, lo=14, hi=15)[0],
                              max_new_tokens=8, prefill_only=True)
        pre.run()
        blob = pre.export_kv(rid)

        registry = el.FileRegistry(str(tmp_path), "fleet", ttl=2.0)
        eng = _engine(cfg, params, admission=AdmissionPolicy(),
                      num_pages=8)
        rep = ReplicaServer(eng, registry, "d0", role="decode")
        held = eng._alloc.alloc(6)       # live streams hold the pool
        body = {"rid": 1, "prompt": blob and list(range(1, 1 + blob["tlen"])),
                "max_new_tokens": 8, "kv": blob, "router": "t"}
        code, ans = rep._h_kv_transfer(body)
        assert code == 429 and ans["reason"] == "pool_pressure", ans
        assert ans["retry_after_s"] > 0
        eng._alloc.free(held)
        code, ans = rep._h_kv_transfer(body)
        assert code == 200 and ans["ok"], ans
        # idempotent accept: a re-POST of the same (router, rid) while
        # queued must not install twice
        code, ans = rep._h_kv_transfer(body)
        assert code == 200 and ans.get("dedup"), ans


# ------------------------------------------------------ review hardening

class TestReviewHardening:
    def test_drifted_blob_refused_400_at_wire(self, small_model, tmp_path):
        """A truncated/mispacked blob answers 400 at /kv_transfer — spec
        drift must be refused at the boundary, never crash the decode
        serve loop (and every other in-flight request with it)."""
        cfg, params = small_model
        pre = _engine(cfg, params)
        rid = pre.add_request(_prompts(1, seed=30, lo=10, hi=11)[0],
                              max_new_tokens=6, prefill_only=True)
        pre.run()
        blob = pre.export_kv(rid)
        registry = el.FileRegistry(str(tmp_path), "fleet", ttl=2.0)
        rep = ReplicaServer(_engine(cfg, params,
                                    admission=AdmissionPolicy()),
                            registry, "d0", role="decode")
        bad = dict(blob)
        bad["data"] = bad["data"][: len(bad["data"]) // 2]
        body = {"rid": 7, "prompt": list(range(1, 1 + blob["tlen"])),
                "max_new_tokens": 6, "kv": bad, "router": "t"}
        code, ans = rep._h_kv_transfer(body)
        assert code == 400 and "invalid" in ans["reason"], ans
        # wrong-pool geometry is refused the same way
        wrong = dict(blob)
        wrong["page_size"] = 16
        code, ans = rep._h_kv_transfer({**body, "kv": wrong})
        assert code == 400, ans
        # a DENSE unified replica (valid decode candidate) has no pool at
        # all: still a 400 answer, never an AttributeError-turned-500 the
        # router would raise RuntimeError on
        dense = ReplicaServer(_engine(cfg, params, kv_layout="dense",
                                      admission=AdmissionPolicy()),
                              registry, "d1")
        code, ans = dense._h_kv_transfer(body)
        assert code == 400 and "dense" in ans["reason"], ans
        # an n_pages/tlen-inconsistent blob (inflated page claim with a
        # self-consistent byte count) is refused at the boundary too
        pre2 = _engine(cfg, params)
        rid2 = pre2.add_request(_prompts(1, seed=32, lo=18, hi=19)[0],
                                max_new_tokens=6, prefill_only=True)
        pre2.run()
        big = pre2.export_kv(rid2)          # 18 tokens → 3 pages
        inflated = dict(blob)               # 10-token prompt, but...
        inflated["n_pages"] = big["n_pages"]
        inflated["data"] = big["data"]      # ...3 pages of bytes
        code, ans = rep._h_kv_transfer({**body, "kv": inflated})
        assert code == 400 and "inconsistent" in ans["reason"], ans
        # a PREFILL replica refuses transfers outright (misdirected
        # routing must not retire as a serve-loop-side terminal error)
        pre_rep = ReplicaServer(_engine(cfg, params,
                                        admission=AdmissionPolicy()),
                                registry, "p1", role="prefill")
        code, ans = pre_rep._h_kv_transfer(body)
        assert code == 400 and "PREFILL" in ans["reason"], ans

    def test_bad_blob_costs_one_request_not_the_loop(self, small_model):
        """A blob the boundary never checked (direct add_request) fails
        as ONE terminal error result; the engine keeps serving and leaks
        no pages."""
        cfg, params = small_model
        pre = _engine(cfg, params)
        p = _prompts(1, seed=31, lo=10, hi=11)[0]
        rid = pre.add_request(p, max_new_tokens=6, prefill_only=True)
        pre.run()
        blob = pre.export_kv(rid)
        bad = dict(blob)
        bad["data"] = bad["data"][:8]
        dec = _engine(cfg, params)
        brid = dec.add_request(p, max_new_tokens=6, kv_import=bad)
        grid = dec.add_request(p, max_new_tokens=6)   # a healthy neighbor
        out = dec.run()
        assert out[brid] == []                        # terminal, empty
        assert out[grid] == _reference(cfg, params, p, 6)
        assert dec.pages_in_use == 0                  # nothing leaked

    def test_late_duplicate_prefilled_keeps_live_inflight(self,
                                                          small_model,
                                                          tmp_path):
        """A falsely-suspected prefill replica's late 'prefilled' result
        must not evict the LIVE decode-stage inflight entry — popping it
        would blind the dead-replica sweep and lose the request."""
        cfg, params = small_model
        registry = el.FileRegistry(str(tmp_path), "fleet", ttl=2.0)
        router = DisaggRouter(registry)
        req = RoutedRequest(0, [1, 2, 3], 8, trace_id=77)
        req.stage = "decode"
        req.replica = "serve.d0"
        router._requests[0] = req
        router._inflight[0] = req
        dup0 = router._fleet_counts["dup_results"]
        router._absorb({"router": router.router_id, "rid": 0,
                        "reason": "prefilled", "tokens": [5],
                        "kv": {"n_pages": 1}})
        assert 0 in router._inflight          # live decode entry survives
        assert router._fleet_counts["dup_results"] == dup0 + 1
        router.close()

    def test_accepted_prefilled_result_unpends_failover_copy(
            self, small_model, tmp_path):
        """A lease blip re-pends a request; when the FIRST attempt's
        prefilled result then arrives, the re-pended copy must leave the
        dispatch queue (the early result wins — no duplicate prompt
        pass)."""
        cfg, params = small_model
        registry = el.FileRegistry(str(tmp_path), "fleet", ttl=2.0)
        router = DisaggRouter(registry)
        req = RoutedRequest(0, [1, 2, 3], 8, trace_id=77)
        req.t_stage = 1.0
        router._requests[0] = req
        router.slo.on_enqueue(0, trace_id=77)
        router._pending.append(req)           # failover re-pended it
        pre = _engine(cfg, params)
        rid = pre.add_request([1, 2, 3], max_new_tokens=8,
                              prefill_only=True)
        pre.run()
        blob = pre.export_kv(rid)
        router._absorb({"router": router.router_id, "rid": 0,
                        "reason": "prefilled", "tokens": [blob["first"]],
                        "kv": blob})
        assert req.stage == "transfer"
        assert req not in router._pending     # no duplicate prompt pass
        assert list(router._xfer) == [0]
        router.close()


# ---------------------------------------------------------------- chaos

class TestDisaggChaos:
    def _run(self, tmp_path, cfg, params, spec, sub, n=3):
        fleet = _DisaggReplicas(tmp_path / sub, cfg, params,
                                ["prefill", "decode"])
        try:
            reqs = list(zip(_prompts(n, seed=9), (6, 9, 5)))
            with chaos.inject(spec or ""):
                router = DisaggRouter(fleet.registry)
                rids = [router.submit(p, m) for p, m in reqs]
                out = router.wait(rids, timeout=60)
                hits = dict(chaos.hit_counts())
            s = router.summary()
            router.close()
            return [out[r] for r in rids], s, hits, reqs
        finally:
            fleet.stop()

    def test_chaos_page_xfer_reprefills_token_identical(self, small_model,
                                                        tmp_path):
        """serve.page_xfer: the faulted transfer drops the blob and the
        request RE-PREFILLS — never lost, and chaos-on output is
        byte-identical to fault-free (analyzer A2's per-site test)."""
        cfg, params = small_model
        ff, _, _, reqs = self._run(tmp_path, cfg, params, None, "ff")
        on, s, hits, _ = self._run(tmp_path, cfg, params,
                                   "serve.page_xfer:1", "on")
        assert on == ff
        assert hits.get("serve.page_xfer", 0) >= 1
        assert s["xfer_faults"] >= 1 and s["reprefills"] >= 1
        for out, (p, m) in zip(on, reqs):
            assert out == _reference(cfg, params, p, m)

    def test_chaos_prefill_dead_defers_never_loses(self, small_model,
                                                   tmp_path):
        """serve.prefill_dead: a dead PREFILL replica's in-flight prompt
        passes fail over (the fault defers ONE re-enqueue a tick); every
        request still completes token-identical."""
        cfg, params = small_model
        fleet = _DisaggReplicas(tmp_path / "pd", cfg, params,
                                ["prefill", "prefill", "decode"], ttl=1.0)
        try:
            reqs = list(zip(_prompts(8, seed=10), (5, 7, 4, 6, 8, 5, 6, 4)))
            with chaos.inject("serve.prefill_dead:1"):
                router = DisaggRouter(fleet.registry)
                rids = [router.submit(p, m) for p, m in reqs]
                # kill a prefill replica hard before its results are ever
                # collected: its in-flight prompt passes MUST fail over
                dead = fleet.reps[0]
                dead.stop()
                out = router.wait(rids, timeout=90)
                hits = dict(chaos.hit_counts())
            for rid, (p, m) in zip(rids, reqs):
                assert out[rid] == _reference(cfg, params, p, m)
            s = router.summary()
            assert s["failovers_prefill"] >= 1, s
            assert hits.get("serve.prefill_dead", 0) >= 1
            assert s["failovers_decode"] == 0
            router.close()
        finally:
            fleet.stop()

    def test_decode_death_reprefills(self, small_model, tmp_path):
        """Stage-3 failover: a decode replica dying post-handoff loses
        the installed pages — the request re-prefills on the prefill
        pool and completes token-identical."""
        cfg, params = small_model
        fleet = _DisaggReplicas(tmp_path / "dd", cfg, params,
                                ["prefill", "decode", "decode"], ttl=1.0)
        try:
            router = DisaggRouter(fleet.registry)
            reqs = list(zip(_prompts(6, seed=11), (16, 20, 16, 18, 16, 20)))
            rids = [router.submit(p, m) for p, m in reqs]
            # tick until at least one request is DECODING, then kill THAT
            # replica hard (victim picked by observed stage, so the stop
            # is guaranteed post-handoff)
            deadline = time.time() + 60
            victim = None
            while time.time() < deadline:
                router.tick()
                stages = router.summary()["stages"]
                decoding = [rid for rid, st in stages.items()
                            if st == "decode"]
                if decoding:
                    victim = router._requests[decoding[0]].replica
                    break
                time.sleep(0.01)
            assert victim, "no request ever reached the decode pool"
            next(r for r in fleet.reps if r.replica_id == victim).stop()
            out = router.wait(rids, timeout=90)
            for rid, (p, m) in zip(rids, reqs):
                assert out[rid] == _reference(cfg, params, p, m)
            s = router.summary()
            assert s["failovers_decode"] >= 1, s
            router.close()
        finally:
            fleet.stop()


# ------------------------------------------------------------- e2e drill

class TestDisaggServingDrill:
    """ISSUE 11 acceptance drill: ≥2 prefill + ≥2 decode SUBPROCESS
    replicas behind the DisaggRouter. All requests complete
    token-identical to llama_generate at temp=0 under (a) fault-free,
    (b) SIGKILL of a prefill replica mid-pass, (c) SIGKILL of a decode
    replica post-handoff — trace ids preserved end-to-end, per-stage
    slo.* histograms populated."""

    def test_mixed_fleet_three_phase_drill(self, small_model, tmp_path):
        cfg, params = small_model
        stage_hists = ("slo.prefill_pool_s", "slo.transfer_s",
                       "slo.decode_pool_s")
        h0 = {h: metrics.histogram(h).stats()["count"]
              for h in stage_hists}
        fleet = ServingFleet(
            4, SPEC, root=str(tmp_path), ttl=1.2, n_prefill=2,
            env={"JAX_PLATFORMS": "cpu", "PADDLE_CHAOS": ""})
        try:
            fleet.start(timeout=180)
            router = fleet.router()
            assert isinstance(router, DisaggRouter)

            def submit_all(reqs):
                rids = []
                for p, m in reqs:
                    while True:
                        try:
                            rids.append(router.submit(p, m))
                            break
                        except AdmissionReject as e:
                            time.sleep(min(e.retry_after_s, 0.3))
                return rids

            def assert_identical(rids, reqs):
                out = router.wait(rids, timeout=180)
                for rid, (p, m) in zip(rids, reqs):
                    assert out[rid] == _reference(cfg, params, p, m), \
                        f"rid {rid} diverged"
                # trace ids end-to-end: the replica-reported id on the
                # terminal record equals the router-issued one
                for rid in rids:
                    req = router._requests[rid]
                    res = router.result(rid)
                    assert res is not None \
                        and res["trace_id"] == req.trace_id

            # (a) fault-free
            reqs_a = list(zip(_prompts(6, seed=20), (6, 9, 5, 12, 3, 8)))
            assert_identical(submit_all(reqs_a), reqs_a)
            for h in stage_hists:
                assert metrics.histogram(h).stats()["count"] - h0[h] >= 6, h

            # (b) SIGKILL a prefill replica mid-pass: submit a burst and
            # kill before its results are ever collected — its in-flight
            # prompt passes MUST fail over to the surviving prefill pool
            reqs_b = list(zip(_prompts(10, seed=21),
                              (5, 7, 4, 6, 8, 5, 6, 4, 7, 5)))
            rids_b = submit_all(reqs_b)
            fleet.kill("r0")
            assert_identical(rids_b, reqs_b)
            s = router.summary()
            assert s["failovers_prefill"] >= 1, s

            # (c) SIGKILL a decode replica post-handoff: long budgets,
            # wait until work is DECODING somewhere, then kill THAT
            # replica (the victim is picked by observed stage, so the
            # kill is guaranteed post-handoff)
            reqs_c = list(zip(_prompts(6, seed=22),
                              (20, 24, 20, 22, 20, 24)))
            rids_c = submit_all(reqs_c)
            deadline = time.time() + 60
            victim = None
            while time.time() < deadline:
                router.tick()
                stages = router.summary()["stages"]
                decoding = [rid for rid, st in stages.items()
                            if st == "decode"]
                if decoding:
                    victim = router._requests[decoding[0]].replica
                    break
                time.sleep(0.01)
            assert victim, "no request ever reached the decode pool"
            assert victim in ("serve.r2", "serve.r3")
            fleet.kill(victim[len("serve."):])
            assert_identical(rids_c, reqs_c)
            s = router.summary()
            assert s["failovers_decode"] >= 1, s
            # the dead replicas left the routing table
            assert "serve.r0" not in s["replicas"]
            assert victim not in s["replicas"]
            assert router.slo.summary()["inflight"] == 0
            router.close()
        finally:
            fleet.shutdown()


# -------------------------------------------- distributed tracing drill

class TestRequestTraceDrill:
    """ISSUE 17 acceptance on the disagg fleet: one end-to-end trace per
    request. A decode replica killed post-handoff forces a failover whose
    ASSEMBLED trace shows both attempts (a second req.prefill_pool span)
    under one trace id, spanning ≥3 processes, critical-path stages
    summing to e2e within the measured clock tolerance, chrome export
    with ≥3 tracks + flow arrows, served over real HTTP by GET /trace.
    And the no-perturbation half: tracing on, tracing off
    (PADDLE_REQTRACE=0), and chaos on trace.push all serve
    token-identical output."""

    def test_decode_kill_failover_assembles_one_trace(
            self, small_model, tmp_path, monkeypatch):
        import urllib.request
        cfg, params = small_model
        # a sub-ms e2e target every request breaches: the tail sampler
        # must RETAIN the failover request's full trace
        monkeypatch.setenv("PADDLE_SLO_E2E_S", "0.0001")
        fleet = _DisaggReplicas(tmp_path, cfg, params,
                                ["prefill", "decode", "decode"], ttl=1.0)
        try:
            router = DisaggRouter(fleet.registry)
            assert router.trace is not None        # on by default
            reqs = list(zip(_prompts(6, seed=17), (16, 20, 16, 18, 16, 20)))
            rids = [router.submit(p, m) for p, m in reqs]
            # tick until a request is DECODING, then kill THAT replica
            deadline = time.time() + 60
            victim = failover_rid = None
            while time.time() < deadline:
                router.tick()
                stages = router.summary()["stages"]
                decoding = [rid for rid, st in stages.items()
                            if st == "decode"]
                if decoding:
                    failover_rid = decoding[0]
                    victim = router._requests[failover_rid].replica
                    break
                time.sleep(0.01)
            assert victim, "no request ever reached the decode pool"
            next(r for r in fleet.reps if r.replica_id == victim).stop()
            out = router.wait(rids, timeout=90)
            for rid, (p, m) in zip(rids, reqs):
                assert out[rid] == _reference(cfg, params, p, m)
            assert router.summary()["failovers_decode"] >= 1

            req = router._requests[failover_rid]
            doc = router.trace.get_trace(failover_rid)
            assert doc is not None, router.trace.summary()
            # ONE trace id across every attempt and process
            assert doc["trace_id"] == req.trace_id
            assert doc["retained_for"] == "breach"
            # ≥3 processes: router + prefill replica + surviving decode
            assert len(doc["processes"]) >= 3, doc["processes"]
            assert doc["processes"][0] == "router"
            # BOTH attempts visible: the failover re-prefilled, so the
            # router timeline carries a SECOND req.prefill_pool span
            pool_spans = [s for s in doc["spans"]
                          if s["name"] == "req.prefill_pool"]
            assert len(pool_spans) >= 2, \
                [s["name"] for s in doc["spans"]]
            # critical path sums to e2e within the measured tolerance
            assert set(doc["crit"]) == set(
                ("router_queue", "prefill_queue", "prefill_compute",
                 "transfer", "decode_queue", "decode", "spec_verify",
                 "other"))
            tol = doc["clock"]["tolerance_s"] + 1e-4   # + retained rounding
            assert abs(sum(doc["crit"].values())
                       - doc["measured"]["e2e"]) <= tol
            # chrome export: one track per process, a flow chain across
            ct = router.trace.chrome_trace(doc)
            assert len({e["pid"] for e in ct["traceEvents"]}) >= 3
            flow = [e for e in ct["traceEvents"]
                    if e["ph"] in ("s", "t", "f")]
            assert flow and flow[0]["ph"] == "s" and flow[-1]["ph"] == "f"

            # the breach postmortem over REAL HTTP: GET /trace?rid=
            admin = router.start_admin()
            base = f"http://127.0.0.1:{admin.port}"
            with urllib.request.urlopen(
                    f"{base}/trace?rid={failover_rid}", timeout=10) as r:
                wire = json.loads(r.read().decode())
            assert wire["trace_id"] == doc["trace_id"]
            assert wire["breaches"], wire
            with urllib.request.urlopen(
                    f"{base}/trace?rid={failover_rid}&fmt=chrome",
                    timeout=10) as r:
                wire_ct = json.loads(r.read().decode())
            assert wire_ct["otherData"]["rid"] == failover_rid
            router.close()
        finally:
            fleet.stop()

    def _serve(self, tmp_path, cfg, params, sub, reqs, spec=None):
        fleet = _DisaggReplicas(tmp_path / sub, cfg, params,
                                ["prefill", "decode"])
        try:
            with chaos.inject(spec or ""):
                router = DisaggRouter(fleet.registry)
                rids = [router.submit(p, m) for p, m in reqs]
                out = router.wait(rids, timeout=60)
            trace_on = router.trace is not None
            router.close()
            return [out[r] for r in rids], trace_on
        finally:
            fleet.stop()

    def test_tracing_on_off_and_chaos_token_identical(
            self, small_model, tmp_path, monkeypatch):
        cfg, params = small_model
        reqs = list(zip(_prompts(3, seed=18), (6, 9, 5)))
        ref = [_reference(cfg, params, p, m) for p, m in reqs]
        # tracing ON (the default): token-identical
        on, trace_on = self._serve(tmp_path, cfg, params, "on", reqs)
        assert trace_on and on == ref
        # chaos at trace.push on EVERY ship: batches drop, tokens don't
        drops0 = metrics.counter("reqtrace.drops").value
        ch, _ = self._serve(tmp_path, cfg, params, "ch", reqs,
                            spec="trace.push:1+")
        assert ch == ref
        assert metrics.counter("reqtrace.drops").value > drops0
        # tracing OFF: the layer vanishes, tokens identical
        monkeypatch.setenv("PADDLE_REQTRACE", "0")
        off, trace_off = self._serve(tmp_path, cfg, params, "off", reqs)
        assert not trace_off and off == ref


# ------------------------------------------------- bench disagg contract

class TestDisaggBenchContract:
    def test_disagg_subobject_schema(self, monkeypatch, capsys):
        """PADDLE_SERVE_DISAGG=1 → the serving_bench JSON line gains the
        disagg sub-object (per-pool latency, transfer accounting with
        the quantized-vs-f32 wire ratio, per-stage failovers) — and the
        line survives the mid-drill prefill SIGKILL. The null-without-
        the-flag half is pinned on the already-paid-for bench run in
        tests/test_ragged_attention.py."""
        from benchmarks import serving_bench
        monkeypatch.setenv("SERVING_TRAIN_STEPS", "0")
        monkeypatch.setenv("PADDLE_SERVE_DISAGG", "1")
        monkeypatch.setenv("PADDLE_SERVE_PREFILL_REPLICAS", "2")
        monkeypatch.delenv("PADDLE_SERVE_REPLICAS", raising=False)
        monkeypatch.setenv("FLEET_DRILL_REQUESTS", "8")
        monkeypatch.setattr(sys, "argv", ["serving_bench.py", "2", "3", "4"])
        rc = serving_bench.main()
        line = [ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("{")][-1]
        doc = json.loads(line)
        assert rc == 0, doc
        d = doc["disagg"]
        assert d and "error" not in d, d
        assert d["prefill_replicas"] == 2 and d["decode_replicas"] == 2
        assert d["completed"] == d["requests"] == 8
        assert d["killed"] == "serve.r0"
        assert d["failovers"]["prefill"] >= 1       # the mid-drill SIGKILL
        xfer = d["transfer"]
        assert xfer["requests"] >= 8                # every request shipped
        assert xfer["bytes_per_request"] > 0
        assert xfer["transfer_s_p50"] > 0
        assert xfer["wire_ratio_vs_f32"] <= 0.30    # quantized wire win
        assert set(d["per_pool"]) >= {"prefill", "decode"}
        for pool in ("prefill", "decode"):
            for stats in d["per_pool"][pool].values():
                assert set(stats) == {"ttft_p50", "ttft_p95",
                                      "tpot_p50", "tpot_p95"}
        # ISSUE 17: critical-path TTFT attribution rides the same line —
        # per-stage p50/p95 SHARES of TTFT from the trace assembler
        crit = d["crit"]
        assert crit and crit["requests"] >= 1, crit
        assert set(crit["stages"]) == {"router_queue", "prefill_queue",
                                       "prefill_compute", "other"}
        for stats in crit["stages"].values():
            assert 0.0 <= stats["p50"] <= 1.0
            assert 0.0 <= stats["p95"] <= 1.0


# ------------------------------------------- sliced first hop (ISSUE 14)
class TestSlicedKvBlobHop:
    """ISSUE 14 satellite (ROADMAP PR-13 follow-up 1): the prefill→router
    /kv_blob hop is sliced too — the router probes the decode pool's
    prefix cache FIRST, then fetches ``?from_page=k``, so pages the
    destination already holds never cross EITHER hop. The replica slices
    the stored frame server-side, byte-equal to a local slice_blob."""

    def _frame_fixture(self, cfg, params):
        from paddle_tpu.inference.disagg.transfer import (blob_meta,
                                                          pack_frame)
        pre = _engine(cfg, params)
        prompt = list(range(1, 2 * 8 + 4))          # 3 pages at ps=8
        rid = pre.add_request(prompt, max_new_tokens=6, prefill_only=True)
        pre.run()
        blob = pre.export_kv(rid)
        frame = pack_frame({"kv": blob_meta(blob)}, blob["data"])
        return prompt, blob, frame

    def test_kv_blob_handler_slices_byte_equal(self, small_model,
                                               tmp_path):
        """GET /kv_blob?from_page=k returns a frame whose header is the
        sliced meta and whose payload is BYTE-EQUAL to slice_blob's —
        the install on the far side is therefore bit-identical to the
        full-transfer path's (install equality already pinned in
        tests/test_prefix_cache.py)."""
        from paddle_tpu.inference.disagg.transfer import (blob_meta,
                                                          slice_blob,
                                                          unpack_frame)
        cfg, params = small_model
        prompt, blob, frame = self._frame_fixture(cfg, params)
        reg = el.FileRegistry(str(tmp_path), "t", ttl=5.0)
        rep = ReplicaServer(_engine(cfg, params), reg, "p0",
                            role="prefill")
        rep._admin.start()   # handlers only; no serve loop, no heartbeat
        try:
            rep._store_frame(("ns", 7), frame)
            code, full = rep._h_kv_blob({"rid": ["7"], "router": ["ns"]})
            assert code == 200 and full == frame
            code, sliced_frame = rep._h_kv_blob(
                {"rid": ["7"], "router": ["ns"], "from_page": ["2"]})
            assert code == 200
            header, payload = unpack_frame(sliced_frame)
            want = slice_blob(blob, 2)
            assert payload == want["data"]            # byte-equal slice
            assert header["kv"] == blob_meta(want)
            assert len(sliced_frame) < len(frame) / 2  # the hop shrank
            # an over-slice (past the tail page) is refused loudly
            code, body = rep._h_kv_blob(
                {"rid": ["7"], "router": ["ns"], "from_page": ["3"]})
            assert code == 400
            code, body = rep._h_kv_blob(
                {"rid": ["7"], "router": ["ns"], "from_page": ["x"]})
            assert code == 400
        finally:
            rep._admin.stop()

    def _router_and_req(self, prompt, meta):
        class _Reg:
            def alive_nodes(self):
                return []

            def info(self, node):
                return {}

        router = DisaggRouter(_Reg())
        req = RoutedRequest(rid=1, prompt=prompt, max_new_tokens=4,
                            trace_id=0)
        req.trace_id = router.slo.on_enqueue(req.rid)
        router._requests[req.rid] = req
        req.kv = dict(meta)                      # meta only — no payload
        req.kv_src = "http://prefill"
        req.stage = "transfer"
        req.t_stage = 0.0
        return router, req

    def test_deferred_fetch_asks_from_page(self, small_model,
                                           monkeypatch):
        """_try_transfer with a meta-only blob probes the decode
        candidate, THEN fetches /kv_blob?from_page=k from the prefill
        replica — the skipped pages never cross the first hop — and the
        POSTed frame carries exactly the server-sliced payload."""
        from paddle_tpu.inference.disagg.transfer import (blob_meta,
                                                          pack_frame,
                                                          slice_blob,
                                                          unpack_frame)
        from paddle_tpu.inference.router import _Handle
        cfg, params = small_model
        prompt, blob, _frame = self._frame_fixture(cfg, params)
        router, req = self._router_and_req(prompt, blob_meta(blob))
        h = _Handle(id="serve.d0", endpoint="http://decode",
                    prefix_sharing=True, free_pages=64, role="decode",
                    ready=True)
        router._handles[h.id] = h
        fetched = {}

        def fake_get_bytes(endpoint, path, timeout=None):
            fetched["endpoint"], fetched["path"] = endpoint, path
            want = slice_blob(blob, 2)
            return pack_frame({"kv": blob_meta(want)}, want["data"])

        posted = {}

        def fake_post_bytes(endpoint, path, data, timeout=None):
            posted["path"], posted["data"] = path, data
            return 200, {"ok": True}

        monkeypatch.setattr(router, "_post",
                            lambda *a, **k: (200, {"from_page": 2}))
        monkeypatch.setattr(router, "_get_bytes", fake_get_bytes)
        monkeypatch.setattr(router, "_post_bytes", fake_post_bytes)
        assert router._try_transfer(req) == "routed"
        assert fetched["endpoint"] == "http://prefill"
        assert "from_page=2" in fetched["path"]
        hdr, payload = unpack_frame(posted["data"])
        assert payload == slice_blob(blob, 2)["data"]   # byte-equal
        assert router.xfer_pages_skipped == 2
        assert router._fleet_counts["transfers_sliced"] == 1
        router.close()

    def test_failover_refetches_missing_prefix(self, small_model,
                                               monkeypatch):
        """The in-hand blob was server-sliced for a WARM candidate that
        then 429'd: the walk's next (cold-cache) candidate must not be
        shipped an unsatisfiable from_page — the router refetches the
        missing prefix from the source and ships the full blob, instead
        of shedding a completed prefill into a re-prefill."""
        from paddle_tpu.inference.disagg.transfer import (blob_meta,
                                                          pack_frame,
                                                          slice_blob,
                                                          unpack_frame)
        from paddle_tpu.inference.router import _Handle
        cfg, params = small_model
        prompt, blob, _frame = self._frame_fixture(cfg, params)
        router, req = self._router_and_req(prompt, blob_meta(blob))
        warm = _Handle(id="serve.dw", endpoint="http://warm", role="decode",
                       prefix_sharing=True, free_pages=64, ready=True)
        cold = _Handle(id="serve.dc", endpoint="http://cold", role="decode",
                       prefix_sharing=False, free_pages=64, ready=True,
                       queue_depth=1)           # sorts after warm
        router._handles[warm.id] = warm
        router._handles[cold.id] = cold
        fetches = []

        def fake_get_bytes(endpoint, path, timeout=None):
            k = 0
            if "from_page=" in path:
                k = int(path.split("from_page=")[1].split("&")[0])
            fetches.append(k)
            b = slice_blob(blob, k) if k else blob
            return pack_frame({"kv": blob_meta(b)}, b["data"])

        posted = {}

        def fake_post_bytes(endpoint, path, data, timeout=None):
            if endpoint == "http://warm":
                return 429, {"retry_after_s": 0.1}
            posted["endpoint"], posted["data"] = endpoint, data
            return 200, {"ok": True}

        monkeypatch.setattr(router, "_post",
                            lambda *a, **k: (200, {"from_page": 2}))
        monkeypatch.setattr(router, "_get_bytes", fake_get_bytes)
        monkeypatch.setattr(router, "_post_bytes", fake_post_bytes)
        assert router._try_transfer(req) == "routed"
        assert fetches == [2, 0]       # sliced for warm, refetched full
        assert posted["endpoint"] == "http://cold"
        _hdr, payload = unpack_frame(posted["data"])
        assert payload == blob["data"]  # the cold pool got the FULL blob
        router.close()

    def test_sliced_accounting_survives_429_walk(self, small_model,
                                                 monkeypatch):
        """An in-hand blob already server-sliced at page 2 ships
        UNCHANGED to a second equally-warm candidate after the first
        429s — the transfer is still a sliced one, so
        transfers_sliced/xfer_pages_skipped count against the FULL blob
        (the old per-attempt recompute's accounting, kept)."""
        from paddle_tpu.inference.disagg.transfer import (blob_meta,
                                                          pack_frame,
                                                          slice_blob)
        from paddle_tpu.inference.router import _Handle
        cfg, params = small_model
        prompt, blob, _frame = self._frame_fixture(cfg, params)
        router, req = self._router_and_req(prompt, blob_meta(blob))
        a = _Handle(id="serve.da", endpoint="http://a", role="decode",
                    prefix_sharing=True, free_pages=64, ready=True)
        b = _Handle(id="serve.db", endpoint="http://b", role="decode",
                    prefix_sharing=True, free_pages=64, ready=True,
                    queue_depth=1)                 # sorts after a
        router._handles[a.id] = a
        router._handles[b.id] = b

        def fake_get_bytes(endpoint, path, timeout=None):
            want = slice_blob(blob, 2)
            return pack_frame({"kv": blob_meta(want)}, want["data"])

        monkeypatch.setattr(router, "_post",
                            lambda *ar, **k: (200, {"from_page": 2}))
        monkeypatch.setattr(router, "_get_bytes", fake_get_bytes)
        monkeypatch.setattr(
            router, "_post_bytes",
            lambda ep, path, data, timeout=None:
                ((429, {"retry_after_s": 0.1}) if ep == "http://a"
                 else (200, {"ok": True})))
        assert router._try_transfer(req) == "routed"
        assert router.xfer_pages_skipped == 2
        assert router._fleet_counts["transfers_sliced"] == 1
        router.close()

    def test_declined_candidate_costs_no_fetch(self, small_model,
                                               monkeypatch):
        """The pressure gate runs on (meta pages − probed prefix) BEFORE
        the fetch: a page-starved decode pool declines the transfer
        without the payload ever crossing the first hop; a gone frame
        surfaces as 'lost' → the established re-prefill recovery."""
        from paddle_tpu.inference.disagg.transfer import blob_meta
        from paddle_tpu.inference.router import _Handle
        cfg, params = small_model
        prompt, blob, _frame = self._frame_fixture(cfg, params)
        router, req = self._router_and_req(prompt, blob_meta(blob))
        h = _Handle(id="serve.d0", endpoint="http://decode",
                    prefix_sharing=False, free_pages=0, role="decode",
                    ready=True)
        router._handles[h.id] = h
        monkeypatch.setattr(
            router, "_get_bytes",
            lambda *a, **k: pytest.fail("fetched past a declined gate"))
        assert router._try_transfer(req) == "declined"
        # frame gone on a passing candidate: "lost", caller re-prefills
        h.free_pages = 64
        monkeypatch.setattr(router, "_get_bytes", lambda *a, **k: None)
        assert router._try_transfer(req) == "lost"
        router.close()
