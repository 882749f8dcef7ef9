"""Request-lifecycle robustness (ISSUE 19 tentpole).

The contracts under test:
  * DEADLINE — a per-request latency budget rides every hop as remaining
    budget; a provably-unmeetable budget (expired, or below the observed
    TTFT floor) is shed typed ``deadline_unmeetable`` AT THE DOOR with a
    retry-after; an admitted-then-expired request retires typed
    ``deadline_exceeded`` — queued ones never start prefill past expiry,
    in-slot ones keep their partial output — pages freed, SLO measured
    exactly once, the trace force-retained for post-mortem.
  * CANCEL — cooperative cancellation by rid at every custody point
    (batcher queue/slot/parked pages, router pending/orphans/in-flight,
    POST /cancel from the admin thread) with exactly-once accounting: a
    cancel racing a retire LOSES cleanly, the pool gauge returns to
    baseline within one step, and the request.cancel chaos site degrades
    a cancel to best-effort (dropped mark, request runs on
    token-identically) — never to a lost request.
  * HEDGE — an in-flight request stalled past the adaptive hedge delay
    (p95 of slo.e2e_s, floored at PADDLE_HEDGE_DELAY_S, 0 = off) is
    re-posted same-rid to another replica under a global retry budget
    (PADDLE_RETRY_BUDGET_PCT token bucket: exhausted → counted once per
    request, no hedge — a sick fleet degrades to shedding, never a
    retry storm); first terminal result wins, the loser is cancelled,
    the client sees exactly one token-identical answer; the router.hedge
    chaos site skips a tick's hedge, never the request.
"""
import json
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from paddle_tpu.distributed.fleet import elastic as el
from paddle_tpu.distributed.resilience import chaos
from paddle_tpu.inference import (AdmissionPolicy, AdmissionReject,
                                  ContinuousBatcher, Router)
from paddle_tpu.inference.replica import ReplicaServer
from paddle_tpu.models.llama import LlamaConfig, llama_init_params
from paddle_tpu.models.llama_decode import llama_generate
from paddle_tpu.observability import metrics
from paddle_tpu.observability import slo as slo_mod

SPEC_BATCHER = {"max_batch": 3, "max_len": 96,
                "prompt_buckets": (8, 16, 32), "burst": 4, "page_size": 8}


@pytest.fixture(scope="module")
def small_model():
    cfg = LlamaConfig.tiny(num_hidden_layers=2, max_position_embeddings=128)
    params = llama_init_params(cfg, jax.random.PRNGKey(3))
    return cfg, params


def _engine(cfg, params, **kw):
    base = dict(SPEC_BATCHER)
    base.update(kw)
    return ContinuousBatcher(cfg, params, **base)


def _reference(cfg, params, prompt, n):
    import jax.numpy as jnp
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None, :])
    out = llama_generate(params, toks, cfg, n, temperature=0.0)
    return [int(t) for t in np.asarray(out)[0]]


def _prompt(seed=0, n=8):
    return np.random.RandomState(seed).randint(1, 256, n).tolist()


class _Replicas:
    """In-process replica harness: N ReplicaServers over one FileRegistry
    (threads, not processes — cheap; serving_bench's reliability drill is
    the subprocess path)."""

    def __init__(self, tmp_path, cfg, params, n=2, ttl=2.0, **engine_kw):
        self.registry = el.FileRegistry(str(tmp_path), "rel-fleet", ttl=ttl)
        self.reps = []
        for i in range(n):
            eng = _engine(cfg, params, admission=AdmissionPolicy(),
                          **engine_kw)
            self.reps.append(ReplicaServer(eng, self.registry,
                                           f"r{i}").start())

    def batcher(self, i):
        return self.reps[i]._b

    def stop(self):
        for rep in self.reps:
            rep.stop()


def _wait_pages_baseline(batchers, timeout=20.0):
    """Poll until every batcher's page pool is back to zero in-use."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if all(b.pages_in_use == 0 for b in batchers):
            return True
        time.sleep(0.02)
    return False


# ------------------------------------------------- batcher-level deadlines

class TestBatcherDeadline:
    def test_expired_budget_shed_typed_at_the_door(self, small_model):
        cfg, params = small_model
        eng = _engine(cfg, params)
        with pytest.raises(AdmissionReject) as ei:
            eng.add_request(_prompt(1), 4, deadline_s=0.0)
        assert ei.value.reason == "deadline_unmeetable"
        assert ei.value.retry_after_s > 0
        assert eng.pending == 0                  # never entered the queue

    def test_generous_deadline_token_identical(self, small_model):
        cfg, params = small_model
        eng = _engine(cfg, params)
        p = _prompt(2)
        rid = eng.add_request(p, 6, deadline_s=600.0)
        out = eng.run()
        assert out[rid] == _reference(cfg, params, p, 6)
        assert eng.stats.get("deadline_exceeded", 0) == 0

    def test_env_default_deadline_applies(self, small_model, monkeypatch):
        """PADDLE_REQUEST_DEADLINE_S is the fallback when the caller
        passes no deadline — an already-expired default rejects the
        same typed way an explicit one does."""
        cfg, params = small_model
        eng = _engine(cfg, params)
        monkeypatch.setenv("PADDLE_REQUEST_DEADLINE_S", "0.0")
        with pytest.raises(AdmissionReject) as ei:
            eng.add_request(_prompt(3), 4)
        assert ei.value.reason == "deadline_unmeetable"
        monkeypatch.setenv("PADDLE_REQUEST_DEADLINE_S", "")
        rid = eng.add_request(_prompt(3), 4)     # unset = no deadline
        assert eng.run()[rid]

    def test_queued_expiry_never_starts_prefill(self, small_model):
        """A queued request whose deadline passes retires typed with
        EMPTY output — expiry runs before this step's scheduling, so no
        prefill work is ever spent past the mark."""
        cfg, params = small_model
        eng = _engine(cfg, params)
        c0 = metrics.counter("serve.deadline_exceeded").value
        rid = eng.add_request(_prompt(4), 6, deadline_s=30.0)
        # force the clock past the deadline by fiat — no sleeping, and no
        # dependence on the admission gate's TTFT-floor estimate
        next(r for r in eng._queue if r.rid == rid).deadline = \
            slo_mod.now() - 1.0
        eng.step()
        fin = eng.take_finished()
        assert fin[rid].reason == "deadline_exceeded"
        assert fin[rid].out == []                # prefill never ran
        assert metrics.counter("serve.deadline_exceeded").value == c0 + 1
        assert eng.pages_in_use == 0
        assert eng.slo.summary()["inflight"] == 0   # measured, once

    def test_in_slot_expiry_keeps_partial_and_frees_pages(self, served):
        cfg, params, read = served
        eng = _engine(cfg, params)
        assert eng.stats["kv_read"] == read
        p = _prompt(5)
        rid = eng.add_request(p, 40, deadline_s=600.0)
        eng.step()                               # prefill + first decode
        eng.step()
        req = next(r for r in eng._slot_req if r is not None)
        assert req.rid == rid and req.out        # mid-decode, partial out
        req.deadline = slo_mod.now() - 1.0
        eng.step()                               # lifecycle pass expires it
        fin = eng.take_finished()
        assert fin[rid].reason == "deadline_exceeded"
        ref = _reference(cfg, params, p, 40)
        assert fin[rid].out == ref[:len(fin[rid].out)]   # partial, exact
        assert 0 < len(fin[rid].out) < 40
        assert eng.pages_in_use == 0             # slot + pages vacated


# ---------------------------------------------------- batcher-level cancel

class TestBatcherCancel:
    def test_cancel_queued_dropped_pool_baseline(self, small_model):
        cfg, params = small_model
        eng = _engine(cfg, params)
        c0 = metrics.counter("serve.cancelled").value
        rid = eng.add_request(_prompt(6), 6)
        assert eng.cancel(rid) is True
        eng.step()
        fin = eng.take_finished()
        assert fin[rid].reason == "cancelled" and fin[rid].out == []
        assert metrics.counter("serve.cancelled").value == c0 + 1
        assert eng.pages_in_use == 0 and eng.pending == 0

    def test_cancel_in_slot_partial_output_pages_freed(self, served):
        """Acceptance: cancelling a decoding request frees its pages
        within one step — the pool gauge returns to baseline."""
        cfg, params, read = served
        eng = _engine(cfg, params)
        assert eng.stats["kv_read"] == read
        c0 = metrics.counter("serve.cancelled").value
        rid = eng.add_request(_prompt(7), 40)
        eng.step()
        eng.step()
        assert eng.pages_in_use > 0              # holding pages mid-decode
        assert eng.cancel(rid) is True
        eng.step()                               # ONE step: applied + freed
        fin = eng.take_finished()
        assert fin[rid].reason == "cancelled" and fin[rid].out
        assert eng.pages_in_use == 0
        assert metrics.counter("serve.cancelled").value == c0 + 1
        assert eng.slo.summary()["inflight"] == 0

    def test_cancel_racing_retire_is_noop(self, small_model):
        """Exactly-once: a rid that already retired takes the cancel as
        a clean no-op — no second result, no second count."""
        cfg, params = small_model
        eng = _engine(cfg, params)
        rid = eng.add_request(_prompt(8), 4)
        out = eng.run()
        assert out[rid]
        c0 = metrics.counter("serve.cancelled").value
        assert eng.cancel(rid) is False          # retired: cancel loses
        assert eng.cancel(999) is False          # never issued: same
        eng.step()
        assert eng.take_finished() == {}
        assert metrics.counter("serve.cancelled").value == c0

    def test_request_cancel_chaos_drops_mark_token_identical(
            self, small_model):
        """request.cancel chaos site: the faulted cancel is DROPPED —
        cancellation is best-effort by contract, so the request runs on
        and completes token-identical to fault-free. Never a lost
        request, never changed tokens."""
        cfg, params = small_model
        eng = _engine(cfg, params)
        p = _prompt(9)
        rid = eng.add_request(p, 6)
        assert eng.cancel(rid) is True
        with chaos.inject("request.cancel:1"):
            out = eng.run()                      # fault eats the mark
            assert chaos.hit_counts().get("request.cancel", 0) >= 1
        assert out[rid] == _reference(cfg, params, p, 6)


# ------------------------------------------------- router-level lifecycle

class TestRouterLifecycle:
    def test_submit_deadline_unmeetable_shed_with_retry_after(
            self, small_model, tmp_path):
        cfg, params = small_model
        h = _Replicas(tmp_path, cfg, params, n=1)
        try:
            router = Router(h.registry)
            with pytest.raises(AdmissionReject) as ei:
                router.submit(_prompt(10), 4, deadline_s=0.0)
            assert ei.value.reason == "deadline_unmeetable"
            assert ei.value.retry_after_s > 0
            assert router.summary()["rejected"] == 1
            assert h.batcher(0).pending == 0   # never reached a replica
        finally:
            h.stop()

    def test_deadline_rides_hops_token_identical(self, small_model,
                                                 tmp_path):
        """An admitted deadline rides to the replica as remaining budget
        (deadline_left_s on /enqueue) and a generous one changes
        nothing: token-identical completion, no typed retires."""
        cfg, params = small_model
        h = _Replicas(tmp_path, cfg, params, n=1)
        try:
            router = Router(h.registry)
            p = _prompt(11)
            rid = router.submit(p, 6, deadline_s=600.0)
            out = router.wait([rid], timeout=60)
            assert out[rid] == _reference(cfg, params, p, 6)
            s = router.summary()
            assert s["deadline_exceeded"] == 0 and s["cancelled"] == 0
        finally:
            h.stop()

    def test_parked_expiry_retires_typed_and_trace_retained(
            self, small_model, tmp_path):
        """A request parked by a route fault whose deadline passes is
        retired typed BEFORE any re-route — and its trace is
        force-retained (retained_for=reliability) even though a sub-ms
        non-breaching e2e would normally be sampled out."""
        cfg, params = small_model
        h = _Replicas(tmp_path, cfg, params, n=1)
        try:
            router = Router(h.registry)
            with chaos.inject("serve.route:1"):
                rid = router.submit(_prompt(12), 6, deadline_s=600.0)
            assert router.summary()["pending"] == 1   # parked by the fault
            router._requests[rid].t_deadline = slo_mod.now() - 1.0
            router.tick()
            res = router.result(rid)
            assert res["reason"] == "deadline_exceeded"
            assert res["tokens"] == []           # never re-routed
            assert router.summary()["deadline_exceeded"] == 1
            assert router.slo.summary()["inflight"] == 0
            doc = router.trace.get_trace(rid)
            assert doc is not None
            assert doc["retained_for"] == "reliability"
        finally:
            h.stop()

    def test_cancel_parked_request_local_retire(self, small_model,
                                                tmp_path):
        cfg, params = small_model
        h = _Replicas(tmp_path, cfg, params, n=1)
        try:
            router = Router(h.registry)
            with chaos.inject("serve.route:1"):
                rid = router.submit(_prompt(13), 6)
            assert router.cancel(rid) == "cancelled"
            res = router.result(rid)
            assert res["reason"] == "cancelled" and res["tokens"] == []
            assert router.summary()["cancelled"] == 1
            assert router.cancel(rid) == "finished"   # no-op, no recount
            assert router.summary()["cancelled"] == 1
        finally:
            h.stop()

    def test_cancel_inflight_propagates_pages_freed_exactly_once(
            self, small_model, tmp_path):
        """Acceptance: cancelling a decoding request propagates to the
        replica, retires typed with partial output, frees its pages
        (pool gauge to baseline), and is measured exactly once."""
        cfg, params = small_model
        h = _Replicas(tmp_path, cfg, params, n=1)
        try:
            router = Router(h.registry)
            rid = router.submit(_prompt(14), 40)
            assert rid in router._inflight
            assert router.cancel(rid) == "propagated"
            out = router.wait([rid], timeout=60)
            res = router.result(rid)
            assert res["reason"] == "cancelled"
            assert len(out[rid]) < 40            # partial, not the budget
            s = router.summary()
            assert s["cancelled"] == 1 and s["dup_results"] == 0
            assert router.slo.summary()["inflight"] == 0
            assert _wait_pages_baseline([h.batcher(0)])
        finally:
            h.stop()

    def test_post_cancel_http_marks_then_router_thread_applies(
            self, small_model, tmp_path):
        """POST /cancel (admin thread) only MARKS the rid; the router
        thread's next tick applies it — and a bad body is a 400, not a
        crash."""
        cfg, params = small_model
        h = _Replicas(tmp_path, cfg, params, n=1)
        try:
            router = Router(h.registry)
            admin = router.start_admin()
            rid = router.submit(_prompt(15), 40)
            from paddle_tpu.observability.admin import job_token
            url = f"http://127.0.0.1:{admin.port}/cancel"
            hdrs = {"Content-Type": "application/json",
                    "X-Paddle-Job-Token": job_token()}
            req = urllib.request.Request(
                url, data=json.dumps({"rid": rid}).encode(),
                headers=hdrs)
            with urllib.request.urlopen(req, timeout=5) as r:
                body = json.loads(r.read())
            assert body["ok"] and body["state"] == "marked"
            assert body["router"] == router.router_id
            router.wait([rid], timeout=60)       # tick applies the mark
            assert router.result(rid)["reason"] == "cancelled"
            bad = urllib.request.Request(
                url, data=b'{"rid": "nope"}', headers=hdrs)
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(bad, timeout=5)
            assert ei.value.code == 400
        finally:
            router.close()
            h.stop()

    def test_request_cancel_chaos_at_router_defers_not_loses(
            self, small_model, tmp_path):
        """request.cancel at the router surface: the faulted cancel
        reports "deferred" and the request runs on token-identically —
        best-effort cancellation never loses the request."""
        cfg, params = small_model
        h = _Replicas(tmp_path, cfg, params, n=1)
        try:
            router = Router(h.registry)
            p = _prompt(16)
            rid = router.submit(p, 6)
            with chaos.inject("request.cancel:1"):
                assert router.cancel(rid) == "deferred"
            out = router.wait([rid], timeout=60)
            assert out[rid] == _reference(cfg, params, p, 6)
            assert router.summary()["cancelled"] == 0
        finally:
            h.stop()


# ------------------------------------------------------ hedged re-dispatch

class TestHedgedRedispatch:
    def _stalled(self, router, rid):
        """Make rid hedge-eligible by fiat: dispatched an hour ago, and
        the adaptive delay pinned to the floor (the process-global
        slo.e2e_s histogram carries other tests' latencies)."""
        router._requests[rid].t_dispatch = slo_mod.now() - 3600.0
        router._hedge_delay = lambda: 0.01

    def test_hedge_fires_winner_token_identical_loser_cancelled(
            self, small_model, tmp_path, monkeypatch):
        """Acceptance drill: a stalled request is re-posted same-rid to
        the other replica; the first terminal result wins and is
        token-identical to the reference; the loser is cancelled (both
        pools back to baseline); the client sees exactly one answer."""
        cfg, params = small_model
        monkeypatch.setenv("PADDLE_HEDGE_DELAY_S", "0.01")
        monkeypatch.setenv("PADDLE_RETRY_BUDGET_PCT", "100")
        h = _Replicas(tmp_path, cfg, params, n=2)
        try:
            router = Router(h.registry)
            p = _prompt(17)
            rid = router.submit(p, 40)
            self._stalled(router, rid)
            router.tick()
            s = router.summary()
            assert s["hedges"] == 1, s
            req = router._requests[rid]
            assert req.hedge_replica is not None
            assert req.hedge_replica != req.replica
            out = router.wait([rid], timeout=90)
            assert out[rid] == _reference(cfg, params, p, 40)
            s = router.summary()
            assert s["done"] == 1                # ONE answer, never two
            assert s["hedge_wins"] in (0, 1)
            assert router.slo.summary()["inflight"] == 0
            assert _wait_pages_baseline([h.batcher(0), h.batcher(1)])
        finally:
            h.stop()

    def test_hedge_fires_whatever_the_clock_reads(
            self, small_model, tmp_path, monkeypatch):
        """slo.now() is monotonic with an arbitrary zero (a machine up for
        under an hour reads less than the stall below): a dispatch time
        at or under zero is a dispatch time, "not dispatched" is None."""
        cfg, params = small_model
        monkeypatch.setenv("PADDLE_HEDGE_DELAY_S", "0.01")
        monkeypatch.setenv("PADDLE_RETRY_BUDGET_PCT", "100")
        t0 = time.perf_counter() - 100.0         # a clock that reads ~100 s
        monkeypatch.setattr(slo_mod, "now",
                            lambda: time.perf_counter() - t0)
        h = _Replicas(tmp_path, cfg, params, n=2)
        try:
            router = Router(h.registry)
            assert all(r.t_dispatch is None
                       for r in router._requests.values())
            rid = router.submit(_prompt(19), 12)
            self._stalled(router, rid)
            assert router._requests[rid].t_dispatch < 0
            router.tick()
            assert router.summary()["hedges"] == 1
            assert router.wait([rid], timeout=90)[rid]
        finally:
            h.stop()

    def test_zero_budget_means_zero_hedges_counted_once(
            self, small_model, tmp_path, monkeypatch):
        """PADDLE_RETRY_BUDGET_PCT=0: the bucket starts empty and never
        earns — no hedge ever fires, and the exhaustion is counted ONCE
        per request (latched), not once per tick: a sick fleet degrades
        to shedding, never a retry storm."""
        cfg, params = small_model
        monkeypatch.setenv("PADDLE_HEDGE_DELAY_S", "0.01")
        monkeypatch.setenv("PADDLE_RETRY_BUDGET_PCT", "0")
        h = _Replicas(tmp_path, cfg, params, n=2)
        try:
            router = Router(h.registry)
            p = _prompt(18)
            rid = router.submit(p, 30)
            self._stalled(router, rid)
            router.tick()
            router.tick()                        # second tick: no recount
            s = router.summary()
            assert s["hedges"] == 0
            assert s["retry_budget_exhausted"] == 1
            out = router.wait([rid], timeout=90)
            assert out[rid] == _reference(cfg, params, p, 30)
        finally:
            h.stop()

    def test_hedge_off_by_default(self, small_model, tmp_path,
                                  monkeypatch):
        monkeypatch.delenv("PADDLE_HEDGE_DELAY_S", raising=False)
        cfg, params = small_model
        h = _Replicas(tmp_path, cfg, params, n=2)
        try:
            router = Router(h.registry)
            rid = router.submit(_prompt(19), 20)
            self._stalled(router, rid)
            router.tick()
            assert router.summary()["hedges"] == 0
            router.wait([rid], timeout=90)
        finally:
            h.stop()

    def test_router_hedge_chaos_skips_tick_token_identical(
            self, small_model, tmp_path, monkeypatch):
        """router.hedge chaos site: the faulted tick skips its hedge —
        the primary still owns the request and completes
        token-identical; the budget is never spent on a skipped
        hedge."""
        cfg, params = small_model
        monkeypatch.setenv("PADDLE_HEDGE_DELAY_S", "0.01")
        monkeypatch.setenv("PADDLE_RETRY_BUDGET_PCT", "100")
        h = _Replicas(tmp_path, cfg, params, n=2)
        try:
            router = Router(h.registry)
            tokens0 = router._retry_tokens
            p = _prompt(20)
            rid = router.submit(p, 20)
            self._stalled(router, rid)
            with chaos.inject("router.hedge:1+"):
                out = router.wait([rid], timeout=90)
                assert chaos.hit_counts().get("router.hedge", 0) >= 1
            s = router.summary()
            assert s["hedges"] == 0              # every tick's hedge skipped
            assert out[rid] == _reference(cfg, params, p, 20)
            # budget intact: earned per dispatch, never spent on a skip
            assert router._retry_tokens >= tokens0
        finally:
            h.stop()


# ----------------------------------------- serving_bench reliability drill

class TestReliabilityBenchContract:
    def test_reliability_subobject_schema(self, monkeypatch, capsys):
        """PADDLE_SERVE_RELIABILITY=1 → the JSON line gains the
        reliability sub-object with the typed-outcome counters, and
        every admitted request accounts for exactly one terminal
        reason. (Absence with the gate off is pinned on the fleet bench
        run in test_serving_fleet.py.)"""
        import sys as _sys

        from benchmarks import serving_bench
        monkeypatch.setenv("SERVING_TRAIN_STEPS", "0")
        monkeypatch.setenv("PADDLE_SERVE_RELIABILITY", "1")
        monkeypatch.setenv("RELIABILITY_DRILL_REQUESTS", "6")
        monkeypatch.setattr(_sys, "argv",
                            ["serving_bench.py", "2", "3", "4"])
        rc = serving_bench.main()
        line = [ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("{")][-1]
        doc = json.loads(line)
        assert rc == 0, doc
        rel = doc["reliability"]
        assert rel and "error" not in rel, rel
        for k in ("requests", "shed", "completed", "cancelled",
                  "deadline_exceeded", "hedges", "hedge_wins",
                  "retry_budget_exhausted", "dup_results"):
            assert k in rel, k
        assert rel["shed"] == 1                  # the expired-budget probe
        # exactly-once: every admitted request has ONE terminal reason
        assert sum(rel["terminal_reasons"].values()) == rel["requests"]
        assert "missing" not in rel["terminal_reasons"]
