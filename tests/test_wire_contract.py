"""Wire-contract registry + runtime mirror (ISSUE 15, rule A8).

The contracts under test:
  * REGISTRY — paddle_tpu/inference/routes.py declares every live HTTP
    route; importing the serving stack arms the AdminServer runtime
    mirror (admin.unregistered_route warn-once, never a raise) — the
    chaos.unregistered_site discipline applied to the wire.
  * ROUTES EXERCISED — the endpoints the A8 coverage check found named
    by no test (/hb, /info, /kvlist on the KV registry; /drain on the
    replica face) are exercised here over REAL HTTP, not just named.
  * A7 REGRESSION — the real finding the blocking-under-lock pass
    surfaced (elastic KVServer answered the bad-version 400 while
    HOLDING the store lock, so one slow/blackholed reader could stall
    every KV op fleet-wide) stays fixed: the 400 contract is pinned at
    the wire, and the old source shape stays pinned as an A7 fixture in
    test_static_analysis.py.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import urllib.error
import urllib.request

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from paddle_tpu.distributed.fleet.elastic import (  # noqa: E402
    FileRegistry, KVServer, _kv_token)
from paddle_tpu.observability import admin as _admin  # noqa: E402
from paddle_tpu.observability import recorder as _recorder  # noqa: E402


def _req(base, path, method="GET", data=None, headers=None, token=True):
    """(status, body bytes, headers) against a local server; HTTP errors
    are answers."""
    hdrs = {"X-Paddle-Job-Token": _kv_token()} if token else {}
    hdrs.update(headers or {})
    req = urllib.request.Request(base + path, method=method, data=data,
                                 headers=hdrs)
    try:
        with urllib.request.urlopen(req, timeout=5) as r:
            return r.status, r.read(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


@pytest.fixture()
def kv_server():
    srv = KVServer(ttl=5.0)
    srv.start()
    yield srv, f"http://127.0.0.1:{srv.port}"
    srv.stop()


class TestKVServerWire:
    """The registry endpoints the A8 coverage pass found unexercised."""

    def test_hb_heartbeat_and_deregister(self, kv_server):
        srv, base = kv_server
        st, _, _ = _req(base, "/hb/n0", "PUT",
                        json.dumps({"endpoint": "e0"}).encode())
        assert st == 200
        st, body, _ = _req(base, "/nodes")
        assert st == 200 and json.loads(body) == ["n0"]
        # DELETE /hb is the deregister half of the lease contract
        st, _, _ = _req(base, "/hb/n0", "DELETE")
        assert st == 200
        st, body, _ = _req(base, "/nodes")
        assert json.loads(body) == []

    def test_hb_put_requires_job_token(self, kv_server):
        srv, base = kv_server
        st, _, _ = _req(base, "/hb/n0", "PUT", b"{}", token=False)
        assert st == 403

    def test_info_payload_and_404_after_lapse(self, kv_server):
        srv, base = kv_server
        _req(base, "/hb/n1", "PUT", json.dumps({"endpoint": "e1",
                                                "role": "decode"}).encode())
        st, body, hdrs = _req(base, "/info/n1")
        assert st == 200
        assert json.loads(body) == {"endpoint": "e1", "role": "decode"}
        # the heartbeat wall time rides a header for quorum freshness picks
        assert float(hdrs["X-Paddle-HB-TS"]) > 0
        _req(base, "/hb/n1", "DELETE")
        st, _, _ = _req(base, "/info/n1")
        assert st == 404

    def test_kvlist_plain_and_versioned(self, kv_server):
        srv, base = kv_server
        _req(base, "/kv/enroll.3.a", "PUT", b"x")
        _req(base, "/kv/enroll.3.b", "PUT", b"y")
        _req(base, "/kv/other", "PUT", b"z")
        st, body, _ = _req(base, "/kvlist/enroll.3.")
        assert st == 200
        assert json.loads(body) == {"enroll.3.a": "x", "enroll.3.b": "y"}
        # ?v=1 answers [value, version, writer] triples (quorum merges)
        st, body, _ = _req(base, "/kvlist/enroll.3.?v=1")
        doc = json.loads(body)
        assert doc["enroll.3.a"][0] == "x" and doc["enroll.3.a"][1] >= 1

    def test_kv_bad_version_is_400_and_store_unharmed(self, kv_server):
        """The A7 fix regression (wire half): a malformed version header
        answers 400 — and because the parse now happens BEFORE the store
        lock, the refused write leaves the key untouched and every other
        op keeps flowing."""
        srv, base = kv_server
        _req(base, "/kv/gen", "PUT", b"7")
        st, _, _ = _req(base, "/kv/gen", "PUT", b"999",
                        headers={"X-Paddle-KV-Ver": "not-an-int"})
        assert st == 400
        st, body, _ = _req(base, "/kv/gen")
        assert st == 200 and body == b"7"


class _StubBatcher:
    """The minimal batcher surface ReplicaServer's HTTP face needs —
    lets the REAL /drain, /enqueue, /results handlers run over real HTTP
    without building a jitted engine."""

    B = 4
    admission = None
    pending = 0
    drained_called = 0

    def admin_summary(self):
        return {"stub": True}

    def health_summary(self):
        return {"queue_depth": 0, "draining": False, "ready": True,
                "active_slots": 0, "max_batch": self.B,
                "free_pages": None, "queued_kv_pages": 0}

    def check_admissible(self, prompt, mnt):
        pass

    def begin_drain(self):
        self.drained_called += 1


class TestReplicaDrainWire:
    def test_post_drain_flips_draining_and_429s_enqueue(self, tmp_path):
        """POST /drain over the wire: 200 {draining: true}, the batcher's
        drain protocol starts, /health reports draining, and a
        non-forced /enqueue now answers the declared 429."""
        from paddle_tpu.inference.replica import ReplicaServer
        b = _StubBatcher()
        rep = ReplicaServer(b, FileRegistry(str(tmp_path), "wire"), "w0")
        rep._admin.start()
        try:
            base = rep.endpoint
            tok = {"X-Paddle-Job-Token": _admin.job_token()}
            st, body, _ = _req(base, "/drain", "POST", b"{}", headers=tok)
            assert st == 200
            doc = json.loads(body)
            assert doc["ok"] is True and doc["draining"] is True
            assert b.drained_called == 1
            st, body, _ = _req(base, "/health", token=False)
            assert json.loads(body)["draining"] is True
            st, body, _ = _req(
                base, "/enqueue", "POST",
                json.dumps({"rid": 1, "prompt": [1, 2],
                            "max_new_tokens": 4}).encode(), headers=tok)
            assert st == 429
            assert json.loads(body)["reason"] == "draining"
            # /results still answers (the router collects during drain)
            st, body, _ = _req(base, "/results?since=0", token=False)
            assert st == 200
            assert json.loads(body)["draining"] is True
        finally:
            rep._admin.stop()


class TestWarmStartWire:
    """GET /warm_cache and /weights (ISSUE 16): the warm-start faces a
    scale-out replica fetches from — driven over real HTTP against a
    ReplicaServer carrying a WarmStartCache."""

    def test_warm_cache_and_weights_routes(self, tmp_path):
        import numpy as np
        from paddle_tpu.inference.replica import ReplicaServer
        from paddle_tpu.inference.warmstart import (
            WarmStartCache, unpack_cache_archive, unpack_params)
        cd = tmp_path / "jitcache"
        cd.mkdir()
        (cd / "entry0").write_bytes(b"xla-bits")
        params = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
        warm = WarmStartCache({"hidden": 8}, str(cd), params=params)
        rep = ReplicaServer(_StubBatcher(), FileRegistry(str(tmp_path),
                                                         "wire"),
                            "w1", warm=warm)
        rep._admin.start()
        try:
            base = rep.endpoint
            st, body, _ = _req(base, f"/warm_cache?spec={warm.hash}")
            assert st == 200 and body
            dest = tmp_path / "dest"
            assert unpack_cache_archive(body, str(dest)) == 1
            assert (dest / "entry0").read_bytes() == b"xla-bits"
            # hash mismatch -> the declared 404 (drifted fleet goes cold)
            st, _, _ = _req(base, "/warm_cache?spec=deadbeef")
            assert st == 404
            # missing spec param -> the declared 400
            st, _, _ = _req(base, "/warm_cache")
            assert st == 400
            st, body, _ = _req(base, f"/weights?spec={warm.hash}")
            assert st == 200
            p2 = unpack_params(body)
            np.testing.assert_array_equal(np.asarray(p2["w"]),
                                          params["w"])
            st, _, _ = _req(base, "/weights?spec=deadbeef")
            assert st == 404
            st, _, _ = _req(base, "/weights")
            assert st == 400
        finally:
            rep._admin.stop()


class TestRequestLifecycleWire:
    """POST /cancel (replica + router faces) and the /enqueue deadline
    field, ISSUE 19 — the request-lifecycle wire contract over real
    HTTP: every declared status (200/400/403 on /cancel, the 429
    deadline_unmeetable shed and 400 malformed-deadline on /enqueue) is
    driven, not just named."""

    def _rep(self, tmp_path, batcher=None):
        from paddle_tpu.inference.replica import ReplicaServer
        b = batcher or _StubBatcher()
        rep = ReplicaServer(b, FileRegistry(str(tmp_path), "wire"), "w2")
        rep._admin.start()
        return rep, b

    def test_enqueue_deadline_shed_and_bad_deadline(self, tmp_path):
        from paddle_tpu.inference.admission import AdmissionPolicy
        b = _StubBatcher()
        b.admission = AdmissionPolicy()
        rep, _ = self._rep(tmp_path, b)
        try:
            base = rep.endpoint
            tok = {"X-Paddle-Job-Token": _admin.job_token()}
            # an expired remaining budget is shed AT THE WIRE: the
            # declared 429 with the typed reason and a retry-after hint
            st, body, _ = _req(
                base, "/enqueue", "POST",
                json.dumps({"rid": 1, "prompt": [1, 2],
                            "max_new_tokens": 4,
                            "deadline_left_s": -1.0}).encode(),
                headers=tok)
            assert st == 429
            doc = json.loads(body)
            assert doc["reason"] == "deadline_unmeetable"
            assert doc["retry_after_s"] > 0
            # a malformed deadline is the declared 400, not a crash
            st, body, _ = _req(
                base, "/enqueue", "POST",
                json.dumps({"rid": 2, "prompt": [1, 2],
                            "max_new_tokens": 4,
                            "deadline_left_s": "soon"}).encode(),
                headers=tok)
            assert st == 400
            assert "bad deadline" in json.loads(body)["reason"]
            # a generous budget is admitted like any other request
            st, body, _ = _req(
                base, "/enqueue", "POST",
                json.dumps({"rid": 3, "prompt": [1, 2],
                            "max_new_tokens": 4,
                            "deadline_left_s": 600.0}).encode(),
                headers=tok)
            assert st == 200 and json.loads(body)["ok"] is True
        finally:
            rep._admin.stop()

    def test_replica_cancel_states_and_statuses(self, tmp_path):
        rep, _ = self._rep(tmp_path)
        try:
            base = rep.endpoint
            tok = {"X-Paddle-Job-Token": _admin.job_token()}
            st, body, _ = _req(
                base, "/enqueue", "POST",
                json.dumps({"rid": 7, "prompt": [1, 2],
                            "max_new_tokens": 4,
                            "router": "nsA"}).encode(), headers=tok)
            assert st == 200
            # still in intake → dropped right here with a typed result
            st, body, _ = _req(
                base, "/cancel", "POST",
                json.dumps({"rid": 7, "router": "nsA"}).encode(),
                headers=tok)
            assert st == 200
            doc = json.loads(body)
            assert doc["ok"] is True and doc["state"] == "intake"
            st, body, _ = _req(base, "/results?since=0", token=False)
            recs = json.loads(body)["results"]
            assert [r["reason"] for r in recs if r["rid"] == 7] \
                == ["cancelled"]
            # a rid this replica no longer holds: 200 no-op, NOT an error
            # (cancel racing retire loses cleanly — exactly-once)
            st, body, _ = _req(
                base, "/cancel", "POST",
                json.dumps({"rid": 7, "router": "nsA"}).encode(),
                headers=tok)
            assert st == 200 and json.loads(body)["state"] == "unknown"
            # malformed rid → the declared 400
            st, body, _ = _req(base, "/cancel", "POST",
                               json.dumps({"rid": "x"}).encode(),
                               headers=tok)
            assert st == 400
            assert "bad cancel" in json.loads(body)["reason"]
            # mutating route: 403 without the job token
            st, _, _ = _req(base, "/cancel", "POST", b'{"rid": 1}',
                            token=False)
            assert st == 403
        finally:
            rep._admin.stop()

    def test_router_admin_cancel_marks_only(self, tmp_path):
        """POST /cancel on the ROUTER admin face answers "marked" (the
        admin thread never walks router state — the router thread's
        next tick applies it) and 400 on a malformed rid."""
        from paddle_tpu.inference.router import Router
        router = Router(FileRegistry(str(tmp_path), "wire-rt", ttl=1.0))
        admin = router.start_admin()
        try:
            base = f"http://127.0.0.1:{admin.port}"
            tok = {"X-Paddle-Job-Token": _admin.job_token()}
            st, body, _ = _req(base, "/cancel", "POST",
                               json.dumps({"rid": 5}).encode(),
                               headers=tok)
            assert st == 200
            doc = json.loads(body)
            assert doc["ok"] is True and doc["state"] == "marked"
            assert doc["router"] == router.router_id
            assert router._cancel_marks == [5]   # applied on next tick
            st, _, _ = _req(base, "/cancel", "POST",
                            json.dumps({"rid": None}).encode(),
                            headers=tok)
            assert st == 400
        finally:
            router.close()


class TestReqTraceWire:
    """GET /trace_pull (replica face) and GET /trace (router admin face),
    ISSUE 17 — the distributed-tracing wire contract over real HTTP."""

    def test_trace_pull_route(self, tmp_path):
        from paddle_tpu.inference.replica import ReplicaServer
        rep = ReplicaServer(_StubBatcher(),
                            FileRegistry(str(tmp_path), "wire"), "w2")
        rep._admin.start()
        try:
            base = rep.endpoint
            # seed one retired-request span batch through the sink surface
            rep._tracebuf.publish({
                "rid": 3, "trace_id": 99, "reason": "complete",
                "tokens": 4, "preemptions": 0,
                "measured": {"e2e": 0.01}, "breaches": [],
                "spans": [{"name": "req", "t0": 0.0, "t1": 0.01,
                           "args": {}}]})
            st, body, _ = _req(base, "/trace_pull?cursor=0", token=False)
            assert st == 200
            doc = json.loads(body)
            assert doc["cursor"] == 1 and doc["base"] == 0
            assert doc["batches"][0]["trace_id"] == 99
            assert doc["source"] == rep.replica_id
            # every response carries a fresh clock anchor (the router's
            # NTP-style minimum filter feeds on these)
            assert doc["trace_clock"]["anchor_wall"] > 0
            assert "anchor_perf" in doc["trace_clock"]
            st, body, _ = _req(base, "/trace_pull?cursor=1", token=False)
            assert json.loads(body)["batches"] == []
            # the declared 400: non-integer cursor
            st, _, _ = _req(base, "/trace_pull?cursor=xyz", token=False)
            assert st == 400
        finally:
            rep._admin.stop()


class TestRouterTraceWire:
    def test_trace_route_json_chrome_and_errors(self, tmp_path):
        """GET /trace on the router's opt-in AdminServer: 200 JSON with
        the crit decomposition, fmt=chrome loads as a chrome trace, and
        the declared 400 (bad rid) / 404 (not retained) answers."""
        from paddle_tpu.inference.router import Router
        r = Router(FileRegistry(str(tmp_path), "wire"))
        try:
            assert r.trace is not None  # PADDLE_REQTRACE defaults on
            admin = r.start_admin()
            assert r.start_admin() is admin  # idempotent
            base = f"http://127.0.0.1:{admin.port}"
            r.trace.on_router_retire({
                "rid": 7, "trace_id": 42, "source": "router",
                "reason": "complete", "tokens": 4, "preemptions": 0,
                "measured": {"e2e": 0.02, "ttft": 0.01, "queue": 0.004},
                "breaches": [{"dim": "e2e", "value": 0.02,
                              "target": 0.001}],
                "spans": [{"name": "req", "t0": 0.0, "t1": 0.02,
                           "args": {}}]})
            st, body, _ = _req(base, "/trace?rid=7", token=False)
            assert st == 200
            doc = json.loads(body)
            assert doc["trace_id"] == 42
            assert doc["retained_for"] == "breach"
            assert abs(sum(doc["crit"].values())
                       - doc["measured"]["e2e"]) < 1e-4
            st, body, _ = _req(base, "/trace?rid=7&fmt=chrome",
                               token=False)
            assert st == 200
            ch = json.loads(body)
            assert any(e["ph"] == "M" for e in ch["traceEvents"])
            assert ch["otherData"]["trace_id"] == 42
            st, _, _ = _req(base, "/trace?rid=zzz", token=False)
            assert st == 400
            st, _, _ = _req(base, "/trace?rid=12345", token=False)
            assert st == 404
        finally:
            r.close()


class TestAutoscaleStatusWire:
    def test_autoscale_route_serves_status(self):
        """GET /autoscale on the controller's own AdminServer: the
        declared 200 with pools + hysteresis + the decision ledger."""
        from paddle_tpu.inference.autoscale import AutoscaleController
        ctl = AutoscaleController(lambda: [], None, ("prefill", "decode"),
                                  interval_s=900.0, status_port=0)
        ctl.start()
        try:
            base = f"http://127.0.0.1:{ctl.port}"
            st, body, _ = _req(base, "/autoscale", token=False)
            assert st == 200
            doc = json.loads(body)
            assert doc["enabled"] is True
            assert doc["pools"] == ["prefill", "decode"]
            assert doc["decisions"] == []
            assert set(doc["breach"]) == {"prefill", "decode"}
        finally:
            ctl.stop()


class TestAdminRouteMirror:
    """admin.unregistered_route: the runtime mirror of rule A8 — exactly
    the warn-once/never-raise contract chaos.hit keeps for sites."""

    def _mirror_events(self, since):
        return [e for e in _recorder.events_since(since)[0]
                if e.get("kind") == "admin.unregistered_route"]

    def test_registry_is_armed_by_serving_import(self):
        import paddle_tpu.inference.routes as routes
        assert _admin._declared_routes is not None
        assert "/enqueue" in _admin._declared_routes
        assert routes.route_of("/kv/gen?x=1") == "/kv"
        assert routes.route_of("") is None

    def test_undeclared_extension_route_warns_once_never_raises(self):
        import paddle_tpu.inference.routes  # noqa: F401  (arms the mirror)
        with _admin._routes_lock:
            _admin._warned_routes.discard("/zzz_undeclared")
        srv = _admin.AdminServer(
            get_routes={"/zzz_undeclared": lambda q: (200, {"ok": True})})
        srv.start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            before = _recorder.events_since(0)[1]
            st, body, _ = _req(base, "/zzz_undeclared", token=False)
            assert st == 200 and json.loads(body)["ok"] is True  # served!
            st, _, _ = _req(base, "/zzz_undeclared", token=False)
            assert st == 200
            evs = self._mirror_events(before)
            assert len(evs) == 1 and evs[0]["route"] == "/zzz_undeclared"
        finally:
            srv.stop()

    def test_declared_routes_warn_nothing(self):
        import paddle_tpu.inference.routes  # noqa: F401
        srv = _admin.AdminServer()
        srv.start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            before = _recorder.events_since(0)[1]
            for path in ("/health", "/metrics", "/snapshot", "/flight"):
                st, _, _ = _req(base, path, token=False)
                assert st == 200
            # an unknown path 404s silently: it was never SERVED, so the
            # mirror has nothing to report
            st, _, _ = _req(base, "/never_served", token=False)
            assert st == 404
            assert self._mirror_events(before) == []
        finally:
            srv.stop()


class TestBuiltinGetTupleNotDrifted:
    def test_builtin_get_matches_do_get_literals(self):
        """admin._BUILTIN_GET (what the runtime mirror checks) must stay
        in lockstep with the routes do_GET actually serves — a new
        builtin added to the if-chain but not the tuple would silently
        escape the very mirror ISSUE 15 built. The A8 collector IS the
        extractor of those literals, so the two can't drift unseen."""
        from tools.analyze.core import FileCtx
        from tools.analyze.rules_routes import WireContractRegistry
        rule = WireContractRegistry()
        ctx = FileCtx(REPO, "paddle_tpu/observability/admin.py")
        rule.check_file(ctx)
        served_get = {route for (_rel, _ln, route, method) in rule._regs
                      if method == "GET"}
        assert served_get == set(_admin._BUILTIN_GET)


class TestRegistryTableShape:
    def test_routes_values_are_well_formed(self):
        from paddle_tpu.inference.routes import IMPLIED_STATUSES, ROUTES
        assert set(IMPLIED_STATUSES) == {403, 404, 500}
        for route, spec in ROUTES.items():
            assert route.startswith("/") and "/" not in route[1:], route
            assert spec["methods"], route
            assert all(m in ("GET", "POST", "PUT", "DELETE")
                       for m in spec["methods"]), route
            assert 200 in spec["statuses"], route
            assert spec["doc"].strip(), route
