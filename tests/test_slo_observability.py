"""Request-level SLO observability (ISSUE 6 tentpole + satellites).

The contracts under test:
  * TRACING — every request gets a process-unique MONOTONIC trace id at
    enqueue (staggered admission + preemption included); lifecycle edges
    fill the pre-registered TTFT/TPOT/queue-wait/e2e histograms; with span
    tracing on, per-request phase spans (req / req.queue / req.prefill /
    req.decode) land on the trace; paged==dense==generate parity is
    UNCHANGED with tracing + policy on.
  * POLICY — PADDLE_SLO_* targets; ``slo.breach`` fires EXACTLY once per
    breaching request (preempted and chaos-retired requests retire once),
    with a flight event naming (rid, trace id, dims).
  * EXPORT — MetricsExporter pushes Prometheus text (full
    ``_bucket{le=...}`` series) or OTLP/JSON to an external endpoint;
    failures (dead sink, chaos site ``telemetry.export``) are counted
    drops that never raise; a chaos-on serving run is token-identical to
    fault-free.
  * BUCKETS — /metrics serves real histogram exposition (cumulative
    bucket series + _sum/_count), exact counts.
  * AUTH — PADDLE_ADMIN_READ_TOKEN gates every admin GET (403 without).
  * LOGS — per-rank flight tails ride telemetry pushes; /logs?rank=N
    serves them (local ring without an aggregator).
  * TRIGGERS — fleet.straggler / slo.breach / watchdog.near_deadline
    signals arm a bounded XPlane window (locally, or on the offending
    rank via commands piggy-backed on the telemetry channel) and snapshot
    CAPTURE_<n>.json naming the breaching request; bounded by cooldown
    and max-captures.
  * LINT O4 — ad-hoc perf_counter/monotonic request timing in
    paddle_tpu/inference/ is banned (allowlist + marker honored).
  * DRILL — end-to-end: an SLO-breaching serve delivers TTFT/TPOT bucket
    series to a fake sink, the trigger engine auto-captures an XPlane
    window + snapshot naming the breaching request, and a chaos-on run
    (telemetry.export faults) serves token-identical output.
"""
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

import paddle_tpu.observability as obs
from paddle_tpu.observability import (admin, exporters, fleet, metrics,
                                      recorder, slo, spans, triggers, xplane)
from paddle_tpu.distributed.resilience import chaos

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


class _FakeProfiler:
    def __init__(self, broken=False):
        self.calls = []
        self.broken = broken

    def start_trace(self, d):
        if self.broken:
            raise RuntimeError("no device")
        self.calls.append(("start", d))

    def stop_trace(self):
        self.calls.append(("stop",))


@pytest.fixture(autouse=True)
def _clean_obs(monkeypatch):
    """Fresh telemetry state per test, plus a FAKE profiler: an armed
    trigger window must never start the real jax profiler inside the
    suite."""
    obs.reset()
    chaos.reset()
    fake = _FakeProfiler()
    monkeypatch.setattr(xplane, "_PROFILER", fake)
    yield fake
    obs.reset()
    chaos.reset()


@pytest.fixture(scope="module")
def small_model():
    import jax
    from paddle_tpu.models.llama import LlamaConfig, llama_init_params
    cfg = LlamaConfig.tiny(num_hidden_layers=2, max_position_embeddings=128)
    params = llama_init_params(cfg, jax.random.PRNGKey(3))
    return cfg, params


def _engine(cfg, params, **kw):
    from paddle_tpu.inference import ContinuousBatcher
    kw.setdefault("max_batch", 3)
    kw.setdefault("max_len", 96)
    kw.setdefault("prompt_buckets", (8, 16, 32))
    kw.setdefault("burst", 4)
    kw.setdefault("page_size", 8)
    return ContinuousBatcher(cfg, params, **kw)


def _mixed_requests(cfg, seed, spec):
    rng = np.random.RandomState(seed)
    return [(rng.randint(1, cfg.vocab_size, n).tolist(), m) for n, m in spec]


def _reference_generate(cfg, params, prompt, n):
    import jax.numpy as jnp
    from paddle_tpu.models.llama_decode import llama_generate
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None, :])
    out = llama_generate(params, toks, cfg, n, temperature=0.0)
    return [int(t) for t in np.asarray(out)[0]]


def _get(url, timeout=10, headers=None):
    req = urllib.request.Request(url, headers=headers or {})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read()


class _Sink:
    """In-test HTTP endpoint capturing POSTed export payloads."""

    def __init__(self):
        hits = self.hits = []

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                hits.append({"path": self.path,
                             "ctype": self.headers.get("Content-Type", ""),
                             "body": self.rfile.read(n) if n else b""})
                self.send_response(200)
                self.send_header("Content-Length", "2")
                self.end_headers()
                self.wfile.write(b"ok")

        self._httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True)
        self._thread.start()

    def url(self, path="/ingest"):
        return f"http://127.0.0.1:{self.port}{path}"

    def stop(self):
        self._httpd.shutdown()
        self._httpd.server_close()


@pytest.fixture()
def sink():
    s = _Sink()
    yield s
    s.stop()


# ------------------------------------------------------- request tracing

class TestRequestTracing:
    def test_trace_ids_unique_monotonic_with_preemption(self, small_model):
        """Staggered admission + a pool sized to force preemption: ids are
        unique, strictly increasing in enqueue order, stable across the
        preempt/re-admit cycle, and the latency histograms fill once per
        request."""
        cfg, params = small_model
        reqs = _mixed_requests(cfg, 37, [(5, 30), (5, 30), (9, 8), (4, 6)])
        eng = _engine(cfg, params, num_pages=8, page_size=8, burst=8)
        c0 = {h: metrics.histogram(h).count
              for h in (slo.HIST_TTFT, slo.HIST_E2E, slo.HIST_QUEUE)}
        rids, tids = [], []
        for p, m in reqs:
            rid = eng.add_request(p, max_new_tokens=m)
            rids.append(rid)
            tids.append(eng.slo.trace_id(rid))
        assert all(isinstance(t, int) for t in tids)
        assert len(set(tids)) == len(tids)
        assert tids == sorted(tids) and tids[0] < tids[-1]
        tid_mid = {r: eng.slo.trace_id(r) for r in rids}
        out = eng.run()
        assert eng.stats["preemptions"] >= 1
        # ids never changed mid-flight (preempted request keeps its trace)
        assert [tid_mid[r] for r in rids] == tids
        for rid, (p, m) in zip(rids, reqs):
            assert out[rid] == _reference_generate(cfg, params, p, m)
        for h, before in c0.items():
            assert metrics.histogram(h).count - before == len(reqs), h
        # TPOT fills only for requests with >= 2 tokens (all of these)
        assert metrics.histogram(slo.HIST_TPOT).count >= len(reqs) - 1

    def test_queue_wait_excludes_preempted_execution(self, monkeypatch):
        """Unit: queue wait is TIME WAITING only — enqueue→first admit
        plus each preemption→re-admit gap, never an attempt's execution.
        The tracker reads its clock through ``slo.now``: a stepped fake
        makes the sums exact."""
        clock = [100.0]
        monkeypatch.setattr(slo, "now", lambda: clock[0])

        def wait(dt):
            clock[0] += dt

        tr = slo.RequestTracker(policy=slo.SloPolicy())
        admits0 = metrics.histogram(slo.HIST_ADMIT_WAIT).count
        tr.on_enqueue(1)
        wait(0.03)                  # waiting in queue
        tr.on_admit(1)
        # the wait is observed AT admit, before anything retires
        h_admit = metrics.histogram(slo.HIST_ADMIT_WAIT)
        assert h_admit.count == admits0 + 1
        assert h_admit.stats()["last"] == pytest.approx(0.03)
        tr.on_first_token(1)
        wait(0.08)                  # EXECUTING (must not count)
        tr.on_preempt(1)
        wait(0.02)                  # waiting again
        tr.on_admit(1)
        assert h_admit.count == admits0 + 2
        assert h_admit.stats()["last"] == pytest.approx(0.02)
        # the admin /snapshot's "slo" object reads it while the request is
        # still in flight
        assert tr.summary()["admit_wait"]["count"] == admits0 + 2
        tr.on_retire(1, n_tokens=3)
        h = metrics.histogram(slo.HIST_QUEUE)
        assert h.count == 1
        assert h.stats()["last"] == pytest.approx(0.05)   # never the 0.08
        e2e = metrics.histogram(slo.HIST_E2E).stats()["last"]
        assert e2e == pytest.approx(0.13)   # e2e still covers the whole life
        ttft = metrics.histogram(slo.HIST_TTFT).stats()["last"]
        assert ttft == pytest.approx(0.03)

    def test_wall_clock_step_moves_no_deadline_and_no_wait(
            self, small_model, tmp_path, monkeypatch):
        """``slo.now`` is the time base of deadlines, timeouts and waits,
        and is monotonic: a step of the wall clock (NTP, a VM resume)
        expires nothing and stretches nothing. Only the spans of a retired
        request follow the wall clock, which is the span clock."""
        cfg, params = small_model
        step = [0]          # seconds the wall clock has been stepped by
        real_ns, real_s = time.time_ns, time.time
        monkeypatch.setattr(time, "time_ns",
                            lambda: real_ns() + step[0] * 10 ** 9)
        monkeypatch.setattr(time, "time", lambda: real_s() + step[0])

        tr = slo.RequestTracker(policy=slo.SloPolicy())
        tr.on_enqueue(1)
        spans.enable_tracing(str(tmp_path))
        try:
            eng = _engine(cfg, params)
            (p, m), = _mixed_requests(cfg, 43, [(6, 4)])
            rid = eng.add_request(p, max_new_tokens=m, deadline_s=600.0)
            t_mono = slo.now()
            step[0] = 3600      # the wall clock jumps an hour ahead
            assert slo.now() - t_mono < 60.0
            tr.on_admit(1)
            res = eng.run()
            t_wall = spans.now()
        finally:
            spans.disable_tracing()
        # the deadline (600 s) did not expire: the request ran to its end
        assert len(res[rid]) == m
        assert eng.stats.get("deadline_exceeded", 0) == 0
        # the wait the step straddled is the seconds that really passed
        assert metrics.histogram(slo.HIST_ADMIT_WAIT).stats()["last"] < 60.0
        assert metrics.histogram(slo.HIST_E2E).stats()["last"] < 600.0
        # and the request's spans sit on the span clock as it reads NOW
        whole, = [e for e in spans.events() if e["name"] == "req"
                  and e["args"]["rid"] == rid]
        end_s = (whole["ts"] + whole["dur"]) * 1e-6
        assert 0.0 <= t_wall - end_s < 60.0

    def test_phase_spans_land_on_the_trace(self, small_model, tmp_path):
        cfg, params = small_model
        spans.enable_tracing(str(tmp_path))
        try:
            eng = _engine(cfg, params)
            reqs = _mixed_requests(cfg, 41, [(6, 5), (12, 7)])
            rids = [eng.add_request(p, max_new_tokens=m) for p, m in reqs]
            eng.run()
        finally:
            spans.disable_tracing()
        evs = spans.events()
        req_spans = [e for e in evs if e.get("cat") == "request"]
        names = {e["name"] for e in req_spans}
        assert {"req", "req.queue", "req.prefill", "req.decode"} <= names
        whole = [e for e in req_spans if e["name"] == "req"]
        assert {e["args"]["rid"] for e in whole} == set(rids)
        assert all(e["args"]["trace"] > 0 for e in whole)
        assert all(e["dur"] >= 0 for e in req_spans)

    def test_paged_dense_parity_unchanged_with_tracing_on(self, small_model,
                                                          tmp_path):
        """ISSUE 6 satellite: tracing + an always-breaching policy on BOTH
        layouts changes nothing about the tokens."""
        cfg, params = small_model
        policy = slo.SloPolicy(ttft_s=1e-9, e2e_s=1e-9)
        spans.enable_tracing(str(tmp_path))
        try:
            reqs = _mixed_requests(
                cfg, 11, [(5, 7), (13, 3), (29, 12), (8, 1), (20, 6)])
            outs = {}
            for layout in ("paged", "dense"):
                eng = _engine(cfg, params, kv_layout=layout,
                              slo_policy=policy)
                rids = [eng.add_request(p, max_new_tokens=m)
                        for p, m in reqs]
                res = eng.run()
                outs[layout] = [res[r] for r in rids]
        finally:
            spans.disable_tracing()
        for (p, m), paged, dense in zip(reqs, outs["paged"], outs["dense"]):
            ref = _reference_generate(cfg, params, p, m)
            assert paged == ref and dense == ref, (len(p), m)


# --------------------------------------------------------------- policy

class TestSloPolicy:
    def test_env_targets_and_explicit_override(self, monkeypatch):
        monkeypatch.setenv(slo.ENV_TTFT, "0.25")
        monkeypatch.setenv(slo.ENV_E2E, "not-a-number")
        p = slo.SloPolicy()
        assert p.targets == {"ttft": 0.25} and p.active
        p2 = slo.SloPolicy(ttft_s=1.0, tpot_s=0.01)
        assert p2.targets == {"ttft": 1.0, "tpot": 0.01}
        # explicit zeros = no targets, whatever the env says
        assert not slo.SloPolicy(ttft_s=0, tpot_s=0, e2e_s=0,
                                 queue_s=0).active
        monkeypatch.delenv(slo.ENV_TTFT)
        assert not slo.SloPolicy().active

    def test_evaluate_only_measured_dims(self):
        p = slo.SloPolicy(ttft_s=0.1, e2e_s=10.0, queue_s=0.5)
        br = p.evaluate({"ttft": 0.2, "e2e": 1.0})  # no queue measurement
        assert [b["dim"] for b in br] == ["ttft"]
        assert br[0]["target"] == 0.1 and br[0]["value"] == 0.2

    def test_breach_fires_exactly_once_per_breaching_request(self,
                                                             small_model):
        """Preemption forces one request through two admission cycles; the
        breach counter still moves once per request."""
        cfg, params = small_model
        reqs = _mixed_requests(cfg, 37, [(5, 30), (5, 30)])
        before = metrics.counter("slo.breach").value
        eng = _engine(cfg, params, num_pages=8, page_size=8, burst=8,
                      slo_policy=slo.SloPolicy(e2e_s=1e-9))
        rids = [eng.add_request(p, max_new_tokens=m) for p, m in reqs]
        eng.run()
        assert eng.stats["preemptions"] >= 1
        assert metrics.counter("slo.breach").value - before == len(reqs)
        assert eng.slo.breached == len(reqs)
        evs = [e for e in recorder.events() if e["kind"] == "slo.breach"]
        assert {e["rid"] for e in evs} == set(rids)
        assert all("e2e" in [b["dim"] for b in e["breaches"]] for e in evs)

    def test_no_targets_no_breaches_histograms_still_fill(self, small_model,
                                                          monkeypatch):
        for var in (slo.ENV_TTFT, slo.ENV_TPOT, slo.ENV_E2E, slo.ENV_QUEUE):
            monkeypatch.delenv(var, raising=False)
        cfg, params = small_model
        before = metrics.counter("slo.breach").value
        h0 = metrics.histogram(slo.HIST_E2E).count
        eng = _engine(cfg, params)
        for p, m in _mixed_requests(cfg, 61, [(6, 4), (10, 5)]):
            eng.add_request(p, max_new_tokens=m)
        eng.run()
        assert metrics.counter("slo.breach").value == before
        assert metrics.histogram(slo.HIST_E2E).count - h0 == 2


# ----------------------------------------------------- bucket exposition

class TestHistogramBuckets:
    def test_exact_cumulative_buckets(self):
        h = metrics.histogram("lat_s")
        for v in (0.0005, 0.003, 0.003, 0.2, 99.0):
            h.observe(v)
        bounds, cum = h.buckets()
        assert cum[-1] == 5
        by = dict(zip(bounds, cum))
        assert by[0.001] == 1 and by[0.005] == 3 and by[0.25] == 4
        assert by[60.0] == 4  # 99 only lands in 120/300/+Inf

    def test_prometheus_renders_bucket_series(self):
        metrics.histogram("lat_s").observe(0.003)
        text = admin.render_prometheus(metrics.snapshot())
        assert "# TYPE paddle_lat_s histogram" in text
        assert 'paddle_lat_s_bucket{le="0.005"} 1' in text
        assert 'paddle_lat_s_bucket{le="+Inf"} 1' in text
        assert "paddle_lat_s_count 1" in text
        # labels stamp every sample
        lab = admin.render_prometheus(metrics.snapshot(),
                                      labels={"node": "n1"})
        assert 'paddle_lat_s_bucket{node="n1",le="0.005"} 1' in lab


# -------------------------------------------------------------- exporter

class TestExporters:
    def test_prom_export_delivers_bucket_series(self, sink):
        metrics.histogram(slo.HIST_TTFT).observe(0.02)
        metrics.counter("serve.requests").inc()
        exp = exporters.MetricsExporter(url=sink.url(), fmt="prom",
                                        labels={"node": "nX"})
        assert exp.export_once()
        assert len(sink.hits) == 1
        body = sink.hits[0]["body"].decode()
        assert sink.hits[0]["ctype"].startswith("text/plain")
        assert 'paddle_slo_ttft_s_bucket{node="nX",le="0.025"} 1' in body
        assert "paddle_serve_requests" in body
        assert metrics.counter("telemetry.exports").value == 1

    def test_otlp_export_and_url_autoselect(self, sink):
        metrics.histogram(slo.HIST_E2E).observe(1.5)
        exp = exporters.MetricsExporter(url=sink.url("/v1/metrics"))
        assert exp.fmt == "otlp"  # autoselected from the URL path
        assert exp.export_once()
        doc = json.loads(sink.hits[0]["body"])
        assert sink.hits[0]["ctype"] == "application/json"
        ms = doc["resourceMetrics"][0]["scopeMetrics"][0]["metrics"]
        hist = next(m for m in ms if m["name"] == slo.HIST_E2E)
        dp = hist["histogram"]["dataPoints"][0]
        assert dp["count"] == "1"
        assert len(dp["bucketCounts"]) == len(dp["explicitBounds"]) + 1
        assert sum(int(c) for c in dp["bucketCounts"]) == 1

    def test_dead_sink_is_a_counted_drop_never_a_raise(self):
        exp = exporters.MetricsExporter(url="http://127.0.0.1:9/x",
                                        fmt="prom", timeout=0.2)
        before = metrics.counter("telemetry.export_drops").value
        assert not exp.export_once()
        assert metrics.counter("telemetry.export_drops").value == before + 1
        assert any(e["kind"] == "telemetry.export_drop"
                   for e in recorder.events())

    def test_chaos_export_fault_swallowed_and_counted(self, sink):
        exp = exporters.MetricsExporter(url=sink.url(), fmt="prom")
        with chaos.inject("telemetry.export:1"):
            assert not exp.export_once()   # injected fault, no raise
            assert exp.export_once()       # next one delivers
        assert len(sink.hits) == 1
        assert metrics.counter("telemetry.export_drops").value == 1

    def test_multi_block_prom_merges_type_lines(self):
        """Per-rank export blocks render ONE # TYPE line per family with
        every block's labeled samples — strict ingesters reject duplicate
        TYPE declarations."""
        metrics.counter("train.steps").inc(5)
        snap = metrics.snapshot()
        rank_snap = {"counters": {"train.steps": 9}, "gauges": {},
                     "histograms": {}}
        text = exporters.prom_multi_text(
            [({"node": "n0", "role": "launcher"}, snap),
             ({"node": "n1", "rank": "1"}, rank_snap)])
        assert text.count("# TYPE paddle_train_steps counter") == 1
        assert 'paddle_train_steps{node="n0",role="launcher"} 5' in text
        assert 'paddle_train_steps{node="n1",rank="1"} 9' in text

    def test_aggregator_export_blocks_reach_the_sink(self, sink):
        """The launcher-side shape: aggregator per-rank snapshots ride the
        exporter, labeled (node, rank) — fleet metrics leave the pod."""
        agg = fleet.TelemetryAggregator()
        metrics.histogram("loop.step_time_s").observe(0.25)
        c = fleet.TelemetryClient(endpoint=None, directory=None, node="nA",
                                  rank=2, interval=0.0)
        report, _ = c.build_report(step=4)
        agg.ingest(report)
        exp = exporters.MetricsExporter(
            url=sink.url(), fmt="prom",
            blocks_fn=lambda: ([({"node": "n0", "role": "launcher"},
                                 metrics.snapshot())]
                               + agg.export_blocks()))
        assert exp.export_once()
        body = sink.hits[0]["body"].decode()
        assert 'node="nA",rank="2"' in body   # the RANK's series, labeled
        assert "paddle_loop_step_time_s_bucket" in body

    def test_shared_exporter_is_a_process_singleton(self, sink, monkeypatch):
        monkeypatch.setenv("PADDLE_METRICS_EXPORT_URL", sink.url())
        a = exporters.shared_from_env(labels={"role": "serving"})
        b = exporters.shared_from_env(labels={"role": "serving"})
        assert a is b and a is not None
        exporters.reset()
        assert exporters.shared_from_env() is not a

    def test_background_loop_and_final_flush(self, sink):
        exp = exporters.MetricsExporter(url=sink.url(), fmt="prom",
                                        interval=0.05).start()
        deadline = time.time() + 5
        while not sink.hits and time.time() < deadline:
            time.sleep(0.02)
        exp.stop()  # final flush pushes at least one more
        assert len(sink.hits) >= 2


# ------------------------------------------------------------- read auth

class TestAdminReadAuth:
    def test_get_routes_403_without_token(self, monkeypatch):
        metrics.counter("auth.unit").inc()
        srv = admin.AdminServer(port=0, host="127.0.0.1").start()
        base = f"http://127.0.0.1:{srv.port}"
        try:
            assert json.loads(_get(base + "/health"))["ok"]  # unset: open
            monkeypatch.setenv("PADDLE_ADMIN_READ_TOKEN", "s3cret")
            for route in ("/health", "/metrics", "/snapshot", "/flight",
                          "/ranks", "/logs"):
                with pytest.raises(urllib.error.HTTPError) as ei:
                    _get(base + route)
                assert ei.value.code == 403, route
            ok = _get(base + "/health",
                      headers={"X-Paddle-Admin-Token": "s3cret"})
            assert json.loads(ok)["ok"]
            ok = _get(base + "/metrics",
                      headers={"Authorization": "Bearer s3cret"})
            assert b"# TYPE" in ok
            with pytest.raises(urllib.error.HTTPError):
                _get(base + "/health",
                     headers={"X-Paddle-Admin-Token": "wrong"})
        finally:
            srv.stop()

    def test_push_keeps_its_own_job_token_discipline(self, monkeypatch):
        monkeypatch.setenv("PADDLE_ADMIN_READ_TOKEN", "s3cret")
        agg = fleet.TelemetryAggregator()
        srv = admin.AdminServer(port=0, aggregator=agg,
                                host="127.0.0.1").start()
        base = f"http://127.0.0.1:{srv.port}"
        try:
            body = json.dumps({"v": 1, "node": "n", "rank": 0,
                               "t_send": time.time()}).encode()
            req = urllib.request.Request(base + "/push", data=body,
                                         method="POST")
            req.add_header("X-Paddle-Job-Token", admin.job_token())
            resp = json.loads(urllib.request.urlopen(req, timeout=5).read())
            assert resp["ok"] and resp["commands"] == []
            assert agg.received == 1
        finally:
            srv.stop()


# ------------------------------------------------------------ /logs tail

class TestLogsRoute:
    def test_flight_tail_rides_pushes_and_serves_per_rank(self, tmp_path):
        recorder.record("unit.alpha", message="a0")
        c = fleet.TelemetryClient(directory=str(tmp_path), node="nA", rank=2,
                                  interval=0.0)
        assert c.maybe_push(step=1, force=True)
        recorder.record("unit.beta", message="b1")
        assert c.maybe_push(step=2, force=True)
        agg = fleet.TelemetryAggregator()
        agg.scan_dir(str(tmp_path))
        lines = agg.logs(2)
        kinds = [e["kind"] for e in lines]
        # incremental: each event shipped exactly once across the 2 pushes
        assert kinds.count("unit.alpha") == 1
        assert kinds.count("unit.beta") == 1
        assert all(e["node"] == "nA" and e["rank"] == 2 for e in lines)

        srv = admin.AdminServer(port=0, aggregator=agg,
                                host="127.0.0.1").start()
        base = f"http://127.0.0.1:{srv.port}"
        try:
            doc = json.loads(_get(base + "/logs?rank=2"))
            assert doc["source"] == "fleet" and doc["rank"] == 2
            assert any(e["kind"] == "unit.beta" for e in doc["lines"])
            assert json.loads(_get(base + "/logs?rank=7"))["lines"] == []
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(base + "/logs")  # aggregator mode needs rank=N
            assert ei.value.code == 400
        finally:
            srv.stop()

    def test_local_logs_without_aggregator(self):
        recorder.record("serve.unit", message="local line")
        srv = admin.AdminServer(port=0, host="127.0.0.1").start()
        try:
            doc = json.loads(
                _get(f"http://127.0.0.1:{srv.port}/logs?limit=50"))
            assert doc["source"] == "local"
            assert any(e["kind"] == "serve.unit" for e in doc["lines"])
        finally:
            srv.stop()


# -------------------------------------------------------------- triggers

class TestTriggers:
    def test_local_breach_arms_xplane_and_writes_capture(self, tmp_path,
                                                         _clean_obs):
        recorder.record("slo.breach", rid=7, trace_id=3, rank=0,
                        breaches=[{"dim": "ttft"}])
        eng = triggers.TriggerEngine(capture_dir=str(tmp_path),
                                     xplane_steps=2, cooldown_s=0.0)
        assert eng.poll() == 0                      # baseline: no new signal
        metrics.counter("slo.breach").inc()
        assert eng.poll() == 1
        assert metrics.counter("trigger.captures").value == 1
        # armed window opens at the next step boundary and closes 2 later
        xplane.maybe_step(5)
        xplane.maybe_step(7)
        assert [c[0] for c in _clean_obs.calls] == ["start", "stop"]
        cap = json.load(open(tmp_path / "CAPTURE_1.json"))
        assert cap["rule"] == "slo.breach" and cap["armed"] == "local"
        assert cap["breaches"] and cap["breaches"][0]["rid"] == 7

    def test_cooldown_and_max_captures_bound_the_engine(self, tmp_path):
        eng = triggers.TriggerEngine(capture_dir=str(tmp_path),
                                     cooldown_s=3600.0, max_captures=3)
        metrics.counter("slo.breach").inc()
        assert eng.poll() == 1
        metrics.counter("slo.breach").inc()
        assert eng.poll() == 0                      # inside the cooldown
        eng2 = triggers.TriggerEngine(capture_dir=str(tmp_path),
                                      cooldown_s=0.0, max_captures=2)
        for _ in range(4):
            metrics.counter("watchdog.near_deadline").inc()
            eng2.poll()
        assert len(eng2.captures) == 2              # capped

    def test_straggler_commands_the_offending_rank(self, tmp_path):
        """Fleet mode: a straggler event posts an xplane command for that
        (node, rank); the rank's client applies it at its next push (dir
        transport here)."""
        agg = fleet.TelemetryAggregator(straggler_k=1.5, straggler_checks=1)
        agg._cmd_dir = str(tmp_path)
        eng = triggers.TriggerEngine(aggregator=agg, cooldown_s=0.0,
                                     capture_dir=str(tmp_path))

        def rep(node, rank, busy):
            return {"v": 1, "node": node, "rank": rank, "gen": 0,
                    "t_send": time.time(), "anchor_wall": time.time(),
                    "anchor_perf": time.perf_counter(),
                    "step_time": {"p50": busy, "last": busy, "count": 3},
                    "wait_time": {"p50": 0.0, "count": 3},
                    "metrics": {"counters": {}, "gauges": {},
                                "histograms": {}}, "spans": []}

        for _ in range(2):
            agg.ingest(rep("n0", 0, 0.1))
            agg.ingest(rep("n1", 1, 0.1))
            agg.ingest(rep("n2", 2, 0.9))
        assert agg.straggler_events, "straggler never fired"
        assert eng.poll() == 1
        # the command file mirrors the queue for shared-dir transports
        cmd_file = tmp_path / "cmd.n2.2.jsonl"
        assert cmd_file.exists()
        cmd = json.loads(cmd_file.read_text().splitlines()[0])
        assert cmd["cmd"] == "xplane"
        # HTTP-queue side: take_commands drains exactly that rank's queue
        q = agg.take_commands("n2", 2)
        assert q and q[0]["cmd"] == "xplane"
        assert agg.take_commands("n2", 2) == []
        cap = json.load(open(tmp_path / "CAPTURE_1.json"))
        assert cap["node"] == "n2" and cap["rank"] == 2
        assert cap["step_table"][0]["node"] == "n2"

        # client side: a push from rank 2 reads the command file -> armed
        c = fleet.TelemetryClient(directory=str(tmp_path), node="n2", rank=2,
                                  interval=0.0)
        assert c.maybe_push(step=9, force=True)
        assert metrics.counter("telemetry.commands").value == 1
        assert xplane._state["armed"] is not None

    def test_http_push_response_carries_commands(self, _clean_obs):
        agg = fleet.TelemetryAggregator()
        srv = admin.AdminServer(port=0, aggregator=agg,
                                host="127.0.0.1").start()
        try:
            agg.post_command("nH", 3, {"cmd": "xplane", "steps": 1,
                                       "reason": "trigger:test"})
            c = fleet.TelemetryClient(endpoint=f"127.0.0.1:{srv.port}",
                                      node="nH", rank=3, interval=0.0)
            assert c.maybe_push(step=1, force=True)
            assert metrics.counter("telemetry.commands").value == 1
            assert xplane._state["armed"] is not None
            xplane.maybe_step(0)
            xplane.maybe_step(1)
            assert _clean_obs.calls and _clean_obs.calls[0][0] == "start"
        finally:
            srv.stop()

    def test_watchdog_near_deadline_counter_fires_trigger(self, monkeypatch):
        from paddle_tpu.distributed.comm_watchdog import watch
        monkeypatch.setenv("PADDLE_WATCHDOG_WARN_FRAC", "0.25")
        eng = triggers.TriggerEngine(cooldown_s=0.0)
        before = metrics.counter("watchdog.near_deadline").value
        with watch("slow-op", timeout=0.4, action="report"):
            time.sleep(0.25)   # past 25% of the budget, before the abort
        assert metrics.counter("watchdog.near_deadline").value == before + 1
        assert any(e["kind"] == "watchdog.near_deadline"
                   for e in recorder.events())
        assert eng.poll() == 1

    def test_disabled_by_env(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TRIGGERS", "0")
        assert not triggers.enabled()
        monkeypatch.delenv("PADDLE_TRIGGERS")
        assert triggers.enabled()


# -------------------------------------------------------------- lint O4

class TestLintRequestTiming:
    LINT = os.path.join(REPO, "tools", "lint_observability.py")

    def _run(self, root):
        return subprocess.run([sys.executable, self.LINT, str(root)],
                              capture_output=True, text=True, timeout=120)

    def test_repo_tree_is_clean(self):
        r = self._run(REPO)
        assert r.returncode == 0, r.stdout + r.stderr

    def test_flags_perf_counter_in_inference(self, tmp_path):
        pkg = tmp_path / "paddle_tpu" / "inference"
        pkg.mkdir(parents=True)
        (pkg / "bad_timing.py").write_text(
            "import time\n"
            "t0 = time.perf_counter()\n"
            "t1 = time.monotonic()\n")
        r = self._run(tmp_path)
        assert r.returncode == 1
        assert r.stdout.count("[O4]") == 2, r.stdout

    def test_outside_inference_not_in_scope(self, tmp_path):
        pkg = tmp_path / "paddle_tpu" / "models"
        pkg.mkdir(parents=True)
        (pkg / "fine.py").write_text("import time\nt = time.perf_counter()\n")
        r = self._run(tmp_path)
        assert r.returncode == 0, r.stdout

    def test_allowlist_and_marker_are_exempt(self, tmp_path):
        pkg = tmp_path / "paddle_tpu" / "inference"
        pkg.mkdir(parents=True)
        (pkg / "__init__.py").write_text(   # allowlisted path
            "import time\nt = time.perf_counter()\n")
        (pkg / "marked.py").write_text(
            "import time\n"
            "t = time.perf_counter()  # observability: ok (audited: test)\n")
        r = self._run(tmp_path)
        assert r.returncode == 0, r.stdout


# -------------------------------------------------- bench slo sub-object

class TestBenchSloContract:
    SLO_KEYS = {"ttft", "tpot", "e2e", "queue_wait", "breaches"}

    def test_absent_without_serving(self):
        assert slo.bench_payload() is None

    def test_schema_after_serving(self, small_model):
        cfg, params = small_model
        eng = _engine(cfg, params)
        for p, m in _mixed_requests(cfg, 71, [(6, 5), (11, 7)]):
            eng.add_request(p, max_new_tokens=m)
        eng.run()
        payload = slo.bench_payload()
        assert payload is not None
        assert set(payload) == self.SLO_KEYS
        for dim in ("ttft", "tpot", "e2e", "queue_wait"):
            assert set(payload[dim]) == {"p50", "p95", "count"}
        assert payload["e2e"]["count"] == 2
        assert payload["e2e"]["p95"] > 0
        assert isinstance(payload["breaches"], int)
        json.dumps(payload)


# ------------------------------------------------------------- the drill

class TestSloServingDrill:
    """ISSUE 6 acceptance: an SLO-breaching serve (decode slow relative to
    its micro-targets) → breach events name the request; the exporter
    delivers TTFT/TPOT bucket series to a local fake sink; the trigger
    engine auto-opens an XPlane window + writes a CAPTURE snapshot naming
    the breaching request and rank; and a chaos-on run (telemetry.export
    faults on EVERY export) serves token-identical output."""

    def _serve(self, cfg, params, tmp_path, tag):
        eng = _engine(
            cfg, params, burst=2,
            slo_policy=slo.SloPolicy(ttft_s=1e-7, tpot_s=1e-7))
        rids = [eng.add_request(p, max_new_tokens=m) for p, m in
                _mixed_requests(cfg, 83, [(6, 10), (12, 8), (5, 12)])]
        out = eng.run()
        eng.stop_exporter()
        return eng, rids, {r: out[r] for r in rids}

    def test_breach_export_capture_and_chaos_token_identity(
            self, small_model, tmp_path, sink, monkeypatch, _clean_obs):
        cfg, params = small_model
        trace = tmp_path / "trace"
        monkeypatch.setenv("PADDLE_TRACE_DIR", str(trace))
        monkeypatch.setenv("PADDLE_METRICS_EXPORT_URL", sink.url())
        monkeypatch.setenv("PADDLE_METRICS_EXPORT_INTERVAL", "0.05")
        monkeypatch.setenv("PADDLE_TRIGGER_XPLANE_STEPS", "1")
        monkeypatch.setenv("PADDLE_TRAINER_ID", "0")

        before = metrics.counter("slo.breach").value
        eng, rids, out = self._serve(cfg, params, tmp_path, "main")

        # --- every request breached (micro-targets vs real CPU decode)
        assert metrics.counter("slo.breach").value - before == len(rids)
        breach_evs = [e for e in recorder.events()
                      if e["kind"] == "slo.breach"]
        assert {e["rid"] for e in breach_evs} == set(rids)

        # --- trigger auto-capture: engine polled in-step, armed a window
        # that the later bursts opened+closed, and wrote the snapshot
        assert metrics.counter("trigger.captures").value >= 1
        kinds = [c[0] for c in _clean_obs.calls]
        assert "start" in kinds and "stop" in kinds
        cap = json.load(open(trace / "CAPTURE_1.json"))
        assert cap["rule"] == "slo.breach"
        assert cap["breaches"], "capture lost the breach context"
        assert cap["breaches"][0]["rid"] in rids
        assert cap["breaches"][0]["rank"] == 0
        assert any(e["kind"] == "trigger.capture" for e in recorder.events())

        # --- exporter delivered TTFT/TPOT bucket series to the fake sink
        # (background pushes during the run and/or the stop() final flush)
        assert sink.hits, "exporter never delivered"
        body = b"\n".join(h["body"] for h in sink.hits).decode()
        assert "paddle_slo_ttft_s_bucket{" in body
        assert "paddle_slo_tpot_s_bucket{" in body
        assert 'le="+Inf"' in body
        assert "paddle_slo_breach" in body

        # --- chaos on telemetry.export: EVERY export faults; tokens are
        # identical and the drops are accounted, never raised
        obs.reset()
        xplane.reset()
        drops0 = metrics.counter("telemetry.export_drops").value
        with chaos.inject("telemetry.export:1+"):
            _, rids2, out2 = self._serve(cfg, params, tmp_path, "chaos")
        assert [out[r] for r in rids] == [out2[r] for r in rids2]
        assert metrics.counter("telemetry.export_drops").value >= drops0
