"""Fleet-wide per-request distributed tracing (ISSUE 17) — unit contracts.

The contracts under test, bottom-up:
  * TAXONOMY — slo.SPAN_TAXONOMY is the single source of every req.* span
    name: the disagg STAGES table and every span a retire emits resolve
    into it (rule O5 polices the rest of the tree).
  * SINK — RequestTracker.trace_sink receives one payload per retire with
    the full span list; a raising sink never reaches the scheduler; a
    rejected request never reaches the sink.
  * BUFFER — ReplicaSpanBuffer publish/collect/pull: collect pops the
    piggy-back exactly once, pull is cursor-addressed with rewind, both
    stores bound by keep, publish is a no-op with PADDLE_REQTRACE=0.
  * CHAOS — a fault at ``trace.push`` drops the batch (reqtrace.drops),
    collect answers None (the /results record ships untouched), and the
    batch stays recoverable through the /trace_pull log.
  * ASSEMBLY — the router assembler aligns a replica clock 1000s of
    perf-skew away onto its own wall timeline, the critical-path stages
    sum to e2e, the chrome export grows one track per process plus a
    cross-process flow chain, redelivered batches dedup.
  * TAIL SAMPLER — non-breaching fast requests feed the histograms then
    drop; breaches and the sliding slowest-p99 are retained, ring bounded.

The end-to-end drill (real fleet, failover, HTTP /trace) lives in
tests/test_disagg_serving.py; the wire shapes in test_wire_contract.py.
"""
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from paddle_tpu.distributed.resilience import chaos  # noqa: E402
from paddle_tpu.observability import metrics  # noqa: E402
from paddle_tpu.observability import reqtrace, slo  # noqa: E402
from paddle_tpu.observability.reqtrace import (CRIT_STAGES,  # noqa: E402
                                               TTFT_STAGES,
                                               ReplicaSpanBuffer,
                                               RouterTraceAssembler)


# ------------------------------------------------------------- taxonomy

class TestSpanTaxonomy:
    def test_stage_span_names_live_in_the_taxonomy(self):
        # pinned here by name (slo.py's STAGES comment points at this
        # test): every disagg stage span resolves into SPAN_TAXONOMY
        for stage, (hist, span_name) in slo.STAGES.items():
            assert span_name in slo.SPAN_TAXONOMY, \
                f"STAGES[{stage!r}] span {span_name!r} not in SPAN_TAXONOMY"
            assert hist.startswith("slo.")

    def test_taxonomy_names_are_req_namespaced(self):
        for name in slo.SPAN_TAXONOMY:
            assert name == "req" or name.startswith("req."), name

    def test_crit_stages_shape(self):
        assert CRIT_STAGES[-1] == "other"      # the residual absorber
        assert set(TTFT_STAGES) <= set(CRIT_STAGES)
        assert reqtrace.crit_hist("decode") == "slo.crit.decode_s"

    def test_master_switch(self, monkeypatch):
        monkeypatch.delenv(reqtrace.ENV_ON, raising=False)
        assert reqtrace.enabled()              # default ON
        for off in ("0", "false", "NO", "off"):
            monkeypatch.setenv(reqtrace.ENV_ON, off)
            assert not reqtrace.enabled()
        monkeypatch.setenv(reqtrace.ENV_ON, "1")
        assert reqtrace.enabled()


# ----------------------------------------------------- tracker -> sink

class TestTrackerSink:
    def _run_one(self, tracker, rid=1, tid=77, n=4):
        assert tracker.on_enqueue(rid, trace_id=tid) == tid
        tracker.on_admit(rid)
        tracker.on_first_token(rid)
        tracker.on_tokens(rid, n - 1)
        tracker.on_retire(rid, n_tokens=n, reason="complete")

    def test_retire_hands_the_sink_one_full_payload(self):
        got = []
        tr = slo.RequestTracker(policy=slo.SloPolicy(), source="serve.r1")
        tr.trace_sink = got.append
        self._run_one(tr)
        assert len(got) == 1
        p = got[0]
        assert p["trace_id"] == 77 and p["rid"] == 1
        assert p["source"] == "serve.r1" and p["reason"] == "complete"
        assert p["measured"]["e2e"] > 0 and "ttft" in p["measured"]
        names = [s["name"] for s in p["spans"]]
        assert "req" in names and "req.queue" in names
        assert set(names) <= set(slo.SPAN_TAXONOMY), \
            f"retire emitted spans outside SPAN_TAXONOMY: {names}"

    def test_a_raising_sink_never_reaches_the_scheduler(self):
        tr = slo.RequestTracker(policy=slo.SloPolicy(), source="t")

        def boom(payload):
            raise RuntimeError("sink down")

        tr.trace_sink = boom
        self._run_one(tr)                      # must not raise
        assert tr.summary()["inflight"] == 0

    def test_rejected_requests_never_reach_the_sink(self):
        got = []
        tr = slo.RequestTracker(policy=slo.SloPolicy(), source="t")
        tr.trace_sink = got.append
        tr.on_enqueue(5, trace_id=9)
        tr.on_reject(5)
        tr.on_retire(5)                        # already popped: no-op
        assert got == []


# ------------------------------------------------- replica span buffer

def _payload(tid, rid=1, reason="complete", spans=None):
    return {"rid": rid, "trace_id": tid, "source": "x", "reason": reason,
            "tokens": 2, "preemptions": 0,
            "measured": {"e2e": 0.01, "ttft": 0.005},
            "breaches": [],
            "spans": spans or [{"name": "req", "t0": 0.0, "t1": 0.01,
                                "args": {}}]}


class TestReplicaSpanBuffer:
    def test_publish_collect_pops_exactly_once(self):
        buf = ReplicaSpanBuffer("serve.r1", role="decode", keep=8)
        shipped0 = metrics.counter(reqtrace.COUNTER_SHIPPED).value
        buf.publish(_payload(11))
        assert buf.pending() == 1
        batch = buf.collect(11)
        assert batch is not None
        assert batch["trace_id"] == 11 and batch["source"] == "serve.r1"
        assert batch["role"] == "decode" and batch["spans"]
        assert metrics.counter(reqtrace.COUNTER_SHIPPED).value \
            == shipped0 + 1
        assert buf.collect(11) is None         # popped: exactly once
        assert buf.collect(None) is None

    def test_pull_cursor_base_and_rewind(self):
        buf = ReplicaSpanBuffer("serve.r1", keep=8)
        for tid in (1, 2, 3):
            buf.publish(_payload(tid))
        body = buf.pull(0)
        assert [b["trace_id"] for b in body["batches"]] == [1, 2, 3]
        assert body["cursor"] == 3 and body["base"] == 0
        assert body["source"] == "serve.r1"
        anchor = body["trace_clock"]
        assert anchor["anchor_wall"] > 0 and "anchor_perf" in anchor \
            and "t_send" in anchor
        assert buf.pull(3)["batches"] == []    # caught up
        # a rewound cursor re-serves the retained log (idempotent ingest
        # on the router side dedups)
        assert len(buf.pull(0)["batches"]) == 3

    def test_keep_bounds_both_stores(self):
        buf = ReplicaSpanBuffer("serve.r1", keep=2)
        for tid in range(1, 5):
            buf.publish(_payload(tid))
        assert buf.pending() == 2              # FIFO-evicted to keep
        body = buf.pull(0)
        assert body["base"] == 2               # log floor advanced
        assert [b["trace_id"] for b in body["batches"]] == [3, 4]
        # a cursor below base rewinds to the floor, not a crash
        assert len(buf.pull(0)["batches"]) == 2

    def test_disabled_publish_is_a_noop(self, monkeypatch):
        monkeypatch.setenv(reqtrace.ENV_ON, "0")
        buf = ReplicaSpanBuffer("serve.r1", keep=8)
        buf.publish(_payload(1))
        assert buf.pending() == 0
        assert buf.pull(0)["batches"] == []

    def test_chaos_trace_push_drops_the_ship_not_the_serving(self):
        """Chaos site ``trace.push``: the piggy-back ship fails → collect
        answers None (the /results record goes out untouched — the
        token-identity half is pinned in test_disagg_serving.py), the
        drop is counted, and the batch stays recoverable through the
        cursor-addressed /trace_pull log."""
        buf = ReplicaSpanBuffer("serve.r1", keep=8)
        buf.publish(_payload(5))
        drops0 = metrics.counter(reqtrace.COUNTER_DROPS).value
        shipped0 = metrics.counter(reqtrace.COUNTER_SHIPPED).value
        with chaos.inject("trace.push:1"):
            assert buf.collect(5) is None      # the fault: batch dropped
        assert metrics.counter(reqtrace.COUNTER_DROPS).value == drops0 + 1
        assert metrics.counter(reqtrace.COUNTER_SHIPPED).value == shipped0
        # dropped from the piggy-back path but NOT lost: the pull log
        # still serves it to the router's /trace_pull fallback
        assert [b["trace_id"] for b in buf.pull(0)["batches"]] == [5]


# ------------------------------------------- router trace assembly

SKEW = 1000.0   # the fake replica's perf clock runs 1000s ahead


def _scene():
    """One synthetic disagg request: router spans on the local perf
    clock, two replica batches on a clock SKEW seconds away. Windows:
    router req 0→100ms; prefill replica queue 5→15ms, prefill 15→35ms;
    transfer 40→50ms (router); decode replica queue 50→60ms, decode
    60→95ms. e2e=100ms ttft=50ms queue=5ms."""
    t0 = slo.now()             # the request-timing (span) clock
    r0 = t0 + SKEW

    def sp(name, a, b, base):
        return {"name": name, "t0": base + a, "t1": base + b, "args": {}}

    payload = {
        "rid": 7, "trace_id": 42, "source": "router", "reason": "complete",
        "tokens": 8, "preemptions": 0,
        "measured": {"e2e": 0.100, "ttft": 0.050, "queue": 0.005},
        "breaches": [{"dim": "e2e", "value": 0.1, "target": 0.05}],
        "spans": [sp("req", 0.0, 0.100, t0),
                  sp("req.transfer", 0.040, 0.050, t0)],
    }
    prefill = {"trace_id": 42, "source": "serve.r1", "role": "prefill",
               "rid": 3, "reason": "prefilled", "tokens": 1,
               "preemptions": 0, "measured": {}, "breaches": [],
               "spans": [sp("req.queue", 0.005, 0.015, r0),
                         sp("req.prefill", 0.015, 0.035, r0)]}
    decode = {"trace_id": 42, "source": "serve.r2", "role": "decode",
              "rid": 4, "reason": "complete", "tokens": 8,
              "preemptions": 0, "measured": {}, "breaches": [],
              "spans": [sp("req.queue", 0.050, 0.060, r0),
                        sp("req.decode", 0.060, 0.095, r0)]}
    anchor = {"anchor_wall": time.time(),
              "anchor_perf": slo.now() + SKEW,
              "t_send": time.time()}
    return payload, prefill, decode, anchor


def _ingest_scene(asm, payload, prefill, decode, anchor, repeats=1):
    for batch in (prefill, decode):
        for _ in range(repeats):
            asm.ingest_results_doc({"replica": batch["source"],
                                    "trace_clock": dict(anchor),
                                    "results": [{"rid": batch["rid"],
                                                 "spans": batch}]})
    asm.on_router_retire(payload)


class TestRouterAssembly:
    def test_crit_decomposition_sums_to_e2e(self):
        asm = RouterTraceAssembler("ns1", keep=8, window=32)
        payload, prefill, decode, anchor = _scene()
        _ingest_scene(asm, payload, prefill, decode, anchor)
        doc = asm.get_trace(7)
        assert doc is not None and doc["trace_id"] == 42
        assert doc["retained_for"] == "breach"
        crit = doc["crit"]
        assert set(crit) == set(CRIT_STAGES)
        assert abs(sum(crit.values()) - doc["measured"]["e2e"]) < 1e-4
        # the stage windows land where the scene put them
        assert abs(crit["router_queue"] - 0.005) < 1e-3
        assert abs(crit["prefill_queue"] - 0.010) < 1e-3
        assert abs(crit["prefill_compute"] - 0.020) < 1e-3
        assert abs(crit["transfer"] - 0.010) < 1e-3
        assert abs(crit["decode_queue"] - 0.010) < 1e-3
        assert abs(crit["decode"] - 0.035) < 1e-3
        assert crit["other"] >= 0.0

    def test_clock_alignment_folds_out_the_skew(self):
        """Replica spans arrive 1000s of perf-skew away; the assembled
        doc lands them ON the router's wall timeline, in request order,
        with per-source offsets that differ by exactly the skew."""
        asm = RouterTraceAssembler("ns2", keep=8, window=32)
        payload, prefill, decode, anchor = _scene()
        _ingest_scene(asm, payload, prefill, decode, anchor)
        doc = asm.get_trace(7)
        assert doc["processes"][0] == "router"
        assert set(doc["processes"]) == {"router", "serve.r1", "serve.r2"}

        def find(src, name):
            return next(s for s in doc["spans"]
                        if s["source"] == src and s["name"] == name)

        t_req = find("router", "req")["t0"]
        # scene truth: prefill queue starts 5ms after enqueue, decode
        # starts 60ms after — a surviving 1000s skew would blow this up
        assert abs((find("serve.r1", "req.queue")["t0"] - t_req) - 0.005) \
            < 0.05
        assert abs((find("serve.r2", "req.decode")["t0"] - t_req) - 0.060) \
            < 0.05
        # spans are globally time-ordered after alignment
        t0s = [s["t0"] for s in doc["spans"]]
        assert t0s == sorted(t0s)
        offs = doc["clock"]["offsets"]
        assert abs((offs["router"] - offs["serve.r1"]) - SKEW) < 0.05
        assert doc["clock"]["tolerance_s"] >= 0.001

    def test_redelivered_batches_dedup(self):
        """A /results cursor rewind redelivers every batch: ingest is
        idempotent on (source, rid, reason) — spans never double."""
        asm = RouterTraceAssembler("ns3", keep=8, window=32)
        payload, prefill, decode, anchor = _scene()
        _ingest_scene(asm, payload, prefill, decode, anchor, repeats=3)
        doc = asm.get_trace(7)
        names = [(s["source"], s["name"]) for s in doc["spans"]]
        assert names.count(("serve.r1", "req.prefill")) == 1
        assert names.count(("serve.r2", "req.decode")) == 1
        assert abs(sum(doc["crit"].values()) - doc["measured"]["e2e"]) \
            < 1e-4                              # dedup'd BEFORE attribution

    def test_chrome_export_tracks_and_flow(self):
        asm = RouterTraceAssembler("ns4", keep=8, window=32)
        payload, prefill, decode, anchor = _scene()
        _ingest_scene(asm, payload, prefill, decode, anchor)
        ct = RouterTraceAssembler.chrome_trace(asm.get_trace(7))
        evs = ct["traceEvents"]
        meta = [e for e in evs if e["ph"] == "M" and
                e["name"] == "process_name"]
        assert len(meta) == 3                  # one track per process
        assert {m["args"]["name"] for m in meta} \
            == {"router", "serve.r1", "serve.r2"}
        assert len({e["pid"] for e in evs}) == 3
        xs = [e for e in evs if e["ph"] == "X"]
        assert len(xs) == 6 and all(e["dur"] >= 0 and e["ts"] >= 0
                                    for e in xs)
        flow = [e for e in evs if e["ph"] in ("s", "t", "f")]
        assert [e["ph"] for e in flow] == ["s", "t", "f"]   # 3-hop chain
        assert len({e["id"] for e in flow}) == 1
        assert flow[-1]["bp"] == "e"
        assert ct["otherData"]["trace_id"] == 42

    def test_autoscale_decisions_annotate_overlapping_traces(self):
        asm = RouterTraceAssembler("ns5", keep=8, window=32)
        payload, prefill, decode, anchor = _scene()
        reqtrace.note_autoscale({"action": "scale_out", "pool": "decode",
                                 "signal": "slo"})
        _ingest_scene(asm, payload, prefill, decode, anchor)
        doc = asm.get_trace(7)
        acts = [a for a in doc["autoscale"]
                if a.get("action") == "scale_out"]
        assert acts and acts[0]["signal"] == "slo"
        assert acts[0]["t_wall"] > 0

    def test_bench_payload_shares_of_ttft(self):
        asm = RouterTraceAssembler("ns6", keep=8, window=32)
        payload, prefill, decode, anchor = _scene()
        _ingest_scene(asm, payload, prefill, decode, anchor)
        bp = asm.bench_payload()
        assert bp is not None
        assert bp["requests"] == 1 and bp["assembled"] == 1
        assert set(bp["stages"]) == set(TTFT_STAGES)
        for s in TTFT_STAGES:
            st = bp["stages"][s]
            assert 0.0 <= st["p50"] <= 1.0 and 0.0 <= st["p95"] <= 1.0
        # prefill compute is 20ms of the 50ms TTFT
        assert abs(bp["stages"]["prefill_compute"]["p50"] - 0.4) < 0.05


# ----------------------------------------------------- tail sampling

def _retire(asm, rid, e2e, breach=False, tid=None):
    asm.on_router_retire({
        "rid": rid, "trace_id": rid if tid is None else tid,
        "source": "router", "reason": "complete", "tokens": 2,
        "preemptions": 0, "measured": {"e2e": e2e, "ttft": e2e / 2},
        "breaches": ([{"dim": "e2e", "value": e2e, "target": e2e / 2}]
                     if breach else []),
        "spans": [{"name": "req", "t0": 0.0, "t1": e2e, "args": {}}]})


class TestTailSampler:
    def test_fast_nonbreaching_requests_are_sampled_out(self):
        asm = RouterTraceAssembler("ns7", keep=8, window=64)
        sampled0 = metrics.counter(reqtrace.COUNTER_SAMPLED).value
        _retire(asm, 1, 1.0)                   # the slow one: retained
        assert asm.get_trace(1) is not None
        assert asm.get_trace(1)["retained_for"] == "tail"
        for rid in range(2, 12):
            _retire(asm, rid, 0.001)           # fast, no breach: dropped
            assert asm.get_trace(rid) is None
        assert metrics.counter(reqtrace.COUNTER_SAMPLED).value \
            == sampled0 + 10
        assert asm.assembled == 11             # histograms still fed

    def test_breaches_are_always_retained(self):
        asm = RouterTraceAssembler("ns8", keep=8, window=64)
        _retire(asm, 1, 1.0)                   # raise the p99 threshold
        _retire(asm, 2, 0.001, breach=True)    # fast BUT breaching
        doc = asm.get_trace(2)
        assert doc is not None and doc["retained_for"] == "breach"

    def test_retained_ring_is_bounded_by_keep(self):
        asm = RouterTraceAssembler("ns9", keep=4, window=64)
        for rid in range(1, 8):
            _retire(asm, rid, 0.01, breach=True)
        assert asm.get_trace(1) is None        # oldest evicted
        assert asm.get_trace(7) is not None
        assert asm.summary()["retained"] == 4
