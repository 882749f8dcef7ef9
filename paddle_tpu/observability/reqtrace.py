"""Fleet-wide per-request distributed tracing (ISSUE 17).

PR 11-16 turned serving into a disaggregated, speculative, autoscaled
fleet, but request observability stopped at the process boundary: trace
ids ride the ``/enqueue`` body and ``slo.py`` emits per-process retire
spans, yet nothing could answer "where did THIS slow request spend its
time?" across router queue → prefill replica → KV transfer → decode
replica. This module is the missing assembly layer:

  * **Replica side** — ``ReplicaSpanBuffer`` is the
    ``RequestTracker.trace_sink``: every retire hands it the request's
    span payload (SPAN_TAXONOMY names, replica-local perf-clock seconds)
    tagged with the fleet trace id. The batch piggy-backs on the NEXT
    ``/results`` record for that request (no new hop), with a
    cursor-addressed ``GET /trace_pull`` fallback for batches whose
    piggy-back was lost. Chaos site ``trace.push`` guards the ship: a
    fault drops the batch (counted in ``reqtrace.drops``) and serving
    never feels it — token-identical by construction, pinned by tests.

  * **Clock alignment** — every ``/results`` / ``/trace_pull`` response
    carries a fresh ``(anchor_wall, anchor_perf, t_send)`` clock anchor;
    the router keeps an NTP-style minimum-filter skew estimate per
    replica (min over observed send→receive deltas ≈ clock offset +
    network floor — the same estimator as
    ``fleet.TelemetryAggregator._rank_offset_s``) and maps every remote
    perf-clock span onto its own wall timeline.

  * **Router side** — ``RouterTraceAssembler`` is the router tracker's
    ``trace_sink``: at retire it folds the replica batches under the
    trace id into ONE multi-process trace, computes the critical-path
    decomposition of e2e (``slo.crit.*`` histograms:
    router_queue / prefill_queue / prefill_compute / transfer /
    decode_queue / decode / spec_verify / other — normalized so the
    stages SUM to e2e), and serves ``GET /trace?rid=`` as JSON or a
    merged chrome trace (one track per process, flow arrows across
    hops).

  * **Tail sampler** — always-on cost stays bounded: full span payloads
    are retained only for SLO-breaching requests plus a sliding
    slowest-p99 reservoir (``PADDLE_REQTRACE_WINDOW`` recent e2e
    samples); everything else feeds the histograms then drops
    (``reqtrace.sampled_out``). The retained ring holds at most
    ``PADDLE_REQTRACE_KEEP`` traces.

``PADDLE_REQTRACE=0`` turns the whole layer off (spans are then never
built nor shipped); greedy decoding is token-identical either way — the
layer only ever observes.

No jax imports; safe from any layer.
"""
from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict, deque

from . import metrics, slo
from .slo import SPAN_TAXONOMY, STAGES

__all__ = ["enabled", "clock_anchor", "crit_hist", "note_autoscale",
           "ReplicaSpanBuffer", "RouterTraceAssembler", "CRIT_STAGES",
           "TTFT_STAGES", "SPAN_TAXONOMY"]

ENV_ON = "PADDLE_REQTRACE"
ENV_KEEP = "PADDLE_REQTRACE_KEEP"
ENV_WINDOW = "PADDLE_REQTRACE_WINDOW"

COUNTER_DROPS = "reqtrace.drops"
COUNTER_SHIPPED = "reqtrace.shipped"
COUNTER_RETAINED = "reqtrace.retained"
COUNTER_SAMPLED = "reqtrace.sampled_out"

# The critical-path stages of one request's e2e, in timeline order. Each
# retire observes slo.crit.<stage>_s; the decomposition is normalized so
# the stages sum to e2e exactly ('other' absorbs router tick / collection
# latency no stage window sees). 'spec_verify' is filled from a decode
# batch's measured verify share when the replica reports one (reserved:
# today's speculative verify is burst-scoped, not request-scoped).
CRIT_STAGES = ("router_queue", "prefill_queue", "prefill_compute",
               "transfer", "decode_queue", "decode", "spec_verify", "other")

# the stages that precede the first token: their SHARE of TTFT is the
# bench `crit` payload (TTFT attribution)
TTFT_STAGES = ("router_queue", "prefill_queue", "prefill_compute", "other")

# span names consumed from the slo.SPAN_TAXONOMY single source
_SPAN_QUEUE = "req.queue"
_SPAN_PREFILL = "req.prefill"
_SPAN_DECODE = "req.decode"
_SPAN_TRANSFER = STAGES["transfer"][1]


def crit_hist(stage: str) -> str:
    return f"slo.crit.{stage}_s"


def enabled() -> bool:
    """PADDLE_REQTRACE master switch — ON by default (the tail sampler
    bounds the always-on cost)."""
    return os.environ.get(ENV_ON, "1").strip().lower() not in (
        "0", "false", "no", "off")


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def clock_anchor() -> dict:
    """One (wall, perf) clock anchor plus a send stamp. Stamped fresh
    into every /results and /trace_pull RESPONSE (not at publish time —
    a batch can sit in the buffer for many poll intervals, and the
    minimum filter needs t_send ≈ the moment the bytes leave)."""
    return {"anchor_wall": time.time(), "anchor_perf": slo.now(),
            "t_send": time.time()}


def _p99(xs) -> float:
    s = sorted(xs)
    if not s:
        return 0.0
    return s[min(len(s) - 1, int(0.99 * (len(s) - 1) + 0.999999))]


# ---------------------------------------------- autoscale annotations
# Autoscale decisions annotate the traces of requests they displaced or
# warmed: the controller notes each ledger entry here; any trace whose
# lifetime overlaps a decision carries it under doc["autoscale"].
_auto_lk = threading.Lock()
_auto_events: deque = deque(maxlen=64)


def note_autoscale(event: dict):
    e = dict(event)
    e["t_wall"] = time.time()
    with _auto_lk:
        _auto_events.append(e)


def _autoscale_overlap(t0_wall: float, t1_wall: float) -> list[dict]:
    with _auto_lk:
        evs = list(_auto_events)
    return [e for e in evs
            if t0_wall - 1.0 <= e.get("t_wall", 0.0) <= t1_wall + 1.0]


def _ship_gate() -> bool:
    """Chaos site ``trace.push``: deterministic fault injection on the
    span-batch ship. True = ship; False = drop. Never raises upward —
    a lost trace batch must not perturb serving."""
    try:
        # lazy: chaos lives above observability in the import DAG
        from ..distributed.resilience import chaos
    except ImportError:
        return True
    try:
        chaos.hit("trace.push")
    except chaos.ChaosError:
        return False
    return True


class ReplicaSpanBuffer:
    """Replica-side holding pen for retired-request span batches.

    ``publish`` is wired as the engine tracker's ``trace_sink``;
    ``collect`` pops one trace's batch so the replica can piggy-back it
    on that request's /results record; ``pull`` serves the
    cursor-addressed ``GET /trace_pull?cursor=`` fallback (same
    cursor/base/rewind semantics as /results). Both stores are bounded
    by PADDLE_REQTRACE_KEEP. Thread-safe (serve loop publishes, admin
    threads collect/pull)."""

    def __init__(self, source: str, role: str = "unified",
                 keep: int | None = None):
        self.source = source
        self.role = role
        self._lk = threading.Lock()
        self._pending: dict[int, dict] = {}   # trace_id -> batch
        self._order: deque = deque()          # FIFO eviction of _pending
        self._log: list[dict] = []            # cursor-addressed for pull()
        self._base = 0
        self._keep = _env_int(ENV_KEEP, 256) if keep is None else int(keep)
        metrics.counter(COUNTER_DROPS)
        metrics.counter(COUNTER_SHIPPED)

    def publish(self, payload: dict):
        """RequestTracker.trace_sink: stash one retired request's spans
        keyed by its fleet trace id. No-op when tracing is off."""
        if not enabled() or not isinstance(payload, dict):
            return
        tid = payload.get("trace_id")
        if tid is None:
            return
        batch = {"trace_id": tid, "source": self.source, "role": self.role,
                 "rid": payload.get("rid"), "reason": payload.get("reason"),
                 "tokens": payload.get("tokens"),
                 "preemptions": payload.get("preemptions"),
                 "measured": payload.get("measured") or {},
                 "breaches": payload.get("breaches") or [],
                 "spans": payload.get("spans") or []}
        with self._lk:
            if tid not in self._pending:
                self._order.append(tid)
            self._pending[tid] = batch
            while len(self._order) > self._keep:
                self._pending.pop(self._order.popleft(), None)
            self._log.append(batch)
            drop = len(self._log) - self._keep
            if drop > 0:
                del self._log[:drop]
                self._base += drop

    def collect(self, trace_id) -> dict | None:
        """Pop the pending batch for ``trace_id`` to ship with its
        result record. The ``trace.push`` chaos site gates the ship: a
        fault drops the batch (``reqtrace.drops``) and returns None —
        the result record goes out untouched."""
        if trace_id is None:
            return None
        with self._lk:
            batch = self._pending.pop(trace_id, None)
        if batch is None:
            return None
        if not _ship_gate():
            metrics.counter(COUNTER_DROPS).inc()
            return None
        metrics.counter(COUNTER_SHIPPED).inc()
        return batch

    def pull(self, cursor: int) -> dict:
        """The /trace_pull body: every retained batch from ``cursor``
        on, plus the next cursor, the base (eviction floor — a cursor
        below it rewinds), and a fresh clock anchor."""
        with self._lk:
            base = self._base
            idx = min(max(0, int(cursor) - base), len(self._log))
            batches = [dict(b) for b in self._log[idx:]]
            nxt = base + len(self._log)
        return {"batches": batches, "cursor": nxt, "base": base,
                "source": self.source, "trace_clock": clock_anchor()}

    def pending(self) -> int:
        with self._lk:
            return len(self._pending)


class RouterTraceAssembler:
    """Router-side end of the distributed trace: clock alignment, batch
    assembly, critical-path attribution, tail sampling, and the
    ``GET /trace`` views. Thread-safe (the admin /trace handler reads
    while the router tick assembles)."""

    def __init__(self, rid_ns: str, keep: int | None = None,
                 window: int | None = None):
        self.rid_ns = rid_ns
        self._lk = threading.Lock()
        self._keep = _env_int(ENV_KEEP, 256) if keep is None else int(keep)
        self._window = _env_int(ENV_WINDOW, 1024) if window is None \
            else int(window)
        # source -> {min_skew, spread, anchor_wall, anchor_perf, samples}
        self._clocks: dict[str, dict] = {}
        self._batches: dict[int, list] = {}   # trace_id -> [batch]
        self._order: deque = deque()          # trace eviction order
        self._retained: OrderedDict[int, dict] = OrderedDict()  # rid -> doc
        self._e2e: deque = deque(maxlen=self._window)
        self._shares: deque = deque(maxlen=self._window)
        self.assembled = 0
        for s in CRIT_STAGES:
            metrics.histogram(crit_hist(s))
        metrics.counter(COUNTER_RETAINED)
        metrics.counter(COUNTER_SAMPLED)
        with self._lk:
            self._clocks["router"] = {
                "min_skew": 0.0, "spread": 0.0,
                "anchor_wall": time.time(),
                "anchor_perf": slo.now(), "samples": 1}

    # ------------------------------------------------- clock alignment
    def note_anchor(self, source: str, anchor: dict):
        """One replica clock observation (a response's ``trace_clock``):
        NTP-style minimum filter over send→receive deltas, same
        estimator as fleet.TelemetryAggregator._rank_offset_s."""
        if not isinstance(anchor, dict):
            return
        try:
            aw = float(anchor["anchor_wall"])
            ap = float(anchor["anchor_perf"])
            ts = float(anchor.get("t_send") or aw)
        except (KeyError, TypeError, ValueError):
            return
        recv = time.time()
        skew = recv - ts
        with self._lk:
            rec = self._clocks.setdefault(
                str(source), {"min_skew": skew, "spread": 0.0, "samples": 0})
            rec["min_skew"] = min(rec["min_skew"], skew)
            rec["spread"] = max(rec["spread"], skew - rec["min_skew"])
            rec["anchor_wall"], rec["anchor_perf"] = aw, ap
            rec["samples"] += 1

    @staticmethod
    def _offset_of(rec: dict | None) -> float | None:
        """Remote perf-clock → router-wall mapping: the (wall, perf)
        anchor plus the minimum-filter skew estimate."""
        if not rec or rec.get("anchor_wall") is None:
            return None
        return (float(rec["anchor_wall"]) - float(rec["anchor_perf"])) \
            + float(rec.get("min_skew", 0.0))

    # --------------------------------------------------------- ingest
    def ingest_results_doc(self, doc: dict, source: str | None = None):
        """Absorb the trace piggy-back of one /results (or /trace_pull)
        response: the fresh clock anchor plus every attached batch.
        Safe on docs with no trace content."""
        if not isinstance(doc, dict):
            return
        src = source or doc.get("replica") or doc.get("source")
        anchor = doc.get("trace_clock")
        if anchor and src:
            self.note_anchor(src, anchor)
        for rec in doc.get("results") or ():
            if isinstance(rec, dict) and rec.get("spans"):
                self.ingest_batch(rec["spans"])
        for b in doc.get("batches") or ():      # /trace_pull body
            self.ingest_batch(b)

    def ingest_batch(self, batch: dict):
        """One replica's retired-request span batch. Idempotent on
        redelivery (a /results cursor rewind or a trace_pull overlap):
        (source, rid, reason) dedups."""
        if not isinstance(batch, dict):
            return
        tid = batch.get("trace_id")
        if tid is None:
            return
        key = (batch.get("source"), batch.get("rid"), batch.get("reason"))
        with self._lk:
            per = self._batches.get(tid)
            if per is None:
                per = self._batches[tid] = []
                self._order.append(tid)
                while len(self._order) > max(64, 4 * self._keep):
                    self._batches.pop(self._order.popleft(), None)
            per[:] = [b for b in per
                      if (b.get("source"), b.get("rid"), b.get("reason"))
                      != key] + [batch]

    # ------------------------------------------------------- assembly
    def on_router_retire(self, payload: dict):
        """The router tracker's trace_sink: assemble the fleet-wide
        trace, feed the slo.crit.* histograms, retain the full payload
        only when the tail sampler says so (breach, or sliding
        slowest-p99)."""
        if not isinstance(payload, dict):
            return
        tid = payload.get("trace_id")
        rid = payload.get("rid")
        measured = payload.get("measured") or {}
        e2e = max(0.0, float(measured.get("e2e") or 0.0))
        with self._lk:
            batches = list(self._batches.pop(tid, ()))
        crit = self._critical_path(payload, batches)
        for s in CRIT_STAGES:
            metrics.histogram(crit_hist(s)).observe(max(0.0, crit[s]))
        share = None
        ttft = measured.get("ttft")
        if ttft and float(ttft) > 0:
            ttft = float(ttft)
            share = {s: min(1.0, max(0.0, crit[s] / ttft))
                     for s in TTFT_STAGES if s != "other"}
            share["other"] = max(0.0, 1.0 - sum(share.values()))
        with self._lk:
            self.assembled += 1
            self._e2e.append(e2e)
            if share is not None:
                self._shares.append(share)
            thresh = _p99(self._e2e)
        reason = payload.get("reason")
        if reason in ("deadline_exceeded", "cancelled"):
            # reliability retires (ISSUE 19) are force-retained: a
            # deadline miss or cancel is exactly the trace an operator
            # pulls to see WHERE the budget went (or where the cancel
            # caught the request) — tail-sampling it out would hide every
            # incident the feature exists to explain
            doc = self._assemble(payload, batches, crit)
            doc["retained_for"] = "reliability"
            with self._lk:
                self._retained[rid] = doc
                while len(self._retained) > self._keep:
                    self._retained.popitem(last=False)
            metrics.counter(COUNTER_RETAINED).inc()
            return
        if not payload.get("breaches") and e2e < thresh:
            metrics.counter(COUNTER_SAMPLED).inc()
            return
        doc = self._assemble(payload, batches, crit)
        with self._lk:
            self._retained[rid] = doc
            while len(self._retained) > self._keep:
                self._retained.popitem(last=False)
        metrics.counter(COUNTER_RETAINED).inc()

    def _critical_path(self, payload: dict, batches: list) -> dict:
        """Decompose e2e into CRIT_STAGES seconds. Router-side windows
        give router_queue and the transfer wire; replica batches split
        each pool window into queue vs compute. Stage windows measured
        on different clocks can overlap at the edges, so the result is
        normalized to SUM to e2e, preserving shares; the remainder is
        'other' (router tick / collection latency no stage sees)."""
        measured = payload.get("measured") or {}
        e2e = max(0.0, float(measured.get("e2e") or 0.0))
        crit = {s: 0.0 for s in CRIT_STAGES}
        crit["router_queue"] = max(0.0, float(measured.get("queue") or 0.0))

        def span_sum(spans, name):
            return sum(max(0.0, float(s.get("t1", 0.0))
                           - float(s.get("t0", 0.0)))
                       for s in spans or () if s.get("name") == name)

        crit["transfer"] = span_sum(payload.get("spans"), _SPAN_TRANSFER)
        for b in batches:
            q = span_sum(b.get("spans"), _SPAN_QUEUE)
            if b.get("role") == "decode" and b.get("reason") != "prefilled":
                crit["decode_queue"] += q
            else:
                crit["prefill_queue"] += q
            crit["prefill_compute"] += span_sum(b.get("spans"), _SPAN_PREFILL)
            crit["decode"] += span_sum(b.get("spans"), _SPAN_DECODE)
            v = (b.get("measured") or {}).get("verify_s")
            if v:
                crit["spec_verify"] += max(0.0, float(v))
        accounted = sum(crit[s] for s in CRIT_STAGES if s != "other")
        if e2e > 0.0 and accounted > e2e:
            scale = e2e / accounted
            for s in CRIT_STAGES:
                crit[s] *= scale
            accounted = e2e
        crit["other"] = max(0.0, e2e - accounted)
        return crit

    def _tolerance(self, sources) -> float:
        """The measured clock-alignment tolerance for a set of sources:
        the worst minimum-filter residual (observed skew spread above
        the minimum, plus the network floor the minimum itself absorbs),
        floored at 1ms. Aligned cross-process timestamps are honest to
        within this bound."""
        with self._lk:
            vals = [0.001]
            for s in sources:
                rec = self._clocks.get(s)
                if rec:
                    vals.append(float(rec.get("spread", 0.0)))
                    vals.append(abs(float(rec.get("min_skew", 0.0))))
        return max(vals)

    def _assemble(self, payload: dict, batches: list, crit: dict) -> dict:
        with self._lk:
            clocks = {s: dict(r) for s, r in self._clocks.items()}
        router_off = self._offset_of(clocks.get("router")) or 0.0

        def off(src):
            o = self._offset_of(clocks.get(src))
            return router_off if o is None else o

        spans_out = []

        def emit(src, sp):
            o = off(src)
            spans_out.append({"name": sp.get("name"), "source": src,
                              "t0": float(sp.get("t0", 0.0)) + o,
                              "t1": float(sp.get("t1", 0.0)) + o,
                              "args": sp.get("args") or {}})

        rsrc = payload.get("source") or "router"
        for sp in payload.get("spans") or ():
            emit(rsrc, sp)
        procs = [rsrc]
        for b in batches:
            src = b.get("source") or "replica"
            if src not in procs:
                procs.append(src)
            for sp in b.get("spans") or ():
                emit(src, sp)
        spans_out.sort(key=lambda s: s["t0"])
        t_lo = min((s["t0"] for s in spans_out), default=0.0)
        t_hi = max((s["t1"] for s in spans_out), default=t_lo)
        return {
            "rid": payload.get("rid"), "trace_id": payload.get("trace_id"),
            "router": self.rid_ns, "reason": payload.get("reason"),
            "tokens": payload.get("tokens"),
            "preemptions": payload.get("preemptions"),
            "breaches": payload.get("breaches") or [],
            "measured": {k: round(float(v), 6)
                         for k, v in (payload.get("measured") or {}).items()},
            "crit": {s: round(crit[s], 6) for s in CRIT_STAGES},
            "processes": procs,
            "spans": spans_out,
            "clock": {"tolerance_s": round(self._tolerance(procs), 6),
                      "offsets": {s: round(off(s), 6) for s in procs}},
            "autoscale": _autoscale_overlap(t_lo, t_hi),
            "retained_for": "breach" if payload.get("breaches") else "tail",
        }

    # ---------------------------------------------------------- views
    def get_trace(self, rid: int) -> dict | None:
        """The retained assembled trace for a router rid (None when the
        tail sampler dropped it or it was evicted)."""
        with self._lk:
            doc = self._retained.get(rid)
            return None if doc is None else dict(doc)

    @staticmethod
    def chrome_trace(doc: dict) -> dict:
        """The merged chrome-trace view of ONE assembled trace: a track
        (pid) per process, ts normalized to the trace start, flow
        arrows chaining the request across hops (loads in Perfetto /
        chrome://tracing)."""
        procs = list(doc.get("processes") or ())
        pids = {src: i + 1 for i, src in enumerate(procs)}
        out = []
        for src, pid in pids.items():
            out.append({"name": "process_name", "ph": "M", "pid": pid,
                        "tid": 0, "args": {"name": src}})
            out.append({"name": "process_sort_index", "ph": "M", "pid": pid,
                        "tid": 0, "args": {"sort_index": pid}})
        spans_in = doc.get("spans") or ()
        t0 = min((s["t0"] for s in spans_in), default=0.0)
        hops: dict[str, tuple] = {}  # src -> earliest (ts, pid)
        for sp in spans_in:
            pid = pids.get(sp.get("source"), 1)
            ts = (sp["t0"] - t0) * 1e6
            out.append({"name": sp.get("name"), "cat": "request", "ph": "X",
                        "ts": ts,
                        "dur": max(0.0, (sp["t1"] - sp["t0"]) * 1e6),
                        "pid": pid, "tid": 0, "args": sp.get("args") or {}})
            src = sp.get("source")
            if src not in hops or ts < hops[src][0]:
                hops[src] = (ts, pid)
        chain = sorted(hops.values())
        if len(chain) >= 2:
            fid = abs(int(doc.get("trace_id") or 0) * 2654435761 + 1) \
                % (1 << 31)
            for j, (ts, pid) in enumerate(chain):
                ph = "s" if j == 0 else ("f" if j == len(chain) - 1 else "t")
                fev = {"name": "req.hop", "cat": "request.flow", "ph": ph,
                       "id": fid, "ts": ts, "pid": pid, "tid": 0}
                if ph == "f":
                    fev["bp"] = "e"
                out.append(fev)
        return {"traceEvents": out, "displayTimeUnit": "ms",
                "otherData": {"trace_id": doc.get("trace_id"),
                              "rid": doc.get("rid"),
                              "router": doc.get("router"),
                              "clock": doc.get("clock")}}

    def bench_payload(self) -> dict | None:
        """The ``crit`` sub-object for bench JSON lines: per-stage
        p50/p95 SHARES of TTFT across assembled requests. None before
        any assembly with a measured TTFT."""
        with self._lk:
            shares = list(self._shares)
            n_assembled = self.assembled
            n_retained = len(self._retained)
        if not shares:
            return None
        n = len(shares)
        out = {"requests": n, "assembled": n_assembled,
               "retained": n_retained, "stages": {}}
        for s in TTFT_STAGES:
            xs = sorted(sh.get(s, 0.0) for sh in shares)
            out["stages"][s] = {"p50": round(xs[int(0.50 * (n - 1))], 4),
                                "p95": round(xs[int(0.95 * (n - 1))], 4)}
        return out

    def summary(self) -> dict:
        with self._lk:
            return {"assembled": self.assembled,
                    "retained": len(self._retained),
                    "pending_traces": len(self._batches),
                    "clocks": {s: {"min_skew": round(float(r.get(
                        "min_skew", 0.0)), 6),
                        "samples": r.get("samples", 0)}
                        for s, r in self._clocks.items()}}
