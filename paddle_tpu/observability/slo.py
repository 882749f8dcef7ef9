"""Request-level SLO observability: per-request lifecycle tracing + policy.

PR 3 made serving fast but observable only in AGGREGATE (``serve.*`` gauges
per burst). The ROADMAP's replicated-serving item needs per-request latency
distributions (TTFT / TPOT / e2e p95) before SLO-aware admission and
least-loaded routing can exist, and the paged layout ("Ragged Paged
Attention", PAPERS.md) makes per-request cost visible only if the request
LIFECYCLE is traced, not the burst. This module is that substrate:

  * every request gets a process-unique, monotonic **trace id** at enqueue;
  * the scheduler reports lifecycle edges (``on_enqueue`` → ``on_admit`` →
    ``on_first_token`` → ``on_tokens``* → ``on_preempt``* → ``on_retire``)
    through a ``RequestTracker`` — pure observation, never a raise into the
    serving step;
  * retire feeds the PRE-REGISTERED latency histograms (exact bucket
    counts, metrics.DEFAULT_BUCKETS):
      slo.ttft_s        enqueue → first generated token (queue included)
      slo.tpot_s        mean seconds per output token after the first
      slo.queue_wait_s  enqueue → admission
      slo.e2e_s         enqueue → retire
  * an ``SloPolicy`` (targets from ``PADDLE_SLO_TTFT_S`` /
    ``PADDLE_SLO_TPOT_S`` / ``PADDLE_SLO_E2E_S`` / ``PADDLE_SLO_QUEUE_S``;
    a dimension with no env var has no target) evaluates each retire ONCE:
    a breaching request increments ``slo.breach`` (plus a per-dimension
    ``slo.breach.<dim>``) and records a flight event naming the request
    (rid, trace id, dims, measured vs target) — the signal
    observability.triggers turns into an automatic XPlane capture, and the
    measurement the ROADMAP's SLO-aware admission will consume;
  * with the span export on, retire reconstructs the request's phase spans
    (``req.queue`` / ``req.prefill`` / ``req.decode`` under one
    ``req`` span, cat="request", args carrying rid/trace/tokens/breach) so
    the merged fleet trace shows request lifecycles next to bursts.

``now()`` is the sanctioned request-timing clock for ``inference/`` —
tools/lint_observability.py rule O4 bans ad-hoc ``time.perf_counter()``
request timing there so latency math cannot drift away from the histograms
the SLO policy evaluates. It is MONOTONIC: deadlines, timeouts, hedge
delays, drain grace and autoscaler timing are arithmetic on it, and a step
of the wall clock must move none of them. Only when a retired request's
phases go into the span ring are they shifted onto the span clock (the
wall clock, which the device trace uses), by ``span_clock_offset()``.

Preemption semantics: a preempted request keeps its trace id and its
ENQUEUE anchor (e2e covers the whole life, preemptions included) and keeps
its first-token time from the first attempt — the preempt is recorded as a
count + span, not a measurement reset. Queue wait accumulates only time
actually spent WAITING (enqueue→first admit, plus each
preemption→re-admit gap — never an earlier attempt's execution). At
temperature=0 the regenerated tokens are identical, so this is the honest
client-visible story.

Nothing of paddle_tpu beyond observability is imported; safe from any
layer.
"""
from __future__ import annotations

import itertools
import os
import threading
import time

from . import metrics, recorder, spans

__all__ = ["SloPolicy", "RequestTracker", "now", "span_clock_offset",
           "bench_payload",
           "HIST_TTFT", "HIST_TPOT", "HIST_QUEUE", "HIST_E2E",
           "HIST_ADMIT_WAIT", "STAGES",
           "SPAN_TAXONOMY"]

ENV_TTFT = "PADDLE_SLO_TTFT_S"
ENV_TPOT = "PADDLE_SLO_TPOT_S"
ENV_E2E = "PADDLE_SLO_E2E_S"
ENV_QUEUE = "PADDLE_SLO_QUEUE_S"

HIST_TTFT = "slo.ttft_s"
HIST_TPOT = "slo.tpot_s"
HIST_QUEUE = "slo.queue_wait_s"
HIST_E2E = "slo.e2e_s"
# one observation per ADMISSION, made at admit: the wait that admission
# ended (enqueue -> admit, or preemption -> re-admit). slo.queue_wait_s is a
# request's accumulated wait and is only known when it retires, which under
# an open loop is long after the queue was felt.
HIST_ADMIT_WAIT = "slo.admit_wait_s"

COUNTER_BREACH = "slo.breach"

# The per-request span taxonomy (ISSUE 17): THE single source of truth for
# every ``req.*`` span name the fleet can emit. reqtrace (trace assembly),
# the analyzer (O5 polices that no other module invents req.* spans; A3
# sees these names through the retire-time emit below), and the README
# "Distributed request tracing" section all consume this table, so a
# renamed stage cannot silently desync the three.
SPAN_TAXONOMY = {
    "req": "whole request: enqueue -> retire (the e2e window)",
    "req.queue": "pure queue wait: enqueue -> admission (per attempt)",
    "req.prefill": "admission -> first token on the executing replica",
    "req.decode": "first token -> last token on the executing replica",
    "req.attempt": "a preempted attempt's admit -> preempt window",
    "req.prefill_pool": "router: dispatch -> prefilled result (disagg)",
    "req.transfer": "router: KV frame crossing the wire (disagg)",
    "req.decode_pool": "router: decode dispatch -> terminal result (disagg)",
}

# disaggregated-serving stages (ISSUE 11): stage key -> (histogram, span
# name). The DisaggRouter reports each lifecycle stage's duration through
# RequestTracker.on_stage — durations fill the histogram immediately and
# the span lands on the request's retire timeline next to req.queue /
# req.prefill / req.decode, so a trace shows WHICH pool (or the wire) a
# slow request spent its life in. Every span name here must exist in
# SPAN_TAXONOMY above (pinned by tests/test_reqtrace.py).
STAGES = {
    "prefill_pool": ("slo.prefill_pool_s", "req.prefill_pool"),
    "transfer": ("slo.transfer_s", "req.transfer"),
    "decode_pool": ("slo.decode_pool_s", "req.decode_pool"),
}

# process-wide: trace ids stay unique and monotonic across engine instances
# (a serving process that rebuilds its batcher must not reissue ids)
_trace_ids = itertools.count(1)


def now() -> float:
    """The request-timing clock (``time.perf_counter``): monotonic, so no
    step of the wall clock expires a deadline or stretches a wait.
    RequestTracker reads it through this name, so a test steps it by
    patching ``slo.now``."""
    return time.perf_counter()


def span_clock_offset() -> float:
    """What to add to a ``now()`` stamp to put it on the span clock: one
    read of each clock, taken when a retired request's phases are emitted
    (seconds after they happened; the two clocks drift microseconds in
    that time)."""
    return spans.now() - now()


def _env_target(name: str) -> float | None:
    raw = os.environ.get(name, "")
    if not raw:
        return None
    try:
        v = float(raw)
    except ValueError:
        return None
    return v if v > 0 else None


class SloPolicy:
    """Latency targets, evaluated once per retired request.

    Explicit constructor args override the env; ``None`` falls back to the
    ``PADDLE_SLO_*`` env var; an unset dimension has no target. With no
    targets at all the policy is inert (``active`` False) and evaluation
    is a no-op returning []."""

    def __init__(self, ttft_s: float | None = None, tpot_s: float | None = None,
                 e2e_s: float | None = None, queue_s: float | None = None):
        self.targets = {
            "ttft": _env_target(ENV_TTFT) if ttft_s is None else float(ttft_s),
            "tpot": _env_target(ENV_TPOT) if tpot_s is None else float(tpot_s),
            "e2e": _env_target(ENV_E2E) if e2e_s is None else float(e2e_s),
            "queue": _env_target(ENV_QUEUE) if queue_s is None
            else float(queue_s),
        }
        self.targets = {k: v for k, v in self.targets.items()
                        if v is not None and v > 0}

    @property
    def active(self) -> bool:
        return bool(self.targets)

    def evaluate(self, measured: dict) -> list[dict]:
        """[{dim, value, target}] for every dimension that has BOTH a
        measurement and a target and exceeds it."""
        breaches = []
        for dim, target in self.targets.items():
            v = measured.get(dim)
            if v is not None and v > target:
                breaches.append({"dim": dim, "value": round(float(v), 6),
                                 "target": target})
        return breaches


class _Rec:
    __slots__ = ("trace_id", "t_enqueue", "t_admit", "t_first", "t_last",
                 "t_requeued", "queue_s", "admitted", "preemptions", "spans",
                 "stages")

    def __init__(self, trace_id, t_enqueue):
        self.trace_id = trace_id
        self.t_enqueue = t_enqueue
        self.t_admit = None      # CURRENT attempt's admit time
        self.t_first = None      # first token EVER (first attempt)
        self.t_last = None
        self.t_requeued = None   # when a preemption put it back in queue
        self.queue_s = 0.0       # accumulated PURE queue wait (all waits)
        self.admitted = False
        self.preemptions = 0
        self.spans = []  # (name, t0, t1) preempted attempts
        self.stages = []  # (span name, t0, t1) disagg lifecycle stages


def _build_spans(rec: _Rec, rid: int, t_retire: float, n_tokens: int,
                 reason: str, breaches: list) -> list[dict]:
    """The request's retire-time span list as plain data
    (``{name, t0, t1, args}``, SPAN_TAXONOMY names, ``now()`` seconds):
    one builder feeds BOTH the span ring (shifted onto its clock on the
    way in) and the reqtrace sink so the two views cannot drift apart."""
    args = {"rid": rid, "trace": rec.trace_id, "tokens": n_tokens,
            "preemptions": rec.preemptions, "reason": reason}
    if breaches:
        args["breach"] = "+".join(b["dim"] for b in breaches)
    out = [{"name": "req", "t0": rec.t_enqueue, "t1": t_retire,
            "args": args}]
    admit = rec.t_admit if rec.t_admit is not None else t_retire
    out.append({"name": "req.queue", "t0": rec.t_enqueue, "t1": admit,
                "args": {"rid": rid, "trace": rec.trace_id}})
    if rec.t_first is not None:
        # prefill span only when the first token belongs to the CURRENT
        # attempt (a preempted request's final admit can come after its
        # first-attempt token — no backwards span)
        if rec.t_admit is not None and rec.t_admit <= rec.t_first:
            out.append({"name": "req.prefill", "t0": rec.t_admit,
                        "t1": rec.t_first,
                        "args": {"rid": rid, "trace": rec.trace_id}})
        out.append({"name": "req.decode", "t0": rec.t_first,
                    "t1": rec.t_last or t_retire,
                    "args": {"rid": rid, "trace": rec.trace_id,
                             "tokens": n_tokens}})
    for name, t0, t1 in rec.spans:  # preempted attempts
        out.append({"name": name, "t0": t0, "t1": t1,
                    "args": {"rid": rid, "trace": rec.trace_id,
                             "preempted": True}})
    for name, t0, t1 in rec.stages:  # disagg lifecycle stages
        out.append({"name": name, "t0": t0, "t1": t1,
                    "args": {"rid": rid, "trace": rec.trace_id}})
    return out


class RequestTracker:
    """Per-engine lifecycle observer. Thread-safe (the admin endpoint may
    snapshot while the scheduler steps). Every hook is a few dict ops and
    clock reads; none can raise into the scheduler (defensive except)."""

    def __init__(self, policy: SloPolicy | None = None, source: str = "serve"):
        self.policy = SloPolicy() if policy is None else policy
        self.source = source
        self._recs: dict[int, _Rec] = {}
        self._lk = threading.Lock()
        self.breached: int = 0
        # reqtrace wiring (ISSUE 17): when set, every retire hands the
        # request's full span payload to the sink (a ReplicaSpanBuffer on
        # replicas, the RouterTraceAssembler on the router) — independent
        # of whether chrome span tracing is on. Sink faults never reach
        # the scheduler.
        self.trace_sink = None
        # pre-register so scrapers/exporters see the latency series (and
        # the breach counter) before the first request ever lands
        for h in (HIST_TTFT, HIST_TPOT, HIST_QUEUE, HIST_E2E,
                  HIST_ADMIT_WAIT):
            metrics.histogram(h)
        metrics.counter(COUNTER_BREACH)

    # ---------------------------------------------------------- lifecycle
    def on_enqueue(self, rid: int, trace_id: int | None = None) -> int:
        """Start a request's lifecycle. ``trace_id`` lets an upstream
        router stamp ITS id on the replica-local record, so a request
        retried on another replica after a failover keeps ONE trace id
        across the fleet (process-unique ids are only issued when none is
        given)."""
        t = now()
        tid = next(_trace_ids) if trace_id is None else int(trace_id)
        with self._lk:
            self._recs[rid] = _Rec(tid, t)
        return tid

    def on_reject(self, rid: int):
        """An admission rejection after on_enqueue: the request never
        entered the system — drop its record WITHOUT a retire measurement
        (retire stays exactly-once per accepted request)."""
        with self._lk:
            self._recs.pop(rid, None)

    def on_admit(self, rid: int):
        t = now()
        waited = None
        with self._lk:
            rec = self._recs.get(rid)
            if rec is not None and rec.t_admit is None:
                rec.t_admit = t
                rec.admitted = True
                # queue wait accumulates only TIME SPENT WAITING: from
                # enqueue (first admit) or from the preemption that
                # re-queued it — never the earlier attempt's execution
                start = rec.t_requeued if rec.t_requeued is not None \
                    else rec.t_enqueue
                waited = max(0.0, t - start)
                rec.queue_s += waited
        if waited is not None:
            metrics.histogram(HIST_ADMIT_WAIT).observe(waited)

    def on_first_token(self, rid: int):
        t = now()
        with self._lk:
            rec = self._recs.get(rid)
            if rec is None:
                return
            if rec.t_first is None:
                rec.t_first = t
            rec.t_last = t

    def on_tokens(self, rid: int, n: int):
        if n <= 0:
            return
        t = now()
        with self._lk:
            rec = self._recs.get(rid)
            if rec is not None:
                rec.t_last = t

    def on_stage(self, rid: int, stage: str, t0: float, t1: float):
        """One disaggregated lifecycle stage finished (ISSUE 11): observe
        its duration histogram (``slo.prefill_pool_s`` /
        ``slo.transfer_s`` / ``slo.decode_pool_s``) NOW — stage latency
        distributions must exist even for requests that later fail over —
        and remember the span for the retire-time trace emit. Unknown
        stages raise (a typo'd stage would silently build an empty
        histogram)."""
        hist, span_name = STAGES[stage]
        metrics.histogram(hist).observe(max(0.0, t1 - t0))
        with self._lk:
            rec = self._recs.get(rid)
            if rec is not None:
                rec.stages.append((span_name, t0, t1))

    def on_preempt(self, rid: int):
        t = now()
        with self._lk:
            rec = self._recs.get(rid)
            if rec is None:
                return
            rec.preemptions += 1
            if rec.t_admit is not None:
                rec.spans.append(("req.attempt", rec.t_admit, t))
            # back to the queue: admission restarts, the queue-wait clock
            # resumes from NOW, and ttft/e2e keep their first-attempt
            # anchors (honest client-visible story)
            rec.t_admit = None
            rec.t_requeued = t

    def trace_id(self, rid: int) -> int | None:
        with self._lk:
            rec = self._recs.get(rid)
            return None if rec is None else rec.trace_id

    # ------------------------------------------------------------- retire
    def on_retire(self, rid: int, n_tokens: int = 0, reason: str = "complete"):
        t = now()
        with self._lk:
            rec = self._recs.pop(rid, None)
        if rec is None:
            return
        measured = {"e2e": t - rec.t_enqueue}
        if rec.admitted:
            measured["queue"] = rec.queue_s
        if rec.t_first is not None:
            measured["ttft"] = rec.t_first - rec.t_enqueue
            if n_tokens >= 2 and rec.t_last is not None \
                    and rec.t_last > rec.t_first:
                measured["tpot"] = (rec.t_last - rec.t_first) / (n_tokens - 1)
        for dim, hist in (("ttft", HIST_TTFT), ("tpot", HIST_TPOT),
                          ("queue", HIST_QUEUE), ("e2e", HIST_E2E)):
            if dim in measured:
                metrics.histogram(hist).observe(measured[dim])

        breaches = self.policy.evaluate(measured)
        if breaches:
            with self._lk:  # summary() reads from the admin thread
                self.breached += 1
            metrics.counter(COUNTER_BREACH).inc()
            for b in breaches:
                metrics.counter(f"{COUNTER_BREACH}.{b['dim']}").inc()
            recorder.record(
                "slo.breach",
                message=f"[slo] request {rid} (trace {rec.trace_id}) "
                        f"breached {'+'.join(b['dim'] for b in breaches)}: "
                        + ", ".join(f"{b['dim']} {b['value'] * 1e3:.1f}ms > "
                                    f"{b['target'] * 1e3:.1f}ms"
                                    for b in breaches),
                rid=rid, trace_id=rec.trace_id, source=self.source,
                node=os.environ.get("PADDLE_NODE_ID"),
                rank=int(os.environ.get("PADDLE_TRAINER_ID", "0") or 0),
                tokens=n_tokens, reason=reason, breaches=breaches,
                measured={k: round(v, 6) for k, v in measured.items()})

        built = None
        if spans.tracing_enabled() or self.trace_sink is not None:
            try:
                built = _build_spans(rec, rid, t, n_tokens, reason, breaches)
            except Exception:
                built = None  # tracing must never fail a retire
        if built is not None and spans.tracing_enabled():
            try:
                self._emit_spans(built)
            except Exception:
                pass
        if built is not None and self.trace_sink is not None:
            try:
                self.trace_sink({
                    "rid": rid, "trace_id": rec.trace_id,
                    "source": self.source, "reason": reason,
                    "tokens": n_tokens, "preemptions": rec.preemptions,
                    "t_enqueue": rec.t_enqueue, "t_retire": t,
                    "measured": measured, "breaches": breaches,
                    "spans": built})
            except Exception:
                pass

    @staticmethod
    def _emit_spans(built: list):
        off = span_clock_offset()
        for d in built:
            spans.add_span(d["name"], "request", d["t0"] + off,
                           d["t1"] + off, **d["args"])

    # ------------------------------------------------------------ summary
    def summary(self) -> dict:
        """Live tracker state for the serving admin /snapshot."""
        with self._lk:
            inflight = len(self._recs)
        snap = metrics.snapshot()["histograms"]

        def pick(name):
            h = snap.get(name) or {}
            return {"p50": h.get("p50"), "p95": h.get("p95"),
                    "count": h.get("count", 0)}

        return {"inflight": inflight, "breached": self.breached,
                "targets": dict(self.policy.targets),
                "ttft": pick(HIST_TTFT), "tpot": pick(HIST_TPOT),
                "e2e": pick(HIST_E2E),
                # the queue as it is felt NOW: observed at each admission,
                # not when the request retires
                "admit_wait": pick(HIST_ADMIT_WAIT)}


def bench_payload() -> dict | None:
    """The ``slo`` sub-object for bench JSON lines (schema pinned by the
    bench contract tests): ttft/tpot/e2e/queue p50+p95+count plus the
    breach counter. Returns None when serving was never exercised in this
    process (no e2e observations) — the sub-object is ABSENT, not empty,
    on pure-training runs."""
    snap = metrics.snapshot()
    e2e = snap["histograms"].get(HIST_E2E)
    if not e2e or not e2e.get("count"):
        return None

    def pick(name):
        h = snap["histograms"].get(name) or {}
        return {"p50": h.get("p50"), "p95": h.get("p95"),
                "count": h.get("count", 0)}

    return {"ttft": pick(HIST_TTFT), "tpot": pick(HIST_TPOT),
            "e2e": pick(HIST_E2E), "queue_wait": pick(HIST_QUEUE),
            "breaches": int(snap["counters"].get(COUNTER_BREACH, 0))}
