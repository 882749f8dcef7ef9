"""Serving-fleet router: lease-based health, least-loaded routing, failover.

ISSUE 9 tentpole. One process is a throughput ceiling AND a single point
of failure; this router puts N ``ReplicaServer`` processes (each a
``ContinuousBatcher``, each optionally GSPMD-sharded) behind one submit()
surface with three robustness guarantees:

  * **health is a lease** — replicas heartbeat ``serve.<name>`` into the
    SAME elastic registry (FileRegistry / KVServer) the training fleet
    uses; the router's routing table is the TTL'd alive set, so a
    SIGKILL'd replica leaves the table within one TTL with no extra
    failure detector. Before declaring a missing lease dead the router
    makes one final ``/results`` poll: a DRAINED replica (deliberate
    deregister) is collected and removed clean — only an UNREACHABLE one
    is failed over.
  * **admission is a decision, not a queue** — submit() consults each
    candidate's readiness probe (``/health``: queue depth, draining) and
    the fleet AdmissionPolicy; when nobody can take the request it
    rejects with a computed ``retry_after_s`` (``AdmissionReject``)
    instead of queueing unboundedly. The router's own ``_pending`` holds
    ONLY already-accepted work (failover re-enqueues and replica sheds) —
    bounded by what was admitted, never by offered load.
  * **failover keeps the trace** — a request in flight on a dead replica
    is re-enqueued on a healthy one carrying the SAME trace id
    (``slo.on_enqueue(trace_id=...)`` on the far side) and ``force=True``
    (accepted work must land); at temperature=0 the retried output is
    token-identical, so a mid-decode SIGKILL is invisible in the token
    stream. Retire stays exactly-once per request: the first result wins,
    late duplicates from a falsely-suspected replica are dropped and
    counted.

Chaos sites (the fleet extension of the chaos==fault-free discipline):
``serve.route`` fails one routing send (the request stays pending and
routes next tick), ``serve.replica_dead`` fails one failover re-enqueue
(deferred to the next tick, never lost), ``serve.reject`` degrades a
rejection's computed retry-after to the floor (the rejection stands) —
a chaos-on drill serves byte-identical tokens to a fault-free one.

Request-lifecycle reliability (ISSUE 19) rides the same surface:

  * **deadlines propagate as remaining budget** — ``submit(...,
    deadline_s=)`` (default ``PADDLE_REQUEST_DEADLINE_S``; unset = no
    deadline) stamps an absolute expiry on the router clock; every hop
    re-derives ``deadline_left_s`` at send time so queueing anywhere
    shrinks the budget. A provably-unmeetable budget (expired, or below
    the observed TTFT floor) sheds typed ``deadline_unmeetable`` at
    admission; an expired parked request retires typed
    ``deadline_exceeded`` without ever (re)starting a prefill.
  * **cancellation is cooperative and exactly-once** — ``cancel(rid)``
    (router thread) or ``POST /cancel`` (admin thread: mark under a
    dedicated lock, the next tick applies — decide-under-lock /
    actuate-outside, the same split the autoscaler uses) drops parked
    work locally and forwards in-flight work to the replica(s) holding
    it; a cancel racing a retire is a no-op and the produced result
    stands.
  * **hedged re-dispatch is budgeted** — an in-flight request stalled
    past the adaptive hedge delay (fleet e2e p95, floored at
    ``PADDLE_HEDGE_DELAY_S``; 0 = off) is re-posted SAME rid to the next
    candidate. The replica-side (router, rid) dedup and the first-result-
    wins retire make the copy token-identical at temp=0; the loser is
    cancelled on settle. The ``PADDLE_RETRY_BUDGET_PCT`` token bucket
    (earn pct/100 per normal dispatch, spend 1 per hedge) caps total
    hedge volume so a sick fleet degrades to shedding, never a retry
    storm.

Threading contract: the Router is SINGLE-THREADED by design — submit /
tick / wait / drain are called from one client thread (the replicas are
the concurrency). The admin server's POST /cancel handler is the one
cross-thread entry and touches ONLY the marks list under its own lock.
Metrics: ``serve.fleet.*`` counters/gauges; the
router's own RequestTracker (source="router") fills the slo.* histograms
with FLEET-level queue/e2e measurements and keeps trace ids.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
import uuid
from collections import deque
from dataclasses import dataclass, field

from ..distributed.fleet.elastic import FileRegistry
from ..distributed.resilience import chaos
from ..distributed.resilience.retry import classify
from ..observability import metrics, recorder as _recorder, \
    reqtrace as _reqtrace, slo as _slo
from ..observability.admin import job_token
from .admission import AdmissionPolicy, AdmissionReject, reject as _reject, \
    retry_after_floor, slo_hists
from .replica import REPLICA_PREFIX

__all__ = ["Router", "RoutedRequest", "ServingFleet", "AdmissionReject"]

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@dataclass
class RoutedRequest:
    rid: int
    prompt: list
    max_new_tokens: int
    trace_id: int
    replica: str | None = None   # where it is in flight (None = pending)
    attempts: int = 0
    retried: bool = False        # went through failover/shed at least once
    retry_hint: float = 0.0      # max computed retry_after_s seen in 429
    #                              bodies this pass — a saturated fleet's
    #                              rejection propagates the replicas' own
    #                              estimate instead of the floor
    last_faulted: str | None = None  # replica whose send faulted mid-wire
    #                                  (AMBIGUOUS: may have landed) — the
    #                                  re-dispatch must try it FIRST so
    #                                  its (router, rid) dedup can absorb
    # disaggregated lifecycle (ISSUE 11, DisaggRouter only — the base
    # router never reads these): which stage the request is in
    # ("prefill" → "transfer" → "decode"), the exported page blob while
    # the router holds it in flight between pools, and the running
    # stage's start time for the per-stage slo histograms
    stage: str = "prefill"
    kv: dict | None = None
    # request-lifecycle reliability (ISSUE 19): absolute deadline on the
    # router clock (None = unbounded), the dispatch timestamp the hedge
    # delay measures from (None = not dispatched: the clock's zero is
    # arbitrary, so no reading of it can say that), the replica running
    # the hedge copy (None = not hedged), and a once-per-request latch so
    # a blocked hedge counts retry_budget_exhausted once, not once per tick
    t_deadline: float | None = None
    t_dispatch: float | None = None
    hedge_replica: str | None = None
    budget_blocked: bool = False
    # where the prefilled result physically came from (ISSUE 14
    # satellite): the /kv_blob fetch is DEFERRED until after the decode
    # pool's prefix probe, so the endpoint must outlive the handle (a
    # falsely-suspected replica's late result arrives exactly after
    # _mark_dead deleted it)
    kv_src: str | None = None
    t_stage: float = 0.0


@dataclass
class _Handle:
    """Routing-table entry for one live replica."""
    id: str
    endpoint: str
    max_batch: int = 1
    queue_depth: int = 0
    active: int = 0
    draining: bool = False
    ready: bool = True
    cursor: int = 0              # /results read position
    role: str = "unified"        # lease-advertised pool (ISSUE 11)
    free_pages: int | None = None    # decode-pool pressure (from /health)
    queued_kv_pages: int = 0         # pages promised to queued transfers
    prefix_sharing: bool = False     # /kv_transfer probe worth a round trip
    evictable_pages: int = 0         # idle prefix-cache pages (reclaimable)
    trace_cursor: int = 0            # /trace_pull read position (ISSUE 17)
    last_probe: float = field(default_factory=_slo.now)

    @property
    def load(self) -> float:
        return (self.queue_depth + self.active) / max(1, self.max_batch)


class Router:
    """router = Router(registry); rid = router.submit(prompt, 16)

    `registry`: the FileRegistry/KVRegistry the replicas lease into.
    `admission`: the fleet AdmissionPolicy (env-built when None).
    """

    def __init__(self, registry, admission: AdmissionPolicy | None = None,
                 http_timeout_s: float | None = None,
                 probe_interval_s: float = 0.05):
        self._registry = registry
        self._admission = admission or AdmissionPolicy()
        # probes are serial and submit() refreshes inline, so one wedged
        # replica (SIGSTOP, GC pause — socket accepts, reads block) must
        # not stall routing for longer than the lease that will bury it:
        # bound the timeout by the TTL unless the caller says otherwise
        ttl = float(getattr(registry, "ttl", 5.0))
        self._timeout = (max(1.0, ttl / 2.0) if http_timeout_s is None
                         else float(http_timeout_s))
        self._probe_s = float(probe_interval_s)
        self._handles: dict[str, _Handle] = {}
        self._pending: deque[RoutedRequest] = deque()
        self._inflight: dict[int, RoutedRequest] = {}
        self._orphans: deque[int] = deque()  # failover deferred by chaos
        # finished-result retention (ISSUE 10 satellite, the PR-9 ROADMAP
        # follow-up): _done holds UNDELIVERED results only. result() ACKS
        # — the record is handed over exactly once and leaves the table —
        # and anything never acked is evicted oldest-first past the same
        # PADDLE_SERVE_RESULTS_KEEP bound the replica side enforces, so a
        # long-lived frontend's memory follows its backlog, not its
        # lifetime. Retired rids (acked or evicted) are remembered as a
        # WATERMARK + exception set, not a growing set: rids are a dense
        # monotone sequence, so "every rid < _retired_floor is finished,
        # plus the out-of-order stragglers in _retired" compacts to O(gap)
        # — late-duplicate detection and wait() membership survive the
        # record itself being gone, at bounded memory over any lifetime.
        self._done: dict[int, dict] = {}
        self._retired: set[int] = set()
        self._retired_floor = 0
        self._retired_count = 0
        from ..utils import env_flags
        from .replica import ENV_RESULTS_KEEP  # ONE knob for both sides
        self._done_keep = int(env_flags.get_float(ENV_RESULTS_KEEP))
        # hedged re-dispatch (ISSUE 19): floor/enable switch and the
        # global retry budget as a token bucket — each NORMAL routed
        # dispatch earns pct/100 tokens, each hedge spends one, so hedge
        # volume is bounded at pct% of throughput no matter how sick the
        # fleet looks. One token of initial credit lets the very first
        # stall hedge before any history accrues; the cap bounds how big
        # a burst an idle accumulation can fund.
        self._hedge_floor = env_flags.get_float("PADDLE_HEDGE_DELAY_S")
        pct = max(0.0, env_flags.get_float("PADDLE_RETRY_BUDGET_PCT"))
        self._hedge_rate = pct / 100.0
        self._retry_tokens = 1.0 if pct > 0 else 0.0  # pct=0: NO hedges
        self._retry_tokens_cap = max(1.0, pct)
        # cooperative cancellation (ISSUE 19): POST /cancel lands on the
        # admin thread, which must never touch router state — it marks
        # the rid HERE under a dedicated lock and the router thread's
        # next tick applies it (decide-under-lock / actuate-outside)
        self._cancel_lk = threading.Lock()
        self._cancel_marks: list[int] = []
        self._requests: dict[int, RoutedRequest] = {}
        self._next_rid = 0
        # rid NAMESPACE: rids are router-local, but /results is one
        # shared per-replica list — every send carries this id and
        # _absorb ignores records stamped by OTHER routers, so N routers
        # over the same lease set cannot deliver each other's tokens
        self._rid_ns = uuid.uuid4().hex[:12]
        self._last_refresh = -1e9
        self._last_collect = -1e9
        self._last_info_check = -1e9
        # fleet-level SLO story: enqueue at submit, admit at routing,
        # preempt at failover, retire exactly-once at the first result —
        # trace ids issued HERE flow to every replica attempt
        self.slo = _slo.RequestTracker(source="router")
        # fleet-wide request tracing (ISSUE 17): the assembler is the
        # tracker's trace_sink — every exactly-once retire folds the
        # replica span batches (piggy-backed on /results) into ONE
        # multi-process trace with critical-path attribution
        self.trace = (_reqtrace.RouterTraceAssembler(self._rid_ns)
                      if _reqtrace.enabled() else None)
        if self.trace is not None:
            self.slo.trace_sink = self.trace.on_router_retire
        self._admin = None   # started on demand by start_admin()
        metrics.gauge("serve.fleet.replicas")
        # instance-scoped fleet counters (ISSUE 10 satellite, the PR-9
        # ROADMAP follow-up): summary() reads THESE, so two routers in
        # one process report their own routing story, not each other's.
        # The process-global serve.fleet.* counters keep incrementing as
        # the fleet-wide aggregate (bench/monitor back-compat), and each
        # instance also exports its own values as gauges suffixed with
        # its router id — the registry has no label support, so the id
        # rides in the name (serve.fleet.<name>.r_<router_id>).
        self._fleet_counts = {c: 0 for c in (
            "routed", "rejected", "retried", "failovers", "route_faults",
            "dup_results", "results_evicted",
            # lifecycle reliability (ISSUE 19) — "cancelled" and
            # "deadline_exceeded" deliberately share their retire
            # reason's spelling: _retire_local and _absorb count by it
            "cancelled", "deadline_exceeded", "hedges", "hedge_wins",
            "retry_budget_exhausted")}
        for c in self._fleet_counts:
            metrics.counter(f"serve.fleet.{c}")

    @property
    def router_id(self) -> str:
        """The instance id stamping this router's sends, results and
        per-instance metric exports."""
        return self._rid_ns

    def _count(self, name: str) -> None:
        """One fleet-counter event: instance tally (what summary()
        reports), process-global aggregate, and the router-id-labeled
        gauge export."""
        self._fleet_counts[name] += 1  # locks: ok (router thread only; _cancel_lk guards only _cancel_marks)
        metrics.counter(f"serve.fleet.{name}").inc()
        metrics.gauge(f"serve.fleet.{name}.r_{self._rid_ns}").set(
            self._fleet_counts[name])

    # --------------------------------------------------------------- HTTP
    def _headers(self, post: bool) -> dict:
        h = {"Content-Type": "application/json"} if post else {}
        if post:
            h["X-Paddle-Job-Token"] = job_token()
        tok = os.environ.get("PADDLE_ADMIN_READ_TOKEN", "")
        if tok:
            h["X-Paddle-Admin-Token"] = tok
        return h

    def _get(self, endpoint: str, path: str) -> dict | None:
        """GET json, None on any transport fault (the lease decides life,
        not one dropped poll). Non-transient errors propagate — a bug in
        OUR code must not masquerade as a dead replica. That includes an
        HTTP status error (403/404/500): a status line IS reachability
        proof, so it must surface loudly (a read-auth misconfig or a
        handler bug), never read as a dead replica and trigger a failover
        that runs the same work twice. HTTPError subclasses OSError, so it
        must be re-raised BEFORE the transient classification."""
        try:
            req = urllib.request.Request(endpoint + path,
                                         headers=self._headers(False))
            with urllib.request.urlopen(req, timeout=self._timeout) as r:
                return json.loads(r.read())
        except urllib.error.HTTPError:
            raise
        except Exception as e:
            if _transient_send(e):
                return None
            raise

    def _post(self, endpoint: str, path: str, obj: dict,
              timeout: float | None = None) -> tuple[int, dict]:
        """POST json -> (status, body). 4xx statuses are ANSWERS (429 =
        admission data); transport faults return (0, {}) and the caller's
        retry/tick discipline owns recovery — the resilience classify()
        split applied to routed sends. ``timeout`` overrides the probe
        timeout (a KV-page transfer ships megabytes, not a health doc)."""
        return self._post_raw(endpoint, path, json.dumps(obj).encode(),
                              "application/json", timeout)

    def _post_bytes(self, endpoint: str, path: str, data: bytes,
                    timeout: float | None = None) -> tuple[int, dict]:
        """POST one binary frame (octet-stream) — the disagg KV-page
        transfer hop (ISSUE 12): payload bytes travel VERBATIM, no
        base64/JSON inflation. Same status contract as :meth:`_post`."""
        return self._post_raw(endpoint, path, data,
                              "application/octet-stream", timeout)

    def _post_raw(self, endpoint: str, path: str, data: bytes,
                  ctype: str, timeout: float | None) -> tuple[int, dict]:
        headers = dict(self._headers(True))
        headers["Content-Type"] = ctype
        try:
            req = urllib.request.Request(endpoint + path, data=data,
                                         headers=headers, method="POST")
            with urllib.request.urlopen(
                    req, timeout=timeout or self._timeout) as r:
                return r.status, json.loads(r.read() or b"{}")
        except urllib.error.HTTPError as e:
            try:
                body = json.loads(e.read() or b"{}")
            except ValueError:
                body = {}
            return e.code, body
        except Exception as e:
            if _transient_send(e):
                return 0, {}
            raise

    def _get_bytes(self, endpoint: str, path: str,
                   timeout: float | None = None) -> bytes | None:
        """GET a binary body (the /kv_blob frame). None on transport
        fault OR 404 (frame evicted/never exported — the caller's answer
        is re-prefill); any other HTTP status propagates loudly, same
        contract as :meth:`_get`."""
        try:
            req = urllib.request.Request(endpoint + path,
                                         headers=self._headers(False))
            with urllib.request.urlopen(
                    req, timeout=timeout or self._timeout) as r:
                return r.read()
        except urllib.error.HTTPError as e:
            if e.code == 404:
                return None
            raise
        except Exception as e:
            if _transient_send(e):
                return None
            raise

    # ---------------------------------------------------------- discovery
    def refresh(self, force: bool = False):
        """Sync the routing table with the lease set and re-probe health.
        Dead-replica handling lives here: lease gone + final poll
        unreachable → fail its in-flight work over."""
        now = _slo.now()
        if not force and now - self._last_refresh < self._probe_s:
            return
        self._last_refresh = now
        alive = {n for n in self._registry.alive_nodes()
                 if n.startswith(REPLICA_PREFIX)}
        # same-name restart within TTL: a supervisor relaunched a replica
        # under the same lease id before the lease ever lapsed, so the
        # alive set never dropped it — but the process (and its port) is
        # NEW. Without a re-read the handle's endpoint goes permanently
        # stale: every send fails transient, the live lease blocks
        # _mark_dead, and the requests park forever. An endpoint change
        # IS the death certificate of the old process — fail its
        # in-flight work over and re-join the fresh one (new handle ⇒
        # results cursor restarts at 0). Throttled to ttl/4: info() is a
        # second registry read per replica that alive_nodes() just paid,
        # and the lease-based detector itself only promises one TTL.
        ttl = float(getattr(self._registry, "ttl", 1.0) or 1.0)
        if force or now - self._last_info_check >= max(self._probe_s,
                                                       ttl / 4.0):
            self._last_info_check = now
            for rid in sorted(alive & set(self._handles)):
                ep = (self._registry.info(rid) or {}).get("endpoint")
                if ep and ep != self._handles[rid].endpoint:
                    self._mark_dead(self._handles[rid])
        for rid in sorted(alive - set(self._handles)):
            info = self._registry.info(rid) or {}
            ep = info.get("endpoint")
            if not ep:
                continue  # lease without an endpoint: not routable yet
            self._handles[rid] = _Handle(
                id=rid, endpoint=ep,
                max_batch=int(info.get("max_batch", 1)),
                role=str(info.get("role") or "unified"))
            _recorder.record("serve.route_table", replica=rid, event="join",
                             endpoint=ep, role=self._handles[rid].role)
        for rid in sorted(set(self._handles) - alive):
            h = self._handles[rid]
            # final poll before the verdict: drained replicas deregister
            # on purpose and keep answering until collected
            res = self._collect_one(h)
            if res is None:
                self._mark_dead(h)        # unreachable: lease was truth
            elif res.get("drained"):
                del self._handles[rid]    # clean exit, results collected
                _recorder.record("serve.route_table", replica=rid,
                                 event="drained")
            # else: reachable but lease lapsed (registry blip / slow beat)
            # — keep routing to it; the next refresh re-checks
        for h in self._handles.values():
            doc = self._get(h.endpoint, "/health")
            if doc:
                h.queue_depth = int(doc.get("queue_depth", h.queue_depth))
                h.active = int(doc.get("active_slots", h.active))
                h.max_batch = int(doc.get("max_batch", h.max_batch))
                h.draining = bool(doc.get("draining"))
                h.ready = bool(doc.get("ready", True))
                if doc.get("role"):
                    h.role = str(doc["role"])
                fp = doc.get("free_pages")
                h.free_pages = None if fp is None else int(fp)
                h.queued_kv_pages = int(doc.get("queued_kv_pages", 0) or 0)
                h.prefix_sharing = bool(doc.get("prefix_sharing"))
                h.evictable_pages = int(doc.get("evictable_pages", 0) or 0)
                h.last_probe = now
        metrics.gauge("serve.fleet.replicas").set(len(self._handles))

    def _mark_dead(self, h: _Handle):
        del self._handles[h.id]
        for q in self._pending:
            if q.last_faulted == h.id:
                # the dedup probe is meaningless once the replica's
                # results can never be collected — and a stale marker
                # would hold tick() in unthrottled /results polling for
                # the whole saturation window
                q.last_faulted = None
        orphans = []
        for rid, q in self._inflight.items():
            if q.hedge_replica == h.id:
                # the hedge copy died with the replica; the primary still
                # runs — the pair just collapses back to one attempt
                q.hedge_replica = None
            if q.replica == h.id:
                if q.hedge_replica is not None:
                    # the PRIMARY died but its hedge survives: promote the
                    # hedge instead of re-enqueueing a third attempt
                    q.replica, q.hedge_replica = q.hedge_replica, None
                else:
                    orphans.append(rid)
        _recorder.record(
            "serve.replica_dead", echo=True,
            message=f"[serve] replica {h.id} lease expired and unreachable"
                    f" — failing over {len(orphans)} in-flight request(s)",
            replica=h.id, inflight=len(orphans))
        self._orphans.extend(orphans)

    def _failover(self):
        """Re-enqueue every orphaned request (same trace id) on the
        pending queue. Chaos site serve.replica_dead defers ONE request to
        the next tick — deferred, never lost."""
        for _ in range(len(self._orphans)):
            rid = self._orphans.popleft()
            req = self._inflight.get(rid)
            if req is None or self._finished(rid):
                continue  # already delivered before the lease lapsed
            try:
                # literal sites (rule A2): the hook picks WHICH of the two
                # registered failover sites guards this request's stage
                if self._failover_site(req) == "serve.prefill_dead":
                    chaos.hit("serve.prefill_dead")
                else:
                    chaos.hit("serve.replica_dead")
            except chaos.ChaosError:
                self._orphans.append(rid)   # deferred; retried next tick
                continue
            del self._inflight[rid]
            req.replica = None
            req.retried = True
            self._on_failover(req)
            self.slo.on_preempt(rid)  # queue-wait resumes, trace id kept
            self._pending.appendleft(req)
            self._count("failovers")

    def _on_failover(self, req: RoutedRequest) -> None:
        """Hook between un-inflighting and re-pending a failed-over
        request — the DisaggRouter resets a decode-stage request to
        re-prefill here (its pages died with the replica's pool)."""

    # -------------------------------------- request lifecycle (ISSUE 19)
    def _retire_local(self, req: RoutedRequest, reason: str) -> None:
        """Terminal local retire of a request not (or no longer) running
        anywhere — typed result record, exactly-once SLO measure, fleet
        counter (the counter name IS the retire reason: "cancelled" /
        "deadline_exceeded"). Any held page blob drops with it."""
        rid = req.rid
        req.kv = None
        self._inflight.pop(rid, None)
        self._record_done(rid, {"rid": rid, "tokens": [], "reason": reason,
                                "trace_id": req.trace_id,
                                "router": self._rid_ns})
        self.slo.on_retire(rid, n_tokens=0, reason=reason)
        self._count(reason)

    def _cancel_parked(self, req: RoutedRequest) -> bool:
        """Remove ``req`` from the router's LOCAL custody (pending queue,
        deferred-failover orphans). The DisaggRouter extends this to the
        transfer-parked lane, dropping the held page blob. True when the
        request was found somewhere local."""
        found = False
        try:
            self._pending.remove(req)
            found = True
        except ValueError:
            pass
        try:
            self._orphans.remove(req.rid)
            found = True
        except ValueError:
            pass
        return found

    def cancel(self, rid: int) -> str:
        """Cooperatively cancel one request NOW (router-thread entry —
        the single-threaded twin of ``POST /cancel``). Returns the state
        the rid was found in: "finished"/"unknown" are no-ops (a cancel
        racing a retire LOSES — the tokens were produced and the result
        stands), "deferred" means the request.cancel chaos site dropped
        it (cancellation is best-effort by contract — the request runs on
        and retires normally, token-identically), "cancelled" retired a
        parked request locally, and "propagated" forwarded it to the
        replica(s) holding it — their typed "cancelled" result retires it
        exactly once through _absorb, pages freed on their side."""
        if self._finished(rid):
            return "finished"
        req = self._requests.get(rid)
        if req is None:
            return "unknown"
        try:
            chaos.hit("request.cancel")
        except chaos.ChaosError:
            return "deferred"
        if req.replica is None:
            self._cancel_parked(req)
            if req.last_faulted:
                # the parked request's last send was AMBIGUOUS — it may be
                # running over there. The local retire below wins the
                # exactly-once race either way (a late result absorbs as a
                # dup), but telling the replica stops the wasted decode.
                lf = self._handles.get(req.last_faulted)
                if lf is not None:
                    self._post(lf.endpoint, "/cancel",
                               {"rid": rid, "router": self._rid_ns})
            self._retire_local(req, "cancelled")
            return "cancelled"
        for rep in {req.replica, req.hedge_replica} - {None}:
            h = self._handles.get(rep)
            if h is not None:
                self._post(h.endpoint, "/cancel",
                           {"rid": rid, "router": self._rid_ns})
        return "propagated"

    def _h_cancel(self, body: dict):
        """POST /cancel — the admin-thread face of :meth:`cancel`. The
        handler only MARKS the rid under the dedicated marks lock; the
        router thread's next tick applies it (decide-under-lock /
        actuate-outside: the admin thread must never walk router state or
        block on replica HTTP while holding anything tick() needs)."""
        try:
            rid = int(body["rid"])
        except (KeyError, TypeError, ValueError) as e:
            return 400, {"ok": False, "reason": f"bad cancel: {e}"}
        with self._cancel_lk:
            self._cancel_marks.append(rid)
        return 200, {"ok": True, "rid": rid, "state": "marked",
                     "router": self._rid_ns}

    def _apply_cancels(self) -> None:
        """Drain the admin-thread cancel marks and apply each on THIS
        (the router) thread — the actuate half of the /cancel split."""
        with self._cancel_lk:
            if not self._cancel_marks:
                return
            marked, self._cancel_marks = self._cancel_marks, []
        for rid in marked:
            self.cancel(rid)

    def _hedge_delay(self) -> float:
        """The adaptive hedge trigger: p95 of the fleet-level e2e
        histogram (the router's own tracker fills it), floored at
        PADDLE_HEDGE_DELAY_S — an empty window hedges at the floor."""
        st = metrics.histogram("slo.e2e_s").stats() or {}
        return max(self._hedge_floor, float(st.get("p95") or 0.0))

    def _maybe_hedge(self) -> None:
        """Budgeted hedged re-dispatch: an in-flight request stalled past
        :meth:`_hedge_delay` is re-posted — same rid, same namespace — to
        the least-loaded OTHER candidate. The replica-side (router, rid)
        dedup makes the copy idempotent per replica, the first terminal
        result wins (_absorb's exactly-once retire), and the loser is
        cancelled on settle — token-identical at temp=0 by the same
        parity contract every failover rides. Gated three ways:
        PADDLE_HEDGE_DELAY_S > 0 (off by default), the retry-budget
        token bucket (exhausted → counted once per request, no hedge —
        a sick fleet degrades to shedding, never a retry storm), and the
        router.hedge chaos site (a fault skips this tick's hedge; the
        primary still completes, token-identical). The hedge send is
        NEVER forced: it is speculative work and takes admission's no
        for an answer."""
        if self._hedge_floor <= 0 or not self._inflight:
            return
        now = _slo.now()
        delay = self._hedge_delay()
        for rid, req in list(self._inflight.items()):
            if req.hedge_replica is not None or req.last_faulted:
                continue
            if req.t_dispatch is None or now - req.t_dispatch < delay:
                continue
            if req.t_deadline is not None and now >= req.t_deadline:
                continue   # expired: the replica's own budget check
                #            retires it typed — a hedge would be waste
            if self._retry_tokens < 1.0:
                if not req.budget_blocked:
                    req.budget_blocked = True
                    self._count("retry_budget_exhausted")
                continue
            cands = [h for h in
                     self._candidates(role=self._route_role(req))
                     if h.id != req.replica]
            if not cands:
                continue
            try:
                chaos.hit("router.hedge")
            except chaos.ChaosError:
                continue
            h = cands[0]
            code, body = self._post(h.endpoint, "/enqueue",
                                    self._enqueue_body(req, False))
            req.attempts += 1
            if code == 200 and body.get("ok"):
                self._retry_tokens -= 1.0  # locks: ok (router thread only; _cancel_lk guards only _cancel_marks)
                req.hedge_replica = h.id
                req.budget_blocked = False
                h.queue_depth += 1   # optimistic; next probe corrects
                self._count("hedges")
                _recorder.record("serve.fleet.hedge", rid=rid,
                                 primary=req.replica, hedge=h.id,
                                 delay_s=round(delay, 4))
            # any other answer (429, transport fault): a hedge is pure
            # opportunism — no hedge this tick, the primary still owns
            # the request and the budget was never spent

    def _settle_hedge(self, req: RoutedRequest, res: dict) -> None:
        """First terminal result of a hedged pair: count the winner,
        cancel the loser. The loser's tokens are identical by the temp=0
        parity contract — the cancel is pure waste reduction, and racing
        its own retire is a no-op on the replica; its late duplicate
        result absorbs as dup_results."""
        winner = res.get("replica")
        if winner == req.hedge_replica:
            self._count("hedge_wins")
        for loser in {req.replica, req.hedge_replica} - {None, winner}:
            h = self._handles.get(loser)
            if h is not None:
                self._post(h.endpoint, "/cancel",
                           {"rid": req.rid, "router": self._rid_ns})
        req.hedge_replica = None

    # ------------------------------------------------------------- routing
    def _candidates(self, include_draining: bool = False,
                    role: str | None = None) -> list[_Handle]:
        # draining replicas sort LAST: only forced (already-accepted)
        # work may land there, and only when no healthy replica can take
        # it — the replica side honors force=True during drain for
        # exactly this case (accepted work must not strand when every
        # survivor is draining). A draining replica's /health reports
        # ready=False BY DESIGN (new admits must not route there), so the
        # forced path ignores readiness entirely: ready=False (draining,
        # a transiently failing health callable, a missed probe) must
        # never strand accepted work — the send itself is the probe that
        # matters, and a 429/fault answer just parks it for the next tick.
        # `role` (ISSUE 11): a disagg stage targets its specialized pool;
        # "unified" replicas serve either stage; role=None (every non-
        # disagg caller) keeps the pre-role behavior byte-identical.
        return sorted((h for h in self._handles.values()
                       if (include_draining
                           or (h.ready and not h.draining))
                       and (role is None or h.role == role
                            or h.role == "unified")),
                      key=lambda h: (h.draining, h.load))

    def _route_role(self, req: RoutedRequest) -> str | None:
        """The pool req's current stage targets — None (any replica) for
        the base router; the DisaggRouter answers per stage."""
        return None

    def _enqueue_body(self, req: RoutedRequest, force: bool) -> dict:
        """The /enqueue POST body — the DisaggRouter stamps prefill_only
        on stage-1 sends. ``deadline_left_s`` is re-derived AT SEND TIME
        (ISSUE 19): the budget a hop ships is what remains NOW, so time
        parked in this router's queues shrinks it like time anywhere
        else."""
        body = {"rid": req.rid, "prompt": req.prompt,
                "max_new_tokens": req.max_new_tokens,
                "trace_id": req.trace_id, "force": force,
                "router": self._rid_ns}
        if req.t_deadline is not None:
            body["deadline_left_s"] = req.t_deadline - _slo.now()
        return body

    def _failover_site(self, req: RoutedRequest) -> str:
        """The chaos site guarding this request's failover re-enqueue —
        the DisaggRouter distinguishes a dead PREFILL replica
        (serve.prefill_dead) from a dead decode/unified one."""
        return "serve.replica_dead"

    def _try_route(self, req: RoutedRequest, force: bool) -> str:
        """One routing attempt over the candidate list, least-loaded
        first. Returns "routed" (a replica accepted), "fault" (a chaos/
        transport fault interrupted the send — the request is ACCEPTED
        work that must stay pending and route next tick), or "declined"
        (every candidate is saturated: an admission answer)."""
        faulted = False
        cands = self._candidates(include_draining=force,
                                 role=self._route_role(req))
        if req.last_faulted:
            # an earlier send to this replica faulted mid-wire and may
            # have landed: retry it first (stable sort keeps least-loaded
            # order among the rest) so its dedup answers instead of a
            # second replica starting a duplicate generation — and it
            # must be REACHED even when the candidate filter (draining)
            # or the saturation gate below would skip it: a dedup probe
            # is one cheap round trip, a skipped one is a full duplicate
            # generation burned exactly when the fleet is saturated
            lf = self._handles.get(req.last_faulted)
            if lf is not None and lf not in cands:
                cands.insert(0, lf)
            else:
                cands.sort(key=lambda c: c.id != req.last_faulted)
        for h in cands:
            if not force and h.id != req.last_faulted and \
                    h.queue_depth >= self._admission.max_queue_for(
                        h.max_batch):
                continue  # saturated: don't bounce off its 429
            try:
                chaos.hit("serve.route")
            except chaos.ChaosError:
                self._count("route_faults")
                faulted = True
                break           # stays pending; routed next tick
            code, body = self._post(h.endpoint, "/enqueue",
                                    self._enqueue_body(req, force))
            req.attempts += 1
            if code == 200 and body.get("ok"):
                req.replica = h.id
                req.last_faulted = None
                self._inflight[req.rid] = req
                h.queue_depth += 1      # optimistic; next probe corrects
                # the hedge clock starts at dispatch, and every NORMAL
                # dispatch earns the retry budget its pct promises
                req.t_dispatch = _slo.now()
                req.hedge_replica = None
                self._retry_tokens = min(self._retry_tokens_cap,
                                         self._retry_tokens
                                         + self._hedge_rate)
                self.slo.on_admit(req.rid)
                self._count("routed")
                return "routed"
            if code == 400:
                # the replica refused the request as never-admissible
                # (over-budget, impossible page demand) — that's a caller
                # error, not capacity: surface it loudly like the direct
                # batcher's add_request ValueError, never an empty result
                raise ValueError(
                    f"replica {h.id} refused request {req.rid}: "
                    f"{body.get('reason', 'invalid')}")
            if code == 429:
                h.queue_depth = max(h.queue_depth,
                                    self._admission.max_queue_for(
                                        h.max_batch))
                try:
                    req.retry_hint = max(req.retry_hint,
                                         float(body.get("retry_after_s")
                                               or 0.0))
                except (TypeError, ValueError):
                    pass
                if body.get("reason") == "draining":
                    h.draining = True
                continue
            if code == 0:
                # transport fault: AMBIGUOUS — the enqueue may have landed
                # before the response was lost (a handler stall past the
                # timeout). Posting the same rid to the next candidate in
                # this same pass could run the generation twice, so stop
                # the pass: the request parks pending, the next tick
                # collects results FIRST (surfacing a landed send),
                # re-tries THIS replica first (dedup), and the lease owns
                # the life-or-death verdict
                req.last_faulted = h.id
                faulted = True
                break
            # any OTHER status (403 auth misconfig, 500 handler bug) is
            # the POST twin of _get's contract: a status line is
            # reachability PROOF, so it must surface loudly — falling
            # through to "declined" would report a broken fleet as
            # saturated and retry-storm an honoring client forever
            raise RuntimeError(
                f"replica {h.id} answered unexpected HTTP {code} at "
                f"/enqueue ({body.get('reason') or body.get('error') or 'no body'})"
                f" — auth misconfig or handler bug, not capacity")
        return "fault" if faulted else "declined"

    def submit(self, prompt_ids, max_new_tokens: int = 32,
               deadline_s: float | None = None) -> int:
        """Route one request or reject-with-retry-after. The ONLY entry
        that can refuse work: everything past here completes (failover,
        shed-retry and drain re-routing are internal, and a send
        interrupted by a fault stays pending — accepted work is never
        converted into a rejection).

        ``deadline_s`` (ISSUE 19) is the request's total latency budget
        in seconds (None falls back to ``PADDLE_REQUEST_DEADLINE_S``;
        unset = no deadline). A budget provably unmeetable — already
        expired, or below the fleet's observed TTFT floor — rejects
        typed ``deadline_unmeetable`` here, before any replica burns
        work on it; an admitted deadline then rides every hop as
        remaining budget."""
        self.refresh()
        req = RoutedRequest(self._next_rid, [int(t) for t in prompt_ids],
                            int(max_new_tokens), trace_id=0)
        self._next_rid += 1  # locks: ok (router thread only; _cancel_lk guards only _cancel_marks)
        req.trace_id = self.slo.on_enqueue(req.rid)
        if deadline_s is None:
            from ..utils import env_flags
            dflt = env_flags.get("PADDLE_REQUEST_DEADLINE_S")
            deadline_s = float(dflt) if dflt else None
        if deadline_s is not None:
            req.t_deadline = _slo.now() + float(deadline_s)
            d = self._admission.decide_deadline(float(deadline_s),
                                                hists=slo_hists)
            if d is not None:
                self.slo.on_reject(req.rid)
                self._count("rejected")
                self._retire_rid(req.rid, count=False)
                _reject(d["reason"], d["retry_after_s"])
        cand = self._candidates(role=self._route_role(req))
        if not cand:
            self.slo.on_reject(req.rid)
            self._count("rejected")
            # the burned rid is retired (uncounted) on EVERY refusal exit:
            # the watermark must advance past it, or one rejection leaves
            # every later retired rid stranded in the exception set
            self._retire_rid(req.rid, count=False)
            _reject("no_replicas", retry_after_floor())
        try:
            status = self._try_route(req, force=False)
        except (ValueError, RuntimeError):
            # never-admissible (replica 400) or a loud non-capacity HTTP
            # status (403/500): the request never entered the system —
            # drop its trace record, then surface the error
            self.slo.on_reject(req.rid)
            self._retire_rid(req.rid, count=False)
            raise
        if status == "declined":
            # every candidate is saturated: the fleet is at capacity —
            # push back with a REAL estimate, not the floor: the max
            # retry_after_s the replicas' 429 bodies computed this pass,
            # or (when every candidate was skipped on known depth and no
            # 429 was ever issued) the hint computed from the least-loaded
            # candidate's depth and the router's OWN fleet-level e2e p50
            # (its RequestTracker fills the local slo.* histograms)
            self.slo.on_reject(req.rid)
            h = cand[0]
            self._count("rejected")
            self._retire_rid(req.rid, count=False)
            _reject("fleet_saturated",
                    max(req.retry_hint,
                        self._admission.retry_after(h.queue_depth,
                                                    h.max_batch,
                                                    hists=slo_hists)))
        self._requests[req.rid] = req
        if status == "fault":
            self._pending.append(req)   # accepted; routes on a later tick
        return req.rid

    # ------------------------------------------------------------- results
    def _collect_one(self, h: _Handle) -> dict | None:
        """Drain one replica's /results cursor. Returns the raw response
        (None on transport fault)."""
        doc = self._get(h.endpoint, f"/results?since={h.cursor}")
        if doc is None:
            return None
        h.cursor = int(doc.get("cursor", h.cursor))
        if self.trace is not None:
            # BEFORE absorbing: a result record's piggy-backed span batch
            # must be in the assembler when _absorb's retire assembles it
            self.trace.ingest_results_doc(doc)
        for res in doc.get("results", []):
            # src: where this record physically came from — the disagg
            # frame fetch needs it even after the handle left the table
            # (a falsely-suspected replica's late result arrives exactly
            # when _mark_dead has already deleted its handle)
            self._absorb(res, src=h.endpoint)
        return doc

    def _finished(self, rid) -> bool:
        """Has this rid ever produced a terminal result? True even after
        the record itself was acked/evicted — the guard every
        duplicate-suppression check needs."""
        return rid in self._done or rid in self._retired \
            or (isinstance(rid, int) and rid < self._retired_floor)

    def _retire_rid(self, rid: int, count: bool = True) -> None:
        """Mark a rid finished-and-record-gone, compacting the watermark:
        contiguous retirements from the floor collapse into it, so the
        exception set holds only the out-of-order gap. EVERY allocated
        rid must eventually come through here — a rejected submit burns
        its rid too (count=False: not a finished request, but a hole the
        floor must advance past, or the set grows forever after one
        overload rejection)."""
        if rid < self._retired_floor or rid in self._retired:
            return
        self._retired.add(rid)
        if count:
            self._retired_count += 1  # locks: ok (router thread only; _cancel_lk guards only _cancel_marks)
        while self._retired_floor in self._retired:
            self._retired.discard(self._retired_floor)
            self._retired_floor += 1  # locks: ok (router thread only; _cancel_lk guards only _cancel_marks)

    def _record_done(self, rid: int, res: dict) -> None:
        """Publish a terminal result and enforce the retention bound:
        past PADDLE_SERVE_RESULTS_KEEP undelivered records the OLDEST are
        evicted (their rids stay retired so dup detection and wait()
        membership survive) — the frontend mirror of the replica-side
        results bound. Eviction means a wait()-only client that never
        result()-acked will read [] for that rid: the loss is DELIBERATE
        (bounded memory beats unbounded hoarding for an absent consumer)
        and observable — counted per instance and flight-recorded."""
        self._done[rid] = res
        keep = self._done_keep
        if keep > 0:
            while len(self._done) > keep:
                old_rid = next(iter(self._done))
                del self._done[old_rid]
                self._retire_rid(old_rid)
                self._requests.pop(old_rid, None)
                self._count("results_evicted")
                _recorder.record(
                    "serve.fleet.result_evicted", rid=old_rid,
                    keep=keep, router=self._rid_ns)

    def _absorb(self, res: dict, src: str | None = None):
        if res.get("router") != self._rid_ns:
            # another sender's record — a second router's, or a direct
            # client's (router=None). Every send THIS router makes is
            # stamped with its namespace, so an unstamped record can never
            # be ours: without the exact match a bare client reusing a
            # small integer rid would have its tokens delivered as this
            # router's result for the same rid
            return
        rid = res.get("rid")
        req = self._requests.get(rid)
        if req is None or self._finished(rid):
            # a late duplicate may still hold an _inflight entry (the rid
            # was re-routed after its first result won) — release it so
            # summary()/inflight accounting can't leak
            self._inflight.pop(rid, None)
            self._count("dup_results")
            return
        reason = res.get("reason", "complete")
        if reason == "shed":
            # replica load-shed it: accepted work, so it re-routes under
            # the same trace id instead of surfacing a failure
            if self._inflight.pop(rid, None) is not None:
                if req.hedge_replica is not None:
                    # one copy of a hedged pair shed — the OTHER copy is
                    # still running, so the pair collapses to it instead
                    # of re-pending a third attempt
                    survivor = (req.replica
                                if res.get("replica") == req.hedge_replica
                                else req.hedge_replica)
                    req.replica = survivor
                    req.hedge_replica = None
                    self._inflight[rid] = req
                    return
                req.replica = None
                req.retried = True
                self.slo.on_preempt(rid)
                self._pending.appendleft(req)
                self._count("retried")
            return
        self._inflight.pop(rid, None)
        if req.hedge_replica is not None:
            # first terminal result of a hedged pair wins; the loser is
            # cancelled (its late duplicate absorbs as dup_results)
            self._settle_hedge(req, res)
        self._record_done(rid, res)
        n = len(res.get("tokens") or [])
        if n:
            self.slo.on_first_token(rid)
            self.slo.on_tokens(rid, n)
        self.slo.on_retire(rid, n_tokens=n, reason=reason)
        if reason in ("cancelled", "deadline_exceeded"):
            # a replica-side cancel/expiry retires HERE exactly once —
            # count it in the same fleet tally the local retires use
            self._count(reason)

    # ---------------------------------------------------------------- tick
    def tick(self):
        """One maintenance pass: leases + health, failover, result
        collection, pending dispatch. wait() calls this in its loop; a
        server embedding the router calls it on its own cadence.
        Collection runs BEFORE dispatch (and the dispatch loop skips
        already-done rids): a request parked in _pending by a send fault
        may in fact have been accepted by the replica — its result must
        not race a redundant second dispatch. While the first attempt is
        still GENERATING, the replica's (router, rid) active-dedup on
        /enqueue is what absorbs the re-send (idempotent 200); this
        ordering covers the already-finished tail. Collection is
        throttled to the probe interval so wait()'s tight loop doesn't
        hammer every replica with an HTTP poll per 4 ms pass."""
        self.refresh()
        self._failover()
        self._apply_cancels()   # admin-thread /cancel marks, applied here
        now = _slo.now()
        if any(r.last_faulted for r in self._pending) \
                or now - self._last_collect >= self._probe_s:
            # unthrottled only while a FAULT-PARKED dispatch is pending:
            # the done-guard below suppresses a duplicate dispatch only
            # if the first (fault-parked but actually-landed) send's
            # result has been collected first. Capacity-parked requests
            # were never accepted anywhere — no result can exist, and
            # polling every replica per 4 ms wait() pass exactly while
            # the fleet is saturated would be pure load
            self._last_collect = now
            for h in list(self._handles.values()):
                self._collect_one(h)
        self._maybe_hedge()   # after collection: a result that already
        #                       arrived must not trigger a wasted hedge
        for _ in range(len(self._pending)):
            req = self._pending.popleft()
            if self._finished(req.rid):
                continue  # fault-parked send actually landed; don't rerun
            if req.t_deadline is not None \
                    and _slo.now() >= req.t_deadline:
                # the budget ran out while parked: retire typed, never
                # dispatch — an expired request must not start (another)
                # prefill past its expiry
                self._retire_local(req, "deadline_exceeded")
                continue
            try:
                status = self._try_route(req, force=req.retried)
            except ValueError as e:
                # a fault-parked request turned out never-admissible (the
                # replica answered 400; submit() never validated it because
                # every first send faulted). There is no caller to throw
                # to — absorb it as a terminal error result so wait()
                # finishes and result() carries the reason, instead of the
                # rid vanishing and stranding wait() forever.
                self._inflight.pop(req.rid, None)
                self._record_done(req.rid, {"rid": req.rid, "tokens": [],
                                            "reason": f"error: {e}",
                                            "trace_id": req.trace_id})
                self.slo.on_retire(req.rid, n_tokens=0, reason="error")
                continue
            except RuntimeError:
                # loud non-capacity HTTP status (auth misconfig / handler
                # bug): surface it, but re-park the request first — it is
                # accepted work and must survive for the retry after the
                # operator fixes the fleet
                self._pending.appendleft(req)
                raise
            if status == "fault":
                # the ambiguous-send invariant is PER-REQUEST: this one
                # parks (its dedup probe retries next tick, appended so
                # this pass cannot re-pop it) but a wedged replica must
                # not head-of-line block every other pending request from
                # reaching healthy replicas for up to one TTL
                self._pending.append(req)
                continue
            if status != "routed":
                self._pending.appendleft(req)
                break  # declined: capacity is fleet-wide; retry next tick

    def wait(self, rids=None, timeout: float = 120.0) -> dict:
        """Block until every rid (default: all submitted) is done; returns
        {rid: [tokens]}. Raises TimeoutError listing the stragglers.
        Does NOT ack: the records stay readable until ``result()`` takes
        them (a rid already acked/evicted counts as done and returns []
        here — its record was handed over or aged out)."""
        want = set(self._requests if rids is None else rids)
        deadline = _slo.now() + timeout
        while any(not self._finished(r) for r in want):
            if _slo.now() > deadline:
                missing = sorted(r for r in want if not self._finished(r))
                raise TimeoutError(
                    f"router.wait: {len(missing)} request(s) not done "
                    f"after {timeout}s: {missing[:8]}")
            self.tick()
            time.sleep(0.004)
        return {rid: self._done.get(rid, {}).get("tokens", [])
                for rid in want}

    def result(self, rid: int) -> dict | None:
        """Full result record (tokens, reason, trace_id) or None — and
        the ACK (ISSUE 10 satellite): the record is handed over exactly
        once and leaves the table, so a long-lived frontend's ``_done``
        holds only never-delivered results (those are bounded by
        PADDLE_SERVE_RESULTS_KEEP eviction in ``_record_done``). A second
        read, or a read after eviction, returns None."""
        rec = self._done.pop(rid, None)
        if rec is not None:
            self._retire_rid(rid)
            self._requests.pop(rid, None)
        return rec

    # ---------------------------------------------------------------- drain
    def drain(self, replica_id: str) -> bool:
        """Ask one replica to drain (finish admitted, reject new,
        deregister, exit clean). Routing skips it immediately."""
        h = self._handles.get(replica_id)
        if h is None:
            return False
        code, _ = self._post(h.endpoint, "/drain", {})
        if code == 200:
            h.draining = True
            return True
        return False

    def pull_traces(self) -> int:
        """The ``/trace_pull`` fallback (ISSUE 17): drain every live
        replica's cursor-addressed trace log. The piggy-back on /results
        is the primary ship; this recovers batches whose piggy-back was
        lost (a chaos-faulted ship, a result record evicted before the
        poll) for postmortem reads. Returns the number of batches
        ingested."""
        if self.trace is None:
            return 0
        n = 0
        for h in list(self._handles.values()):
            doc = self._get(h.endpoint,
                            f"/trace_pull?cursor={h.trace_cursor}")
            if doc is None:
                continue
            n += len(doc.get("batches") or ())
            self.trace.ingest_results_doc(doc,
                                          source=doc.get("source") or h.id)
            h.trace_cursor = max(int(doc.get("base", 0)),
                                 int(doc.get("cursor", h.trace_cursor)))
        return n

    def _h_trace(self, query: dict):
        """GET /trace?rid=<router rid>[&fmt=chrome] — the assembled
        end-to-end trace of one retained request (tail-sampled: breaches
        and the sliding slowest-p99). fmt=chrome returns the merged
        chrome-trace document (one track per process, flow arrows)."""
        raw = query.get("rid", [""])[0]
        try:
            rid = int(raw)
        except (TypeError, ValueError):
            return 400, {"ok": False,
                         "reason": f"rid must be an integer, got {raw!r}"}
        doc = None if self.trace is None else self.trace.get_trace(rid)
        if doc is None:
            return 404, {"ok": False, "rid": rid,
                         "reason": ("tracing disabled (PADDLE_REQTRACE=0)"
                                    if self.trace is None else
                                    "no retained trace for this rid "
                                    "(sampled out, evicted, or still "
                                    "in flight)")}
        if (query.get("fmt", [""])[0] or "").lower() == "chrome":
            return 200, self.trace.chrome_trace(doc)
        return 200, doc

    def start_admin(self, port: int = 0, host: str = "127.0.0.1"):
        """Opt-in admin endpoint for the ROUTER process — serves
        ``GET /trace`` and ``POST /cancel`` (plus the admin builtins) so
        operators read breach postmortems and cancel runaway requests
        over HTTP. Plain Routers embedded in a client process never open
        a socket unless this is called. Idempotent; returns the
        AdminServer (``.port`` carries the bound port)."""
        if self._admin is None:
            from ..observability.admin import AdminServer
            self._admin = AdminServer(
                port=port, host=host,
                extra={"router": self.summary,
                       **({"trace": self.trace.summary}
                          if self.trace is not None else {})},
                get_routes={"/trace": self._h_trace},
                post_routes={"/cancel": self._h_cancel}).start()
        return self._admin

    def replica_snapshots(self) -> dict:
        """{replica id: its admin /snapshot} over the current routing
        table — the PUBLIC read of per-replica telemetry (benches report
        per-replica TTFT from it). Unreachable replicas are omitted."""
        out = {}
        for h in list(self._handles.values()):
            snap = self._get(h.endpoint, "/snapshot")
            if snap is not None:
                out[h.id] = snap
        return out

    def summary(self) -> dict:
        """THIS router's story: the counters are instance-scoped (ISSUE
        10 satellite), so two routers sharing a process — or a lease set
        — never read each other's routed/rejected/failover numbers.
        ``done`` counts every request that ever finished here;
        ``done_held`` is the undelivered records currently retained."""
        return {"replicas": sorted(self._handles),
                "router_id": self._rid_ns,
                "pending": len(self._pending),
                "inflight": len(self._inflight),
                "done": len(self._done) + self._retired_count,
                "done_held": len(self._done),
                **dict(self._fleet_counts)}

    def close(self) -> None:
        """Release this instance's registry exports (the per-router
        serve.fleet.<c>.r_<id> gauges). The registry is process-global:
        a frontend loop that recreates routers without close() would
        accumulate dead routers' gauges in every snapshot forever."""
        for c in self._fleet_counts:
            metrics.remove_gauge(f"serve.fleet.{c}.r_{self._rid_ns}")
        if self._admin is not None:
            self._admin.stop()
            self._admin = None


def _transient_send(e: Exception) -> bool:
    """Routed-send classification — resilience.retry.classify applied to
    the router's HTTP sends: connection refused/reset, timeouts and wire
    noise are transient (the LEASE, not one exception, decides whether a
    replica is dead); a truncated JSON body is the same wire noise, and
    so is a connection dying MID-BODY (http.client.IncompleteRead /
    BadStatusLine are HTTPException, not OSError — a replica SIGKILLed
    while streaming a multi-MB /kv_blob frame must degrade to the
    re-prefill recovery, not crash the poll loop). urllib's HTTPError —
    a STATUS answer, which must surface — is re-raised by every caller
    before this classification runs. Everything else (a TypeError in
    our own code) must surface."""
    import http.client
    return isinstance(e, (json.JSONDecodeError,
                          http.client.HTTPException)) or classify(e)


# ----------------------------------------------------------- fleet spawner

class ServingFleet:
    """Spawn N replica PROCESSES over one FileRegistry and route to them.

        fleet = ServingFleet(3, spec, root=tmpdir).start()
        router = fleet.router()
        rid = router.submit(prompt, 16); router.wait()
        fleet.shutdown()

    The kill drill's and serving_bench's harness: every replica builds
    identical weights from `spec` (see replica.build_batcher), logs to
    <root>/<name>.log, and is reaped on shutdown. ``kill()`` SIGKILLs one
    replica (death is detected by lease expiry, nothing is told).

    Disaggregation (ISSUE 11): ``n_prefill > 0`` spawns a MIXED fleet —
    the first ``n_prefill`` replicas run ``--role prefill`` (the prompt
    pool) and the remaining ``n - n_prefill`` run ``--role decode``;
    ``router()`` then returns a ``DisaggRouter`` that drives the
    two-stage lifecycle. ``n_prefill == 0`` (default) spawns the classic
    unified fleet, byte-identical to the pre-disagg behavior.

    Replicated registry (ISSUE 12): ``registry_endpoint`` (one
    ``host:port``, or a comma-separated peer list) replaces the shared
    FileRegistry with the HTTP registry — a LIST makes every lease and
    routing-table read go through the quorum client, so killing any
    single registry peer costs a client-side failover, not the fleet."""

    def __init__(self, n: int, spec: dict, root: str,
                 job_id: str = "serve-fleet", ttl: float = 1.5,
                 host: str = "127.0.0.1", env: dict | None = None,
                 n_prefill: int = 0, registry_endpoint: str = ""):
        self.spec = dict(spec)
        self.root, self.job_id, self.ttl, self.host = root, job_id, ttl, host
        self.registry_endpoint = registry_endpoint
        # replica logs land under root either way; only the FileRegistry
        # used to create it as a side effect
        os.makedirs(root, exist_ok=True)
        if registry_endpoint:
            from ..distributed.fleet.replicated_kv import make_registry
            self.registry = make_registry(registry_endpoint, ttl=ttl)
        else:
            self.registry = FileRegistry(root, job_id, ttl=ttl)
        self._env = {**os.environ, **(env or {})}
        self._procs: dict[str, subprocess.Popen] = {}
        self._logs: dict[str, str] = {}
        self.n_prefill = int(n_prefill)
        if not 0 <= self.n_prefill <= n:
            raise ValueError(f"n_prefill={n_prefill} outside [0, {n}]")
        if self.n_prefill == n and n > 0:
            raise ValueError("an all-prefill fleet can never stream "
                             "tokens — leave at least one decode replica")
        self._names = [f"r{i}" for i in range(n)]
        self._roles = {name: ("prefill" if self.n_prefill and i < self.n_prefill
                              else "decode" if self.n_prefill
                              else "unified")
                       for i, name in enumerate(self._names)}
        self._spawn_extra: dict[str, list] = {}  # per-replica CLI extras

    def start(self, timeout: float = 60.0) -> "ServingFleet":
        for name in self._names:
            self.spawn(name)
        self.wait_ready(len(self._names), timeout=timeout)
        return self

    def spawn(self, name: str) -> subprocess.Popen:
        log_path = os.path.join(self.root, f"{name}.log")
        self._logs[name] = log_path
        log = open(log_path, "w")
        role = self._roles.get(name, "unified")
        if self.registry_endpoint:
            reg_args = ["--registry-endpoint", self.registry_endpoint]
        else:
            reg_args = ["--registry-root", self.root]
        extra = list(self._spawn_extra.get(name, ()))
        if self._env.get("PADDLE_WARMSTART") == "1" \
                and "--cache-dir" not in extra:
            # warm-started fleets give every replica its OWN persistent
            # jit cache dir — donors populate theirs during warmup, a
            # scale-out fetches a donor's into its own
            extra += ["--cache-dir",
                      os.path.join(self.root, f"{name}.jitcache")]
        proc = subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.inference.replica",
             "--name", name, "--spec", json.dumps(self.spec),
             *reg_args, "--job-id", self.job_id,
             "--ttl", str(self.ttl), "--host", self.host,
             "--role", role, *extra],
            stdout=log, stderr=subprocess.STDOUT, cwd=_REPO_ROOT,
            env=self._env)
        log.close()  # the child holds the fd
        self._procs[name] = proc
        return proc

    def wait_ready(self, n: int, timeout: float = 60.0):
        """Until n leases are present. A replica dying during warmup fails
        fast with its log tail instead of a timeout."""
        deadline = _slo.now() + timeout
        while True:
            alive = [x for x in self.registry.alive_nodes()
                     if x.startswith(REPLICA_PREFIX)]
            if len(alive) >= n:
                return
            for name, p in self._procs.items():
                if p.poll() is not None:
                    raise RuntimeError(
                        f"replica {name} died during warmup "
                        f"(rc={p.returncode}):\n{self.log_tail(name)}")
            if _slo.now() > deadline:
                raise TimeoutError(
                    f"fleet not ready: {len(alive)}/{n} leases after "
                    f"{timeout}s")
            time.sleep(0.05)

    def log_tail(self, name: str, nbytes: int = 3000) -> str:
        try:
            with open(self._logs[name]) as f:
                return f.read()[-nbytes:]
        except OSError:
            return "<no log>"

    def router(self, **kw) -> Router:
        if self.n_prefill > 0:
            # lazy import: disagg.coordinator subclasses Router, so a
            # module-level import here would be a cycle
            from .disagg.coordinator import DisaggRouter
            return DisaggRouter(self.registry, **kw)
        return Router(self.registry, **kw)

    def kill(self, name: str, sig: int = 9):
        self._procs[name].send_signal(sig)

    # ------------------------------------------- autoscale actuators (16)
    def add_replica(self, name: str | None = None, role: str = "unified",
                    warm_from: str = "") -> str:
        """Scale-out actuator: spawn ONE new replica into the running
        fleet. ``warm_from`` (a live peer's host:port) rides to the
        child as ``--warm-from`` so it fetches the jit cache + weights
        instead of compiling cold. Returns the replica name; its lease
        appearing in the registry is the ready signal."""
        if name is None:
            i = 0
            while f"r{i}" in self._roles:
                i += 1
            name = f"r{i}"
        if name in self._procs and self._procs[name].poll() is None:
            raise ValueError(f"replica {name} is already running")
        if name not in self._names:
            self._names.append(name)
        self._roles[name] = role
        if warm_from:
            self._spawn_extra[name] = ["--warm-from", warm_from]
        else:
            self._spawn_extra.pop(name, None)
        self.spawn(name)
        return name

    def reap(self, name: str, timeout: float = 5.0) -> int | None:
        """Scale-in collector: wait for a DRAINED replica's process to
        exit and forget it. Never signals — the drain protocol owns the
        exit; a process that hasn't exited yet answers None and the
        controller retries next window."""
        p = self._procs.get(name)
        if p is None:
            return None
        rc = p.poll()
        if rc is None:
            try:
                rc = p.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                return None
        self._procs.pop(name, None)
        self._spawn_extra.pop(name, None)
        if name in self._names:
            self._names.remove(name)
        self._roles.pop(name, None)
        return rc

    def replica_id(self, name: str) -> str:
        return REPLICA_PREFIX + name

    def shutdown(self):
        for p in self._procs.values():
            if p.poll() is None:
                p.kill()
        for p in self._procs.values():
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
