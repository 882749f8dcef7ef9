"""One serving replica: a ContinuousBatcher behind HTTP, held by a lease.

The fleet runtime (ISSUE 9) runs N of these — each its own PROCESS
(``python -m paddle_tpu.inference.replica``), each optionally
GSPMD-sharded across its own devices — behind ``inference/router.py``.
A replica is three things bolted onto one batcher:

  * **an HTTP face** — the sanctioned AdminServer (lint O3) extended with
    POST ``/enqueue`` (body ``{rid, prompt, max_new_tokens, trace_id,
    force, deadline_left_s}``; 200 admits, 429 carries the computed
    ``retry_after_s``), GET ``/results?since=N`` (finished outputs after
    cursor N — the router polls, nothing pushes), POST ``/cancel``
    (cooperative cancellation by rid, ISSUE 19), POST ``/drain``, and the
    readiness ``/health`` (ready / draining / queue depth / free pages —
    the one probe endpoint a router or external LB needs);
  * **a lease** — a heartbeat under ``serve.<id>`` into the SAME elastic
    registry (FileRegistry / KVServer) training uses for membership, TTL'd
    so a SIGKILL'd replica leaves the routing table within one TTL with no
    extra machinery;
  * **a serve loop** — the ONE thread that owns the batcher (the scheduler
    is not thread-safe by design); HTTP handler threads only touch the
    intake/results buffers under ``self._lk``, and the loop moves intake →
    ``add_request`` → ``step()`` → results between bursts.

Admission happens at the HTTP boundary (AdmissionPolicy against intake +
queue depth and the local SLO histograms) so a 429 is computed WITHOUT
waiting for the serve loop; ``force`` (router failover re-enqueues of
already-accepted work) bypasses the policy — the batcher's newest-first
shed valve bounds the queue even then.

Drain protocol: ``/drain`` (or SIGTERM) → finish every accepted request,
429 new admits with retry-after, deregister the lease, keep answering
``/results`` until the router has collected everything, exit 0. Past
``PADDLE_DRAIN_GRACE_S`` the still-queued remainder is shed (reason
"shed" — the router re-routes it); in-flight slots always run to their
budget.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading
from collections import deque

from ..distributed.fleet.elastic import FileRegistry
from ..distributed.resilience import chaos
from ..observability import metrics, recorder as _recorder, \
    reqtrace as _reqtrace, slo as _slo
from ..observability.admin import AdminServer
from ..utils import env_flags
from .admission import AdmissionPolicy, AdmissionReject, \
    reject as _admission_reject, retry_after_floor, slo_hists
from .serving import ContinuousBatcher

__all__ = ["ReplicaServer", "REPLICA_PREFIX", "ROLES", "build_batcher",
           "main"]

# registry node ids of serving replicas: "serve.<replica name>" — the
# router discovers the fleet by this prefix in the shared alive set
REPLICA_PREFIX = "serve."

# declared (defaults + docs) in utils/env_flags.py
ENV_TTL = "PADDLE_SERVE_TTL"
ENV_HEARTBEAT = "PADDLE_SERVE_HEARTBEAT_S"
ENV_DRAIN_GRACE = "PADDLE_DRAIN_GRACE_S"
ENV_RESULTS_KEEP = "PADDLE_SERVE_RESULTS_KEEP"
ENV_ROLE = "PADDLE_SERVE_ROLE"

# replica roles (ISSUE 11): advertised in the lease payload and /health so
# the router's candidate selection can filter by stage. "unified" is the
# pre-disagg replica (prefills AND decodes) — every single-pool deployment
# keeps it implicitly, so routing behavior is unchanged with the flag
# unset. "prefill" runs prompt passes and exports pages; "decode" installs
# transferred pages and streams tokens.
ROLES = ("unified", "prefill", "decode")

# exported KV frames retained for router pickup (multi-MB each, so the
# bound is count-based and small; an evicted frame's request re-prefills)
_KV_FRAME_KEEP = 32


def normalize_role(raw) -> str:
    """''/None mean "unified"; anything else must name a role — a typo'd
    PADDLE_SERVE_ROLE must not silently deploy a unified replica into a
    pool the router believes is specialized."""
    v = (raw or "").strip().lower()
    if not v:
        return "unified"
    if v not in ROLES:
        raise ValueError(f"unknown replica role {v!r} (one of {ROLES})")
    return v


class ReplicaServer:
    """rep = ReplicaServer(batcher, registry, "r0").start(); rep.endpoint

    Owns the batcher's serve loop, the admin HTTP face, and the lease
    heartbeat. ``stop()`` kills it hard (tests); ``begin_drain()`` runs
    the drain protocol and lets the loop exit clean."""

    def __init__(self, batcher: ContinuousBatcher, registry, name: str,
                 host: str = "127.0.0.1", port: int = 0,
                 heartbeat_s: float | None = None,
                 drain_grace_s: float | None = None,
                 role: str | None = None, warm=None,
                 lease_extra: dict | None = None):
        self._b = batcher
        self._registry = registry
        self._warm = warm  # WarmStartCache | None (ISSUE 16 donor side)
        self._lease_extra = dict(lease_extra or {})
        self.role = normalize_role(role if role is not None
                                   else env_flags.get(ENV_ROLE))
        self.replica_id = (name if name.startswith(REPLICA_PREFIX)
                           else REPLICA_PREFIX + name)
        ttl = getattr(registry, "ttl", env_flags.get_float(ENV_TTL))
        self._hb_s = (heartbeat_s if heartbeat_s is not None
                      else max(0.05, env_flags.get_float(ENV_HEARTBEAT)
                               or ttl / 4.0))
        self._drain_grace = (drain_grace_s if drain_grace_s is not None
                             else env_flags.get_float(ENV_DRAIN_GRACE))
        self._lk = threading.Lock()
        # (rid, prompt, mnt, trace_id, force, router-namespace,
        #  prefill_only, kv, deadline) — deadline is the ABSOLUTE local
        # expiry on the slo.now() clock (None = none), fixed at the HTTP
        # boundary so serve-loop lag never stretches the budget
        self._intake: deque = deque()
        # cancels for rids already past intake ((router ns, rid)): the
        # handler marks under _lk, the serve loop resolves the local rid
        # and routes it through the batcher's lifecycle pass (ISSUE 19)
        self._pending_cancels: list = []
        # finished results, cursor-addressed: the wire cursor for
        # _results[i] is _results_base + i. The prefix every poller has
        # had PADDLE_SERVE_RESULTS_KEEP results' worth of polls to collect
        # is truncated (base advances) so a replica serving steady traffic
        # for days holds a BOUNDED result tail, not every token it ever
        # emitted; a draining replica never truncates (its drained answer
        # promises the slice is complete)
        self._results: list[dict] = []
        self._results_base = 0
        self._results_keep = int(env_flags.get_float(ENV_RESULTS_KEEP))
        # exported KV page frames (disagg, ISSUE 12 binary wire): the
        # prefilled RESULT carries only the blob's JSON-able meta; the
        # multi-MB payload stays here, packed once, and the router pulls
        # it through GET /kv_blob as one raw octet-stream frame (no
        # base64, no JSON escaping). Bounded: a router that never
        # fetched within _KV_FRAME_KEEP exports re-prefills (404 → the
        # established recovery), which bounds replica RSS the same way
        # results retention does.
        self._kv_frames: dict[tuple, bytes] = {}
        self._kv_frame_order: deque = deque()
        self._active: set = set()       # (router ns, rid) queued/in flight
        self._draining = False
        self._drain_t0: float | None = None
        self._drained_flag = False  # set by the serve loop AFTER its final
        #                             _collect(), so /results never reports
        #                             drained with a result still unpushed
        self._stop = threading.Event()
        self.crash: BaseException | None = None  # serve-loop death, if any
        self._rid_map: dict[int, tuple] = {}  # local rid -> (router rid, tid)
        # distributed request tracing (ISSUE 17): the engine tracker hands
        # every retire's span payload to this buffer; batches piggy-back
        # on /results records (chaos site trace.push gates the ship) with
        # /trace_pull as the cursor-addressed fallback. PADDLE_REQTRACE=0
        # leaves the sink unset — spans are then never built.
        self._tracebuf = _reqtrace.ReplicaSpanBuffer(self.replica_id,
                                                     role=self.role)
        slo_tracker = getattr(batcher, "slo", None)  # stubs have no slo
        if _reqtrace.enabled() and slo_tracker is not None:
            slo_tracker.trace_sink = self._tracebuf.publish
        self._admin = AdminServer(
            port=port, host=host,
            extra={"serve": batcher.admin_summary, "replica": self.summary},
            health=self._health,
            get_routes={"/results": self._h_results,
                        "/kv_blob": self._h_kv_blob,
                        "/trace_pull": self._h_trace_pull,
                        "/warm_cache": self._h_warm_cache,
                        "/weights": self._h_weights},
            post_routes={"/enqueue": self._h_enqueue,
                         "/kv_transfer": self._h_kv_transfer,
                         "/cancel": self._h_cancel,
                         "/drain": self._h_drain})
        self.port = self._admin.port
        self.endpoint = f"http://{host}:{self.port}"
        self._threads: list[threading.Thread] = []

    # ------------------------------------------------------------ lifecycle
    def start(self) -> "ReplicaServer":
        # first heartbeat is synchronous: the lease exists before start()
        # returns, so a spawner can wait on the registry, not on logs
        self._registry.heartbeat(self.replica_id, self._lease_info())
        self._admin.start()
        for fn in (self._beat, self._loop):
            t = threading.Thread(target=fn, daemon=True)
            t.start()
            self._threads.append(t)
        return self

    def begin_drain(self):
        with self._lk:
            if not self._draining:
                self._draining = True
                self._drain_t0 = _slo.now()
        self._b.begin_drain()

    def join(self, timeout: float | None = None) -> bool:
        """Wait for the serve loop to exit (drain complete or stop())."""
        self._threads[1].join(timeout)
        return not self._threads[1].is_alive()

    def stop(self):
        """Hard stop (tests/teardown): no drain, lease left to lapse."""
        self._stop.set()
        self.join(5.0)
        self._admin.stop()

    def _lease_info(self) -> dict:
        info = {"endpoint": self.endpoint, "pid": os.getpid(),
                "max_batch": self._b.B, "role": self.role}
        # warm-start/rejoin breadcrumbs (ISSUE 16): ready_s, warm, gen —
        # the autoscale controller reads these off the lease it was
        # already watching, no extra probe
        info.update(self._lease_extra)
        return info

    # ------------------------------------------------------- HTTP handlers
    def _health(self) -> dict:
        doc = self._b.health_summary()
        with self._lk:
            doc["queue_depth"] += len(self._intake)
            doc["draining"] = doc["draining"] or self._draining
            doc["ready"] = doc["ready"] and not self._draining
        doc["replica"] = self.replica_id
        doc["role"] = self.role
        return doc

    def summary(self) -> dict:
        with self._lk:
            return {"replica": self.replica_id, "endpoint": self.endpoint,
                    "role": self.role,
                    "intake": len(self._intake),
                    "results": len(self._results),
                    "draining": self._draining}

    def _h_enqueue(self, body: dict):
        """POST /enqueue — the admission boundary. Decided HERE, in the
        handler thread, against intake+queue depth and the local SLO
        histograms; the serve loop is never waited on, so a 429 costs one
        round trip even mid-burst."""
        try:
            rid = int(body["rid"])
            prompt = [int(t) for t in body["prompt"]]
            mnt = int(body.get("max_new_tokens", 32))
        except (KeyError, TypeError, ValueError) as e:
            return 400, {"ok": False, "reason": f"bad request: {e}"}
        tid = body.get("trace_id")
        force = bool(body.get("force"))
        rtr = body.get("router")
        po = bool(body.get("prefill_only"))
        try:
            dl = body.get("deadline_left_s")
            dl = None if dl is None else float(dl)
        except (TypeError, ValueError) as e:
            return 400, {"ok": False, "reason": f"bad deadline: {e}"}
        try:
            # never-admissible requests (over-budget, impossible page
            # demand) are refused HERE with a 400 — BEFORE any retryable
            # rejection (accepting one would turn the serve loop's
            # add_request ValueError into a silent empty result, and a
            # 429 would have an honoring client resubmit the impossible
            # request forever); reads only immutable engine config
            self._b.check_admissible(prompt, mnt)
        except ValueError as e:
            return 400, {"ok": False, "reason": f"invalid: {e}"}
        pol = self._b.admission
        # the slo_hists FUNCTION, not its result: decide() evaluates it
        # at most once and only when a decision consumes it (configured
        # latency threshold, or a rejection's retry-after), so the common
        # admit costs zero reservoir sorts; when it IS consumed the sorts
        # run under _lk, acceptable because rejection is not the
        # steady-state path and the two reservoirs are bounded
        hists = (slo_hists if pol is not None and not force else None)
        with self._lk:
            if rtr is not None and (rtr, rid) in self._active:
                # idempotent accept: a send whose response was lost after
                # the enqueue landed is retried by the router — while the
                # first copy is still queued/in flight the retry must NOT
                # start a second generation. Only namespaced (router)
                # senders get dedup: a bare client's rids carry no
                # cross-send identity
                return 200, {"ok": True, "rid": rid, "dedup": True,
                             "replica": self.replica_id}
            if self._draining and (not force or self._drained_flag):
                # force (router failover of already-accepted work) is
                # honored during drain — same contract as add_request —
                # but only while the serve loop is still alive to run it
                # (_drained_flag flips atomically with the loop's exit
                # decision under this lock, so an accept here is GUARANTEED
                # to be seen by the loop's next drained check)
                return self._reject_429("draining", retry_after_floor())
            if pol is not None and not force:
                depth = len(self._intake) + self._b.health_summary()[
                    "queue_depth"]
                d = pol.decide(depth, self._b.B, hists=hists)
                if d is None:
                    # deadline shedding (ISSUE 19): a remaining budget
                    # provably unmeetable here — below this pool's
                    # observed TTFT floor — is refused at the wire
                    # instead of burning a prefill it can never deliver
                    d = pol.decide_deadline(dl, hists=hists)
                if d is not None:
                    return self._reject_429(d["reason"],
                                            d["retry_after_s"])
            self._intake.append((rid, prompt, mnt, tid, force, rtr, po,
                                 None,
                                 None if dl is None else _slo.now() + dl))
            self._active.add((rtr, rid))
        return 200, {"ok": True, "rid": rid, "replica": self.replica_id}

    def _h_kv_blob(self, query: dict):
        """GET /kv_blob?rid=N[&router=ns][&from_page=k] — one exported
        page frame as a raw octet-stream (ISSUE 12 binary wire). 404
        once evicted: the router's established answer to a lost blob is
        re-prefill. ``from_page`` (ISSUE 14 satellite) slices the frame
        SERVER-SIDE to pages [k, n): the router probed the decode pool's
        prefix cache first, so pages the destination already holds never
        cross this hop either — the prefill→router leg stops hauling
        bytes the router would immediately slice away."""
        try:
            rid = int(query.get("rid", [""])[0])
        except (ValueError, IndexError):
            return 400, {"ok": False, "reason": "rid=N required"}
        rtr = (query.get("router") or [None])[0]
        try:
            k = int((query.get("from_page") or ["0"])[0])
        except ValueError:
            return 400, {"ok": False,
                         "reason": "from_page must be an integer"}
        with self._lk:
            frame = self._kv_frames.get((rtr, rid))
        if frame is None:
            return 404, {"ok": False, "reason": "no frame for rid "
                                                f"{rid} (evicted or "
                                                "never exported)"}
        if k > 0:
            from .disagg.transfer import (blob_meta, pack_frame,
                                          slice_blob, unpack_frame)
            try:
                header, payload = unpack_frame(frame)
                blob = dict(header.get("kv") or {})
                blob["data"] = payload
                sliced = slice_blob(blob, k)
                frame = pack_frame({"kv": blob_meta(sliced)},
                                   sliced["data"])
            except (ValueError, KeyError) as e:
                # an over-slice (k past the tail page) is a router logic
                # bug, not capacity — answer loudly, never a torn frame
                return 400, {"ok": False, "reason": f"bad slice: {e}"}
        return 200, frame

    def _h_warm_cache(self, query: dict):
        """GET /warm_cache?spec=<hash> — warm-start donor (ISSUE 16):
        this replica's jit executable cache as one tar frame. 404 when
        warm start is disabled here (no WarmStartCache wired) — the
        fetcher's cold-path fallback, same as a spec mismatch."""
        if self._warm is None:
            return 404, {"ok": False,
                         "reason": "warm start disabled on this replica "
                                   "(PADDLE_WARMSTART=0)"}
        return self._warm.handle_warm_cache(query)

    def _h_weights(self, query: dict):
        """GET /weights?spec=<hash> — the donor's params pytree as one
        npz frame; 404 when warm start is disabled here."""
        if self._warm is None:
            return 404, {"ok": False,
                         "reason": "warm start disabled on this replica "
                                   "(PADDLE_WARMSTART=0)"}
        return self._warm.handle_weights(query)

    def _h_kv_transfer(self, body):
        """POST /kv_transfer — the disagg page-transfer boundary (ISSUE
        11): a prefilled request arrives WITH its KV pages (the wire blob
        disagg.transfer serialized) and enters the queue as a kv_import
        admit — no prefill ever runs here. Admission gains the SECOND
        pressure dimension: besides queue depth, the pool itself — free
        pages minus pages already promised to queued transfers must cover
        this request's live pages, else 429 ``pool_pressure`` with the
        page-turnover retry hint (admission.decide_pages).

        Over HTTP the body is one length-prefixed BINARY frame (ISSUE 12
        satellite: header JSON + raw payload, no base64); in-process
        callers may still hand the blob dict directly."""
        if isinstance(body, (bytes, bytearray, memoryview)):
            from .disagg.transfer import unpack_frame
            try:
                body, payload = unpack_frame(body)
                body["kv"] = dict(body.get("kv") or {})
                body["kv"]["data"] = payload
            except (ValueError, TypeError) as e:
                return 400, {"ok": False, "reason": f"bad frame: {e}"}
        if body.get("probe"):
            # prefix probe (ISSUE 13): how many leading prompt pages THIS
            # pool's prefix cache could supply a sliced transfer. A tiny
            # JSON round trip — advisory (admit re-matches under the
            # cache lock); never touches intake, dedup, or admission
            try:
                prompt = [int(t) for t in body["prompt"]]
            except (KeyError, TypeError, ValueError) as e:
                return 400, {"ok": False, "reason": f"bad probe: {e}"}
            if self.role == "prefill":
                return 400, {"ok": False,
                             "reason": "invalid: prefill pool takes no "
                                       "transfers"}
            return 200, {"ok": True,
                         "from_page": int(self._b.prefix_probe(prompt)),
                         "replica": self.replica_id}
        try:
            rid = int(body["rid"])
            prompt = [int(t) for t in body["prompt"]]
            mnt = int(body.get("max_new_tokens", 32))
            kv = dict(body["kv"])
            int(kv["tlen"]), int(kv["first"])  # shape of a transfer blob
        except (KeyError, TypeError, ValueError) as e:
            return 400, {"ok": False, "reason": f"bad transfer: {e}"}
        tid = body.get("trace_id")
        force = bool(body.get("force"))
        rtr = body.get("router")
        try:
            dl = body.get("deadline_left_s")
            dl = None if dl is None else float(dl)
        except (TypeError, ValueError) as e:
            return 400, {"ok": False, "reason": f"bad deadline: {e}"}
        if self.role == "prefill":
            # a misdirected transfer (stale role view, misconfigured
            # router) is refused AT the wire like every other
            # never-installable request — accepting it would only retire
            # as a terminal error on the serve loop (a prefill replica
            # forces prefill_only on every admit, which excludes
            # kv_import)
            return 400, {"ok": False,
                         "reason": "invalid: this replica is the PREFILL "
                                   "pool — transfers install on decode/"
                                   "unified replicas"}
        try:
            self._b.check_admissible(prompt, mnt)
            # geometry/byte-count validation HERE, with a 400 — a drifted
            # or truncated blob must be refused at the wire, not crash
            # the serve loop (and with it every other in-flight request)
            # at install time
            need = self._b.check_kv_blob(kv)
            if int(kv["tlen"]) != len(prompt):
                raise ValueError(
                    f"blob holds {kv['tlen']} prompt positions, request "
                    f"prompt has {len(prompt)}")
        except ValueError as e:
            return 400, {"ok": False, "reason": f"invalid: {e}"}
        pol = self._b.admission
        hists = (slo_hists if pol is not None and not force else None)
        with self._lk:
            if rtr is not None and (rtr, rid) in self._active:
                # idempotent accept — the ambiguous-send dedup contract
                # /enqueue keeps, extended to the transfer boundary (a
                # re-POSTed blob must not install twice)
                return 200, {"ok": True, "rid": rid, "dedup": True,
                             "replica": self.replica_id}
            if self._draining and (not force or self._drained_flag):
                return self._reject_429("draining", retry_after_floor())
            if pol is not None and not force:
                health = self._b.health_summary()
                depth = len(self._intake) + health["queue_depth"]
                d = pol.decide(depth, self._b.B, hists=hists)
                if d is None and health["free_pages"] is not None:
                    # pages already promised: the batcher queue's tally
                    # PLUS blobs still sitting in OUR intake (the queue
                    # dimension counts intake the same way) — two routers
                    # posting into one step must not both pass on the
                    # same free-page snapshot. Idle prefix-cache pages
                    # (ISSUE 13) count as free: reclaim turns them into
                    # free pages before any admit would stall on them
                    intake_kv = sum(
                        int(e[7].get("n_pages", 0) or 0)
                        for e in self._intake if e[7] is not None)
                    free = (health["free_pages"]
                            + health.get("evictable_pages", 0)
                            - health["queued_kv_pages"] - intake_kv)
                    d = pol.decide_pages(free, need, hists=hists)
                if d is None:
                    d = pol.decide_deadline(dl, hists=hists)
                if d is not None:
                    return self._reject_429(d["reason"],
                                            d["retry_after_s"])
            self._intake.append((rid, prompt, mnt, tid, force, rtr, False,
                                 kv,
                                 None if dl is None else _slo.now() + dl))
            self._active.add((rtr, rid))
        return 200, {"ok": True, "rid": rid, "replica": self.replica_id}

    def _reject_429(self, reason: str, retry_after_s: float):
        """Route the HTTP rejection through admission.reject — the ONE
        rejection exit — so the serve.reject chaos site and the
        serve.rejected counter cover this boundary too; the raise is
        translated back to the wire 429 here."""
        metrics.counter("serve.replica.rejected").inc()
        try:
            _admission_reject(reason, retry_after_s)
        except AdmissionReject as e:
            return 429, {"ok": False, "reason": e.reason,
                         "retry_after_s": e.retry_after_s}

    def _h_results(self, query: dict):
        """GET /results?since=N — finished outputs after cursor N.
        Cursors are monotone over the replica's lifetime; the retained
        list may have a truncated prefix (bounded retention), so position
        N lives at list index N - base. A ``since`` behind the base gets
        the oldest retained results plus the base, so a lagging poller
        can SEE it missed some instead of silently resyncing."""
        try:
            since = int(query.get("since", ["0"])[0])
        except ValueError:
            since = 0
        with self._lk:
            # drained is read in the SAME lock snapshot as the results
            # slice: the serve loop only sets the flag after its final
            # _collect(), so drained=true implies this slice is complete
            # (a router deletes a drained handle — a result published
            # after a drained answer would be lost forever; truncation is
            # disabled while draining for the same reason)
            base = self._results_base
            out = self._results[max(0, since - base):]
            cursor = base + len(self._results)
            draining = self._draining
            drained = self._drained_flag
        doc = {"results": out, "cursor": cursor, "base": base,
               "draining": draining, "drained": drained,
               "replica": self.replica_id}
        if _reqtrace.enabled():
            # clock anchor stamped at RESPONSE time (not publish time):
            # the router's minimum-filter offset estimate needs t_send ≈
            # the moment the bytes leave, not when the batch was queued
            doc["trace_clock"] = _reqtrace.clock_anchor()
        return 200, doc

    def _h_trace_pull(self, query: dict):
        """GET /trace_pull?cursor=N — the retained retired-request span
        batches after cursor N (ISSUE 17 fallback for a lost /results
        piggy-back). Same cursor/base semantics as /results: a cursor
        behind the base gets the oldest retained batches plus the base."""
        try:
            cursor = int(query.get("cursor", ["0"])[0])
        except ValueError:
            return 400, {"ok": False, "reason": "cursor must be an integer"}
        return 200, self._tracebuf.pull(cursor)

    def _h_drain(self, body: dict):
        self.begin_drain()
        return 200, {"ok": True, "draining": True,
                     "pending": self._b.pending}

    def _h_cancel(self, body: dict):
        """POST /cancel — cooperative cancellation by rid (ISSUE 19).
        Still in intake → dropped here (typed "cancelled" result, the
        active-set entry released); already with the batcher → marked
        for the serve loop, which resolves the local rid and routes it
        through the engine's lifecycle pass (queued dropped, in-slot
        retired with partial output and pages freed, parked pages
        dropped). A rid this replica no longer holds is a NO-OP answer,
        not an error: cancel racing retire loses cleanly, so fleet
        accounting stays exactly-once."""
        try:
            rid = int(body["rid"])
        except (KeyError, TypeError, ValueError) as e:
            return 400, {"ok": False, "reason": f"bad cancel: {e}"}
        rtr = body.get("router")
        dropped = None
        with self._lk:
            entry = next((e for e in self._intake
                          if e[0] == rid and e[5] == rtr), None)
            if entry is not None:
                try:
                    chaos.hit("request.cancel")
                except chaos.ChaosError:
                    # fault = this cancel is dropped; the request runs on
                    # and retires normally (best-effort contract, same as
                    # the engine-side gate — tokens never change)
                    return 200, {"ok": True, "rid": rid,
                                 "state": "deferred",
                                 "replica": self.replica_id}
                self._intake.remove(entry)
                self._active.discard((rtr, rid))
                dropped = entry
                state = "intake"
            elif (rtr, rid) in self._active:
                self._pending_cancels.append((rtr, rid))
                state = "marked"
            else:
                state = "unknown"
        if dropped is not None:
            # the typed result publishes OUTSIDE _lk (_push_result takes
            # the lock itself); the request never reached the batcher, so
            # this is its one retire record
            metrics.counter("serve.cancelled").inc()
            self._push_result(rid, dropped[3], rtr, [], "cancelled")
        return 200, {"ok": True, "rid": rid, "state": state,
                     "replica": self.replica_id}

    @property
    def drained(self) -> bool:
        """ONE definition of drained, shared with /results: the flag the
        serve loop sets only AFTER its final collect. Deriving it from
        intake/pending here would re-open the hardened race (True in the
        window between the last step() and _collect(), with the final
        result still unpublished)."""
        with self._lk:
            return self._drained_flag

    # ---------------------------------------------------------- serve loop
    def _beat(self):
        info = self._lease_info()
        while not self._stop.wait(self._hb_s):
            with self._lk:
                if self._draining:
                    return  # the loop deregisters; stop renewing the lease
            try:
                self._registry.heartbeat(self.replica_id, info)
                with self._lk:
                    draining = self._draining
                if draining:
                    # drain began while that heartbeat was in flight —
                    # it may have landed AFTER the serve loop's leave()
                    # and resurrected the lease (the drained replica
                    # would then absorb routing attempts for a full
                    # TTL). Deregister again; leave is idempotent.
                    try:
                        self._registry.leave(self.replica_id)
                    except Exception:
                        pass
                    return
            except Exception as e:
                # a registry blip must not kill serving; the TTL is the
                # arbiter — if blips outlast it, the router fails us over
                _recorder.record("serve.replica.heartbeat_error",
                                 replica=self.replica_id,
                                 error=f"{type(e).__name__}: {e}")

    def _loop(self):
        try:
            self._run_loop()
        except Exception as e:
            # the serve loop dying must NOT leave a zombie: the heartbeat
            # thread would keep renewing the lease and the HTTP face would
            # keep accepting, so the router would route to a replica that
            # can never serve and failover would never fire. Tear down the
            # failure-detector inputs instead — deregister, stop the admin
            # (unreachable /results is what lets the router declare death
            # and fail our in-flight work over), and stop the heartbeat.
            _recorder.record("serve.replica.loop_crash", echo=True,
                             message=f"[serve] replica {self.replica_id} "
                                     f"serve loop died: "
                                     f"{type(e).__name__}: {e}",
                             replica=self.replica_id,
                             error=f"{type(e).__name__}: {e}")
            self.crash = e      # main() turns this into a nonzero exit
            self._stop.set()
            try:
                self._registry.leave(self.replica_id)
            except Exception:
                pass
            try:
                self._admin.stop()
            except Exception:
                pass
            # no re-raise: the flight record above (echo=True) already
            # carries the story to stderr/logs; an unhandled daemon-thread
            # exception would only add noise on top of the teardown

    def _run_loop(self):
        deregistered = False
        while not self._stop.is_set():
            with self._lk:
                moved = list(self._intake)
                self._intake.clear()
                cancels = list(self._pending_cancels)
                self._pending_cancels.clear()
                draining = self._draining
                drain_t0 = self._drain_t0
            for rid, prompt, mnt, tid, force, rtr, po, kv, dl in moved:
                try:
                    # admission already happened at the HTTP boundary —
                    # force=True here so the policy isn't double-applied.
                    # A prefill replica treats EVERY admit as prefill_only
                    # (its pool exists to run prompt passes, not to hold
                    # decode streams a router never asked it for).
                    local = self._b.add_request(
                        prompt, mnt, trace_id=tid, force=True,
                        prefill_only=po or self.role == "prefill",
                        kv_import=kv,
                        deadline_s=(None if dl is None
                                    else dl - _slo.now()))
                except Exception as e:
                    self._push_result(rid, tid, rtr, [],
                                      f"error: {type(e).__name__}: {e}")
                    continue
                self._rid_map[local] = (rid, tid, rtr)
            # cancels resolve AFTER the intake move: a rid marked while
            # its tuple sat in `moved` has its local rid by now, so the
            # mark lands in the engine's lifecycle pass this very step
            for rtr_ns, rid in cancels:
                local = next((l for l, v in self._rid_map.items()
                              if v[0] == rid and v[2] == rtr_ns), None)
                if local is not None:
                    self._b.cancel(local)
            if draining and not deregistered:
                # reject-new is already live (the handler checks); now
                # leave the routing table so the router stops choosing us
                try:
                    self._registry.leave(self.replica_id)
                except Exception:
                    pass
                deregistered = True
            if draining and drain_t0 is not None \
                    and _slo.now() - drain_t0 > self._drain_grace:
                # grace exceeded: shed the still-QUEUED remainder (the
                # router re-routes it); in-flight slots run to budget
                self._b.shed_newest(
                    self._b.health_summary()["queue_depth"])
            if self._b.pending:
                self._b.step()
            self._collect()
            if draining:
                # atomic exit decision: the drained check and the flag
                # flip share one lock acquisition with /enqueue's accept,
                # so a force re-enqueue either lands BEFORE this check
                # (intake non-empty → the loop keeps serving) or is
                # rejected AFTER the flag flips — never accepted into a
                # loop that already decided to exit
                with self._lk:
                    if not self._intake and self._b.pending == 0:
                        self._drained_flag = True
                        break
            if not self._b.pending:
                self._stop.wait(0.003)  # idle: don't spin the scheduler
        with self._lk:
            clean = self._draining
        if clean:
            _recorder.record("serve.replica.drained", echo=True,
                             message=f"[serve] replica {self.replica_id} "
                                     "drained clean",
                             replica=self.replica_id)

    def _store_frame(self, key: tuple, frame: bytes):
        """Retain one exported KV frame under the count bound. A
        re-export of the SAME (router, rid) — a re-prefill that landed
        back here — overwrites in place without a second eviction-order
        entry: a duplicate deque key would otherwise evict the LIVE
        replacement frame when the stale entry aged out."""
        with self._lk:
            if key not in self._kv_frames:
                self._kv_frame_order.append(key)
            self._kv_frames[key] = frame
            while len(self._kv_frame_order) > _KV_FRAME_KEEP:
                old = self._kv_frame_order.popleft()
                self._kv_frames.pop(old, None)

    def _push_result(self, rid, tid, rtr, tokens, reason, kv=None):
        # the retire's span batch (published by the tracker sink moments
        # ago) rides OUT on the result record the router polls anyway —
        # no new hop. collect() runs the trace.push chaos gate OUTSIDE
        # self._lk; a faulted ship just means no "spans" key.
        batch = self._tracebuf.collect(tid)
        with self._lk:
            # the (router, rid) key leaves the active set in the same
            # lock acquisition that publishes the result: a shed request
            # re-routed back here must be accepted again, not deduped
            self._active.discard((rtr, rid))
            rec = {"rid": rid, "trace_id": tid, "router": rtr,
                   "tokens": list(tokens), "reason": reason,
                   # which replica produced it: a hedged pair's first
                   # terminal result names the WINNER, so the router can
                   # cancel the loser (ISSUE 19)
                   "replica": self.replica_id}
            if batch is not None:
                rec["spans"] = batch
            if kv is not None:
                # a prefilled request's exported pages ride OUT on the
                # result the router was polling for anyway — the transfer
                # needs no extra replica round trip, and the pool pages
                # were freed the moment this blob was serialized
                rec["kv"] = kv
            self._results.append(rec)
            keep = self._results_keep
            if keep > 0 and not self._draining \
                    and len(self._results) > keep:
                # bound the retained tail: a router polls every tick, so
                # lagging `keep` whole results behind means it long ago
                # declared us dead (or is gone); its loss is a timeout on
                # ITS side, not unbounded RSS on ours
                drop = len(self._results) - keep
                del self._results[:drop]
                self._results_base += drop

    def _collect(self):
        for local, req in self._b.take_finished().items():
            rid, tid, rtr = self._rid_map.pop(local,
                                              (local, req.trace_id, None))
            kv = None
            if req.reason == "prefilled":
                # serialize-and-free on THE thread that owns the batcher;
                # an export failure degrades to a shed (the router
                # re-routes it under the same trace id — re-prefilled,
                # never lost, never a half-written blob). The RESULT
                # carries only the blob meta; the payload is packed once
                # into a binary frame the router pulls via /kv_blob
                # (ISSUE 12: /results stays a small JSON doc instead of
                # hauling base64 megabytes on every poll)
                try:
                    blob = self._b.export_kv(local)
                    from .disagg.transfer import blob_meta, pack_frame
                    kv = blob_meta(blob)
                    frame = pack_frame({"kv": kv}, blob["data"])
                except Exception as e:
                    _recorder.record("serve.replica.export_error",
                                     replica=self.replica_id, rid=rid,
                                     error=f"{type(e).__name__}: {e}")
                    self._b.drop_parked(local)
                    self._push_result(rid, tid, rtr, [], "shed")
                    continue
                self._store_frame((rtr, rid), frame)
            self._push_result(rid, tid, rtr, req.out, req.reason, kv=kv)
            # completed means SERVED to budget: a shed (never served,
            # re-routed elsewhere) or an error result counted here would
            # make fleet-summed completions exceed the request count
            # exactly during the degradation events the counter is meant
            # to illuminate
            if req.reason == "complete":
                metrics.counter("serve.replica.completed").inc()


# ------------------------------------------------------------ process entry

def _spec_config(spec: dict):
    import jax.numpy as jnp

    from ..models.llama import LlamaConfig

    # the model spec as JSON carries it: LlamaConfig's own field names, a
    # dtype by name, a pattern (layer kinds, FFN kinds, the kinds that
    # rotate, the experts held) as a list
    ckw = dict(spec.get("config") or {})
    for key in ("dtype", "state_dtype"):
        if key in ckw:
            ckw[key] = jnp.dtype(ckw[key])
    for key in ("layer_types", "mlp_layer_types", "rope_layer_types",
                "experts_held"):
        if ckw.get(key) is not None:
            ckw[key] = tuple(ckw[key])
    return LlamaConfig(**ckw)


def build_params(spec: dict):
    """The seeded parameter pytree the spec describes — what every
    replica of the fleet serves. Warm start fetches these SAME values
    from a peer instead of initializing (bit-identical either way)."""
    import jax

    from ..models.llama import llama_init_params

    return llama_init_params(_spec_config(spec),
                             jax.random.PRNGKey(int(spec.get("seed", 0))))


def build_batcher(spec: dict, params=None) -> ContinuousBatcher:
    """A batcher from a JSON-able spec: {"config": {LlamaConfig kwargs,
    "dtype": "float32"}, "seed": 0, "batcher": {ContinuousBatcher kwargs}}.
    The config may state a layer pattern ("layer_types": full_attention /
    linear_attention / sliding_attention, with the linear layers' sizes or
    "sliding_window"; "qk_norm" / "qk_norm_per_head", "norm_placement",
    "rope_theta": null, "rope_layer_types") and FFN kinds
    ("mlp_layer_types": dense / sparse, with "num_experts",
    "num_experts_per_tok", "moe_intermediate_size", "num_shared_experts",
    "scoring_func", "norm_topk_prob", "routed_scaling_factor",
    "experts_held": [first, count]): the batcher then holds a recurrent
    state or a ring per slot beside the paged KV and runs the dropless
    expert layer over the experts held.
    Every replica of a fleet builds from the SAME spec, so weights are
    identical across replicas and a failover retry at temperature=0 is
    token-identical to the first attempt. ``params`` short-circuits the
    seeded init with an identical tree fetched from a peer (ISSUE 16
    warm start)."""
    cfg = _spec_config(spec)
    if params is None:
        params = build_params(spec)
    bkw = dict(spec.get("batcher") or {})
    bkw.setdefault("temperature", 0.0)
    if isinstance(bkw.get("prompt_buckets"), list):
        bkw["prompt_buckets"] = tuple(bkw["prompt_buckets"])
    return ContinuousBatcher(cfg, params, admission=AdmissionPolicy(),
                             **bkw)


def serve_warmup(batcher: ContinuousBatcher, role: str = "unified"):
    """Run one tiny request through the batcher BEFORE the lease
    registers: the replica's executables are compiled (or loaded from
    the warm cache) and a token has actually been served by the time the
    fleet can see the lease — "ready" means ready, not "will compile on
    your first request"."""
    po = role == "prefill"
    local = batcher.add_request([1, 2, 3], 2, force=True, prefill_only=po)
    while batcher.pending:
        batcher.step()
        for lid, req in batcher.take_finished().items():
            if req.reason == "prefilled":
                batcher.drop_parked(lid)
    batcher.take_finished()
    return local


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="serving replica process (ISSUE 9 fleet runtime)")
    p.add_argument("--name", required=True,
                   help="replica name (lease id = serve.<name>)")
    p.add_argument("--spec", required=True,
                   help="model/batcher spec JSON, or @/path/to/spec.json")
    p.add_argument("--registry-root", default="",
                   help="FileRegistry root directory")
    p.add_argument("--registry-endpoint", default="",
                   help="KVServer endpoint (host:port) instead of a root "
                        "dir; a comma-separated list is a replicated peer "
                        "set — leases then commit on a majority and the "
                        "heartbeat/refresh paths fail over between peers "
                        "(ISSUE 12)")
    p.add_argument("--job-id", default=os.environ.get("PADDLE_JOB_ID",
                                                      "default"))
    p.add_argument("--ttl", type=float,
                   default=env_flags.get_float(ENV_TTL))
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--role", default=env_flags.get(ENV_ROLE),
                   help="replica role: prefill | decode | unified "
                        "(default PADDLE_SERVE_ROLE, else unified)")
    p.add_argument("--cache-dir",
                   default=env_flags.get("PADDLE_WARMSTART_CACHE_DIR"),
                   help="persistent jit cache dir for this replica "
                        "(PADDLE_WARMSTART=1: populated locally, "
                        "exported via /warm_cache, installable from a "
                        "peer)")
    p.add_argument("--warm-from",
                   default=env_flags.get("PADDLE_WARMSTART_PEER"),
                   help="host:port of a live peer replica to fetch the "
                        "jit cache + weights from before building "
                        "(PADDLE_WARMSTART=1; empty = cold start)")
    args = p.parse_args(argv)
    t0 = _slo.now()  # breach-to-first-token starts at process main

    raw = args.spec
    if raw.startswith("@"):
        with open(raw[1:]) as f:
            raw = f.read()
    spec = json.loads(raw)

    if args.registry_endpoint:
        # ONE endpoint → the untouched single-master KVRegistry
        # (byte-identical pre-replication behavior); a peer LIST → the
        # quorum client, so a SIGKILL'd registry peer costs a failover
        # inside the client, never a lapsed lease
        from ..distributed.fleet.replicated_kv import make_registry
        registry = make_registry(args.registry_endpoint, ttl=args.ttl)
    elif args.registry_root:
        registry = FileRegistry(args.registry_root, args.job_id,
                                ttl=args.ttl)
    else:
        p.error("--registry-root or --registry-endpoint required")

    # warm start (ISSUE 16): cache + weights from a peer, warmup BEFORE
    # the lease registers — a visible lease means compiled-and-served
    warm_on = env_flags.get_bool("PADDLE_WARMSTART")
    warm_cache = None
    params = None
    warm_used = {"cache": False, "weights": False}
    if warm_on:
        from .warmstart import (WarmStartCache, enable_jit_cache,
                                fetch_warm_cache, fetch_weights,
                                spec_hash)
        shash = spec_hash(spec)
        if args.cache_dir:
            if args.warm_from:
                warm_used["cache"] = fetch_warm_cache(
                    args.warm_from, shash, args.cache_dir) is not None
            enable_jit_cache(args.cache_dir)
        if args.warm_from:
            params = fetch_weights(args.warm_from, shash)
            warm_used["weights"] = params is not None
        if params is None:
            params = build_params(spec)  # cold: seeded init, same values
    batcher = build_batcher(spec, params=params)
    role = normalize_role(args.role)
    if warm_on:
        serve_warmup(batcher, role)
        warm_cache = WarmStartCache(spec, args.cache_dir or None,
                                    params=params)
    ready_s = _slo.now() - t0
    # rejoin breadcrumb: adopt the fleet generation (the re-rendezvous
    # counter behind ElasticManager.behind_generation()) so a stale lease
    # from an older fleet formation is distinguishable on sight
    gen = None
    try:
        if hasattr(registry, "kv_counter"):
            gen = int(registry.kv_counter("gen"))
    except Exception:
        gen = None
    lease_extra = {"ready_s": round(ready_s, 4),
                   "warm": warm_used["cache"] or warm_used["weights"]}
    if gen is not None:
        lease_extra["gen"] = gen
    rep = ReplicaServer(batcher, registry, args.name, host=args.host,
                        port=args.port, role=args.role, warm=warm_cache,
                        lease_extra=lease_extra)
    signal.signal(signal.SIGTERM, lambda *a: rep.begin_drain())
    rep.start()
    # one machine-readable line for the spawner, then serve until drained
    print(json.dumps({"replica": rep.replica_id,  # observability: ok (spawner handshake line on stdout, not runtime telemetry)
                      "endpoint": rep.endpoint,
                      "role": rep.role,
                      "ready_s": round(ready_s, 4),
                      "warm": warm_used,
                      "pid": os.getpid()}), flush=True)
    while not rep.join(timeout=60.0):
        pass
    # linger so the router can collect the final /results page, then exit
    rep._stop.wait(max(1.0, args.ttl))
    rep._admin.stop()
    # a crashed serve loop must NOT exit 0: rc=0 is the drain protocol's
    # "finished clean" signal — a supervisor with restart-on-failure
    # (systemd/k8s) would treat a crash as a deliberate exit and never
    # restart it, silently losing fleet capacity
    return 0 if rep.crash is None else 1


if __name__ == "__main__":
    sys.exit(main())
