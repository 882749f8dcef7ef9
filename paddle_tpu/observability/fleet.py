"""Fleet-wide telemetry: per-rank push clients, rank-0 aggregation.

PR 2 gave every PROCESS spans/metrics/flight-recorder; this module makes the
FLEET observable as one system. Each rank runs a lightweight
``TelemetryClient`` (hooked into Engine / LlamaTrainStep / ResilientLoop /
ContinuousBatcher step boundaries via ``maybe_push``) that periodically
pushes a report — metrics snapshot, recent span batch, heartbeat
step/clock anchors — to the rank-0 launcher's ``TelemetryAggregator``.
On top of the aggregate:

  * ``merged_chrome_trace`` — ONE Perfetto trace for the whole job, one
    track per (node, rank). Per-rank span-clock timelines are
    clock-aligned with a heartbeat-exchange offset estimate (each report
    carries a (wall, perf) anchor plus its send time; the aggregator keeps
    the MINIMUM observed send→receive skew per rank — the NTP-style
    minimum filter — and maps every span onto its own wall clock).
    Collective spans (``comm.*``, which comm_watchdog stamps with a
    per-op ``seq``) additionally get chrome flow events binding the same
    (kind, seq) across ranks, so one barrier reads as one arrow.
  * straggler detection — per rank, the trailing-window step time MINUS
    collective wait time (a rank stalled waiting for a slow peer is not
    itself slow) is compared to the fleet median; a rank persistently
    above ``PADDLE_STRAGGLER_K``× the median for
    ``PADDLE_STRAGGLER_CHECKS`` consecutive reports raises the
    ``fleet.straggler`` metric and a flight event naming the rank.
  * ``merge_flight_files`` — folds every per-rank FLIGHT.json under
    PADDLE_TRACE_DIR into one sorted, rank-tagged FLEET_FLIGHT.json.

Transports (mirroring the dual-registry pattern of fleet/elastic.py):
  * HTTP — POST /push to an ``admin.AdminServer`` (token-authed; the
    launcher exports PADDLE_TELEMETRY_ENDPOINT to its children);
  * shared-dir — append-only per-rank JSONL files under
    PADDLE_TELEMETRY_DIR (NFS / GCS-fuse on real pods; /tmp in tests),
    polled by the aggregator.

Loss tolerance is the contract: a failed push (dead aggregator, full disk,
chaos site ``telemetry.push``) increments ``telemetry.drops`` and returns —
it can NEVER raise into a training step, so a chaos-on run stays bitwise
identical to fault-free.

ISSUE 6 rides two more payloads on the same channel (no new transport):
  * reports carry a FLIGHT TAIL (recorder.events_since batches) so the
    rank-0 admin endpoint can serve ``/logs?rank=N`` — per-rank recent
    log/flight lines without ssh;
  * the aggregator can queue COMMANDS for a (node, rank)
    (``post_command``) — e.g. the trigger engine arming an XPlane window
    on the slow rank. HTTP transport piggy-backs them on the ``/push``
    response body; shared-dir transport writes ``cmd.<node>.<rank>.jsonl``
    next to the push files. The client applies commands AFTER a
    successful push (xplane.arm / flight dump), swallowing every error —
    a malformed command is a recorded curiosity, never a step failure.

Env:
  PADDLE_TELEMETRY_DIR       shared-dir transport root
  PADDLE_TELEMETRY_ENDPOINT  host:port of the rank-0 admin server
  PADDLE_TELEMETRY_INTERVAL  min seconds between pushes (default 0.5)
  PADDLE_TELEMETRY_TIMEOUT   HTTP push timeout seconds (default 1.0)
  PADDLE_STRAGGLER_K         straggler multiplier over fleet median (2.0)
  PADDLE_STRAGGLER_CHECKS    consecutive over-threshold reports (3)
"""
from __future__ import annotations

import json
import os
import statistics
import threading
import time
import urllib.request
from collections import deque

from . import metrics, recorder, spans, xplane
from .admin import job_token

__all__ = ["TelemetryClient", "TelemetryAggregator", "maybe_push",
           "merge_flight_files", "reset",
           "FLEET_FLIGHT_NAME", "FLEET_TRACE_NAME"]

ENV_DIR = "PADDLE_TELEMETRY_DIR"
ENV_ENDPOINT = "PADDLE_TELEMETRY_ENDPOINT"
ENV_INTERVAL = "PADDLE_TELEMETRY_INTERVAL"
ENV_TIMEOUT = "PADDLE_TELEMETRY_TIMEOUT"
ENV_STRAGGLER_K = "PADDLE_STRAGGLER_K"
ENV_STRAGGLER_CHECKS = "PADDLE_STRAGGLER_CHECKS"
ENV_STALE_S = "PADDLE_TELEMETRY_STALE_S"

FLEET_FLIGHT_NAME = "FLEET_FLIGHT.json"
FLEET_TRACE_NAME = "FLEET_TRACE.json"

_SPANS_PER_RANK = 50000  # merged-trace memory bound per rank
_LOGS_PER_RANK = 500     # /logs?rank= tail bound per rank
_FLIGHT_BATCH = 200      # flight-tail events shipped per push (newest win)


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


# --------------------------------------------------------------- client

class TelemetryClient:
    """Per-rank push side. Built from env by ``maybe_push`` (the runtime
    hook); constructible directly for tests. Never raises from a push."""

    def __init__(self, endpoint: str | None = None, directory: str | None = None,
                 node: str | None = None, rank: int | None = None,
                 interval: float | None = None, timeout: float | None = None):
        self.endpoint = endpoint
        self.directory = directory
        self.node = node or os.environ.get("PADDLE_NODE_ID") or "node"
        self.rank = int(os.environ.get("PADDLE_TRAINER_ID", "0") or 0) \
            if rank is None else int(rank)
        self.interval = _env_float(ENV_INTERVAL, 0.5) \
            if interval is None else float(interval)
        self.timeout = _env_float(ENV_TIMEOUT, 1.0) \
            if timeout is None else float(timeout)
        self._last = 0.0          # monotonic time of the last push attempt
        self._cursor = 0          # spans already shipped (events_since)
        self._flight_cursor = 0   # flight/log events already shipped
        self._cmd_off = 0         # shared-dir command-file read offset
        self._lk = threading.Lock()

    def build_report(self, step=None) -> tuple[dict, dict]:
        """(report, next cursors) — the cursors only advance once the
        report is actually delivered, so spans/log lines survive a
        dropped push."""
        snap = metrics.snapshot()
        hists = snap["histograms"]
        step_h = hists.get("train.step_time_s") \
            or hists.get("loop.step_time_s")
        wait_h = hists.get("collective.wait_s")
        batch, nxt = (spans.events_since(self._cursor)
                      if spans.tracing_enabled() else ([], self._cursor))
        flight_batch, flight_nxt = recorder.events_since(self._flight_cursor)
        flight_batch = flight_batch[-_FLIGHT_BATCH:]
        now_wall = time.time()
        report = {
            "v": 1,
            "node": self.node,
            "rank": self.rank,
            "gen": int(os.environ.get("PADDLE_ELASTIC_GEN", "0") or 0),
            "pid": os.getpid(),
            "step": None if step is None else int(step),
            "t_send": now_wall,
            # clock anchor: span-clock ts in span events map onto this
            # rank's wall clock via (anchor_wall - anchor_perf); the span
            # clock IS the wall clock, so the pair differs by microseconds
            "anchor_wall": now_wall,
            "anchor_perf": spans.now(),
            "step_time": None if step_h is None else
                {"p50": step_h["p50"], "last": step_h["last"],
                 "count": step_h["count"]},
            "wait_time": None if wait_h is None else
                {"p50": wait_h["p50"], "count": wait_h["count"]},
            "metrics": snap,
            "spans": batch,
            "spans_dropped": spans.dropped(),
            # recent flight/log lines: the rank-0 /logs?rank= tail
            "flight": flight_batch,
        }
        return report, {"spans": nxt, "flight": flight_nxt}

    def _send(self, report: dict):
        data = json.dumps(report, default=str)
        if self.endpoint:
            base = self.endpoint if self.endpoint.startswith("http") \
                else f"http://{self.endpoint}"
            req = urllib.request.Request(
                f"{base}/push", method="POST", data=data.encode(),
                headers={"X-Paddle-Job-Token": job_token(),
                         "Content-Type": "application/json"})
            body = urllib.request.urlopen(req, timeout=self.timeout).read()
            # piggy-backed commands ride the push RESPONSE (no second
            # transport); a legacy plain-"ok" body simply carries none
            try:
                cmds = json.loads(body).get("commands") or []
            except (ValueError, AttributeError):
                cmds = []
            self._apply_commands(cmds)
            return
        if self.directory:
            os.makedirs(self.directory, exist_ok=True)
            path = os.path.join(self.directory,
                                f"push.{self.node}.{self.rank}.jsonl")
            # single append write per report: one writer per (node, rank)
            # file, so the aggregator's line-split read never interleaves
            with open(path, "a") as f:
                f.write(data + "\n")
            self._apply_commands(self._read_dir_commands())
            return
        raise RuntimeError("TelemetryClient has no transport configured")

    def _read_dir_commands(self) -> list[dict]:
        """New whole lines of this rank's command file (aggregator-written
        mirror of the push files), tracked by a private offset."""
        path = os.path.join(self.directory,
                            f"cmd.{self.node}.{self.rank}.jsonl")
        # offset read + advance under the client lock: concurrent pushes
        # (engine hook + a force-push) would otherwise both read from the
        # same offset and apply the same commands twice
        with self._lk:
            try:
                with open(path, "rb") as f:
                    f.seek(self._cmd_off)
                    chunk = f.read()
            except OSError:
                return []
            last_nl = chunk.rfind(b"\n")
            if last_nl < 0:
                return []
            self._cmd_off += last_nl + 1
        cmds = []
        for line in chunk[:last_nl].splitlines():
            try:
                cmds.append(json.loads(line))
            except ValueError:
                continue
        return cmds

    def _apply_commands(self, cmds: list):
        """Apply aggregator commands (trigger-armed deep capture). Every
        failure is swallowed into a flight event — commands are advice
        from the observability plane, never a correctness dependency."""
        for cmd in cmds:
            try:
                if not isinstance(cmd, dict):
                    continue
                kind = cmd.get("cmd")
                if kind == "xplane":
                    xplane.arm(steps=cmd.get("steps"),
                               xdir=cmd.get("dir"),
                               reason=cmd.get("reason") or "fleet-command")
                elif kind == "flight_dump":
                    recorder.dump_flight(
                        reason=cmd.get("reason") or "fleet-command")
                else:
                    recorder.record("telemetry.cmd_unknown", cmd=str(cmd))
                    continue
                metrics.counter("telemetry.commands").inc()
            except Exception as e:
                recorder.record("telemetry.cmd_error", cmd=str(cmd),
                                error=f"{type(e).__name__}: {e}")

    def maybe_push(self, step=None, force: bool = False) -> bool:
        """Push a report if the pacing interval elapsed. Loss-tolerant BY
        CONSTRUCTION: any failure (including the ``telemetry.push`` chaos
        site) is counted in ``telemetry.drops`` and swallowed — the caller
        is a training/serving step and must never feel telemetry."""
        now = time.monotonic()
        with self._lk:
            if not force and now - self._last < self.interval:
                return False
            self._last = now
        try:
            report, cursors = self.build_report(step)
            try:
                # lazy: chaos lives above observability in the import DAG
                from ..distributed.resilience import chaos
                chaos.hit("telemetry.push")
            except ImportError:
                pass
            self._send(report)
        except Exception as e:
            metrics.counter("telemetry.drops").inc()
            recorder.record("telemetry.drop",
                            error=f"{type(e).__name__}: {e}")
            return False
        with self._lk:
            self._cursor = cursors["spans"]
            self._flight_cursor = cursors["flight"]
        metrics.counter("telemetry.pushes").inc()
        return True


# the runtime hook's singleton, rebuilt when the env contract changes
_client_box: list = [None, None]  # [key, TelemetryClient]
_client_lock = threading.Lock()


def _configured_client() -> TelemetryClient | None:
    endpoint = os.environ.get(ENV_ENDPOINT)
    directory = os.environ.get(ENV_DIR)
    if not endpoint and not directory:
        return None
    key = (endpoint, directory, os.environ.get("PADDLE_NODE_ID"),
           os.environ.get("PADDLE_TRAINER_ID"))
    with _client_lock:
        if _client_box[0] != key:
            _client_box[0] = key
            # prefer HTTP when both are configured (better skew estimate);
            # the launcher only exports the endpoint to its OWN children
            _client_box[1] = TelemetryClient(
                endpoint=endpoint, directory=None if endpoint else directory)
        return _client_box[1]


def maybe_push(step=None, force: bool = False) -> bool:
    """The step-boundary hook: two env lookups when telemetry is off."""
    c = _configured_client()
    if c is None:
        return False
    return c.maybe_push(step, force=force)


def reset():
    """Drop the cached client (tests)."""
    with _client_lock:
        _client_box[0] = _client_box[1] = None


# ----------------------------------------------------------- aggregator

class TelemetryAggregator:
    """Rank-0 side: ingest reports (HTTP POST via AdminServer, or shared-dir
    polling), keep per-rank state, detect stragglers, merge traces."""

    def __init__(self, straggler_k: float | None = None,
                 straggler_checks: int | None = None):
        self.k = _env_float(ENV_STRAGGLER_K, 2.0) \
            if straggler_k is None else float(straggler_k)
        self.checks = int(_env_float(ENV_STRAGGLER_CHECKS, 3)) \
            if straggler_checks is None else int(straggler_checks)
        # a rank silent past this (or reporting an old fleet generation)
        # is STALE: dropped from the world count and the straggler median
        # — a dead node's frozen step time must not skew the fleet
        self.stale_s = _env_float(ENV_STALE_S, 30.0)
        self._max_gen = 0
        self._lk = threading.Lock()
        self._ranks: dict[tuple, dict] = {}   # (node, rank) -> state
        self._spans: dict[tuple, deque] = {}  # (node, rank) -> span events
        self._logs: dict[tuple, deque] = {}   # (node, rank) -> flight tail
        self._commands: dict[tuple, list] = {}  # (node, rank) -> queued cmds
        self._cmd_dir: str | None = None      # shared-dir command mirror
        self.received = 0
        self.malformed = 0
        self.straggler_events: list[dict] = []
        self._watch_stop: threading.Event | None = None
        self._watch_thread = None
        self._offsets: dict[str, int] = {}    # shared-dir file read offsets
        # serializes scans: the watch thread and a shutdown's final scan
        # must not read the same offset twice (double-ingested spans)
        self._scan_lk = threading.Lock()

    # ---- ingest ----
    def ingest(self, report: dict, recv_wall: float | None = None):
        """Fold one report in. Tolerates ANY malformed input (missing keys,
        wrong types) by counting it — a version-skewed client or corrupted
        line must never kill the aggregation thread."""
        try:
            self._ingest(report, recv_wall)
        except Exception:
            with self._lk:
                self.malformed += 1

    def _ingest(self, report: dict, recv_wall: float | None):
        if not isinstance(report, dict) or "node" not in report \
                or "rank" not in report:
            raise ValueError("report lacks node/rank")
        recv_wall = time.time() if recv_wall is None else recv_wall
        key = (str(report["node"]), int(report["rank"]))
        skew = recv_wall - float(report.get("t_send") or recv_wall)
        busy = self._busy_estimate(report)
        gen = int(report.get("gen") or 0)
        with self._lk:
            rec = self._ranks.setdefault(key, {
                "min_skew": skew, "streak": 0, "flagged": False})
            self._max_gen = max(self._max_gen, gen)
            rec["min_skew"] = min(rec["min_skew"], skew)
            rec["recv_wall"] = recv_wall
            rec["gen"] = gen
            rec["step"] = report.get("step")
            rec["pid"] = report.get("pid")
            rec["anchor_wall"] = report.get("anchor_wall")
            rec["anchor_perf"] = report.get("anchor_perf")
            rec["step_time"] = report.get("step_time")
            rec["wait_time"] = report.get("wait_time")
            rec["counters"] = (report.get("metrics") or {}).get("counters", {})
            rec["snap"] = report.get("metrics") or {}  # full: the launcher
            # exporter ships every rank's series out of the pod
            if busy is not None:
                rec["busy_s"] = busy
            batch = report.get("spans") or []
            if batch:
                dq = self._spans.setdefault(
                    key, deque(maxlen=_SPANS_PER_RANK))
                dq.extend(e for e in batch if isinstance(e, dict))
            fl = report.get("flight") or []
            if fl:
                dq = self._logs.setdefault(key, deque(maxlen=_LOGS_PER_RANK))
                dq.extend(e for e in fl if isinstance(e, dict))
            self.received += 1
        self._check_straggler(key)

    @staticmethod
    def _busy_estimate(report: dict) -> float | None:
        """Step time minus collective wait (trailing p50s): the straggler
        signal. A rank blocked at a barrier waiting for a SLOW PEER shows a
        long step but a long wait too — subtracting the wait attributes the
        slowness to the rank that earns it."""
        st = report.get("step_time")
        if not st or st.get("p50") is None:
            return None
        wait = report.get("wait_time") or {}
        w = wait.get("p50") or 0.0
        return max(float(st["p50"]) - float(w), 0.0)

    # ---- shared-dir transport ----
    def scan_dir(self, directory: str):
        """Ingest new report lines appended since the last scan."""
        with self._scan_lk:
            self._scan_dir_locked(directory)

    def _scan_dir_locked(self, directory: str):
        try:
            names = sorted(os.listdir(directory))
        except OSError:
            return
        for fn in names:
            if not (fn.startswith("push.") and fn.endswith(".jsonl")):
                continue
            path = os.path.join(directory, fn)
            off = self._offsets.get(path, 0)
            try:
                with open(path, "rb") as f:
                    f.seek(off)
                    chunk = f.read()
            except OSError:
                continue
            if not chunk:
                continue
            # consume only whole lines; a mid-append tail waits for the
            # next scan
            last_nl = chunk.rfind(b"\n")
            if last_nl < 0:
                continue
            self._offsets[path] = off + last_nl + 1
            now = time.time()
            for line in chunk[:last_nl].splitlines():
                line = line.strip()
                if not line:
                    continue
                try:
                    self.ingest(json.loads(line), recv_wall=now)
                except ValueError:
                    with self._lk:
                        self.malformed += 1

    def watch_dir(self, directory: str, interval: float = 0.25):
        """Poll `directory` on a daemon thread until ``stop()``."""
        self.stop()
        self._cmd_dir = directory  # command mirror rides the same dir
        stop = threading.Event()

        def poll():
            while not stop.wait(interval):
                try:
                    self.scan_dir(directory)
                except Exception:
                    pass  # the poll thread must outlive any one bad scan

        self._watch_stop = stop
        self._watch_thread = threading.Thread(target=poll, daemon=True)
        self._watch_thread.start()

    def stop(self):
        if self._watch_stop is not None:
            self._watch_stop.set()
            self._watch_stop = None
            self._watch_thread = None

    def _is_fresh(self, rec: dict, now: float) -> bool:
        """Live rank: reported recently AND at the newest fleet generation
        (a reformed fleet's old-generation entries are fenced everywhere
        else; the observability plane fences them too)."""
        return (now - rec.get("recv_wall", 0.0) <= self.stale_s
                and rec.get("gen", 0) >= self._max_gen)

    # ---- straggler detection ----
    def _check_straggler(self, key: tuple):
        now = time.time()
        with self._lk:
            busies = {k: r["busy_s"] for k, r in self._ranks.items()
                      if r.get("busy_s") is not None
                      and self._is_fresh(r, now)}
            rec = self._ranks.get(key)
        if rec is None or len(busies) < 2 or key not in busies:
            return
        med = statistics.median(busies.values())
        mine = busies[key]
        if med <= 0:
            return
        if mine > self.k * med:
            with self._lk:
                rec["streak"] = rec.get("streak", 0) + 1
                fire = rec["streak"] >= self.checks and not rec["flagged"]
                if fire:
                    rec["flagged"] = True
                    ev = {"node": key[0], "rank": key[1],
                          "busy_s": round(mine, 6),
                          "fleet_median_s": round(med, 6),
                          "ratio": round(mine / med, 3),
                          "k": self.k, "t": time.time()}
                    self.straggler_events.append(ev)
            if fire:
                metrics.counter("fleet.straggler").inc()
                recorder.record(
                    "fleet.straggler", echo=True,
                    message=f"[fleet] straggler: node={key[0]} rank={key[1]} "
                            f"busy p50 {mine * 1e3:.0f}ms > {self.k}x fleet "
                            f"median {med * 1e3:.0f}ms "
                            f"(x{mine / med:.2f})",
                    **ev)
        else:
            with self._lk:
                rec["streak"] = 0
                rec["flagged"] = False  # recovered: re-arm the detector

    # ---- command channel (piggy-backed on the telemetry transport) ----
    def post_command(self, node, rank, cmd: dict):
        """Queue one command for a (node, rank) — the trigger engine's
        deep-capture hook. HTTP clients receive it in their next /push
        response; shared-dir clients read the mirrored
        ``cmd.<node>.<rank>.jsonl`` line at their next push."""
        key = (str(node), int(rank))
        with self._lk:
            self._commands.setdefault(key, []).append(dict(cmd))
        if self._cmd_dir:
            try:
                os.makedirs(self._cmd_dir, exist_ok=True)
                path = os.path.join(self._cmd_dir,
                                    f"cmd.{key[0]}.{key[1]}.jsonl")
                with open(path, "a") as f:
                    f.write(json.dumps(cmd, default=str) + "\n")
            except OSError:
                pass  # the HTTP fallback (if any) still carries it
        recorder.record("fleet.command", node=key[0], rank=key[1],
                        cmd=cmd.get("cmd"), detail=cmd)

    def take_commands(self, node, rank) -> list[dict]:
        """Pop every queued command for (node, rank) — the admin /push
        handler drains these into the push response."""
        key = (str(node), int(rank))
        with self._lk:
            return self._commands.pop(key, [])

    # ---- per-rank accessors ----
    def rank_counters(self) -> list[dict]:
        """[{node, rank, counters}] of the latest reported counter
        snapshot per rank — what the trigger engine watches for
        slo.breach / watchdog.near_deadline deltas."""
        with self._lk:
            items = sorted(self._ranks.items())
        return [{"node": node, "rank": rank,
                 "counters": dict(rec.get("counters") or {})}
                for (node, rank), rec in items]

    def export_blocks(self) -> list[tuple[dict, dict]]:
        """[({node, rank}, latest reported metrics snapshot)] for every
        FRESH rank — what the launcher's MetricsExporter pushes so the
        external sink sees per-rank train/collective/serve series, not
        just the launcher's own registry."""
        now = time.time()
        with self._lk:
            items = sorted(self._ranks.items())
        out = []
        for (node, rank), rec in items:
            snap = rec.get("snap")
            if snap and self._is_fresh(rec, now):
                out.append(({"node": node, "rank": str(rank)}, snap))
        return out

    def logs(self, rank: int, node=None, limit: int = 200) -> list[dict]:
        """The recent flight/log tail of one rank (newest last). With
        several nodes carrying the same rank id, `node` narrows it."""
        with self._lk:
            keys = [k for k in self._logs
                    if k[1] == int(rank) and (node is None or k[0] == str(node))]
            out = []
            for k in sorted(keys):
                out.extend(dict(e, node=k[0], rank=k[1])
                           for e in self._logs[k])
        out.sort(key=lambda e: (e.get("t") or 0, e.get("seq") or 0))
        return out[-int(limit):]

    # ---- summaries ----
    def ranks(self) -> list[dict]:
        now = time.time()
        out = []
        with self._lk:
            items = sorted(self._ranks.items())
        for (node, rank), rec in items:
            st = rec.get("step_time") or {}
            out.append({
                "node": node, "rank": rank, "gen": rec.get("gen", 0),
                "step": rec.get("step"),
                "age_s": round(now - rec.get("recv_wall", now), 3),
                "step_time_p50": st.get("p50"),
                "busy_s": rec.get("busy_s"),
                "straggler": bool(rec.get("flagged")),
                "stale": not self._is_fresh(rec, now),
            })
        return out

    def fleet_snapshot(self) -> dict:
        ranks = self.ranks()
        with self._lk:
            drops = sum(int(r.get("counters", {}).get("telemetry.drops", 0))
                        for r in self._ranks.values())
            received, malformed = self.received, self.malformed
            stragglers = list(self.straggler_events)
        # world = LIVE ranks: a reformed/shrunk fleet must not keep
        # counting entries a dead generation left behind
        return {"world": sum(not r["stale"] for r in ranks), "ranks": ranks,
                "stragglers": stragglers, "received": received,
                "malformed": malformed, "drops_reported": drops}

    def step_time_table(self) -> list[dict]:
        """Per-rank step-time ranking, slowest first — embedded in the
        launcher FLIGHT.json on every reform so the postmortem names the
        slow rank without re-deriving it."""
        rows = []
        with self._lk:
            items = sorted(self._ranks.items())
        for (node, rank), rec in items:
            st = rec.get("step_time") or {}
            rows.append({"node": node, "rank": rank, "step": rec.get("step"),
                         "step_time_p50": st.get("p50"),
                         "busy_s": rec.get("busy_s"),
                         "straggler": bool(rec.get("flagged"))})
        rows.sort(key=lambda r: -(r["busy_s"] or 0.0))
        return rows

    # ---- merged fleet trace ----
    def _rank_offset_s(self, rec: dict) -> float | None:
        """span clock → aggregator-wall mapping for one rank: the
        report's (wall, perf) anchor plus the minimum-filter skew estimate
        (min over observed send→receive deltas ≈ clock offset + network
        floor — the heartbeat-exchange offset estimate)."""
        aw, ap = rec.get("anchor_wall"), rec.get("anchor_perf")
        if aw is None or ap is None:
            return None
        return (float(aw) - float(ap)) + float(rec.get("min_skew", 0.0))

    def merged_chrome_trace(self, path: str) -> str | None:
        """Write ONE chrome trace covering every rank: track (pid) per
        (node, rank), clock-aligned ts, flow events binding collective
        spans by (name, seq) across ranks. Returns the path, or None when
        no spans were collected."""
        with self._lk:
            keys = sorted(self._spans.keys())
            per_rank = {k: list(self._spans[k]) for k in keys}
            recs = {k: dict(self._ranks.get(k, {})) for k in keys}
        if not keys:
            return None
        aligned: dict[tuple, list] = {}
        t0 = None
        for key in keys:
            off = self._rank_offset_s(recs[key])
            if off is None:
                off = 0.0
            evs = []
            for ev in per_rank[key]:
                ts = ev.get("ts")
                if ts is None:
                    continue
                evs.append((float(ts) + off * 1e6, ev))
            aligned[key] = evs
            for ts, _ in evs:
                t0 = ts if t0 is None else min(t0, ts)
        if t0 is None:
            return None

        out = []
        flows: dict[tuple, list] = {}  # (name, seq) -> [(ts, pid, tid)]
        rank_meta = []
        for i, key in enumerate(keys):
            pid = i + 1
            node, rank = key
            out.append({"name": "process_name", "ph": "M", "pid": pid,
                        "tid": 0, "args": {"name": f"{node} rank {rank}"}})
            out.append({"name": "process_sort_index", "ph": "M", "pid": pid,
                        "tid": 0, "args": {"sort_index": rank}})
            rank_meta.append({"pid": pid, "node": node, "rank": rank,
                              "offset_s": self._rank_offset_s(recs[key])})
            for ts, ev in aligned[key]:
                ev2 = dict(ev)
                ev2["pid"] = pid
                ev2["ts"] = ts - t0
                out.append(ev2)
                args = ev.get("args") or {}
                if ev.get("cat") == "collective" and "seq" in args:
                    fk = (ev.get("name"), args["seq"])
                    flows.setdefault(fk, []).append(
                        (ts - t0, pid, ev.get("tid", 0)))
        for (name, seq), hits in flows.items():
            if len(hits) < 2:
                continue  # a flow needs both ends
            hits.sort()
            fid = abs(hash((name, seq))) % (1 << 31)
            for j, (ts, pid, tid) in enumerate(hits):
                ph = "s" if j == 0 else ("f" if j == len(hits) - 1 else "t")
                fev = {"name": f"{name}", "cat": "collective.flow",
                       "ph": ph, "id": fid, "ts": ts, "pid": pid, "tid": tid}
                if ph == "f":
                    fev["bp"] = "e"
                out.append(fev)

        doc = {"traceEvents": out, "displayTimeUnit": "ms",
               "otherData": {
                   "clock": "fleet-aligned wall (heartbeat-offset estimate)",
                   "ranks": rank_meta}}
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, default=str)
        os.replace(tmp, path)
        return path


# ------------------------------------------------- flight-file merging

def merge_flight_files(trace_dir: str, out_path: str | None = None) -> str | None:
    """Fold every ``<trace_dir>/<rank-dir>/FLIGHT.json`` into ONE
    rank-tagged, time-sorted ``FLEET_FLIGHT.json`` — the postmortem reads
    one file instead of ssh'ing around per-rank dumps. Returns the output
    path, or None when no per-rank flights exist. Never raises."""
    try:
        out_path = out_path or os.path.join(trace_dir, FLEET_FLIGHT_NAME)
        sources, events = [], []
        for entry in sorted(os.listdir(trace_dir)):
            fp = os.path.join(trace_dir, entry, recorder.FLIGHT_NAME)
            if not os.path.isfile(fp):
                continue
            try:
                with open(fp) as f:
                    doc = json.load(f)
            except (OSError, ValueError):
                continue
            evs = doc.get("events") or []
            sources.append({"source": entry, "reason": doc.get("reason"),
                            "pid": doc.get("pid"), "events": len(evs)})
            for ev in evs:
                if isinstance(ev, dict):
                    events.append(dict(ev, source=entry))
        if not sources:
            return None
        events.sort(key=lambda e: (e.get("t") or 0, e.get("source", ""),
                                   e.get("seq") or 0))
        doc = {"merged_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
               "trace_dir": trace_dir, "sources": sources, "events": events}
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1, default=str)
        os.replace(tmp, out_path)
        return out_path
    except Exception:
        return None
