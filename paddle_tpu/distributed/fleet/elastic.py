"""Elastic training: membership, heartbeats, scale decisions.

Reference: /root/reference/python/paddle/distributed/fleet/elastic/manager.py
(ElasticManager :125 — etcd leases as heartbeats, np-change watch, scale
up/down decisions via ElasticLevel/ElasticStatus :44,:49, relaunch) and
launch/utils/kv_server.py (the in-launcher HTTP KV master used instead of
etcd for single-node jobs).

TPU-native: etcd isn't vendored, so membership is pluggable transport:

* ``FileRegistry`` — heartbeat files with a TTL over a shared directory
  (NFS / GCS-fuse on real pods; /tmp for same-host tests).
* ``KVRegistry`` — the reference's HTTP-KV-master pattern: node 0 serves a
  tiny TTL'd KV over HTTP (``KVServer``), every node heartbeats via PUT and
  reads membership via GET. No shared filesystem needed.

``ElasticManager`` owns the decision loop (HOLD / RESTART / ERROR /
COMPLETED); the launcher (``distributed/launch/main.py``) owns process
supervision and acts on the decisions.

Self-healing (re-rendezvous): both registries additionally expose a small
DURABLE key/value space (``kv_put/kv_get/kv_max/kv_list/kv_del`` — no TTL)
that backs the generation-numbered re-rendezvous barrier:

  * the fleet generation lives under key ``gen`` and only ever grows
    (``kv_max`` is a max-CAS, so concurrent survivors proposing the next
    generation converge on one number);
  * survivors re-enroll under ``enroll.<gen>.<node>``;
  * the deterministic leader (lowest enrolled node id) waits for the
    enrollment set to hold still for a join window, then publishes
    ``assign.<gen>`` — contiguous ranks over the sorted survivors and the
    new world size;
  * anything tagged with an older generation is fenced (rpc messages carry
    the generation; a superseded barrier is abandoned mid-flight and the
    new one chased).

``ElasticManager.re_rendezvous()`` drives one pass of that barrier and
returns the node's new (generation, rank, world).
"""
from __future__ import annotations

import dataclasses
import enum
import json
import os
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from ...observability import metrics as _metrics, recorder as _recorder, \
    spans as _spans

__all__ = ["ElasticLevel", "ElasticStatus", "FileRegistry", "KVServer",
           "KVRegistry", "ElasticManager", "RendezvousResult",
           "elastic_active", "set_elastic_active", "TELEMETRY_KEY"]

# durable-KV key under which the rank-0 launcher advertises its admin /
# telemetry endpoint (observability.admin.AdminServer) — late joiners and
# re-formed fleets find the observability plane through the registry they
# already speak, no extra wiring
TELEMETRY_KEY = "telemetry.admin"


_active = [False]


def set_elastic_active(on: bool):
    """In-process switch consulted by the collective/watchdog layers (the
    launcher exports PADDLE_ELASTIC_ACTIVE=1 to its children instead)."""
    _active[0] = bool(on)


def elastic_active() -> bool:
    """True when this process runs under elastic supervision: blocking
    collective waits become deadline-bounded (abort-and-reform) and the
    comm watchdog defers its exit-124 abort to the reform path."""
    return _active[0] or os.environ.get("PADDLE_ELASTIC_ACTIVE", "") == "1"


def _kv_token() -> str:
    """Job token required on mutating KV endpoints: a peer outside the job
    (who does not know PADDLE_JOB_ID / PADDLE_RPC_SECRET) cannot forge or
    delete heartbeats to force elastic restarts."""
    import hashlib
    job = os.environ.get("PADDLE_JOB_ID", "default")
    secret = os.environ.get("PADDLE_RPC_SECRET", "")
    return hashlib.sha256(f"paddle-tpu-kv:{secret}:{job}".encode()).hexdigest()


class ElasticLevel(enum.IntEnum):
    FAULT_TOLERANCE = 1  # fixed np, restart on failure
    ELASTIC = 2          # np range, scale up/down


class ElasticStatus(enum.Enum):
    COMPLETED = "completed"
    ERROR = "error"
    HOLD = "hold"
    RESTART = "restart"
    EXIT = "exit"


class FileRegistry:
    """Heartbeat registry over a shared directory."""

    def __init__(self, root: str, job_id: str, ttl: float = 10.0):
        self.dir = os.path.join(root, job_id)
        os.makedirs(self.dir, exist_ok=True)
        self.ttl = ttl

    def heartbeat(self, node_id: str, info=None):
        path = os.path.join(self.dir, f"{node_id}.hb")
        with open(path, "w") as f:
            json.dump({"ts": time.time(), "info": info or {}}, f)

    def alive_nodes(self):
        now = time.time()
        out = []
        for fn in os.listdir(self.dir):
            if not fn.endswith(".hb"):
                continue
            try:
                with open(os.path.join(self.dir, fn)) as f:
                    rec = json.load(f)
                if now - rec["ts"] <= self.ttl:
                    out.append(fn[:-3])
            except Exception:
                continue
        return sorted(out)

    def leave(self, node_id: str):
        try:
            os.remove(os.path.join(self.dir, f"{node_id}.hb"))
        except OSError:
            pass

    def info(self, node_id: str) -> dict | None:
        """The node's last heartbeat info payload, None when the lease has
        lapsed (same TTL contract as alive_nodes) — how the serving router
        learns a replica's endpoint from its lease."""
        try:
            with open(os.path.join(self.dir, f"{node_id}.hb")) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            return None
        if time.time() - rec.get("ts", 0) > self.ttl:  # observability: ok (wall-clock liveness TTL, not perf timing)
            return None
        return rec.get("info") or {}

    # ---- durable KV (re-rendezvous barrier state; no TTL) ----
    def _kv_path(self, key: str) -> str:
        return os.path.join(self.dir, "kv__" + key.replace(os.sep, "_"))

    def kv_put(self, key: str, value: str):
        path = self._kv_path(key)
        tmp = f"{path}.tmp{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(value)
        os.replace(tmp, path)

    def kv_get(self, key: str) -> str | None:
        try:
            with open(self._kv_path(key)) as f:
                return f.read()
        except OSError:
            return None

    def kv_del(self, key: str):
        try:
            os.remove(self._kv_path(key))
        except OSError:
            pass

    def kv_list(self, prefix: str) -> dict:
        pfx = "kv__" + prefix.replace(os.sep, "_")
        out = {}
        for fn in os.listdir(self.dir):
            if not fn.startswith(pfx) or ".tmp" in fn or fn.endswith(".lock"):
                continue
            try:
                with open(os.path.join(self.dir, fn)) as f:
                    out[fn[4:]] = f.read()
            except OSError:
                continue  # racing a concurrent replace/delete
        return out

    def kv_max(self, key: str, value: int) -> int:
        """Max-CAS: the counter becomes max(current, value); returns the
        winner. Monotone WITHOUT locks: each proposed value is its own
        `<key>.v<value>` marker file (O_CREAT is atomic and idempotent) and
        the counter's value is the max over markers — concurrent proposals
        can only ADD markers, so there is no read-modify-write window in
        which a racer with a stale read could regress the generation."""
        try:
            os.close(os.open(f"{self._kv_path(key)}.v{int(value)}",
                             os.O_CREAT | os.O_WRONLY))
        except OSError:
            pass  # an existing marker is the same proposal already counted
        return max(int(value), self.kv_counter(key))

    def kv_counter(self, key: str) -> int:
        """Current value of a kv_max counter (0 when never proposed)."""
        pfx = os.path.basename(self._kv_path(key)) + ".v"
        best = 0
        try:
            for fn in os.listdir(self.dir):
                if fn.startswith(pfx):
                    tail = fn[len(pfx):]
                    if tail.isdigit():
                        best = max(best, int(tail))
        except FileNotFoundError:
            pass
        return best

    def kv_max_gc(self, key: str, floor: int):
        """Drop counter markers below `floor`. The counter's value (the max
        over markers) is preserved as long as callers pass floor <= the
        current value — keeps listdir scans bounded on long-lived fleets."""
        pfx = os.path.basename(self._kv_path(key)) + ".v"
        try:
            for fn in os.listdir(self.dir):
                if fn.startswith(pfx):
                    tail = fn[len(pfx):]
                    if tail.isdigit() and int(tail) < floor:
                        try:
                            os.remove(os.path.join(self.dir, fn))
                        except OSError:
                            pass
        except FileNotFoundError:
            pass


def _merge_snapshot(store: dict, kv: dict, maxkeys: set, snap: dict):
    """Merge one /dump-shaped snapshot into raw store dicts — hb by
    freshest ts, kv by version, kvmax counters by VALUE. Shared by
    ``KVServer.load_snapshot`` and WAL replay so a replayed snapshot
    record applies byte-identically to the live merge it logged."""
    for node, rec in (snap.get("hb") or {}).items():
        ts, info = float(rec[0]), str(rec[1])
        if ts > store.get(node, (0, ""))[0]:
            store[node] = (ts, info)
    maxkeys.update(set(snap.get("maxkeys") or []))
    for key, rec in (snap.get("kv") or {}).items():
        val, vn, w = str(rec[0]), int(rec[1]), str(rec[2])
        old, cur_vn, cur_w = kv.get(key, ("", 0, ""))
        if key in maxkeys:
            try:
                if int(val or 0) > int(old or 0):
                    kv[key] = (val, max(vn, cur_vn), w)
            except ValueError:
                pass
        elif (vn, w) > (cur_vn, cur_w):
            kv[key] = (val, vn, w)


def _wal_replay(path: str, store: dict, kv: dict, maxkeys: set):
    """Apply every committed record of a write-ahead file, in commit
    order. A torn tail line (the crash interrupted the append) parses as
    invalid JSON and is skipped — everything before it was fsynced whole."""
    try:
        with open(path) as f:
            lines = f.readlines()
    except OSError:
        return
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        op = rec.get("op")
        if op == "hb":
            store[rec["n"]] = (float(rec["ts"]), str(rec["i"]))
        elif op == "kv":
            kv[rec["k"]] = (str(rec["v"]), int(rec["vn"]), str(rec["w"]))
        elif op == "kvmax":
            kv[rec["k"]] = (str(rec["v"]), int(rec["vn"]), "")
            maxkeys.add(rec["k"])
        elif op == "delhb":
            store.pop(rec["n"], None)
        elif op == "delkv":
            kv.pop(rec["k"], None)
        elif op == "snap":
            _merge_snapshot(store, kv, maxkeys, rec)


class KVServer:
    """TTL'd KV over HTTP — the master side of KVRegistry.

    Reference: launch/utils/kv_server.py (the launcher master's KV store).
    Endpoints: PUT /hb/<node> (body = info json), GET /nodes (alive list),
    DELETE /hb/<node>; durable (no-TTL) re-rendezvous state under
    PUT/GET/DELETE /kv/<key>, PUT /kvmax/<key> (atomic max-CAS, body = int,
    response = winning value) and GET /kvlist/<prefix> (JSON dict).

    Replication (ISSUE 12): every durable entry carries a per-key VERSION
    ``(vn, writer)`` so N peers driven by the quorum client
    (``fleet.replicated_kv``) converge by last-writer-wins instead of
    diverging. Versioned protocol, all backward compatible with the plain
    single-master client:

      * PUT /kv/<key> accepts optional ``X-Paddle-KV-Ver`` /
        ``X-Paddle-KV-Writer`` headers — the write applies only when its
        version exceeds the stored one (equal = idempotent re-accept);
        the JSON response reports ``{"applied", "ver", "writer"}``.
        Without the headers the server bumps the version locally (the
        pre-replication behavior, byte-identical for one master).
      * GET /kv/<key> answers the stored version in the same headers;
        GET /kvlist/<prefix>?v=1 answers ``{key: [value, vn, writer]}``.
      * GET /info/<node> answers the heartbeat wall time in
        ``X-Paddle-HB-TS`` so a quorum read can pick the freshest lease.
      * GET /dump + PUT /load move a whole-store snapshot — a restarted
        peer catches up from a majority snapshot (``kvmax`` keys merge by
        numeric max, never by version: the counter is monotone by VALUE).

    Durability (ISSUE 16): with ``wal_path`` set, every committed
    mutation is appended to a JSON-lines write-ahead file (fsynced inside
    the store lock, so line order IS commit order) and replayed on
    construction — a peer that restarts with its WAL recovers every write
    it ever acked, even when ALL peers died simultaneously and no
    snapshot survives to catch up from. Replay compacts the file to one
    snapshot line, so restart cost is O(state), not O(lifetime writes).
    """

    def __init__(self, port: int = 0, ttl: float = 10.0,
                 wal_path: str | None = None):
        store: dict = {}
        # durable: generation counter, enrollments, assignments —
        # key -> (value, vn, writer)
        kv: dict = {}
        maxkeys: set = set()  # keys written through /kvmax (merge by value)
        lock = threading.Lock()
        self._store, self._kv, self._lock, self.ttl = store, kv, lock, ttl
        self._maxkeys = maxkeys
        self.wal_path = wal_path
        wal: list = [None]  # closure cell: append handle, None = WAL off
        if wal_path:
            _wal_replay(wal_path, store, kv, maxkeys)
            # compact: one snapshot line replaces the replayed history
            tmp = wal_path + ".tmp"
            with open(tmp, "w") as f:
                f.write(json.dumps(
                    {"op": "snap",
                     "hb": {n: list(r) for n, r in store.items()},
                     "kv": {k: list(r) for k, r in kv.items()},
                     "maxkeys": sorted(maxkeys)}) + "\n")
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, wal_path)
            wal[0] = open(wal_path, "a")
        self._wal = wal

        def _wal_append(rec: dict):
            # caller holds `lock`; a failed append is flight-recorded,
            # never raised into the KV response path (the in-memory
            # commit already happened — durability degrades, the
            # registry keeps serving)
            f = wal[0]
            if f is None:
                return
            try:
                f.write(json.dumps(rec) + "\n")
                f.flush()
                os.fsync(f.fileno())
            except (OSError, ValueError) as e:
                _recorder.record("kv.wal_write_failed", echo=True,
                                 message=f"[kv] WAL append failed: {e}",
                                 path=wal_path, error=str(e))

        self._wal_append = _wal_append
        ttl_ref = self

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):  # quiet
                pass

            def _send(self, code, body=b""):
                self.send_response(code)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _authed(self):
                import hmac as _hmac
                tok = self.headers.get("X-Paddle-Job-Token", "")
                return _hmac.compare_digest(tok, _kv_token())

            def _body(self) -> bytes:
                n = int(self.headers.get("Content-Length", 0))
                return self.rfile.read(n) if n else b""

            def do_PUT(self):
                if not self._authed():
                    return self._send(403)
                if self.path.startswith("/hb/"):
                    node = self.path[4:]
                    info = self._body() or b"{}"
                    with lock:
                        ts = time.time()
                        store[node] = (ts, info.decode() or "{}")
                        _wal_append({"op": "hb", "n": node, "ts": ts,
                                     "i": store[node][1]})
                    return self._send(200)
                if self.path.startswith("/kv/"):
                    key = self.path[4:]
                    val = self._body().decode()
                    hdr_vn = self.headers.get("X-Paddle-KV-Ver")
                    writer = self.headers.get("X-Paddle-KV-Writer", "")
                    if hdr_vn is not None:
                        # parse (and answer 400) BEFORE taking the store
                        # lock: the 400 response is a socket send, and a
                        # slow/blackholed reader must stall only its own
                        # connection, never every KV op fleet-wide
                        # (analyzer rule A7 surfaced the old shape)
                        try:
                            hdr_vn = int(hdr_vn)
                        except ValueError:
                            return self._send(400)
                    with lock:
                        _, cur_vn, cur_w = kv.get(key, ("", 0, ""))
                        if hdr_vn is None:
                            # unversioned (single-master) write: local bump
                            vn, applied = cur_vn + 1, True
                        else:
                            vn = hdr_vn
                            # last-writer-wins by (vn, writer); an equal
                            # version re-accepts idempotently (a quorum
                            # client retrying its own write), an older one
                            # is stale and must not regress the key
                            applied = (vn, writer) >= (cur_vn, cur_w)
                        if applied:
                            if key in maxkeys:
                                # monotone guard: a kvmax counter's value
                                # order is authoritative — per-peer
                                # versions are bumped independently, so a
                                # version-ordered read-repair could
                                # otherwise write a LOWER committed value
                                # over a higher one and regress the
                                # generation fleet-wide
                                old, _, _ = kv.get(key, ("", 0, ""))
                                try:
                                    val = str(max(int(val or 0),
                                                  int(old or 0)))
                                except ValueError:
                                    pass
                            kv[key] = (val, vn, writer)
                            _wal_append({"op": "kv", "k": key, "v": val,
                                         "vn": vn, "w": writer})
                        else:
                            vn, writer = cur_vn, cur_w
                    return self._send(200, json.dumps(
                        {"applied": applied, "ver": vn,
                         "writer": writer}).encode())
                if self.path.startswith("/kvmax/"):
                    key = self.path[7:]
                    try:
                        val = int(self._body().decode() or "0")
                    except ValueError:
                        return self._send(400)
                    with lock:  # the lock IS the CAS: read-max-write is atomic
                        old, cur_vn, _ = kv.get(key, ("", 0, ""))
                        try:
                            cur = int(old or 0)
                        except ValueError:
                            cur = 0
                        new = max(cur, val)
                        kv[key] = (str(new), cur_vn + 1, "")
                        maxkeys.add(key)
                        _wal_append({"op": "kvmax", "k": key, "v": str(new),
                                     "vn": cur_vn + 1})
                    return self._send(200, str(new).encode())
                if self.path == "/load":
                    # snapshot install (peer catch-up): merge, never clobber
                    try:
                        snap = json.loads(self._body().decode() or "{}")
                    except ValueError:
                        return self._send(400)
                    ttl_ref.load_snapshot(snap)
                    return self._send(200)
                self._send(404)

            def do_DELETE(self):
                if not self._authed():
                    return self._send(403)
                if self.path.startswith("/hb/"):
                    with lock:
                        store.pop(self.path[4:], None)
                        _wal_append({"op": "delhb", "n": self.path[4:]})
                    return self._send(200)
                if self.path.startswith("/kv/"):
                    with lock:
                        kv.pop(self.path[4:], None)
                        _wal_append({"op": "delkv", "k": self.path[4:]})
                    return self._send(200)
                self._send(404)

            def do_GET(self):
                path, _, query = self.path.partition("?")
                if path.startswith("/kv/"):
                    with lock:
                        rec = kv.get(path[4:])
                    if rec is None:
                        return self._send(404)
                    val, vn, w = rec
                    self.send_response(200)
                    body = val.encode()
                    self.send_header("Content-Length", str(len(body)))
                    self.send_header("X-Paddle-KV-Ver", str(vn))
                    self.send_header("X-Paddle-KV-Writer", w)
                    self.end_headers()
                    return self.wfile.write(body)
                if path.startswith("/kvlist/"):
                    pfx = path[8:]
                    versioned = "v=1" in query.split("&")
                    with lock:
                        if versioned:
                            out = {k: list(rec) for k, rec in kv.items()
                                   if k.startswith(pfx)}
                        else:
                            out = {k: rec[0] for k, rec in kv.items()
                                   if k.startswith(pfx)}
                    return self._send(200, json.dumps(out).encode())
                if path == "/dump":
                    with lock:
                        snap = {"hb": {n: list(rec)
                                       for n, rec in store.items()},
                                "kv": {k: list(rec)
                                       for k, rec in kv.items()},
                                "maxkeys": sorted(maxkeys)}
                    return self._send(200, json.dumps(snap).encode())
                if path.startswith("/info/"):
                    node = path[6:]
                    with lock:
                        rec = store.get(node)
                    # same TTL contract as /nodes: stale entries are gone
                    if rec is None or time.time() - rec[0] > ttl_ref.ttl:  # observability: ok (wall-clock liveness TTL, not perf timing)
                        return self._send(404)
                    self.send_response(200)
                    body = rec[1].encode()
                    self.send_header("Content-Length", str(len(body)))
                    self.send_header("X-Paddle-HB-TS", repr(rec[0]))
                    self.end_headers()
                    return self.wfile.write(body)
                if path != "/nodes":
                    return self._send(404)
                now = time.time()
                with lock:
                    alive = sorted(k for k, (ts, _) in store.items()
                                   if now - ts <= ttl_ref.ttl)
                self._send(200, json.dumps(alive).encode())

        self._httpd = ThreadingHTTPServer(("0.0.0.0", port), H)
        self.port = self._httpd.server_address[1]
        self._started = False
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True)

    def load_snapshot(self, snap: dict):
        """Merge one /dump snapshot into this store — hb by freshest ts,
        kv by version, kvmax counters by VALUE. Callable BEFORE start():
        a restarted peer is caught up while its port only queues
        connections, so no client ever reads the blank pre-merge store."""
        with self._lock:
            _merge_snapshot(self._store, self._kv, self._maxkeys, snap)
            self._wal_append({"op": "snap",
                              "hb": snap.get("hb") or {},
                              "kv": snap.get("kv") or {},
                              "maxkeys": list(snap.get("maxkeys") or [])})

    def start(self):
        self._started = True
        self._thread.start()
        return self

    def stop(self):
        if self._started:
            # shutdown() handshakes with serve_forever — on a never-
            # started server it would block forever
            self._httpd.shutdown()
        self._httpd.server_close()
        with self._lock:
            f, self._wal[0] = self._wal[0], None
        if f is not None:
            f.close()


class KVRegistry:
    """Client of a KVServer: heartbeat + membership over HTTP.

    Every PUT/GET routes through resilience.retry — one dropped HTTP
    request (connection reset, master GC pause) retries with jittered backoff
    instead of surfacing as a dead node / empty membership."""

    def __init__(self, endpoint: str, ttl: float = 10.0, timeout: float = 3.0,
                 retry_policy=None):
        from ..resilience.retry import RetryPolicy
        self.base = endpoint if endpoint.startswith("http") else f"http://{endpoint}"
        self.ttl = ttl
        self.timeout = timeout
        # budget stays well under the TTL: a heartbeat that retries past
        # its own expiry is worse than a miss. deadline is only checked
        # BETWEEN attempts and each attempt can block `timeout` seconds,
        # so half the ttl leaves the other half for the in-flight request
        # plus the beat interval before the entry lapses
        self.retry_policy = retry_policy or RetryPolicy(
            max_attempts=3, base_delay=0.1, max_delay=0.5,
            deadline=max(1.0, ttl * 0.5))

    def heartbeat(self, node_id: str, info=None):
        from ..resilience import chaos
        from ..resilience.retry import retry_call

        def put():
            chaos.hit("kv.heartbeat")
            req = urllib.request.Request(
                f"{self.base}/hb/{node_id}", method="PUT",
                data=json.dumps(info or {}).encode(),
                headers={"X-Paddle-Job-Token": _kv_token()})
            urllib.request.urlopen(req, timeout=self.timeout).read()

        retry_call(put, op=f"kv.heartbeat {node_id}",
                   policy=self.retry_policy)

    def alive_nodes(self):
        from ..resilience.retry import retry_call

        def get():
            with urllib.request.urlopen(f"{self.base}/nodes",
                                        timeout=self.timeout) as r:
                return json.loads(r.read())

        try:
            return retry_call(get, op="kv.alive_nodes",
                              policy=self.retry_policy)
        except Exception:
            # exhausted budget: report empty so the manager's own-heartbeat
            # guard (watch() HOLD) treats it as an unreliable read
            return []

    def leave(self, node_id: str):
        try:
            req = urllib.request.Request(
                f"{self.base}/hb/{node_id}", method="DELETE",
                headers={"X-Paddle-Job-Token": _kv_token()})
            urllib.request.urlopen(req, timeout=self.timeout).read()
        except Exception:
            pass

    def info(self, node_id: str) -> dict | None:
        """The node's last heartbeat info payload via GET /info/<node>
        (404 = lease lapsed). Mirrors FileRegistry.info for the router."""
        try:
            out = self._kv_req(f"/info/{node_id}", op=f"kv.info {node_id}")
        except Exception:
            return None
        if out is None:
            return None
        try:
            return json.loads(out)
        except ValueError:
            return None

    # ---- durable KV (re-rendezvous barrier state) ----
    def _kv_req(self, path: str, method: str = "GET", data: bytes | None = None,
                op: str = "kv"):
        from ..resilience.retry import retry_call
        import urllib.error

        def go():
            req = urllib.request.Request(
                f"{self.base}{path}", method=method, data=data,
                headers={"X-Paddle-Job-Token": _kv_token()})
            try:
                with urllib.request.urlopen(req, timeout=self.timeout) as r:
                    return r.read()
            except urllib.error.HTTPError as e:
                if e.code == 404:
                    return None  # a missing key is an answer, not a blip
                raise

        return retry_call(go, op=op, policy=self.retry_policy)

    def kv_put(self, key: str, value: str):
        self._kv_req(f"/kv/{key}", "PUT", value.encode(), op=f"kv.put {key}")

    def kv_get(self, key: str) -> str | None:
        out = self._kv_req(f"/kv/{key}", op=f"kv.get {key}")
        return None if out is None else out.decode()

    def kv_del(self, key: str):
        try:
            self._kv_req(f"/kv/{key}", "DELETE", op=f"kv.del {key}")
        except Exception:
            pass

    def kv_list(self, prefix: str) -> dict:
        out = self._kv_req(f"/kvlist/{prefix}", op=f"kv.list {prefix}")
        return {} if out is None else json.loads(out)

    def kv_max(self, key: str, value: int) -> int:
        # the server applies max(current, value) under ITS lock — one
        # process owns the counter, so this transport cannot regress it
        out = self._kv_req(f"/kvmax/{key}", "PUT", str(int(value)).encode(),
                           op=f"kv.max {key}")
        return int(out)

    def kv_counter(self, key: str) -> int:
        try:
            return int(self.kv_get(key) or 0)
        except ValueError:
            return 0


@dataclasses.dataclass
class RendezvousResult:
    """Outcome of one re-rendezvous barrier pass for this node."""
    generation: int
    rank: int          # contiguous node rank in the new world; -1 = spare
    world: int         # new node count
    hosts: list        # sorted surviving node ids, rank order


class ElasticManager:
    """Membership watcher + scale decisions (reference manager.py:125).

    Decision table (watch()):
      membership == np, unchanged            → HOLD
      changed, min_np <= n, n != np          → RESTART (scale to n)
      n < min_np for < elastic_timeout       → HOLD (wait for rejoin)
      n < min_np for >= elastic_timeout      → ERROR (give up)
    FAULT_TOLERANCE (min==max) never scales: a lost node is HOLD until
    rejoin or timeout→ERROR; the restart budget is the launcher's.
    """

    def __init__(self, node_id: str, np: int, min_np: int | None = None,
                 max_np: int | None = None, registry=None,
                 root: str = "/tmp/paddle_tpu_elastic", job_id: str = "default",
                 heartbeat_interval: float = 2.0, elastic_timeout: float = 120.0):
        self.node_id = node_id
        self.np = np
        self.min_np = min_np or np
        self.max_np = max_np or np
        self.level = (ElasticLevel.ELASTIC if self.min_np != self.max_np
                      else ElasticLevel.FAULT_TOLERANCE)
        self.registry = registry or FileRegistry(root, job_id)
        self.interval = heartbeat_interval
        self.elastic_timeout = elastic_timeout
        self._stop = threading.Event()
        self._thread = None
        self._last_membership: tuple | None = None  # None = never observed
        self._below_min_since: float | None = None
        self.generation = 0  # fleet generation; bumped by re_rendezvous

    # ---- lifecycle ----
    def start(self):
        # the first heartbeat may race a KV master that is still coming up
        # on node 0 — retry under a deadline budget before giving up
        from ..resilience.retry import RetryPolicy, retry_call
        # should_retry overrides classify: the registry's OWN small retry
        # budget raises DeadlineExceeded (normally fatal) well inside
        # elastic_timeout, and this outer loop must keep trying anyway
        retry_call(self.registry.heartbeat, self.node_id,
                   op=f"elastic.first-heartbeat {self.node_id}",
                   policy=RetryPolicy(max_attempts=0,
                                      base_delay=min(self.interval, 0.5),
                                      max_delay=self.interval,
                                      deadline=self.elastic_timeout),
                   should_retry=lambda e: True)

        # adopt the fleet's current generation (a node joining after a
        # reform must not speak with generation 0 — it would be fenced)
        try:
            self.generation = max(self.generation, self._gen())
        except Exception:
            pass

        def beat():
            while not self._stop.wait(self.interval):
                try:
                    self.registry.heartbeat(self.node_id)
                except Exception:
                    pass

        self._thread = threading.Thread(target=beat, daemon=True)
        self._thread.start()

    def stop(self):
        self._stop.set()
        self.registry.leave(self.node_id)

    # ---- decisions (reference manager.py watch loop) ----
    def watch(self) -> ElasticStatus:
        alive = tuple(self.registry.alive_nodes())
        if self.node_id not in alive:
            # our own heartbeat thread keeps us registered, so a read that
            # lacks us is an unreliable/transient registry read (KV timeout
            # returns []) — don't let it masquerade as a membership change
            return ElasticStatus.HOLD
        prev = self._last_membership
        self._last_membership = alive
        n = len(alive)

        if n < self.min_np:
            now = time.time()
            if self._below_min_since is None:
                self._below_min_since = now
            if now - self._below_min_since >= self.elastic_timeout:
                return ElasticStatus.ERROR
            return ElasticStatus.HOLD
        self._below_min_since = None

        if prev is None:
            # first observation: baseline, never a restart decision
            if self.level == ElasticLevel.ELASTIC:
                self.np = min(n, self.max_np)
            return ElasticStatus.HOLD
        changed = alive != prev
        if self.level == ElasticLevel.FAULT_TOLERANCE:
            # fixed world: membership back at np → restart if it had changed
            if changed and n == self.np:
                return ElasticStatus.RESTART
            return ElasticStatus.HOLD
        # ELASTIC: scale to current membership when it settles inside range
        target = min(n, self.max_np)
        if changed and target != self.np:
            self.np = target
            return ElasticStatus.RESTART
        return ElasticStatus.HOLD

    def world_hosts(self):
        return list(self._last_membership or self.registry.alive_nodes())

    # ---- fleet observability plane discovery ----
    def publish_telemetry_endpoint(self, endpoint: str):
        """Advertise the rank-0 admin/telemetry endpoint (host:port) in the
        durable KV. Best-effort: the fleet runs fine blind."""
        try:
            self.registry.kv_put(TELEMETRY_KEY, endpoint)
        except Exception:
            pass

    def telemetry_endpoint(self) -> str | None:
        try:
            return self.registry.kv_get(TELEMETRY_KEY)
        except Exception:
            return None

    def rank_of(self, node_id: str | None = None) -> int:
        """Stable node rank = index in the sorted alive membership."""
        hosts = self.world_hosts()
        nid = node_id or self.node_id
        return hosts.index(nid) if nid in hosts else -1

    # ---- self-healing: the generation-numbered re-rendezvous barrier ----
    def behind_generation(self) -> bool:
        """True when the fleet's generation counter has advanced past ours —
        someone re-formed without us (we enrolled too late, or our published
        assignment was superseded). The launcher treats this as a reform
        trigger so every node converges on the newest barrier."""
        try:
            return self._gen() > self.generation
        except Exception:
            return False

    def _gen(self) -> int:
        """The fleet generation counter (kv_max-backed; monotone)."""
        reg = self.registry
        try:
            if hasattr(reg, "kv_counter"):
                return int(reg.kv_counter("gen"))
            return int(reg.kv_get("gen") or 0)
        except (ValueError, TypeError):
            return 0

    def _enrolled(self, gen: int) -> list:
        pfx = f"enroll.{gen}."
        return [k[len(pfx):] for k in self.registry.kv_list(pfx)]

    def _enroll(self, gen: int, t0: float, budget: float):
        """Re-enroll this node in generation `gen`. Chaos site
        ``elastic.enroll``: the barrier itself is the recovery boundary for
        a faulted enroll — pace and retry under the rendezvous budget."""
        from ..resilience import chaos
        from ..resilience.retry import DeadlineExceeded
        while True:
            try:
                chaos.hit("elastic.enroll")
                self.registry.kv_put(f"enroll.{gen}.{self.node_id}",
                                     json.dumps({"t": time.time()}))
                return
            except Exception as e:
                if time.monotonic() - t0 > budget:
                    raise DeadlineExceeded(f"elastic.enroll gen={gen}", 0,
                                           time.monotonic() - t0, last=e)
                _recorder.record("elastic.enroll_retry", gen=gen,
                                 error=f"{type(e).__name__}: {e}")
                time.sleep(min(self.interval, 0.2))  # resilience: ok (budget-bounded above; ChaosError must reach THIS boundary, so retry_call cannot own it)

    def _gc_generations(self, gen: int):
        """Best-effort cleanup of barrier state two generations behind —
        anything that old can never satisfy a live barrier (fenced)."""
        try:
            for prefix in ("enroll.", "assign."):
                for key in self.registry.kv_list(prefix):
                    head = key[len(prefix):].split(".", 1)[0]
                    if head.isdigit() and int(head) <= gen - 2:
                        self.registry.kv_del(key)
            if hasattr(self.registry, "kv_max_gc"):
                # drop stale generation markers too (floor <= current gen
                # keeps the counter's max intact)
                self.registry.kv_max_gc("gen", gen - 1)
        except Exception:
            pass

    def re_rendezvous(self, reason: str = "membership-change",
                      join_window: float | None = None,
                      budget: float | None = None) -> RendezvousResult:
        """One pass of the survivor barrier: propose/join the next fleet
        generation, re-enroll, and adopt the leader's rank assignment.

        Every survivor (and every restarted node) calls this concurrently.
        The generation is a max-CAS counter, so concurrent proposals
        converge; a barrier superseded mid-flight (another failure bumped
        the generation again) is abandoned and the new one chased — the
        stale generation's state can never produce an assignment anyone
        adopts. The deterministic leader is the lowest enrolled node id; it
        publishes once the enrollment set has held still for `join_window`
        seconds and covers at least min_np nodes. Raises DeadlineExceeded
        when the fleet cannot re-form within `budget` (default
        elastic_timeout) — the min_np floor held too long.
        """
        from ..resilience.retry import DeadlineExceeded
        t0 = time.monotonic()
        budget = self.elastic_timeout if budget is None else float(budget)
        join = max(self.interval, 0.5) if join_window is None \
            else float(join_window)
        pace = min(max(self.interval / 4.0, 0.02), 0.25)
        result = None
        with _spans.span("elastic.rendezvous", cat="elastic", reason=reason,
                         node=self.node_id):
            # join an in-flight reform if one is newer than us; otherwise
            # propose the next generation (max-CAS: survivors converge)
            cur = self._gen()
            if cur > self.generation:
                gen = cur
            else:
                gen = self.registry.kv_max("gen", cur + 1)
            self._enroll(gen, t0, budget)
            last_seen: tuple | None = None
            stable_since = time.monotonic()
            while result is None:
                if time.monotonic() - t0 > budget:
                    raise DeadlineExceeded(
                        f"elastic.re_rendezvous gen={gen} "
                        f"(survivors below min_np={self.min_np}?)", 0,
                        time.monotonic() - t0)
                cur = self._gen()
                if cur > gen:
                    # superseded: a newer failure started a newer barrier —
                    # fence this one and chase the current generation
                    gen = cur
                    self._enroll(gen, t0, budget)
                    last_seen, stable_since = None, time.monotonic()
                    continue
                raw = self.registry.kv_get(f"assign.{gen}")
                if raw:
                    rec = json.loads(raw)
                    if self.node_id in rec["hosts"]:
                        result = rec
                        continue
                    if int(rec["world"]) >= self.max_np:
                        # the published world is already at max_np: we were
                        # capped out, not missed — adopt it in standby
                        # (rank -1) instead of forcing a new barrier the cap
                        # would exclude us from again (livelock)
                        result = rec
                        continue
                    # published without us while below the cap — the leader
                    # missed our enrollment; force the next generation so
                    # the fleet re-forms around us too
                    self.registry.kv_max("gen", gen + 1)
                    continue
                enrolled = tuple(sorted(self._enrolled(gen)))
                if enrolled != last_seen:
                    last_seen, stable_since = enrolled, time.monotonic()
                if enrolled and enrolled[0] == self.node_id \
                        and time.monotonic() - stable_since >= join \
                        and len(enrolled) >= self.min_np:
                    hosts = list(enrolled[: self.max_np])
                    self.registry.kv_put(f"assign.{gen}", json.dumps({
                        "gen": gen, "hosts": hosts, "world": len(hosts),
                        "leader": self.node_id, "reason": reason,
                        "t": time.time()}))
                    continue  # adopt through the same read path as followers
                time.sleep(pace)

        gen = int(result["gen"])
        hosts = list(result["hosts"])
        rank = hosts.index(self.node_id) if self.node_id in hosts else -1
        self.generation = gen
        self.np = len(hosts)
        # re-baseline membership: the next watch() observation starts fresh
        # instead of re-firing RESTART on the world we just formed
        self._last_membership = None
        self._below_min_since = None
        elapsed = time.monotonic() - t0
        _metrics.gauge("elastic.regen").set(gen)
        _metrics.histogram("elastic.rejoin_s").observe(elapsed)
        _recorder.record(
            "elastic.regen", echo=True,
            message=f"[elastic] re-rendezvous complete: gen={gen} "
                    f"world={len(hosts)} rank={rank} ({elapsed:.2f}s, "
                    f"reason: {reason})",
            gen=gen, world=len(hosts), rank=rank, reason=reason,
            rejoin_s=round(elapsed, 3))
        self._gc_generations(gen)
        return RendezvousResult(gen, rank, len(hosts), hosts)
