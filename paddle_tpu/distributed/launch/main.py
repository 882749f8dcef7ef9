"""python -m paddle_tpu.distributed.launch — multi-host process launcher.

Reference: /root/reference/python/paddle/distributed/launch/main.py:23 +
controllers/ (pod build, env contract PADDLE_TRAINER_ID/_ENDPOINTS/_MASTER,
watch/restart loop, master KV server or etcd) and fleet/elastic/ (etcd
membership, scale decisions).

TPU-native: on TPU pods there is ONE process per host (SPMD single-controller)
and the rendezvous is JAX's coordination service — so the launcher's job is:
set the env contract, start the local trainer process(es), supervise
(restart-on-failure, the reference's ControllerBase.watch), and on multi-host
point everyone at the coordinator. CPU multi-process simulation (`--nproc`)
spawns N local ranks for the multi-node-shaped tests (SURVEY.md §4).

Elastic: `--nnodes MIN:MAX` (reference syntax) turns on membership watching
via fleet.elastic — heartbeats over a shared dir (`--elastic_root`) or the
HTTP KV master (`--elastic_server host:port`; node 0 with `--elastic_server
auto` serves it in-process).

Self-healing: node death (heartbeat lapse) or a worker's REFORM_EXIT (75 —
"I hit a communication deadline, checkpointed, re-rendezvous me") triggers
the generation-numbered re-rendezvous barrier (fleet.elastic): survivors
re-enroll, the deterministic leader re-assigns contiguous ranks and the new
world size, and the pod relaunches under the new generation — workers
resume through the preemption-marker path, step-exact. A dead LOCAL worker
(non-zero exit that isn't a reform request) is restarted in place under the
--max_restarts budget instead of tearing the pod down. Consecutive reforms
widen the leader's join window exponentially (--join_window base), so a
flapping node can't make the fleet thrash. Workers inherit
PADDLE_ELASTIC_GEN / PADDLE_ELASTIC_ACTIVE / PADDLE_RESILIENT, and when
PADDLE_TRACE_DIR is set each rank gets its own subdirectory for
FLIGHT.json postmortems.

Fleet observability (observability.fleet / observability.admin): the
rank-0 launcher runs the aggregation plane — a TelemetryAggregator fed by
every rank's TelemetryClient (shared-dir JSONL under PADDLE_TELEMETRY_DIR,
or HTTP push to the exported PADDLE_TELEMETRY_ENDPOINT) and a live admin
endpoint (/metrics /snapshot /flight /health /ranks). On exit and on every
reform it leaves three artifacts under PADDLE_TRACE_DIR: the launcher's own
FLIGHT.json (now carrying the ranked per-rank step-time table), a merged
FLEET_FLIGHT.json folding every rank's flight, and FLEET_TRACE.json — one
clock-aligned chrome trace with a track per (node, rank) and straggler
attribution (fleet.straggler events name persistently slow ranks).
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time

__all__ = ["main", "launch"]

# resilience.loop.REFORM_EXIT without importing the heavy jax-backed module
# into the supervisor process
REFORM_RC = 75

# consecutive re-rendezvous passes (none separated by a stable stretch of
# running) before the launcher gives up named. Bounds the RUNNING→reform
# spin of a fleet that re-forms successfully but can never complete a step
# (relaunched workers reset their own in-process reform budgets, so the
# launcher must hold the line) — distinct from --max_restarts, which
# budgets worker FAILURES.
MAX_CONSEC_REFORMS = 8


def _parse(argv):
    p = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    p.add_argument("--master", default=os.environ.get("PADDLE_MASTER"),
                   help="coordinator address host:port")
    p.add_argument("--nnodes", default=os.environ.get("PADDLE_NNODES", "1"),
                   help="node count N, or elastic range MIN:MAX")
    p.add_argument("--rank", type=int, default=int(os.environ.get("PADDLE_NODE_RANK", "-1")))
    p.add_argument("--nproc_per_node", "--nproc", type=int, default=1,
                   help="local processes (1 on TPU hosts; N for CPU simulation)")
    p.add_argument("--devices", default=None)
    p.add_argument("--log_dir", default=None)
    p.add_argument("--max_restarts", type=int, default=0,
                   help="restart budget on non-zero exit (elastic-lite)")
    p.add_argument("--run_mode", default="collective")
    p.add_argument("--job_id", default="default")
    p.add_argument("--elastic_root", default="/tmp/paddle_tpu_elastic",
                   help="shared dir for heartbeat files (FileRegistry)")
    p.add_argument("--elastic_server", default=None,
                   help="HTTP KV master host:port, or 'auto' (node 0 "
                        "serves); a comma-separated host:port list is a "
                        "replicated peer set — registry ops then commit "
                        "on a majority (ISSUE 12)")
    p.add_argument("--kv_replicas", type=int,
                   default=int(os.environ.get("PADDLE_KV_REPLICAS", "1")
                               or 1),
                   help="with --elastic_server auto: spawn this many "
                        "registry peers in-process (supervised; a dead "
                        "peer restarts on its port and catches up from a "
                        "majority snapshot). 1 = the single KV master")
    p.add_argument("--elastic_timeout", type=float, default=120.0)
    p.add_argument("--heartbeat_interval", type=float, default=2.0)
    p.add_argument("--join_window", type=float, default=1.0,
                   help="base leader stability window for re-rendezvous; "
                        "doubles per consecutive reform (exponential "
                        "node-join window)")
    p.add_argument("training_script")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)

    nn = str(args.nnodes)
    if ":" in nn:
        lo, _, hi = nn.partition(":")
        args.min_nodes, args.max_nodes = int(lo), int(hi)
        args.nnodes = args.max_nodes
    else:
        args.nnodes = int(nn)
        args.min_nodes = args.max_nodes = args.nnodes
    return args


def _spawn(args, local_rank: int, world: int, base_rank: int, nnodes: int,
           node_id: str = "node", gen: int = 0, elastic_on: bool = False):
    env = dict(os.environ)
    rank = base_rank + local_rank
    env.update({
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(world),
        "PADDLE_LOCAL_RANK": str(local_rank),
        "PADDLE_NNODES": str(nnodes),
        "PADDLE_JOB_ID": args.job_id,
        # fleet generation: rpc messages are stamped with it (stale-world
        # fencing) and per-generation barriers key on it
        "PADDLE_ELASTIC_GEN": str(gen),
        # stable node identity (ranks are reassigned across generations)
        "PADDLE_NODE_ID": node_id,
    })
    # trainers wrap their step loops in the resilience protocol by default
    # (Engine.fit / ResilientLoop honor PADDLE_RESILIENT=0 to opt out)
    env.setdefault("PADDLE_RESILIENT", "1")
    if elastic_on:
        # blocking collective waits become deadline-bounded and a comm loss
        # exits REFORM_RC instead of wedging (resilience.loop)
        env["PADDLE_ELASTIC_ACTIVE"] = "1"
    trace = os.environ.get("PADDLE_TRACE_DIR")
    if trace:
        # one trace dir per (node, local rank), stable across generations —
        # every rank leaves its own FLIGHT.json for the postmortem
        env["PADDLE_TRACE_DIR"] = os.path.join(
            trace, f"{node_id}.{local_rank}")
    if args.master:
        env["PADDLE_MASTER"] = args.master
        host, _, port = args.master.partition(":")
        env.setdefault("MASTER_ADDR", host)
        if port:
            env.setdefault("MASTER_PORT", port)
    if args.nproc_per_node > 1:
        # CPU simulation: give each rank its own virtual device set. A chip
        # belongs to one process at a time, so several local ranks cannot
        # share one — say where they went, once per launch
        env.setdefault("JAX_PLATFORMS", "cpu")
        env.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
        if local_rank == 0:
            print(f"[launch] --nproc_per_node {args.nproc_per_node} > 1: "
                  f"local ranks run on JAX_PLATFORMS={env['JAX_PLATFORMS']} "
                  f"(one process per chip; use --nproc_per_node 1 on a TPU "
                  f"host)", file=sys.stderr)

    stdout = stderr = None
    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)
        stdout = open(os.path.join(args.log_dir, f"worker.{rank}.log"), "ab")
        stderr = subprocess.STDOUT
    cmd = [sys.executable, args.training_script] + args.training_script_args
    return subprocess.Popen(cmd, env=env, stdout=stdout, stderr=stderr)


def _make_elastic(args, node_id: str):
    from ..fleet.elastic import ElasticManager, FileRegistry, KVServer
    from ..fleet.replicated_kv import KVPeerSet, make_registry

    server = None
    ttl = 5 * args.heartbeat_interval
    if args.elastic_server:
        ep = args.elastic_server
        if ep == "auto":
            if (args.rank if args.rank >= 0 else 0) != 0:
                raise SystemExit(
                    "--elastic_server auto is only valid on node 0; pass the "
                    "master's host:port (or the peer list) on other nodes")
            host = (args.master or "127.0.0.1").partition(":")[0]
            if args.kv_replicas > 1:
                # the replicated control plane (ISSUE 12): N supervised
                # in-process peers — a dead one restarts on its own port
                # and catches up from a majority snapshot, and every
                # registry op below commits on a majority, so no single
                # peer is load-bearing anymore
                from ...utils import env_flags as _flags
                wal_dir = _flags.get("PADDLE_KV_WAL_DIR") or None
                server = KVPeerSet(args.kv_replicas, ttl=ttl,
                                   host=host, wal_dir=wal_dir).start()
                ep = ",".join(server.endpoints)
                print(f"[launch] elastic KV peers at {ep} "
                      f"(majority {args.kv_replicas // 2 + 1}/"
                      f"{args.kv_replicas})", file=sys.stderr)
            else:
                server = KVServer(ttl=ttl).start()
                ep = f"{host}:{server.port}"
                print(f"[launch] elastic KV master at {ep}",
                      file=sys.stderr)
            # children (and serving replicas spawned under them) find the
            # same control plane without re-plumbing their own flags
            os.environ["PADDLE_KV_PEERS"] = ep
        registry = make_registry(ep, ttl=ttl)
    elif os.environ.get("PADDLE_KV_PEERS"):
        registry = make_registry(os.environ["PADDLE_KV_PEERS"], ttl=ttl)
    else:
        registry = FileRegistry(args.elastic_root, args.job_id, ttl=ttl)
    mgr = ElasticManager(
        node_id, np=args.nnodes, min_np=args.min_nodes, max_np=args.max_nodes,
        registry=registry, heartbeat_interval=args.heartbeat_interval,
        elastic_timeout=args.elastic_timeout)
    mgr.start()
    return mgr, server


def _telemetry_active(args) -> bool:
    """The aggregation plane runs when telemetry is configured explicitly
    (PADDLE_TELEMETRY_DIR / PADDLE_TELEMETRY=1) or the launcher owns more
    than one local rank (the common mp-simulation case). PADDLE_TELEMETRY=0
    always wins."""
    if os.environ.get("PADDLE_TELEMETRY") == "0":
        return False
    return bool(os.environ.get("PADDLE_TELEMETRY_DIR")
                or os.environ.get("PADDLE_TELEMETRY") == "1"
                or args.nproc_per_node > 1)


def _telemetry_start(args, node_id, mgr):
    """Rank-0 only: start the TelemetryAggregator + admin endpoint, wire
    the report transport (shared-dir poll, or exported HTTP endpoint), and
    advertise the endpoint (endpoint file in the telemetry dir + elastic
    durable KV) so peers and tools can find it."""
    from ...observability import fleet as _fleet
    from ...observability.admin import AdminServer, write_endpoint_file
    agg = _fleet.TelemetryAggregator()
    try:
        port = int(os.environ.get("PADDLE_TELEMETRY_ADMIN_PORT", "0") or 0)
    except ValueError:
        port = 0
    admin = AdminServer(port=port, aggregator=agg).start()
    host = (args.master or "").partition(":")[0]
    if not host:
        # no --master (FileRegistry-over-NFS fleets): advertise this
        # host's address, not a loopback a peer node can't reach
        import socket
        try:
            host = socket.gethostbyname(socket.gethostname())
        except OSError:
            host = "127.0.0.1"
    ep = f"{host}:{admin.port}"
    tdir = os.environ.get("PADDLE_TELEMETRY_DIR")
    if tdir:
        agg.watch_dir(tdir)
        try:
            write_endpoint_file(tdir, ep, node=node_id)
        except OSError:
            pass
    else:
        # children of THIS launcher push straight to the admin server
        os.environ["PADDLE_TELEMETRY_ENDPOINT"] = f"127.0.0.1:{admin.port}"
    if mgr is not None:
        mgr.publish_telemetry_endpoint(ep)
    # ISSUE 6: external sink + trigger-driven deep capture ride with the
    # aggregation plane. Exporter only when PADDLE_METRICS_EXPORT_URL is
    # set; triggers unless PADDLE_TRIGGERS=0 (cheap background poll that
    # reacts to stragglers / reported slo.breach / watchdog.near_deadline
    # by arming an XPlane window on the offending rank via post_command).
    from ...observability import exporters as _exporters, \
        metrics as _metrics, triggers as _triggers

    def _export_blocks():
        # the launcher's own registry PLUS every fresh rank's reported
        # snapshot, labeled (node, rank) — aggregated fleet metrics leave
        # the pod, not just the aggregator process's counters
        return ([({"node": node_id, "role": "launcher"},
                  _metrics.snapshot())]
                + agg.export_blocks())

    exporter = _exporters.maybe_from_env(
        labels={"node": node_id, "role": "launcher"},
        blocks_fn=_export_blocks)
    trig = None
    if _triggers.enabled():
        trig = _triggers.TriggerEngine(aggregator=agg).start()
    print(f"[launch] telemetry admin at {ep}", file=sys.stderr)
    return {"agg": agg, "admin": admin, "dir": tdir,
            "exporter": exporter, "triggers": trig}


def _telemetry_close(telem):
    """Leave the fleet artifacts behind (merged trace + merged flight) and
    shut the plane down. Never raises — observability must not turn a clean
    exit into a failure."""
    if telem is None:
        return
    try:
        if telem["dir"]:
            # catch the final reports: peers on OTHER launchers (the slow
            # rank especially) may still be force-pushing their last span
            # batch while this launcher's own child already exited
            telem["agg"].scan_dir(telem["dir"])
            time.sleep(1.0)  # resilience: ok (bounded exit grace, not a retry loop)
            telem["agg"].scan_dir(telem["dir"])
        trace = os.environ.get("PADDLE_TRACE_DIR")
        if trace:
            from ...observability import fleet as _fleet
            telem["agg"].merged_chrome_trace(
                os.path.join(trace, _fleet.FLEET_TRACE_NAME))
            _fleet.merge_flight_files(trace)
    except Exception:
        pass
    try:
        if telem.get("triggers") is not None:
            telem["triggers"].stop()
        if telem.get("exporter") is not None:
            telem["exporter"].stop()  # final flush to the external sink
    except Exception:
        pass
    try:
        telem["agg"].stop()
        telem["admin"].stop()
    except Exception:
        pass


def _stop_procs(procs, grace: float = 5.0):
    """Terminate children, escalating to SIGKILL after `grace`.

    Escalation is NOT optional: trainers that ran jax.distributed install a
    preemption notifier that CATCHES SIGTERM (it's a graceful-shutdown
    signal to jax), so terminate() alone leaves them running — observed as
    orphaned trainers holding the coordination-service port and crashing
    the relaunched world with 'different incarnation' fatals."""
    for p in procs:
        if p.poll() is None:
            p.terminate()
    deadline = time.time() + grace
    while time.time() < deadline and any(p.poll() is None for p in procs):
        time.sleep(0.2)
    for p in procs:
        if p.poll() is None:
            p.kill()
    for p in procs:
        try:
            p.wait(timeout=5)
        except Exception:
            pass


def launch(argv=None):
    import socket

    args = _parse(argv if argv is not None else sys.argv[1:])
    node_rank = args.rank if args.rank >= 0 else 0
    elastic_on = args.min_nodes != args.max_nodes
    # node identity must be unique per host even when --rank is omitted
    # (a shared default would collapse elastic membership to one node)
    node_id = os.environ.get("PADDLE_NODE_ID") or (
        f"node-{args.rank}" if args.rank >= 0
        else f"{socket.gethostname()}-{os.getpid()}")

    mgr = server = None
    if elastic_on:
        from ..fleet.elastic import ElasticStatus
        mgr, server = _make_elastic(args, node_id)

    nnodes = args.nnodes
    restarts = 0
    reform_streak = 0  # consecutive reforms; widens the join window
    have_assignment = False  # re_rendezvous already fixed (rank, world)
    procs: list = []
    stop_sig = {"sig": None}
    telem_box = {"t": None}  # rank-0 aggregation plane (started lazily)

    def on_term(sig, _frm):
        # record and let the supervision/wait loops stop the pod AND the
        # launcher (terminating only children leaves launchers lingering
        # when children swallow SIGTERM; dying instantly skips _stop_procs)
        stop_sig["sig"] = sig

    def _dump_launcher_flight(reason):
        if not os.environ.get("PADDLE_TRACE_DIR"):
            return
        try:
            from ...observability import recorder
            telem = telem_box["t"]
            if telem is not None:
                # the ranked per-rank step-time table rides in every
                # launcher flight dump: reform postmortems name the slow
                # rank without re-deriving it
                try:
                    recorder.record("fleet.step_table", reason=reason,
                                    table=telem["agg"].step_time_table(),
                                    stragglers=telem["agg"].straggler_events)
                except Exception:
                    pass
            recorder.dump_flight(
                os.path.join(os.environ["PADDLE_TRACE_DIR"],
                             f"{node_id}.launcher"), reason=reason)
            if telem is not None:
                from ...observability import fleet as _fleet
                _fleet.merge_flight_files(os.environ["PADDLE_TRACE_DIR"])
        except Exception:
            pass

    signal.signal(signal.SIGTERM, on_term)
    try:
        while True:
            if stop_sig["sig"] is not None:  # SIGTERM during a restart path
                return 128 + int(stop_sig["sig"])
            if mgr is not None and not have_assignment:
                # wait until ≥ min_nodes members are up AND our own heartbeat
                # is visible with an in-range rank; a node beyond max_np is a
                # spare and stays in standby until membership changes. Hold
                # one extra join window once quorum is met so a whole fleet
                # booting together starts at full strength instead of
                # spawning at min_np and immediately reforming.
                deadline = time.time() + args.elastic_timeout
                stable_since = time.time()
                prev_hosts = None
                while True:
                    if stop_sig["sig"] is not None:
                        return 128 + int(stop_sig["sig"])
                    mgr.watch()
                    nnodes = max(args.min_nodes, min(mgr.np, args.max_nodes))
                    rank = mgr.rank_of(node_id)
                    hosts = tuple(mgr.world_hosts())
                    if hosts != prev_hosts:
                        prev_hosts, stable_since = hosts, time.time()
                    if len(hosts) >= args.min_nodes and 0 <= rank < nnodes \
                            and (len(hosts) >= args.max_nodes
                                 or time.time() - stable_since
                                 >= args.join_window):
                        break
                    if rank >= nnodes:
                        deadline = time.time() + args.elastic_timeout  # spare
                    if time.time() > deadline:
                        print("[launch] elastic: not enough nodes (or own "
                              "heartbeat never registered)", file=sys.stderr)
                        return 1
                    time.sleep(args.heartbeat_interval)
                node_rank = rank
            have_assignment = False
            if telem_box["t"] is None and node_rank == 0 \
                    and _telemetry_active(args):
                # rank 0 owns the fleet aggregation plane (started once;
                # survives reforms — ranks are re-reported under the new
                # generation)
                try:
                    telem_box["t"] = _telemetry_start(args, node_id, mgr)
                except Exception as e:
                    print(f"[launch] telemetry plane failed to start ({e}); "
                          f"running blind", file=sys.stderr)
            world = nnodes * args.nproc_per_node
            base = node_rank * args.nproc_per_node
            gen = mgr.generation if mgr is not None else 0
            # append as we spawn: if _spawn rank k raises, ranks 0..k-1 are
            # already in `procs` and the finally's _stop_procs reaps them
            # (a discarded list-comprehension would orphan them)
            procs.clear()
            for i in range(args.nproc_per_node):
                procs.append(_spawn(args, i, world, base, nnodes,
                                    node_id=node_id, gen=gen,
                                    elastic_on=elastic_on))
            spawned_at = time.monotonic()

            # supervision loop (reference controller.py:87 watch)
            failed = None
            reform_reason = None
            while True:
                if stop_sig["sig"] is not None:
                    _stop_procs(procs)
                    return 128 + int(stop_sig["sig"])
                alive = 0
                for i, p in enumerate(procs):
                    prc = p.poll()
                    if prc is None:
                        alive += 1
                    elif prc == REFORM_RC and mgr is not None:
                        # worker hit a communication deadline, checkpointed,
                        # and asks for a fleet re-rendezvous — not a failure
                        if reform_reason is None:
                            reform_reason = (f"worker {base + i} requested "
                                             f"reform (rc={REFORM_RC})")
                    elif prc != 0 and failed is None:
                        # (a REFORM_RC without an elastic manager is a plain
                        # failure — nobody can re-rendezvous it)
                        if mgr is not None and restarts < args.max_restarts \
                                and args.nproc_per_node == 1:
                            # self-heal locally: restart JUST the dead
                            # worker instead of tearing the job down. Only
                            # coherent for single-worker pods — a lone
                            # respawn into a half-live multi-rank pod would
                            # face peers blocked mid-collective on the dead
                            # incarnation.
                            restarts += 1
                            print(f"[launch] elastic: local worker "
                                  f"{base + i} died (exit {prc}); restart "
                                  f"in place {restarts}/{args.max_restarts}",
                                  file=sys.stderr)
                            procs[i] = _spawn(args, i, world, base, nnodes,
                                              node_id=node_id, gen=gen,
                                              elastic_on=elastic_on)
                            alive += 1
                        elif mgr is not None \
                                and restarts < args.max_restarts:
                            # multi-rank pod: re-form it whole (checkpoint
                            # resume keeps this cheap) under the same
                            # budget. ONE charge per reform event — all
                            # ranks of one crash die in the same poll pass
                            # and must not each burn a restart unit.
                            if reform_reason is None:
                                restarts += 1
                                reform_reason = (
                                    f"local worker {base + i} died (exit "
                                    f"{prc}); pod reform "
                                    f"{restarts}/{args.max_restarts}")
                        else:
                            failed = prc
                if reform_reason is not None:
                    break
                if failed is not None:
                    _stop_procs(procs)
                    break
                if alive == 0:
                    _dump_launcher_flight("run complete")
                    return 0
                if mgr is not None:
                    st = mgr.watch()
                    if st is not None and st.value == "restart":
                        reform_reason = "membership changed"
                        break
                    if mgr.behind_generation():
                        # the fleet re-formed without us (we published or
                        # adopted an assignment a slower peer superseded) —
                        # chase the newest generation
                        reform_reason = "fleet generation advanced"
                        break
                    if st is not None and st.value == "error":
                        print("[launch] elastic: below min_np past timeout",
                              file=sys.stderr)
                        _stop_procs(procs)
                        _dump_launcher_flight("below min_np past timeout")
                        return 1
                time.sleep(0.5)  # resilience: ok (supervision poll; every exit is a named decision — reform, error, budget-exhausted failure, or clean completion)
            if reform_reason is not None:
                _stop_procs(procs)
                # exponential node-join window: a stretch of stable running
                # resets the streak; consecutive reforms double the leader's
                # stability wait so a flapping node can't thrash the fleet
                if time.monotonic() - spawned_at \
                        > 20 * args.heartbeat_interval:
                    reform_streak = 0
                join = args.join_window * (2 ** min(reform_streak, 4))
                reform_streak += 1
                if reform_streak > MAX_CONSEC_REFORMS:
                    print(f"[launch] elastic: {reform_streak} consecutive "
                          f"reforms without a stable run — the fleet "
                          f"re-forms but never makes progress; giving up",
                          file=sys.stderr)
                    _dump_launcher_flight("reform streak exhausted")
                    return 1
                print(f"[launch] elastic: {reform_reason} → re-rendezvous "
                      f"(gen {mgr.generation} → ?, join window {join:.1f}s)",
                      file=sys.stderr)
                try:
                    res = mgr.re_rendezvous(reason=reform_reason,
                                            join_window=join)
                except Exception as e:
                    print(f"[launch] elastic: re-rendezvous failed ({e})",
                          file=sys.stderr)
                    _dump_launcher_flight(f"re-rendezvous failed: {e}")
                    return 1
                _dump_launcher_flight(
                    f"re-rendezvous: gen={res.generation} rank={res.rank}")
                if res.rank < 0:
                    print("[launch] elastic: standby (spare beyond max_np)",
                          file=sys.stderr)
                    continue  # back to the quorum wait
                node_rank, nnodes = res.rank, res.world
                have_assignment = True
                print(f"[launch] elastic: membership changed → relaunch at "
                      f"np={res.world} gen={res.generation} rank={res.rank}",
                      file=sys.stderr)
                continue
            if mgr is None and restarts < args.max_restarts:
                restarts += 1
                print(f"[launch] rank failed (exit {failed}); restart "
                      f"{restarts}/{args.max_restarts}", file=sys.stderr)
                continue
            return failed or 1
    finally:
        _stop_procs(procs)  # never orphan trainers past the launcher
        _telemetry_close(telem_box["t"])  # FLEET_TRACE + FLEET_FLIGHT land
        if mgr is not None:
            mgr.stop()
        if server is not None:
            server.stop()


def main():
    sys.exit(launch())


if __name__ == "__main__":
    main()
