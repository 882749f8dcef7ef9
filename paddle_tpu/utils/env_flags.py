"""The PADDLE_* environment-flag registry: one declaration per flag.

Every ``PADDLE_*`` env var the runtime reads is declared here with its
default and a one-line doc — the single inventory the static analyzer
(``tools/analyze`` rule A4) checks every flag-shaped literal in the tree
against, and the source the README "Environment flags" reference table is
generated from (``python -m tools.analyze --env-table``). Before this
registry existed, ~60 flags were read ad-hoc and a typo'd env var failed
OPEN: the default silently applied and nothing ever reported the dead
knob. Now an undeclared (or edit-distance-1 mistyped) flag name anywhere
in the tree is a lint finding.

Declaring is the contract; call sites MAY keep their existing
``os.environ.get`` reads (the analyzer matches names, not call forms) or
use :func:`get` / :func:`get_bool` here for the documented default.

Import-light on purpose: stdlib only, no paddle_tpu imports — both the
runtime and the (jax-free) analyzer tooling can load it.
"""
from __future__ import annotations

import os
from dataclasses import dataclass

__all__ = ["EnvFlag", "FLAGS", "declare", "declared", "get", "get_bool",
           "get_float", "get_int", "table_rows"]


@dataclass(frozen=True)
class EnvFlag:
    name: str
    default: str
    doc: str


FLAGS: dict[str, EnvFlag] = {}


def declare(name: str, default: str, doc: str) -> str:
    """Register one flag (name, default-as-string, one-line doc). Returns
    the name so modules can bind constants: ENV_X = declare("PADDLE_X",...)."""
    if name in FLAGS:
        raise ValueError(f"env flag {name} declared twice")
    FLAGS[name] = EnvFlag(name, default, doc)
    return name


def declared(name: str) -> bool:
    return name in FLAGS


def get(name: str, default: str | None = None) -> str:
    """The env value, else the explicit default, else the DECLARED default.
    Unknown names raise — reads through this helper cannot typo."""
    if name not in FLAGS:
        raise KeyError(f"undeclared env flag {name!r} — declare it in "
                       "paddle_tpu/utils/env_flags.py")
    v = os.environ.get(name)
    if v is not None:
        return v
    return FLAGS[name].default if default is None else default


def get_bool(name: str) -> bool:
    return get(name).lower() in ("1", "true", "yes", "on")


def get_float(name: str) -> float:
    try:
        return float(get(name) or 0)
    except ValueError:
        return float(FLAGS[name].default or 0)


def get_int(name: str) -> int:
    try:
        return int(get(name) or 0)
    except ValueError:
        return int(FLAGS[name].default or 0)


def table_rows() -> list[tuple[str, str, str]]:
    """(name, default, doc) sorted by name — the README table's source."""
    return [(f.name, f.default, f.doc) for _, f in sorted(FLAGS.items())]


# ---------------------------------------------------------------- identity

declare("PADDLE_JOB_ID", "default",
        "job identity scoping rpc/elastic/admin auth tokens and KV spaces")
declare("PADDLE_NODE_ID", "",
        "stable node identity (launcher-assigned; telemetry/elastic keys)")
declare("PADDLE_NODE_RANK", "-1",
        "node rank for the launcher (-1 = take from --rank/registry)")
declare("PADDLE_NNODES", "1",
        "node count (launcher; supports min:max elastic ranges)")
declare("PADDLE_LOCAL_RANK", "0",
        "process-local rank on this node")
declare("PADDLE_TRAINER_ID", "0",
        "global trainer rank of this process")
declare("PADDLE_TRAINERS_NUM", "1",
        "global world size (trainer count)")
declare("PADDLE_TRAINER_ENDPOINTS", "",
        "comma-separated endpoints of every trainer (reference parity)")
declare("PADDLE_CURRENT_ENDPOINT", "",
        "this trainer's own endpoint (reference parity)")
declare("PADDLE_DIST_INITIALIZED", "",
        "set to '1' by init_parallel_env once distributed init has run")
declare("PADDLE_MASTER", "",
        "master endpoint host:port for elastic/rpc rendezvous")

# --------------------------------------------------------------- transport

declare("PADDLE_RPC_SECRET", "",
        "shared secret for rpc/elastic-KV/admin write auth (real boundary; "
        "without it the job-id-derived token only stops accidents)")
declare("PADDLE_RPC_BIND_HOST", "",
        "explicit rpc server bind interface (default: derive from master)")
declare("PADDLE_RPC_TIMEOUT", "300",
        "rpc rendezvous deadline in seconds")
declare("PADDLE_RPC_DEBUG", "",
        "'1' records rpc rendezvous debug events to the flight recorder")

# -------------------------------------------------------------- resilience

declare("PADDLE_CHAOS", "",
        "deterministic fault injection spec 'site:sel[,site:sel...]' "
        "(sel: N exact | N+ from | pP probability); off when unset")
declare("PADDLE_CHAOS_SEED", "0",
        "seed for probabilistic chaos selectors (reruns reproduce exactly)")
declare("PADDLE_CKPT_DIR", "",
        "checkpoint directory; when set, Engine.fit routes through "
        "ResilientLoop (restore + bitwise replay)")
declare("PADDLE_CKPT_KEEP", "0",
        "garbage-collect checkpoint generations older than the newest K "
        "published ones (0 = keep everything)")
declare("PADDLE_CKPT_VERIFY", "1",
        "save-side crc read-back verify of every renamed shard "
        "('0' disables)")
declare("PADDLE_RESILIENT", "1",
        "'0' opts Engine.fit out of the ResilientLoop routing")
declare("PADDLE_PREEMPT_GRACE_S", "0",
        "SIGTERM grace budget in seconds for the emergency save")
declare("PADDLE_ELASTIC_ACTIVE", "",
        "'1' under elastic supervision: collective waits become "
        "deadline-bounded and the watchdog defers to re-rendezvous")
declare("PADDLE_ELASTIC_GEN", "0",
        "current re-rendezvous generation (rpc generation fencing)")
declare("PADDLE_WATCHDOG_WARN_FRAC", "0.75",
        "fraction of the comm-watchdog abort budget at which the "
        "near-deadline warn signal fires")
declare("PADDLE_KV_PEERS", "",
        "comma-separated replicated-registry peer endpoints "
        "(host:port,...); >1 peer = quorum-replicated KV master, "
        "empty/1 = the single-master pre-replication topology")
declare("PADDLE_KV_QUORUM_TIMEOUT_S", "5",
        "budget for one replicated-registry op to reach majority ack "
        "before it raises the typed NoQuorumError")
declare("PADDLE_KV_REPLICAS", "1",
        "registry peer count the launcher spawns with --elastic_server "
        "auto (in-process peer set, supervised + snapshot catch-up)")
declare("PADDLE_KV_WAL_DIR", "",
        "directory for per-peer replicated-registry write-ahead files "
        "(peer<i>.wal): committed mutations are fsynced and replayed on "
        "restart, so a majority simultaneous crash loses no acked write "
        "(empty = memory-only peers, the pre-WAL behavior)")

# ----------------------------------------------------------- observability

declare("PADDLE_TRACE_DIR", "",
        "turns the span export on; chrome traces, FLIGHT.json and capture "
        "artifacts land here (launcher fans out per-(node,rank) subdirs)")
declare("PADDLE_TRACE_MAX_EVENTS", "100000",
        "span ring bound while the export is on (8192 otherwise); the "
        "oldest spans fall off and are counted as dropped")
declare("PADDLE_FLIGHT_RECORDER", "512",
        "flight-recorder ring capacity ('0'/'off' disables)")
declare("PADDLE_METRICS_SINK", "",
        "per-step metrics sink path (.csv or .jsonl)")
declare("PADDLE_PROFILER_DIR", "/tmp/paddle_tpu_trace",
        "profiler chrome-trace export directory")
declare("PADDLE_XPLANE_DIR", "",
        "XPlane (jax.profiler) dump dir; enables the env-configured "
        "capture window, whose trace holds the program's spans beside the "
        "device's operations")
declare("PADDLE_XPLANE_START", "2",
        "first step of the env XPlane window")
declare("PADDLE_XPLANE_STEPS", "2",
        "length in steps of the env XPlane window")

# ------------------------------------------------------------ fleet plane

declare("PADDLE_TELEMETRY", "",
        "'1' forces the fleet telemetry plane on, '0' kills it "
        "(default: on when a transport or nproc>1 says so)")
declare("PADDLE_TELEMETRY_DIR", "",
        "shared-directory telemetry transport (push.<node>.<rank>.jsonl)")
declare("PADDLE_TELEMETRY_ENDPOINT", "",
        "HTTP telemetry push endpoint (the rank-0 admin server)")
declare("PADDLE_TELEMETRY_INTERVAL", "0.5",
        "minimum seconds between telemetry pushes per rank")
declare("PADDLE_TELEMETRY_TIMEOUT", "1.0",
        "telemetry HTTP push timeout in seconds")
declare("PADDLE_TELEMETRY_STALE_S", "30",
        "ranks silent this long leave the fleet views (world count, "
        "straggler median)")
declare("PADDLE_TELEMETRY_ADMIN_PORT", "0",
        "fixed port for the rank-0 admin endpoint (0 = ephemeral)")
declare("PADDLE_ADMIN_READ_TOKEN", "",
        "when set, every admin GET requires this token (header or Bearer)")
declare("PADDLE_STRAGGLER_K", "2.0",
        "straggler threshold: compute-time multiplier over fleet median")
declare("PADDLE_STRAGGLER_CHECKS", "3",
        "consecutive over-threshold reports before a rank is named")

# ------------------------------------------------------------- SLO + export

declare("PADDLE_SLO_TTFT_S", "",
        "time-to-first-token SLO target in seconds (empty = no target)")
declare("PADDLE_SLO_TPOT_S", "",
        "per-output-token SLO target in seconds (empty = no target)")
declare("PADDLE_SLO_E2E_S", "",
        "end-to-end request SLO target in seconds (empty = no target)")
declare("PADDLE_SLO_QUEUE_S", "",
        "queue-wait SLO target in seconds (empty = no target)")
declare("PADDLE_METRICS_EXPORT_URL", "",
        "external metric sink URL (exporter off when unset)")
declare("PADDLE_METRICS_EXPORT_FORMAT", "prom",
        "'prom' text exposition or 'otlp' JSON (auto-otlp when the URL "
        "ends in /v1/metrics)")
declare("PADDLE_METRICS_EXPORT_INTERVAL", "10",
        "seconds between exporter pushes")
declare("PADDLE_METRICS_EXPORT_TIMEOUT", "2",
        "exporter HTTP timeout in seconds")
declare("PADDLE_TRIGGERS", "1",
        "'0' disables the trigger engine (auto deep-capture)")
declare("PADDLE_TRIGGER_COOLDOWN_S", "30",
        "minimum seconds between trigger-armed captures")
declare("PADDLE_TRIGGER_MAX_CAPTURES", "3",
        "maximum trigger-armed captures per process")
declare("PADDLE_TRIGGER_XPLANE_STEPS", "4",
        "steps per trigger-armed XPlane window")

# ----------------------------------------------------- distributed tracing

declare("PADDLE_REQTRACE", "1",
        "'0' disables fleet-wide per-request distributed tracing (span "
        "batches, /results piggy-back, router trace assembly); tail "
        "sampling bounds the always-on cost, tokens identical either way")
declare("PADDLE_REQTRACE_KEEP", "256",
        "bound on retained trace state per process: pending span batches "
        "on a replica, assembled traces in the router's retained ring")
declare("PADDLE_REQTRACE_WINDOW", "1024",
        "sliding window of recent request e2e samples the tail sampler's "
        "slowest-p99 threshold is computed over")

# ------------------------------------------------------- quantized numerics

declare("PADDLE_QUANT_ALLREDUCE", "0",
        "block-wise quantized allreduce wire format for gradient sync "
        "('int8' | 'fp8'; 0/off = full-precision collectives, the default)")
declare("PADDLE_QUANT_BLOCK", "256",
        "block size (elements per scale) for the quantized allreduce wire")
declare("PADDLE_SERVE_KV_DTYPE", "",
        "paged KV-cache page dtype ('int8' | 'fp8' store quantized pages "
        "+ per-row scales; ''/bf16 = pages in the model dtype, default)")

# ------------------------------------------------------------ paged serving

declare("PADDLE_SPEC_DECODE", "0",
        "'1' enables speculative decoding on the paged serving engine: a "
        "small draft model proposes PADDLE_SPEC_K tokens per slot and ONE "
        "target launch verifies them (accept-prefix, temp=0 "
        "token-identical; silently plain decode when unsupported)")
declare("PADDLE_SPEC_K", "4",
        "draft tokens proposed per slot per speculative step (the verify "
        "row carries k+1 positions; k is traced per slot, so mixed "
        "proposal counts share one executable)")
declare("PADDLE_SPEC_DRAFT_LAYERS", "0",
        "draft model depth: the target truncated to this many leading "
        "layers (0 = half the target's layers; == target layers is the "
        "self-draft used by tests for a deterministic 100% accept rate)")
declare("PADDLE_SPEC_DRAFT_PRECISION", "",
        "draft model weight precision: 'int8' serves the draft "
        "weight-only-quantized (near-free in HBM); '' = the target's "
        "weights as handed in")
declare("PADDLE_PREFIX_CACHE_PAGES", "0",
        "prefix-sharing cache size in pool pages (>0 enables the "
        "page-granular prefix-hash index: shared-prompt admissions map "
        "cached pages copy-on-write and prefill only their suffix; "
        "0 = off, the pre-sharing engine byte-for-byte)")
declare("PADDLE_SERVE_MESH_MODEL", "0",
        "shard the serving KV page pool over this many devices along the "
        "'model' mesh axis (GSPMD; 0/1 = single-chip)")

# ------------------------------------------------------------ serving fleet

declare("PADDLE_SERVE_REPLICAS", "0",
        "serving replica count for the fleet drill in "
        "benchmarks/serving_bench.py (0/1 = single-process bench only)")
declare("PADDLE_SERVE_TTL", "5",
        "serving replica lease TTL in seconds — a dead replica leaves the "
        "routing table within one TTL")
declare("PADDLE_SERVE_HEARTBEAT_S", "",
        "replica lease heartbeat interval (default: TTL / 4)")
declare("PADDLE_ADMIT_MAX_QUEUE", "0",
        "admission cap on queued-not-admitted requests per replica "
        "(0 = 4 x max_batch); beyond it requests reject with retry-after")
declare("PADDLE_ADMIT_QUEUE_P95_S", "",
        "admission rejects while measured queue-wait p95 exceeds this "
        "target in seconds (empty = queue latency never rejects)")
declare("PADDLE_ADMIT_E2E_P95_S", "",
        "admission rejects while measured request e2e p95 exceeds this "
        "target in seconds (empty = e2e latency never rejects)")
declare("PADDLE_ADMIT_RETRY_AFTER_S", "0.25",
        "floor / fallback retry_after_s hint on admission rejections")
declare("PADDLE_DRAIN_GRACE_S", "30",
        "drain grace in seconds: past it a draining replica sheds its "
        "still-queued remainder (in-flight slots always run to budget)")
declare("PADDLE_SERVE_RESULTS_KEEP", "4096",
        "finished results retained per replica for /results polling "
        "(prefix truncated past it, cursors stay monotone; 0 = unbounded; "
        "draining replicas never truncate)")

# ---------------------------------------------------- disaggregated serving

declare("PADDLE_SERVE_DISAGG", "0",
        "'1' runs benchmarks/serving_bench.py's disaggregated-fleet drill "
        "(prefill + decode pools behind a DisaggRouter) and populates the "
        "bench line's disagg sub-object")
declare("PADDLE_SERVE_ROLE", "",
        "this replica's pool role: 'prefill' | 'decode' | 'unified' "
        "(empty = unified, the single-pool pre-disagg replica)")
declare("PADDLE_SERVE_PREFILL_REPLICAS", "2",
        "prefill-pool size for the serving_bench disagg drill (decode "
        "pool = PADDLE_SERVE_REPLICAS - this, min 2 each)")
declare("PADDLE_SERVE_KV_SCALE_GRAN", "",
        "KV-page transfer wire scale granularity: 'row' (per-(row, head) "
        "pool scales verbatim — bit-exact, default) | 'page' (one scale "
        "per (page, head): ~page_size x fewer scale bytes, requantized)")
declare("PADDLE_SERVE_XFER_TIMEOUT_S", "15",
        "HTTP timeout for a KV page-transfer POST (/kv_transfer ships "
        "megabytes where a health probe ships a doc)")

# ----------------------------------------------------- elastic autoscaling

declare("PADDLE_AUTOSCALE", "0",
        "'1' runs the SLO-driven autoscale controller beside the router: "
        "prefill/decode pools grow on sustained breach and shrink (via "
        "drain) on sustained idle, independently per pool")
declare("PADDLE_AUTOSCALE_INTERVAL_S", "1.0",
        "controller observation-window length in seconds (one pool "
        "pressure sample + at most one decision per window per pool)")
declare("PADDLE_AUTOSCALE_BREACH_WINDOWS", "3",
        "hysteresis N: pool pressure must exceed the high water for this "
        "many consecutive windows before a scale-out")
declare("PADDLE_AUTOSCALE_IDLE_WINDOWS", "5",
        "hysteresis M: pool pressure must sit below the low water for "
        "this many consecutive windows before a scale-in")
declare("PADDLE_AUTOSCALE_HIGH_WATER", "1.0",
        "scale-out threshold on pool pressure (queued work / pool serving "
        "slots); >1.0 means a standing queue beyond capacity")
declare("PADDLE_AUTOSCALE_LOW_WATER", "0.1",
        "scale-in threshold on pool pressure — below it the pool is idle "
        "enough to drain its newest surplus replica")
declare("PADDLE_AUTOSCALE_COOLDOWN_S", "10",
        "per-pool cooldown after any decision: no further decision for "
        "this many seconds (the flapping bound, with hysteresis)")
declare("PADDLE_AUTOSCALE_MIN", "1",
        "per-pool floor: scale-in never drains below this many replicas")
declare("PADDLE_AUTOSCALE_MAX", "4",
        "per-pool ceiling: scale-out never spawns beyond this many "
        "replicas")
declare("PADDLE_AUTOSCALE_SLO", "0",
        "'1' adds the slo.* breach rate as a second scale-out trigger "
        "beside queue pressure: a pool whose requests breach their SLO "
        "targets inside a window counts a breach-window even when its "
        "queue looks healthy; each ledger entry records which signal "
        "fired ('pressure', 'slo', or 'pressure+slo')")
declare("PADDLE_AUTOSCALE_DRAIN_TIMEOUT_S", "60",
        "deadline for a scale-in drain: past it the stall is flight-"
        "recorded and the drain retried — never force-killed (in-flight "
        "work is never lost to the autoscaler)")
declare("PADDLE_WARMSTART", "0",
        "'1' enables warm scale-out: a new replica fetches the jit "
        "executable cache and weights from a live peer over HTTP instead "
        "of compiling/loading cold, then serves a warmup token before "
        "registering its lease")
declare("PADDLE_WARMSTART_CACHE_DIR", "",
        "this replica's jit persistent-cache directory (populated by "
        "jax's compilation cache; exported to peers via /warm_cache; "
        "empty = no persistent cache, cold compilation)")
declare("PADDLE_WARMSTART_PEER", "",
        "host:port of the peer replica to warm-start from (the "
        "controller passes the donor explicitly; empty = cold start)")
declare("PADDLE_WARMSTART_TIMEOUT_S", "20",
        "HTTP timeout for one warm-start fetch (/warm_cache or /weights "
        "— archives ship megabytes where a health probe ships a doc)")

# ------------------------------------------------------ request reliability

declare("PADDLE_REQUEST_DEADLINE_S", "",
        "default per-request deadline in seconds applied at submit when "
        "the client supplies none (empty = no deadline); the remaining "
        "budget rides every hop and an expired request retires typed "
        "'deadline_exceeded' with its pages freed")
declare("PADDLE_HEDGE_DELAY_S", "0",
        "floor (and enable switch) for the router's hedged re-dispatch "
        "delay in seconds: a dispatched stage stalled past "
        "max(this, stage p95) is re-posted to the next candidate and the "
        "loser cancelled on first completion (0 = hedging off)")
declare("PADDLE_RETRY_BUDGET_PCT", "10",
        "global hedge/retry budget as a percent of recent dispatches "
        "(token bucket): each normal dispatch earns pct/100 tokens, each "
        "hedge spends one — a sick fleet degrades to shedding, never a "
        "retry storm")
declare("PADDLE_SERVE_RELIABILITY", "0",
        "serving_bench gate: 1 runs the request-lifecycle reliability "
        "drill (deadline shed, mid-flight cancels, hedged re-dispatch "
        "against a 2-replica fleet) and the JSON line gains the "
        "'reliability' sub-object")

# ------------------------------------------------------------------- misc

declare("PADDLE_EXTENSION_DIR", "<tempdir>/paddle_tpu_extensions",
        "build/cache dir for cpp_extension artifacts")
declare("PADDLE_TPU_HUB_DIR", "~/.cache/paddle_tpu/hub",
        "paddle.hub download cache directory")
