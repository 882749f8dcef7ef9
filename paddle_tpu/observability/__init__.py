"""paddle_tpu.observability — unified runtime telemetry.

One substrate that every layer of the runtime reports through, replacing the
pre-PR-2 archipelago (comm_watchdog prints, resilience stderr lines, ad-hoc
``time.time()`` deltas, the distributed/metric island):

  spans    — THE span API (``span("train.step")`` context manager +
             decorator, ``add_span``) on the device trace's clock: a
             bounded in-memory ring that is always there plus a profiler
             annotation per span; chrome-trace (Perfetto-compatible) JSON
             export when PADDLE_TRACE_DIR / enable_tracing() asks for it;
             one compile.* span per JAX compile-path event.
  metrics  — process-wide registry of counters / gauges / histograms
             (step time, tokens/sec, retry counts, checkpoint bytes,
             collective latency) with a ``snapshot()`` dict and an optional
             per-step CSV/JSONL sink (``PADDLE_METRICS_SINK``).
  recorder — bounded flight-recorder ring buffer of structured events that
             auto-dumps ``FLIGHT.json`` on crash, SIGTERM/preemption (via
             the resilience preempt latch) and on every ResilientLoop
             restore — postmortems of chaos/preemption runs need no re-run.
  fleet    — fleet-wide telemetry: per-rank ``TelemetryClient`` pushes
             (metrics snapshot + span batches + heartbeat) to the rank-0
             launcher's ``TelemetryAggregator``; merged cross-rank chrome
             trace, straggler detection, FLEET_FLIGHT.json merging.
  admin    — the live admin HTTP endpoint (/metrics Prometheus text with
             full histogram buckets, /snapshot, /flight, /health, /ranks,
             /logs?rank=N, POST /push; PADDLE_ADMIN_READ_TOKEN read auth)
             served by the launcher for training and ContinuousBatcher
             for serving.
  xplane   — optional on-device (jax.profiler) trace window keyed by
             PADDLE_XPLANE_DIR, linked from the host chrome trace; also
             programmatically armable (``xplane.arm``) by the triggers.
  slo      — request-level SLO observability: per-request trace ids +
             lifecycle spans, TTFT/TPOT/queue-wait/e2e histograms, and an
             SloPolicy (PADDLE_SLO_*) emitting ``slo.breach`` per
             breaching request.
  exporters— background push of metric snapshots to an external sink
             (PADDLE_METRICS_EXPORT_URL; Prometheus text or OTLP/JSON),
             loss-tolerant like telemetry pushes.
  triggers — rule engine turning fleet.straggler / slo.breach /
             watchdog.near_deadline signals into bounded automatic XPlane
             captures + CAPTURE_<n>.json snapshots.

Env vars:
  PADDLE_TRACE_DIR        turn the span export on; chrome trace + FLIGHT.json
                          land here (trace exported at process exit too)
  PADDLE_METRICS_SINK     path ending .jsonl or .csv: per-step metric rows
  PADDLE_FLIGHT_RECORDER  ring capacity (default 512; 0/off disables)
  PADDLE_TELEMETRY_DIR    shared-dir fleet telemetry transport root
  PADDLE_TELEMETRY_ENDPOINT  host:port of the rank-0 admin server
  PADDLE_TELEMETRY_INTERVAL  min seconds between pushes (default 0.5)
  PADDLE_XPLANE_DIR       device-trace window dump dir (off when unset)
  PADDLE_SLO_TTFT_S / _TPOT_S / _E2E_S / _QUEUE_S   serving SLO targets
  PADDLE_METRICS_EXPORT_URL / _FORMAT / _INTERVAL   external metric sink
  PADDLE_ADMIN_READ_TOKEN admin GET read auth (403 without when set)
  PADDLE_TRIGGERS         0 disables trigger-driven deep capture

The core modules import nothing of paddle_tpu (the stdlib, and in spans
jax.profiler's TraceAnnotation) — any module in paddle_tpu (including the
earliest-imported resilience layer) can depend on them without cycles
(fleet/xplane resolve chaos/jax.profiler sessions lazily, inside guarded
calls).
"""
from __future__ import annotations

from . import metrics  # noqa: F401
from . import recorder  # noqa: F401
from . import spans  # noqa: F401
from . import admin  # noqa: F401
from . import xplane  # noqa: F401
from . import fleet  # noqa: F401
from . import slo  # noqa: F401
from . import exporters  # noqa: F401
from . import triggers  # noqa: F401
from .metrics import counter, gauge, histogram, snapshot, timer  # noqa: F401
from .recorder import dump_flight, record  # noqa: F401
from .spans import (  # noqa: F401
    disable_tracing, enable_tracing, export_chrome_trace, span, traced,
    tracing_enabled,
)

__all__ = [
    "spans", "metrics", "recorder", "fleet", "admin", "xplane",
    "slo", "exporters", "triggers",
    "span", "traced", "tracing_enabled", "enable_tracing", "disable_tracing",
    "export_chrome_trace",
    "counter", "gauge", "histogram", "snapshot", "timer",
    "record", "dump_flight",
]


def reset():
    """Clear all telemetry state (tests). Metrics counters are normally
    NEVER reset in a live process — monotonicity across ResilientLoop
    restores is part of the contract."""
    spans.reset()
    metrics.reset()
    recorder.reset()
    fleet.reset()
    xplane.reset()
    exporters.reset()
