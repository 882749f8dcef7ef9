"""External metric export: push snapshots out of the process.

The PR-5 plane made the fleet observable from INSIDE the job (rank-0
aggregator + admin endpoint). This module closes the ROADMAP carry-over
"stream aggregated fleet metrics to an external sink": a background
``MetricsExporter`` periodically pushes ``metrics.snapshot()`` to an HTTP
endpoint (``PADDLE_METRICS_EXPORT_URL``) in one of two wire formats:

  * ``prom`` — Prometheus text exposition v0.0.4 with FULL histogram bucket
    series (``_bucket{le=...}``, ``_sum``, ``_count`` — exact cumulative
    counts from metrics.Histogram.buckets), POSTed as ``text/plain``. This
    is the remote-write-adjacent text ingestion path VictoriaMetrics
    (``/api/v1/import/prometheus``), the Pushgateway, and vector agents
    accept; true protobuf+snappy remote-write needs deps the image doesn't
    bake, so the text form is the sanctioned stand-in (same series, same
    labels).
  * ``otlp`` — an OTLP/JSON ``ExportMetricsServiceRequest`` (counters →
    monotonic cumulative sums, gauges → gauges, histograms → explicit-bounds
    histogram data points), POSTed as ``application/json`` to an OTLP/HTTP
    collector (``.../v1/metrics``).

Who runs one: the rank-0 launcher (next to the TelemetryAggregator —
training metrics leave the pod) and ``ContinuousBatcher`` (serving — the
request-level slo.* distributions leave the process). Both are env-gated:
no URL, no thread, no cost.

Loss tolerance is the same contract as telemetry pushes: a failed export
(dead collector, chaos site ``telemetry.export``) increments
``telemetry.export_drops`` + a flight event and RETURNS — it can never
raise into a training or serving step, pinned by chaos==fault-free
bitwise/token equality tests.

Env:
  PADDLE_METRICS_EXPORT_URL       endpoint URL (off when unset)
  PADDLE_METRICS_EXPORT_FORMAT    "prom" (default) | "otlp"; auto-"otlp"
                                  when the URL path ends in /v1/metrics
  PADDLE_METRICS_EXPORT_INTERVAL  seconds between pushes (default 10)
  PADDLE_METRICS_EXPORT_TIMEOUT   HTTP timeout seconds (default 2)
"""
from __future__ import annotations

import atexit
import json
import os
import threading
import time
import urllib.request

from . import metrics, recorder

__all__ = ["MetricsExporter", "otlp_payload", "prom_multi_text",
           "maybe_from_env", "shared_from_env", "flush_shared", "reset"]

ENV_URL = "PADDLE_METRICS_EXPORT_URL"
ENV_FORMAT = "PADDLE_METRICS_EXPORT_FORMAT"
ENV_INTERVAL = "PADDLE_METRICS_EXPORT_INTERVAL"
ENV_TIMEOUT = "PADDLE_METRICS_EXPORT_TIMEOUT"

def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def prom_multi_text(blocks) -> str:
    """Spec-clean Prometheus text for SEVERAL labeled snapshots (the
    rank-0 launcher exports its own registry plus every fresh rank's
    reported snapshot, labeled {node, rank}): ONE ``# TYPE`` line per
    family, then all blocks' labeled samples — duplicate TYPE lines are a
    text-format violation strict ingesters reject. A single block is
    byte-identical to admin.render_prometheus."""
    from .admin import _fmt_le, _label_str, _prom_name, render_prometheus
    blocks = list(blocks)
    if len(blocks) == 1:
        labels, snap = blocks[0]
        return render_prometheus(snap, labels=labels)
    types: dict = {}
    samples: dict = {}

    def fam(name, kind):
        m = _prom_name(name)
        types.setdefault(m, kind)
        return samples.setdefault(m, []), m

    for labels, snap in blocks:
        lab = _label_str(labels)
        for n, v in (snap.get("counters") or {}).items():
            lines, m = fam(n, "counter")
            lines.append(f"{m}{lab} {v}")
        for n, v in (snap.get("gauges") or {}).items():
            lines, m = fam(n, "gauge")
            lines.append(f"{m}{lab} {v}")
        for n, st in (snap.get("histograms") or {}).items():
            if not isinstance(st, dict):
                continue
            lines, m = fam(n, "histogram")
            bk = st.get("buckets") or {}
            bounds, cum = bk.get("bounds") or [], bk.get("cum") or []
            for b, c in zip(bounds, cum):
                le = 'le="%s"' % _fmt_le(b)
                lines.append(f"{m}_bucket{_label_str(labels, le)} {c}")
            total = cum[-1] if cum else st.get("count", 0)
            inf = 'le="+Inf"'
            lines.append(f"{m}_bucket{_label_str(labels, inf)} {total}")
            lines.append(f"{m}_sum{lab} {st.get('sum', 0)}")
            lines.append(f"{m}_count{lab} {st.get('count', 0)}")
    out = []
    for m, lines in samples.items():
        out.append(f"# TYPE {m} {types[m]}")
        out.extend(lines)
    return "\n".join(out) + "\n"


def otlp_payload(snap: dict, labels: dict | None = None,
                 t_unix_nano: int | None = None) -> dict:
    """``metrics.snapshot()`` → OTLP/JSON ExportMetricsServiceRequest."""
    t = int(time.time() * 1e9) if t_unix_nano is None else int(t_unix_nano)
    attrs = [{"key": "service.name",
              "value": {"stringValue": "paddle_tpu"}}]
    for k, v in sorted((labels or {}).items()):
        attrs.append({"key": str(k), "value": {"stringValue": str(v)}})
    out = []
    for n, v in snap.get("counters", {}).items():
        # a counter of seconds (gc.seconds) is a float: no truncation
        value = {"asInt": str(v)} if isinstance(v, int) \
            else {"asDouble": float(v)}
        out.append({"name": n, "sum": {
            "dataPoints": [{**value, "timeUnixNano": str(t)}],
            "aggregationTemporality": 2, "isMonotonic": True}})
    for n, v in snap.get("gauges", {}).items():
        out.append({"name": n, "gauge": {
            "dataPoints": [{"asDouble": float(v), "timeUnixNano": str(t)}]}})
    for n, st in snap.get("histograms", {}).items():
        bk = st.get("buckets") or {}
        cum = bk.get("cum") or []
        # OTLP bucketCounts are PER-bucket; the snapshot ships cumulative
        per, prev = [], 0
        for c in cum:
            per.append(int(c) - prev)
            prev = int(c)
        out.append({"name": n, "histogram": {
            "dataPoints": [{
                "count": str(int(st.get("count", 0))),
                "sum": float(st.get("sum", 0.0)),
                "bucketCounts": [str(c) for c in per],
                "explicitBounds": list(bk.get("bounds") or []),
                "timeUnixNano": str(t)}],
            "aggregationTemporality": 2}})
    return {"resourceMetrics": [{
        "resource": {"attributes": attrs},
        "scopeMetrics": [{
            "scope": {"name": "paddle_tpu.observability"},
            "metrics": out}]}]}


class MetricsExporter:
    """exp = MetricsExporter().start(); ... exp.stop()  (final flush)

    `snapshot_fn` defaults to the process registry. `blocks_fn` (optional)
    returns ``[(labels, snapshot), ...]`` for multi-origin export — the
    rank-0 launcher passes the aggregator's per-rank snapshots so EVERY
    rank's series reaches the sink, labeled {node, rank}, not just the
    launcher's own registry. `labels` become Prometheus labels / OTLP
    resource attributes naming the origin (node, role)."""

    def __init__(self, url: str | None = None, fmt: str | None = None,
                 interval: float | None = None, timeout: float | None = None,
                 snapshot_fn=None, labels: dict | None = None,
                 blocks_fn=None):
        self.url = url if url is not None else os.environ.get(ENV_URL)
        fmt = fmt or os.environ.get(ENV_FORMAT) or ""
        if not fmt:
            fmt = "otlp" if (self.url or "").rstrip("/").endswith(
                "/v1/metrics") else "prom"
        if fmt not in ("prom", "otlp"):
            raise ValueError(f"unknown export format {fmt!r}")
        self.fmt = fmt
        self.interval = _env_float(ENV_INTERVAL, 10.0) \
            if interval is None else float(interval)
        self.timeout = _env_float(ENV_TIMEOUT, 2.0) \
            if timeout is None else float(timeout)
        self._snapshot = snapshot_fn or metrics.snapshot
        self._blocks_fn = blocks_fn
        self.labels = dict(labels or {})
        if "node" not in self.labels and os.environ.get("PADDLE_NODE_ID"):
            self.labels["node"] = os.environ["PADDLE_NODE_ID"]
        self._stop: threading.Event | None = None
        self._thread: threading.Thread | None = None

    # ------------------------------------------------------------ payload
    def _blocks(self) -> list:
        if self._blocks_fn is not None:
            blocks = list(self._blocks_fn())
            if blocks:
                return blocks
        return [(self.labels, self._snapshot())]

    def _body(self) -> tuple[bytes, str]:
        blocks = self._blocks()
        if self.fmt == "otlp":
            rms = []
            for labels, snap in blocks:
                rms.extend(otlp_payload(snap, labels)["resourceMetrics"])
            return (json.dumps({"resourceMetrics": rms}).encode(),
                    "application/json")
        return prom_multi_text(blocks).encode(), "text/plain; version=0.0.4"

    # ------------------------------------------------------------- export
    def export_once(self) -> bool:
        """One push. Loss-tolerant BY CONSTRUCTION: any failure (including
        the ``telemetry.export`` chaos site) counts
        ``telemetry.export_drops`` + a flight event and returns False —
        the caller is a step boundary / background loop and must never
        feel the sink."""
        if not self.url:
            return False
        try:
            body, ctype = self._body()
            try:
                # lazy: chaos lives above observability in the import DAG
                from ..distributed.resilience import chaos
                chaos.hit("telemetry.export")
            except ImportError:
                pass
            req = urllib.request.Request(
                self.url, method="POST", data=body,
                headers={"Content-Type": ctype})
            urllib.request.urlopen(req, timeout=self.timeout).read()
        except Exception as e:
            metrics.counter("telemetry.export_drops").inc()
            recorder.record("telemetry.export_drop", url=self.url,
                            fmt=self.fmt,
                            error=f"{type(e).__name__}: {e}")
            return False
        metrics.counter("telemetry.exports").inc()
        return True

    # ---------------------------------------------------------- lifecycle
    def start(self) -> "MetricsExporter":
        """Spawn the daemon push loop (idempotent; no-op without a URL)."""
        if self._thread is not None or not self.url:
            return self
        stop = threading.Event()

        def loop():
            while not stop.wait(self.interval):
                self.export_once()

        self._stop = stop
        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="paddle-metrics-exporter")
        self._thread.start()
        return self

    def stop(self, final_flush: bool = True):
        """Stop the loop; by default push one last snapshot so the
        end-of-run totals reach the sink."""
        if self._stop is not None:
            self._stop.set()
            if self._thread is not None:
                self._thread.join(timeout=self.timeout + 1.0)
            self._stop = self._thread = None
        if final_flush and self.url:
            self.export_once()


def maybe_from_env(labels: dict | None = None,
                   blocks_fn=None) -> MetricsExporter | None:
    """Start an exporter when PADDLE_METRICS_EXPORT_URL is set; None (and
    zero cost beyond one env lookup) otherwise."""
    if not os.environ.get(ENV_URL):
        return None
    return MetricsExporter(labels=labels, blocks_fn=blocks_fn).start()


# ------------------------------------------------ process-shared exporter
# The metrics registry is process-global, so N ContinuousBatchers must not
# run N exporter threads pushing N copies of the SAME snapshot (duplicate,
# double-countable series at the sink). They share ONE exporter; its final
# flush is guaranteed by atexit even when nobody calls stop().

_shared_lock = threading.Lock()
_shared: list = [None]


def shared_from_env(labels: dict | None = None) -> MetricsExporter | None:
    """The process-wide exporter (created + started on first call when
    PADDLE_METRICS_EXPORT_URL is set; the same instance ever after).
    Callers must NOT stop() it — use ``flush_shared`` for an end-of-wave
    flush, ``reset`` (tests) to tear it down."""
    if not os.environ.get(ENV_URL):
        return None
    with _shared_lock:
        if _shared[0] is None:
            exp = MetricsExporter(labels=labels).start()
            _shared[0] = exp
            atexit.register(_atexit_flush)
        return _shared[0]


def _atexit_flush():
    with _shared_lock:
        exp = _shared[0]
    if exp is not None:
        exp.stop(final_flush=True)


def flush_shared():
    """One immediate push from the shared exporter (end-of-run totals)."""
    with _shared_lock:
        exp = _shared[0]
    if exp is not None:
        exp.export_once()


def reset():
    """Stop and drop the shared exporter (tests — a monkeypatched sink URL
    must not outlive its test)."""
    with _shared_lock:
        exp, _shared[0] = _shared[0], None
    if exp is not None:
        exp.stop(final_flush=False)
