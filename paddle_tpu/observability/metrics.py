"""Process-wide metrics registry: counters, gauges, histograms.

The runtime's numeric telemetry lives in ONE registry — step time, tokens,
retry/chaos counts, checkpoint bytes, collective latency — so `snapshot()`
is the single perf-trajectory artifact: bench.py embeds it in its JSON line,
tests assert on it, and a per-step CSV/JSONL sink (``PADDLE_METRICS_SINK``)
streams it for live runs.

Contracts:
  * counters are MONOTONIC for the life of the process: a ResilientLoop
    checkpoint restore rolls model state back but never rolls telemetry
    back (the restore itself is part of the story the numbers tell).
  * everything is thread-safe (the checkpoint async writer, watchdog timers
    and data workers all report concurrently).
  * histograms keep running count/sum/min/max exactly and percentiles over
    a bounded reservoir of the most recent observations (bounded memory on
    million-step runs). They ALSO keep exact cumulative bucket counts over
    fixed boundaries (seconds-scale latency defaults) so external scrapers
    and push exporters see full latency distributions
    (``_bucket{le=...}``), not just summary counts — the ISSUE 6
    request-latency (TTFT/TPOT/e2e) distributions ride on this.

No jax, no paddle_tpu imports — safe to import from anywhere in the tree.
"""
from __future__ import annotations

import bisect
import json
import os
import threading
import time
from collections import deque

__all__ = ["Counter", "Gauge", "Histogram", "counter", "counter_nowait",
           "gauge", "histogram",
           "remove_gauge", "snapshot", "counter_values", "timer", "set_sink",
           "maybe_emit_step", "reset", "DEFAULT_BUCKETS"]

ENV_SINK = "PADDLE_METRICS_SINK"

_lock = threading.Lock()
_counters: dict[str, "Counter"] = {}
_gauges: dict[str, "Gauge"] = {}
_histograms: dict[str, "Histogram"] = {}

_RESERVOIR = 4096  # most-recent observations kept per histogram

# Default bucket boundaries: seconds-scale latencies from 100 µs to 5 min.
# Exact counts (unlike the percentile reservoir, which is windowed), so a
# scraped histogram is correct over the whole process life.
DEFAULT_BUCKETS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0,
)


class Counter:
    """Monotonic counter. inc() only — there is deliberately no decrement
    or reset-per-run: restores/retries must remain visible."""

    __slots__ = ("name", "_v", "_lk")

    def __init__(self, name):
        self.name = name
        self._v = 0
        self._lk = threading.Lock()

    def inc(self, n: int = 1):
        with self._lk:
            self._v += n

    @property
    def value(self) -> int:
        return self._v


class Gauge:
    """Last-write-wins scalar (queue depth, learning rate, alive workers)."""

    __slots__ = ("name", "_v", "_lk")

    def __init__(self, name):
        self.name = name
        self._v = 0.0
        self._lk = threading.Lock()

    def set(self, v: float):
        with self._lk:
            self._v = float(v)

    @property
    def value(self) -> float:
        return self._v


class Histogram:
    """Running count/sum/min/max + recent-window percentiles + exact
    cumulative bucket counts (Prometheus-style ``le`` boundaries)."""

    __slots__ = ("name", "_lk", "_count", "_sum", "_min", "_max", "_last",
                 "_window", "_bounds", "_bucket_counts")

    def __init__(self, name, buckets=DEFAULT_BUCKETS):
        self.name = name
        self._lk = threading.Lock()
        self._count = 0
        self._sum = 0.0
        self._min = None
        self._max = None
        self._last = None
        self._window = deque(maxlen=_RESERVOIR)
        self._bounds = tuple(sorted(float(b) for b in buckets))
        # per-bucket (non-cumulative) counts; index len(bounds) == +Inf
        self._bucket_counts = [0] * (len(self._bounds) + 1)

    def observe(self, v: float):
        v = float(v)
        with self._lk:
            self._count += 1
            self._sum += v
            self._last = v
            if self._min is None or v < self._min:
                self._min = v
            if self._max is None or v > self._max:
                self._max = v
            self._window.append(v)
            i = bisect.bisect_left(self._bounds, v)
            self._bucket_counts[i] += 1

    def buckets(self) -> tuple[tuple[float, ...], list[int]]:
        """(upper bounds, CUMULATIVE counts) — counts has one extra entry
        (the +Inf bucket, == total count). Exact over the process life."""
        with self._lk:
            per = list(self._bucket_counts)
        cum, running = [], 0
        for c in per:
            running += c
            cum.append(running)
        return self._bounds, cum

    @property
    def count(self) -> int:
        return self._count

    def percentile(self, p: float) -> float | None:
        """p in [0, 100], over the retained recent window."""
        with self._lk:
            win = sorted(self._window)
        if not win:
            return None
        idx = min(len(win) - 1, max(0, int(round(p / 100.0 * (len(win) - 1)))))
        return win[idx]

    def stats(self) -> dict:
        with self._lk:
            win = sorted(self._window)
            count, total = self._count, self._sum
            lo, hi, last = self._min, self._max, self._last
            per = list(self._bucket_counts)  # SAME lock scope as count:
            # the exported +Inf bucket must equal _count in one exposition
        bounds = self._bounds
        cum, running = [], 0
        for c in per:
            running += c
            cum.append(running)

        def pct(p):
            if not win:
                return None
            return win[min(len(win) - 1,
                           max(0, int(round(p / 100.0 * (len(win) - 1)))))]

        return {"count": count, "sum": total,
                "mean": (total / count) if count else None,
                "min": lo, "max": hi, "last": last,
                "p50": pct(50), "p95": pct(95), "p99": pct(99),
                # exact cumulative distribution (last entry = +Inf = count):
                # exporters / the Prometheus endpoint render _bucket series
                # straight from the snapshot, no second registry walk
                "buckets": {"bounds": list(bounds), "cum": cum}}


def counter(name: str) -> Counter:
    with _lock:
        c = _counters.get(name)
        if c is None:
            c = _counters[name] = Counter(name)
        return c


def counter_nowait(name: str) -> Counter | None:
    """``counter(name)`` for a caller that must not wait on the registry's
    lock (the collector's hook, which can run while its own thread holds
    it): None where the counter is new and the lock is taken."""
    c = _counters.get(name)
    if c is not None or not _lock.acquire(blocking=False):
        return c
    try:
        return _counters.setdefault(name, Counter(name))
    finally:
        _lock.release()


def gauge(name: str) -> Gauge:
    with _lock:
        g = _gauges.get(name)
        if g is None:
            g = _gauges[name] = Gauge(name)
        return g


def histogram(name: str) -> Histogram:
    with _lock:
        h = _histograms.get(name)
        if h is None:
            h = _histograms[name] = Histogram(name)
        return h


def remove_gauge(name: str) -> None:
    """Drop one gauge from the registry. For PER-INSTANCE exports (e.g. a
    Router's ``serve.fleet.<c>.r_<id>`` gauges): the registry is
    process-global and append-only otherwise, so an instance that dies
    without removing its gauges leaves stale series in every snapshot
    and export forever."""
    with _lock:
        _gauges.pop(name, None)


class timer:
    """``with metrics.timer("train.step_time_s"): ...`` — observe the scoped
    wall time into a histogram. The ONE sanctioned way to time a region
    outside the observability layer (tools/lint_observability.py bans raw
    clock-subtraction timing elsewhere in paddle_tpu)."""

    __slots__ = ("_h", "_t0")

    def __init__(self, name_or_hist):
        self._h = histogram(name_or_hist) if isinstance(name_or_hist, str) \
            else name_or_hist
        self._t0 = None

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._h.observe(time.perf_counter() - self._t0)
        return False


def counter_values() -> dict:
    """Counters only — no histogram-window sorting. The cheap read for
    per-step pollers (the trigger engine) that only watch counters."""
    with _lock:
        cs = dict(_counters)
    return {n: c.value for n, c in cs.items()}


def snapshot() -> dict:
    """One JSON-serializable dict of every metric in the process."""
    with _lock:
        cs = dict(_counters)
        gs = dict(_gauges)
        hs = dict(_histograms)
    return {
        "counters": {n: c.value for n, c in sorted(cs.items())},
        "gauges": {n: g.value for n, g in sorted(gs.items())},
        "histograms": {n: h.stats() for n, h in sorted(hs.items())},
    }


def reset():
    """Drop every metric (TESTS ONLY — live processes never reset)."""
    with _lock:
        _counters.clear()
        _gauges.clear()
        _histograms.clear()


# ---------------------------------------------------------------- sink

_sink = [None]  # (path, kind, csv_columns | None) once configured
_sink_lk = threading.Lock()


# the runtime's standard metric names, pre-registered when a sink is
# configured: CSV pins its columns at the first emitted row, and a fault
# counter that first increments at step 30 must not be invisible because it
# didn't exist at step 1 (JSONL rows always carry whatever exists).
_STANDARD_COUNTERS = (
    "train.steps", "train.tokens", "resilience.retries",
    "resilience.restores", "chaos.faults", "watchdog.stall", "io.batches",
    "checkpoint.save_bytes", "checkpoint.load_bytes", "collective.barriers",
    "serve.requests", "serve.tokens", "serve.tokens_discarded",
    "serve.admission_stalls", "serve.preemptions", "serve.chaos_retired",
    "serve.prefix_hits", "serve.pages_shared", "serve.cow_copies",
    "serve.prefill_skips", "serve.prefix_evictions",
    "slo.prefill_skipped_s",
    "telemetry.pushes", "telemetry.drops", "fleet.straggler",
    "slo.breach", "telemetry.exports", "telemetry.export_drops",
    "trigger.captures", "watchdog.near_deadline",
)
_STANDARD_GAUGES = (
    "serve.pages_in_use", "serve.tokens_per_s", "serve.kv_read_mb_per_tok",
    "serve.prefix_cached_pages",
)
_STANDARD_HISTOGRAMS = (
    "train.step_time_s", "loop.step_time_s", "collective.wait_s",
    "checkpoint.save_time_s", "checkpoint.load_time_s",
    "checkpoint.crc_time_s", "serve.burst_time_s",
    "slo.ttft_s", "slo.tpot_s", "slo.queue_wait_s", "slo.e2e_s",
)


def set_sink(path: str | None):
    """Route per-step snapshots to `path` (.jsonl or .csv). None disables.
    Overrides the PADDLE_METRICS_SINK env default. CSV columns are pinned at
    the first emitted row; the standard runtime metrics are pre-registered
    here so late-first-incremented fault counters still have a column —
    nonstandard metrics created after the first row appear only in JSONL."""
    with _sink_lk:
        if path is None:
            _sink[0] = None
            return
        kind = "csv" if path.endswith(".csv") else "jsonl"
        _sink[0] = {"path": path, "kind": kind, "columns": None}
    for n in _STANDARD_COUNTERS:
        counter(n)
    for n in _STANDARD_GAUGES:
        gauge(n)
    for n in _STANDARD_HISTOGRAMS:
        histogram(n)


def _configured_sink():
    s = _sink[0]
    if s is not None:
        return s
    env = os.environ.get(ENV_SINK)
    if env:
        set_sink(env)
        return _sink[0]
    return None


def _flat_row(step):
    snap = snapshot()
    row = {"step": int(step), "time": time.time()}
    for n, v in snap["counters"].items():
        row[n] = v
    for n, v in snap["gauges"].items():
        row[n] = v
    for n, st in snap["histograms"].items():
        for k in ("count", "mean", "p50", "p95", "last"):
            row[f"{n}.{k}"] = st[k]
    return row


def maybe_emit_step(step: int):
    """Append one metrics row for `step` when a sink is configured; a no-op
    (one None check + one env lookup) otherwise. Called by the trainer /
    engine at each step boundary."""
    s = _configured_sink()
    if s is None:
        return
    row = _flat_row(step)
    with _sink_lk:
        try:
            if s["kind"] == "jsonl":
                with open(s["path"], "a") as f:
                    f.write(json.dumps(row) + "\n")
            else:  # csv: columns pinned at the first emitted row
                if s["columns"] is None:
                    s["columns"] = list(row.keys())
                    with open(s["path"], "a") as f:
                        f.write(",".join(s["columns"]) + "\n")
                with open(s["path"], "a") as f:
                    f.write(",".join("" if row.get(c) is None else str(row.get(c))
                                     for c in s["columns"]) + "\n")
        except OSError:
            pass  # a full disk must never kill the training step
