"""paddle_tpu.profiler.

Reference: /root/reference/python/paddle/profiler/profiler.py:358 (Profiler
with scheduler windows, chrome-trace export via the C++ host/CUPTI tracers —
SURVEY.md §5.1).

TPU-native: device tracing is jax.profiler (XPlane → TensorBoard/Perfetto);
`RecordEvent` is an observability span (which annotates the device trace and
lands in the span ring and its chrome export) plus a light python timer tree
for summary() tables.
"""
from __future__ import annotations

import contextlib
import enum
import os
import threading
import time
from collections import defaultdict

import jax

from ..observability import spans as _spans

__all__ = ["Profiler", "ProfilerTarget", "ProfilerState", "RecordEvent",
           "make_scheduler", "export_chrome_tracing", "load_profiler_result"]


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(closed=0, ready=0, record=1, repeat=0, skip_first=0):
    """Window scheduler (reference profiler.py make_scheduler)."""

    def schedule(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        period = closed + ready + record
        if repeat and s >= period * repeat:
            return ProfilerState.CLOSED
        pos = s % period if period else 0
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


def export_chrome_tracing(dir_name, worker_name=None):
    def handler(prof):
        prof._export_dir = dir_name
    return handler


_events = threading.local()


def _tree():
    if not hasattr(_events, "stack"):
        _events.stack = []
        _events.records = []
        _events.first_start = None
        _events.last_end = None
    return _events


def reset_host_events():
    """Drop recorded host events (called by Profiler.start so each profiling
    window reports its own wall time and doesn't grow without bound)."""
    tls = _tree()
    tls.records = []
    tls.first_start = None
    tls.last_end = None


class RecordEvent:
    """Host-side scoped event: feeds summary() and annotates the device trace
    (reference phi/api/profiler/event_tracing.h RecordEvent). Nesting is
    tracked so the statistics tables can report SELF time per event."""

    def __init__(self, name, event_type=None):
        from .statistics import TracerEventType
        self.name = name
        self.event_type = event_type or TracerEventType.UserDefined

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()

    def begin(self):
        tls = _tree()
        now = time.perf_counter()
        if tls.first_start is None:
            tls.first_start = now
        # frame: [name, type, start, child_time_accumulator]
        tls.stack.append([self.name, self.event_type, now, 0.0])
        # the span annotates the device trace and lands in the span ring,
        # so ONE exported chrome trace carries RecordEvent scopes next to
        # train-step / checkpoint / collective spans
        self._span = _spans.span(self.name, cat="profiler").begin()

    def end(self):
        from .statistics import EventRecord
        self._span.end()
        tls = _tree()
        name, etype, t0, child = tls.stack.pop()
        now = time.perf_counter()
        dur = now - t0
        tls.last_end = now
        if tls.stack:
            tls.stack[-1][3] += dur  # contribute to parent's child time
        tls.records.append(EventRecord(name, etype, t0, dur,
                                       depth=len(tls.stack),
                                       self_dur=max(dur - child, 0.0)))


class Profiler:
    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False, emit_nvtx=False):
        if callable(scheduler):
            self._scheduler = scheduler
        elif isinstance(scheduler, (tuple, list)) and len(scheduler) == 2:
            lo, hi = scheduler
            self._scheduler = make_scheduler(closed=lo, ready=0, record=hi - lo)
        else:
            self._scheduler = None
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self._export_dir = None
        self._step = 0
        self._tracing = False
        self._trace_dir = None
        self._step_times = []
        self._t_last = None
        self._win_span = None  # open "profiler.window" span while recording

    def start(self):
        reset_host_events()  # each profiling window reports its own events
        self._t_last = time.perf_counter()
        if not self._timer_only:
            self._maybe_transition(first=True)

    def stop(self):
        self._stop_trace()
        if self._on_trace_ready:
            self._on_trace_ready(self)
        if self._export_dir and self._trace_dir is None:
            pass

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._t_last is not None:
            self._step_times.append(now - self._t_last)
        self._t_last = now
        self._step += 1
        if not self._timer_only:
            self._maybe_transition()

    def step_info(self, unit=None):
        if not self._step_times:
            return ""
        import numpy as np
        arr = np.asarray(self._step_times[-10:])
        return (f"avg step: {arr.mean() * 1e3:.2f} ms "
                f"(min {arr.min() * 1e3:.2f}, max {arr.max() * 1e3:.2f})")

    def _maybe_transition(self, first=False):
        if self._scheduler is None:
            if first:
                self._start_trace()
            return
        state = self._scheduler(self._step)
        if state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            self._start_trace()
        else:
            self._stop_trace()

    def _start_trace(self):
        if self._win_span is None:
            # the scheduler WINDOW itself is a span: the merged chrome trace
            # shows exactly which steps each profiling window covered
            self._win_span = _spans.span(
                "profiler.window", cat="profiler",
                step=self._step).begin(nest=False)  # ended by a later step
        if not self._tracing:
            self._trace_dir = self._export_dir or os.environ.get(
                "PADDLE_PROFILER_DIR", "/tmp/paddle_tpu_trace")
            try:
                jax.profiler.start_trace(self._trace_dir)
                self._tracing = True
            except Exception:
                self._tracing = False

    def _stop_trace(self):
        if self._win_span is not None:
            self._win_span.end()
            self._win_span = None
        if self._tracing:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._tracing = False

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms", views=None):
        """Statistics tables (reference profiler_statistic.py _build_table):
        event-type overview + per-event calls/total/avg/max/min/self/%."""
        from .statistics import SortedKeys, build_summary
        tls = _tree()
        if not tls.records:
            print("(no host events recorded — wrap regions in profiler.RecordEvent)")
            return
        wall = (tls.last_end or 0) - (tls.first_start or 0)
        print(build_summary(tls.records, wall,
                            sorted_by=sorted_by or SortedKeys.CPUTotal,
                            op_detail=op_detail, time_unit=time_unit,
                            views=views))


def load_profiler_result(path):
    raise NotImplementedError("open the XPlane/perfetto trace in TensorBoard")
