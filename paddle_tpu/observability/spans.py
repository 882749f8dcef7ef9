"""The one span API: a bounded in-memory ring of completed spans on the
device trace's clock, each span also entered as a profiler annotation.

What a span records: name, category, start, end, the span that caused it
(the innermost span open on the same thread when it began), and small
integer/str arguments. Request-lifecycle spans (observability.slo) share
their ``rid``/``trace`` arguments instead.

Clock. Start and end are ``time.time_ns()``: wall-clock nanoseconds, the
clock the profiler stamps its host and TPU events with. An ``.xplane.pb``
holds its events as nanoseconds from ``profile_start_time`` (a stat of the
"Task Environment" plane, wall ns), so a span lies at
``(t_ns - profile_start_time) * 1e-9`` seconds on a trace's own timeline:
no calibration run, one clock read per edge (pinned by
``tools/span_clock_check.py``: on the chip a device interval against the
span around it, anywhere the ring's copy of a span against the profiler's).

Two sinks, one call:
  * the ring, always there: ``DEFAULT_CAPACITY`` spans, the oldest falling
    off (``dropped()`` counts them). ``records()`` is the public read;
    ``events()`` / ``events_since()`` give the same spans as chrome-trace
    dicts for the fleet telemetry and the export.
  * ``jax.profiler.TraceAnnotation``: a flag check while no profiler
    session is live, and the span beside the device's operations in the
    ``.xplane.pb`` while one is (``PADDLE_XPLANE_DIR``, a trigger-armed
    window, ``perfbench.run --trace 1``).

Cost with no reader: two clock reads, one annotation enter/exit, one deque
append; no lock, no ``os.environ`` read, no file, no thread. Nothing runs at
import beyond assignments (and reading ``PADDLE_TRACE_DIR`` once).

``enable_tracing()`` / ``PADDLE_TRACE_DIR`` turn the EXPORT on: the ring
grows to ``PADDLE_TRACE_MAX_EVENTS`` (100 000) and the process leaves
``trace_<pid>.json`` (chrome://tracing / Perfetto) behind at exit.
``tracing_enabled()`` answers for the export; layers whose spans cost more
than a span to build (request lifecycles, collective sequence numbers) ask
it first.

Usage:
    with spans.span("train.step", cat="step", step=i): ...
    @spans.traced("load_batch", cat="data")
    def load_batch(...): ...
    spans.add_span("req.queue", "request", t0, t1, rid=7)   # spans.now() s

Which program compiled, when, and for how long (``watch_compiles()`` is
called when paddle_tpu is imported):
    [(s.args.get("fun"), s.t0_ns, (s.t1_ns - s.t0_ns) / 1e9)
     for s in spans.records() if s.name == "compile.backend"]
and ``s.parent`` names the open span (a ``serve.dispatch_burst``, a
``train.step``) under which a shape that set-up did not warm compiled.
"""
from __future__ import annotations

import atexit
import functools
import itertools
import json
import os
import threading
import time
from collections import deque, namedtuple

from jax import monitoring as _monitoring
from jax.profiler import TraceAnnotation as _Annotation

try:    # not a documented name: without it nested traces are told by length
    from jax.core import trace_ctx as _trace_ctx
    _tracing_at_top_level = _trace_ctx.is_top_level
except (ImportError, AttributeError):
    _tracing_at_top_level = None

from . import metrics

__all__ = ["span", "traced", "annotate", "add_span", "now", "now_ns", "records",
           "Span",
           "tracing_enabled", "enable_tracing", "disable_tracing",
           "export_chrome_trace", "reset", "events", "events_since",
           "dropped", "capacity", "set_trace_metadata", "watch_compiles",
           "COMPILE_SPANS"]

ENV_DIR = "PADDLE_TRACE_DIR"
ENV_MAX = "PADDLE_TRACE_MAX_EVENTS"
DEFAULT_CAPACITY = 8192
EXPORT_CAPACITY = 100000

now_ns = time.time_ns


def now() -> float:
    """Seconds on the span clock (what ``add_span`` takes)."""
    return time.time_ns() * 1e-9


Span = namedtuple("Span", "seq name cat t0_ns t1_ns tid id parent args")

_enabled = False
_trace_dir: str | None = None
_ring: deque = deque(maxlen=DEFAULT_CAPACITY)   # Span fields, as tuples
_ids = itertools.count(1)       # a span's id, taken when it begins
_seq = itertools.count(1)       # its place in the ring, taken when it ends
_appended = [0]                 # the newest seq handed out
_tls = threading.local()        # .stack: this thread's open (nested) spans
_atexit_registered = [False]
_extra_meta: dict = {}  # merged into export otherData (xplane links etc.)


def _stack() -> list:
    try:
        return _tls.stack
    except AttributeError:
        _tls.stack = []
        return _tls.stack


def _append(name, cat, t0_ns, t1_ns, sid, parent, args):
    seq = next(_seq)
    _ring.append((seq, name, cat, t0_ns, max(t0_ns, t1_ns),
                  threading.get_ident(), sid, parent, args))
    _appended[0] = seq      # GIL-atomic like the append; readers only


class _Span:
    """An open span. Context manager, decorator, or manual begin()/end()."""

    __slots__ = ("name", "cat", "args", "id", "parent", "_t0", "_ann")

    def __init__(self, name, cat, args):
        self.name = name
        self.cat = cat
        self.args = args
        self._t0 = None

    def begin(self, nest: bool = True):
        """`nest=False` is for a span that the frame which began it does
        not end (a generator's, a window over many steps): it has a parent
        and is never one, so an end that comes late, or on another thread,
        leaves no stale parent on this thread's stack."""
        stack = _stack()
        self.id = next(_ids)
        self.parent = stack[-1].id if stack else 0
        if nest:
            stack.append(self)
        self._ann = _Annotation(self.name, **self.args) if self.args \
            else _Annotation(self.name)
        self._ann.__enter__()
        self._t0 = now_ns()
        return self

    def end(self):
        if self._t0 is None:
            return
        t1 = now_ns()
        self._ann.__exit__(None, None, None)
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:         # ended out of order (manual begin/end)
            stack.remove(self)
        _append(self.name, self.cat, self._t0, t1, self.id, self.parent,
                self.args)
        self._t0 = None

    def __enter__(self):
        return self.begin()

    def __exit__(self, *exc):
        self.end()
        return False

    def __call__(self, fn):
        return traced(self.name, self.cat, **(self.args or {}))(fn)


def span(name: str, cat: str = "user", **args):
    """Open a span named `name` under category `cat` (the chrome-trace
    category lane: step / checkpoint / collective / data / resilience /
    serve / compile / profiler / user). Extra kwargs (small ints and strs)
    become the span's arguments."""
    return _Span(name, cat, args or None)


def annotate(**args) -> None:
    """Add arguments to the calling thread's innermost open span: for a
    function under ``@traced`` that learns them while it runs. They reach
    the ring when the span ends; the profiler's annotation, made at the
    span's start, does not have them. Outside any span it does nothing."""
    stack = _stack()
    if stack:
        stack[-1].args = {**(stack[-1].args or {}), **args}


def traced(name: str, cat: str = "user", **args):
    """Decorator factory: ``@traced("load_batch", cat="data")``."""
    span_args = args or None

    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*a, **k):
            with _Span(name, cat, span_args):
                return fn(*a, **k)
        return wrapped
    return deco


def add_span(name: str, cat: str, t0: float, t1: float, **args):
    """Append a COMPLETED span with explicit endpoints in ``spans.now()``
    seconds. The request-lifecycle tracker (observability.slo) records
    phase timestamps as requests move through the scheduler and
    reconstructs the queue/prefill/decode spans at retire time, and the
    compile listener learns of a compilation when it has ended: a live
    ``span()`` cannot straddle either. The parent is the calling thread's
    innermost open span; nothing is annotated after the fact."""
    stack = _stack()
    _append(name, cat, int(float(t0) * 1e9), int(float(t1) * 1e9),
            next(_ids), stack[-1].id if stack else 0, args or None)


# ------------------------------------------------------------------ reads

def _tail(since: int = 0) -> list:
    """The ring's rows with seq > since, oldest first: walked from the
    newest end, so an incremental read costs its batch, not the ring."""
    ring = _ring
    for _ in range(5):  # a concurrent append can invalidate the iterator
        try:
            out = []
            for r in reversed(ring):
                if r[0] <= since:
                    break
                out.append(r)
            return out[::-1]
        except RuntimeError:
            continue
    return [r for r in list(ring) if r[0] > since]


def records(since: int = 0) -> list[Span]:
    """The ring's spans with ``seq > since``, oldest first (ordered by when
    they ENDED). Times are wall-clock ns: see the module docstring for a
    trace's timeline."""
    return [Span._make(r) for r in _tail(since)]


def _chrome(r) -> dict:
    ev = {"name": r[1], "cat": r[2], "ph": "X", "ts": r[3] / 1e3,
          "dur": (r[4] - r[3]) / 1e3, "pid": os.getpid(), "tid": r[5],
          "id": r[6], "parent": r[7]}
    if r[8]:
        ev["args"] = r[8]
    return ev


def events() -> list[dict]:
    """The ring as chrome-trace events (``ts``/``dur`` in microseconds of
    the span clock)."""
    return [_chrome(r) for r in _tail()]


def events_since(start: int) -> tuple[list[dict], int]:
    """(events that ended after cursor `start`, next cursor). The
    incremental read the fleet TelemetryClient ships span batches with:
    O(batch) to build, and eviction-safe (a cursor older than the ring's
    oldest span returns the whole ring). A cursor past the newest span (a
    reset() happened) rewinds to 0."""
    if start > _appended[0] or start < 0:
        start = 0
    rows = _tail(start)
    return [_chrome(r) for r in rows], (rows[-1][0] if rows else start)


def dropped() -> int:
    """Spans that have fallen off the ring since the last reset()."""
    return max(0, _appended[0] - len(_ring))


def capacity() -> int:
    return _ring.maxlen


# ----------------------------------------------------------------- export

def _read_max_events() -> int:
    try:
        return int(os.environ.get(ENV_MAX, "") or EXPORT_CAPACITY)
    except ValueError:
        return EXPORT_CAPACITY


def _resize(cap: int):
    global _ring
    if _ring.maxlen != cap:
        _ring = deque(_ring, maxlen=max(1, cap))


def tracing_enabled() -> bool:
    """Is the chrome-trace export on? (The ring records either way.)"""
    return _enabled


def enable_tracing(trace_dir: str | None = None):
    """Turn the export on. `trace_dir` (or $PADDLE_TRACE_DIR) is where
    export_chrome_trace lands by default; the ring grows to
    $PADDLE_TRACE_MAX_EVENTS, and the first enable registers an atexit
    export so a traced process always leaves a trace file."""
    global _enabled, _trace_dir
    _trace_dir = trace_dir or os.environ.get(ENV_DIR) or _trace_dir
    _resize(_read_max_events())
    _enabled = True
    if not _atexit_registered[0]:
        _atexit_registered[0] = True
        atexit.register(_export_at_exit)


def disable_tracing():
    """Turn the export off; the ring keeps its size and keeps recording."""
    global _enabled
    _enabled = False


def reset():
    """Drop collected spans (tests); the export stays in its current state
    and the ring takes the size that state asks for."""
    global _seq
    _ring.clear()
    _seq = itertools.count(1)
    _appended[0] = 0
    _extra_meta.clear()
    _tls.__dict__.pop("stack", None)
    _resize(_read_max_events() if _enabled else DEFAULT_CAPACITY)


def set_trace_metadata(key: str, value):
    """Attach one key to the exported trace's otherData (e.g. the XPlane
    dump dir, so the host trace links the device-side story)."""
    _extra_meta[key] = value


def export_chrome_trace(path: str | None = None) -> str:
    """Write the collected spans as a chrome://tracing / Perfetto JSON file
    and return its path. Default location: $PADDLE_TRACE_DIR (or the
    enable_tracing dir) /trace_<pid>.json. The file is written atomically
    and is always valid JSON, even with zero spans."""
    if path is None:
        base = _trace_dir or os.environ.get(ENV_DIR) or "."
        os.makedirs(base, exist_ok=True)
        path = os.path.join(base, f"trace_{os.getpid()}.json")
    meta = [{"name": "process_name", "ph": "M", "pid": os.getpid(), "tid": 0,
             "args": {"name": "paddle_tpu"}}]
    doc = {"traceEvents": meta + events(), "displayTimeUnit": "ms",
           "otherData": {"clock": "unix_us", "dropped_events": dropped(),
                         **_extra_meta}}
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, default=str)  # numpy scalars etc. in span args
    os.replace(tmp, path)
    return path


def _export_at_exit():
    if _enabled and (_trace_dir or os.environ.get(ENV_DIR)):
        try:
            export_chrome_trace()
        except OSError:
            pass


# ---------------------------------------------------------- compile spans
# JAX reports every step of its compile path through jax.monitoring; each
# becomes one completed span, so "which program compiled, when, for how
# long, under which span" is a query over the ring, and set-up's split into
# tracing, lowering and compiling (or fetching from the cache) is a sum.
COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    # compiles, or fetches the executable from the persistent cache
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_NESTED_TRACE_S = 1e-3  # where JAX does not say: a one-liner's trace is us
_watching = [False]


def _on_compile_span(event, start_time, end_time, **kw):
    name = COMPILE_SPANS.get(event)
    if name is None:
        return
    if name == "compile.trace" and not (
            _tracing_at_top_level() if _tracing_at_top_level is not None
            else end_time - start_time >= _NESTED_TRACE_S):
        # a jitted function traced while another is being traced (every
        # jnp.where inside a model) is part of that outer trace, whose own
        # span covers it: thousands a program, and none on its own
        return
    fun = kw.get("fun_name")
    add_span(name, "compile", start_time, end_time,
             **({"fun": str(fun)} if fun else {}))
    if name == "compile.backend":
        metrics.counter("compile.programs").inc()


def _on_event(event, **kw):
    if event == _CACHE_HIT:
        metrics.counter("compile.cache_hits").inc()


def watch_compiles():
    """Register the jax.monitoring listeners, once per process: two list
    appends, nothing compiled, nothing started."""
    if _watching[0]:
        return
    _watching[0] = True
    # start and end as JAX took them (time.time(): the span clock)
    _monitoring.register_event_time_span_listener(_on_compile_span)
    _monitoring.register_event_listener(_on_event)


# a run launched with PADDLE_TRACE_DIR set exports from the first import
if os.environ.get(ENV_DIR):
    enable_tracing()
