"""What every runner shares: files, the device, the clock, the profiler,
the count of compilations, and the table of numbers compared."""
from __future__ import annotations

import contextlib
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
now = time.perf_counter


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def say(obj) -> None:
    """An earlier line of standard output: information, never the result."""
    print(json.dumps(obj), flush=True)


def load_cell(workload: str, rehearse: bool) -> dict:
    """A cell's files, found by the names in BENCHMARK.json."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg_path = os.path.join(ROOT, conf["file"])
    if rehearse:
        cfg_path = os.path.join(os.path.dirname(cfg_path), "rehearse",
                                os.path.basename(cfg_path))
    with open(cfg_path) as f:
        cfg = json.load(f)
    traffic = load_json("traffic", cell["traffic"] + ".json")
    if rehearse:
        for key, val in traffic.get("rehearse", {}).items():
            if isinstance(val, dict) and isinstance(traffic.get(key), dict):
                traffic[key] = {**traffic[key], **val}
            else:
                traffic[key] = val
    limits = load_json("limits", workload + ".json")
    if rehearse:    # a limit belongs to a size: the tiny size has its own
        limits["limits"].update(limits.get("rehearse_limits", {}))
    return {"bench": bench, "cell": cell, "cfg": cfg, "traffic": traffic,
            "limits": limits}


def metrics_of(bench: dict, cell: dict, group: str) -> list[dict]:
    """The metrics of `group` that this cell reports."""
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or cell["name"] in m["workloads"]}
    if group == "end_to_end":
        return [m for m in bench["end_to_end"] if m["name"] in e2e]
    return [m for m in bench["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


# ------------------------------------------------------------------ device

def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(dev: dict, chips: int, rehearse: bool) -> None:
    """No accelerator, or fewer chips than the cell asks for, is an error:
    there is no CPU fall-back outside a rehearsal."""
    if dev["count"] < chips:
        raise SystemExit(f"the cell needs {chips} device(s), JAX has "
                         f"{dev['count']}")
    if not rehearse and dev["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX found platform {dev['platform']!r}")
    if not rehearse and dev["count"] != chips:
        raise SystemExit(f"the cell is for {chips} chip(s), this machine "
                         f"has {dev['count']}")


def memory_peak_bytes(chips: int):
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None     # the CPU backend reports none


# ------------------------------------------------------------ compilations

class CompileCounter:
    """Counts programs compiled, or fetched from the compilation cache,
    while it is open: both mean a shape the set-up did not warm."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    _installed = None

    def __init__(self):
        import jax.monitoring as mon
        self.count = 0
        self.open = False
        if CompileCounter._installed is None:
            mon.register_event_duration_secs_listener(CompileCounter._on)
        CompileCounter._installed = self

    @staticmethod
    def _on(event, duration, **kw):
        me = CompileCounter._installed
        if me is not None and me.open and event == CompileCounter.EVENT:
            me.count += 1

    def __enter__(self):
        self.open = True
        return self

    def __exit__(self, *exc):
        self.open = False


# ---------------------------------------------------------------- profiler

@contextlib.contextmanager
def traced_window(enabled: bool):
    """Yields the directory the trace is written to (None when off). The
    window itself is marked by the span `perfbench.window`; the Python
    tracer is off, it slows the host and the harness has spans of its own."""
    import jax
    if not enabled:
        with jax.profiler.TraceAnnotation("perfbench.window"):
            yield None
        return
    tmp = tempfile.mkdtemp(prefix="perfbench_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
    except (AttributeError, TypeError):
        jax.profiler.start_trace(tmp)
    try:
        with jax.profiler.TraceAnnotation("perfbench.window"):
            yield tmp
    finally:
        jax.profiler.stop_trace()


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation("perfbench." + name)


# ------------------------------------------------------------------ checks

class Checks:
    """The numbers compared, each beside its limit. A number over its limit,
    or one that is not a number, makes the run not correct."""

    def __init__(self, limits: dict):
        self.limits = limits
        self.rows: dict = {}

    def add(self, name: str, value) -> None:
        limit = self.limits[name]
        v = float(value)
        ok = v == v and v <= limit
        self.rows[name] = {"value": v, "limit": limit, "ok": bool(ok)}

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows.values())

    def report(self, title: str = "compared") -> None:
        for name, r in self.rows.items():
            print(f"{title}: {name} = {r['value']:.6g} (limit "
                  f"{r['limit']:.6g}) {'ok' if r['ok'] else 'OVER'}",
                  file=sys.stderr, flush=True)
