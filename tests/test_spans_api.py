"""The one span API (ISSUE 27): what a span records, what it costs when
nobody reads it, and where the program's spans and names are.

  * RECORD — name, start, end, parent (innermost open span of the same
    thread), args; add_span on the same clock; the ring's bound and its
    drop count; the chrome view and the incremental cursor.
  * COST — a span with no reader reads no environment variable and opens
    no file; importing paddle_tpu and constructing a tiny LlamaTrainStep
    and ContinuousBatcher starts no profiler session and no thread, and
    compiles exactly what it compiles with the tracing layer taken out.
  * CLOCK — the ring's copy of a span and the profiler's copy of the same
    span agree (tools/span_clock_check.py; on the chip the same tool holds
    a device interval inside its span).
  * PLACES — every pallas_call under paddle_tpu/ops has a name=; the one
    TraceAnnotation under paddle_tpu/ is in observability/spans.py and
    profiler.RecordEvent goes through it; a tiny engine run yields
    serve.step spans whose children tile them and whose pad counts equal
    a hand count; greedy tokens are the same with the export on and off;
    a compilation inside an open span names that span as its parent.
"""
import ast
import bisect
import builtins
import importlib.util
import os
import re
import resource
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401
from paddle_tpu import observability as obs
from paddle_tpu.inference.serving import DISPATCH_PHASES
from paddle_tpu.observability import metrics, spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, "paddle_tpu")


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.delenv("PADDLE_TRACE_DIR", raising=False)
    monkeypatch.delenv("PADDLE_TRACE_MAX_EVENTS", raising=False)
    spans.disable_tracing()
    obs.reset()
    yield
    spans.disable_tracing()
    obs.reset()


def _tiny():
    from paddle_tpu.models import LlamaConfig
    from paddle_tpu.models.llama import llama_init_params
    cfg = LlamaConfig.tiny()
    return cfg, llama_init_params(cfg, jax.random.PRNGKey(0))


def _engine(cfg, params, **kw):
    from paddle_tpu.inference import ContinuousBatcher
    base = dict(max_batch=4, max_len=64, prompt_buckets=(8, 16), burst=4,
                page_size=8)
    return ContinuousBatcher(cfg, params, **{**base, **kw})


class _NoAnnotation:
    """The profiler's sink taken out."""

    def __init__(self, name, **kw):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _prompts(cfg, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab_size, n).tolist() for n in lens]


# ----------------------------------------------------------------- record

@pytest.mark.usefixtures("no_automatic_collection")
class TestRecord:
    def test_parent_is_the_innermost_open_span_of_the_thread(self):
        got = {}

        def other():
            with spans.span("t.outer", cat="user") as o:
                with spans.span("t.inner", cat="user") as i:
                    got["ids"] = (o.id, i.id, i.parent, o.parent)

        with spans.span("m.outer", cat="step", step=3) as mo:
            th = threading.Thread(target=other)
            th.start()
            th.join()
            with spans.span("m.inner", cat="step") as mi:
                pass
        o_id, i_id, i_parent, o_parent = got["ids"]
        assert i_parent == o_id and o_parent == 0   # not the main thread's
        assert mi.parent == mo.id and mo.parent == 0
        recs = {r.name: r for r in spans.records()}
        assert set(recs) == {"m.outer", "m.inner", "t.outer", "t.inner"}
        assert recs["m.outer"].args == {"step": 3}
        assert recs["t.inner"].tid != recs["m.inner"].tid
        for inner, outer in (("m.inner", "m.outer"), ("t.inner", "t.outer")):
            assert recs[inner].parent == recs[outer].id
            assert recs[outer].t0_ns <= recs[inner].t0_ns \
                <= recs[inner].t1_ns <= recs[outer].t1_ns

    def test_a_generators_span_is_never_a_parent(self):
        """A span begun inside a generator is ended when the generator is
        exhausted or collected, between or after the consumer's own spans:
        `begin(nest=False)` gives it a parent and keeps it off the stack."""
        def batches():
            sp = spans.span("io.epoch", cat="data").begin(nest=False)
            try:
                yield 1
                yield 2
            finally:
                sp.end()

        with spans.span("loop") as loop:
            gen = batches()
            next(gen)
            with spans.span("train.step", cat="step") as step:
                pass
        del gen                     # abandoned after one batch
        with spans.span("later") as later:
            pass
        assert step.parent == loop.id and later.parent == 0
        recs = {r.name: r for r in spans.records()}
        assert recs["io.epoch"].parent == loop.id
        # and the pool's own generator does so
        import inspect
        from paddle_tpu.io import worker_pool
        assert "begin(nest=False)" in inspect.getsource(
            worker_pool.WorkerPool.run_epoch)

    def test_add_span_is_on_the_same_clock_and_under_the_open_span(self):
        with spans.span("holder") as h:
            t0 = spans.now()
            time.sleep(0.002)
            t1 = spans.now()
            spans.add_span("done.earlier", "request", t0, t1, rid=7)
        recs = {r.name: r for r in spans.records()}
        a, hold = recs["done.earlier"], recs["holder"]
        assert a.parent == h.id and a.args == {"rid": 7} and a.cat == "request"
        assert hold.t0_ns <= a.t0_ns < a.t1_ns <= hold.t1_ns
        assert 1.5e6 <= a.t1_ns - a.t0_ns <= 50e6
        # the clock is the wall clock in ns: what the profiler stamps with
        assert abs(hold.t0_ns - time.time_ns()) < 5e9

    def test_ring_is_bounded_and_counts_what_fell_off(self, monkeypatch):
        assert spans.capacity() == spans.DEFAULT_CAPACITY
        n = spans.DEFAULT_CAPACITY + 40
        for i in range(n):
            with spans.span("s", i=i):
                pass
        recs = spans.records()
        assert len(recs) == spans.DEFAULT_CAPACITY and spans.dropped() == 40
        assert recs[0].args == {"i": 40} and recs[-1].args == {"i": n - 1}
        # the export raises the bound to PADDLE_TRACE_MAX_EVENTS
        monkeypatch.setenv("PADDLE_TRACE_MAX_EVENTS", "50000")
        spans.enable_tracing()
        assert spans.capacity() == 50000 and len(spans.records()) == len(recs)
        spans.disable_tracing()
        spans.reset()
        assert spans.capacity() == spans.DEFAULT_CAPACITY
        assert spans.records() == [] and spans.dropped() == 0

    def test_chrome_view_and_cursor(self):
        with spans.span("a", cat="step", k=1):
            pass
        batch, cur = spans.events_since(0)
        assert [e["name"] for e in batch] == ["a"] and cur == 1
        ev = batch[0]
        assert ev["ph"] == "X" and ev["cat"] == "step" and ev["dur"] >= 0
        assert ev["args"] == {"k": 1} and ev["pid"] == os.getpid()
        assert abs(ev["ts"] * 1e3 - time.time_ns()) < 5e9    # us, wall clock
        with spans.span("b"):
            pass
        batch, cur = spans.events_since(cur)
        assert [e["name"] for e in batch] == ["b"] and cur == 2
        assert spans.events_since(cur) == ([], 2)
        assert [e["name"] for e in spans.events_since(99)[0]] == ["a", "b"]
        assert [r.name for r in spans.records(since=1)] == ["b"]


# --------------------------------------------------------------- overlays

class TestOverlay:
    def test_an_overlay_is_nobodys_child_and_nobodys_parent(self):
        """It lies inside a span without being its child, so the self time
        that `engine.dispatch_ms_per_step` reads (duration less child
        spans) is the same with it as without it."""
        from perfbench.reducers import _program
        with spans.span("holder") as h:
            with spans.span("phase.a", overlay=True, k=1) as a:
                with spans.span("inner") as inner:
                    time.sleep(0.002)
            t0 = spans.now()
            time.sleep(0.001)
            spans.add_span("phase.b", "user", t0, spans.now(), overlay=True)
        recs = spans.records()
        by = {r.name: r for r in recs}
        assert a.parent == 0 and by["phase.a"].parent == 0
        assert by["phase.a"].args == {"k": 1} and by["phase.b"].parent == 0
        assert inner.parent == h.id     # the overlay was not on the stack
        assert not [r for r in recs if r.parent in (a.id, by["phase.b"].id)]
        for r in ("phase.a", "phase.b"):
            assert by["holder"].t0_ns <= by[r].t0_ns <= by[r].t1_ns \
                <= by["holder"].t1_ns
        start = by["holder"].t0_ns

        def self_s(rows):
            ps = _program.ProgramSpans(rows, start, (0.0, 1.0))
            return ps.self_seconds(next(x for x in ps.rows
                                        if x[2].name == "holder"))

        bare = [r for r in recs if not r.name.startswith("phase.")]
        assert self_s(recs) == self_s(bare)
        assert self_s(recs) == pytest.approx(
            (by["holder"].t1_ns - by["holder"].t0_ns
             - (by["inner"].t1_ns - by["inner"].t0_ns)) * 1e-9)


@pytest.mark.usefixtures("no_automatic_collection")
class TestCollector:
    """Every collection counted is one the test asked for."""

    def test_a_forced_collection_is_one_overlay_and_counts(self):
        import gc
        seq0 = max((r.seq for r in spans.records()), default=0)
        with spans.span("holder") as h:
            gc.collect()
        got = [r for r in spans.records(since=seq0)
               if r.name == spans.GC_SPAN and r.args["gen"] == 2]
        assert len(got) == 1
        g = got[0]
        assert g.parent == 0 and g.cat == "gc" and g.args["collected"] >= 0
        hold = next(r for r in spans.records() if r.id == h.id)
        assert hold.t0_ns <= g.t0_ns <= g.t1_ns <= hold.t1_ns
        counts = metrics.snapshot()["counters"]
        assert counts["gc.collections"] >= 1
        assert counts["gc.seconds"] >= (g.t1_ns - g.t0_ns) * 1e-9 * 0.99
        # exported as what they are: a count, and seconds not cut to whole
        from paddle_tpu.observability import exporters
        ms = exporters.otlp_payload(metrics.snapshot())["resourceMetrics"][
            0]["scopeMetrics"][0]["metrics"]
        dps = {m["name"]: m["sum"]["dataPoints"][0] for m in ms
               if m["name"].startswith("gc.")}
        assert dps["gc.collections"]["asInt"] == str(counts["gc.collections"])
        assert dps["gc.seconds"]["asDouble"] == counts["gc.seconds"]
        # generation 0 counts and leaves no span
        n0 = len([r for r in spans.records() if r.name == spans.GC_SPAN])
        c0 = metrics.counter("gc.collections").value
        gc.collect(0)
        assert metrics.counter("gc.collections").value == c0 + 1
        assert len([r for r in spans.records()
                    if r.name == spans.GC_SPAN]) == n0

    def test_the_hook_survives_a_reset_in_mid_collection(self):
        import gc
        resets = []

        def reset_between(phase, info):     # after the hook, on "start"
            if phase == "start" and not resets:
                resets.append(phase)
                obs.reset()

        gc.callbacks.append(reset_between)
        try:
            gc.collect()
        finally:
            gc.callbacks.remove(reset_between)
        assert resets
        # the reading lands in the fresh ring and fresh counters, or is lost;
        # the next collection is read whole
        gc.collect()
        got = [r for r in spans.records() if r.name == spans.GC_SPAN
               and r.args["gen"] == 2]
        assert 1 <= len(got) <= 2
        assert 1 <= metrics.counter("gc.collections").value <= 2
        assert spans._on_gc("stop", {}) is None     # a stop with no start

    def test_a_collection_inside_an_append_keeps_the_ring_in_order(
            self, monkeypatch):
        """A collection can fall between an append's seq and its ring
        append (the row's tuple is an allocation). Its row comes after that
        row, so an incremental read, which stops at the first seq it has
        seen, misses neither and `dropped()` counts none."""
        real, fell = threading.get_ident, []

        def ident():        # called while the row's tuple is built
            if not fell:
                fell.append(1)
                spans._on_gc("start", {"generation": 2, "collected": 0})
                spans._on_gc("stop", {"generation": 2, "collected": 3})
            return real()

        _, cursor = spans.events_since(0)
        monkeypatch.setattr(spans.threading, "get_ident", ident)
        with spans.span("outer"):
            pass
        monkeypatch.setattr(spans.threading, "get_ident", real)
        assert fell
        got, _ = spans.events_since(cursor)
        assert [e["name"] for e in got] == ["outer", spans.GC_SPAN]
        assert got[1]["args"] == {"gen": 2, "collected": 3}
        seqs = [r.seq for r in spans.records()]
        assert seqs == sorted(seqs) and spans.dropped() == 0

    def test_collections_with_no_span_recorded_hold_a_bounded_tail(self):
        """A process that records and reads no span between collections
        holds the newest of them, at most a ring's worth, not every one."""
        n = spans._held.maxlen + 5
        for i in range(n):
            spans._on_gc("start", {})
            spans._on_gc("stop", {"generation": 1, "collected": i})
        assert len(spans._held) == spans._held.maxlen
        got = [r.args["collected"] for r in spans.records()
               if r.name == spans.GC_SPAN]
        assert got[-1] == n - 1 and not spans._held

    def test_the_hook_waits_on_no_lock(self):
        """A collection that falls while its own thread holds the metrics
        registry's lock, with the counters not yet made, loses its count
        and does not wait."""
        import gc
        metrics.reset()
        done = threading.Event()

        def collect_under_the_lock():
            with metrics._lock:
                gc.collect()
            done.set()

        th = threading.Thread(target=collect_under_the_lock, daemon=True)
        th.start()
        th.join(timeout=60)
        assert done.is_set() and not th.is_alive()
        assert "gc.collections" not in metrics.snapshot()["counters"]
        gc.collect()
        assert metrics.counter("gc.collections").value == 1
        assert metrics.counter("gc.seconds").value > 0

    def test_registered_once_at_import(self):
        import gc
        spans.watch_gc()
        assert gc.callbacks.count(spans._on_gc) == 1


# ------------------------------------------------------------------- cost

class TestCost:
    def test_a_span_with_no_reader_reads_no_env_and_opens_no_file(
            self, monkeypatch):
        def boom(*a, **k):
            raise AssertionError("the tracing layer touched the outside")

        with spans.span("warm"):       # thread-local state made beforehand
            pass
        monkeypatch.setattr(os.environ, "get", boom)
        monkeypatch.setattr(os, "getenv", boom)
        monkeypatch.setattr(builtins, "open", boom)
        n_threads = threading.active_count()
        with spans.span("serve.step", cat="serve", burst=1, live=2):
            with spans.span("serve.merge", cat="serve"):
                spans.add_span("req", "request", spans.now(), spans.now())

        @spans.traced("loader.next", cat="data")
        def f():
            return 1

        assert f() == 1
        monkeypatch.undo()
        assert threading.active_count() == n_threads
        assert {r.name for r in spans.records()} >= {
            "serve.step", "serve.merge", "req", "loader.next"}

    def test_import_left_one_span_and_registered_listeners_once(self):
        from jax._src import monitoring as mon
        spans.watch_compiles()      # a second call registers nothing more
        mine = [cb for cb in mon.get_event_time_span_listeners()
                if getattr(cb, "__module__", "") == spans.__name__]
        assert len(mine) == 1
        # the ring was reset by the fixture; the import's span is made at
        # import, so ask a fresh interpreter for it
        import subprocess
        import sys
        code = ("import threading, paddle_tpu, jax\n"
                "from paddle_tpu.observability import spans\n"
                "from jax._src import profiler as p\n"
                "r = [s for s in spans.records() "
                "if s.name == 'import.paddle_tpu']\n"
                "assert len(r) == 1 and r[0].t1_ns - r[0].t0_ns > 1e8\n"
                "assert p._profile_state.profile_session is None\n"
                "assert not spans.tracing_enabled()\n"
                "assert [t.name for t in threading.enumerate()] == "
                "['MainThread'], threading.enumerate()\n")
        env = {k: v for k, v in os.environ.items()
               if k not in ("PADDLE_TRACE_DIR", "PADDLE_XPLANE_DIR")}
        subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       check=True, timeout=120)

    def test_construction_compiles_nothing_for_tracings_sake(
            self, monkeypatch):
        """LlamaTrainStep and ContinuousBatcher, built once as they are and
        once with the tracing layer's sinks taken out: the same programs
        compile, no profiler session starts, no thread appears."""
        from jax._src import profiler as jprof
        from paddle_tpu.models import LlamaConfig, LlamaTrainStep

        seen: list = []

        def on(event, start, end, **kw):
            if event.endswith("backend_compile_duration"):
                seen.append(kw.get("fun_name"))

        import jax.monitoring as mon
        mon.register_event_time_span_listener(on)
        try:
            def build():
                jax.clear_caches()
                seen.clear()
                cfg = LlamaConfig.tiny()
                step = LlamaTrainStep(cfg, remat=True, seed=0)
                eng = _engine(cfg, step.params)
                del step, eng
                return sorted(map(str, seen))

            n_threads = threading.active_count()
            with_tracing = build()
            assert jprof._profile_state.profile_session is None
            assert threading.active_count() == n_threads
            built = [r.name for r in spans.records()
                     if r.name in ("train.init", "serve.init")]
            assert built == ["train.init", "serve.init"]
            monkeypatch.setattr(spans, "_Annotation", _NoAnnotation)
            monkeypatch.setattr(spans, "_append", lambda *a: None)
            without = build()
            assert with_tracing == without and len(without) > 0
        finally:
            mon.unregister_event_time_span_listener(on)


# ------------------------------------------------------------------ clock

def test_ring_and_profiler_agree_on_one_span():
    spec = importlib.util.spec_from_file_location(
        "span_clock_check", os.path.join(ROOT, "tools", "span_clock_check.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = mod.check(repeats=3, size=64)
    assert out["ok"], out
    assert out["host_annotations"] == 3 and out["spans"] == 3


# ----------------------------------------------------------------- places

def _calls(tree, attr):
    return [n for n in ast.walk(tree) if isinstance(n, ast.Call)
            and isinstance(n.func, ast.Attribute) and n.func.attr == attr]


def test_every_pallas_call_has_a_name():
    found = {}
    ops = os.path.join(PKG, "ops")
    for fn in sorted(os.listdir(ops)):
        if not fn.endswith(".py"):
            continue
        with open(os.path.join(ops, fn)) as f:
            tree = ast.parse(f.read())
        for call in _calls(tree, "pallas_call"):
            names = [k.value.value for k in call.keywords if k.arg == "name"
                     and isinstance(k.value, ast.Constant)]
            assert names, f"{fn}:{call.lineno}: pallas_call without name="
            found[names[0]] = fn
    assert set(found) == {
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
        "paged_decode_attention", "paged_kv_scatter", "block_sparse_fwd", "block_sparse_bwd_dq",
        "block_sparse_bwd_dkv", "gdn_step"}


def test_trace_annotation_lives_in_one_place():
    hits = []
    for d, _, files in os.walk(PKG):
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(d, fn)) as f:
                    src = f.read()
                if re.search(r"\bTraceAnnotation\(|annotate_function", src) \
                        or "import TraceAnnotation" in src:
                    hits.append(os.path.relpath(os.path.join(d, fn), PKG))
    assert hits == [os.path.join("observability", "spans.py")]


def test_record_event_goes_through_the_span_api(monkeypatch):
    from paddle_tpu import profiler
    entered = []

    class Ann:
        def __init__(self, name, **kw):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            entered.append("/" + self.name)

    monkeypatch.setattr(spans, "_Annotation", Ann)
    with profiler.RecordEvent("fwd"):
        with profiler.RecordEvent("matmul"):
            pass
    assert entered == ["fwd", "matmul", "/matmul", "/fwd"]
    recs = {r.name: r for r in spans.records()}
    assert recs["matmul"].parent == recs["fwd"].id
    assert recs["fwd"].cat == "profiler"
    assert not hasattr(profiler.RecordEvent("x"), "_ann")


def _note_cpu_at_every_edge(monkeypatch) -> list:
    """Every reading of the span clock on this thread, with the thread's
    CPU time and context switches beside it: [(wall ns, thread CPU ns,
    voluntary switches, involuntary switches)], in order."""
    stamps: list = []
    me = threading.get_ident()

    def now_ns():
        t = time.time_ns()
        if threading.get_ident() == me:
            ru = resource.getrusage(resource.RUSAGE_THREAD)
            stamps.append((t, time.thread_time_ns(), ru.ru_nvcsw,
                           ru.ru_nivcsw))
        return t

    monkeypatch.setattr(spans, "now_ns", now_ns)
    monkeypatch.setattr(spans, "now", lambda: now_ns() * 1e-9)
    return stamps


def _gaps_left(stamps, recs, gaps) -> tuple[int, int]:
    """(what the scheduler took, what nothing explains) of the wall time of
    `gaps` (edges of spans, ns). The scheduler took a gap's time off the
    CPU where the thread was switched out and never gave the CPU up itself
    (involuntary switches rose, voluntary ones did not): a pytest worker
    beside five others loses 5-17 ms so in one gap under load. A gap with a
    voluntary switch (a wait: a lock, a device_get, I/O) keeps its whole
    time. A `gc.collect` overlay in a gap explains its part: a collection
    falls wherever its thread allocated, and is no phase's."""
    walls = [st[0] for st in stamps]
    gcs = [(r.t0_ns, r.t1_ns) for r in recs if r.name == spans.GC_SPAN]

    def at(t):      # the stamp nearest the edge: add_span rounds through s
        i = bisect.bisect_left(walls, t)
        return stamps[min((j for j in (i - 1, i) if 0 <= j < len(walls)),
                          key=lambda j: abs(walls[j] - t))]

    took = left = 0
    for a, b in gaps:
        (_, ca, va, ia), (_, cb, vb, ib) = at(a), at(b)
        off = max(0, (b - a) - (cb - ca)) if ib > ia and vb == va else 0
        collected = sum(max(0, min(b, g1) - max(a, g0)) for g0, g1 in gcs)
        took += off
        left += max(0, (b - a) - off - collected)
    return took, left


class TestEngineSpans:
    LENS = (5, 9, 12, 7, 3)

    def _run(self, new_tokens=6, model=None, **kw):
        cfg, params = model or _tiny()
        eng = _engine(cfg, params, **kw)
        for p in _prompts(cfg, self.LENS):
            eng.add_request(p, max_new_tokens=new_tokens)
        seq0 = max((r.seq for r in spans.records()), default=0)
        out = eng.run()
        return eng, out, spans.records(since=seq0)

    @pytest.mark.parametrize("layout,read", [
        ("paged", "gather"), ("paged", "kernel"), ("dense", "dense")],
        ids=["paged", "kernel", "dense"])
    def test_children_tile_the_step(self, wide_model, layout, read,
                                    monkeypatch):
        stamps = _note_cpu_at_every_edge(monkeypatch)
        eng, out, recs = self._run(
            kv_layout=layout, model=wide_model if read == "kernel" else None)
        assert eng.stats["kv_read"] == read
        assert all(r.args["kv_read"] == read for r in recs
                   if r.name == "serve.dispatch_burst")
        steps = [r for r in recs if r.name == "serve.step"]
        assert steps and all(len(t) == 6 for t in out.values())
        phases = ("serve.dispatch_burst", "serve.admit", "serve.readback",
                  "serve.merge")
        busy = 0
        for st in steps:
            kids = [r for r in recs if r.parent == st.id
                    and not r.name.startswith("compile.")]
            assert {k.name for k in kids} <= set(phases)
            assert all(st.t0_ns <= k.t0_ns <= k.t1_ns <= st.t1_ns
                       for k in kids)
            kids.sort(key=lambda r: r.t0_ns)
            assert all(a.t1_ns <= b.t0_ns for a, b in zip(kids, kids[1:]))
            dur = st.t1_ns - st.t0_ns
            # the phases are the step, of a step over 2 ms: less what the
            # scheduler took between them, a collection between them
            # counted as covered
            edges = [st.t0_ns] + [t for k in kids for t in (k.t0_ns, k.t1_ns)] \
                + [st.t1_ns]
            took, left = _gaps_left(stamps, recs,
                                    zip(edges[::2], edges[1::2]))
            own = dur - took
            if kids and own > 2e6:
                covered = own - left
                assert covered >= 0.9 * own, (covered, dur, own)
            busy += bool(kids)
            assert set(st.args) == {"burst", "live"}
        assert busy >= 2
        names = {r.name for r in recs}
        assert set(phases) <= names

    @pytest.mark.parametrize("read", ["gather", "kernel"])
    def test_the_dispatch_phases_tile_the_dispatch(self, wide_model, read,
                                                   monkeypatch):
        stamps = _note_cpu_at_every_edge(monkeypatch)
        eng, out, recs = self._run(
            model=wide_model if read == "kernel" else None)
        assert eng.stats["kv_read"] == read
        phases = [r for r in recs if r.name.startswith("serve.dispatch.")]
        assert {r.name for r in phases} == set(DISPATCH_PHASES)
        assert all(r.parent == 0 and r.args is None for r in phases)
        assert len(phases) == 3 * eng.stats["bursts"]
        for d in (r for r in recs if r.name == "serve.dispatch_burst"):
            mine = sorted((r for r in phases if d.t0_ns <= r.t0_ns
                           and r.t1_ns <= d.t1_ns), key=lambda r: r.t0_ns)
            if not mine:                # nothing active: an early return
                continue
            assert [r.name for r in mine] == list(DISPATCH_PHASES)
            assert all(a.t1_ns == b.t0_ns for a, b in zip(mine, mine[1:]))
            took, left = _gaps_left(
                stamps, recs,
                [(d.t0_ns, mine[0].t0_ns), (mine[-1].t1_ns, d.t1_ns)])
            own = d.t1_ns - d.t0_ns - took
            if own > 2e6:
                covered = own - left
                assert covered >= 0.9 * own, (covered, own)

    def test_pad_counts_equal_a_hand_count(self):
        real0 = metrics.counter("serve.prefill_tokens_real").value
        eng, out, recs = self._run()
        # buckets (8, 16): 5->8, 9->16, 12->16, 7->8, 3->8
        real, padded = sum(self.LENS), 8 + 16 + 16 + 8 + 8
        admits = [r.args for r in recs if r.name == "serve.admit"]
        assert sum(a["real"] for a in admits) == real
        assert sum(a["padded"] for a in admits) == padded
        assert sum(a["prefills"] for a in admits) == len(self.LENS)
        snap = metrics.snapshot()["counters"]
        assert snap["serve.prefill_tokens_real"] - real0 == real
        assert snap["serve.prefill_tokens_padded"] == padded

    def test_kv_read_gauge_is_set_when_the_bucket_changes(self, monkeypatch):
        asked = []
        real = metrics.gauge
        monkeypatch.setattr(metrics, "gauge",
                            lambda name: (asked.append(name), real(name))[1])
        eng, out, recs = self._run(new_tokens=40)
        n = asked.count("serve.kv_read_mb_per_tok")
        # once per change of bucket (it can come back down), not per burst
        assert len(eng.stats["page_buckets_used"]) <= n < eng.stats["bursts"]
        assert metrics.snapshot()["gauges"]["serve.kv_read_mb_per_tok"] > 0

    def test_tokens_are_the_same_with_the_export_on(self, tmp_path):
        _, plain, _ = self._run()
        spans.enable_tracing(str(tmp_path))
        try:
            _, traced, recs = self._run()
            path = spans.export_chrome_trace()
        finally:
            spans.disable_tracing()
        assert list(plain.values()) == list(traced.values())
        assert os.path.exists(path)
        assert {"req", "req.queue", "req.decode"} <= {r.name for r in recs}


def test_a_compilation_names_the_span_it_happened_under():
    @jax.jit
    def fresh_program_for_this_test(x):
        return jnp.tanh(x) * 3.0 + jnp.where(x > 0, x, -x)

    x = jnp.ones((3, 5))
    n0 = metrics.counter("compile.programs").value
    with spans.span("train.step", cat="step", step=9) as sp:
        jax.block_until_ready(fresh_program_for_this_test(x))
    mine = [r for r in spans.records() if r.cat == "compile"
            and r.parent == sp.id]
    by_name = {r.name: r for r in mine}
    assert set(by_name) == {"compile.trace", "compile.lower",
                            "compile.backend"}
    assert "fresh_program_for_this_test" in by_name["compile.backend"].args[
        "fun"]
    # jnp.where is traced inside the outer trace: no span of its own
    assert len([r for r in mine if r.name == "compile.trace"]) == 1
    order = [by_name[n] for n in ("compile.trace", "compile.lower",
                                  "compile.backend")]
    assert all(a.t0_ns <= b.t0_ns for a, b in zip(order, order[1:]))
    assert metrics.counter("compile.programs").value == n0 + 1
    # the second call compiles nothing: the query stays at one program
    with spans.span("train.step", cat="step", step=10) as sp2:
        fresh_program_for_this_test(x)
    assert not [r for r in spans.records() if r.parent == sp2.id]


def test_nested_traces_are_told_by_length_where_jax_does_not_say(monkeypatch):
    """`jax.core.trace_ctx` is not a documented name: without it the
    listener keeps the traces of a millisecond and more."""
    monkeypatch.setattr(spans, "_tracing_at_top_level", None)
    ev = "/jax/core/compile/jaxpr_trace_duration"
    t = spans.now()
    spans._on_compile_span(ev, t, t + 2e-5, fun_name="where")
    spans._on_compile_span(ev, t, t + 0.25, fun_name="step_fn")
    got = [r.args["fun"] for r in spans.records() if r.name == "compile.trace"]
    assert got == ["step_fn"]


def test_train_and_loader_spans(tmp_path):
    from paddle_tpu.io.token_loader import TokenDataLoader, write_token_file
    from paddle_tpu.models import LlamaConfig, LlamaTrainStep
    cfg = LlamaConfig.tiny()
    path = str(tmp_path / "c.u16")
    write_token_file(path, np.arange(4000) % cfg.vocab_size)
    loader = TokenDataLoader(path, 2, 16, seed=1)
    step = LlamaTrainStep(cfg, remat=True)
    try:
        for _ in range(2):
            tok, lab = next(loader)
            loss = step(tok, lab)
    finally:
        loader.close()
    assert np.isfinite(float(loss))
    recs = spans.records()
    names = [r.name for r in recs if r.name in ("loader.next", "train.step")]
    assert names == ["loader.next", "train.step"] * 2
    steps = [r for r in recs if r.name == "train.step"]
    assert [r.args["step"] for r in steps] == [1, 2]
    # the step program compiled under the first step, not the second
    compiled = [r for r in recs if r.name == "compile.backend"
                and "step_fn" in (r.args or {}).get("fun", "")]
    assert [r.parent for r in compiled] == [steps[0].id]
    # the scopes are in the program the device runs
    txt = step._jitted.lower(
        step._params, step._opt_state, jnp.asarray(tok), jnp.asarray(lab),
        jnp.float32(1e-3), jnp.int32(1)).as_text(debug_info=True)
    for scope in ("embed", "attn", "mlp", "head_loss", "optimizer"):
        assert re.search(r"[/(]" + scope + r"[/)]", txt), scope
