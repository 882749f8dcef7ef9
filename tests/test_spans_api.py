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
import builtins
import importlib.util
import os
import re
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401
from paddle_tpu import observability as obs
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
        "block_sparse_bwd_dkv"}


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
    def test_children_tile_the_step(self, wide_model, layout, read):
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
            covered = sum(k.t1_ns - k.t0_ns for k in kids)
            dur = st.t1_ns - st.t0_ns
            if kids and dur > 2e6:      # the phases are the step
                assert covered >= 0.9 * dur, (covered, dur)
            busy += bool(kids)
            assert set(st.args) == {"burst", "live"}
        assert busy >= 2
        names = {r.name for r in recs}
        assert set(phases) <= names

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
