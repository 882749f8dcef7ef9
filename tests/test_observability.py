"""Unified runtime telemetry tests (ISSUE 2): span tracing + chrome-trace
export, the process-wide metrics registry, and the crash-dump flight
recorder — plus the end-to-end acceptance contract: a PADDLE_CHAOS-injected
run under ResilientLoop leaves, without any re-run, a loadable chrome trace
(step/checkpoint/collective categories), a metrics snapshot naming the
injected faults, and a FLIGHT.json whose last events explain them.

Also wires tools/lint_observability.py (no bare print / raw time.time()
timing outside the telemetry layer) into tier-1.
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu  # noqa: F401  (registers the observability subpackage)
from paddle_tpu import observability as obs
from paddle_tpu.observability import metrics, recorder, spans
from paddle_tpu.distributed.resilience import ResilientLoop, RetryPolicy, chaos

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_telemetry(monkeypatch):
    monkeypatch.delenv("PADDLE_TRACE_DIR", raising=False)
    monkeypatch.delenv("PADDLE_METRICS_SINK", raising=False)
    monkeypatch.delenv("PADDLE_FLIGHT_RECORDER", raising=False)
    metrics.set_sink(None)
    spans.disable_tracing()
    obs.reset()
    chaos.reset()
    yield
    metrics.set_sink(None)
    spans.disable_tracing()
    obs.reset()
    chaos.reset()
    recorder.uninstall_crash_hook()


# ---------------------------------------------------------------- spans

class TestSpans:
    def test_disabled_path_is_a_flagcheck_noop(self, tmp_path, monkeypatch):
        """With the export off a span costs a ring append and nothing else
        leaves the process: no trace file at exit, nothing for the fleet
        client to ship. (The ring itself is always there: ISSUE 27.)"""
        assert not spans.tracing_enabled()
        monkeypatch.setenv("PADDLE_TRACE_DIR", str(tmp_path))
        with spans.span("hot", cat="step", i=1):
            pass
        spans._export_at_exit()
        assert os.listdir(tmp_path) == []
        from paddle_tpu.observability import fleet
        report, _ = fleet.TelemetryClient(node="n0", rank=0).build_report(
            step=1)
        assert report["spans"] == []
        assert [r.name for r in spans.records()] == ["hot"]

    def test_spans_nest_and_export_valid_chrome_trace(self, tmp_path):
        spans.enable_tracing(str(tmp_path))
        with spans.span("outer", cat="step", step=3):
            with spans.span("inner", cat="checkpoint"):
                time.sleep(0.002)
        path = spans.export_chrome_trace()
        doc = json.load(open(path))  # must be VALID json
        evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        by_name = {e["name"]: e for e in evs}
        assert set(by_name) == {"outer", "inner"}
        outer, inner = by_name["outer"], by_name["inner"]
        # proper nesting on the shared clock: inner ⊆ outer
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
        assert outer["cat"] == "step" and outer["args"]["step"] == 3
        assert inner["cat"] == "checkpoint"

    def test_decorator_form(self, tmp_path):
        spans.enable_tracing(str(tmp_path))

        @spans.span("work.unit", cat="user")
        def work(x):
            return x * 2

        assert work(21) == 42
        assert [e["name"] for e in spans.events()] == ["work.unit"]

    def test_decorator_late_binds_enablement(self, tmp_path):
        """traced() and span() used as decorators keep their explicit
        name/cat whenever they were made, and what was recorded before the
        export was turned on is exported with the rest."""
        @spans.traced("late.work", cat="data")
        def f():
            return 1

        @spans.span("via-span", cat="data")
        def g():
            return 2

        assert f() == 1 and g() == 2
        spans.enable_tracing(str(tmp_path))
        f()
        g()
        doc = json.load(open(spans.export_chrome_trace()))
        names = [e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert names == ["late.work", "via-span"] * 2
        evs = {e["name"]: e for e in spans.events()}
        assert evs["late.work"]["cat"] == evs["via-span"]["cat"] == "data"

    def test_threads_record_their_own_tid(self, tmp_path):
        spans.enable_tracing(str(tmp_path))

        def other():
            with spans.span("in-thread", cat="user"):
                pass

        t = threading.Thread(target=other)
        t.start()
        t.join()
        with spans.span("in-main", cat="user"):
            pass
        tids = {e["name"]: e["tid"] for e in spans.events()}
        assert tids["in-thread"] != tids["in-main"]

    def test_event_buffer_is_bounded(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PADDLE_TRACE_MAX_EVENTS", "10")
        spans.enable_tracing(str(tmp_path))
        for i in range(25):
            with spans.span(f"s{i}"):
                pass
        assert len(spans.events()) == 10
        assert spans.dropped() == 15

    def test_profiler_record_event_merges_into_trace(self, tmp_path):
        """RecordEvent scopes and profiler windows land in the SAME exported
        chrome trace as runtime spans (the tentpole merge contract)."""
        from paddle_tpu import profiler
        spans.enable_tracing(str(tmp_path))
        with spans.span("train.step", cat="step"):
            with profiler.RecordEvent("matmul-ish"):
                pass
        cats = {e["cat"]: e["name"] for e in spans.events()}
        assert cats.get("profiler") == "matmul-ish"
        assert "step" in cats

    def test_profiler_window_span(self, tmp_path, monkeypatch):
        import jax
        from paddle_tpu import profiler
        # the window span is host-side; don't start a real device trace
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda *a, **k: None)
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
        spans.enable_tracing(str(tmp_path))
        prof = profiler.Profiler(scheduler=profiler.make_scheduler(
            closed=1, ready=0, record=1, repeat=1))
        prof.start()
        for _ in range(3):
            prof.step()
        prof.stop()
        names = [e["name"] for e in spans.events()]
        assert "profiler.window" in names


# --------------------------------------------------------------- metrics

class TestMetrics:
    def test_counter_gauge_histogram_snapshot(self):
        metrics.counter("c").inc()
        metrics.counter("c").inc(4)
        metrics.gauge("g").set(2.5)
        for v in range(100):
            metrics.histogram("h").observe(float(v))
        s = metrics.snapshot()
        assert s["counters"]["c"] == 5
        assert s["gauges"]["g"] == 2.5
        h = s["histograms"]["h"]
        assert h["count"] == 100 and h["min"] == 0.0 and h["max"] == 99.0
        assert 45 <= h["p50"] <= 55 and 90 <= h["p95"] <= 99
        json.dumps(s)  # snapshot is always JSON-serializable

    def test_registry_returns_same_instance(self):
        assert metrics.counter("x") is metrics.counter("x")
        assert metrics.histogram("y") is metrics.histogram("y")

    def test_timer_observes_scoped_duration(self):
        with metrics.timer("op_s"):
            time.sleep(0.01)
        st = metrics.histogram("op_s").stats()
        assert st["count"] == 1 and st["last"] >= 0.005

    def test_thread_safety_exact_counts(self):
        def bump():
            for _ in range(1000):
                metrics.counter("mt").inc()
                metrics.histogram("mth").observe(1.0)

        ts = [threading.Thread(target=bump) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert metrics.counter("mt").value == 8000
        assert metrics.histogram("mth").count == 8000

    def test_jsonl_sink_appends_per_step_rows(self, tmp_path):
        sink = tmp_path / "m.jsonl"
        metrics.set_sink(str(sink))
        metrics.counter("steps").inc()
        metrics.maybe_emit_step(1)
        metrics.counter("steps").inc()
        metrics.maybe_emit_step(2)
        rows = [json.loads(l) for l in sink.read_text().splitlines()]
        assert [r["step"] for r in rows] == [1, 2]
        assert rows[0]["steps"] == 1 and rows[1]["steps"] == 2

    def test_csv_sink_pins_columns(self, tmp_path):
        sink = tmp_path / "m.csv"
        metrics.set_sink(str(sink))
        metrics.counter("a").inc()
        metrics.maybe_emit_step(1)
        metrics.maybe_emit_step(2)
        lines = sink.read_text().splitlines()
        assert lines[0].startswith("step,time,")
        assert len(lines) == 3  # header + 2 rows

    def test_env_var_configures_sink(self, tmp_path, monkeypatch):
        sink = tmp_path / "env.jsonl"
        monkeypatch.setenv("PADDLE_METRICS_SINK", str(sink))
        metrics.maybe_emit_step(7)
        assert json.loads(sink.read_text())["step"] == 7

    def test_no_sink_is_noop(self):
        metrics.maybe_emit_step(1)  # must not raise or create files


# -------------------------------------------------------------- recorder

class TestFlightRecorder:
    def test_ring_is_bounded(self, monkeypatch):
        monkeypatch.setenv("PADDLE_FLIGHT_RECORDER", "5")
        recorder.reset()
        for i in range(12):
            recorder.record("tick", i=i)
        evs = recorder.events()
        assert len(evs) == 5
        assert [e["i"] for e in evs] == list(range(7, 12))

    def test_disabled_by_env(self, monkeypatch):
        monkeypatch.setenv("PADDLE_FLIGHT_RECORDER", "0")
        recorder.reset()
        recorder.record("tick")
        assert recorder.events() == []
        assert recorder.dump_flight() is None

    def test_dump_writes_valid_json(self, tmp_path):
        recorder.record("alpha", message="first", n=1)
        recorder.record("omega", n=2)
        path = recorder.dump_flight(str(tmp_path), reason="unit test")
        assert os.path.basename(path) == "FLIGHT.json"
        doc = json.load(open(path))
        assert doc["reason"] == "unit test"
        assert [e["kind"] for e in doc["events"]] == ["alpha", "omega"]
        assert doc["events"][0]["message"] == "first"

    def test_echo_prints_to_stderr_and_records(self, capsys):
        recorder.record("loud", message="[test] hello operator", echo=True)
        assert "[test] hello operator" in capsys.readouterr().err
        assert recorder.events()[-1]["message"] == "[test] hello operator"

    def test_crash_dumps_flight_json(self, tmp_path):
        # the recorder module is stdlib-only by design: load it standalone so
        # the subprocess doesn't pay the full jax import just to crash
        code = (
            "import importlib.util, os\n"
            "spec = importlib.util.spec_from_file_location('rec', os.path.join("
            f"{ROOT!r}, 'paddle_tpu', 'observability', 'recorder.py'))\n"
            "recorder = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(recorder)\n"
            "recorder.install_crash_hook()\n"
            "recorder.record('pre', message='about to die')\n"
            "raise RuntimeError('boom')\n")
        r = subprocess.run(
            [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
            text=True, timeout=120,
            env={**os.environ, "PADDLE_TRACE_DIR": str(tmp_path)})
        assert r.returncode != 0 and "boom" in r.stderr
        doc = json.load(open(tmp_path / "FLIGHT.json"))
        assert doc["reason"].startswith("crash: RuntimeError")
        kinds = [e["kind"] for e in doc["events"]]
        assert kinds[-1] == "crash" and "pre" in kinds
        assert "boom" in doc["events"][-1]["message"]

    def test_sigterm_preemption_dumps_flight_json(self, tmp_path, monkeypatch):
        """The resilience preempt latch dumps the ring the moment the signal
        lands — the grace window may be too short for anything later."""
        from paddle_tpu.distributed.resilience.preempt import PreemptionHandler
        monkeypatch.setenv("PADDLE_TRACE_DIR", str(tmp_path))
        recorder.record("train.progress", step=41)
        h = PreemptionHandler(signals=(signal.SIGTERM,)).install()
        try:
            os.kill(os.getpid(), signal.SIGTERM)
            deadline = time.monotonic() + 5
            while not h.requested and time.monotonic() < deadline:
                time.sleep(0.01)  # resilience: ok (bounded 5s poll for signal delivery)
            assert h.requested
        finally:
            h.uninstall()
        doc = json.load(open(tmp_path / "FLIGHT.json"))
        assert "preemption" in doc["reason"]
        kinds = [e["kind"] for e in doc["events"]]
        assert "preempt.latch" in kinds and "train.progress" in kinds


# ------------------------------------------------- instrumented hot paths

class Toy:
    """Deterministic momentum-descent trainable (resilience protocol)."""

    def __init__(self, dim=4, seed=0):
        rng = np.random.RandomState(seed)
        self.w = rng.rand(dim).astype(np.float32)
        self.m = np.zeros(dim, np.float32)
        self.step_i = 0

    def resilience_state(self):
        return {"w": self.w.copy(), "m": self.m.copy(),
                "step": np.asarray(self.step_i, np.int64)}

    def load_resilience_state(self, state):
        self.w = np.asarray(state["w"], np.float32).copy()
        self.m = np.asarray(state["m"], np.float32).copy()
        self.step_i = int(np.asarray(state["step"]))

    def train_step(self, target):
        g = self.w - np.asarray(target, np.float32)
        self.m = 0.9 * self.m + g
        self.w = self.w - 0.1 * self.m
        self.step_i += 1
        return float(((self.w - target) ** 2).sum())


def _toy_batch(step):
    return np.full(4, np.float32(step % 3), np.float32)


def _fast_loop(trainable, ckpt_dir, **kw):
    kw.setdefault("policy", RetryPolicy(max_attempts=0, base_delay=0.0,
                                        max_delay=0.0, jitter=0.0))
    kw.setdefault("handle_signals", False)
    return ResilientLoop(trainable, str(ckpt_dir), **kw)


class TestCheckpointSinglePassCrc:
    def _save(self, tmp_path, seed=0):
        from paddle_tpu.distributed.checkpoint import save_state_dict
        rng = np.random.RandomState(seed)
        sd = {"w": rng.rand(8, 4).astype(np.float32),
              "b": rng.rand(4).astype(np.float32)}
        uid = save_state_dict(sd, str(tmp_path))
        return sd, uid

    def test_each_shard_file_read_exactly_once(self, tmp_path, monkeypatch):
        """The ROADMAP 2x-IO item: crc verify + data load now share ONE
        read of each storage file."""
        import importlib
        L = importlib.import_module(
            "paddle_tpu.distributed.checkpoint.load_state_dict")
        sd, _ = self._save(tmp_path)
        reads = []
        orig = L._read_and_crc
        monkeypatch.setattr(L, "_read_and_crc",
                            lambda fp: (reads.append(fp), orig(fp))[1])
        holders = {k: np.zeros_like(v) for k, v in sd.items()}
        L.load_state_dict(holders, str(tmp_path))
        np.testing.assert_array_equal(holders["w"], sd["w"])
        npz_files = [f for f in os.listdir(tmp_path) if f.endswith(".npz")]
        assert len(reads) == len(npz_files) == 1
        assert len(set(reads)) == len(reads)  # no file read twice

    def test_load_metrics_recorded(self, tmp_path):
        sd, _ = self._save(tmp_path)
        from paddle_tpu.distributed.checkpoint import load_state_dict
        before = metrics.counter("checkpoint.load_bytes").value
        load_state_dict({k: np.zeros_like(v) for k, v in sd.items()},
                        str(tmp_path))
        assert metrics.counter("checkpoint.load_bytes").value > before
        assert metrics.histogram("checkpoint.load_time_s").count >= 1
        assert metrics.histogram("checkpoint.crc_time_s").count >= 1

    def test_crc_mismatch_still_falls_back_and_records(self, tmp_path):
        from paddle_tpu.distributed.checkpoint import load_state_dict
        sd0, _ = self._save(tmp_path, seed=0)
        sd1, uid1 = self._save(tmp_path, seed=1)
        # corrupt the newest generation's shard in place
        shard = os.path.join(tmp_path, f"{uid1}_rank0.npz")
        with open(shard, "r+b") as f:
            f.seek(30)
            f.write(b"\xde\xad\xbe\xef")
        holders = {k: np.zeros_like(v) for k, v in sd0.items()}
        load_state_dict(holders, str(tmp_path))
        np.testing.assert_array_equal(holders["w"], sd0["w"])  # fell back
        kinds = [e["kind"] for e in recorder.events()]
        assert "ckpt.rejected" in kinds

    def test_save_metrics_recorded(self, tmp_path):
        self._save(tmp_path)
        assert metrics.counter("checkpoint.save_bytes").value > 0
        assert metrics.histogram("checkpoint.save_time_s").count >= 1
        kinds = [e["kind"] for e in recorder.events()]
        assert "ckpt.save" in kinds and "ckpt.published" in kinds


class TestWatchdogTelemetry:
    def test_stall_counter_and_event_keep_message_text(self, tmp_path, capsys):
        from paddle_tpu.distributed.comm_watchdog import watch
        before = metrics.counter("watchdog.stall").value
        with watch("slow-op", timeout=0.05, action="report"):
            time.sleep(0.3)  # resilience: ok (fixed test sleep, not a retry)
        assert metrics.counter("watchdog.stall").value == before + 1
        stalls = [e for e in recorder.events() if e["kind"] == "watchdog.stall"]
        assert len(stalls) == 1
        # the old print text survives in the event payload AND on stderr
        assert "[comm-watchdog] TIMEOUT" in stalls[0]["message"]
        assert "op=slow-op" in stalls[0]["message"]
        assert stalls[0]["op"] == "slow-op" and stalls[0]["action"] == "report"
        assert "[comm-watchdog] TIMEOUT" in capsys.readouterr().err


class TestDataPipelineTelemetry:
    def test_worker_pool_epoch_counts_batches(self):
        from paddle_tpu.io.worker_pool import WorkerPool
        pool = WorkerPool(list(range(16)), num_workers=1)
        try:
            before = metrics.counter("io.batches").value
            out = list(pool.run_epoch([[0, 1], [2, 3], [4, 5]], timeout=60))
            assert len(out) == 3
            assert metrics.counter("io.batches").value == before + 3
            assert any(e["kind"] == "io.epoch" for e in recorder.events())
        finally:
            pool.shutdown()


# --------------------------------------------- the acceptance contract

class TestChaosRunPostmortem:
    """ISSUE 2 acceptance: one PADDLE_CHAOS-injected run under ResilientLoop
    leaves every postmortem artifact behind, no re-run needed."""

    N = 8

    def _chaos_run(self, tmp_path):
        import paddle_tpu.distributed as dist
        spans.enable_tracing(str(tmp_path))
        ckpt = tmp_path / "ckpt"
        with chaos.inject("ckpt.rename:3"):
            loop = _fast_loop(Toy(), ckpt, save_every=2)
            res = loop.run(_toy_batch, self.N,
                           on_step=lambda s, l: dist.barrier())
        return res, ckpt

    def test_trace_metrics_and_flight_all_land(self, tmp_path):
        res, ckpt = self._chaos_run(tmp_path)
        assert res.steps == self.N and res.restores >= 1

        # (1) chrome trace: valid JSON, >= 3 span categories
        trace = spans.export_chrome_trace()
        doc = json.load(open(trace))
        cats = {e.get("cat") for e in doc["traceEvents"] if e.get("ph") == "X"}
        assert {"step", "checkpoint", "collective"} <= cats

        # (2) metrics snapshot names the injected faults and the recovery
        snap = metrics.snapshot()
        assert snap["counters"]["chaos.faults"] >= 1
        assert snap["counters"]["resilience.restores"] == res.restores
        assert snap["histograms"]["collective.wait_s"]["count"] >= self.N

        # (3) FLIGHT.json in the ckpt dir explains the fault
        doc = json.load(open(ckpt / "FLIGHT.json"))
        assert "restore" in doc["reason"]
        kinds = [e["kind"] for e in doc["events"]]
        assert "chaos.fault" in kinds
        fault = next(e for e in doc["events"] if e["kind"] == "chaos.fault")
        assert fault["site"] == "ckpt.rename"
        # the fault is followed by the recovery story, in order
        assert kinds.index("chaos.fault") \
            < kinds.index("resilience.recover") \
            < kinds.index("resilience.restored")

    def test_counters_survive_restore_monotonic(self, tmp_path):
        """A checkpoint restore rolls model state back; telemetry counters
        must keep counting forward (the restore is part of the story)."""
        import paddle_tpu.distributed as dist

        seen = []

        def on_step(step, loss):
            dist.barrier()
            seen.append((step, metrics.counter("resilience.restores").value,
                         metrics.counter("collective.barriers").value))

        with chaos.inject("ckpt.rename:3"):
            loop = _fast_loop(Toy(), tmp_path / "ck", save_every=2)
            res = loop.run(_toy_batch, self.N, on_step=on_step)
        assert res.restores >= 1
        restores = [r for _, r, _ in seen]
        barriers = [b for _, _, b in seen]
        assert restores == sorted(restores), "restore counter went backwards"
        assert barriers == sorted(barriers), "barrier counter went backwards"
        assert max(restores) == res.restores
        # replayed steps appear twice in `seen` but the barrier counter keeps
        # climbing: telemetry was NOT rolled back with the model state
        assert len(barriers) > self.N
        assert barriers[-1] == len(barriers)


# ------------------------------------------------------------ bench.py

class TestBenchMetricsEmbed:
    def test_error_payload_carries_metrics_snapshot(self):
        """Even the bench's error JSON line carries the perf-trajectory
        metrics dict (BENCH_*.json gains the dimension on every path)."""
        r = subprocess.run(
            [sys.executable, os.path.join(ROOT, "bench.py")], cwd=ROOT,
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "BENCH_TPU_WAIT_S": "0",
                 "BENCH_REQUIRE_TPU": "1",  # force the strict error path
                 "BENCH_RETRY_LOG": "/dev/null"})  # keep evidence log clean
        assert r.returncode != 0
        lines = [l for l in r.stdout.splitlines() if l.startswith("{")]
        assert len(lines) == 1
        payload = json.loads(lines[0])
        assert "metrics" in payload
        assert "counters" in (payload["metrics"] or {})

    def test_metrics_payload_shape(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "bench_under_test", os.path.join(ROOT, "bench.py"))
        bench = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bench)
        metrics.histogram("train.step_time_s").observe(0.5)
        metrics.counter("chaos.faults").inc()
        payload = bench._metrics_payload()
        assert payload["counters"]["chaos.faults"] == 1
        assert payload["step_time_s"]["count"] == 1


# ---------------------------------------------------------- lint (tier-1)

class TestObservabilityLint:
    LINT = os.path.join(ROOT, "tools", "lint_observability.py")

    def test_tree_is_clean(self):
        r = subprocess.run([sys.executable, self.LINT, ROOT],
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stdout + r.stderr

    def test_flags_bare_print_and_raw_timing(self, tmp_path):
        pkg = tmp_path / "paddle_tpu"
        pkg.mkdir()
        (pkg / "bad.py").write_text(
            "import time\n"
            "def f():\n"
            "    t0 = time.time()\n"
            "    work()\n"
            "    print('step took', time.time() - t0)\n")
        r = subprocess.run([sys.executable, self.LINT, str(tmp_path)],
                           capture_output=True, text=True)
        assert r.returncode == 1
        assert "[O1]" in r.stdout and "[O2]" in r.stdout

    def test_marker_and_allowlist_are_exempt(self, tmp_path):
        pkg = tmp_path / "paddle_tpu"
        (pkg / "hapi").mkdir(parents=True)
        (pkg / "marked.py").write_text(
            "import time\n"
            "def f(rec, ttl):\n"
            "    return time.time() - rec > ttl  # observability: ok (liveness TTL)\n")
        (pkg / "hapi" / "callbacks.py").write_text(
            "def f():\n"
            "    print('progress bar')\n")
        r = subprocess.run([sys.executable, self.LINT, str(tmp_path)],
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stdout

    def test_observability_layer_itself_is_exempt(self, tmp_path):
        pkg = tmp_path / "paddle_tpu" / "observability"
        pkg.mkdir(parents=True)
        (pkg / "recorder.py").write_text("print('the echo path')\n")
        r = subprocess.run([sys.executable, self.LINT, str(tmp_path)],
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stdout
