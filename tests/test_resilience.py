"""Resilience layer tests (ISSUE 1): unified retry/backoff, deterministic
chaos injection, preemption-safe checkpointing, and ResilientLoop's
bitwise-exact recovery contract (resume_max_rel == 0.0, proven on CPU via
chaos).

Also wires the static resilience lint (tools/lint_resilience.py) and the
bench never-JSON-less contract into tier-1.
"""
import json
import os
import signal
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.distributed as dist
from paddle_tpu.distributed.resilience import (
    ChaosError, DeadlineExceeded, FatalError, ResilientLoop, RetryPolicy,
    TransientError, chaos, classify, preempt, retry_call, wait_for,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_chaos():
    chaos.reset()
    yield
    chaos.reset()


# ---------------------------------------------------------------- retry.py

class TestRetry:
    def test_transient_retry_then_succeed(self):
        sleeps, calls = [], []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise TransientError("blip")
            return "ok"

        out = retry_call(flaky, policy=RetryPolicy(max_attempts=5, seed=0),
                         op="flaky", sleep=sleeps.append)
        assert out == "ok"
        assert len(calls) == 3
        assert len(sleeps) == 2  # one backoff per failure

    def test_fatal_not_retried(self):
        calls = []

        def bad():
            calls.append(1)
            raise ValueError("bug, not weather")

        with pytest.raises(ValueError):
            retry_call(bad, sleep=lambda d: None)
        assert len(calls) == 1

    def test_deadline_exceeded_names_op_and_attempts(self):
        def always():
            raise ConnectionError("down")

        with pytest.raises(DeadlineExceeded) as ei:
            retry_call(always, policy=RetryPolicy(max_attempts=3, seed=0),
                       op="kv.put", sleep=lambda d: None)
        assert ei.value.op == "kv.put"
        assert ei.value.attempts == 3
        assert "kv.put" in str(ei.value) and "ConnectionError" in str(ei.value)
        assert isinstance(ei.value, TimeoutError)  # callers catching TimeoutError still work

    def test_classify(self):
        assert classify(TransientError("x"))
        assert classify(ConnectionResetError("x"))
        assert classify(OSError("x"))
        # permanent misconfiguration dressed as IO is NOT transient
        assert not classify(FileNotFoundError("x"))
        assert not classify(PermissionError("x"))
        assert not classify(FatalError("x"))
        assert not classify(ValueError("x"))
        assert not classify(DeadlineExceeded("op", 1, 0.0))

    def test_backoff_is_exponential_capped_and_seeded(self):
        pol = RetryPolicy(base_delay=0.1, max_delay=0.4, jitter=0.0)
        g = pol.delays()
        assert [next(g) for _ in range(4)] == [0.1, 0.2, 0.4, 0.4]
        a = RetryPolicy(seed=7).delays()
        b = RetryPolicy(seed=7).delays()
        assert [next(a) for _ in range(5)] == [next(b) for _ in range(5)]

    def test_delays_generator_survives_thousands_of_draws(self):
        """A long-lived unlimited-attempt consumer (poller, the ISSUE-12
        per-peer backoff) draws from ONE delays() generator for the life
        of the process: the exponent must saturate at the cap instead of
        walking 2.0**k into float OverflowError (~k=1024), which would
        kill the generator and every later retry with StopIteration."""
        g = RetryPolicy(base_delay=0.1, max_delay=1.0, jitter=0.0).delays()
        seen = [next(g) for _ in range(2000)]
        assert seen[-1] == 1.0 and max(seen) == 1.0
        # base_delay=0 never reaches the cap, so the exponent itself must
        # be bounded or 2.0**k still overflows at k=1024
        g0 = RetryPolicy(base_delay=0.0, max_delay=1.0, jitter=0.0).delays()
        assert [next(g0) for _ in range(1500)][-1] == 0.0

    def test_chaos_error_passes_through_unretried(self):
        calls = []

        def injected():
            calls.append(1)
            raise ChaosError("site", 1)

        with pytest.raises(ChaosError):
            retry_call(injected, policy=RetryPolicy(max_attempts=5),
                       sleep=lambda d: None)
        assert len(calls) == 1  # reaches the outer recovery boundary intact

    def test_wait_for_returns_value_and_times_out_named(self):
        state = {"n": 0}

        def pred():
            state["n"] += 1
            return "ready" if state["n"] >= 3 else None

        assert wait_for(pred, "warmup", timeout=10, sleep=lambda d: None) == "ready"
        with pytest.raises(DeadlineExceeded) as ei:
            wait_for(lambda: False, "peer-files", timeout=0.05,
                     describe=lambda: "missing rank3.npz")
        assert "peer-files" in str(ei.value)
        assert "missing rank3.npz" in str(ei.value)


# ---------------------------------------------------------------- chaos.py

class TestChaos:
    def test_exact_hit_selector(self):
        with chaos.inject("s:2"):
            assert chaos.hit("s") == 1
            with pytest.raises(ChaosError) as ei:
                chaos.hit("s")
            assert ei.value.site == "s" and ei.value.hit_index == 2
            assert chaos.hit("s") == 3  # exactly one failure

    def test_from_selector_and_other_sites_untouched(self):
        with chaos.inject("s:2+"):
            chaos.hit("s")
            for _ in range(3):
                with pytest.raises(ChaosError):
                    chaos.hit("s")
            assert chaos.hit("other") == 1  # unconfigured site never fails

    def test_prob_selector_is_deterministic_per_seed(self):
        def failing_set():
            fails = set()
            for i in range(1, 21):
                try:
                    chaos.hit("p")
                except ChaosError as e:
                    fails.add(e.hit_index)
            return fails

        with chaos.inject("p:p0.5", seed=7):
            first = failing_set()
        with chaos.inject("p:p0.5", seed=7):
            assert failing_set() == first
        assert 0 < len(first) < 20  # actually probabilistic

    def test_inject_scopes_env_and_counters(self):
        assert not chaos.active()
        with chaos.inject("s:1"):
            assert chaos.active()
            assert os.environ["PADDLE_CHAOS"] == "s:1"
        assert not chaos.active()
        assert chaos.hit_counts() == {}

    def test_data_next_site_fires_in_batch_reader(self):
        from paddle_tpu.batch import batch

        def reader():
            yield from range(8)

        with chaos.inject("data.next:2"):
            it = batch(reader, 2)()
            assert next(it) == [0, 1]
            with pytest.raises(ChaosError):
                next(it)


# ------------------------------------------------- checkpoint hardening

def _save_gen(tmp_path, value, **kw):
    sd = {"w": pt.to_tensor(np.full((4, 4), value, np.float32))}
    return dist.checkpoint.save_state_dict(sd, str(tmp_path), **kw)


def _load_w(tmp_path, unique_id=None):
    out = {"w": pt.zeros([4, 4])}
    dist.checkpoint.load_state_dict(out, str(tmp_path), unique_id=unique_id)
    return np.asarray(out["w"].numpy())


class TestCheckpointHardening:
    def test_corrupt_shard_falls_back_to_previous_generation(self, tmp_path, capsys):
        _save_gen(tmp_path, 1.0)
        u2 = _save_gen(tmp_path, 2.0)
        shard = tmp_path / f"{u2}_rank0.npz"
        shard.write_bytes(b"garbage" + shard.read_bytes()[7:])
        np.testing.assert_array_equal(_load_w(tmp_path), 1.0)
        err = capsys.readouterr().err
        assert "rejected" in err and "crc32" in err

    def test_missing_shard_falls_back(self, tmp_path):
        _save_gen(tmp_path, 1.0)
        u2 = _save_gen(tmp_path, 2.0)
        os.remove(tmp_path / f"{u2}_rank0.npz")
        np.testing.assert_array_equal(_load_w(tmp_path), 1.0)

    def test_pinned_unique_id_never_falls_back(self, tmp_path):
        _save_gen(tmp_path, 1.0)
        u2 = _save_gen(tmp_path, 2.0)
        os.remove(tmp_path / f"{u2}_rank0.npz")
        with pytest.raises(FileNotFoundError):
            _load_w(tmp_path, unique_id=u2)

    def test_chaos_rename_leaves_no_published_torn_generation(self, tmp_path):
        """Kill between write and rename: the tmp file exists but no
        metadata was published, so load cleanly uses the previous gen."""
        _save_gen(tmp_path, 1.0)
        with chaos.inject("ckpt.rename:1"):
            with pytest.raises(ChaosError):
                _save_gen(tmp_path, 2.0)
        assert any(fn.endswith(".tmp.npz") for fn in os.listdir(tmp_path))
        np.testing.assert_array_equal(_load_w(tmp_path), 1.0)

    def test_transient_write_error_is_retried(self, tmp_path, monkeypatch):
        import importlib
        ssd = importlib.import_module(
            "paddle_tpu.distributed.checkpoint.save_state_dict")
        real_savez, calls = np.savez, []

        def flaky_savez(*a, **kw):
            calls.append(1)
            if len(calls) == 1:
                raise OSError("disk blip")
            return real_savez(*a, **kw)

        monkeypatch.setattr(ssd.np, "savez", flaky_savez)
        _save_gen(tmp_path, 3.0)
        assert len(calls) == 2
        np.testing.assert_array_equal(_load_w(tmp_path), 3.0)

    def test_keep_last_k_gc(self, tmp_path):
        uids = [_save_gen(tmp_path, float(i), keep_last_k=2) for i in range(5)]
        metas = sorted(fn for fn in os.listdir(tmp_path)
                       if fn.endswith("_metadata.json"))
        assert metas == sorted(f"{u}_metadata.json" for u in uids[-2:])
        assert not (tmp_path / f"{uids[0]}_rank0.npz").exists()
        np.testing.assert_array_equal(_load_w(tmp_path), 4.0)

    def test_wait_for_files_raises_named_deadline(self, tmp_path):
        from paddle_tpu.distributed.checkpoint.save_state_dict import \
            _wait_for_files
        with pytest.raises(DeadlineExceeded) as ei:
            _wait_for_files([str(tmp_path / "never_rank7.npz")],
                            "coordinator merge", timeout_s=0.1)
        msg = str(ei.value)
        assert "coordinator merge" in msg and "never_rank7.npz" in msg


# -------------------------------------------------------------- preempt.py

class TestPreempt:
    def test_marker_roundtrip(self, tmp_path):
        assert preempt.read_marker(str(tmp_path)) is None
        preempt.write_marker(str(tmp_path), step=17, unique_id=3,
                             signum=signal.SIGTERM)
        m = preempt.read_marker(str(tmp_path))
        assert m["step"] == 17 and m["unique_id"] == 3
        preempt.clear_marker(str(tmp_path))
        assert preempt.read_marker(str(tmp_path)) is None

    def test_handler_latches_and_restores_previous(self):
        prev = signal.getsignal(signal.SIGTERM)
        h = preempt.PreemptionHandler(signals=(signal.SIGTERM,))
        with h:
            assert not h.requested
            os.kill(os.getpid(), signal.SIGTERM)
            assert h.requested and h.signum == signal.SIGTERM
        assert signal.getsignal(signal.SIGTERM) is prev

    def test_programmatic_request(self):
        h = preempt.PreemptionHandler()
        h.request()
        assert h.requested
        h.clear()
        assert not h.requested


# ---------------------------------------------------------- ResilientLoop

class Toy:
    """Deterministic momentum-descent trainable implementing the protocol."""

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


class TestResilientLoop:
    N = 8

    def _baseline(self, tmp_path):
        loop = _fast_loop(Toy(), tmp_path / "base")
        return loop.run(_toy_batch, self.N), loop.trainable

    @pytest.mark.parametrize("spec", ["ckpt.rename:1", "ckpt.rename:3",
                                      "ckpt.write:2", "data-free"])
    def test_chaos_run_matches_fault_free_exactly(self, tmp_path, spec):
        """The acceptance contract: PADDLE_CHAOS='ckpt.rename:1' (and
        harder variants) under ResilientLoop completes N steps with the
        final loss EXACTLY equal to a no-fault run — resume_max_rel == 0.0."""
        base, base_toy = self._baseline(tmp_path)
        assert base.steps == self.N and base.restores == 0

        if spec == "data-free":  # control: chaos env set, nothing targeted
            spec = "unused.site:1"
        with chaos.inject(spec):
            loop = _fast_loop(Toy(), tmp_path / "chaos", save_every=2)
            res = loop.run(_toy_batch, self.N)
        assert res.steps == self.N and not res.preempted
        if spec != "unused.site:1":
            assert res.restores >= 1
        assert res.last_loss == base.last_loss  # resume_max_rel == 0.0
        np.testing.assert_array_equal(loop.trainable.w, base_toy.w)
        np.testing.assert_array_equal(loop.trainable.m, base_toy.m)

    def test_midrun_fault_restores_from_checkpoint(self, tmp_path, capsys):
        """ckpt.rename:3 with save_every=2: anchor save is hit 1, the
        step-2 save is hit 2, the step-4 save FAILS (hit 3) — the loop must
        restore the step-2 generation and replay to an identical end."""
        base, base_toy = self._baseline(tmp_path)
        with chaos.inject("ckpt.rename:3"):
            loop = _fast_loop(Toy(), tmp_path / "mid", save_every=2)
            res = loop.run(_toy_batch, self.N)
        assert res.restores == 1
        assert "restored checkpoint at step" in capsys.readouterr().err
        np.testing.assert_array_equal(loop.trainable.w, base_toy.w)

    def test_fatal_error_is_not_absorbed(self, tmp_path):
        loop = _fast_loop(Toy(), tmp_path)

        def bad_batch(step):
            raise ValueError("label out of range")

        with pytest.raises(ValueError):
            loop.run(bad_batch, 2)

    def test_max_restores_bounds_recovery(self, tmp_path):
        with chaos.inject("ckpt.write:1+"):  # every save fails, forever
            loop = _fast_loop(Toy(), tmp_path, max_restores=3)
            with pytest.raises(DeadlineExceeded):
                loop.run(_toy_batch, 4)

    def test_preemption_saves_marker_and_resumes_step_exact(self, tmp_path):
        base, base_toy = self._baseline(tmp_path)

        loop = _fast_loop(Toy(), tmp_path / "pre")
        loop.preemption.install = lambda: loop.preemption  # keep latch-only
        stop_at = 3

        def on_step(step, loss):
            if step == stop_at:
                loop.preemption.request(signal.SIGTERM)

        res = loop.run(_toy_batch, self.N, on_step=on_step)
        assert res.preempted and res.steps == stop_at
        marker = preempt.read_marker(str(tmp_path / "pre"))
        assert marker["step"] == stop_at
        assert marker["signum"] == signal.SIGTERM

        # relaunch: a FRESH trainable with different init must resume from
        # the emergency save and end bitwise-identical to the base run
        loop2 = _fast_loop(Toy(seed=99), tmp_path / "pre")
        res2 = loop2.run(_toy_batch, self.N)
        assert res2.resumed_from == stop_at
        assert res2.steps == self.N and not res2.preempted
        assert preempt.read_marker(str(tmp_path / "pre")) is None
        assert res2.last_loss == base.last_loss
        np.testing.assert_array_equal(loop2.trainable.w, base_toy.w)

    def test_sigterm_triggers_emergency_save(self, tmp_path):
        """Real-signal path: SIGTERM mid-run ends with marker + checkpoint."""
        loop = ResilientLoop(Toy(), str(tmp_path), handle_signals=True,
                             policy=RetryPolicy(base_delay=0.0, jitter=0.0))

        def on_step(step, loss):
            if step == 2:
                os.kill(os.getpid(), signal.SIGTERM)

        res = loop.run(_toy_batch, self.N, on_step=on_step)
        assert res.preempted and res.steps == 2
        assert preempt.read_marker(str(tmp_path))["step"] == 2

    def test_completed_run_resumes_as_noop(self, tmp_path):
        loop = _fast_loop(Toy(), tmp_path)
        res = loop.run(_toy_batch, self.N)
        assert res.steps == self.N
        loop2 = _fast_loop(Toy(seed=5), tmp_path)
        res2 = loop2.run(_toy_batch, self.N)
        assert res2.resumed_from == self.N
        np.testing.assert_array_equal(loop2.trainable.w, loop.trainable.w)

    def test_protocol_violation_raises_early(self, tmp_path):
        with pytest.raises(TypeError):
            ResilientLoop(object(), str(tmp_path))


class TestResilientLoopLlama:
    """End-to-end on the real train step: chaos-faulted run under
    ResilientLoop reproduces the fault-free loss bitwise."""

    B, T, V, N = 2, 16, 64, 4

    def _step(self):
        import jax.numpy as jnp
        from paddle_tpu.models import LlamaConfig, LlamaTrainStep
        from paddle_tpu.optimizer import AdamW
        cfg = LlamaConfig(
            vocab_size=self.V, hidden_size=32, intermediate_size=64,
            num_hidden_layers=1, num_attention_heads=2,
            num_key_value_heads=2, max_position_embeddings=self.T,
            dtype=jnp.float32)
        return LlamaTrainStep(cfg, mesh=None, remat=False,
                              optimizer=AdamW(learning_rate=1e-3))

    def _batch(self, step):
        rng = np.random.RandomState(1000 + step)
        toks = rng.randint(0, self.V, (self.B, self.T)).astype(np.int32)
        return toks, toks.copy()

    def test_llama_chaos_rename_bitwise_exact(self, tmp_path):
        base = _fast_loop(self._step(), tmp_path / "base", save_every=2)
        rb = base.run(self._batch, self.N)
        with chaos.inject("ckpt.rename:1"):
            loop = _fast_loop(self._step(), tmp_path / "chaos", save_every=2)
            rc = loop.run(self._batch, self.N)
        assert rc.steps == self.N and rc.restores >= 1
        assert rc.last_loss == rb.last_loss  # resume_max_rel == 0.0


# -------------------------------------------------- elastic KV retry routing

class _FakeResp:
    def __init__(self, data=b"{}"):
        self._d = data

    def read(self):
        return self._d

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _registry():
    from paddle_tpu.distributed.fleet.elastic import KVRegistry
    return KVRegistry("127.0.0.1:1", ttl=10, timeout=0.1,
                      retry_policy=RetryPolicy(max_attempts=3, base_delay=0.0,
                                               max_delay=0.0, jitter=0.0))


class TestElasticRetry:
    def test_heartbeat_survives_one_dropped_put(self, monkeypatch):
        calls = []

        def flaky(req, timeout=None):
            calls.append(req)
            if len(calls) == 1:
                raise ConnectionResetError("dropped")
            return _FakeResp()

        monkeypatch.setattr(urllib.request, "urlopen", flaky)
        _registry().heartbeat("node0")  # must NOT look like a dead node
        assert len(calls) == 2

    def test_heartbeat_outage_dies_named(self, monkeypatch):
        monkeypatch.setattr(
            urllib.request, "urlopen",
            lambda *a, **k: (_ for _ in ()).throw(ConnectionError("down")))
        with pytest.raises(DeadlineExceeded) as ei:
            _registry().heartbeat("node0")
        assert "kv.heartbeat node0" in str(ei.value)

    def test_alive_nodes_retries_then_returns(self, monkeypatch):
        calls = []

        def flaky(req, timeout=None):
            calls.append(req)
            if len(calls) == 1:
                raise ConnectionResetError("dropped")
            return _FakeResp(b'["a", "b"]')

        monkeypatch.setattr(urllib.request, "urlopen", flaky)
        assert _registry().alive_nodes() == ["a", "b"]
        assert len(calls) == 2

    def test_alive_nodes_exhausted_reports_empty(self, monkeypatch):
        monkeypatch.setattr(
            urllib.request, "urlopen",
            lambda *a, **k: (_ for _ in ()).throw(ConnectionError("down")))
        assert _registry().alive_nodes() == []

    def test_chaos_heartbeat_site_reaches_caller(self, monkeypatch):
        calls = []
        monkeypatch.setattr(urllib.request, "urlopen",
                            lambda *a, **k: calls.append(1) or _FakeResp())
        with chaos.inject("kv.heartbeat:1"):
            with pytest.raises(ChaosError):
                _registry().heartbeat("node0")
        assert calls == []  # injected fault is never absorbed by retry


# ------------------------------------------------------------ comm watchdog

class TestCommWatchdog:
    def test_watch_exit_124_names_op_and_group(self, tmp_path):
        code = (
            "import time\n"
            "from paddle_tpu.distributed.comm_watchdog import watch\n"
            "class G:\n"
            "    ranks = [0, 1]\n"
            "    id = 7\n"
            "with watch('allreduce-under-test', group=G(), timeout=0.3):\n"
            "    time.sleep(60)\n")
        r = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                           capture_output=True, text=True, timeout=120,
                           env={**os.environ, "JAX_PLATFORMS": "cpu",
                                "PADDLE_TRAINER_ID": "3",
                                # the abort path dumps FLIGHT.json (PR 2):
                                # keep the postmortem out of the repo root
                                "PADDLE_TRACE_DIR": str(tmp_path)})
        assert r.returncode == 124, (r.returncode, r.stderr[-500:])
        assert "op=allreduce-under-test" in r.stderr
        assert "gid=7" in r.stderr and "ranks=[0, 1]" in r.stderr
        assert "rank=3" in r.stderr
        assert (tmp_path / "FLIGHT.json").exists()  # abort left the story

    def test_watch_no_timeout_is_transparent(self):
        from paddle_tpu.distributed.comm_watchdog import watch
        with watch("fast-op", timeout=30):
            pass  # returns before the timer fires; nothing aborts


# ------------------------------------------------------- bench.py contract

class TestBenchNeverJsonless:
    """A caller's timeout once killed the bench mid-retry (rc=124, nothing
    to parse). The bench must emit exactly one machine-readable JSON line
    on EVERY exit path."""

    @staticmethod
    def _json_lines(out: str):
        lines = []
        for ln in out.splitlines():
            try:
                obj = json.loads(ln)
            except ValueError:
                continue
            if isinstance(obj, dict):
                lines.append(obj)
        return lines

    def _run_bench(self, env, kill_after=None):
        env = {"BENCH_RETRY_LOG": "/dev/null", **env}  # keep evidence log clean
        p = subprocess.Popen([sys.executable, os.path.join(ROOT, "bench.py")],
                             cwd=ROOT, stdout=subprocess.PIPE,
                             stderr=subprocess.PIPE, text=True,
                             env={**os.environ, **env})
        try:
            out, err = p.communicate(timeout=kill_after or 120)
        except subprocess.TimeoutExpired:
            p.terminate()  # the driver's kill
            try:
                out, err = p.communicate(timeout=60)
            except subprocess.TimeoutExpired:
                p.kill()
                out, err = p.communicate()
        return p.returncode, out, err

    def test_unreachable_tpu_falls_back_to_cpu_json(self):
        """PR 3 contract: probe exhaustion falls back to the CPU smoke so
        a real (rc=0) JSON line always lands, tagged device=cpu."""
        rc, out, err = self._run_bench(
            {"JAX_PLATFORMS": "cpu", "BENCH_TPU_WAIT_S": "0",
             "PADDLE_TRAINERS_NUM": "1"})
        assert rc == 0
        parsed = self._json_lines(out)
        assert len(parsed) == 1, out
        assert parsed[0]["device"] == "cpu"
        assert "error" not in parsed[0]
        assert parsed[0]["vs_baseline"] == 0.0   # CPU numbers never score
        assert "fleet" not in parsed[0]          # single-rank: no sub-object
        assert "slo" not in parsed[0]            # no serving: no slo object

    def test_require_tpu_restores_strict_error_exit(self):
        """BENCH_REQUIRE_TPU=1 keeps the old behavior: error JSON line +
        nonzero rc, no silent CPU benching."""
        rc, out, err = self._run_bench(
            {"JAX_PLATFORMS": "cpu", "BENCH_TPU_WAIT_S": "0",
             "BENCH_REQUIRE_TPU": "1"})
        assert rc != 0
        parsed = self._json_lines(out)
        assert len(parsed) == 1, out
        assert "error" in parsed[0] and "unreachable" in parsed[0]["error"]
        assert parsed[0]["device"] == "none"

    def test_kill_timer_still_yields_one_json_line(self):
        """Run with a 5 s kill timer while the bench is deep in its TPU
        retry window: SIGTERM must produce the error JSON, not silence."""
        rc, out, err = self._run_bench(
            {"JAX_PLATFORMS": "cpu", "BENCH_TPU_WAIT_S": "600",
             "BENCH_DRIVER_BUDGET_S": "2700"},
            kill_after=5)
        assert rc != 0
        parsed = self._json_lines(out)
        assert len(parsed) == 1, out
        assert "error" in parsed[0]
        assert "SIGTERM" in parsed[0]["error"]

    def test_multirank_fleet_subobject_schema(self):
        """ISSUE 5 satellite: on multi-rank runs (PADDLE_TRAINERS_NUM > 1,
        exported by the launcher) the JSON line carries a `fleet`
        sub-object with exactly rank count + straggler/drop counters;
        single-rank runs omit it."""
        rc, out, err = self._run_bench(
            {"JAX_PLATFORMS": "cpu", "BENCH_TPU_WAIT_S": "0",
             "PADDLE_TRAINERS_NUM": "3"})
        assert rc == 0
        parsed = self._json_lines(out)
        assert len(parsed) == 1, out
        fleet = parsed[0].get("fleet")
        assert fleet is not None, parsed[0]
        assert set(fleet) == {"ranks", "straggler_events",
                              "telemetry_drops"}, fleet
        assert fleet["ranks"] == 3
        assert isinstance(fleet["straggler_events"], int)
        assert isinstance(fleet["telemetry_drops"], int)

    def test_retry_window_capped_below_driver_budget(self):
        """Even an absurd BENCH_TPU_WAIT_S is clamped to (budget - 300 s):
        with a 300 s driver budget the wait window collapses to a single
        probe and the bench proceeds to the CPU fallback (one JSON line)
        almost immediately instead of retrying into the driver's kill."""
        import time
        t0 = time.time()
        rc, out, err = self._run_bench(
            {"JAX_PLATFORMS": "cpu", "BENCH_TPU_WAIT_S": "99999",
             "BENCH_DRIVER_BUDGET_S": "300"})
        assert rc == 0
        parsed = self._json_lines(out)
        assert len(parsed) == 1, out
        assert parsed[0]["device"] == "cpu"
        assert time.time() - t0 < 90, "wait window was not capped"


# ---------------------------------------------------------- lint (tier-1)

class TestResilienceLint:
    def test_tree_is_clean(self):
        r = subprocess.run([sys.executable,
                            os.path.join(ROOT, "tools", "lint_resilience.py"),
                            ROOT], capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stdout + r.stderr

    def test_flags_bare_retry_loop(self, tmp_path):
        pkg = tmp_path / "paddle_tpu"
        pkg.mkdir()
        (pkg / "bad.py").write_text(
            "import time\n"
            "def f():\n"
            "    while True:\n"
            "        try:\n"
            "            return 1\n"
            "        except Exception:\n"
            "            time.sleep(1)\n")
        r = subprocess.run([sys.executable,
                            os.path.join(ROOT, "tools", "lint_resilience.py"),
                            str(tmp_path)], capture_output=True, text=True)
        assert r.returncode == 1
        assert "[R1]" in r.stdout and "bad.py" in r.stdout

    def test_audited_marker_is_exempt(self, tmp_path):
        pkg = tmp_path / "paddle_tpu"
        pkg.mkdir()
        (pkg / "ok.py").write_text(
            "import time\n"
            "def f():\n"
            "    while True:\n"
            "        try:\n"
            "            return 1\n"
            "        except Exception:\n"
            "            time.sleep(1)  # resilience: ok (audited: bounded by caller)\n")
        r = subprocess.run([sys.executable,
                            os.path.join(ROOT, "tools", "lint_resilience.py"),
                            str(tmp_path)], capture_output=True, text=True)
        assert r.returncode == 0, r.stdout
