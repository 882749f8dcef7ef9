"""SLO-driven autoscaler + sub-second warm start (ISSUE 16 tentpole).

The contracts under test:
  * HYSTERESIS — pressure must breach the high water for N consecutive
    windows to scale out and idle under the low water for M windows to
    scale in; per-pool min/max bounds hold (in-flight spawns count
    against the ceiling, the floor is never drained through).
  * FLAPPING BOUND — after ANY decision a pool is in cooldown:
    oscillating load produces at most one decision per cooldown window.
  * INDEPENDENT POOLS — prefill and decode scale on their own signals:
    a prefill breach scales only the prefill pool while decode holds.
  * CHAOS — a fault at ``autoscale.decide`` degrades one pool's window
    to "no action + a flight record" (counters freeze, nothing is
    killed, the controller resumes when the fault lifts); a fault at
    ``warmstart.fetch`` degrades a scale-out to a cold start (fetch
    answers None + a flight record, the caller compiles locally).
  * DRAIN, NEVER KILL — scale-in goes through the drain protocol; a
    drain stalled past its deadline is flight-recorded and re-POSTed,
    never escalated to a signal, and the replica is reaped only after
    its lease leaves and its process exits on its own.
  * ELASTIC DRILL (subprocess) — flash crowd on a 1-replica warm fleet
    → scale-out within the hysteresis windows → the new replica warm
    starts (jit cache + weights fetched from the donor, asserted via
    both replicas' /metrics) and its breach-to-first-token beats the
    cold baseline by ≥2× → every request completes token-identically
    to the fault-free reference → load drop → drain-back to the floor
    with zero lost or duplicated requests.
"""
import json
import os
import sys
import time
import urllib.request

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from paddle_tpu.distributed.resilience import chaos  # noqa: E402
from paddle_tpu.inference import (AdmissionReject,  # noqa: E402
                                  ServingFleet)
from paddle_tpu.inference.autoscale import (AutoscaleController,  # noqa: E402
                                            FleetActuator, RegistryObserver)
from paddle_tpu.models.llama import (LlamaConfig,  # noqa: E402
                                     llama_init_params)
from paddle_tpu.models.llama_decode import llama_generate  # noqa: E402
from paddle_tpu.observability import metrics  # noqa: E402
from paddle_tpu.observability import recorder as _recorder  # noqa: E402

SPEC = {
    "config": {"vocab_size": 256, "hidden_size": 64,
               "intermediate_size": 128, "num_hidden_layers": 2,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "max_position_embeddings": 128, "dtype": "float32"},
    "seed": 3,
    "batcher": {"max_batch": 3, "max_len": 96, "prompt_buckets": [8, 16, 32],
                "burst": 4, "page_size": 8},
}


@pytest.fixture(scope="module")
def small_model():
    cfg = LlamaConfig.tiny(num_hidden_layers=2, max_position_embeddings=128)
    params = llama_init_params(cfg, jax.random.PRNGKey(3))
    return cfg, params


def _reference(cfg, params, prompt, n):
    import jax.numpy as jnp
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None, :])
    out = llama_generate(params, toks, cfg, n, temperature=0.0)
    return [int(t) for t in np.asarray(out)[0]]


# ------------------------------------------------- stub observer/actuator

def _obs(pools):
    """pools: {pool: [(name, queue_depth, active, max_batch, ready)]} →
    one observation list in the RegistryObserver shape."""
    out = []
    for pool, reps in pools.items():
        for (n, q, a, m, r) in reps:
            out.append({"name": n, "role": pool,
                        "endpoint": f"http://stub/{n}", "queue_depth": q,
                        "active_slots": a, "max_batch": m,
                        "draining": False, "ready": r,
                        "lease": {"warm": True, "ready_s": 0.1}})
    return out


class _StubActuator:
    """Records every actuation; spawns are named n1, n2, ...; reap
    answers the configured rc (None = process still running)."""

    def __init__(self, reap_rc=0):
        self.calls = []
        self.reap_rc = reap_rc
        self._n = 0

    def scale_out(self, pool, warm_from=""):
        self._n += 1
        self.calls.append(("scale_out", pool, warm_from))
        return f"n{self._n}"

    def drain(self, name, endpoint):
        self.calls.append(("drain", name))
        return True

    def reap(self, name):
        self.calls.append(("reap", name))
        return self.reap_rc

    def of(self, kind):
        return [c for c in self.calls if c[0] == kind]


def _ctl(observer, actuator, pools=("unified",), **kw):
    base = dict(interval_s=9.0, breach_windows=3, idle_windows=2,
                high_water=1.0, low_water=0.1, cooldown_s=0.0,
                min_replicas=1, max_replicas=4, drain_timeout_s=60.0)
    base.update(kw)
    return AutoscaleController(observer, actuator, pools, **base)


class TestHysteresisAndBounds:
    def test_breach_must_persist_n_windows(self):
        act = _StubActuator()
        state = {"obs": _obs({"unified": [("r0", 9, 3, 3, True)]})}
        c = _ctl(lambda: state["obs"], act, breach_windows=3)
        c.tick()
        c.tick()
        assert act.calls == []          # 2 breach windows: not yet
        c.tick()
        assert act.of("scale_out") == [("scale_out", "unified",
                                        "http://stub/r0")]

    def test_one_calm_window_resets_the_breach_count(self):
        act = _StubActuator()
        state = {"obs": _obs({"unified": [("r0", 9, 3, 3, True)]})}
        c = _ctl(lambda: state["obs"], act, breach_windows=3)
        c.tick()
        c.tick()
        state["obs"] = _obs({"unified": [("r0", 1, 1, 3, True)]})
        c.tick()                        # mid-band window: counters reset
        state["obs"] = _obs({"unified": [("r0", 9, 3, 3, True)]})
        c.tick()
        c.tick()
        assert act.calls == []          # the streak started over

    def test_idle_scale_in_respects_the_floor(self):
        act = _StubActuator()
        two = _obs({"unified": [("r0", 0, 0, 3, True),
                                ("r1", 0, 0, 3, True)]})
        state = {"obs": two}
        c = _ctl(lambda: state["obs"], act, idle_windows=2, min_replicas=1)
        c.tick()
        c.tick()                        # 2 idle windows → drain one
        assert len(act.of("drain")) == 1
        state["obs"] = _obs({"unified": [("r0", 0, 0, 3, True)]})
        for _ in range(6):
            c.tick()                    # idle forever at the floor
        assert len(act.of("drain")) == 1    # never drains below min

    def test_max_bound_counts_pending_spawns(self):
        act = _StubActuator()
        state = {"obs": _obs({"unified": [("r0", 9, 3, 3, True)]})}
        c = _ctl(lambda: state["obs"], act, breach_windows=1,
                 max_replicas=2)
        c.tick()                        # spawns n1 (pending: no lease yet)
        for _ in range(5):
            c.tick()                    # 1 live + 1 pending == max → hold
        assert len(act.of("scale_out")) == 1

    def test_oscillating_load_is_bounded_by_cooldown(self):
        """The flapping bound: load alternating breach/idle every window
        produces at most ONE decision per cooldown window."""
        act = _StubActuator()
        hot = _obs({"unified": [("r0", 9, 3, 3, True),
                                ("r1", 9, 3, 3, True)]})
        cold = _obs({"unified": [("r0", 0, 0, 3, True),
                                 ("r1", 0, 0, 3, True)]})
        state = {"obs": hot}
        c = _ctl(lambda: state["obs"], act, breach_windows=1,
                 idle_windows=1, cooldown_s=3600.0)
        for i in range(50):
            state["obs"] = hot if i % 2 == 0 else cold
            c.tick()
        # 50 oscillating windows inside one cooldown: exactly 1 decision
        assert len(c.decisions()) == 1
        assert metrics.counter("autoscale.decisions").value >= 1


class TestIndependentPools:
    def test_prefill_breach_scales_only_prefill(self):
        act = _StubActuator()
        state = {"obs": _obs({"prefill": [("p0", 9, 3, 3, True)],
                              "decode": [("d0", 1, 1, 3, True)]})}
        c = _ctl(lambda: state["obs"], act, ("prefill", "decode"),
                 breach_windows=2)
        c.tick()
        c.tick()
        assert act.of("scale_out") == [("scale_out", "prefill",
                                        "http://stub/p0")]
        assert c.decisions("scale_in") == []

    def test_decode_idle_drains_only_decode(self):
        act = _StubActuator()
        state = {"obs": _obs({"prefill": [("p0", 1, 1, 3, True)],
                              "decode": [("d0", 0, 0, 3, True),
                                         ("d1", 0, 1, 3, True)]})}
        c = _ctl(lambda: state["obs"], act, ("prefill", "decode"),
                 idle_windows=2)
        c.tick()
        c.tick()
        drains = act.of("drain")
        assert drains == [("drain", "d0")]   # the emptiest decode member
        assert c.decisions("scale_out") == []


class TestSloBreachSignal:
    """ISSUE 17 satellite: the slo.breach.* counter advance is a SECOND
    scale-out trigger behind PADDLE_AUTOSCALE_SLO — a pool whose latency
    is breaching scales even while queue pressure looks healthy, and the
    ledger records WHICH signal fired."""

    def test_breaches_scale_out_while_pressure_is_mid_band(self):
        act = _StubActuator()
        mid = _obs({"unified": [("r0", 2, 1, 3, True)]})  # pressure 0.67
        c = _ctl(lambda: mid, act, breach_windows=2, slo_signal=True)
        c.tick()
        assert act.calls == []          # mid-band, no breach advance: calm
        for _ in range(2):              # hysteresis applies to slo too
            metrics.counter("slo.breach.ttft").inc()
            c.tick()
        assert len(act.of("scale_out")) == 1
        d = c.decisions("scale_out")
        assert d and d[-1]["signal"] == "slo"

    def test_off_by_default_breaches_alone_never_scale(self):
        act = _StubActuator()
        mid = _obs({"unified": [("r0", 2, 1, 3, True)]})
        c = _ctl(lambda: mid, act, breach_windows=1)
        assert c.status()["slo_signal"] is False
        for _ in range(3):
            metrics.counter("slo.breach.e2e").inc()
            c.tick()
        assert act.calls == []

    def test_pressure_plus_slo_records_both_signals(self):
        act = _StubActuator()
        hot = _obs({"unified": [("r0", 9, 3, 3, True)]})
        c = _ctl(lambda: hot, act, breach_windows=2, slo_signal=True)
        for _ in range(2):
            metrics.counter("slo.breach.queue").inc()
            c.tick()
        d = c.decisions("scale_out")
        assert d and d[-1]["signal"] == "pressure+slo"

    def test_historical_breaches_before_construction_never_fire(self):
        metrics.counter("slo.breach.tpot").inc()   # pre-existing counts
        act = _StubActuator()
        mid = _obs({"unified": [("r0", 2, 1, 3, True)]})
        c = _ctl(lambda: mid, act, breach_windows=1, slo_signal=True)
        c.tick()                        # baseline was taken at construction
        assert act.calls == []


class TestChaosNeverWedges:
    def test_decide_fault_is_a_recorded_noop_then_recovers(self):
        """chaos at autoscale.decide: no action, counters freeze, a
        flight record lands — and the controller resumes the moment the
        fault lifts (never wedged, never flapping)."""
        act = _StubActuator()
        state = {"obs": _obs({"unified": [("r0", 9, 3, 3, True)]})}
        c = _ctl(lambda: state["obs"], act, breach_windows=2)
        before = _recorder.events_since(0)[1]
        with chaos.inject("autoscale.decide:1+"):
            for _ in range(5):
                c.tick()
        assert act.calls == []
        assert c.status()["breach"]["unified"] == 0    # frozen, not built
        skips = [e for e in _recorder.events_since(before)[0]
                 if e.get("kind") == "autoscale.chaos_skip"]
        assert len(skips) == 5
        c.tick()
        c.tick()                        # fault lifted: hysteresis rebuilds
        assert len(act.of("scale_out")) == 1

    def test_warmstart_fetch_fault_degrades_to_cold(self, tmp_path):
        """chaos at warmstart.fetch: both fetchers answer None + a
        flight record; the caller falls back to local compile/init."""
        from paddle_tpu.inference.warmstart import (fetch_warm_cache,
                                                    fetch_weights)
        before = _recorder.events_since(0)[1]
        with chaos.inject("warmstart.fetch:1+"):
            assert fetch_warm_cache("127.0.0.1:9", "abc",
                                    str(tmp_path)) is None
            assert fetch_weights("127.0.0.1:9", "abc") is None
        evs = [e for e in _recorder.events_since(before)[0]
               if e.get("kind") == "warmstart.fetch_failed"]
        assert len(evs) == 2
        assert metrics.counter("warmstart.fetch_failed").value >= 2

    def test_stalled_drain_is_recorded_and_retried_never_killed(self):
        act = _StubActuator(reap_rc=None)   # process never exits
        two = _obs({"unified": [("r0", 0, 0, 3, True),
                                ("r1", 0, 1, 3, True)]})
        state = {"obs": two}
        c = _ctl(lambda: state["obs"], act, idle_windows=1,
                 cooldown_s=3600.0, drain_timeout_s=0.0)
        before = _recorder.events_since(0)[1]
        c.tick()                        # decides: drain r0 (emptiest)
        assert act.of("drain") == [("drain", "r0")]
        c.tick()                        # past the 0s deadline → stall
        stalls = [e for e in _recorder.events_since(before)[0]
                  if e.get("kind") == "autoscale.drain_stalled"]
        assert stalls and stalls[0]["replica"] == "r0"
        # the reaction to a stall is ANOTHER drain POST — never a signal
        assert len(act.of("drain")) == 2
        # the lease never left, so the replica is never reaped (and the
        # actuator has no kill verb at all: reap only waits)
        assert act.of("reap") == []
        # lease leaves → reaped; rc None (still exiting) keeps it tracked
        state["obs"] = _obs({"unified": [("r1", 0, 1, 3, True)]})
        c.tick()
        assert len(act.of("reap")) == 1
        assert c.status()["draining"] == ["r0"]   # rc None: not done yet
        act.reap_rc = 0
        c.tick()
        assert c.status()["draining"] == []

    def test_actuator_crash_is_a_recorded_decision_not_a_wedge(self):
        class _Boom(_StubActuator):
            def scale_out(self, pool, warm_from=""):
                raise RuntimeError("spawn backend down")

        act = _Boom()
        state = {"obs": _obs({"unified": [("r0", 9, 3, 3, True)]})}
        c = _ctl(lambda: state["obs"], act, breach_windows=1,
                 cooldown_s=3600.0)
        c.tick()
        d = c.decisions()
        assert len(d) == 1 and d[0]["outcome"] == "error"
        assert "spawn backend down" in d[0]["error"]
        for _ in range(5):
            c.tick()                    # cooldown armed: no retry storm
        assert len(c.decisions()) == 1


# ------------------------------------ serving_bench autoscale sub-object

class TestAutoscaleBenchContract:
    def test_autoscale_subobject_schema(self, monkeypatch, capsys):
        """PADDLE_AUTOSCALE=1 → the bench JSON line gains an `autoscale`
        sub-object (decision totals, warm/cold ready, breach-to-first-
        token) and the line exists on every exit path. Absence when the
        controller is off is asserted on the already-paid-for fleet
        bench run in test_serving_fleet.py."""
        import sys as _sys

        from benchmarks import serving_bench
        monkeypatch.setenv("SERVING_TRAIN_STEPS", "0")
        monkeypatch.setenv("PADDLE_AUTOSCALE", "1")
        monkeypatch.setenv("AUTOSCALE_DRILL_REQUESTS", "8")
        monkeypatch.setattr(_sys, "argv", ["serving_bench.py", "2", "3",
                                           "4"])
        rc = serving_bench.main()
        line = [ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("{")][-1]
        doc = json.loads(line)
        assert rc == 0, doc
        a = doc["autoscale"]
        assert a and "error" not in a, a
        assert a["completed"] == a["requests"] == 8
        assert a["scale_out"] >= 1 and a["scale_in"] >= 1
        assert a["decisions"] >= a["scale_out"] + a["scale_in"]
        assert a["warm"] is True
        assert a["warm_ready_s"] > 0 and a["cold_ready_s"] > 0
        assert a["breach_to_first_token_s"] > 0
        assert a["pool_after_drain_back"] == 1


# ---------------------------------------------- the elastic drill (16)

def _prom_value(endpoint, name):
    """One counter's value from a replica's /metrics exposition."""
    with urllib.request.urlopen(endpoint + "/metrics", timeout=5) as r:
        text = r.read().decode()
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[-1])
    return 0.0


class TestElasticDrill:
    N_REQ = 10

    def test_flash_crowd_warm_scale_out_then_drain_back(
            self, small_model, tmp_path):
        cfg, params = small_model
        rng = np.random.RandomState(16)
        reqs = [(rng.randint(1, 256, int(n)).tolist(), 8)
                for n in rng.randint(4, 12, self.N_REQ)]
        dup0 = metrics.counter("serve.fleet.dup_results").value
        fleet = ServingFleet(
            1, SPEC, root=str(tmp_path), ttl=1.5,
            env={"JAX_PLATFORMS": "cpu", "PADDLE_WARMSTART": "1",
                 "PADDLE_CHAOS": ""})
        ctl = None
        try:
            fleet.start(timeout=240)
            router = fleet.router()
            # the cold baseline is r0 itself: same measurement (process
            # start → first warmup token served), no warm peer existed
            lease0 = fleet.registry.info("serve.r0")
            cold_s = float(lease0["ready_s"])
            assert lease0["warm"] is False
            ctl = AutoscaleController(
                RegistryObserver(fleet.registry), FleetActuator(fleet),
                ("unified",), interval_s=0.25, breach_windows=2,
                idle_windows=4, high_water=1.0, low_water=0.05,
                cooldown_s=4.0, min_replicas=1, max_replicas=2,
                drain_timeout_s=60.0).start()

            # ---- flash crowd: far more queued work than r0 has slots
            rids = []
            for p, m in reqs:
                while True:
                    try:
                        rids.append(router.submit(p, m))
                        break
                    except AdmissionReject as e:
                        time.sleep(min(e.retry_after_s, 0.3))

            # ---- scale-out within the hysteresis windows, warm
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                if ctl.decisions("scale_out") \
                        and not ctl.status()["pending_out"]:
                    break
                time.sleep(0.1)
            outs = ctl.decisions("scale_out")
            assert outs and outs[0]["outcome"] == "spawned", \
                f"no scale-out: {ctl.status()}"
            new = outs[0]["name"]
            assert outs[0]["warm_from"]          # donor endpoint rode along
            lease1 = fleet.registry.info("serve." + new)
            assert lease1 is not None and lease1["warm"] is True
            warm_s = float(lease1["ready_s"])
            # breach-to-first-token: transfer beats compilation ≥2×
            assert warm_s * 2 <= cold_s, \
                f"warm start not ≥2× faster: warm={warm_s}s cold={cold_s}s"
            # the warm path really ran: fetches on the new replica,
            # serves on the donor — read off each replica's /metrics
            assert _prom_value(lease1["endpoint"],
                               "paddle_warmstart_cache_fetched") >= 1
            assert _prom_value(lease1["endpoint"],
                               "paddle_warmstart_weights_fetched") >= 1
            assert _prom_value(lease0["endpoint"],
                               "paddle_warmstart_cache_served") >= 1
            assert _prom_value(lease0["endpoint"],
                               "paddle_warmstart_weights_served") >= 1

            # ---- every request completes, token-identical to the
            # un-scaled fault-free reference
            out = router.wait(timeout=240)
            assert len(out) == self.N_REQ
            for rid, (p, m) in zip(rids, reqs):
                assert out[rid] == _reference(cfg, params, p, m), \
                    f"rid {rid} diverged across the scale-out"
            assert metrics.counter("serve.fleet.dup_results").value == dup0

            # ---- load drop → idle windows → drain-back to the floor
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                st = ctl.status()
                alive = [x for x in fleet.registry.alive_nodes()
                         if x.startswith("serve.")]
                if ctl.decisions("scale_in") and not st["draining"] \
                        and len(alive) == 1:
                    break
                time.sleep(0.2)
            ins = ctl.decisions("scale_in")
            assert ins and ins[0]["outcome"] == "draining", \
                f"no drain-back: {ctl.status()}"
            assert len([x for x in fleet.registry.alive_nodes()
                        if x.startswith("serve.")]) == 1
            # nothing lost, nothing duplicated across grow + shrink
            assert metrics.counter("serve.fleet.dup_results").value == dup0
            assert router.slo.summary()["inflight"] == 0
        finally:
            if ctl is not None:
                ctl.stop()
            fleet.shutdown()
