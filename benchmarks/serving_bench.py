"""Continuous-batching serving throughput (VERDICT r3 next #8 "Done"
criterion: mixed-length throughput showing >B=1 utilization).

Serves a mixed-prompt-length request set under a mixed prefill/decode
request mix, several ways on the real chip:
  sequential — one llama_generate per request (B=1, the old LLMPredictor
               serving mode);
  continuous — the slot-pool ContinuousBatcher (inference/serving.py),
               timed for BOTH KV layouts (paged pool and dense slots).

    python benchmarks/serving_bench.py [n_requests] [max_batch] [burst]

Prints one JSON line with tokens/s for every mode and the speedups; the
line is emitted on EVERY exit path (an exception prints an `error`
payload first — bench contract, never JSON-less). Uses the r3 850M bench
model so the number is comparable to the decode bench (352 tok/s B=1
greedy, benchmarks/decode_bench.py).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    try:
        return _main()
    except BaseException as e:  # bench contract: never exit JSON-less
        print(json.dumps({
            "metric": "serving_continuous_batching_tokens_per_sec",
            "error": f"{type(e).__name__}: {e}"}))
        return 1


def _fleet_drill(n_replicas: int) -> dict:
    """ISSUE 9: N replica PROCESSES + router under a heavy-tail request
    mix — SIGKILL one replica mid-drill, client honors retry-after on
    admission rejections, everything accepted must complete. Runs the
    CPU-smoke model on every backend (replicas are separate processes; N
    copies of the TPU bench model contending for one chip would measure
    OOM, not the fleet), so the numbers are about SCHEDULING: rejections,
    retries, failovers, per-replica TTFT."""
    import tempfile
    import time as _time

    import numpy as np

    from paddle_tpu.inference.admission import (AdmissionPolicy,
                                                AdmissionReject)
    from paddle_tpu.inference.router import ServingFleet

    spec = {
        "config": {"vocab_size": 256, "hidden_size": 64,
                   "intermediate_size": 128, "num_hidden_layers": 2,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "max_position_embeddings": 128, "dtype": "float32"},
        "seed": 3,
        "batcher": {"max_batch": 3, "max_len": 96,
                    "prompt_buckets": [8, 16, 32], "burst": 4,
                    "page_size": 8},
    }
    n_req = int(os.environ.get("FLEET_DRILL_REQUESTS", "18"))
    rng = np.random.RandomState(11)
    # heavy tail: mostly short prompts/budgets, a fat tail of long ones
    lens = rng.choice([4, 6, 9, 14, 24], n_req, p=[.35, .3, .2, .1, .05])
    budgets = rng.choice([4, 6, 10, 24], n_req, p=[.4, .3, .2, .1])
    reqs = [(rng.randint(1, 256, int(n)).tolist(), int(m))
            for n, m in zip(lens, budgets)]

    import shutil

    root = tempfile.mkdtemp(prefix="fleet_bench_")
    fleet = ServingFleet(
        n_replicas, spec, root=root, ttl=1.2,
        env={"JAX_PLATFORMS": "cpu", "PADDLE_ADMIT_MAX_QUEUE": "4",
             "PADDLE_CHAOS": "", "PADDLE_SPEC_DECODE": "0"})
    t_up0 = _time.perf_counter()
    try:
        fleet.start(timeout=180)
        warmup_s = _time.perf_counter() - t_up0
        # the router must see the SAME cap the replicas enforce (their
        # env sets PADDLE_ADMIT_MAX_QUEUE=4): a looser router policy
        # would burn a doomed round trip + 429 per dispatch to a loaded
        # replica and distort the least-loaded ordering
        router = fleet.router(admission=AdmissionPolicy(max_queue=4))
        rejected = 0
        rids = []
        t0 = _time.perf_counter()
        kill_at = n_req // 2
        for i, (p, m) in enumerate(reqs):
            if i == kill_at:
                fleet.kill(f"r{n_replicas - 1}")   # mid-drill SIGKILL
            # a well-behaved client honors retry-after — but bounded: a
            # fleet that loses its LAST replica rejects no_replicas
            # forever, and an unbounded retry loop would hang the bench
            # instead of landing the failure in fleet_serve.error (a
            # hang has no exit for the JSON-line contract to cover)
            submit_deadline = _time.perf_counter() + 150.0
            while True:
                try:
                    rids.append(router.submit(p, m))
                    break
                except AdmissionReject as e:
                    rejected += 1
                    if _time.perf_counter() > submit_deadline:
                        raise TimeoutError(
                            f"fleet drill: request {i} still rejected "
                            f"({e.reason}) after 150s of honoring "
                            "retry-after") from e
                    _time.sleep(min(e.retry_after_s, 1.0))
        out = router.wait(timeout=180)
        drill_s = _time.perf_counter() - t0
        total_tokens = sum(len(v) for v in out.values())

        # per-replica TTFT distributions off each survivor's /snapshot
        # (the PR-5/6 observability plane read fleet-wide)
        per_replica = {}
        for rid_, snap in router.replica_snapshots().items():
            ttft = ((snap.get("extra", {}).get("serve", {}) or {})
                    .get("slo", {}).get("ttft", {}))
            per_replica[rid_] = {"ttft_p50": ttft.get("p50"),
                                 "ttft_p95": ttft.get("p95"),
                                 "count": ttft.get("count", 0)}
        s = router.summary()
        return {
            "replicas": n_replicas,
            "requests": n_req,
            # only reason=="complete" counts: router.wait() also returns
            # requests absorbed as terminal errors (empty tokens), and
            # completed==requests must not mask one of those
            "completed": sum(
                1 for rid in out
                if (router.result(rid) or {}).get("reason") == "complete"),
            "rejected": rejected,
            "retried": s["retried"],
            "failovers": s["failovers"],
            "killed": f"serve.r{n_replicas - 1}",
            "tokens_per_sec": round(total_tokens / drill_s, 1),
            "warmup_s": round(warmup_s, 2),
            "per_replica": per_replica,
        }
    finally:
        fleet.shutdown()
        shutil.rmtree(root, ignore_errors=True)


def _autoscale_drill() -> dict:
    """ISSUE 16: a 1-replica warm fleet + AutoscaleController under a
    flash crowd — the controller must scale out THROUGH the warm-start
    path (jit cache + weights fetched from the donor), serve everything,
    then drain back to the floor when the load drops. Reports the
    decision ledger totals and the warm-vs-cold breach-to-first-token
    story (ready_s is measured identically on both replicas: process
    main() start → first warmup token served)."""
    import shutil
    import tempfile
    import time as _time

    import numpy as np

    from paddle_tpu.inference.admission import AdmissionReject
    from paddle_tpu.inference.autoscale import (AutoscaleController,
                                                FleetActuator,
                                                RegistryObserver)
    from paddle_tpu.inference.router import ServingFleet
    from paddle_tpu.observability import recorder as _recorder

    spec = {
        "config": {"vocab_size": 256, "hidden_size": 64,
                   "intermediate_size": 128, "num_hidden_layers": 2,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "max_position_embeddings": 128, "dtype": "float32"},
        "seed": 3,
        "batcher": {"max_batch": 3, "max_len": 96,
                    "prompt_buckets": [8, 16, 32], "burst": 4,
                    "page_size": 8},
    }
    n_req = int(os.environ.get("AUTOSCALE_DRILL_REQUESTS", "10"))
    rng = np.random.RandomState(16)
    reqs = [(rng.randint(1, 256, int(n)).tolist(), 8)
            for n in rng.randint(4, 12, n_req)]

    root = tempfile.mkdtemp(prefix="autoscale_bench_")
    fleet = ServingFleet(
        1, spec, root=root, ttl=1.5,
        env={"JAX_PLATFORMS": "cpu", "PADDLE_WARMSTART": "1",
             "PADDLE_CHAOS": "", "PADDLE_SPEC_DECODE": "0"})
    ctl = None
    try:
        fleet.start(timeout=240)
        router = fleet.router()
        lease0 = fleet.registry.info("serve.r0")
        cold_s = float(lease0["ready_s"])     # r0 compiled from scratch
        ctl = AutoscaleController(
            RegistryObserver(fleet.registry), FleetActuator(fleet),
            ("unified",), interval_s=0.25, breach_windows=2,
            idle_windows=4, high_water=1.0, low_water=0.05,
            cooldown_s=4.0, min_replicas=1, max_replicas=2,
            drain_timeout_s=60.0).start()
        ev0 = _recorder.events_since(0)[1]
        for p, m in reqs:                     # the flash crowd
            deadline = _time.perf_counter() + 150.0
            while True:
                try:
                    router.submit(p, m)
                    break
                except AdmissionReject as e:
                    if _time.perf_counter() > deadline:
                        raise TimeoutError(
                            "autoscale drill: submission still rejected "
                            "after 150s of honoring retry-after") from e
                    _time.sleep(min(e.retry_after_s, 1.0))
        deadline = _time.monotonic() + 120
        while _time.monotonic() < deadline:   # scale-out resolves
            if ctl.decisions("scale_out") \
                    and not ctl.status()["pending_out"]:
                break
            _time.sleep(0.1)
        outs = ctl.decisions("scale_out")
        new = outs[0]["name"] if outs else None
        lease1 = fleet.registry.info("serve." + new) if new else None
        out = router.wait(timeout=240)
        deadline = _time.monotonic() + 120
        while _time.monotonic() < deadline:   # idle → drain-back
            alive = [x for x in fleet.registry.alive_nodes()
                     if x.startswith("serve.")]
            if ctl.decisions("scale_in") and not ctl.status()["draining"] \
                    and len(alive) == 1:
                break
            _time.sleep(0.2)
        ready = [e for e in _recorder.events_since(ev0)[0]
                 if e.get("kind") == "autoscale.scale_out_ready"]
        return {
            "requests": n_req,
            "completed": sum(
                1 for rid in out
                if (router.result(rid) or {}).get("reason") == "complete"),
            "decisions": len(ctl.decisions()),
            "scale_out": len(outs),
            "scale_in": len(ctl.decisions("scale_in")),
            "warm": bool(lease1 and lease1.get("warm")),
            "cold_ready_s": round(cold_s, 3),
            "warm_ready_s": (round(float(lease1["ready_s"]), 3)
                             if lease1 else None),
            "breach_to_first_token_s": (
                round(ready[0]["breach_to_first_token_s"], 3)
                if ready else None),
            "pool_after_drain_back": len(
                [x for x in fleet.registry.alive_nodes()
                 if x.startswith("serve.")]),
        }
    finally:
        if ctl is not None:
            ctl.stop()
        fleet.shutdown()
        shutil.rmtree(root, ignore_errors=True)


def _reliability_drill() -> dict:
    """ISSUE 19: a 2-replica fleet with deadlines, cancels and hedged
    re-dispatch in the request mix. Reports the reliability counters the
    feature exists to bound: typed deadline shedding at the door,
    exactly-once mid-flight cancels, and hedge volume under the global
    retry budget. Every admitted request must account for exactly one
    terminal reason — complete + cancelled sums to the admit count."""
    import shutil
    import tempfile
    import time as _time

    import numpy as np

    from paddle_tpu.inference.admission import AdmissionReject
    from paddle_tpu.inference.router import ServingFleet

    spec = {
        "config": {"vocab_size": 256, "hidden_size": 64,
                   "intermediate_size": 128, "num_hidden_layers": 2,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "max_position_embeddings": 128, "dtype": "float32"},
        "seed": 3,
        "batcher": {"max_batch": 3, "max_len": 96,
                    "prompt_buckets": [8, 16, 32], "burst": 4,
                    "page_size": 8},
    }
    n_req = int(os.environ.get("RELIABILITY_DRILL_REQUESTS", "10"))
    rng = np.random.RandomState(19)
    reqs = [(rng.randint(1, 256, int(n)).tolist(), int(m))
            for n, m in zip(rng.randint(4, 16, n_req),
                            rng.choice([4, 6, 10], n_req))]

    root = tempfile.mkdtemp(prefix="reliability_bench_")
    fleet = ServingFleet(
        2, spec, root=root, ttl=1.2,
        env={"JAX_PLATFORMS": "cpu", "PADDLE_CHAOS": "",
             "PADDLE_SPEC_DECODE": "0"})
    # hedging is ROUTER config (read at construction, in this process):
    # a low floor makes ordinary CPU-fleet latency hedge-eligible, so the
    # drill exercises the hedge path without needing a wedged replica —
    # token parity makes the hedge invisible in the outputs either way
    saved = {k: os.environ.get(k)
             for k in ("PADDLE_HEDGE_DELAY_S", "PADDLE_RETRY_BUDGET_PCT")}
    os.environ.setdefault("PADDLE_HEDGE_DELAY_S", "0.5")
    os.environ.setdefault("PADDLE_RETRY_BUDGET_PCT", "50")
    try:
        fleet.start(timeout=180)
        router = fleet.router()
        shed = 0
        try:
            # an already-expired budget is shed typed AT THE DOOR —
            # no replica ever sees it
            router.submit(reqs[0][0], reqs[0][1], deadline_s=0.0)
        except AdmissionReject as e:
            if e.reason != "deadline_unmeetable":
                raise RuntimeError(
                    f"expected deadline_unmeetable, got {e.reason}")
            shed += 1
        rids = []
        for p, m in reqs:
            submit_deadline = _time.perf_counter() + 150.0
            while True:
                try:
                    rids.append(router.submit(p, m, deadline_s=120.0))
                    break
                except AdmissionReject as e:
                    if _time.perf_counter() > submit_deadline:
                        raise TimeoutError(
                            "reliability drill: submission still "
                            f"rejected ({e.reason}) after 150s") from e
                    _time.sleep(min(e.retry_after_s, 1.0))
        # cooperative cancel on the freshest two — they may already have
        # finished (cancel racing retire is a no-op by contract), so the
        # terminal-reason tally below is what must balance, not these
        cancel_states = [router.cancel(r) for r in rids[-2:]]
        router.wait(rids, timeout=240)
        s = router.summary()
        reasons: dict = {}
        for r in rids:
            rec = router.result(r) or {}
            k = rec.get("reason", "missing")
            reasons[k] = reasons.get(k, 0) + 1
        return {
            "requests": n_req,
            "shed": shed,
            "completed": reasons.get("complete", 0),
            "cancelled": s["cancelled"],
            "deadline_exceeded": s["deadline_exceeded"],
            "hedges": s["hedges"],
            "hedge_wins": s["hedge_wins"],
            "retry_budget_exhausted": s["retry_budget_exhausted"],
            "dup_results": s["dup_results"],
            "cancel_states": cancel_states,
            "terminal_reasons": reasons,
        }
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        fleet.shutdown()
        shutil.rmtree(root, ignore_errors=True)


def _disagg_drill(n_prefill: int, n_decode: int) -> dict:
    """ISSUE 11: a MIXED fleet — prefill-pool + decode-pool subprocess
    replicas behind a DisaggRouter, quantized (int8) KV pages on the
    transfer wire, one prefill replica SIGKILLed mid-drill. Reports what
    disaggregation is for: per-POOL latency (the prefill pool's TTFT no
    longer competes with the decode pool's TPOT), the transfer bill
    (bytes/request, transfer_s, quantized-vs-f32 wire ratio) and the
    per-stage failover story."""
    import shutil
    import tempfile
    import time as _time

    import numpy as np

    from paddle_tpu.inference.admission import AdmissionReject
    from paddle_tpu.inference.disagg.transfer import wire_ratio_vs_f32
    from paddle_tpu.inference.router import ServingFleet
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.observability import metrics

    # head_dim 32 (128 / 4): the quantized wire ratio is a deployment
    # number only at deployment-ish head dims — at hd 16 the f32 scale
    # per (row, head) would eat the payload win
    spec = {
        "config": {"vocab_size": 256, "hidden_size": 128,
                   "intermediate_size": 256, "num_hidden_layers": 2,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "max_position_embeddings": 128, "dtype": "float32"},
        "seed": 3,
        "batcher": {"max_batch": 3, "max_len": 96,
                    "prompt_buckets": [8, 16, 32], "burst": 4,
                    "page_size": 8, "kv_dtype": "int8"},
    }
    cfg = LlamaConfig(**{**spec["config"], "dtype": np.float32})
    n_req = int(os.environ.get("FLEET_DRILL_REQUESTS", "14"))
    rng = np.random.RandomState(13)
    lens = rng.choice([4, 6, 9, 14, 24], n_req, p=[.35, .3, .2, .1, .05])
    budgets = rng.choice([4, 6, 10, 24], n_req, p=[.4, .3, .2, .1])
    reqs = [(rng.randint(1, 256, int(n)).tolist(), int(m))
            for n, m in zip(lens, budgets)]

    root = tempfile.mkdtemp(prefix="disagg_bench_")
    fleet = ServingFleet(
        n_prefill + n_decode, spec, root=root, ttl=1.2,
        n_prefill=n_prefill,
        env={"JAX_PLATFORMS": "cpu", "PADDLE_ADMIT_MAX_QUEUE": "6",
             "PADDLE_CHAOS": "", "PADDLE_SPEC_DECODE": "0"})
    xfer0 = metrics.histogram("slo.transfer_s").stats()["count"]
    t_up0 = _time.perf_counter()
    try:
        fleet.start(timeout=180)
        warmup_s = _time.perf_counter() - t_up0
        router = fleet.router()
        rejected = 0
        rids = []
        t0 = _time.perf_counter()
        kill_at = n_req // 2
        for i, (p, m) in enumerate(reqs):
            if i == kill_at:
                fleet.kill("r0")            # a PREFILL replica, mid-drill
            submit_deadline = _time.perf_counter() + 150.0
            while True:
                try:
                    rids.append(router.submit(p, m))
                    break
                except AdmissionReject as e:
                    rejected += 1
                    if _time.perf_counter() > submit_deadline:
                        raise TimeoutError(
                            f"disagg drill: request {i} still rejected "
                            f"({e.reason}) after 150s") from e
                    _time.sleep(min(e.retry_after_s, 1.0))
        out = router.wait(timeout=180)
        drill_s = _time.perf_counter() - t0
        total_tokens = sum(len(v) for v in out.values())

        per_pool: dict = {"prefill": {}, "decode": {}}
        for rid_, snap in router.replica_snapshots().items():
            extra = snap.get("extra", {}) or {}
            role = (extra.get("replica", {}) or {}).get("role", "unified")
            slo = (extra.get("serve", {}) or {}).get("slo", {})
            per_pool.setdefault(role, {})[rid_] = {
                "ttft_p50": (slo.get("ttft") or {}).get("p50"),
                "ttft_p95": (slo.get("ttft") or {}).get("p95"),
                "tpot_p50": (slo.get("tpot") or {}).get("p50"),
                "tpot_p95": (slo.get("tpot") or {}).get("p95"),
            }
        xs = metrics.histogram("slo.transfer_s").stats()
        s = router.summary()
        return {
            "prefill_replicas": n_prefill,
            "decode_replicas": n_decode,
            "requests": n_req,
            "completed": sum(
                1 for rid in out
                if (router.result(rid) or {}).get("reason") == "complete"),
            "rejected": rejected,
            "killed": "serve.r0",
            "tokens_per_sec": round(total_tokens / drill_s, 1),
            "warmup_s": round(warmup_s, 2),
            "per_pool": per_pool,
            "transfer": {
                "requests": s["transfers"],
                "bytes_per_request": (
                    round(router.xfer_bytes_total / s["transfers"])
                    if s["transfers"] else None),
                "transfer_s_p50": xs["p50"] if xs["count"] > xfer0 else None,
                "transfer_s_p95": xs["p95"] if xs["count"] > xfer0 else None,
                "wire_ratio_vs_f32": round(wire_ratio_vs_f32(
                    cfg, spec["batcher"]["page_size"], "int8",
                    os.environ.get("PADDLE_SERVE_KV_SCALE_GRAN") or "row"),
                    4),
            },
            "failovers": {
                "prefill": s["failovers_prefill"],
                "decode": s["failovers_decode"],
                "transfer_faults": s["xfer_faults"],
                "reprefills": s["reprefills"],
            },
            # critical-path TTFT attribution (ISSUE 17): per-stage
            # p50/p95 SHARES of TTFT from the router's trace assembler
            # (None when tracing is off — PADDLE_REQTRACE=0)
            "crit": (router.trace.bench_payload()
                     if router.trace is not None else None),
        }
    finally:
        fleet.shutdown()
        shutil.rmtree(root, ignore_errors=True)


def _prefix_bench(cfg, params, max_batch, max_len, buckets, burst,
                  page_size, cache_pages, prompt, n_req) -> dict:
    """ISSUE 13: the prefix-sharing sub-object — a common system prompt
    (2 full pages; fits the smallest bucket grid with its tails) with
    per-request tails, served with the cache ON (second pass warm: every
    admit hits) vs OFF. TTFT is measured directly as single-request
    mnt=1 serve walls (enqueue → first token IS the whole serve),
    because the slo histograms are process-global and the other serving
    passes already filled them."""
    import time as _time

    import numpy as np

    from paddle_tpu.inference import ContinuousBatcher

    rng = np.random.RandomState(17)
    sys_prompt = prompt(2 * page_size)
    tail_lens = rng.choice([3, 7, 11], n_req)
    reqs = [(sys_prompt + prompt(int(k)), 6) for k in tail_lens]

    def engine(pages):
        # spec_decode pinned off: the prefix sub-object is a prefill/TTFT
        # comparison — a fleet-wide PADDLE_SPEC_DECODE must not inject
        # draft+verify launches into its walls (same rule as serve()'s)
        return ContinuousBatcher(cfg, params, max_batch=max_batch,
                                 max_len=max_len, prompt_buckets=buckets,
                                 burst=burst, kv_layout="paged",
                                 page_size=page_size,
                                 prefix_cache_pages=pages,
                                 spec_decode=False)

    def ttft_p50(eng, n=5):
        walls = []
        for i in range(n):
            t0 = _time.perf_counter()
            eng.add_request(sys_prompt + prompt(3 + i), max_new_tokens=1)
            eng.run()
            walls.append(_time.perf_counter() - t0)
        return float(np.median(walls))

    on = engine(cache_pages)
    for p, m in reqs:                      # pass 1: compiles + populates
        on.add_request(p, max_new_tokens=m)
    on.run()
    h0 = on.stats.get("prefix_hits", 0)
    for p, m in reqs:                      # pass 2: warm — every admit hits
        on.add_request(p, max_new_tokens=m)
    on.run()
    hits = on.stats.get("prefix_hits", 0) - h0
    snap = dict(on.stats)                  # before the TTFT probes admit more
    ttft_shared = ttft_p50(on)

    off = engine(0)
    for p, m in reqs:                      # compile pass
        off.add_request(p, max_new_tokens=m)
    off.run()
    ttft_unshared = ttft_p50(off)

    total_hits = snap.get("prefix_hits", 0)
    return {
        "cache_pages": int(cache_pages),
        "hit_rate": round(hits / max(1, n_req), 3),
        "pages_shared": int(snap.get("prefix_pages_shared", 0)),
        "marginal_pages_per_shared_admit": (
            round(snap.get("prefix_marginal_pages", 0) / total_hits, 2)
            if total_hits else None),
        "resumes": int(snap.get("prefix_resumes", 0)),
        "cow_copies": int(snap.get("cow_copies", 0)),
        "ttft_p50_shared_s": round(ttft_shared, 5),
        "ttft_p50_unshared_s": round(ttft_unshared, 5),
    }


def _main():
    n_req = int(sys.argv[1]) if len(sys.argv) > 1 else 16
    max_batch = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    burst = int(sys.argv[3]) if len(sys.argv) > 3 else 16

    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference import ContinuousBatcher
    from paddle_tpu.models.llama import LlamaConfig, llama_init_params
    from paddle_tpu.models.llama_decode import llama_generate

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=14, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=2048,
            dtype=jnp.bfloat16)
        max_len, buckets = 512, (64, 128, 256)
    else:  # CPU smoke
        cfg = LlamaConfig.tiny(num_hidden_layers=2)
        max_len, buckets = 96, (16, 32)
        n_req = min(n_req, 6)

    # ---- pre-train on a structured corpus (VERDICT r4 weak #2): with
    # RANDOM weights the two serving paths' different prefill shapes break
    # bf16 argmax TIES differently, so greedy equality was informational
    # only. ~150 train steps on the Zipf-Markov corpus peak the logits,
    # ties vanish, and equality becomes a hard assertion.
    # SERVING_TRAIN_STEPS=0 restores the random-weight informational mode.
    train_steps = int(os.environ.get(
        "SERVING_TRAIN_STEPS", "150" if on_tpu else "40"))
    rng = np.random.RandomState(0)
    corpus = None
    if train_steps:
        from paddle_tpu.io.token_loader import synthetic_corpus
        from paddle_tpu.models import LlamaTrainStep
        from paddle_tpu.optimizer import AdamW

        corpus = np.asarray(synthetic_corpus(
            400_000, vocab_size=min(512, cfg.vocab_size), seed=7))
        # seed=0 init inside the trainer == the llama_init_params(PRNGKey(0))
        # init above; `params` is simply replaced by the trained weights
        step = LlamaTrainStep(
            cfg, optimizer=AdamW(learning_rate=3e-4, weight_decay=0.1,
                                 moment_dtype=jnp.bfloat16),
            remat=True, seed=0)
        B_tr, T_tr = (4, 512) if on_tpu else (2, 64)
        span = B_tr * (T_tr + 1)
        t0 = time.perf_counter()
        for i in range(train_steps):
            off = (i * span) % (len(corpus) - span - 1)
            chunk = corpus[off:off + span].reshape(B_tr, T_tr + 1)
            loss = step(chunk[:, :-1].astype(np.int32),
                        chunk[:, 1:].astype(np.int32))
        final_loss = float(jax.device_get(loss))
        train_s = time.perf_counter() - t0
        params = step.params
        del step
        print(f"# pre-train {train_steps} steps in {train_s:.0f}s, "
              f"loss {final_loss:.3f}", file=sys.stderr)
    else:
        params = llama_init_params(cfg, jax.random.PRNGKey(0))

    def prompt(n):
        if corpus is not None:  # on-distribution spans → peaked logits
            off = int(rng.randint(0, len(corpus) - n - 1))
            return [int(t) or 1 for t in corpus[off:off + n]]
        return rng.randint(1, cfg.vocab_size, int(n)).tolist()

    lens = rng.choice([24, 57, 100, 190] if on_tpu else [5, 11, 23], n_req)
    budgets = rng.choice([32, 64, 96] if on_tpu else [4, 8, 12], n_req)
    reqs = [(prompt(int(n)), int(m)) for n, m in zip(lens, budgets)]
    total_new = int(sum(m for _, m in reqs))

    # ---- sequential B=1: one llama_generate executable per (T, budget)
    # signature — the per-signature compile cost is the usage model the
    # reference's predictor has too (pad prompts to cut signatures)
    t0 = time.perf_counter()
    seq_out = []
    for p, m in reqs:
        toks = jnp.asarray(np.asarray(p, np.int32)[None, :])
        out = llama_generate(params, toks, cfg, m, temperature=0.0)
        seq_out.append([int(t) for t in np.asarray(out)[0]])
    seq_s = time.perf_counter() - t0
    # re-run once compiled (first pass pays one compile per signature)
    t0 = time.perf_counter()
    for p, m in reqs:
        toks = jnp.asarray(np.asarray(p, np.int32)[None, :])
        np.asarray(llama_generate(params, toks, cfg, m, temperature=0.0))
    seq_s = time.perf_counter() - t0

    # ---- continuous batching (includes its compiles on first run; measure
    # a second pass for steady-state, same as sequential). Both KV layouts
    # are timed: paged (block-table pool, the default) and dense slots.
    page_size = 64 if on_tpu else 8   # ONE knob: engines + bytes/token math

    def serve(kv_layout, kv_dtype="", spec=False):
        # kv_dtype="" pins the baseline passes to full-precision pages
        # even under a fleet-wide PADDLE_SERVE_KV_DTYPE (dense ignores
        # it); prefix_cache_pages=0 and spec_decode likewise pin the
        # baselines: the `prefix` and `spec` sub-objects are the ONE
        # comparison surface for those features — a fleet-wide env must
        # not silently recompute them inside every baseline pass, and
        # null-off must mean OFF, not zero-hits (ISSUE 14 satellite)
        kw = {} if kv_layout == "dense" else {"kv_dtype": kv_dtype,
                                              "prefix_cache_pages": 0,
                                              "spec_decode": spec}
        eng = ContinuousBatcher(cfg, params, max_batch=max_batch,
                                max_len=max_len, prompt_buckets=buckets,
                                burst=burst, kv_layout=kv_layout,
                                page_size=page_size, **kw)
        rids = [eng.add_request(p, max_new_tokens=m) for p, m in reqs]
        return eng, rids, eng.run()

    serve("paged")  # compile pass
    t0 = time.perf_counter()
    eng, rids, out = serve("paged")
    cont_s = time.perf_counter() - t0

    serve("dense")  # compile pass
    t0 = time.perf_counter()
    _, dense_rids, dense_out = serve("dense")
    dense_s = time.perf_counter() - t0

    # ---- quantized KV pages (ISSUE 10): the same workload once more with
    # int8/fp8 pages through the gather path — the `quant` sub-object
    # reports what the quantized pool buys (bytes/token + capacity at an
    # equal HBM budget vs bf16 pages) and what it costs (greedy token
    # agreement vs the full-precision paged serve).
    from benchmarks._quant_report import bench_kv_dtype, kv_quant_subobject
    kv_dt = bench_kv_dtype()
    serve("paged", kv_dtype=kv_dt)  # compile pass
    t0 = time.perf_counter()
    _, quant_rids, quant_out = serve("paged", kv_dtype=kv_dt)
    quant_s = time.perf_counter() - t0
    dense_pages = (max_len - 1) // page_size + 1
    quant_obj = kv_quant_subobject(
        cfg, page_size, dense_pages, kv_dt,
        [out[r] for r in rids], [quant_out[r] for r in quant_rids],
        tokens_per_sec=round(total_new / quant_s, 1))

    # ---- speculative decoding (ISSUE 14): PADDLE_SPEC_DECODE=1 serves
    # the same workload once more through draft-propose + one-launch
    # verify on the paged engine and reports the `spec` sub-object
    # (accept rate, tokens per slot-launch, draft overhead, spec-vs-plain
    # ratio); null otherwise — off must be distinguishable from
    # zero-accepts. A failure lands as spec.error (never JSON-less).
    from benchmarks._spec_report import spec_enabled, spec_subobject
    from paddle_tpu.observability import metrics as _metrics
    spec_obj = None
    spec_divergent = 0
    if spec_enabled():
        try:
            serve("paged", spec=True)  # compile pass
            ar0 = _metrics.histogram("serve.spec_accept_rate") \
                .stats()["count"]
            t0 = time.perf_counter()
            seng, spec_rids, spec_out = serve("paged", spec=True)
            spec_s = time.perf_counter() - t0
            spec_divergent = sum(spec_out[s] != out[r]
                                 for s, r in zip(spec_rids, rids))
            spec_obj = spec_subobject(seng, total_new, spec_s=spec_s,
                                      plain_s=cont_s,
                                      parity=spec_divergent == 0,
                                      accept_hist_count0=ar0)
        except BaseException as e:
            spec_obj = {"error": f"{type(e).__name__}: {e}"}

    # With trained weights greedy equality is a HARD assertion (logits
    # peaked, no load-bearing argmax ties); with random weights
    # (SERVING_TRAIN_STEPS=0) the different prefill/attention SHAPES break
    # bf16 ties differently and the count is informational only. The f32
    # CPU suite (tests/test_serving.py) pins exact equality either way.
    mismatch = sum(out[r] != s for r, s in zip(rids, seq_out))
    paged_vs_dense = sum(out[r] != dense_out[d]
                         for r, d in zip(rids, dense_rids))

    # request-level SLO distributions (ISSUE 6): TTFT/TPOT/e2e p50+p95 and
    # the breach count over every request the serving passes retired —
    # schema pinned by the bench contract tests, absent only when serving
    # is not exercised (never here)
    from paddle_tpu.observability import slo as _slo
    slo_obj = _slo.bench_payload()

    # multi-replica heavy-tail traffic drill (ISSUE 9, ROADMAP-named):
    # PADDLE_SERVE_REPLICAS >= 2 spawns a replica fleet + router, runs a
    # heavy-tail request mix with a retry-after-honoring client, SIGKILLs
    # one replica mid-drill, and reports the fleet_serve sub-object. A
    # drill failure lands as fleet_serve.error — the JSON line survives.
    n_replicas = int(os.environ.get("PADDLE_SERVE_REPLICAS", "0") or 0)
    fleet_obj = None
    if n_replicas >= 2:
        try:
            fleet_obj = _fleet_drill(n_replicas)
        except BaseException as e:
            fleet_obj = {"error": f"{type(e).__name__}: {e}"}

    # prefix sharing (ISSUE 13): PADDLE_PREFIX_CACHE_PAGES > 0 serves a
    # common-system-prompt workload with the cache on (warm) vs off and
    # reports the `prefix` sub-object; null otherwise (all-unique prompts
    # would only pay the hash cost — the README says when not to enable).
    # A failure lands as prefix.error — the JSON line survives.
    prefix_obj = None
    cache_pages = int(os.environ.get("PADDLE_PREFIX_CACHE_PAGES", "0")
                      or 0)
    if cache_pages > 0:
        try:
            prefix_obj = _prefix_bench(
                cfg, params, max_batch, max_len, buckets, burst,
                page_size, cache_pages, prompt,
                n_req=min(n_req, 8))
        except BaseException as e:
            prefix_obj = {"error": f"{type(e).__name__}: {e}"}

    # disaggregated prefill/decode drill (ISSUE 11): PADDLE_SERVE_DISAGG=1
    # spawns a mixed fleet (PADDLE_SERVE_PREFILL_REPLICAS prefill +
    # max(2, PADDLE_SERVE_REPLICAS - prefill) decode) behind a
    # DisaggRouter and reports the disagg sub-object; null otherwise. A
    # drill failure lands as disagg.error — the JSON line survives.
    disagg_obj = None
    if (os.environ.get("PADDLE_SERVE_DISAGG", "") or "0") not in ("", "0"):
        n_pre = max(2, int(os.environ.get("PADDLE_SERVE_PREFILL_REPLICAS",
                                          "2") or 2))
        n_dec = max(2, n_replicas - n_pre)
        try:
            disagg_obj = _disagg_drill(n_pre, n_dec)
        except BaseException as e:
            disagg_obj = {"error": f"{type(e).__name__}: {e}"}

    # SLO-driven autoscaler drill (ISSUE 16): PADDLE_AUTOSCALE=1 runs a
    # 1→2 warm-scale-out / drain-back drill and the JSON line gains the
    # `autoscale` sub-object; the key is ABSENT (not null) when the
    # controller is off. A drill failure lands as autoscale.error — the
    # JSON line survives.
    autoscale_obj = None
    if (os.environ.get("PADDLE_AUTOSCALE", "") or "0") not in ("", "0"):
        try:
            autoscale_obj = _autoscale_drill()
        except BaseException as e:
            autoscale_obj = {"error": f"{type(e).__name__}: {e}"}

    # request-lifecycle reliability drill (ISSUE 19):
    # PADDLE_SERVE_RELIABILITY=1 runs a deadline/cancel/hedge mix against
    # a 2-replica fleet and the JSON line gains the `reliability`
    # sub-object; the key is ABSENT (not null) when off. A drill failure
    # lands as reliability.error — the JSON line survives.
    reliability_obj = None
    if (os.environ.get("PADDLE_SERVE_RELIABILITY", "")
            or "0") not in ("", "0"):
        try:
            reliability_obj = _reliability_drill()
        except BaseException as e:
            reliability_obj = {"error": f"{type(e).__name__}: {e}"}

    payload = {
        "metric": "serving_continuous_batching_tokens_per_sec",
        "value": round(total_new / cont_s, 1),
        "unit": "tokens/s",
        "kv_layout": "paged",
        "slo": slo_obj,
        "fleet_serve": fleet_obj,
        "disagg": disagg_obj,
        "prefix": prefix_obj,
        "spec": spec_obj,
        "quant": quant_obj,
        "vs_sequential_b1": round(seq_s / cont_s, 2),
        "vs_dense_slots": round(dense_s / cont_s, 2),
        "config": {"requests": n_req, "max_batch": max_batch,
                   "burst": burst, "prompt_lens": lens.tolist(),
                   "budgets": budgets.tolist(),
                   "bursts_run": eng.stats["bursts"],
                   "page_buckets_used": eng.stats["page_buckets_used"]},
        "sequential_tokens_per_sec": round(total_new / seq_s, 1),
        "dense_tokens_per_sec": round(total_new / dense_s, 1),
        "trained_weights": bool(train_steps),
        "greedy_divergent_requests": mismatch,
        "paged_vs_dense_divergent_requests": paged_vs_dense,
        "device": str(getattr(jax.devices()[0], "device_kind", "?")),
    }
    if autoscale_obj is not None:
        payload["autoscale"] = autoscale_obj
    if reliability_obj is not None:
        payload["reliability"] = reliability_obj
    print(json.dumps(payload))

    # hard parity gate AFTER the JSON line: the measured throughputs must
    # never be discarded by the failure they diagnose (cf. bench.py
    # _record_latest rationale). Plain `if` — `assert` dies under -O.
    if train_steps and (mismatch or paged_vs_dense or spec_divergent):
        print(f"# FAIL: {mismatch}/{n_req} paged-vs-sequential, "
              f"{paged_vs_dense}/{n_req} paged-vs-dense and "
              f"{spec_divergent}/{n_req} spec-vs-plain requests diverged "
              f"WITH TRAINED WEIGHTS — a real numerics bug, not a bf16 "
              f"tiebreak", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
