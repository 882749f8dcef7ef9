"""Headline benchmark: Llama pretrain step throughput on the local TPU chip.

Prints ONE JSON line: tokens/sec/chip + MFU on the flagship train step
(fwd+bwd+AdamW, bf16 compute+moments, Pallas flash attention, selective
remat, donation). vs_baseline = MFU / 0.45 (BASELINE.md north-star).

STATUS (PR 22): this file predates the local chip and is replaced whole by
the benchmark PR (ROADMAP S1/D5). `chip_smoke.py` is the proof that the
system runs on the chip; nothing here has been measured on it. Its
behaviour is pinned by tests/test_resilience.py::TestBenchNeverJsonless and
tests/test_observability.py::TestBenchMetricsEmbed and is left as it was:

TPU probing is BOUNDED: the probe window is capped (~300 s default,
BENCH_TPU_WAIT_S overrides) and on exhaustion the bench FALLS BACK to the
tiny CPU smoke sizing (vs_baseline=0, device=cpu) so a JSON line always
lands — a retry window that outlives the caller's time limit dies
JSON-less at rc=124. A CPU line is a smoke result, never a device number.
Every JSON line carries a top-level ``device`` field (``cpu`` / the TPU
device_kind / ``none`` on the error path). BENCH_REQUIRE_TPU=1 restores
the strict mode (error JSON + rc 1 instead of the CPU fallback).

Measurement:
  * steady-state chains: each sample enqueues CHAIN dependent steps and
    syncs ONCE via device_get of the final loss (each step's params depend
    on the previous step's donated outputs, so the chip runs the chain
    sequentially). A real training loop does not host-sync per step, so
    per-step sync time is not chip throughput. Per-step wall = chain wall
    / CHAIN.
  * headline step time = MEDIAN of chain samples (min + mean reported
    alongside).

MFU accounting (honest, GQA-aware, fwd+bwd):
  matmul flops/token    = 6 * (N_params - embed_table)   (fwd 2N + bwd 4N;
    the input-embedding GATHER is not a matmul and does no MXU flops —
    counting it inflated r2's headline by ~7%)
  attention flops/token = 6 * layers * H_q * head_dim * T  (causal 1/2 ×
    qk^T+pv fwd, 2× in bwd); GQA enters through N_params while the score/
    value matmuls scale with the QUERY head count.
  Remat recompute is NOT counted (model flops, not hardware flops).
  `mfu_incl_embed` reports the r2-style number for comparability.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))

# ---- the never-JSON-less contract (a caller's timeout once killed the
# bench mid-retry: rc=124 and no machine-readable line). EVERY exit path routes
# through _emit(); signal handlers + a dead-man alarm guarantee the JSON
# line lands even when the driver starts killing us.

_EMITTED = [False]


def _emit(payload: dict) -> None:
    """Print exactly ONE machine-readable JSON line per process, ever."""
    if _EMITTED[0]:
        return
    _EMITTED[0] = True
    print(json.dumps(payload), flush=True)


def _metrics_payload() -> dict | None:
    """The observability snapshot embedded in the bench JSON line: step-time
    p50/p95, retry/chaos/restore counters — the perf-trajectory dimension of
    BENCH_*.json. Never raises (the bench may die before paddle_tpu ever
    imported; the JSON contract survives regardless)."""
    try:
        if "paddle_tpu" in sys.modules:
            from paddle_tpu.observability import metrics
        else:
            # error paths that never imported paddle_tpu (tpu unreachable,
            # SIGTERM in the probe window) must not pay the full jax import
            # just to report an empty registry: load the stdlib-only metrics
            # module standalone
            import importlib.util
            spec = importlib.util.spec_from_file_location(
                "_bench_obs_metrics",
                os.path.join(_HERE, "paddle_tpu", "observability",
                             "metrics.py"))
            metrics = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(metrics)
        snap = metrics.snapshot()
        return {
            "counters": snap["counters"],
            "step_time_s": snap["histograms"].get("train.step_time_s"),
        }
    except Exception:
        return None


def _slo_payload() -> dict | None:
    """The ``slo`` sub-object (TTFT/TPOT/e2e p50+p95 + breach count) —
    present ONLY when this process exercised serving (slo.e2e_s has
    observations); a pure-training bench line carries no slo key at all.
    Schema pinned by the bench contract tests."""
    try:
        if "paddle_tpu" not in sys.modules:
            return None  # paddle never imported => nothing ever served
        from paddle_tpu.observability import slo
        return slo.bench_payload()
    except Exception:
        return None


def _fleet_payload() -> dict | None:
    """The ``fleet`` sub-object (rank count, straggler events, telemetry
    drop counter) — present only on multi-rank runs (the launcher exports
    PADDLE_TRAINERS_NUM > 1). Schema pinned by the bench contract tests."""
    try:
        world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1") or "1")
    except ValueError:
        return None
    if world <= 1:
        return None
    snap = _metrics_payload() or {}
    counters = snap.get("counters", {})
    return {
        "ranks": world,
        "straggler_events": int(counters.get("fleet.straggler", 0)),
        "telemetry_drops": int(counters.get("telemetry.drops", 0)),
    }


def _quant_payload(n_params: int | None = None) -> dict | None:
    """The ``quant`` sub-object (ISSUE 10): present only when
    PADDLE_QUANT_ALLREDUCE selects a quantized gradient-sync wire —
    reports the bytes each rank would put on the wire for one allreduce
    of the step's gradients next to the fp32 sync it replaces, plus the
    fallback/call counters (a chaos-degraded call shows up here). Never
    raises (bench JSON contract)."""
    try:
        mode = os.environ.get("PADDLE_QUANT_ALLREDUCE", "")
        if not mode or mode.strip().lower() in ("0", "off", "false"):
            return None
        from paddle_tpu.quant import allreduce as qar
        m = qar.mode_from_env()
        if m is None:
            return None
        world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1") or "1")
        snap = _metrics_payload() or {}
        counters = snap.get("counters", {})
        out = {"allreduce": qar.wire_bytes(int(n_params or 0),
                                           max(2, world), m),
               "calls": int(counters.get("quant.allreduce_calls", 0)),
               "fallbacks": int(
                   counters.get("quant.allreduce_fallbacks", 0))}
        return out
    except Exception:
        return None


def _error_payload(msg: str) -> dict:
    err = {
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": 0.0, "unit": "tokens/s", "vs_baseline": 0.0,
        "device": "none",
        "error": msg,
        "metrics": _metrics_payload(),
    }
    fleet = _fleet_payload()
    if fleet is not None:
        err["fleet"] = fleet
    slo = _slo_payload()
    if slo is not None:
        err["slo"] = slo
    quant = _quant_payload()
    if quant is not None:
        err["quant"] = quant
    # surface the last committed success so an outage at bench time still
    # points the reader at a real number
    try:
        with open(os.path.join(_HERE, "benchmarks", "BENCH_latest.json")) as f:
            err["last_success"] = json.load(f)
    except (OSError, ValueError):
        pass
    return err


def _driver_budget_s() -> float:
    """Wall budget the driver gives `python bench.py` before killing it
    (BENCH_DRIVER_BUDGET_S overrides). Every internal wait is capped
    strictly below this."""
    return float(os.environ.get("BENCH_DRIVER_BUDGET_S", 2700.0))


def _install_signal_handlers() -> None:
    """SIGTERM/SIGINT/SIGALRM → error JSON, then exit 1. The SIGALRM
    dead-man fires shortly before the driver budget expires, so even a
    wedged device runtime can't produce a JSON-less rc=124 death."""
    import signal

    def die(signum, frame):
        try:
            name = signal.Signals(signum).name
        except ValueError:
            name = str(signum)
        _emit(_error_payload(
            f"killed by {name} before completion — error JSON emitted by "
            "the bench's own signal handler (never die JSON-less)"))
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(1)

    for s in (signal.SIGTERM, signal.SIGINT, signal.SIGALRM):
        try:
            signal.signal(s, die)
        except (ValueError, OSError):
            pass  # non-main thread / exotic platform: best effort
    deadman = float(os.environ.get("BENCH_DEADMAN_S",
                                   max(60.0, _driver_budget_s() - 120.0)))
    if deadman > 0:
        signal.alarm(int(deadman))


def peak_bf16_flops(device) -> float:
    kind = getattr(device, "device_kind", "").lower()
    # order matters: "v5 lite"/"v5e" must match before the bare "v5"
    # (v5p chips report device_kind "TPU v5")
    table = {
        "v5 lite": 197e12, "v5e": 197e12, "v5litepod": 197e12,
        "v5p": 459e12, "v5": 459e12,
        "v4": 275e12, "v3": 123e12, "v6e": 918e12, "v6 lite": 918e12,
    }
    for k, v in table.items():
        if k in kind:
            return v
    raise ValueError(f"no bf16 peak known for device_kind {kind!r}: add it "
                     f"to the table with its source, never assume one")


def _tpu_reachable(timeout_s: int = 240) -> bool:
    """Probe TPU client creation in a child so a wedged runtime can't hang
    the bench process itself. The probe runs a real tiny computation, not
    just device enumeration. NOTE for the benchmark PR: on a local chip the
    child takes the chip for its lifetime, and a parent that already holds
    it makes the child fail — this probe only works from a parent that has
    not touched JAX, which is how main() calls it."""
    import subprocess
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        return False
    try:
        r = subprocess.run(
            [sys.executable, "-c",
             "import jax, sys; import jax.numpy as jnp;\n"
             "sys.exit(1) if jax.default_backend() != 'tpu' else None\n"
             "x = jnp.ones((8, 8)); v = float(jax.device_get((x @ x).sum()))\n"
             "sys.exit(0 if v == 512.0 else 1)"],
            timeout=timeout_s, capture_output=True)
        return r.returncode == 0
    except Exception:
        return False


def _wait_for_tpu(deadline_s: float) -> bool:
    """Bounded retry with exponential backoff. The window now defaults to
    ~300 s TOTAL: a window sized to "most of the driver budget" converts
    an unreachable device into a JSON-less rc=124 kill, while a capped
    probe converts it into a CPU-fallback JSON line that still records the
    outage (probe log + device field).
    Probe attempts are appended to benchmarks/bench_retry_log.txt
    (git-ignored; BENCH_RETRY_LOG overrides) so an exhausted window leaves
    evidence.
    BENCH_TPU_WAIT_S overrides the deadline (0 = single probe), but the
    window is ALWAYS capped strictly below the driver budget — the tail is
    reserved for the bench run + JSON emit."""
    deadline_s = float(os.environ.get("BENCH_TPU_WAIT_S", deadline_s))
    deadline_s = min(deadline_s, max(0.0, _driver_budget_s() - 300.0))
    t0 = time.time()
    attempt = 0
    sleep_s = 15.0
    log_path = os.environ.get(
        "BENCH_RETRY_LOG",
        os.path.join(_HERE, "benchmarks", "bench_retry_log.txt"))

    def _log(line: str) -> None:
        print(line, file=sys.stderr)
        try:
            with open(log_path, "a") as f:
                f.write(f"{time.strftime('%Y-%m-%dT%H:%M:%SZ', time.gmtime())}"
                        f" {line}\n")
        except OSError:
            pass

    while True:
        attempt += 1
        # a single probe can never overshoot what's left of the window
        left = deadline_s - (time.time() - t0)
        probe_t = 240 if deadline_s <= 0 else int(max(10.0, min(240.0, left)))
        if _tpu_reachable(probe_t):
            if attempt > 1:
                _log(f"# tpu reachable after {attempt} probes "
                     f"({time.time() - t0:.0f}s)")
            return True
        elapsed = time.time() - t0
        if elapsed >= deadline_s:
            _log(f"# tpu wait EXHAUSTED: {attempt} probes over "
                 f"{elapsed:.0f}s (window {deadline_s:.0f}s)")
            return False
        _log(f"# tpu probe {attempt} failed ({elapsed:.0f}s elapsed, "
             f"retrying until {deadline_s:.0f}s)")
        time.sleep(min(sleep_s, max(0.0, deadline_s - elapsed)))
        sleep_s = min(sleep_s * 2.0, 120.0)


def _record_latest(payload: dict, suffix: str = "") -> None:
    """Atomically persist every successful bench result to
    benchmarks/BENCH_latest.json (git-ignored; timestamp + git sha +
    device) so a later failure cannot leave the run with no numeric
    artifact."""
    import subprocess
    try:
        sha = subprocess.run(["git", "rev-parse", "HEAD"], cwd=_HERE,
                             capture_output=True, text=True, timeout=10,
                             check=True).stdout.strip()
    except Exception:
        sha = "unknown"
    rec = {
        "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_sha": sha,
        **payload,
    }
    path = os.path.join(_HERE, "benchmarks", f"BENCH_latest{suffix}.json")
    tmp = path + ".tmp"
    try:
        with open(tmp, "w") as f:
            json.dump(rec, f, indent=1)
            f.write("\n")
        os.replace(tmp, path)
    except OSError as e:
        print(f"# could not write BENCH_latest.json: {e}", file=sys.stderr)


def main() -> int:
    # Probe window capped at ~300 s (a window as long as the caller's
    # budget dies JSON-less at rc=124). On exhaustion
    # fall back to the CPU smoke so a bench JSON always lands; strict mode
    # (error JSON + rc 1, the pre-PR-3 behavior) via BENCH_REQUIRE_TPU=1.
    on_tpu = _wait_for_tpu(deadline_s=300.0)
    if not on_tpu:
        if os.environ.get("BENCH_REQUIRE_TPU") == "1":
            _emit(_error_payload(
                "tpu unreachable within the capped probe window — "
                "BENCH_REQUIRE_TPU=1 forbids the CPU fallback"))
            return 1
        print("# tpu unreachable — falling back to the CPU smoke sizing "
              "(device=cpu, vs_baseline=0)", file=sys.stderr)
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    # persistent compile cache: a re-run skips the first compile; placed
    # by JAX_COMPILATION_CACHE_DIR when set (utils/compile_cache.py)
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax.numpy as jnp

    from paddle_tpu.models import LlamaConfig, LlamaTrainStep
    from paddle_tpu.optimizer import AdamW

    dev = jax.devices()[0]
    on_tpu = jax.default_backend() == "tpu"

    size = os.environ.get("BENCH_MODEL", "850m").lower()
    if on_tpu and size == "2b":
        # ~2.1B-param llama (BENCH_MODEL=2b): the scale-proof config
        # — bf16 weights + SR-bf16 Adam moments keep
        # states ~8.4 GB of 16 GB; B sized so activations (dots remat) fit.
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2560, intermediate_size=8192,
            num_hidden_layers=22, num_attention_heads=20,
            num_key_value_heads=20, max_position_embeddings=2048,
            dtype=jnp.bfloat16)
        B, T = int(os.environ.get("BENCH_BATCH", 3)), 2048
        chain, samples = 8, 5
    elif on_tpu:
        # ~850M-param llama on one 16GB v5e chip. bf16 Adam moments halve
        # optimizer HBM (f32 moments cap the batch at 4); B=6 +
        # dots_saveable remat (an earlier builder's choice, not re-measured).
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=14, num_attention_heads=16, num_key_value_heads=16,
            max_position_embeddings=2048, dtype=jnp.bfloat16)
        B, T = int(os.environ.get("BENCH_BATCH", 6)), 2048
        chain, samples = 10, 6
    else:  # CPU smoke sizing (probe-exhaustion fallback / JAX_PLATFORMS=cpu)
        cfg = LlamaConfig.tiny()
        B, T = 4, 64
        chain, samples = 2, 3

    opt = AdamW(learning_rate=3e-4, weight_decay=0.1,
                moment_dtype=jnp.bfloat16)
    step = LlamaTrainStep(cfg, mesh=None, optimizer=opt, remat=True)
    rng = np.random.RandomState(0)
    toks = rng.randint(0, cfg.vocab_size, (B, T)).astype(np.int32)
    labels = np.roll(toks, -1, axis=1)

    n_params = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(step.params))
    embed_params = int(np.prod(step.params["embed_tokens"].shape))

    # warmup / compile
    for _ in range(2):
        loss = step(toks, labels)
    float(jax.device_get(loss))

    from benchmarks._timing import summarize, timed_chain
    times = timed_chain(lambda: step(toks, labels), chain, samples)
    loss = step(toks, labels)
    dt, dt_min, dt_mean = summarize(times)

    tokens_per_sec = B * T / dt
    attn_flops_per_token = 6.0 * cfg.num_hidden_layers * \
        cfg.num_attention_heads * cfg.head_dim * T
    fpt_honest = 6.0 * (n_params - embed_params) + attn_flops_per_token
    fpt_incl_embed = 6.0 * n_params + attn_flops_per_token
    model_flops = fpt_honest * tokens_per_sec
    # the CPU smoke sizing reports no utilization, so it needs no peak
    peak = peak_bf16_flops(dev) if on_tpu else 0.0
    mfu = model_flops / peak if on_tpu else 0.0
    mfu_incl = fpt_incl_embed * tokens_per_sec / peak if on_tpu else 0.0

    result = {
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.45, 4) if on_tpu else 0.0,
        "device": str(getattr(dev, "device_kind", dev)) if on_tpu else "cpu",
        "extra": {
            "mfu": round(mfu, 4),
            "mfu_incl_embed": round(mfu_incl, 4),
            "model_tflops_per_sec": round(model_flops / 1e12, 2),
            "peak_tflops": round(peak / 1e12, 1),
            "params": n_params,
            "batch": B, "seq": T,
            "step_ms": round(dt * 1e3, 2),
            "step_ms_min": round(dt_min * 1e3, 2),
            "step_ms_mean": round(dt_mean * 1e3, 2),
            "chain": chain, "samples": samples,
            "device": str(getattr(dev, "device_kind", dev)),
            "model": size,
            "loss": float(jax.device_get(loss)),
        },
        "metrics": _metrics_payload(),
    }
    fleet = _fleet_payload()
    if fleet is not None:
        result["fleet"] = fleet
    slo = _slo_payload()
    if slo is not None:
        result["slo"] = slo
    quant = _quant_payload(n_params)
    if quant is not None:
        result["quant"] = quant
    if on_tpu:
        # non-default sizes record to their own file: the canonical 850M
        # BENCH_latest.json must not be clobbered by a 2b scale-proof run
        _record_latest(result, suffix="" if size == "850m" else f"_{size}")
    _emit(result)
    return 0


if __name__ == "__main__":
    _install_signal_handlers()
    try:
        rc = main()
    except SystemExit:
        raise
    except BaseException as e:  # never die JSON-less, whatever happened
        import traceback
        traceback.print_exc()
        _emit(_error_payload(f"bench crashed: {type(e).__name__}: {e}"))
        rc = 1
    import signal as _signal
    _signal.alarm(0)  # bench is done; disarm the dead-man
    sys.exit(rc)
