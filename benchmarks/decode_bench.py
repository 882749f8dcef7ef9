"""KV-cache decode throughput (VERDICT r2 next #2; paged mode PR 3).

Dense mode (default) times the compiled prefill+scan generate
(models/llama_decode.py) and prints one JSON line with decode tokens/s.
The whole generate is ONE executable; sync via np.asarray of the result.

    python benchmarks/decode_bench.py [B] [PROMPT] [NEW]

Paged mode serves a mixed-length workload through the paged
ContinuousBatcher (inference/serving.py + models/llama_paged.py) and emits
the two numbers the paged design is FOR:

  * kv_read_bytes_per_token — the per-token K/V bytes the decode attention
    actually gathers (page bucket × page size), next to the dense
    worst-case (max_len) it replaces;
  * executables — compiled-program inventory (one burst per page bucket +
    one prefill per prompt bucket), read straight off the jit caches, so
    the O(buckets) bound is a measured fact, not a claim.

    python benchmarks/decode_bench.py --paged [N_REQ] [MAX_BATCH] [BURST]

On CPU both modes drop to the tiny config automatically (the 850M flagship
sizing stays TPU-only) — that is what the tier-1 smokes
(tests/test_serving_paged.py, tests/test_speculative.py) run to pin
the compile-count bounds. The JSON line is emitted on EVERY exit path
(bench contract): failures print an ``error`` payload before re-raising.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _flagship_or_tiny(on_tpu, jnp):
    from paddle_tpu.models.llama import LlamaConfig
    if on_tpu:
        return LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=14, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=2048,
            dtype=jnp.bfloat16), 850
    return LlamaConfig.tiny(num_hidden_layers=2), 0


def _dense_main(args) -> dict:
    B = int(args[0]) if len(args) > 0 else 1
    prompt = int(args[1]) if len(args) > 1 else 128
    new = int(args[2]) if len(args) > 2 else 128

    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.llama import llama_init_params
    from paddle_tpu.models.llama_decode import llama_generate

    on_tpu = jax.default_backend() == "tpu"
    cfg, params_m = _flagship_or_tiny(on_tpu, jnp)
    if not on_tpu:
        prompt, new = min(prompt, 32), min(new, 16)
    params = llama_init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (B, prompt)).astype(np.int32))

    t0 = time.time()
    out = llama_generate(params, toks, cfg, new)
    np.asarray(out)
    compile_s = time.time() - t0

    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        out = llama_generate(params, toks, cfg, new)
        np.asarray(out)
        times.append(time.perf_counter() - t0)

    dt = float(np.median(times))
    return {
        "metric": "llama_decode_tokens_per_sec",
        "config": {"B": B, "prompt": prompt, "new_tokens": new,
                   "params_m": params_m},
        "total_ms_median": round(dt * 1e3, 1),
        "decode_tokens_per_sec": round(B * new / dt, 1),
        "ms_per_token": round(dt * 1e3 / new, 2),
        "compile_s": round(compile_s, 1),
        "device": str(getattr(jax.devices()[0], "device_kind", "cpu")),
    }


def ragged_read_bytes(cfg, reqs, page_size):
    """(page-granular mean, exact-live mean) K/V bytes per emitted token
    for a serve of `reqs` [(prompt, max_new), ...] that reads live pages
    only (the decode kernel): token t of a request reads
    ceil((t+1)/page_size) pages; the HBM roofline reads exactly t+1 rows.
    The bucket-width bill the gather pays does not apply to the kernel's
    per-page copies."""
    from paddle_tpu.inference.paging import pages_for
    from paddle_tpu.models.llama_paged import paged_kv_bytes_per_token
    row_bytes = paged_kv_bytes_per_token(cfg, 1, 1)  # one K+V row, all layers
    rows_paged = rows_exact = ntok = 0
    for prompt, m in reqs:
        t0 = len(prompt)
        for t in range(t0, t0 + m):
            rows_paged += pages_for(t + 1, page_size) * page_size
            rows_exact += t + 1
            ntok += 1
    ntok = max(ntok, 1)
    return row_bytes * rows_paged // ntok, row_bytes * rows_exact // ntok


def _paged_main(args) -> dict:
    n_req = int(args[0]) if len(args) > 0 else 16
    max_batch = int(args[1]) if len(args) > 1 else 8
    burst = int(args[2]) if len(args) > 2 else 16

    import jax
    import jax.numpy as jnp
    from paddle_tpu.inference import ContinuousBatcher
    from paddle_tpu.models.llama import llama_init_params
    from paddle_tpu.models.llama_paged import (
        llama_paged_decode_burst, llama_paged_prefill_slot,
        paged_kv_bytes_per_token)

    on_tpu = jax.default_backend() == "tpu"
    cfg, params_m = _flagship_or_tiny(on_tpu, jnp)
    if on_tpu:
        max_len, buckets, page_size = 512, (64, 128, 256), 64
        lens, budgets = [24, 57, 100, 190], [32, 64, 96]
    else:
        max_len, buckets, page_size = 96, (16, 32), 8
        lens, budgets = [5, 11, 23, 30], [4, 8, 12]
        n_req = min(n_req, 8)
        max_batch = min(max_batch, 4)
    params = llama_init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    reqs = [(rng.randint(1, cfg.vocab_size, int(n)).tolist(), int(m))
            for n, m in zip(rng.choice(lens, n_req),
                            rng.choice(budgets, n_req))]
    total_new = sum(m for _, m in reqs)

    def serve(kv_dtype="", spec=False):
        # kv_dtype="" pins the baseline passes to full-precision pages
        # even when PADDLE_SERVE_KV_DTYPE is set fleet-wide — the quant
        # sub-object below is a COMPARISON, not a global override; the
        # prefix-cache and spec-decode envs are pinned off the baselines
        # for the same reason (their sub-objects own those comparisons)
        eng = ContinuousBatcher(cfg, params, max_batch=max_batch,
                                max_len=max_len, prompt_buckets=buckets,
                                burst=burst, page_size=page_size,
                                kv_dtype=kv_dtype,
                                prefix_cache_pages=0, spec_decode=spec)
        rids = [eng.add_request(p, max_new_tokens=m) for p, m in reqs]
        out = eng.run()
        return eng, [out[r] for r in rids]

    serve()  # compile pass
    t0 = time.perf_counter()
    eng, gather_out = serve()
    dt = time.perf_counter() - t0

    buckets_used = eng.stats["page_buckets_used"]
    worst_bucket = max(buckets_used) if buckets_used else 0
    dense_pages = (max_len - 1) // page_size + 1
    payload = {
        "metric": "llama_paged_decode_tokens_per_sec",
        "value": round(total_new / dt, 1),
        "unit": "tokens/s",
        "config": {"requests": n_req, "max_batch": max_batch,
                   "burst": burst, "max_len": max_len,
                   "page_size": page_size, "params_m": params_m,
                   "prompt_buckets": list(buckets),
                   "page_buckets": list(eng._page_buckets)},
        "page_buckets_used": buckets_used,
        "bursts_run": eng.stats["bursts"],
        # per-token K/V bytes the attention gathers at the widest bucket
        # this workload hit, vs the dense layout's always-max_len read
        "kv_read_bytes_per_token": paged_kv_bytes_per_token(
            cfg, worst_bucket, page_size),
        "kv_read_bytes_per_token_dense": paged_kv_bytes_per_token(
            cfg, dense_pages, page_size),
        # measured executable inventory: the O(buckets) bound as a fact
        "executables": {
            "paged_burst": llama_paged_decode_burst._cache_size(),
            "paged_prefill": llama_paged_prefill_slot._cache_size(),
        },
        "device": str(getattr(jax.devices()[0], "device_kind", "cpu")),
    }

    # ---- quantized KV pages (ISSUE 10): same workload with int8/fp8
    # pages — the sub-object the capacity claim is audited from:
    # bytes/token vs bf16 pages, pages-per-budget capacity ratio, and
    # the greedy token-agreement rate vs the full-precision serve.
    from benchmarks._quant_report import bench_kv_dtype, kv_quant_subobject
    kv_dt = bench_kv_dtype()
    _, quant_out = serve(kv_dtype=kv_dt)
    payload["quant"] = kv_quant_subobject(cfg, page_size, worst_bucket,
                                          kv_dt, gather_out, quant_out)

    # ---- speculative decoding (ISSUE 14): PADDLE_SPEC_DECODE=1 reruns
    # the workload with draft-propose + one-launch verify on the GATHER
    # engine (the decode bench's baseline path) and lands the `spec`
    # sub-object; null otherwise — off is distinguishable from
    # zero-accepts.
    from benchmarks._spec_report import spec_enabled, spec_subobject
    from paddle_tpu.observability import metrics as _metrics
    payload["spec"] = None
    if spec_enabled():
        serve(spec=True)  # compile pass
        ar0 = _metrics.histogram("serve.spec_accept_rate").stats()["count"]
        t0 = time.perf_counter()
        seng, spec_out = serve(spec=True)
        spec_s = time.perf_counter() - t0
        payload["spec"] = spec_subobject(
            seng, total_new, spec_s=spec_s, plain_s=dt,
            parity=spec_out == gather_out, accept_hist_count0=ar0)
    return payload


def main(argv=None) -> dict:
    argv = sys.argv[1:] if argv is None else list(argv)
    paged = "--paged" in argv
    args = [a for a in argv if not a.startswith("--")]
    try:
        payload = _paged_main(args) if paged else _dense_main(args)
    except BaseException as e:  # bench contract: never exit JSON-less
        print(json.dumps({"metric": "llama_paged_decode_tokens_per_sec"
                          if paged else "llama_decode_tokens_per_sec",
                          "error": f"{type(e).__name__}: {e}"}))
        raise
    print(json.dumps(payload))
    return payload


if __name__ == "__main__":
    main()
