"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

    python chip_smoke.py              one TPU chip: phase `train`, then `serve`
    python chip_smoke.py --chips 4    four chips: the mesh train step and the
                                      single-device run it is compared with
    python chip_smoke.py --rehearse   tiny sizes on whatever backend JAX has;
                                      never passes off the TPU

Both phases run Llama-2-7B at its published widths (hidden 4096, FFN 11008,
32 heads x 128, vocab 32000, bf16) with random weights made from --seed.
Only depth is cut, to what one 16 GB chip holds; every layer is the same
kind, so any depth is a whole period.

One process, JAX imported once, no child that needs the chip. Each phase
prints one JSON object; the last line of stdout is
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
when every phase passed on a TPU, and {"ok": false, ...} with a non-zero
exit code otherwise. Step and request times on the phase lines are
information seen during bring-up, not benchmark results.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time
import traceback

MODEL = "Llama-2-7B (LlamaConfig.llama2_7b)"
TRAIN_LAYERS, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 2, 2048, 16
SERVE_LAYERS, SERVE_F32_LAYERS = 8, 2
SERVE_MAX_BATCH, SERVE_MAX_LEN, SERVE_PROMPT_BUCKETS = 8, 1024, (128, 256)
SERVE_POOL_BYTES = 8 << 30
# (prompt length, new tokens): ten requests over both prompt buckets, more
# than max_batch so that the queue, admission and slot reuse are on the path
SERVE_REQUESTS = ((40, 16), (100, 24), (120, 8), (128, 16), (130, 24),
                  (200, 8), (250, 16), (256, 24), (77, 8), (180, 16))
# a served token that differs from llama_generate's is held to the
# benchmark's measure: its logit within this of the best at its position
# (perfbench/limits/internlm2-1.8b.longctx-batch.json: served_logit_gap_max)
SERVE_LOGIT_GAP_LIMIT = 0.25
MESH_AXES, MESH_SHAPE = ("dp", "tp"), (2, 2)
MESH_REL_TOL = 2e-2     # the tolerance __graft_entry__ holds virtual meshes to


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def device_info() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def memory(dev) -> dict:
    stats = dev.memory_stats() or {}    # the CPU backend reports none
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}


def width_summary(cfg) -> dict:
    import jax.numpy as jnp
    return {"model": MODEL, "hidden": cfg.hidden_size,
            "ffn": cfg.intermediate_size, "heads": cfg.num_attention_heads,
            "kv_heads": cfg.num_key_value_heads, "head_dim": cfg.head_dim,
            "vocab": cfg.vocab_size, "layers": cfg.num_hidden_layers,
            "dtype": jnp.dtype(cfg.dtype).name}


def custom_calls(lowered) -> int:
    """Pallas kernels in a lowered program: each is one tpu_custom_call."""
    return lowered.as_text().count("tpu_custom_call")


def model_config(rehearse: bool, layers: int, **kw):
    from paddle_tpu.models import LlamaConfig
    if rehearse:
        return LlamaConfig.tiny(num_hidden_layers=2,
                                max_position_embeddings=2048, **kw)
    return LlamaConfig.llama2_7b(num_hidden_layers=layers, **kw)


# ------------------------------------------------------------------ train

def train_inputs(args):
    """The train phases' model and fresh batches through the input
    pipeline: a seeded Zipf-Markov corpus on disk, cut by TokenDataLoader
    (native feeder when it builds). Returns (cfg, batches, sizes)."""
    from paddle_tpu.io.token_loader import (TokenDataLoader, synthetic_corpus,
                                            write_token_file)
    cfg = model_config(args.rehearse, TRAIN_LAYERS)
    batch, seq, steps = ((2, 128, 4) if args.rehearse
                         else (TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS))
    n_tokens = max(8 * steps * batch * (seq + 1), 65536)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "corpus.u16")
        write_token_file(path, synthetic_corpus(n_tokens, cfg.vocab_size,
                                                args.seed))
        loader = TokenDataLoader(path, batch, seq, seed=args.seed)
        try:
            batches = [next(loader) for _ in range(steps)]
        finally:
            loader.close()
    return cfg, batches, {"batch": batch, "seq": seq, "steps": steps,
                          "native_feeder": bool(loader._native)}


def run_trainer(cfg, mesh, batches, seed, on_tpu):
    """A few steps of LlamaTrainStep; returns (losses, facts, trainer)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import LlamaTrainStep
    from paddle_tpu.optimizer import AdamW

    t0 = time.perf_counter()
    step = LlamaTrainStep(
        cfg, mesh=mesh, remat=True, seed=seed,
        optimizer=AdamW(learning_rate=3e-4, weight_decay=0.1,
                        moment_dtype=jnp.bfloat16))
    jax.block_until_ready(step.params)
    init_s = time.perf_counter() - t0

    tok0 = jnp.asarray(batches[0][0], jnp.int32)
    sh = step.data_sharding(2)
    if sh is not None:
        tok0 = jax.device_put(tok0, sh)
    n_kernels = custom_calls(step._jitted.lower(
        step._params, step._opt_state, tok0, tok0, jnp.float32(3e-4),
        jnp.int32(1)))
    if on_tpu and n_kernels < 3:
        raise AssertionError(
            f"the Pallas flash kernel is not in the train step: "
            f"{n_kernels} tpu_custom_call (forward + two backward expected)")

    losses, times = [], []
    for tokens, labels in batches:
        t0 = time.perf_counter()
        losses.append(float(jax.block_until_ready(step(tokens, labels))))
        times.append(round(time.perf_counter() - t0, 4))
    if not all(l == l and abs(l) != float("inf") for l in losses):
        raise AssertionError(f"non-finite losses {losses}")
    # fresh batches make single steps noisy (a window that overlaps an
    # earlier one is half memorized): compare the ends of the run
    k = max(1, len(losses) // 4)
    if not sum(losses[-k:]) < sum(losses[:k]):
        raise AssertionError(f"losses did not fall: {losses}")
    facts = {"init_s": round(init_s, 2), "first_step_s_with_compile": times[0],
             "step_s": times[1:], "losses": [round(l, 4) for l in losses],
             "flash_tpu_custom_calls": n_kernels}
    return losses, facts, step


def phase_train(args, dev) -> dict:
    cfg, batches, sizes = train_inputs(args)
    _, facts, step = run_trainer(cfg, None, batches, args.seed,
                                 dev["platform"] == "tpu")
    del step
    return {"config": {**width_summary(cfg), **sizes,
                       "optimizer": "AdamW bf16 moments", "remat": True},
            "reduced": [f"depth 32 -> {cfg.num_hidden_layers} layers: bf16 "
                        f"params + grads + two bf16 AdamW moments + remat "
                        f"activations at B={sizes['batch']} T={sizes['seq']} "
                        f"fit one 16 GB chip"],
            **facts}


# ------------------------------------------------------------------ serve

def make_requests(cfg, seed, rehearse):
    import numpy as np
    rng = np.random.RandomState(seed)
    # each request compiles its own llama_generate: a rehearsal takes four
    spec = SERVE_REQUESTS[::3] if rehearse else SERVE_REQUESTS
    return [(rng.randint(1, cfg.vocab_size, n).astype(np.int32).tolist(), m)
            for n, m in spec]


def serve(cfg, params, requests, on_tpu, **engine_kw):
    """Serve `requests` through the default ContinuousBatcher; returns each
    request's greedy tokens and the facts of the pass."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.inference import ContinuousBatcher
    from paddle_tpu.models.llama_paged import llama_paged_prefill_slot
    from paddle_tpu.observability import metrics

    eng = ContinuousBatcher(cfg, params,
                            max_batch=SERVE_MAX_BATCH, max_len=SERVE_MAX_LEN,
                            prompt_buckets=SERVE_PROMPT_BUCKETS, burst=8,
                            **engine_kw)
    ps = eng.page_size
    bucket = SERVE_PROMPT_BUCKETS[0]
    # Llama-2-7B's pool (32 KV heads x 128, bf16 or float32) is one the
    # decode kernel takes: on a TPU the burst must read through it
    if on_tpu and eng.stats["kv_read"] != "kernel":
        raise AssertionError(f"the paged engine's decode steps read "
                             f"through {eng.stats['kv_read']!r}, not "
                             f"the kernel")
    kernel = {"kv_read": eng.stats["kv_read"],
              "flash_tpu_custom_calls_in_prefill": custom_calls(
        llama_paged_prefill_slot.lower(
            params, eng._cache, jnp.zeros(bucket, jnp.int32),
            jnp.zeros(-(-bucket // ps), jnp.int32), jnp.int32(1),
            jax.random.PRNGKey(0), config=cfg, temperature=0.0, top_k=0,
            dequant=None, kv_dtype=None,
            # the page writes as a loop: every kernel counted is flash
            kv_read="gather"))}
    if on_tpu and kernel["flash_tpu_custom_calls_in_prefill"] < 1:
        raise AssertionError("the Pallas flash kernel is not in the "
                             "bucketed-prefill program")

    t0 = time.perf_counter()
    rids = [eng.add_request(p, max_new_tokens=m) for p, m in requests]
    out = eng.run()
    serve_s = time.perf_counter() - t0
    for rid, (_, m) in zip(rids, requests):
        if len(out[rid]) != m:
            raise AssertionError(f"request {rid} asked for {m} tokens, got "
                                 f"{len(out[rid])}")
    gauge = metrics.snapshot()["gauges"].get("serve.pages_in_use")
    if eng.pages_in_use != 0 or gauge != 0:
        raise AssertionError(f"pages leaked after the drain: allocator "
                             f"{eng.pages_in_use}, serve.pages_in_use {gauge}")
    facts = {"requests": len(requests),
             "tokens": sum(m for _, m in requests),
             "page_size": ps, "num_pages": eng._alloc.num_pages,
             "serve_s_with_compile": round(serve_s, 2), **kernel,
             **{k: eng.stats[k] for k in ("bursts", "decode_steps",
                                          "prefills", "max_concurrent")}}
    return [list(out[rid]) for rid in rids], facts


def reference_tokens(cfg, params, requests):
    """Per-request llama_generate on the same device."""
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.models.llama_decode import llama_generate
    return [np.asarray(llama_generate(params, jnp.asarray([p], jnp.int32),
                                      cfg, m, temperature=0.0))[0].tolist()
            for p, m in requests]


def agreement(a, b) -> str:
    same = sum(x == y for ta, tb in zip(a, b) for x, y in zip(ta, tb))
    return f"{same}/{sum(len(t) for t in a)}"


def served_logit_gap(cfg, params, requests, served, ref) -> float:
    """The widest gap by which a served token's logit lies below the best
    at its position, over the requests whose tokens differ from `ref`: one
    plain forward over prompt + served tokens each (no cache, no kernel in
    the read), padded to one length so that one program serves them all.
    A near-tie that a changed summation order flips reads a few hundredths
    here; a wrong read reads whole units."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.models.llama import llama_forward

    width = -(-max(len(p) + m for p, m in requests) // 128) * 128
    # params as an argument: closed over, 3.8 GB of weights would be
    # lowered as constants of the program (40 GiB of host memory on the chip)
    forward = jax.jit(lambda p, toks: llama_forward(p, toks, cfg)[0])
    gap = 0.0
    for (prompt, _), out, want in zip(requests, served, ref):
        if out == want:
            continue
        toks = np.zeros((1, width), np.int32)
        toks[0, :len(prompt) + len(out)] = list(prompt) + list(out)
        logits = np.asarray(forward(params, jnp.asarray(toks))[0],
                            np.float32)
        at = logits[len(prompt) - 1:len(prompt) - 1 + len(out)]
        gap = max(gap, float((at.max(axis=-1)
                              - at[np.arange(len(out)), out]).max()))
    return gap


def serve_against_reference(cfg, params, requests, on_tpu, **engine_kw):
    """The default paged layout against per-request llama_generate.
    Returns the facts and whether the two agree on every token."""
    paged, facts_p = serve(cfg, params, requests, on_tpu, **engine_kw)
    gc.collect()
    t0 = time.perf_counter()
    ref = reference_tokens(cfg, params, requests)
    facts_p["tokens_equal_llama_generate"] = agreement(paged, ref)
    equal = paged == ref
    facts = {"paged": facts_p,
             "reference_s_with_compile": round(time.perf_counter() - t0, 2)}
    if not equal:
        # the kernel's online softmax sums in another order than
        # llama_generate's full-width one: hold what differs to the
        # benchmark's measure instead of to equality
        facts["served_logit_gap_max"] = served_logit_gap(
            cfg, params, requests, paged, ref)
    return facts, equal


def phase_serve(args, dev) -> dict:
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.llama import llama_init_params

    on_tpu = dev["platform"] == "tpu"
    cfg = model_config(args.rehearse, SERVE_LAYERS,
                       **({"dtype": jnp.bfloat16} if args.rehearse else {}))
    requests = make_requests(cfg, args.seed, args.rehearse)
    params = llama_init_params(cfg, jax.random.PRNGKey(args.seed))
    pool = {} if args.rehearse else {"pool_hbm_bytes": SERVE_POOL_BYTES}
    facts, equal = serve_against_reference(cfg, params, requests, on_tpu,
                                            **pool)
    result = {"config": {**width_summary(cfg),
                         "prompt_lens": [len(p) for p, _ in requests],
                         "prompt_buckets": list(SERVE_PROMPT_BUCKETS),
                         "max_batch": SERVE_MAX_BATCH,
                         "max_len": SERVE_MAX_LEN},
              "reduced": [f"depth 32 -> {cfg.num_hidden_layers} layers: bf16 "
                          f"weights plus an {SERVE_POOL_BYTES >> 30} GiB KV "
                          f"page pool fit one 16 GB chip"],
              "bf16": facts, "compared_in": "bfloat16"}
    if not facts.get("served_logit_gap_max", 0.0) < SERVE_LOGIT_GAP_LIMIT:
        raise AssertionError(
            f"bfloat16 greedy tokens differ from llama_generate by more "
            f"than a near-tie (limit {SERVE_LOGIT_GAP_LIMIT}): {facts}")
    if not equal or args.rehearse:      # a rehearsal walks both passes
        # bf16 near-ties between random-weight logits flip a greedy argmax
        # between two correct programs; the equality tier-1 pins on the CPU
        # is then decided in float32 at a depth that fits
        del params
        gc.collect()
        cfg32 = model_config(args.rehearse, SERVE_F32_LAYERS,
                             dtype=jnp.float32)
        params32 = llama_init_params(cfg32, jax.random.PRNGKey(args.seed))
        with jax.default_matmul_precision("highest"):
            facts32, equal32 = serve_against_reference(cfg32, params32,
                                                       requests, on_tpu)
        result.update(float32=facts32, compared_in="float32",
                      float32_config=width_summary(cfg32))
        result["reduced"].append(
            f"token equality with llama_generate decided in "
            f"float32 at {cfg32.num_hidden_layers} layers (a token that "
            f"still differs there is held to served_logit_gap_max < "
            f"{SERVE_LOGIT_GAP_LIMIT})")
        if not (facts32.get("served_logit_gap_max", 0.0)
                < SERVE_LOGIT_GAP_LIMIT):
            raise AssertionError(
                f"float32 greedy tokens differ from llama_generate by more "
                f"than a near-tie (limit {SERVE_LOGIT_GAP_LIMIT}): "
                f"{facts32}")
    return result


# ------------------------------------------------------------- four chips

def phase_mesh_train(args, dev) -> dict:
    """The sharded train step on four devices against the single-device
    run of the same model on the same batches."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from paddle_tpu.distributed.process_mesh import ProcessMesh

    on_tpu = dev["platform"] == "tpu"
    devices = jax.devices()
    if len(devices) < 4:
        raise AssertionError(f"--chips 4 needs four devices, JAX has "
                             f"{len(devices)}")
    cfg, batches, sizes = train_inputs(args)
    single, facts1, step = run_trainer(cfg, None, batches, args.seed, on_tpu)
    del step
    gc.collect()

    mesh = ProcessMesh(Mesh(np.asarray(devices[:4]).reshape(MESH_SHAPE),
                            MESH_AXES))
    sharded, facts4, step = run_trainer(cfg, mesh, batches, args.seed, on_tpu)
    # the parameters must really span the four devices
    spans = {}
    for name in ("wq", "wo", "w_gate", "w_down"):     # sharded on dp AND tp
        p = step.params[name]
        shards = p.addressable_shards
        spans[name] = {"spec": str(p.sharding.spec),
                       "devices": len({s.device for s in shards}),
                       "shard_shape": list(shards[0].data.shape),
                       "shape": list(p.shape)}
        if spans[name]["devices"] != 4 or \
                4 * shards[0].data.size != p.size:
            raise AssertionError(f"{name} does not span four devices: "
                                 f"{spans[name]}")
    per_device = [memory(d)["bytes_in_use"] for d in devices[:4]]
    if on_tpu and not all(b and b > 0 for b in per_device):
        raise AssertionError(f"a device holds nothing: {per_device}")
    del step

    rel = np.abs(np.asarray(sharded) - np.asarray(single)) \
        / np.maximum(np.abs(np.asarray(single)), 1e-6)
    if not rel.max() < MESH_REL_TOL:
        raise AssertionError(f"mesh trajectory left the single-device one: "
                             f"rel {rel.tolist()} > {MESH_REL_TOL}")
    return {"config": {**width_summary(cfg), **sizes,
                       "mesh": dict(zip(MESH_AXES, MESH_SHAPE))},
            "reduced": [f"depth 32 -> {cfg.num_hidden_layers} layers: the "
                        f"single-device run it is compared with must fit one "
                        f"16 GB chip"],
            "single": facts1, "mesh": facts4,
            "max_rel_diff": float(rel.max()), "rel_tolerance": MESH_REL_TOL,
            "param_spans": spans, "bytes_in_use_per_device": per_device}


# ------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; never prints the "
                         "passing line off the TPU")
    args = ap.parse_args(argv)

    dev, failed = None, None
    try:
        import jax

        from paddle_tpu.utils.compile_cache import enable_compile_cache
        cache_dir = enable_compile_cache()
        dev = device_info()
        emit({"phase": "start", **dev, "chips": args.chips,
              "rehearse": args.rehearse, "seed": args.seed,
              "compile_cache": cache_dir, "jax": jax.__version__})
        if not args.rehearse:
            if dev["platform"] != "tpu":
                raise RuntimeError(f"no TPU: JAX found platform "
                                   f"{dev['platform']!r}")
            if dev["count"] != args.chips:
                raise RuntimeError(f"--chips {args.chips} but JAX has "
                                   f"{dev['count']} devices")
        phases = ((("mesh_train", phase_mesh_train),) if args.chips == 4
                  else (("train", phase_train), ("serve", phase_serve)))
        for name, fn in phases:
            t0 = time.perf_counter()
            try:
                facts = fn(args, dev)
            except Exception:
                failed = name
                raise
            if args.rehearse:   # tiny widths: nothing below is the model's
                facts = {"rehearsal": True, **facts}
            emit({"phase": name, "ok": True, **dev, **facts,
                  "phase_s": round(time.perf_counter() - t0, 2),
                  **memory(jax.devices()[0])})
            gc.collect()
        if dev["platform"] != "tpu":
            raise RuntimeError("rehearsal: every phase ran, but not on a TPU")
    except Exception as e:
        traceback.print_exc()
        sys.stderr.flush()
        emit({"ok": False, "device": dev, "failed_phase": failed,
              "error": f"{type(e).__name__}: {e}"[:2000]})
        return 1
    emit({"ok": True, "device": dev})
    return 0


if __name__ == "__main__":
    sys.exit(main())
