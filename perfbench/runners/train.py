"""Traffic kind `train`: the compiled train step on loader batches.

Set-up builds ONE train step of the configuration's family
(perfbench/families/), with weights made by the benchmark from the seed in
it, and drives it through its first `check_steps` steps through the
window's own call (`step(tokens, labels)` on batches from the running
TokenDataLoader); the same object then runs the window. After the window,
with the peak read and the program's state freed, the plain reference
follows those steps in float32 and the readings are compared (check.py).
"""
from __future__ import annotations

import contextlib
import gc
import math
import os
import tempfile

import numpy as np

from .. import check, families, faults, gen, harness as hs
from ..weights import make_weights


def build_mesh(traffic: dict, chips: int):
    """The mix's mesh (axes, shape) over the first `chips` devices; one
    chip runs without a mesh."""
    spec = traffic.get("mesh")
    if chips == 1 or not spec:
        return None
    import jax
    from jax.sharding import Mesh
    from paddle_tpu.distributed.process_mesh import ProcessMesh
    devs = np.asarray(jax.devices()[:chips]).reshape(spec["shape"])
    return ProcessMesh(Mesh(devs, tuple(spec["axes"])))


@contextlib.contextmanager
def open_loader(cfg: dict, job: dict, seed: int):
    """The cell's feed: the program's TokenDataLoader over the benchmark's
    seeded corpus, written to a temporary file for as long as it is read.
    Yields (loader, corpus)."""
    from paddle_tpu.io.token_loader import TokenDataLoader, write_token_file
    with tempfile.TemporaryDirectory(prefix="perfbench_corpus_") as tmp:
        path = os.path.join(tmp, "corpus.u16")
        corpus = gen.synthetic_corpus(job["corpus_tokens"], cfg["vocab_size"],
                                      seed)
        write_token_file(path, corpus)
        loader = TokenDataLoader(path, job["batch"], job["seq_len"], seed=seed)
        try:
            yield loader, corpus
        finally:
            loader.close()


def run_control(ctx: dict, n_check: int) -> dict:
    """The control's readings need no window: the reference, and the same
    reference in the next lower precision put in the program's place."""
    cfg, job, seed = ctx["cfg"], ctx["traffic"]["job"], ctx["seed"]
    with open_loader(cfg, job, seed) as (loader, corpus):
        batches = [next(loader) for _ in range(n_check)]
    ref = check.reference_trajectory(cfg, job, seed, batches, "f32")
    ctl = check.reference_trajectory(cfg, job, seed, batches, ctx["control"])
    control = hs.Checks(ctx["limits"]["limits"])
    check.compare_training(control, ctl, ref,
                           check.fresh_steps(corpus, batches))
    return {"control": control, "checks": None, "e2e": {}, "record": {},
            "attempted": 0, "failed": 0, "setup_s": hs.now() - ctx["t0"],
            "memory_peak_bytes": hs.memory_peak_bytes(ctx["cell"]["chips"]),
            "trace_dir": None}


def run(ctx: dict) -> dict:
    import jax

    cfg, traffic, seed = ctx["cfg"], ctx["traffic"], ctx["seed"]
    job, chips = traffic["job"], ctx["cell"]["chips"]
    B, T = job["batch"], job["seq_len"]
    n_check = int(ctx["limits"].get("check_steps", 3))
    if ctx["control"]:
        return run_control(ctx, n_check)

    fam = families.of(cfg)
    with open_loader(cfg, job, seed) as (loader, corpus):
        step = fam.train_step(cfg, job, build_mesh(traffic, chips),
                              lambda: make_weights(cfg, seed))
        call = faults.plant("train", ctx.get("fault"), step, cfg)

        # the first steps, through the window's own call and feed
        batches, prog = [], {"loss": []}
        for i in range(n_check):
            tokens, labels = next(loader)
            batches.append((tokens, labels))
            prog["loss"].append(float(jax.block_until_ready(
                call(tokens, labels))))
            if i == 0:
                prog["grad_norm"] = check.first_grad_norms(
                    step.resilience_state()["opt_state"],
                    job["optimizer"]["beta1"], fam)
            if i == min(1, n_check - 1):
                prog["change_norm"] = check.change_norms(
                    step.params, make_weights(cfg, seed), fam)

        record = {"step_t": [], "data_wait_s": 0.0, "batch": B, "seq_len": T,
                  "chips": chips}
        losses = []
        counter = hs.CompileCounter()
        setup_s = hs.now() - ctx["t0"]
        with hs.traced_window(ctx["trace"]) as trace_dir, counter:
            t_open = hs.now()
            while hs.now() - t_open < ctx["seconds"]:
                ta = hs.now()
                with hs.span("next_batch"):
                    tokens, labels = next(loader)
                record["data_wait_s"] += hs.now() - ta
                with hs.span("train_step"):
                    losses.append(jax.block_until_ready(
                        call(tokens, labels)))
                record["step_t"].append(hs.now() - t_open)
            t_close = hs.now()
        record["window_s"] = t_close - t_open
        losses = [float(l) for l in losses]
        steps = len(losses)
        peak = hs.memory_peak_bytes(chips)
        native = bool(loader._native)
        fresh = check.fresh_steps(corpus, batches)
        del corpus
    hs.say({"window_s": record["window_s"], "steps": steps,
            "compilations_in_window": counter.count,
            "native_feeder": native, "setup_s": setup_s,
            "first_losses": prog["loss"], "last_loss": losses[-1]})

    del step, call
    gc.collect()
    t_ref = hs.now()
    ref = check.reference_trajectory(cfg, job, seed, batches, "f32")
    hs.say({"reference_s": hs.now() - t_ref})
    checks = hs.Checks(ctx["limits"]["limits"])
    check.compare_training(checks, prog, ref, fresh)
    checks.add("compilations_in_window", counter.count)
    return {"checks": checks, "setup_s": setup_s,
            "e2e": {"train_tokens_per_s": steps * B * T / record["window_s"]},
            "record": record, "attempted": steps,
            "failed": sum(not math.isfinite(l) for l in losses),
            "memory_peak_bytes": peak, "trace_dir": trace_dir}
