"""The flash kernels alone on the chip: microseconds a call and a block.

    chiprun -- python3 tools/flash_microbench.py [--block-q 512,1024] [--block-k 512]

Each kernel of ``ops/flash_attention.py`` is run by itself, jitted, with its
arguments passed (not closed over: XLA folds constants), at the five
geometries the benchmark's cells run (PERF.md section 6, PR 31): the train
cell's ``[2, 2048, 32, 128]`` forward and gradient, the serving cells'
prefill buckets forward only; bf16, causal. The time is the kernel's own
device time, read from a profiler trace by the name its ``pallas_call``
gives it, which is how the cells' ``kernel.flash_*_roofline.train`` read it:
the transposes around the kernel are XLA's operations and are not in it.

"A block" is one 512 x 512 tile pair of the causal set (T=2048: 10 of 16 a
head), whatever tiles the kernel really uses, so that numbers of different
tile shapes compare; its two forward products need 0.68 us at 197 TFLOP/s.

Needs a TPU: off the chip there is no device plane in the trace and every
reading is empty. One JSON line a reading on stdout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (name, [B, T, H, D], gradient too)
GEOMETRIES = [
    ("train", (2, 2048, 32, 128), True),
    ("internlm2_1024", (1, 1024, 16, 128), False),
    ("internlm2_1536", (1, 1536, 16, 128), False),
    ("hybrid_2048", (1, 2048, 30, 128), False),
    ("hybrid_3072", (1, 3072, 30, 128), False),
]
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
UNIT = 512          # a block of the tables is a 512 x 512 tile pair


def causal_blocks(shape) -> int:
    """512 x 512 tile pairs with a visible entry, over batch and heads."""
    b, t, h, _ = shape
    n = -(-t // UNIT)
    return b * h * n * (n + 1) // 2


def traced(fn, args, iters: int):
    """(the trace of `iters` runs of `fn`, None where there is no device
    plane; the warm-up run's result): `fn` jitted, warmed, then run under
    the profiler; the trace is read as the benchmark reads its own."""
    import jax
    from perfbench.trace import Trace, find_xplane

    run = jax.jit(fn)
    first = jax.block_until_ready(run(*args))
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(iters):
            out = run(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        path = find_xplane(d)
        return (Trace(path) if path else None), first


def kernel_seconds(fn, args, iters: int = 10) -> dict:
    """name -> (device seconds a call, calls a run of `fn`) for every kernel
    of KERNELS that ran in `traced(fn, args, iters)`."""
    tr, _ = traced(fn, args, iters)
    got = {}
    for kern in KERNELS if tr is not None else ():
        # jax.vjp at top level prefixes the instruction with its
        # transformations (%transpose_jvp_flash_bwd_dq__.1); inside the
        # train step the name is bare (%flash_bwd_dq.3), which is what the
        # cells' readers match
        found = tr.matching_seconds(rf"^%(?:[a-z_]*_)?{kern}_*[.\d]* =")
        if found:
            got[kern] = (found[0] / found[1], found[1] / iters)
    return got


def inputs(shape, seed: int = 0):
    """q, k, v, dout: bf16, from the seed."""
    import jax
    import jax.numpy as jnp
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return [(0.3 * jax.random.normal(k, shape, jnp.float32)
             ).astype(jnp.bfloat16) for k in keys]


def measure(flash, label: str, geometries=GEOMETRIES, iters: int = 10,
            say=print) -> list:
    """`flash(q, k, v)` -> out, differentiable. One line a kernel and
    geometry; returns the lines."""
    import jax
    lines = []
    for name, shape, grad in geometries:
        q, k, v, dout = inputs(shape)
        got = kernel_seconds(flash, (q, k, v), iters)
        if grad:
            # the backward by itself: the residuals are arguments
            _, pullback = jax.vjp(flash, q, k, v)
            got.update(kernel_seconds(lambda f, d: f(d), (pullback, dout),
                                      iters))
        blocks = causal_blocks(shape)
        for kern, (sec, calls) in got.items():
            line = {"impl": label, "geometry": name, "shape": list(shape),
                    "kernel": kern, "us_per_call": round(sec * 1e6, 2),
                    "us_per_block": round(sec * 1e6 / blocks, 4),
                    "calls_per_run": calls}
            say(json.dumps(line), flush=True)
            lines.append(line)
    return lines


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--block-q", default="0",
                    help="comma list of q tiles to force; 0 = the kernel's rule")
    ap.add_argument("--block-k", default="0")
    ap.add_argument("--iters", type=int, default=10)
    a = ap.parse_args()

    import jax
    if jax.default_backend() != "tpu":
        sys.exit("flash_microbench: no TPU here; a kernel's time comes "
                 "only from a chip run")
    import paddle_tpu  # noqa: F401  (jax_enable_x64, as the programs run)
    from paddle_tpu.ops.flash_attention import flash_attention_raw
    from paddle_tpu.utils.flags import set_flags

    for bq in (int(x) for x in a.block_q.split(",")):
        for bk in (int(x) for x in a.block_k.split(",")):
            set_flags({"flash_block_q": bq, "flash_block_k": bk})
            measure(lambda q, k, v: flash_attention_raw(q, k, v, causal=True),
                    f"repo bq={bq or 'rule'} bk={bk or 'rule'}",
                    iters=a.iters)
    set_flags({"flash_block_q": 0, "flash_block_k": 0})


if __name__ == "__main__":
    main()
