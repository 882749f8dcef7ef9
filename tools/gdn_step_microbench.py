"""The gated delta rule's decode step alone on the chip: microseconds a layer.

    python3 tools/gdn_step_microbench.py [--frozen 0,1] [--seeds 0]   (on a TPU host)

``ops/gated_delta.gdn_step`` is run by itself, jitted, at the geometry the
hybrid cell hands it (PERF.md section 5): 24 slots of Olmo-Hybrid's 30
heads, key 96 / value 192, q / k / v in bf16, g, beta and the state in
float32, ``--frozen`` of the slots frozen (the cell holds ~1 of 24 done).
Two bodies: ``kernel`` (the Pallas pass, ``gdn_step_body``'s choice) and
``jnp`` (``gdn_step_jnp``, as XLA makes it). Every array is an ARGUMENT of
the jitted call (XLA folds what a function closes over), and the state is
donated and handed from call to call, as the burst carries it, so the
kernel updates it in place. The kernel's time is its own device time, read
from a profiler trace by the name its ``pallas_call`` gives it
(``gdn_step``), as the cell's ``kernel.gdn_step_roofline.batch`` reads the
scope around it; ``program_us`` is the whole jitted call (the kernel with
its small transposes and casts, or the jnp body's fusions).

The shares are of the chip's 819 GB/s over the live slots' state read once
and written once: ``tiled`` as the chip lays it out (dk 96 on 128 lanes),
``numbers`` the float32 values alone. ``max_gap`` is the widest difference
of the kernel's output and new state from the jnp body's on the same
inputs.

Needs a TPU: off the chip there is no device plane in the trace. One JSON
line a reading on stdout.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES_PER_S = 819e9      # one v5e chip (perfbench/peaks.json)

# what gdn_step is handed in the hybrid cell: perfbench/traffic/
# longdoc-batch.json's 24 slots, perfbench/configs/olmo-hybrid-7b.l16.json's
# linear layer (30 value heads, key 96, value 192)
GEOMETRY = dict(slots=24, heads=30, dk=96, dv=192)
KERNEL = r"^%gdn_step[\w.]* ="


def inputs(geometry: dict, seed: int = 0, frozen: int = 0):
    """q, k, v, g, beta, state, frozen for one call, from the seed: unit q
    and k as the mixer makes them, gates over the range its random weights
    give; the last ``frozen`` slots frozen."""
    import jax
    import jax.numpy as jnp
    g = geometry
    B, H, dk, dv = g["slots"], g["heads"], g["dk"], g["dv"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(key, scale):
        a = jax.random.normal(key, (B, H, dk), jnp.float32)
        return (a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True))
                * scale).astype(jnp.bfloat16)

    return (unit(ks[0], dk ** -0.5), unit(ks[1], 1.0),
            jax.random.normal(ks[2], (B, H, dv), jnp.float32
                              ).astype(jnp.bfloat16),
            -jax.random.uniform(ks[3], (B, H), jnp.float32, 0.05, 1.5),
            jax.random.uniform(ks[4], (B, H), jnp.float32, 0.0, 2.0),
            jax.random.normal(ks[5], (B, H, dv, dk), jnp.float32),
            jnp.arange(B) >= B - frozen)


def timed(fn, args, iters: int):
    """(the trace of ``iters`` calls of ``fn``, the state handed from each
    call to the next; the first call's outputs): ``fn`` jitted with the
    state donated, warmed, then run under the profiler."""
    import jax
    import numpy as np
    from perfbench.trace import Trace, find_xplane

    run = jax.jit(fn, donate_argnums=(5,))
    first = jax.block_until_ready(
        run(*args[:5], args[5].copy(), args[6]))
    first = tuple(np.asarray(a) for a in first)
    state = args[5].copy()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(iters):
            _, state = run(*args[:5], state, args[6])
        jax.block_until_ready(state)
        jax.profiler.stop_trace()
        path = find_xplane(d)
        return (Trace(path) if path else None), first


def measure(frozen=(0,), seed: int = 0, iters: int = 100,
            geometry=GEOMETRY, say=print) -> list:
    """One line a body and count of frozen slots; returns the lines."""
    import numpy as np
    from paddle_tpu.ops import gated_delta as gd

    g = geometry
    bodies = {"kernel": functools.partial(gd.gdn_step, interpret=False),
              "jnp": gd.gdn_step_jnp}
    lines = []
    for n in frozen:
        args = inputs(g, seed, n)
        live = g["slots"] - n
        numbers = 2 * live * g["heads"] * g["dv"] * g["dk"] * 4
        tiled = 2 * live * g["heads"] * g["dv"] * (-(-g["dk"] // 128) * 128) * 4
        outs = {}
        for name, fn in bodies.items():
            tr, outs[name] = timed(fn, args, iters)
            if tr is None:
                continue
            busy = tr.busy_seconds()
            prog = busy[0] / iters if busy else None
            found = tr.matching_seconds(KERNEL)
            sec = found[0] / found[1] if found and name == "kernel" else prog
            line = {"body": name, "seed": seed, "frozen": n, **g,
                    "us_per_layer": round(sec * 1e6, 2),
                    "program_us": round(prog * 1e6, 2) if prog else None,
                    "tiled_share": round(tiled / sec / HBM_BYTES_PER_S, 4),
                    "numbers_share": round(numbers / sec / HBM_BYTES_PER_S,
                                           4)}
            lines.append(line)
        if len(outs) == 2 and lines:
            lines[-1]["max_gap"] = float(max(
                np.abs(a - b).max() for a, b in zip(outs["kernel"],
                                                    outs["jnp"])))
        for line in lines[-len(outs):]:
            say(json.dumps(line), flush=True)
    return lines


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--frozen", default="0,1",
                    help="comma list of counts of frozen slots")
    ap.add_argument("--seeds", default="0")
    ap.add_argument("--iters", type=int, default=100)
    a = ap.parse_args()

    import jax
    if jax.default_backend() != "tpu":
        sys.exit("gdn_step_microbench: no TPU here; a kernel's time comes "
                 "only from a chip run")
    import paddle_tpu  # noqa: F401  (jax_enable_x64, as the programs run)

    for seed in (int(x) for x in a.seeds.split(",")):
        measure([int(x) for x in a.frozen.split(",")], seed, a.iters)


if __name__ == "__main__":
    main()
