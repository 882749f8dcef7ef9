"""The grouped products of one expert layer alone on the chip: ms a layer.

    chiprun -- python3 tools/moe_microbench.py [--held 16 --width 2048 --layers 7]

One expert layer's three products (gate, up, down) over the rows that land
on the experts held here, at the shapes the cell
`k-exaone-236b.reasoning-batch` hands `ops/moe_dropless.py`: a decode step
(64 tokens x 8 assignments = 512 rows, about an eighth of them on held
experts) and a prefill (2048 tokens: 16 384 rows, about 2048 on held
experts), hidden 6144, expert width 2048, 16 experts of 128 held, bf16.
Candidates for the grouped product, each jitted by itself with every array
an argument:

  ragged   `jax.lax.ragged_dot` (on the TPU XLA makes it a grouped-matmul
           kernel of its own, tiles chosen by the compiler)
  gmm      `jax.experimental.pallas.ops.tpu.megablox.gmm`, by tiling
  dense    every token through every held expert, masked afterwards (a
           batched product; decode only)

`gmm` is handed what the engine hands it: the expert weights STACKED over
`--layers` sparse layers as layers x held groups, of which one layer's have
rows (`ops/moe_dropless._grouped_ffn`); `ragged` and `dense` take that
layer's slice. PR 34's readings (PERF.md section 6) were taken at
`--layers 1`.

Host clock around `--iters` calls that end in `block_until_ready`, the
least of three rounds; the share is the held experts' weight bytes over the
time against 819 GB/s (decode) or the routed rows' operations against 197
TFLOP/s (prefill). Needs a TPU. One JSON line a reading on stdout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES_PER_S = 819e9      # one v5e chip (perfbench/peaks.json)
BF16_FLOPS_PER_S = 197e12


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--hidden", type=int, default=6144)
    ap.add_argument("--width", type=int, default=2048)
    ap.add_argument("--held", type=int, default=16)
    ap.add_argument("--layers", type=int, default=7)
    ap.add_argument("--experts", type=int, default=128)
    ap.add_argument("--topk", type=int, default=8)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    if jax.default_backend() != "tpu":
        raise SystemExit("no TPU: a product's time comes only from a chip run")
    D, F, E = args.hidden, args.width, args.held
    groups, at = args.layers * E, (args.layers // 2) * E
    key = jax.random.PRNGKey(args.seed)
    kw = jax.random.split(key, 4)
    stack = [jax.random.normal(k, (groups, D, F), jnp.bfloat16) * 0.02
             for k in kw[:2]]
    stack.append(jax.random.normal(kw[2], (groups, F, D), jnp.bfloat16) * 0.02)
    layer = [w[at:at + E] for w in stack]

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(args.iters):
                out = fn(*a)
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / args.iters)
        return best

    def ragged(x, wg, wu, wd, gs):
        g = jax.lax.ragged_dot(x, wg, gs)
        u = jax.lax.ragged_dot(x, wu, gs)
        return jax.lax.ragged_dot(jax.nn.silu(g) * u, wd, gs)

    def via_gmm(tiling):
        def f(x, wg, wu, wd, gs):
            g = gmm(x, wg, gs, jnp.bfloat16, tiling)
            u = gmm(x, wu, gs, jnp.bfloat16, tiling)
            return gmm(jax.nn.silu(g) * u, wd, gs, jnp.bfloat16, tiling)
        return f

    def dense(x, wg, wu, wd, gs):
        g = jnp.einsum("td,edf->etf", x, wg)
        u = jnp.einsum("td,edf->etf", x, wu)
        return jnp.einsum("etf,efd->etd", jax.nn.silu(g) * u, wd)

    rng = np.random.RandomState(args.seed)
    weight_bytes = 3 * E * D * F * 2
    for phase, tokens in (("decode", 64), ("prefill", 2048)):
        rows = tokens * args.topk
        # each assignment lands on a held expert with probability E / experts
        picks = rng.randint(0, args.experts, rows)
        sizes = np.bincount(picks[picks < E], minlength=E).astype(np.int32)
        local = int(sizes.sum())
        x = jax.random.normal(kw[3], (rows, D), jnp.bfloat16)
        gs = jnp.asarray(sizes)
        gs_stack = jnp.zeros((groups,), jnp.int32).at[at:at + E].set(gs)
        flops = 2.0 * local * 3 * D * F
        least = max(weight_bytes / HBM_BYTES_PER_S, flops / BF16_FLOPS_PER_S)
        cands = {"ragged": ragged}
        for tiling in ((128, 512, 512), (128, 1024, 1024), (128, 2048, 512),
                       (256, 1024, 1024), (512, 1024, 1024)):
            if rows % tiling[0] == 0:
                cands["gmm" + "x".join(map(str, tiling))] = via_gmm(tiling)
        if phase == "decode":
            cands["dense"] = dense
        for name, fn in cands.items():
            xin = x[:tokens] if name == "dense" else x
            operands = (*stack, gs_stack) if name.startswith("gmm") \
                else (*layer, gs)
            try:
                s = timed(jax.jit(fn), xin, *operands)
                print(json.dumps({
                    "phase": phase, "impl": name, "rows": rows,
                    "local_rows": local, "busiest": int(sizes.max()),
                    "ms": s * 1e3, "least_ms": least * 1e3,
                    "share_of_roofline": least / s}), flush=True)
            except Exception as e:      # one candidate must not end the rest
                print(json.dumps({"phase": phase, "impl": name,
                                  "error": repr(e)[:300]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
