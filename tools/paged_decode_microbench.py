"""The paged decode read alone on the chip: microseconds a layer.

    chiprun -- python3 tools/paged_decode_microbench.py [--chunk-rows 512,1024,2048]

``ops/ragged_attention.paged_decode_attention`` is run by itself, jitted,
at the geometries the two serving cells hand it (PERF.md section 5): the
batch cell's 48 slots over a 128-page table of 8 KV heads, the hybrid
cell's 24 slots over a 216-page table of 32 (30 padded); bf16, pages of 16
rows, one context length a slot drawn from the seed over the range the
cell's traffic holds, the slots' pages scattered over the pool. Every
array is an ARGUMENT of the jitted call, the block table and the lengths
too: XLA folds what a function closes over, and a reading taken that way
was void (PERF.md section 6, PR 28). The time is the kernel's own device
time, read from a profiler trace by the name its ``pallas_call`` gives it,
as ``tools/flash_microbench.py`` reads the flash kernels'.

"Live bytes" are the K and V rows under the slots' lengths (what
``live_kv_bytes_at_close`` counts a layer); the share is those bytes over
the kernel's time against the chip's 819 GB/s. The kernel copies whole
pages, so it moves up to a page a slot more (``page_bytes``).

``--chunk-rows`` overrides the module's ``_DECODE_CHUNK_ROWS`` for the
reading (0 = as the module has it): how the chunk's size was chosen.

Needs a TPU: off the chip there is no device plane in the trace. One JSON
line a reading on stdout.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import zlib

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES_PER_S = 819e9      # one v5e chip (perfbench/peaks.json)

# what paged_decode_attention is handed in each serving cell: the engine's
# settings of perfbench/traffic/<mix>.json, the pool's heads of
# llama_paged.pool_kv_heads, contexts = prompt_len.lo .. max_len
GEOMETRIES = {
    "batch": dict(slots=48, table_pages=128, page_size=16, kv_heads=8,
                  q_heads=16, head_dim=128, contexts=(512, 2048)),
    "hybrid": dict(slots=24, table_pages=216, page_size=16, kv_heads=32,
                   q_heads=32, head_dim=128, contexts=(1024, 3456)),
}
KERNEL = r"paged_decode_attention[\w.]* ="


def inputs(geometry: dict, seed: int = 0, contexts=None):
    """q, k_pool, v_pool, block_table, q_lens, kv_lens for one launch:
    bf16 pools from the seed, each slot's pages a draw from a permutation
    of the pool (page 0 left as the engine's scratch page)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    g = geometry
    lo, hi = contexts or g["contexts"]
    rng = np.random.RandomState(seed)
    B, P = g["slots"], g["table_pages"]
    kv_lens = rng.randint(lo, hi + 1, B).astype(np.int32)
    table = (1 + rng.permutation(B * P)).reshape(B, P).astype(np.int32)
    pool = (B * P + 1, g["page_size"], g["kv_heads"], g["head_dim"])
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)

    def normal(key, shape):
        return (0.3 * jax.random.normal(key, shape, jnp.float32)
                ).astype(jnp.bfloat16)

    return (normal(kq, (B, 1, g["q_heads"], g["head_dim"])),
            normal(kk, pool), normal(kv, pool), jnp.asarray(table),
            jnp.ones(B, jnp.int32), jnp.asarray(kv_lens))


def kernel_seconds(fn, args, iters: int):
    """(device seconds a call of the decode kernel inside `fn`, CRC-32 of
    its result's bytes), the trace read by ``flash_microbench.traced``. Two
    trees whose chunks are the same compute the same bits: equal CRCs."""
    import numpy as np
    from tools.flash_microbench import traced

    tr, out = traced(fn, args, iters)
    found = tr.matching_seconds(KERNEL) if tr is not None else None
    return ((found[0] / found[1] if found else None),
            zlib.crc32(np.asarray(out).tobytes()))


def measure(label: str, chunk_rows=(0,), geometries=GEOMETRIES, seed: int = 0,
            iters: int = 50, contexts=None, say=print) -> list:
    """One line a geometry and chunk size; returns the lines."""
    import numpy as np
    from paddle_tpu.ops import ragged_attention as ra

    was = ra._DECODE_CHUNK_ROWS
    lines = []
    for name, g in geometries.items():
        args = inputs(g, seed, contexts)
        lens = np.asarray(args[-1])
        row_bytes = 2 * g["kv_heads"] * g["head_dim"] * 2     # K + V, bf16
        live = int(lens.sum()) * row_bytes
        for cr in chunk_rows:
            # the constant is read where the launch is built: a new jit
            # of the launch itself, not the module's cached one
            ra._DECODE_CHUNK_ROWS = cr or was
            try:
                sec, crc = kernel_seconds(functools.partial(
                    ra.paged_decode_attention.__wrapped__, interpret=False),
                    args, iters)
            finally:
                ra._DECODE_CHUNK_ROWS = was
            if sec is None:
                continue
            line = {"impl": label, "geometry": name, "seed": seed,
                    "chunk_rows": cr or was,
                    "contexts": [int(lens.min()), int(lens.max())],
                    "us_per_layer": round(sec * 1e6, 2), "out_crc": crc,
                    "live_bytes": live,
                    "page_bytes": int(-(-lens // g["page_size"]).sum())
                    * g["page_size"] * row_bytes,
                    "bandwidth_share": round(live / sec / HBM_BYTES_PER_S,
                                             4)}
            say(json.dumps(line), flush=True)
            lines.append(line)
    return lines


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chunk-rows", default="0",
                    help="comma list of flat rows a chunk; 0 = the module's")
    ap.add_argument("--geometry", default=",".join(GEOMETRIES))
    ap.add_argument("--contexts", default="",
                    help="lo,hi in place of the cell's range of contexts")
    ap.add_argument("--seeds", default="0")
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--label", default="repo")
    a = ap.parse_args()

    import jax
    if jax.default_backend() != "tpu":
        sys.exit("paged_decode_microbench: no TPU here; a kernel's time "
                 "comes only from a chip run")
    import paddle_tpu  # noqa: F401  (jax_enable_x64, as the programs run)

    contexts = tuple(int(x) for x in a.contexts.split(",")) \
        if a.contexts else None
    for seed in (int(x) for x in a.seeds.split(",")):
        measure(a.label, [int(x) for x in a.chunk_rows.split(",")],
                {n: GEOMETRIES[n] for n in a.geometry.split(",")},
                seed, a.iters, contexts)


if __name__ == "__main__":
    main()
