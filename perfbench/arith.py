"""Operations and bytes from shapes: the part of the yardstick's arithmetic
that is the same for every model (what a model's shape decides is its
family's, perfbench/families/). Nothing here imports the program. The
live-KV accounting is the exact-live count of
benchmarks/decode_bench.py::ragged_read_bytes, a copy, so that a later PR to
the program cannot move the yardstick.
"""
from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def live_kv_rows(ctx0: int, n_new: int) -> int:
    """Rows read by n_new consecutive tokens when the first attends ctx0+1
    rows (itself included): token t reads exactly t + 1 rows."""
    return n_new * (ctx0 + 1) + n_new * (n_new - 1) // 2


# ----------------------------------------------------------------- kernels

def flash_fwd_cost(batch: int, heads: int, kv_heads: int, seq: int,
                   head_dim: int, dtype_bytes: int = 2):
    """(flops, bytes) one causal flash forward needs: QK^T and PV over the
    lower triangle; q, k, v read and o, lse written once."""
    flops = 4.0 * batch * heads * seq * seq * head_dim / 2
    byts = batch * seq * head_dim * dtype_bytes * (2 * heads + 2 * kv_heads) \
        + batch * heads * seq * 4
    return flops, float(byts)


def flash_bwd_cost(batch: int, heads: int, kv_heads: int, seq: int,
                   head_dim: int, dtype_bytes: int = 2):
    """(flops, bytes) the causal backward needs from q, k, v, do and lse:
    five products (S, dP, dV, dK, dQ). A kernel pair that forms S and dP
    twice does more than this and is not credited for it."""
    flops = 10.0 * batch * heads * seq * seq * head_dim / 2
    byts = batch * seq * head_dim * dtype_bytes * (3 * heads + 4 * kv_heads) \
        + 2 * batch * heads * seq * 4
    return flops, float(byts)


# ------------------------------------------------------------------- peaks

def load_peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown kind is an error, never a
    default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks known for device_kind {device_kind!r}: "
                       f"add it to perfbench/peaks.json with its source")
    return table[device_kind]


def roofline_seconds(flops: float, byts: float, peaks: dict):
    """(least seconds, which bound applies)."""
    tf = flops / peaks["bf16_flops_per_s"]
    tb = byts / peaks["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")
