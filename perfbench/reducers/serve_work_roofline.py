"""Least device time for the window's work over the device's busy time.

Each prefill: max(operations / peak, bytes / bandwidth), bytes = the weights
once + the prompt's KV written. Each burst: the weights once per executed
decode step + the LIVE KV rows each emitted token attends + the rows
written. It reads the same work whether a gather or a kernel does it."""
from .. import arith


def least_seconds(record, cfg, peaks):
    w, kv = arith.weight_bytes(cfg), arith.kv_bytes_per_token(cfg)
    total = 0.0
    for s in record["steps"]:
        for t in s["prefills"]:
            total += arith.roofline_seconds(
                arith.prefill_flops(cfg, t), w + t * kv, peaks)[0]
        if s["decode_steps"]:
            flops = sum(arith.decode_flops(cfg, c + 1 + j)
                        for c, n in s["decodes"] for j in range(n))
            rows = sum(arith.live_kv_rows(c, n) + n for c, n in s["decodes"])
            total += arith.roofline_seconds(
                flops, s["decode_steps"] * w + rows * kv, peaks)[0]
    return total


def read(env):
    busy = env["busy"]
    if not busy or env["peaks"] is None or busy[0] <= 0:
        return None
    return 100.0 * least_seconds(env["record"], env["cfg"],
                                 env["peaks"]) / busy[0]
