"""The served step's share of the chip's peak: the operations that every
real (unpadded) prompt and output token of the traced window needs, over
peak x the traced window."""
from .. import arith


def window_flops(record, cfg):
    total = 0.0
    for s in record["steps"]:
        total += sum(arith.prefill_flops(cfg, t) for t in s["prefills"])
        for ctx0, n in s["decodes"]:
            total += sum(arith.decode_flops(cfg, ctx0 + 1 + j)
                         for j in range(n))
    return total


def read(env):
    busy = env["busy"]
    if not busy or env["peaks"] is None:
        return None
    flops = window_flops(env["record"], env["cfg"])
    chips = env["record"]["chips"]
    return 100.0 * flops / (env["peaks"]["bf16_flops_per_s"] * busy[1] * chips)
