"""The served step's share of the chip's peak: the operations that every
real (unpadded) prompt and output token of the traced window needs, over
peak x the traced window."""
from .. import families


def window_flops(record, cfg):
    fam = families.of(cfg)
    total = 0.0
    for s in record["steps"]:
        total += sum(fam.prefill_work(cfg, t)[0] for t in s["prefills"])
        total += fam.burst_work(cfg, s["decode_steps"], s["decodes"])[0]
    return total


def read(env):
    busy = env["busy"]
    if not busy or env["peaks"] is None:
        return None
    flops = window_flops(env["record"], env["cfg"])
    chips = env["record"]["chips"]
    return 100.0 * flops / (env["peaks"]["bf16_flops_per_s"] * busy[1] * chips)
