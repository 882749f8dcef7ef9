"""The flash kernels' share of their roofline: the least time the chip
could take for the calls the traced window made (the family's attention
calls a step, perfbench/arith.py's operations and bytes of each) over the
device durations of the kernels' events, found in the trace by name inside
the programs whose name matches `within`. Every call is a forward and a
backward, and a forward recomputed by remat is time with no credit."""
from .. import arith, harness as hs
from .flash_kernel_roofline import credited


def read(env, match, within=None):
    tr, peaks = env["trace"], env["peaks"]
    if tr is None or peaks is None:
        return None
    found = tr.matching_seconds(match, within)
    if not found:
        return None
    seconds, calls = found
    _, flops, byts = credited(env, [arith.flash_fwd_cost,
                                    arith.flash_bwd_cost])
    least, bound = arith.roofline_seconds(flops, byts, peaks)
    hs.say({"flash_roofline": within, "bound": bound, "kernel_calls": calls,
            "kernel_seconds": seconds, "least_seconds": least})
    return 100.0 * least / seconds
