"""The flash kernels' share of their roofline: the least time the chip
could take for the calls the traced window made (perfbench/arith.py's
operations and bytes) over the device durations of the kernels' events,
found in the trace by name inside the programs whose name matches `within`.
Every train step runs the forward and the backward in every layer, and a
forward recomputed by remat is time with no credit."""
from .. import arith, harness as hs


def read(env, match, within=None):
    tr, peaks = env["trace"], env["peaks"]
    if tr is None or peaks is None:
        return None
    found = tr.matching_seconds(match, within)
    if not found:
        return None
    seconds, calls = found
    d, rec = arith.dims(env["cfg"]), env["record"]
    shape = (rec["batch"] // rec["chips"] or 1, d["H"], d["KV"],
             rec["seq_len"], d["hd"])
    f1, b1 = arith.flash_fwd_cost(*shape)
    f2, b2 = arith.flash_bwd_cost(*shape)
    n = len(rec["step_t"]) * d["L"]
    least, bound = arith.roofline_seconds(n * (f1 + f2), n * (b1 + b2), peaks)
    hs.say({"flash_roofline": within, "bound": bound, "kernel_calls": calls,
            "kernel_seconds": seconds, "least_seconds": least})
    return 100.0 * least / seconds
