"""One flash kernel's share of its roofline, the kernel found by the name
its pallas_call gives it (the trace's instruction name): the least time the
chip could take for the calls the algorithm needs in the traced window
(perfbench/arith.py, one forward and one backward a layer and step) over
the device durations of ALL the kernel's events, so a forward that remat
runs again is time with no credit."""
from .. import arith, harness as hs


def read(env, kernels, cost, within=None):
    tr, peaks = env.get("trace"), env.get("peaks")
    if tr is None or peaks is None:
        return None
    found = tr.matching_seconds(kernels, within)
    if not found:
        return None
    seconds, calls = found
    d, rec = arith.dims(env["cfg"]), env["record"]
    shape = (rec["batch"] // rec["chips"] or 1, d["H"], d["KV"],
             rec["seq_len"], d["hd"])
    flops, byts = {"fwd": arith.flash_fwd_cost,
                   "bwd": arith.flash_bwd_cost}[cost](*shape)
    n = len(rec["step_t"]) * d["L"]
    least, bound = arith.roofline_seconds(n * flops, n * byts, peaks)
    hs.say({"flash_kernel_roofline": kernels, "bound": bound,
            "kernel_calls": calls, "credited_calls": n,
            "kernel_seconds": seconds, "least_seconds": least})
    return 100.0 * least / seconds
