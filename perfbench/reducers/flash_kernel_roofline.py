"""One flash kernel's share of its roofline, the kernel found by the name
its pallas_call gives it (the trace's instruction name): the least time the
chip could take for the calls the algorithm needs in the traced window
(the family's attention calls a step, perfbench/arith.py's cost of each)
over the device durations of ALL the kernel's events, so a forward that
remat runs again is time with no credit."""
from .. import arith, families, harness as hs


def credited(env, costs):
    """(calls, operations, bytes) of the attention calls that the steps of
    the traced window need, each call costing the sum of `costs`."""
    rec, cfg = env["record"], env["cfg"]
    calls, flops, byts = 0, 0.0, 0.0
    for shape, count in families.of(cfg).train_attention_calls(
            cfg, rec["batch"] // rec["chips"] or 1, rec["seq_len"]):
        n = len(rec["step_t"]) * count
        calls += n
        flops += n * sum(cost(*shape)[0] for cost in costs)
        byts += n * sum(cost(*shape)[1] for cost in costs)
    return calls, flops, byts


def read(env, kernels, cost, within=None):
    tr, peaks = env.get("trace"), env.get("peaks")
    if tr is None or peaks is None:
        return None
    found = tr.matching_seconds(kernels, within)
    if not found:
        return None
    seconds, calls = found
    n, flops, byts = credited(env, [{"fwd": arith.flash_fwd_cost,
                                     "bwd": arith.flash_bwd_cost}[cost]])
    least, bound = arith.roofline_seconds(flops, byts, peaks)
    hs.say({"flash_kernel_roofline": kernels, "bound": bound,
            "kernel_calls": calls, "credited_calls": n,
            "kernel_seconds": seconds, "least_seconds": least})
    return 100.0 * least / seconds
