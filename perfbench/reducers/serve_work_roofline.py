"""Least device time for the window's work over the device's busy time.

Each prefill and each burst: max(operations / peak, bytes / bandwidth), the
operations and bytes being what the configuration's family says that step
needs (perfbench/families/: a prefill's weights once + what it writes; a
burst's weights once per executed decode step + the LIVE cache and state its
tokens read and write). It reads the same work whether a gather or a kernel
does it."""
from .. import arith, families


def least_seconds(record, cfg, peaks):
    fam = families.of(cfg)
    total = 0.0
    for s in record["steps"]:
        for t in s["prefills"]:
            total += arith.roofline_seconds(*fam.prefill_work(cfg, t),
                                            peaks)[0]
        if s["decode_steps"]:
            total += arith.roofline_seconds(*fam.burst_work(
                cfg, s["decode_steps"], s["decodes"]), peaks)[0]
    return total


def read(env):
    busy = env["busy"]
    if not busy or env["peaks"] is None or busy[0] <= 0:
        return None
    return 100.0 * least_seconds(env["record"], env["cfg"],
                                 env["peaks"]) / busy[0]
