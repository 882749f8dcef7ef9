"""Pin the span clock to the device trace's.

    python3 tools/span_clock_check.py          # on the chip: exit 0 or 1

Two things are asked, once the span's wall-clock ns are put on the trace's
timeline by `profile_start_time` alone (no calibration, no offset search):

  host    the ring's copy of a span and the profiler's copy of the same span
          (its TraceAnnotation on the host plane) agree within HOST_TOL_S:
          the span clock IS the clock the profiler stamps host events with.
  device  a span around a blocking device call contains that call's device
          interval as the trace records it, within DEVICE_TOL_S. On a TPU
          v5e the profiler's own planes disagree by about a millisecond:
          a program's device interval starts 0.7-1.1 ms BEFORE the host
          annotation around its dispatch (the same in the traces of PR 25
          and PR 26, whose annotations were the harness's own), so 1 ms
          cannot be held by any host clock; the tolerance is 2 ms, and the
          lead and tail are printed for the record.

On a backend with no device plane (the CPU) only the first is asked. The
last line of output is one JSON object.
"""
from __future__ import annotations

import glob
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HOST_TOL_S = 1e-4
DEVICE_TOL_S = 2e-3
SPAN = "clock.probe"


def check(repeats: int = 5, size: int = 2048) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from paddle_tpu.observability import spans

    @jax.jit
    def span_clock_probe(x):
        for _ in range(8):
            x = jnp.tanh(x @ x) * 0.5
        return x

    x = jnp.eye(size, dtype=jnp.float32) * 0.5
    jax.block_until_ready(span_clock_probe(x))          # compiled before
    seq0 = max((r.seq for r in spans.records()), default=0)
    with tempfile.TemporaryDirectory(prefix="span_clock_") as tmp:
        jax.profiler.start_trace(tmp)
        try:
            for i in range(repeats):
                with spans.span(SPAN, cat="user", i=i):
                    jax.block_until_ready(span_clock_probe(x))
        finally:
            jax.profiler.stop_trace()
        path = sorted(glob.glob(os.path.join(
            tmp, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        pd = ProfileData.from_file(path)
    mine = [r for r in spans.records(since=seq0) if r.name == SPAN]
    start_ns, device, host = None, [], []
    for plane in pd.planes:
        if plane.name == "Task Environment":
            start_ns = dict(plane.stats).get("profile_start_time")
        for line in plane.lines:
            for e in line.events:
                iv = (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                if plane.name.startswith("/device:TPU:") \
                        and line.name == "XLA Modules" \
                        and "span_clock_probe" in e.name:
                    device.append(iv)
                elif plane.name.startswith("/host:") \
                        and e.name.startswith(SPAN):
                    host.append(iv)
    out = {"platform": jax.devices()[0].platform, "spans": len(mine),
           "device_intervals": len(device), "host_annotations": len(host),
           "profile_start_time": start_ns, "host_tolerance_s": HOST_TOL_S,
           "device_tolerance_s": DEVICE_TOL_S}
    if start_ns is None or len(mine) != repeats or len(host) != repeats:
        return {**out, "ok": False, "why": "no profile_start_time or spans"}
    on_trace = [((r.t0_ns - start_ns) * 1e-9, (r.t1_ns - start_ns) * 1e-9)
                for r in mine]
    pairs = list(zip(on_trace, sorted(host)))
    out["host_start_gap_s"] = [s[0] - h[0] for s, h in pairs]
    out["host_end_gap_s"] = [h[1] - s[1] for s, h in pairs]
    ok = all(abs(s[0] - h[0]) <= HOST_TOL_S and abs(s[1] - h[1]) <= HOST_TOL_S
             for s, h in pairs)
    if device:      # every device interval inside its span
        pairs = list(zip(on_trace, sorted(device)))
        out["device_lead_s"] = [d[0] - s[0] for s, d in pairs]
        out["device_tail_s"] = [s[1] - d[1] for s, d in pairs]
        # the evidence for DEVICE_TOL_S, with no span clock in it: the
        # device interval against the PROFILER'S OWN host annotation of
        # the same span. A program cannot start before the call that
        # dispatches it, so a positive number here is the disagreement of
        # the profiler's host and device planes, which no host clock can
        # take out; ISSUE 27's 1 ms is held where it can be, on the host
        # side (HOST_TOL_S)
        out["device_start_before_profilers_own_annotation_s"] = [
            h[0] - d[0] for h, d in zip(sorted(host), sorted(device))]
        ok = ok and len(device) == repeats and all(
            d[0] >= s[0] - DEVICE_TOL_S and d[1] <= s[1] + DEVICE_TOL_S
            for s, d in pairs)
    return {**out, "ok": bool(ok)}


if __name__ == "__main__":
    result = check()
    print(json.dumps(result), flush=True)
    sys.exit(0 if result["ok"] else 1)
