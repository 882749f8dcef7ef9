"""Find an open-loop cell's knee once: one engine, several rates.

    python3 -m perfbench.tools.sweep --workload <cell> --rates 2,3,4 --seconds 30

Builds and warms the cell's engine once, then runs a window and its drain at
each rate in turn (a fresh seed each), printing per rate what decides the
knee: failed requests, the queue at mid-window and at the close, the drain,
and the latencies. The knee is the highest rate with no failed request and
no backlog still growing at the window's end; the cell's traffic file then
holds 0.8 of it as a number."""
from __future__ import annotations

import argparse
import copy
import json

from .. import harness as hs, run as prun
from ..runners import serve


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=900)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    ctx = prun.context(a.workload, seed=a.seed, seconds=a.seconds,
                       rehearse=a.rehearse)
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    dev = hs.device_info()
    hs.require_chips(dev, ctx["cell"]["chips"], a.rehearse)
    state = serve.prepare(ctx)
    for i, rate in enumerate(float(r) for r in a.rates.split(",")):
        c = dict(ctx, seed=a.seed + 1 + i, traffic=copy.deepcopy(ctx["traffic"]))
        c["traffic"]["arrivals"]["rate_per_s"] = rate
        obs = serve.measure(state, c)
        while state["eng"].pending:         # empty before the next rate
            state["eng"].step()
        state["eng"].take_finished()
        print(json.dumps({"sweep_rate_per_s": rate, "e2e": obs["e2e"],
                          **{k: obs["info"].get(k) for k in (
                              "requests", "failed", "queued_mid_window",
                              "queued_at_close", "live_at_close", "drain_s",
                              "ttft_p50_ms", "tpot_p50_ms",
                              "page_buckets_used")}}), flush=True)


if __name__ == "__main__":
    main()
