"""How far a closed cell's rate spreads from seed to seed by its traffic alone.

    python3 -m perfbench.tools.loop_model --traffic longdoc-batch \
        --step-ms 21.2 --prefill-s 0.105,0.19 --seconds 40,80,160

A model of the closed loop on the host's clock, no engine and no JAX: the
mix's own lengths from `gen.request_schedule`, its slots, burst and prompt
buckets; a decode step of every slot costs `--step-ms`, a prefill of a
prompt in bucket i costs `--prefill-s[i]` and stalls every slot (one
device), the fill ends when every slot was occupied once, the window closes
with the engine step in which its seconds pass, and the rate is the tokens
seen in the window over the window as it closed, all as `runners/serve.py`
does it. Two runs of one seed on a chip differ by ~0.15 % (PERF.md section
6, PR 30), so what this prints is the part of a cell's spread that no
quieter machine removes: if it is over half the metric's bound at the
benchmark's `run_seconds`, the cell needs a longer window or another rate.
The step and prefill seconds are a traced chip run's, read off its line.
"""
from __future__ import annotations

import argparse
import statistics

from .. import gen, harness as hs


def rate(traffic: dict, seed: int, seconds: float, step_s: float,
         prefill_s: list[float]) -> float:
    e = traffic["engine"]
    slots, burst, buckets = e["max_batch"], e["burst"], e["prompt_buckets"]
    sched = gen.request_schedule(traffic, seed, seconds,
                                 count=traffic["arrivals"]["requests"])
    left = [0] * slots          # output tokens a slot still owes; 0: free
    nxt, now, seen = 0, 0.0, 0
    t_open = tokens0 = None
    while True:
        if t_open is None and nxt >= slots:     # the fill is over
            t_open, tokens0 = now, seen
        if t_open is not None and now - t_open >= seconds:
            return (seen - tokens0) / (now - t_open)
        steps = [min(burst, n) for n in left]
        now += step_s * max(steps, default=0)   # the burst of live slots
        seen += sum(steps)
        left = [n - s for n, s in zip(left, steps)]
        for i in range(slots):                  # admit under the burst
            if left[i] == 0:
                r = sched[nxt]
                nxt += 1
                which = sum(r["prompt_len"] > b for b in buckets)
                now += prefill_s[min(which, len(prefill_s) - 1)]
                seen += 1                       # the prefill's own token
                left[i] = r["output_len"] - 1


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--step-ms", type=float, required=True)
    ap.add_argument("--prefill-s", required=True)
    ap.add_argument("--seconds", default="40")
    ap.add_argument("--seeds", type=int, default=300)
    a = ap.parse_args(argv)
    traffic = hs.load_json(f"traffic/{a.traffic}.json")
    prefill = [float(x) for x in a.prefill_s.split(",")]
    for seconds in (float(x) for x in a.seconds.split(",")):
        rates = [rate(traffic, 2147480000 + s, seconds, a.step_ms / 1e3,
                      prefill) for s in range(a.seeds)]
        q1, med, q3 = statistics.quantiles(rates, n=4)
        print(f"window {seconds:g} s: median {med:.1f} tokens/s, quartile "
              f"spread {100 * (q3 - q1) / med:.2f} % over {a.seeds} seeds")


if __name__ == "__main__":
    main()
