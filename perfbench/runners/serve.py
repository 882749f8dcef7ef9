"""Traffic kinds `serve_closed` and `serve_open`: the serving engine of the
configuration's family (perfbench/families/) under a request schedule from
the seed.

Closed: a backlog that never drains; set-up fills every slot, the window
opens when every slot has been occupied once, and each finished request is
replaced from the backlog. Open: arrivals at the rate fixed in the traffic
file; every request is timed from when it was DUE, the generator's lateness
is printed, and after the window the harness keeps stepping until the
requests due in it have finished or the drain allowance has passed.

The engine gives a caller no view of one request in flight, so the harness
keeps the ServedRequest object that `add_request` has just queued and reads
its `out` after every `step()`: a token is visible when the step that
produced it has returned.
"""
from __future__ import annotations

import gc
import time

from .. import check, families, faults, gen, harness as hs, stats
from ..weights import make_weights


class Tracker:
    """Per-request observations on the harness's clock."""

    def __init__(self):
        self.recs: list[dict] = []
        self.active: list[dict] = []

    def add(self, eng, prompt, n_out, due_t, index):
        rid = eng.add_request(prompt, max_new_tokens=n_out)
        req = eng._queue[-1]
        if req.rid != rid:
            raise RuntimeError(f"the queue's newest request is {req.rid}, "
                               f"not the {rid} just added")
        rec = {"index": index, "req": req, "prompt_len": len(prompt),
               "n_out": n_out, "due_t": due_t, "seen": 0,
               "first_t": None, "last_t": None, "done_t": None}
        self.recs.append(rec)
        self.active.append(rec)

    def observe(self, t: float) -> dict:
        """After a step: what became visible. Returns the step's work:
        prefills (real prompt lengths) and decode runs (rows attended by
        the first new token less one, new tokens)."""
        prefills, decodes, still = [], [], []
        for rec in self.active:
            n = len(rec["req"].out)
            if n > rec["seen"]:
                if rec["seen"] == 0:
                    rec["first_t"] = t
                    prefills.append(rec["prompt_len"])
                    if n > 1:
                        decodes.append((rec["prompt_len"], n - 1))
                else:
                    decodes.append((rec["prompt_len"] + rec["seen"] - 1,
                                    n - rec["seen"]))
                rec["seen"], rec["last_t"] = n, t
            if rec["req"].done:
                rec["done_t"] = t
            else:
                still.append(rec)
        self.active = still
        return {"prefills": prefills, "decodes": decodes}


def _top(spec: dict, default: int) -> int:
    return int(spec.get("hi", spec.get("value", default)))


def warm_up(eng, traffic: dict, cfg: dict, seed: int) -> list:
    """One lone request for every prompt bucket and one whose context ends
    in every page bucket the traffic can reach: the cell's shapes and no
    others. Returns the seconds each took."""
    e = traffic["engine"]
    ps, burst = e["page_size"], e["burst"]
    top = max(e["prompt_buckets"])
    reach = min(e["max_len"], _top(traffic["prompt_len"], top)
                + _top(traffic["output_len"], burst + 1))
    plans = [(b, burst + 1, None) for b in e["prompt_buckets"]]
    prev = 0
    for pb in eng._page_buckets:
        first_row = prev * ps + 1               # a context inside (prev, pb]
        prompt = max(1, min(top, first_row))
        need = max(burst + 1, first_row - prompt + 1)
        if first_row <= reach and prompt + need <= e["max_len"]:
            plans.append((prompt, need, pb))
        prev = pb
    took = []
    for i, (plen, n_out, _) in enumerate(plans):
        t0 = hs.now()
        eng.add_request(gen.prompt_ids(seed, 10 ** 6 + i, plen,
                                       cfg["vocab_size"]),
                        max_new_tokens=n_out)
        eng.run()
        took.append(round(hs.now() - t0, 2))
    missing = {pb for _, _, pb in plans if pb} \
        - set(eng.stats["page_buckets_used"])
    if missing:
        raise RuntimeError(f"warm-up did not reach page buckets {missing}")
    eng.take_finished()
    return took


def prepare(ctx: dict) -> dict:
    """Set-up up to a warm, empty engine."""
    import jax
    cfg, traffic, seed = ctx["cfg"], ctx["traffic"], ctx["seed"]
    t0 = hs.now()
    weights = make_weights(cfg, seed)
    eng = families.of(cfg).engine(cfg, traffic, weights)
    jax.block_until_ready(weights)
    t1 = hs.now()
    took = warm_up(eng, traffic, cfg, seed)
    hs.say({"setup_phases_s": {"before_runner": t0 - ctx["t0"],
                               "weights_and_engine": t1 - t0,
                               "warm_up_requests": took}})
    return {"eng": faults.plant("serve", ctx.get("fault"), eng, cfg),
            "weights": weights}


def measure(state: dict, ctx: dict) -> dict:
    """Fill (closed), the window, and the drain (open) on a warm engine."""
    eng = state["eng"]
    cfg, traffic, seed = ctx["cfg"], ctx["traffic"], ctx["seed"]
    e, horizon = traffic["engine"], ctx["seconds"]
    closed = traffic["kind"] == "serve_closed"
    sched = gen.request_schedule(
        traffic, seed, horizon,
        count=traffic["arrivals"].get("requests") if closed else None)
    trk, nxt = Tracker(), 0
    steps_log: list[dict] = []

    def send(due_t):
        nonlocal nxt
        r = sched[nxt]
        trk.add(eng, gen.prompt_ids(seed, nxt, r["prompt_len"],
                                    cfg["vocab_size"]),
                r["output_len"], due_t, nxt)
        nxt += 1

    def top_up():
        while len(eng._queue) < traffic["arrivals"]["backlog"]:
            if nxt >= len(sched):
                raise RuntimeError("the backlog ran dry: raise "
                                   "arrivals.requests")
            send(hs.now())

    def step(t_open):
        d0 = eng.stats["decode_steps"]
        t0 = hs.now()
        with hs.span("eng_step"):
            eng.step()
        t1 = hs.now()
        steps_log.append({"t0": t0 - t_open, "t1": t1 - t_open,
                          "decode_steps": eng.stats["decode_steps"] - d0,
                          "queued": len(eng._queue), **trk.observe(t1)})

    t_fill = hs.now()
    if closed:              # fill: every slot occupied once
        while sum(r["seen"] > 0 for r in trk.recs) < e["max_batch"]:
            top_up()
            step(hs.now())
        steps_log.clear()
    stats0 = dict(eng.stats)
    counter = hs.CompileCounter()
    late: list[float] = []
    setup_s = hs.now() - ctx["t0"]
    with hs.traced_window(ctx["trace"]) as trace_dir, counter:
        t_open = hs.now()
        tokens0 = sum(r["seen"] for r in trk.recs)
        if closed:
            while hs.now() - t_open < horizon:
                top_up()
                step(t_open)
        else:
            while True:
                rel = hs.now() - t_open
                while nxt < len(sched) and sched[nxt]["due_s"] <= rel:
                    late.append(rel - sched[nxt]["due_s"])
                    send(t_open + sched[nxt]["due_s"])
                if rel >= horizon:
                    break
                if eng.pending == 0:
                    nxt_due = sched[nxt]["due_s"] if nxt < len(sched) \
                        else horizon
                    with hs.span("wait_due"):
                        time.sleep(max(0.0, min(nxt_due, horizon) - rel))
                    continue
                step(t_open)
        t_close = hs.now()
    window_s = t_close - t_open
    stats1 = dict(eng.stats)
    live = [r["prompt_len"] + r["seen"] for r in trk.active
            if r["seen"]]                # the cache the engine holds now

    drain_s = 0.0
    if not closed:          # the requests due in the window get their answer
        # the allowance runs from here: a traced run has just spent a while
        # writing its trace, which is no time the engine had
        t_drain = hs.now()
        while trk.active and hs.now() - t_drain < traffic["drain_allowance_s"]:
            step(t_open)
        drain_s = hs.now() - t_drain
    peak = hs.memory_peak_bytes(ctx["cell"]["chips"])

    # ---- what the window saw
    if closed:      # the requests the window retired
        counted = [r for r in trk.recs
                   if r["done_t"] is not None and r["done_t"] >= t_open]
        out_tokens = sum(r["seen"] for r in trk.recs) - tokens0
        e2e = {"output_tokens_per_s": stats.rate(out_tokens, t_open, t_close)}
    else:           # the requests due in the window
        counted, e2e = trk.recs, {}
    bad = [r for r in counted if r["done_t"] is not None and (
        r["req"].reason != "complete" or len(r["req"].out) != r["n_out"])]
    unfinished = [r for r in counted if r["done_t"] is None]
    failed = len(bad) + (0 if closed else len(unfinished))
    info = {"window_s": window_s, "fill_s": t_open - t_fill,
            "engine_steps": len(steps_log), "requests": len(counted),
            "failed": failed, "compilations_in_window": counter.count,
            "page_buckets_used": stats1["page_buckets_used"],
            "live_kv_bytes_at_close": families.of(cfg).held_bytes(
                cfg, sum(live), len(live))}
    took = [s["t1"] - s["t0"] for s in steps_log if s["t1"] <= window_s + 1e-9]
    if took:        # a slow run shows here whether every step was slow
        info["engine_step_s"] = {
            "min": min(took), "p50": stats.percentile(took, 50),
            "p90": stats.percentile(took, 90), "max": max(took)}
    if not closed:
        ok = [r for r in counted if r["first_t"] is not None]
        ttft = [(r["first_t"] - r["due_t"]) * 1e3 for r in ok]
        tpot = [(r["last_t"] - r["first_t"]) / (r["seen"] - 1) * 1e3
                for r in ok if r["seen"] > 1]
        if ttft and tpot:
            e2e = {"ttft_p95_ms": stats.percentile(ttft, 95),
                   "tpot_p95_ms": stats.percentile(tpot, 95)}
            info.update(ttft_p50_ms=stats.percentile(ttft, 50),
                        tpot_p50_ms=stats.percentile(tpot, 50))
        in_win = [s for s in steps_log if s["t1"] <= window_s]
        info.update(
            generator_lateness_ms={
                "p50": stats.percentile(late, 50) * 1e3 if late else None,
                "max": max(late) * 1e3 if late else None},
            unfinished_after_drain=len(unfinished), drain_s=drain_s,
            queued_mid_window=in_win[len(in_win) // 2]["queued"]
            if in_win else None,
            queued_at_close=in_win[-1]["queued"] if in_win else None,
            live_at_close=sum(1 for r in counted if r["done_t"] is None
                              or r["done_t"] > t_close))
    record = {
        "window_s": window_s,
        "steps": [s for s in steps_log if s["t1"] <= window_s + 1e-9],
        "stats_delta": {k: stats1[k] - stats0[k] for k in
                        ("bursts", "decode_steps", "prefills", "preemptions",
                         "admission_stalls")},
        "max_batch": e["max_batch"], "chips": ctx["cell"]["chips"],
        "page_size": e["page_size"]}
    info["stats_delta"] = record["stats_delta"]
    hs.say(info)
    finished = [{"prompt": list(r["req"].prompt), "out": list(r["req"].out)}
                for r in counted if r["done_t"] is not None and r not in bad]
    return {"setup_s": setup_s, "e2e": e2e, "record": record,
            "attempted": len(counted), "failed": failed, "finished": finished,
            "memory_peak_bytes": peak, "trace_dir": trace_dir,
            "compilations": counter.count, "info": info}


def run(ctx: dict) -> dict:
    state = prepare(ctx)
    obs = measure(state, ctx)

    # ---- correct: the reference over a sample, the program's state freed
    weights = state.pop("weights")
    state.clear()
    gc.collect()
    checks = hs.Checks(ctx["limits"]["limits"])
    sample = check.sample_requests(obs.pop("finished"), ctx["seed"],
                                   ctx["limits"]["sample_requests"])
    t_ref = hs.now()
    gaps = check.served_gaps(
        weights, ctx["cfg"], sample, ctx["control"],
        **{k: ctx["limits"][k] for k in ("pad_tokens", "pad_outputs")
           if k in ctx["limits"]})
    # no request to compare is no proof: not a number, so not correct
    checks.add("served_logit_gap_max",
               gaps["served"] if sample else float("nan"))
    checks.add("failed_requests", obs["failed"])
    checks.add("compilations_in_window", obs["compilations"])
    hs.say({"compared_requests": len(sample),
            "compared_tokens": gaps["tokens"],
            "reference_s": hs.now() - t_ref})
    control = None
    if ctx["control"]:
        control = hs.Checks(ctx["limits"]["limits"])
        control.add("served_logit_gap_max", gaps["control"])
    return {**obs, "checks": checks, "control": control}
