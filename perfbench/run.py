"""The benchmark's command.

    python3 -m perfbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, JAX imported once, no child that needs the chip. It finds the
cell's configuration, traffic mix, limits and per-layer readers by the names
in BENCHMARK.json, runs the runner of the mix's kind, and prints as the last
line of standard output one JSON object: correct, attempted, failed,
metrics, device, with --trace 1 also breakdown, and last the numbers
compared, each beside its limit. With no TPU, or fewer chips than the cell
asks for, it exits non-zero and prints no result.

    --rehearse       tiny configurations from perfbench/configs/rehearse/ on
                     whatever backend JAX has; every number is marked as a
                     rehearsal and none is a device metric
    --keep-trace D   copy the traced run's .xplane.pb into D, and leave the
                     profiler's own directory where it is (a run without it
                     removes that directory once the readers have run)
    --control int8   the control of `correct`: the reference in the next
                     lower precision put where the program's answers stand;
                     it has to come out as not correct (never run by the
                     driver)
    --fault NAME     the cell with its timed path broken underneath
                     (perfbench/faults.py): it has to come out as not
                     correct too (never run by the driver)
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

if __package__ in (None, ""):       # run as a file: python3 perfbench/run.py
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    __package__ = "perfbench"

from . import faults, harness as hs  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(prog="perfbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", choices=("int8",), default=None)
    ap.add_argument("--fault", choices=faults.NAMES, default=None)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the traced run's .xplane.pb into DIR")
    return ap.parse_args(argv)


def per_layer(ctx: dict, result: dict, dev: dict) -> tuple[dict, dict, dict]:
    """(metrics, device additions, breakdown) of a traced run: each metric's
    own reader takes it from the runner's record and the trace; one that
    finds nothing to read is left out, and so is every device-trace metric
    of a trace that was cut short."""
    from . import arith, trace
    tr = None
    path = trace.find_xplane(result["trace_dir"]) if result["trace_dir"] \
        else None
    if path:
        tr = trace.Trace(path)
        if ctx.get("keep_trace"):
            os.makedirs(ctx["keep_trace"], exist_ok=True)
            shutil.copy(path, ctx["keep_trace"])
    peaks = None if ctx["rehearse"] else arith.load_peaks(dev["kind"])
    busy = tr.busy_seconds() if tr else None    # (busy_s, window_s)
    cut = tr.cut_short() if tr else None
    if cut:
        hs.say({"trace_cut_short": f"the last device event ends {cut:.3f} s "
                f"before the window's {busy[1]:.3f} s do: the profiler's "
                "buffer was full, so the device-trace metrics are left out"})
    env = {"record": result["record"], "trace": tr, "busy": busy,
           "xplane_path": path, "cfg": ctx["cfg"], "traffic": ctx["traffic"],
           "peaks": peaks}
    out = {}
    for m in hs.metrics_of(ctx["bench"], ctx["cell"], "per_layer"):
        if cut and m["source"] == "device_trace":
            continue
        spec = hs.load_json("metrics", m["name"] + ".json")
        reader = importlib.import_module(
            f"{__package__}.reducers.{spec['reducer']}")
        value = reader.read(env, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    extra, brk = {}, {}
    if busy:
        extra = {"busy_s": busy[0], "window_s": busy[1]}
        brk = trace.breakdown(tr)
    if result["trace_dir"] and not ctx.get("keep_trace"):
        shutil.rmtree(result["trace_dir"], ignore_errors=True)
    return out, extra, brk


def drive(ctx: dict, dev: dict) -> dict:
    """Everything after the look for a chip: run the cell's runner and
    build the result's line."""
    runner = importlib.import_module(
        f"{__package__}.runners.{ctx['traffic']['kind']}")
    result = runner.run(ctx)

    mark = "rehearsal." if ctx["rehearse"] else ""
    units = {m["name"]: m["unit"] for m in ctx["bench"]["end_to_end"]}
    device = {**dev, "memory_peak_bytes": result["memory_peak_bytes"]}
    line = {"correct": False, "attempted": result["attempted"],
            "failed": result["failed"], "metrics": {}, "device": device}
    if ctx["trace"]:
        metrics, extra, brk = per_layer(ctx, result, dev)
        device.update(extra)
        if brk:
            line["breakdown"] = brk
    else:
        e2e = {"setup_s": result["setup_s"], **result["e2e"]}
        wanted = [m["name"] for m in
                  hs.metrics_of(ctx["bench"], ctx["cell"], "end_to_end")]
        metrics = {n: {"value": e2e[n], "unit": units[n]}
                   for n in wanted if n in e2e}
    line["metrics"] = {mark + n: v for n, v in metrics.items()}
    if ctx["rehearse"]:
        line["rehearsal"] = True

    checks, control = result.get("checks"), result.get("control")
    if control is not None:     # the control has to come out as not correct
        control.report("control")
        line["control_correct"] = control.correct
        line["control_compared"] = control.rows
    if checks is not None:
        line["correct"] = checks.correct
        line["compared"] = checks.rows      # last: each number, its limit
        sys.stdout.flush()
        checks.report()
    return line


def context(workload: str, seed: int = 0, seconds: float | None = None,
            trace: bool = False, rehearse: bool = False, control=None,
            keep_trace=None, t0: float | None = None, fault=None) -> dict:
    ctx = hs.load_cell(workload, rehearse)
    ctx.update(seed=seed, trace=bool(trace), rehearse=rehearse,
               control=control, fault=fault, keep_trace=keep_trace,
               t0=hs.now() if t0 is None else t0,
               seconds=float(ctx["bench"]["run_seconds"]
                             if seconds is None else seconds))
    return ctx


def main(argv=None) -> int:
    args = parse(argv)
    ctx = context(args.workload, args.seed, args.seconds, args.trace,
                  args.rehearse, args.control, args.keep_trace, T0,
                  args.fault)

    from paddle_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    dev = hs.device_info()
    hs.require_chips(dev, ctx["cell"]["chips"], args.rehearse)
    hs.say({"workload": args.workload, "seed": args.seed,
            "seconds": ctx["seconds"], "trace": args.trace,
            "rehearsal": args.rehearse, "control": args.control,
            "fault": args.fault,
            "compile_cache": cache_dir, **dev})
    print(json.dumps(drive(ctx, dev)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
