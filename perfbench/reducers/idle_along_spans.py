"""Device-idle milliseconds a step laid along the program's spans, on the
span clock fitted to the device's.

Host and device planes of a v5e trace disagree by about a millisecond
(tools/span_clock_check.py), as long as a phase of the dispatch. So the
spans are first shifted by an offset delta (device time = span time +
delta) taken between two bounds, from the window's `serve.dispatch.launch`
spans paired with the device's bursts: the modules on the first chip whose
name matches `program`, paired by order (the i-th launch with the (i + k)-th
burst, k the commonest shift of index between a launch and the burst that
starts nearest it), never with "the first device operation after": in the
hybrid cell prefills fill the device between bursts.

  upper  a burst cannot start before its launch began:
         delta <= min over pairs (burst start - launch start)
  lower  the step's `serve.readback` cannot end before its burst's last
         operation: delta >= max over pairs (burst end - readback end)

delta is taken at the lower bound. A step with no prefill reads back
nothing but its burst's few hundred bytes, so its `device_get` returns
within tens of microseconds of the burst's end, and the max over pairs
finds such a step; the launch span begins with the host's handling of the
call's arguments, which comes before anything is enqueued (1-1.7 ms for
the per-layer weight tuples on a TPU v5e: bounds 1.5-1.7 ms apart in
every serving cell, PERF.md section 6), so the upper bound lies that far
above delta. Where the pairs are fewer than MIN_PAIRS, the name matches
no module, or the bounds cross, nothing is shifted, the line
`span_clock_fit` says why, and the reading is still returned.

The split is exposed_host_ms.gaps_by_span's "along" (every instant of
every idle gap of the window goes to the innermost span over it; the
gaps are the device's, so the labels sum to the idle time of the window),
summed over the labels in `spans`, or over every label not in
`other_than` (the step's own time, `serve.admit`, no program span), over
the count of `per` spans begun in the window. A program without the
dispatch's phases (no `serve.dispatch.launch` in the window) reports
nothing: the reader returns None, prints why, and never raises.
"""
import bisect
import re
from collections import Counter

from .. import harness as hs
from . import _program
from .exposed_host_ms import gaps_by_span

LAUNCH, READBACK = "serve.dispatch.launch", "serve.readback"
BURST = r"^jit_llama_paged_decode_burst\("
MIN_PAIRS = 8


def pair_bursts(launches, readbacks, bursts) -> list:
    """[(launch start, readback end, burst start, burst end)], each on its
    own clock. `launches`, `readbacks`, `bursts`: sorted (start, end); a
    launch's readback is the first to start after the launch ends."""
    if not launches or not bursts:
        return []
    starts = [s for s, _ in bursts]
    shifts: Counter = Counter()
    for i, (s, _) in enumerate(launches):
        j = bisect.bisect_left(starts, s)
        near = min((x for x in (j - 1, j) if 0 <= x < len(starts)),
                   key=lambda x: abs(starts[x] - s))
        shifts[near - i] += 1
    k = shifts.most_common(1)[0][0]
    rb_starts = [s for s, _ in readbacks]
    out = []
    for i, (ls, le) in enumerate(launches):
        j = bisect.bisect_left(rb_starts, le)
        if 0 <= i + k < len(bursts) and j < len(readbacks):
            out.append((ls, readbacks[j][1]) + tuple(bursts[i + k]))
    return out


def fit_offset(pairs, min_pairs: int = MIN_PAIRS) -> dict:
    """{"pairs", "lower", "upper", "delta"} in seconds; "delta" None and a
    "why" where no offset can be told."""
    fit: dict = {"pairs": len(pairs), "delta": None}
    if len(pairs) < min_pairs:
        fit["why"] = f"{len(pairs)} launch-burst pairs, fewer than " \
                     f"{min_pairs}"
        return fit
    fit["upper"] = min(bs - ls for ls, _, bs, _ in pairs)
    fit["lower"] = max(be - rb for _, rb, _, be in pairs)
    if fit["lower"] > fit["upper"]:
        fit["why"] = "the bounds cross: some pair is no launch and its burst"
    else:
        fit["delta"] = fit["lower"]
    return fit


def _window(env, per: str):
    """(program spans, steps, launches), or None and a line that says why."""
    ps = _program.program_spans(env)
    if ps is None:
        return None
    steps = len(ps.inside(per))
    launches = sorted((s, e) for s, e, _ in ps.inside(LAUNCH))
    if not steps or not launches:
        hs.say({"idle_along_spans": f"no {per} or {LAUNCH} span in the "
                "window: nothing to read"})
        return None
    return ps, steps, launches


def _split(env, program: str, per: str):
    """(idle seconds by innermost fitted span, steps), once a run."""
    tr, got = env.get("trace"), _window(env, per)
    if got is None:
        return None
    ps, steps, launches = got
    lo = ps.window[0]
    readbacks = sorted((s, e) for s, e, r in ps.rows
                       if r.name == READBACK and s >= lo)
    rx = re.compile(program)
    chip = sorted(tr.ops)[0]
    bursts = sorted((s, e) for s, e, n in tr.modules.get(chip, [])
                    if rx.search(n))
    fit = fit_offset(pair_bursts(launches, readbacks, bursts))
    if not bursts:
        fit["why"] = f"no module on the device matches {program!r}"
    shift = fit["delta"] or 0.0
    start_ns = _program.meta(env)["profile_start_ns"]
    fitted = _program.ProgramSpans(_program.program_records(),
                                   start_ns - round(shift * 1e9), ps.window)
    _, along = gaps_by_span(tr, fitted)
    hs.say({"span_clock_fit": {**fit, "program": program,
                               "shifted_s": shift},
            "idle_s_along_fitted_spans": along, "steps": steps,
            # the ring still holds a span begun before the window: no
            # step of the window fell off it
            "ring_holds_window": min(s for s, _, _ in ps.rows) < lo})
    return along, steps


def read(env, per, spans=None, other_than=None, program=BURST):
    cache = env.setdefault("_idle_along", {})
    if (program, per) not in cache:
        try:
            cache[(program, per)] = _split(env, program, per)
        except Exception as e:      # noqa: BLE001 - a reader never raises
            hs.say({"idle_along_spans": f"not read: {e!r}"})
            cache[(program, per)] = None
    got = cache[(program, per)]
    if got is None:
        return None
    along, steps = got
    if spans is not None:
        secs = sum(along.get(n, 0.0) for n in spans)
    else:
        secs = sum(t for n, t in along.items() if n not in other_than)
    return 1e3 * secs / steps
