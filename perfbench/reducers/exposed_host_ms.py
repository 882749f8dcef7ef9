"""Device-idle milliseconds a step that the program's own spans account
for. Every idle gap of the first chip inside the window goes, whole, to the
innermost program span over its middle (the rule by which the harness books
gaps to its own spans); the gaps that land in any span, summed, over the
count of `per` spans. Also printed, finer: the same idle seconds split
along each gap over the innermost span at every instant, which says what
the host was doing while the device waited. Host and device planes of one
trace disagree by about a millisecond on a TPU v5e (tools/
span_clock_check.py), so that split is good to a millisecond a gap."""
import bisect
from collections import defaultdict

from .. import harness as hs
from . import _program

OUTSIDE = "outside_program_spans"


def gaps_by_span(tr, ps) -> tuple[dict, dict]:
    """(idle seconds by the span over each gap's middle, idle seconds by
    the span over each instant of each gap)."""
    lo, hi = ps.window
    chip = sorted(tr.ops)[0]
    edges = [lo] + [t for iv in tr.busy(chip, lo, hi) for t in iv] + [hi]
    spans = sorted((s, e, r.name) for s, e, r in ps.rows
                   if e > lo and s < hi and not r.name.startswith("compile."))
    starts = [s for s, _, _ in spans]

    def innermost(t):
        at = bisect.bisect_right(starts, t)
        # spans of one thread nest: the few that start last before t are
        # the only candidates
        cover = [sp for sp in spans[max(0, at - 16):at] if sp[1] >= t]
        return min(cover, key=lambda sp: sp[1] - sp[0])[2] if cover \
            else OUTSIDE

    whole: dict = defaultdict(float)
    along: dict = defaultdict(float)
    for i in range(0, len(edges), 2):
        s, e = edges[i], edges[i + 1]
        if e <= s:
            continue
        whole[innermost((s + e) / 2)] += e - s
        a, b = bisect.bisect_left(starts, s), bisect.bisect_right(starts, e)
        cuts = sorted({s, e} | {t for sp in spans[max(0, a - 16):b]
                                for t in sp[:2] if s < t < e})
        for t0, t1 in zip(cuts, cuts[1:]):
            along[innermost((t0 + t1) / 2)] += t1 - t0
    return dict(whole), dict(along)


def read(env, per):
    tr, ps = env.get("trace"), _program.program_spans(env)
    if tr is None or ps is None or not tr.ops:
        return None
    steps = len(ps.inside(per))
    if not steps:
        return None
    whole, along = gaps_by_span(tr, ps)
    hs.say({"exposed_host_s_by_span": whole,
            "exposed_host_s_along_gaps": along, "steps": steps})
    return 1e3 * sum(t for n, t in whole.items() if n != OUTSIDE) / steps
