"""From a profiler trace (.xplane.pb) to numbers.

jax.profiler.ProfileData reads the file with nothing but JAX. A TPU's plane
is named "/device:TPU:<n>"; its line "XLA Ops" holds one event per executed
HLO operation (a while loop's event encloses its body's events), its line
"XLA Modules" one per executed program. The harness's own spans
(jax.profiler.TraceAnnotation, names starting "perfbench.") are on the host
plane, on the same clock.

  busy     union of the intervals of the device's operations
  window   the span of the annotation "perfbench.window"
  op time  an operation's self time: its duration less what its children
           on the same line cover, so a loop is not counted beside its body
  gaps     idle intervals of the device inside the window, each given to the
           innermost harness span that covers its middle
  cut      the profiler's buffer holds a fixed number of events; when it is
           full the device's later operations are lost, and every share of
           busy time is wrong (PERF.md section 6, PR 28)
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "perfbench."
WINDOW_SPAN = "perfbench.window"
CUT_TAIL = (0.05, 4.0)      # of the window; times the longest gap before it


def find_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def self_times(events) -> dict:
    """name -> seconds of self time. events: (start, end, name), any order.
    An event that starts inside another and ends inside it is its child."""
    out: dict = defaultdict(float)
    stack: list[list] = []      # [end, name, start, covered_by_children]

    def close(item):
        end, name, start, covered = item
        out[name] += (end - start) - covered
        if stack:
            stack[-1][3] += end - start

    for s, e, name in sorted(events, key=lambda t: (t[0], -t[1])):
        while stack and s >= stack[-1][0]:
            close(stack.pop())
        stack.append([e, name, s, 0.0])
    while stack:
        close(stack.pop())
    return dict(out)


class Trace:
    """The events of one .xplane.pb, in seconds from the trace's start."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        self.ops: dict[int, list] = {}        # chip -> (start, end, name)
        self.modules: dict[int, list] = {}
        self.spans: list = []                 # harness spans, all threads
        names: dict = {}    # an operation's name is its whole HLO text, the
        # same for every execution: keep one copy
        for plane in pd.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                chip = int(m.group(1))
                for line in plane.lines:
                    if line.name not in (OPS_LINE, MODULES_LINE):
                        continue
                    evs = [(e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9,
                            names.setdefault(e.name, e.name))
                           for e in line.events]
                    (self.ops if line.name == OPS_LINE
                     else self.modules)[chip] = evs
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(SPAN_PREFIX):
                            self.spans.append(
                                (e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9, e.name))
        self.spans.sort()
        self._busy: dict[int, list] = {}      # chip -> merged intervals

    # -- window and busy ---------------------------------------------------
    def window(self) -> tuple[float, float] | None:
        """The harness's window span; without one, the span of the device's
        operations."""
        w = [(s, e) for s, e, n in self.spans if n == WINDOW_SPAN]
        if w:
            return min(s for s, _ in w), max(e for _, e in w)
        evs = [ev for chip in self.ops.values() for ev in chip]
        if not evs:
            return None
        return min(s for s, _, _ in evs), max(e for _, e, _ in evs)

    def busy(self, chip: int, lo: float, hi: float):
        if chip not in self._busy:      # merged once: a trace is read-only
            self._busy[chip] = union((s, e) for s, e, _ in self.ops[chip])
        return clip(self._busy[chip], lo, hi)

    def busy_seconds(self) -> tuple[float, float] | None:
        """(busy seconds averaged over the chips, window seconds)."""
        w = self.window()
        if w is None or not self.ops:
            return None
        per_chip = [total(self.busy(c, *w)) for c in sorted(self.ops)]
        return sum(per_chip) / len(per_chip), w[1] - w[0]

    def cut_short(self) -> float | None:
        """The idle seconds at the window's end, where they are over 5 % of
        the window and over four times the longest idle gap before them: the
        last device event then ends well before the window does, as no
        steady run's does. None where the trace is whole."""
        w = self.window()
        if w is None or not self.ops:
            return None
        busy = self.busy(sorted(self.ops)[0], *w)
        if not busy:
            return w[1] - w[0]
        tail = w[1] - busy[-1][1]
        inner = max([busy[0][0] - w[0]] + [b[0] - a[1] for a, b
                                          in zip(busy, busy[1:])])
        cut = tail > max(CUT_TAIL[0] * (w[1] - w[0]), CUT_TAIL[1] * inner)
        return tail if cut else None

    # -- where the time goes ----------------------------------------------
    def op_seconds(self, line: str = "ops") -> dict:
        """name -> self seconds inside the window, averaged over chips."""
        w = self.window()
        src = self.ops if line == "ops" else self.modules
        if w is None or not src:
            return {}
        acc: dict = defaultdict(float)
        for evs in src.values():
            inside = [(max(s, w[0]), min(e, w[1]), n) for s, e, n in evs
                      if min(e, w[1]) > max(s, w[0])]
            for n, t in self_times(inside).items():
                acc[n] += t / len(src)
        return dict(acc)

    def matching_seconds(self, pattern: str, within: str | None = None):
        """(seconds, calls) of operations whose name matches `pattern`, on
        the first chip; `within` keeps those that ran inside a program
        whose name matches it."""
        w = self.window()
        if w is None or not self.ops:
            return None
        chip = sorted(self.ops)[0]
        rx = re.compile(pattern)
        mods = None
        if within is not None:
            wx = re.compile(within)
            mods = union((s, e) for s, e, n in self.modules.get(chip, [])
                         if wx.search(n))
        secs, calls = 0.0, 0
        for s, e, n in self.ops[chip]:
            if s < w[0] or e > w[1] or not rx.search(n):
                continue
            if mods is not None and not any(a <= s and e <= b
                                            for a, b in mods):
                continue
            secs += e - s
            calls += 1
        return (secs, calls) if calls else None

    def idle_gaps(self) -> dict:
        """label -> idle seconds of the first chip inside the window, by the
        innermost harness span over each gap's middle."""
        w = self.window()
        if w is None or not self.ops:
            return {}
        chip = sorted(self.ops)[0]
        busy = self.busy(chip, *w)
        edges = [w[0]] + [t for iv in busy for t in iv] + [w[1]]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        spans = [sp for sp in self.spans if sp[2] != WINDOW_SPAN]
        starts = [sp[0] for sp in spans]
        out: dict = defaultdict(float)
        for s, e in gaps:
            mid = (s + e) / 2
            hi = bisect.bisect_right(starts, mid)
            # spans of one thread nest: the few that start last before the
            # middle are the only candidates
            cover = [sp for sp in spans[max(0, hi - 16):hi] if sp[1] >= mid]
            label = (min(cover, key=lambda sp: sp[1] - sp[0])[2]
                     if cover else "outside_harness_spans")
            out[label] += e - s
        return dict(out)


_HLO = re.compile(r"^(%[\w.\-]+) = \(?([a-z0-9]+\[[\d,]*\])?.*?\b([a-z][a-z\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(hlo: str) -> str:
    """The trace names an operation by its whole HLO text: keep the
    instruction's name, its opcode (a custom call's target) and the first
    result's shape."""
    m = _HLO.match(hlo)
    if not m:
        return hlo[:120]
    name, shape, op = m.groups()
    t = _TARGET.search(hlo)
    return " ".join(x for x in (name, t.group(1) if t else op, shape) if x)


def breakdown(tr: Trace, top: int = 10) -> dict:
    ops = sorted(tr.op_seconds().items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(tr.idle_gaps().items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[short_name(n), t] for n, t in ops],
            "idle_gaps": [[n, t] for n, t in gaps]}
