"""What the readers of the program's own instrumentation share.

The program's spans come from its span API's public read in this process
(`paddle_tpu.observability.spans.records()`, wall-clock ns); the device side
from `env["trace"]`. Two things the harness's Trace does not keep are read
here from the same `.xplane.pb`, whose path is `env["xplane_path"]`:

  profile_start_time   a stat of the plane "Task Environment", wall-clock
                       ns (through jax.profiler.ProfileData): the trace's
                       events are ns from it, so a span lies at
                       (t_ns - profile_start_time) * 1e-9 on its timeline
  op_name              the trace names an operation by its HLO text, which
                       leaves the metadata out; the programs' HLO protos are
                       in the plane "/host:metadata", which ProfileData does
                       not open: they are read off the protobuf wire, and
                       there every instruction has its `jax.named_scope` path

A program without the span API, a run without a trace, or a trace without
those planes gives None, and the metric is left out.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

from .. import trace as tracemod

INSTR = re.compile(r"^%([\w.\-]+)")


# ------------------------------------------------------- protobuf wire

def _varint(buf, i):
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i
        shift += 7


def fields(buf, start=0, end=None):
    """(field number, value) of one message: an int for a varint, a
    (start, end) pair into `buf` for a length-delimited field; fixed-width
    fields are skipped."""
    i, end = start, len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        num, kind = key >> 3, key & 7
        if kind == 0:
            v, i = _varint(buf, i)
            yield num, v
        elif kind == 2:
            n, i = _varint(buf, i)
            yield num, (i, i + n)
            i += n
        elif kind == 1:
            i += 8
        elif kind == 5:
            i += 4
        else:
            raise ValueError(f"wire type {kind} at byte {i}")


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _module_op_names(buf, module) -> tuple[str, dict]:
    """(module name, {instruction name: op_name}) of one HloModuleProto. A
    fusion with no op_name of its own takes the commonest among the
    instructions of the computation it calls."""
    name, own, calls, by_comp = "", {}, {}, {}
    for num, v in fields(buf, *module):
        if num == 1:
            name = _text(buf, v)
        elif num == 3:                              # computations
            comp_id, instrs = None, []
            for n2, v2 in fields(buf, *v):
                if n2 == 5:
                    comp_id = v2
                elif n2 == 2:                       # instructions
                    iname = op = None
                    called = []
                    for n3, v3 in fields(buf, *v2):
                        if n3 == 1:
                            iname = _text(buf, v3)
                        elif n3 == 7:               # OpMetadata
                            for n4, v4 in fields(buf, *v3):
                                if n4 == 2:
                                    op = _text(buf, v4)
                        elif n3 == 38:              # called_computation_ids
                            if isinstance(v3, tuple):       # packed
                                j = v3[0]
                                while j < v3[1]:
                                    c, j = _varint(buf, j)
                                    called.append(c)
                            else:
                                called.append(v3)
                    own[iname] = op
                    calls[iname] = called
                    instrs.append(iname)
            by_comp[comp_id] = instrs

    def resolve(iname, depth=0):
        if own.get(iname) or depth > 4:
            return own.get(iname)
        votes: dict = defaultdict(int)
        for c in calls.get(iname, ()):
            for inner in by_comp.get(c, ()):
                op = resolve(inner, depth + 1)
                if op:
                    votes[op] += 1
        return max(votes, key=votes.get) if votes else None

    return name, {i: resolve(i) for i in own}


def read_op_names(path: str) -> dict:
    """{program name as the trace's module line has it: {instruction:
    op_name}}, from the HLO protos in the plane "/host:metadata"."""
    with open(path, "rb") as f:
        buf = f.read()
    out = {}
    for num, plane in fields(buf):
        if num != 1:
            continue
        name, metas = None, []
        for n2, v2 in fields(buf, *plane):
            if n2 == 2:
                name = _text(buf, v2)
            elif n2 == 4:
                metas.append(v2)
        if name != "/host:metadata":
            continue
        for entry in metas:             # map<int64, XEventMetadata>
            for n3, v3 in fields(buf, *entry):
                if n3 != 2:
                    continue
                program, protos = None, []
                for n4, v4 in fields(buf, *v3):
                    if n4 == 2:
                        program = _text(buf, v4)
                    elif n4 == 5:       # XStat: bytes_value is an HloProto
                        protos += [v5 for n5, v5 in fields(buf, *v4)
                                   if n5 == 6]
                for proto in protos:
                    for n6, v6 in fields(buf, *proto):
                        if n6 == 1:                 # hlo_module
                            out[program] = _module_op_names(buf, v6)[1]
    return out


def read_xplane_meta(path: str, window=None) -> dict | None:
    """{"profile_start_ns": the stat `profile_start_time` of the plane
    "Task Environment" (wall-clock ns), "op_names": read_op_names}. With
    `window`, None unless the file's own `perfbench.window` annotation is
    that window: the file is then the run's, not a neighbour's."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    start_ns, marks = None, []
    for plane in pd.planes:
        if plane.name == "Task Environment":
            start_ns = dict(plane.stats).get("profile_start_time")
        elif plane.name.startswith("/host:") and window is not None:
            marks += [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                      for line in plane.lines for e in line.events
                      if e.name == tracemod.WINDOW_SPAN]
    if window is not None and not any(
            abs(s - window[0]) < 1e-6 and abs(e - window[1]) < 1e-6
            for s, e in marks):
        return None
    return {"profile_start_ns": start_ns, "op_names": read_op_names(path)}


def meta(env) -> dict | None:
    """read_xplane_meta of the run's trace, `env["xplane_path"]`, read once
    a run; None unless its window annotation is `env["trace"]`'s."""
    tr, path = env.get("trace"), env.get("xplane_path")
    if tr is None:
        return None
    if "_xplane_meta" not in env:
        try:
            env["_xplane_meta"] = read_xplane_meta(path, tr.window()) \
                if path else None
        except (OSError, ValueError, IndexError):
            env["_xplane_meta"] = None
    return env["_xplane_meta"]


# ------------------------------------------------------ program spans

def program_records():
    """The span ring's public read, or None where the program has none."""
    try:
        from paddle_tpu.observability import spans
        return spans.records()
    except (ImportError, AttributeError):
        return None


class ProgramSpans:
    """The program's spans on the trace's clock (seconds), and the window."""

    def __init__(self, records, start_ns: int, window):
        self.window = window
        self.rows = [((r.t0_ns - start_ns) * 1e-9, (r.t1_ns - start_ns) * 1e-9,
                      r) for r in records]
        self.children: dict = defaultdict(list)
        for row in self.rows:
            self.children[row[2].parent].append(row)

    def inside(self, name: str | None = None):
        """Spans that began inside the window (named `name`, or all)."""
        lo, hi = self.window
        return [row for row in self.rows if lo <= row[0] < hi
                and (name is None or row[2].name == name)]

    def before(self, names):
        """Spans named in `names` that ended before the window opened."""
        return [row for row in self.rows
                if row[2].name in names and row[1] <= self.window[0]]

    def self_seconds(self, row) -> float:
        s, e, r = row
        return (e - s) - sum(min(ce, e) - max(cs, s)
                             for cs, ce, _ in self.children.get(r.id, ())
                             if min(ce, e) > max(cs, s))


def program_spans(env) -> ProgramSpans | None:
    """None too where the trace has no device plane (a rehearsal on the
    CPU): the spans are read against the device's timeline, and a run
    without one reports nothing under these names."""
    if "_program_spans" not in env:
        tr = env.get("trace")
        recs, m = program_records(), meta(env)
        w = tr.window() if tr is not None and tr.ops else None
        ok = recs and m and m["profile_start_ns"] and w
        env["_program_spans"] = ProgramSpans(
            recs, m["profile_start_ns"], w) if ok else None
    return env["_program_spans"]


# -------------------------------------------------- operations by scope

def scope_pattern(scope: str):
    """A jax.named_scope as a component of an op_name path, bare or wrapped
    by a transform: .../attn/..., jvp(attn), transpose(jvp(attn))."""
    return re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?:[/)]|$)")


def op_rows(env) -> list | None:
    """[(self seconds in the window, program, instruction, op_name | None)]
    of the first chip's operations; None without names to give them."""
    if "_op_rows" in env:
        return env["_op_rows"]
    env["_op_rows"] = None
    tr, m = env.get("trace"), meta(env)
    if tr is None or not tr.ops or not m or not m["op_names"]:
        return None
    w = tr.window()
    chip = sorted(tr.ops)[0]
    mods = sorted(tr.modules.get(chip, []))
    starts = [s for s, _, _ in mods]

    def program_of(t):
        i = bisect.bisect_right(starts, t) - 1
        return mods[i][2] if i >= 0 and t < mods[i][1] else None

    events = []
    for s, e, text in tr.ops[chip]:
        if min(e, w[1]) <= max(s, w[0]):
            continue
        im = INSTR.match(text)
        events.append((max(s, w[0]), min(e, w[1]),
                       (program_of(s), im.group(1) if im else text[:40])))
    rows = []
    for (program, instr), secs in tracemod.self_times(events).items():
        rows.append((secs, program, instr,
                     m["op_names"].get(program, {}).get(instr)))
    env["_op_rows"] = sorted(rows, key=lambda r: -r[0])
    return env["_op_rows"]
