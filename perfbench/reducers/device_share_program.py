"""Share of the device's busy seconds inside the programs whose name on the
trace's module line matches `program`."""
import re

from .. import trace as tracemod


def read(env, program):
    tr, busy = env.get("trace"), env.get("busy")
    if tr is None or not busy or not busy[0] or not tr.modules:
        return None
    w, rx = tr.window(), re.compile(program)
    chip = sorted(tr.modules)[0]
    mine = tracemod.clip(tracemod.union(
        (s, e) for s, e, n in tr.modules[chip] if rx.search(n)), *w)
    if not mine:
        return None
    inside = sum(tracemod.total(tracemod.clip(tr.busy(chip, *w), s, e))
                 for s, e in mine)
    return 100.0 * inside / busy[0] if inside else None
