"""Share of the traced window that the program spent inside one of its
spans (clipped to the window), from the span ring on the trace's clock."""
from . import _program


def read(env, span):
    ps = _program.program_spans(env)
    if ps is None:
        return None
    lo, hi = ps.window
    rows = [r for r in ps.rows if r[2].name == span and r[1] > lo
            and r[0] < hi]
    if not rows:
        return None
    inside = sum(min(e, hi) - max(s, lo) for s, e, _ in rows)
    return 100.0 * inside / (hi - lo)
