"""Host milliseconds of one of the program's spans per step of the window:
the self time of every `span` that began inside the window (its duration
less what its child spans cover) over the count of `per` spans that began
there. Read from the program's span ring on the trace's clock."""
from . import _program


def read(env, span, per):
    ps = _program.program_spans(env)
    if ps is None:
        return None
    steps = len(ps.inside(per))
    rows = ps.inside(span)
    if not steps or not rows:
        return None
    return 1e3 * sum(ps.self_seconds(r) for r in rows) / steps
