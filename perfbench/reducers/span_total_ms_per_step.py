"""Host milliseconds a step the program spent in one of its spans that
marks a rare event: the whole duration of every `span` that began inside
the window over the count of `per` spans that began there, and 0 where
none began (a collection of generation 1 or 2, `gc.collect`, comes a few
times a window or not at all). Only a program that counts the event (its
counter `counter` exists) reports it: an older program gives None. The
reader prints why it gives None and never raises."""
from .. import harness as hs
from . import _program


def program_counters() -> dict:
    """The program's counters, {} where it has no registry."""
    try:
        from paddle_tpu.observability import metrics
        return metrics.counter_values()
    except (ImportError, AttributeError):
        return {}


def _read(env, span, per, counter):
    if counter not in program_counters():
        return f"the program counts no {counter}"
    ps = _program.program_spans(env)
    steps = len(ps.inside(per)) if ps is not None else 0
    if not steps:
        return f"no {per} span in a traced window"
    return 1e3 * sum(e - s for s, e, _ in ps.inside(span)) / steps


def read(env, span, per, counter):
    try:
        got = _read(env, span, per, counter)
    except Exception as e:      # noqa: BLE001 - a reader never raises
        got = f"not read: {e!r}"
    if isinstance(got, str):
        hs.say({"span_total_ms_per_step": f"{span}: {got}"})
        return None
    return got
