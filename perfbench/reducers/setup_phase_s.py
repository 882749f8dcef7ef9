"""Seconds of set-up inside the program's spans named in `spans`: the union
of those that ended before the window opened (a function traced while
another is lowered counts once). import.paddle_tpu is the package's import;
compile.trace / compile.lower / compile.backend are JAX's own compile-path
events, one span each (backend: compiling, or fetching from the cache)."""
from .. import harness as hs, trace as tracemod
from . import _program


def read(env, spans):
    ps = _program.program_spans(env)
    if ps is None:
        return None
    rows = ps.before(set(spans))
    if not rows:
        return None
    longest = sorted(rows, key=lambda r: r[0] - r[1])[:3]
    hs.say({"setup_phase": spans, "spans": len(rows), "longest": [
        [r.name, (r.args or {}).get("fun"), e - s] for s, e, r in longest]})
    return tracemod.total(tracemod.union((s, e) for s, e, _ in rows))
