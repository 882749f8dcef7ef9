"""1 - real / padded prompt tokens of the prefills the window dispatched:
the program counts both where it picks the bucket (the counters
serve.prefill_tokens_real / _padded) and hangs each admission's share on
its `serve.admit` span, which is what places them in the window."""
from .. import harness as hs
from . import _program


def read(env):
    ps = _program.program_spans(env)
    if ps is None:
        return None
    args = [r.args or {} for _, _, r in ps.inside("serve.admit")]
    real = sum(a.get("real", 0) for a in args)
    padded = sum(a.get("padded", 0) for a in args)
    if not padded:
        return None
    hs.say({"prefill_tokens": {"real": real, "padded": padded,
                               "prefills": sum(a.get("prefills", 0)
                                               for a in args)}})
    return 100.0 * (1.0 - real / padded)
