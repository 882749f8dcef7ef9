"""A scope's share of its roofline: the least seconds the chip could take
for the work the window needs under one `jax.named_scope` of the program,
over the device seconds its operations took there. The work is what the
configuration's family counts for the scope from the runner's steps
(`scope_work(cfg, scope, steps)`: operations and bytes, the same whatever
implements it), so a later kernel under the same scope is judged on the
same yardstick. A family that counts nothing for the scope, a program
without the scope, or a run without a trace gives None."""
from .. import arith, families, harness as hs
from . import _program


def scope_seconds(rows, scope) -> float:
    pat = _program.scope_pattern(scope)
    return sum(secs for secs, _, _, op in rows if op and pat.search(op))


def read(env, scope):
    rows, peaks = _program.op_rows(env), env.get("peaks")
    count = getattr(families.of(env["cfg"]), "scope_work", None)
    if not rows or peaks is None or count is None:
        return None
    seconds = scope_seconds(rows, scope)
    work = count(env["cfg"], scope, env["record"]["steps"])
    if not seconds or not work or not (work[0] or work[1]):
        return None
    least, bound = arith.roofline_seconds(*work, peaks)
    hs.say({"scope_roofline": scope, "device_s": seconds, "least_s": least,
            "bound": bound, "flops": work[0], "bytes": work[1]})
    return 100.0 * least / seconds
