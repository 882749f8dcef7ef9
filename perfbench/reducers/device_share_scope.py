"""Share of the device's busy seconds under one `jax.named_scope` of the
program: the self seconds of the operations whose op_name path holds the
scope (forward, backward and recompute alike), over busy seconds. Printed
once a run: the split over all of `scopes`, what no scope covers, and the
scopes of the largest operations; and for each scope read, what it holds by
the primitive at the end of the path (a gather beside the products that
read what it gathered)."""
from .. import harness as hs
from . import _program


def split(rows, scopes) -> dict:
    """scope -> {total, backward, recompute} seconds; "(none)" the rest."""
    pats = [(s, _program.scope_pattern(s)) for s in scopes]
    out = {s: {"total": 0.0, "backward": 0.0, "recompute": 0.0}
           for s in list(scopes) + ["(none)"]}
    for secs, _, _, op in rows:
        name = next((s for s, p in pats if op and p.search(op)), "(none)")
        out[name]["total"] += secs
        if op and "rematted_computation" in op:
            out[name]["recompute"] += secs
        elif op and "transpose(" in op:
            out[name]["backward"] += secs
    return out


def read(env, scope, scopes):
    rows, busy = _program.op_rows(env), env.get("busy")
    if not rows or not busy or not busy[0]:
        return None
    if "_scope_split" not in env:
        env["_scope_split"] = split(rows, scopes)
        total = sum(r[0] for r in rows)
        hs.say({"device_share_by_scope": {
            s: {k: 100.0 * v / total for k, v in d.items()}
            for s, d in env["_scope_split"].items()},
            "largest_ops": [[instr, op, secs]
                            for secs, _, instr, op in rows[:8]]})
    seconds = env["_scope_split"][scope]["total"]
    if not seconds:
        return None
    pat, inside = _program.scope_pattern(scope), {}
    for secs, _, _, op in rows:
        if op and pat.search(op):
            prim = op.rsplit("/", 1)[-1]
            inside[prim] = inside.get(prim, 0.0) + secs
    top = sorted(inside.items(), key=lambda kv: -kv[1])[:5]
    hs.say({"device_share_inside": scope,
            "by_primitive": {k: 100.0 * v / busy[0] for k, v in top}})
    return 100.0 * seconds / busy[0]
