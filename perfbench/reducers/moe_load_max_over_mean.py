"""The busiest held expert's load over the mean held expert's, over the
bursts of the window: the program hangs on every `serve.dispatch_burst` span
what ITS burst put on the experts this chip holds, all layers together
(`moe_local`: the assignments; `moe_max`: those on the busiest expert), and
the engine says how many it holds (`eng.stats["moe_expert_tokens"]`, which
the runner does not keep, so the count is the configuration's `num_experts`:
the experts held). 1 is an even load; a grouped product's time follows the
rows of its busiest tile. A program without the arguments (no expert layer,
an older program) gives None."""
from .. import harness as hs
from . import _program


def read(env):
    ps = _program.program_spans(env)
    held = (env.get("cfg") or {}).get("num_experts")
    if ps is None or not held:
        return None
    args = [r.args or {} for _, _, r in ps.inside("serve.dispatch_burst")]
    args = [a for a in args if a.get("moe_local")]
    if not args:
        return None
    local = sum(a["moe_local"] for a in args)
    busiest = sum(a["moe_max"] for a in args)
    hs.say({"moe_assignments": {"bursts": len(args), "on_held": local,
                                "on_busiest": busiest, "held": held}})
    return busiest / (local / held)
