"""Share of the decode slots that emitted a token: decode tokens of the
window over (the engine's decode_steps counter x max_batch)."""


def read(env):
    rec = env["record"]
    slots = rec["stats_delta"]["decode_steps"] * rec["max_batch"]
    if slots <= 0:
        return None
    tokens = sum(n for s in rec["steps"] for _, n in s["decodes"])
    return 100.0 * tokens / slots
