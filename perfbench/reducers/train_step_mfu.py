"""Model FLOP/s utilization of the train step: the family's operations a
token (recomputation not counted) x the tokens of the steps the traced
window completed, over chips x peak x the traced window."""
from .. import families


def read(env):
    busy = env["busy"]
    if not busy or env["peaks"] is None:
        return None
    rec = env["record"]
    tokens = len(rec["step_t"]) * rec["batch"] * rec["seq_len"]
    flops = tokens * families.of(env["cfg"]).train_flops_per_token(
        env["cfg"], rec["seq_len"])
    return 100.0 * flops / (env["peaks"]["bf16_flops_per_s"] * busy[1]
                            * rec["chips"])
