"""One module per model family, chosen by the configuration's `family` key.

Everything under perfbench/ that knows a model's shape lives in
perfbench/families/<family>.py (with its plain reference in
perfbench/ref/<family>.py); runners, the comparison and the readers ask the
family and keep what is the same for every model. A family answers, for the
configuration's plain dict `cfg` (families/llama.py is the pattern):

  the program    shapes(cfg) -> {leaf: shape} and GAINS (leaves made as gains
                 near 1 in float32); engine(cfg, traffic, weights) -> the
                 serving engine of the mix's `engine` settings;
                 train_step(cfg, job, mesh, make_weights) -> the compiled
                 step with the benchmark's weights in it
  the reference  reference() -> the module with served_logits,
                 loss_and_grads, loss_only, adamw_leaf, hashable;
                 layer_axes(leaf, ndim) -> the axes one norm a layer is
                 taken over (None: the leaf is whole)
  the work       by step, as (operations, bytes): prefill_work(cfg, t) of t
                 real tokens; burst_work(cfg, decode_steps, decodes) of one
                 burst, decodes = [(rows attended by a request's first new
                 token less one, its new tokens)]; train_flops_per_token(cfg,
                 seq_len); held_bytes(cfg, live_rows, n_live): cache and
                 state held for n_live requests of live_rows rows together;
                 train_attention_calls(cfg, batch, seq_len) -> [((batch,
                 heads, kv_heads, seq, head_dim), calls a step)]

Nothing of JAX or of the program is imported until a function that needs it
is called. There is no default family: a configuration says what it is.
"""
from __future__ import annotations

import importlib


def of(cfg: dict):
    """The module perfbench.families.<cfg["family"]>."""
    name, conf = cfg.get("family"), cfg.get("name")
    if not name:
        raise SystemExit(
            f'configuration {conf!r} names no family: add "family" to its '
            f"file under perfbench/configs/ (and configs/rehearse/)")
    mod = f"{__name__}.{name}"
    try:
        return importlib.import_module(mod)
    except ModuleNotFoundError as e:
        if e.name != mod:
            raise
        raise SystemExit(
            f"configuration {conf!r} is of family {name!r}: add "
            f"perfbench/families/{name}.py") from None
