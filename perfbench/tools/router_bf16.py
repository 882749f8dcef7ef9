"""A second control of `correct` for a cell with routed experts: the PROGRAM
with its router's scores in bfloat16, the nearest precision below the
float32 the configuration states for them.

    python3 -m perfbench.tools.router_bf16 --workload <cell> --seed <n> ...

takes `perfbench.run`'s arguments and prints its lines; the run has to come
out as not correct. The program has no such option (its router is float32
and nothing else): the fault is planted from here, in the place of
`paddle_tpu.ops.moe_dropless.route`, before the engine is built. The driver
never runs this.
"""
from __future__ import annotations

import contextlib
import sys


def route_bf16(g, gate_w, gate_bias, config):
    """`moe_dropless.route` with the product, the scores and the biased
    scores the selection reads all bfloat16."""
    import jax
    import jax.numpy as jnp
    c, bf = config, jnp.bfloat16
    logits = jnp.matmul(g.astype(bf), gate_w.astype(bf))
    s = jax.nn.sigmoid(logits) if c.scoring_func == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    _, experts = jax.lax.top_k(s + gate_bias.astype(bf)[None],
                               c.num_experts_per_tok)
    w = jnp.take_along_axis(s, experts, axis=-1).astype(jnp.float32)
    if c.norm_topk_prob:
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-20)
    return experts.astype(jnp.int32), w * jnp.float32(c.routed_scaling_factor)


@contextlib.contextmanager
def planted():
    """The program's router replaced while the block runs. Programs traced
    before or inside it are dropped at both ends: a jitted step keeps the
    router it was traced with."""
    import jax
    from paddle_tpu.ops import moe_dropless
    sound = moe_dropless.route
    moe_dropless.route = route_bf16
    jax.clear_caches()
    try:
        yield
    finally:
        moe_dropless.route = sound
        jax.clear_caches()


def main(argv=None) -> int:
    from .. import run as prun
    with planted():
        return prun.main(argv)


if __name__ == "__main__":
    sys.exit(main())
