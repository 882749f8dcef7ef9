"""The dropless expert layer of a SPARSE FFN (``LlamaConfig.mlp_layer_types``):
what a serving prefill and a decode step run, one function for both.

For the layer's input ``g`` [N, D] (what ``block_in`` gives of the stream,
in float32)::

    s   = score(g W_r)                  float32, over ALL num_experts
          (sigmoid, or softmax: ``scoring_func``); the router scores g as
          it is, float32: the experts take it rounded to the compute dtype
    sel = the num_experts_per_tok experts with the largest s + b
          (b: a per-expert selection bias, used for the selection only)
    w_e = routed_scaling_factor * s_e [/ sum over sel of s: norm_topk_prob]
    y   = shared(g) + sum over e in sel HELD HERE of w_e FFN_e(g)

with every FFN ``(silu(g W_gate) * (g W_up)) W_down``. No token is dropped
and no capacity exists: the assignments that land on the experts this device
holds (``LlamaConfig.experts_held``: a range of the router's experts; the
layer's expert weights are stacked over that range only) are sorted by
expert, the three products run grouped over the sorted rows
(``jax.experimental.pallas.ops.tpu.megablox.gmm``: a tile of rows times the
weights of the expert it belongs to, nothing for an expert without rows), and
the results are combined, weighted, in float32. What the experts held
elsewhere would add is NOT computed and nothing stands in for it or for the
exchange that would bring it: with every expert held the sum is the whole
layer, and the shares of all devices plus the shared expert once add up to it
(tests/test_moe_dropless.py). The training block
(``models/llama.py::_moe_block``, ``parallel/moe.py``) is another thing: it
drops tokens over a capacity.

The sorted buffer holds all N x num_experts_per_tok assignments (the worst
case is every one of them landing here); the rows past the held ones are
never visited by the products. On the chip, decode (512 rows, ~64 held) runs
the three products at 85 % of the weights' bandwidth with 128-row tiles, a
2048-token prefill best with 256-row tiles; ``jax.lax.ragged_dot`` took 2.3x
and 1.6x as long (tools/moe_microbench.py; PERF.md section 6, PR 34).

Device-side names (``jax.named_scope``): ``moe_router`` (scores, selection,
sort, gather, combine), ``moe_experts`` (the grouped products),
``moe_shared``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
# rows of the sorted buffer up to which a tile of 128 rows is the faster one
_SMALL_ROWS = 2048
_TILE_KN = 1024


def route(g, gate_w, gate_bias, config):
    """(experts [N, k] int32, weights [N, k] float32) of every token: the
    selection over all ``num_experts`` and the weight each selected expert's
    output is combined with. The scores are float32, as published."""
    c = config
    logits = jnp.matmul(g.astype(_F32), gate_w.astype(_F32), precision=_HI)
    if c.scoring_func == "sigmoid":
        s = jax.nn.sigmoid(logits)
    else:
        s = jax.nn.softmax(logits, axis=-1)
    _, experts = jax.lax.top_k(s + gate_bias.astype(_F32)[None],
                               c.num_experts_per_tok)
    w = jnp.take_along_axis(s, experts, axis=-1)
    if c.norm_topk_prob:
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-20)
    return experts.astype(jnp.int32), w * _F32(c.routed_scaling_factor)


def _row_tile(rows: int) -> int:
    """Rows of the sorted buffer a grid step of the products takes."""
    return 128 if rows <= _SMALL_ROWS else 256


def _grouped_ffn(xs, lp, sizes, interpret, layer):
    """(silu(xs W_gate_e) * (xs W_up_e)) W_down_e for rows sorted by held
    expert e; ``sizes`` [held] rows an expert. Rows past their sum are not
    computed (their output is whatever the buffer held).

    The weights in ``lp`` are STACKED over the sparse layers ([layers, held,
    ...]) and this is layer ``layer`` of them. The stack is handed to the
    kernel whole, as [layers x held] groups of which only this layer's have
    rows: a slice of it as a kernel's operand is a COPY (compiled for a
    described v5e: 21 copies of 384 MiB in one burst program, made anew
    every decode step), where the kernel's own block index reads an expert's
    tiles in place."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    dt, tm = xs.dtype, _row_tile(xs.shape[0])
    layers, held = lp["moe_w_gate"].shape[:2]
    sizes = jnp.zeros((layers, held), jnp.int32).at[layer].set(sizes)
    sizes = sizes.reshape(-1)

    def dot(a, w):
        w = w.reshape((-1,) + w.shape[2:])
        tiling = (tm, min(_TILE_KN, w.shape[1]), min(_TILE_KN, w.shape[2]))
        return gmm(a, w, sizes, dt, tiling, interpret=interpret)

    # traced 32-bit: paddle_tpu turns jax_enable_x64 on, under which gmm's
    # count of tiles (the kernel's grid) is an i64 the TPU compiler refuses
    with jax.enable_x64(False):
        act = jax.nn.silu(dot(xs, lp["moe_w_gate"])) \
            * dot(xs, lp["moe_w_up"])
        return dot(act, lp["moe_w_down"])


def moe_dropless(g, lp, config, layer, valid=None, interpret=None):
    """The layer's output y [N, D] (``config.dtype``) for its input g [N, D]
    float32, and the count of assignments [held + 1] int32: on each held
    expert, and last those the router sent to experts held elsewhere.
    ``valid`` [N] bool: tokens that are real (a bucket's padding and a frozen
    slot route nowhere and count nowhere). ``lp``: gate_w [D, E], gate_bias
    [E], with shared experts shared_w_gate / shared_w_up [D, Fs],
    shared_w_down [Fs, D], and moe_w_gate / moe_w_up [layers, held, D, F],
    moe_w_down [layers, held, F, D] stacked over the sparse layers, of which
    this is layer ``layer`` (``_grouped_ffn`` says why).

    The router scores g before it is rounded to the compute dtype: a
    selection is a discrete thing, and one that flips on the rounding of its
    input moves the layer's output by a whole expert (on the chip the served
    logits then lie up to 1.2 under the float32 reference's best, PERF.md
    section 6, PR 34)."""
    c = config
    N, D = g.shape
    k = c.num_experts_per_tok
    first, held = c.held
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if valid is None:
        valid = jnp.ones((N,), bool)
    with jax.named_scope("moe_router"):
        experts, w = route(g, lp["gate_w"], lp["gate_bias"], c)
        g = g.astype(c.dtype)
        at = experts.reshape(-1) - jnp.int32(first)             # [N k]
        real = jnp.repeat(valid, k)
        here = real & (at >= 0) & (at < held)
        # held experts first and in order, then elsewhere, then unreal
        key = jnp.where(here, at, jnp.where(real, held, held + 1))
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        counts = jnp.sum(key[:, None] == jnp.arange(held + 1)[None, :],
                         axis=0, dtype=jnp.int32)
        pad = -(N * k) % _row_tile(N * k)    # whole tiles
        xs = jnp.take(g, jnp.pad(order // k, (0, pad)), axis=0)
    with jax.named_scope("moe_experts"):
        ys = _grouped_ffn(xs, lp, counts[:held], interpret, layer)
    with jax.named_scope("moe_router"):
        # an assignment's row of the sorted buffer: the inverse of `order`
        row = jnp.zeros((N * k,), jnp.int32).at[order].set(
            jnp.arange(N * k, dtype=jnp.int32))
        y = jnp.take(ys, row, axis=0).astype(_F32)              # [N k, D]
        y = jnp.where(here[:, None], y * w.reshape(-1)[:, None], 0.0)
        y = jnp.sum(y.reshape(N, k, D), axis=1)
    if c.num_shared_experts:
        with jax.named_scope("moe_shared"):
            act = jax.nn.silu(g @ lp["shared_w_gate"]) \
                * (g @ lp["shared_w_up"])
            y = y + (act @ lp["shared_w_down"]).astype(_F32)
    return y.astype(g.dtype), counts
