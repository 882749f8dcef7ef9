"""The mixer of a LINEAR layer of the model spec (``LlamaConfig.layer_types``):
gated delta-rule linear attention around ``ops/gated_delta.py``.

For the layer's input ``h`` (what ``block_in`` gives of the stream)::

    [q~, k~, v~] = silu(conv(h W_qkv))     depthwise, causal, no bias
    q = q~ / |q~|_2 / sqrt(dk),  k = k~ / |k~|_2       per head
    beta = sigmoid(h W_b) (twice that where linear_allow_neg_eigval)
    g = -exp(A_log) * softplus(h W_a + dt_bias)        per head, <= 0
    o = the gated delta rule over (q, k, v, g, beta)
    y = [RMSNorm_dv(o) * norm_gain * silu(h W_g)] W_o

Per request the layer holds the rule's state [Hv, dv, dk] (``state_dtype``)
and the last ``conv_kernel - 1`` rows of ``h W_qkv``. ``mixer_prefill`` runs
one prompt through the chunk scan from a zero state; ``mixer_step`` one
token of every slot. Device-side names: ``lin_proj`` (projections,
convolution, gating, output), ``gdn_scan`` / ``gdn_step`` (the rule).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.gated_delta import gdn_chunk_scan, gdn_step

_F32 = jnp.float32


def _heads(y, c):
    """The convolved [.., conv_dim] row split into q, k [.., Hk, dk] and v
    [.., Hv, dv], q and k normalised, in the compute dtype."""
    Hk, dk = c.linear_num_key_heads, c.linear_key_head_dim
    Hv, dv = c.linear_num_value_heads, c.linear_value_head_dim
    lead = y.shape[:-1]
    q, k, v = jnp.split(y, (Hk * dk, 2 * Hk * dk), axis=-1)
    q = q.reshape(lead + (Hk, dk)).astype(_F32)
    k = k.reshape(lead + (Hk, dk)).astype(_F32)

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q = unit(q) * (dk ** -0.5)
    return q.astype(c.dtype), unit(k).astype(c.dtype), \
        v.reshape(lead + (Hv, dv))


def _gates(h, lp, c):
    """(g, beta) [.., Hv] float32 from the layer's input."""
    a = (h @ lp["lin_wa"]).astype(_F32) + lp["lin_dt_bias"].astype(_F32)
    g = -jnp.exp(lp["lin_A_log"].astype(_F32)) * jax.nn.softplus(a)
    beta = jax.nn.sigmoid((h @ lp["lin_wb"]).astype(_F32))
    return g, (2.0 * beta if c.linear_allow_neg_eigval else beta)


def _out(o, h, lp, c):
    """The rule's output o [.., Hv, dv] float32 -> the mixer's [.., D]:
    RMSNorm over each head's values, the output gate, W_o."""
    Hv, dv = c.linear_num_value_heads, c.linear_value_head_dim
    var = jnp.mean(o * o, axis=-1, keepdims=True)
    o = o * jax.lax.rsqrt(var + c.rms_norm_eps) * lp["lin_norm"].astype(_F32)
    gate = (h @ lp["lin_wg"]).reshape(o.shape[:-2] + (Hv, dv))
    y = (o * jax.nn.silu(gate.astype(_F32))).astype(c.dtype)
    return y.reshape(o.shape[:-2] + (Hv * dv,)) @ lp["lin_wo"]


def mixer_prefill(h, lp, config, tlen):
    """One prompt. h [T, D] (rows at or past the traced ``tlen`` are bucket
    padding). Returns (y [T, D], the state after token tlen - 1 [Hv, dv,
    dk], the convolution's tail: rows tlen - K + 1 .. tlen - 1 of h W_qkv,
    zeros before the prompt's start)."""
    c = config
    K = c.linear_conv_kernel_dim
    T = h.shape[0]
    with jax.named_scope("lin_proj"):
        x = h @ lp["lin_wqkv"]                              # [T, conv_dim]
        xp = jnp.pad(x, ((K - 1, 0), (0, 0)))
        tail = jax.lax.dynamic_slice_in_dim(xp, tlen, K - 1, axis=0)
        w = lp["lin_conv"].astype(_F32)
        y = sum(xp[i:i + T].astype(_F32) * w[i] for i in range(K))
        q, k, v = _heads(jax.nn.silu(y).astype(c.dtype), c)
        g, beta = _gates(h, lp, c)
    with jax.named_scope("gdn_scan"):
        o, state = gdn_chunk_scan(q, k, v, g, beta, None, length=tlen)
    with jax.named_scope("lin_proj"):
        return _out(o, h, lp, c), state.astype(c.state_dtype), tail


def mixer_step(h, lp, config, state, tail, frozen, interpret=None):
    """One token of every slot. h [B, D]; state [B, Hv, dv, dk]; tail [B,
    K - 1, conv_dim]; ``frozen`` [B] bool: slots whose state and tail stay
    as they are (finished, or free). The rule is ``gdn_step``'s body for
    the state (``interpret``: as it takes it). Returns (y [B, D], state,
    tail)."""
    c = config
    with jax.named_scope("lin_proj"):
        x = h @ lp["lin_wqkv"]                              # [B, conv_dim]
        window = jnp.concatenate([tail, x[:, None].astype(tail.dtype)], 1)
        y = jnp.sum(window.astype(_F32) * lp["lin_conv"].astype(_F32)[None],
                    axis=1)
        q, k, v = _heads(jax.nn.silu(y).astype(c.dtype), c)
        g, beta = _gates(h, lp, c)
        new_tail = jnp.where(frozen[:, None, None], tail, window[:, 1:])
    with jax.named_scope("gdn_step"):
        o, new = gdn_step(q, k, v, g, beta, state, frozen,
                          interpret=interpret)
    with jax.named_scope("lin_proj"):
        return _out(o, h, lp, c), new, new_tail
