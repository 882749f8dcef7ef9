"""Plain reference of the decoder the configurations share.

Straightforward jax.numpy in float32 with matmul precision "highest": RMSNorm,
rotary positions (the half-split rotation of the published models), grouped
query attention with a full causal score matrix, SwiGLU, an untied output
head, no bias. No kernel, no cache, no batching: one sequence [T] at a time,
the layers under one scan. It follows the published descriptions of
InternLM2 and Mistral-7B-v0.2 (sliding_window null); the one departure is
InternLM2's packed `wqkv`, a storage layout, held here as three matrices.

Nothing here imports the program. Parameters arrive as a dict of arrays
under the names the program's tree uses (embed_tokens, wq, wk, wv, wo,
w_gate, w_up, w_down, ln1, ln2 stacked by layer; norm; lm_head), in the
type they are served or trained in, made by perfbench/weights.py from the
seed; every use casts to float32 first.

`dot` is the product of activations [T, K] with a weight [K, N]. The
reference uses `f32_dot`; the control of `correct` puts `int8_dot` in its
place: both operands rounded to int8 on their absolute maximum along the
contraction (per token, per output channel), the step below bfloat16 that
would tempt a later PR on a chip whose int8 peak is twice its bf16 peak.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
              "ln1", "ln2")
F32 = jnp.float32


def f32_dot(x, w):
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _q8(a, axis):
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(a / s) * s


@jax.custom_vjp
def int8_dot(x, w):
    return f32_dot(_q8(x, 1), _q8(w, 0))


def _int8_dot_fwd(x, w):
    return int8_dot(x, w), (x, w)


def _int8_dot_bwd(res, g):
    x, w = res
    return (f32_dot(_q8(g, 1), _q8(w, 1).T), f32_dot(_q8(x, 0).T, _q8(g, 0)))


int8_dot.defvjp(_int8_dot_fwd, _int8_dot_bwd)

DOTS = {"f32": f32_dot, "int8": int8_dot}


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x [T, heads, hd] at positions 0..T-1."""
    T, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(x, lp, cfg, dot):
    """One decoder layer over one sequence x [T, D]."""
    H, KV, hd = cfg["H"], cfg["KV"], cfg["hd"]
    T = x.shape[0]
    lp = {k: v.astype(F32) for k, v in lp.items()}
    h = rmsnorm(x, lp["ln1"], cfg["eps"])
    q = rope(dot(h, lp["wq"]).reshape(T, H, hd), cfg["theta"])
    k = rope(dot(h, lp["wk"]).reshape(T, KV, hd), cfg["theta"])
    v = dot(h, lp["wv"]).reshape(T, KV, hd)
    q = q.reshape(T, KV, H // KV, hd)
    s = jnp.einsum("tkgd,skd->kgts", q, k,
                   precision=jax.lax.Precision.HIGHEST) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    a = jnp.einsum("kgts,skd->tkgd", p, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(T, H * hd)
    x = x + dot(a, lp["wo"])
    h = rmsnorm(x, lp["ln2"], cfg["eps"])
    return x + dot(jax.nn.silu(dot(h, lp["w_gate"])) * dot(h, lp["w_up"]),
                   lp["w_down"])


def ref_dims(cfg: dict) -> dict:
    """The hashable sizes the reference needs from a configuration file."""
    H = cfg["num_attention_heads"]
    return {"H": H, "KV": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or cfg["hidden_size"] // H,
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"])}


def hidden(params, tokens, cfg, dot, remat=False):
    """Final hidden states [T, D] (before the last norm) of one sequence."""
    x = jnp.take(params["embed_tokens"], tokens, axis=0).astype(F32)
    body = lambda c, lp: (layer(c, lp, cfg, dot), None)
    if remat:       # same values, one layer's activations alive at a time
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, {k: params[k] for k in LAYER_KEYS})
    return x


def head_logits(params, x, cfg, dot):
    x = rmsnorm(x, params["norm"].astype(F32), cfg["eps"])
    return dot(x, params["lm_head"].astype(F32))


@functools.partial(jax.jit, static_argnames=("cfg", "dot", "n"))
def served_logits(params, tokens, start, picks, *, cfg, dot, n):
    """Teacher-forced logits of one request. tokens [T] is the prompt
    followed by the served tokens (zero-padded on the right, which a causal
    model never sees); rows start..start+n-1 are the positions that
    predicted the served tokens. Returns, for each, the best logit, the
    logit of picks[i] and the best token."""
    cfg = dict(cfg)
    x = hidden(params, tokens, cfg, DOTS[dot])
    rows = jax.lax.dynamic_slice_in_dim(x, start, n, axis=0)
    lg = head_logits(params, rows, cfg, DOTS[dot])
    at = jnp.take_along_axis(lg, picks[:, None], axis=1)[:, 0]
    return lg.max(-1), at, jnp.argmax(lg, -1).astype(jnp.int32)


# ---------------------------------------------------------------- training

def batch_loss(params, tokens, labels, cfg, dot):
    """Mean next-token cross-entropy over the labelled positions (label
    >= 0) of all rows of [B, T], one row at a time: rows in blocks, so that
    the reference fits."""
    def row(carry, tl):
        t, l = tl
        lg = head_logits(params, hidden(params, t, cfg, dot, remat=True),
                         cfg, dot)
        ll = jnp.take_along_axis(jax.nn.log_softmax(lg, -1),
                                 jnp.maximum(l, 0)[:, None], axis=1)[:, 0]
        return carry - jnp.sum(jnp.where(l >= 0, ll, 0.0)), None

    total, _ = jax.lax.scan(jax.checkpoint(row), F32(0), (tokens, labels))
    return total / jnp.maximum(jnp.sum(labels >= 0), 1)


@functools.partial(jax.jit, static_argnames=("cfg", "dot"))
def loss_and_grads(params, tokens, labels, *, cfg, dot):
    return jax.value_and_grad(batch_loss)(params, tokens, labels, dict(cfg),
                                          DOTS[dot])


@functools.partial(jax.jit, static_argnames=("cfg", "dot"))
def loss_only(params, tokens, labels, *, cfg, dot):
    return batch_loss(params, tokens, labels, dict(cfg), DOTS[dot])


@functools.partial(jax.jit, static_argnames=("hp",), donate_argnums=(0,))
def adamw_leaf(p, grads, t, *, hp):
    """AdamW on one leaf at step t (1-based) from the gradients of steps
    1..t, newest last: the moments are their decayed sums, so the state
    kept between steps is the gradients themselves."""
    lr, b1, b2, eps, wd = hp
    m = v = 0.0
    for i, g in enumerate(grads):
        age = len(grads) - 1 - i
        m = m + (1 - b1) * b1 ** age * g
        v = v + (1 - b2) * b2 ** age * g * g
    tf = t.astype(F32)
    mhat, vhat = m / (1 - b1 ** tf), v / (1 - b2 ** tf)
    return p - lr * mhat / (jnp.sqrt(vhat) + eps) - lr * wd * p


def hashable(cfg: dict) -> tuple:
    return tuple(sorted(ref_dims(cfg).items()))
