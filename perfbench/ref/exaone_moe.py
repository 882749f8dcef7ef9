"""Plain reference of the K-EXAONE decoder (model_type exaone_moe): window and
full attention mixed in one pattern, a dense FFN in the leading layer and
sigmoid-routed experts beside a shared expert in the others.

Straightforward jax.numpy in float32 with matmul precision "highest", one
sequence [T] at a time, no kernel, no cache, no sort (x is the stream, every
norm an RMSNorm):

  attention   h = norm(x; ln1); q = h Wq as H heads, k = h Wk, v = h Wv as
              KV heads; q and k RMS-normalised per head over head_dim with
              one gain vector each; rotation (rope_theta, default type) on q
              and k in the layer kinds `rope_layer_types` names (the window
              layers); a full causal score matrix softmax(q k^T / sqrt(hd))
              v, one head at a time, in a window layer under the mask
              i - window < j <= i; x += att Wo.
  dense FFN   g = norm(x; ln2); x += (silu(g Wg) * (g Wu)) Wd.
  sparse FFN  g = norm(x; ln2); s = sigmoid(g Wr) over ALL experts; the k
              experts with the largest s + b are selected (b: the selection
              bias, for the selection only); w_e = scale * s_e / sum over the
              selected of s; x += shared(g) + sum over the selected e that
              are HELD of w_e FFN_e(g): every token through every held
              expert, one expert at a time, weighted 0 where it was not
              selected. No capacity, no dropped token. What the experts held
              elsewhere would add is left out (`held` = (first, count); all
              of them held is the uncut layer).
  head        norm(x; norm) Wh over the vocabulary slice, untied.

What the published config.json does not spell out is listed under `assumed`
in the configuration's file and read from it here (`qk_norm`,
`rope_layer_types`, `norm_placement`).

A selection is discrete, and a side in bf16 takes other experts than this
float32 one wherever the 8th and 9th scores lie within its rounding: such a
position's logits then differ by a whole expert's output on every side, the
lower precisions' controls included, and the widest gap of a run says
nothing of precision. `route` therefore also gives every expert's `edge`,
and `served_logits` leaves out of the comparison the positions where a held
expert's edge is under the file's `decided_selection_margin` in some sparse
layer (the rehearsal's float32 file says 0: none).

Nothing here imports the program. Parameters arrive as a dict of arrays
under the names the program's tree uses, in the type they are served in:
wq, wk, wv, wo, q_norm, k_norm stacked over all (attention) layers; w_gate,
w_up, w_down over the dense layers; gate_w, gate_bias, moe_w_gate, moe_w_up,
moe_w_down, shared_w_gate, shared_w_up, shared_w_down over the sparse ones;
ln1, ln2 over all. Every use casts to float32 first, one layer and one
expert at a time: a layer is one jitted call that slices what it needs out of
the stacked arrays (its place a traced index, so a kind of layer is one
program), and the experts run under a scan, so one expert's float32 copy is
alive at a time beside the 12 GB of bf16 weights.

`dot` is the product of activations [T, K] with a weight [K, N]: `f32_dot`,
or for the control of `correct` `int8_dot` (both operands rounded to int8 on
their absolute maximum along the contraction), as perfbench/ref/llama.py has
them. No training functions: the configuration has no training cell.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"


def f32_dot(x, w):
    return jnp.matmul(x, w, precision=HI)


def _q8(a, axis):
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(a / s) * s


def int8_dot(x, w):
    return f32_dot(_q8(x, 1), _q8(w, 0))


DOTS = {"f32": f32_dot, "int8": int8_dot}


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rotate(x, theta):
    """Rotary embedding, default type, halves paired: x [T, heads, hd] at
    positions 0..T-1."""
    T, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(T, dtype=F32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _at(a, *index):
    """a[index] with traced leading indices, as float32."""
    for i in index:
        a = jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
    return a.astype(F32)


def attention(x, p, ai, kind, cfg, dot):
    """x + att Wo for one sequence x [T, D]; `ai` the layer's place (every
    layer of this family is an attention layer)."""
    H, KV, hd, eps = cfg["H"], cfg["KV"], cfg["hd"], cfg["eps"]
    T = x.shape[0]
    h = rmsnorm(x, _at(p["ln1"], ai), eps)
    q = dot(h, _at(p["wq"], ai)).reshape(T, H, hd)
    k = dot(h, _at(p["wk"], ai)).reshape(T, KV, hd)
    v = dot(h, _at(p["wv"], ai)).reshape(T, KV, hd)
    if cfg["qk_norm"] == "per_head":
        q = rmsnorm(q, _at(p["q_norm"], ai), eps)
        k = rmsnorm(k, _at(p["k_norm"], ai), eps)
    if kind in cfg["rope_kinds"]:
        q, k = rotate(q, cfg["theta"]), rotate(k, cfg["theta"])
    if KV != H:
        k, v = (jnp.repeat(a, H // KV, axis=1) for a in (k, v))
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = j <= i
    if kind == SLIDING:
        seen &= j > i - cfg["window"]

    def head(qkv):          # the whole T x T score matrix of one head
        qh, kh, vh = qkv
        s = jnp.matmul(qh, kh.T, precision=HI) / jnp.sqrt(F32(hd))
        p_ = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.matmul(p_, vh, precision=HI)

    a = jax.lax.map(head, tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v)))
    a = jnp.moveaxis(a, 0, 1).reshape(T, H * hd)
    return x + dot(a, _at(p["wo"], ai))


def swiglu(g, wg, wu, wd, dot):
    return dot(jax.nn.silu(dot(g, wg)) * dot(g, wu), wd)


def route(g, wr, bias, cfg, dot=f32_dot):
    """(w, edge), both [T, E]. w: the weight each expert's output is combined
    with, 0 where the expert was not selected. edge: how far the expert's
    s + b lies from the other side of the selection: above the best score
    left out if it was selected, under the last one selected if it was not.
    The router's product is a weight product like the others: the control
    rounds its operands too."""
    s = jax.nn.sigmoid(dot(g, wr)) if cfg["scoring"] == "sigmoid" \
        else jax.nn.softmax(dot(g, wr), axis=-1)
    k = cfg["k"]
    biased = s + bias[None]
    top, sel = jax.lax.top_k(biased, min(k + 1, s.shape[1]))
    picked = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], sel[:, :k]].set(True)
    edge = jnp.where(picked, biased - top[:, -1:], top[:, k - 1:k] - biased)
    w = jnp.where(picked, s, 0.0)
    if cfg["norm_topk"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w * cfg["scale"], edge


def sparse_ffn(g, p, fi, cfg, dot, held=None):
    """(y, margin) for g [T, D]: y [T, D] = shared(g) + the held experts'
    weighted outputs; margin [T] the least `edge` over the HELD experts:
    how far the token's nearest held expert is from being selected where it
    was not, or left out where it was. `fi` the layer's place among the
    sparse layers; `held` = (first, count) of the router's experts whose
    weights p["moe_w_*"] stacks (cfg's own by default)."""
    first, count = held or cfg["held"]
    w, edge = route(g, _at(p["gate_w"], fi), _at(p["gate_bias"], fi), cfg, dot)
    w, edge = (a[:, first:first + count] for a in (w, edge))

    def expert(y, e):
        out = swiglu(g, _at(p["moe_w_gate"], fi, e), _at(p["moe_w_up"], fi, e),
                     _at(p["moe_w_down"], fi, e), dot)
        return y + jnp.take(w, e, axis=1)[:, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(g), jnp.arange(count))
    if cfg["shared"]:
        y = y + swiglu(g, _at(p["shared_w_gate"], fi),
                       _at(p["shared_w_up"], fi),
                       _at(p["shared_w_down"], fi), dot)
    return y, jnp.min(edge, axis=1)


@functools.partial(jax.jit, static_argnames=("kind", "ffn", "cfg", "dot"))
def layer(x, p, li, fi, *, kind, ffn, cfg, dot):
    """One layer over one sequence x [T, D] float32: `li` its place among
    all layers, `fi` among the layers of its FFN kind (traced: a kind of
    layer is one program). Returns (x, margin [T]): `sparse_ffn`'s margin,
    None for a dense FFN, which selects nothing."""
    cfg, dot = dict(cfg), DOTS[dot]
    x = attention(x, p, li, kind, cfg, dot)
    g = rmsnorm(x, _at(p["ln2"], li), cfg["eps"])
    if ffn == DENSE:
        y = swiglu(g, _at(p["w_gate"], fi), _at(p["w_up"], fi),
                   _at(p["w_down"], fi), dot)
        return x + y, None
    y, margin = sparse_ffn(g, p, fi, cfg, dot)
    return x + y, margin


def hidden(params, tokens, cfg, dot):
    """(x, margins) of one sequence: the final hidden states [T, D] (before
    the last norm), and every sparse layer's margin [sparse layers, T]."""
    c = dict(cfg)
    x = jnp.take(params["embed_tokens"], tokens, axis=0).astype(F32)
    seen, margins = {DENSE: 0, SPARSE: 0}, []
    for li, (kind, ffn) in enumerate(zip(c["pattern"], c["ffns"])):
        x, m = layer(x, params, jnp.int32(li), jnp.int32(seen[ffn]),
                     kind=kind, ffn=ffn, cfg=cfg, dot=dot)
        if ffn == SPARSE:
            margins.append(m)
        seen[ffn] += 1
    return x, jnp.stack(margins)


@functools.partial(jax.jit, static_argnames=("eps", "dot", "n"))
def _head(params, x, start, picks, *, eps, dot, n):
    rows = jax.lax.dynamic_slice_in_dim(x, start, n, axis=0)
    rows = rmsnorm(rows, params["norm"].astype(F32), eps)
    lg = DOTS[dot](rows, params["lm_head"].astype(F32))
    at = jnp.take_along_axis(lg, picks[:, None], axis=1)[:, 0]
    return lg.max(-1), at, jnp.argmax(lg, -1).astype(jnp.int32)


def ref_dims(cfg: dict) -> dict:
    """The hashable sizes the reference needs from a configuration file."""
    H = cfg["num_attention_heads"]
    E = cfg["num_experts_routed"]
    return {"H": H, "KV": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or cfg["hidden_size"] // H,
            "eps": float(cfg["rms_norm_eps"]),
            "pattern": tuple(cfg["layer_types"]),
            "ffns": tuple(cfg["mlp_layer_types"]),
            "window": int(cfg["sliding_window"]),
            "theta": float(cfg["rope_parameters"]["rope_theta"]),
            "rope_kinds": tuple(cfg["rope_layer_types"]),
            "qk_norm": cfg["qk_norm"],
            "k": int(cfg["num_experts_per_tok"]),
            "scoring": cfg["scoring_func"],
            "norm_topk": bool(cfg["norm_topk_prob"]),
            "scale": float(cfg["routed_scaling_factor"]),
            "shared": int(cfg["num_shared_experts"]),
            "E": E,
            "held": (int(cfg["experts_held_first"]), int(cfg["num_experts"])),
            "decided": float(cfg.get("decided_selection_margin", 0.0))}


def hashable(cfg: dict) -> tuple:
    return tuple(sorted(ref_dims(cfg).items()))


def served_logits(params, tokens, start, picks, *, cfg, dot, n):
    """Teacher-forced logits of one request. tokens [T] is the prompt
    followed by the served tokens (zero-padded on the right, which a causal
    model never sees); rows start..start+n-1 are the positions that
    predicted the served tokens. Returns, for each, the best logit, the
    logit of picks[i] and the best token.

    A selection is discrete. Where a held expert's s + b lies within the
    file's `decided_selection_margin` of the other side of the selection
    (`route`'s edge) in some sparse layer, the precision the configuration
    states does not decide which experts the position takes, and either
    choice moves its logits by a whole expert's output: such a position
    says nothing of the precision of a side that chose otherwise, and its
    pick is returned AT the best logit (gap 0: not compared). With the
    margin 0 every position is compared."""
    c = dict(cfg)
    x, margins = hidden(params, tokens, cfg, dot)
    best, at, first = _head(params, x, start, picks, eps=c["eps"], dot=dot,
                            n=n)
    if c["decided"] > 0:
        least = jax.lax.dynamic_slice_in_dim(margins.min(0), start, n)
        at = jnp.where(least < c["decided"], best, at)
    return best, at, first
