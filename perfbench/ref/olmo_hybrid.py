"""Plain reference of the Olmo-Hybrid decoder: linear-attention (gated
delta-rule) layers with a full-attention layer after every few, the OLMo 2
family's reordered norm around both and around the SwiGLU FFN.

Straightforward jax.numpy in float32 with matmul precision "highest", one
sequence [T] at a time, no kernel, no cache, no chunks:

  full layer    q = RMSNorm_q(x Wq), k = RMSNorm_k(x Wk) over the whole
                projection, then heads; v = x Wv; a full causal score matrix
                softmax(q k^T / sqrt(hd)) v, one head at a time; Wo. No
                rotation: the published rope_theta is null.
  linear layer  [q~, k~, v~] = silu(conv(x Wqkv)), depthwise, causal, no
                bias; q = q~ / |q~| / sqrt(dk), k = k~ / |k~| (|a| = sqrt(sum
                a^2 + 1e-6)); beta = 2 sigmoid(x Wb); g = -exp(A_log)
                softplus(x Wa + dt_bias); then TOKEN BY TOKEN under a scan,
                per head, S in R^{dv x dk} from zeros:
                    S' = exp(g_t) S;  u = beta_t (v_t - S' k_t);
                    S = S' + u k_t^T;  o_t = S q_t
                y = [RMSNorm_dv(o) w * silu(x Wg)] Wo.
  wiring        h = x + RMSNorm(mixer(x)); y = h + RMSNorm(FFN(h)); a final
                RMSNorm before the untied head.

What the published config.json does not spell out (the reordered norm, the
QK-norm, the rule's normalisations, the gated RMSNorm) is the family's and
the published implementations' convention, listed under `assumed` in the
configuration's file.

Nothing here imports the program. Parameters arrive as a dict of arrays
under the names the program's tree uses, each kind of layer stacked on a
leading axis of its own (wq, wk, wv, wo, q_norm, k_norm over the full
layers; lin_* over the linear ones; w_gate, w_up, w_down, ln1, ln2 over
all), in the type they are served in; every use casts to float32 first, one
layer at a time: the layers of one period of the pattern run under nested
scans, so one layer's float32 copy is alive at a time.

`dot` is the product of activations [T, K] with a weight [K, N]: `f32_dot`,
or for the control of `correct` `int8_dot` (both operands rounded to int8 on
their absolute maximum along the contraction), as perfbench/ref/llama.py
has them. No training functions: the configuration has no training cell.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
FULL, LINEAR = "full_attention", "linear_attention"
ATTN_KEYS = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
LINEAR_KEYS = ("lin_wqkv", "lin_wa", "lin_wb", "lin_wg", "lin_wo",
               "lin_conv", "lin_A_log", "lin_dt_bias", "lin_norm")
FFN_KEYS = ("w_gate", "w_up", "w_down", "ln1", "ln2")


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


def ffn(h, lp, cfg, dot):
    y = dot(jax.nn.silu(dot(h, lp["w_gate"])) * dot(h, lp["w_up"]),
            lp["w_down"])
    return h + rmsnorm(y, lp["ln2"], cfg["eps"])


def full_layer(x, lp, cfg, dot):
    """One full-attention layer over one sequence x [T, D]."""
    H, KV, hd, eps = cfg["H"], cfg["KV"], cfg["hd"], cfg["eps"]
    T = x.shape[0]
    lp = {k: v.astype(F32) for k, v in lp.items()}
    q = rmsnorm(dot(x, lp["wq"]), lp["q_norm"], eps).reshape(T, H, hd)
    k = rmsnorm(dot(x, lp["wk"]), lp["k_norm"], eps).reshape(T, KV, hd)
    v = dot(x, lp["wv"]).reshape(T, KV, hd)
    if KV != H:
        k, v = (jnp.repeat(a, H // KV, axis=1) for a in (k, v))
    causal = jnp.tril(jnp.ones((T, T), bool))

    def head(qkv):          # the whole T x T score matrix of one head
        qh, kh, vh = qkv
        s = jnp.matmul(qh, kh.T, precision=HI) / jnp.sqrt(F32(hd))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.matmul(p, vh, precision=HI)

    a = jax.lax.map(head, tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v)))
    a = jnp.moveaxis(a, 0, 1).reshape(T, H * hd)
    h = x + rmsnorm(dot(a, lp["wo"]), lp["ln1"], eps)
    return ffn(h, lp, cfg, dot)


def delta_rule(q, k, v, g, beta):
    """The gated delta rule, token by token. q, k [T, Hv, dk]; v [T, Hv,
    dv]; g, beta [T, Hv]. Returns o [T, Hv, dv]."""
    Hv, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def token(S, x):
        qt, kt, vt, gt, bt = x
        S = jnp.exp(gt)[:, None, None] * S
        u = bt[:, None] * (vt - jnp.einsum("hvk,hk->hv", S, kt, precision=HI))
        S = S + u[:, :, None] * kt[:, None, :]
        return S, jnp.einsum("hvk,hk->hv", S, qt, precision=HI)

    _, o = jax.lax.scan(token, jnp.zeros((Hv, dv, dk), F32),
                        (q, k, v, g, beta))
    return o


def linear_layer(x, lp, cfg, dot):
    """One linear-attention layer over one sequence x [T, D]."""
    Hk, Hv, dk, dv, K = (cfg[n] for n in ("Hk", "Hv", "dk", "dv", "K"))
    eps, T = cfg["eps"], x.shape[0]
    lp = {k: v.astype(F32) for k, v in lp.items()}
    z = jnp.pad(dot(x, lp["lin_wqkv"]), ((K - 1, 0), (0, 0)))
    z = jax.nn.silu(sum(z[i:i + T] * lp["lin_conv"][i] for i in range(K)))
    q, k, v = jnp.split(z, (Hk * dk, 2 * Hk * dk), axis=-1)

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q = unit(q.reshape(T, Hk, dk)) / jnp.sqrt(F32(dk))
    k = unit(k.reshape(T, Hk, dk))
    if Hk != Hv:
        q, k = (jnp.repeat(a, Hv // Hk, axis=1) for a in (q, k))
    beta = jax.nn.sigmoid(dot(x, lp["lin_wb"])) * (2.0 if cfg["neg"] else 1.0)
    g = -jnp.exp(lp["lin_A_log"]) * jax.nn.softplus(
        dot(x, lp["lin_wa"]) + lp["lin_dt_bias"])
    o = delta_rule(q, k, v.reshape(T, Hv, dv), g, beta)
    o = rmsnorm(o, lp["lin_norm"], eps)
    gate = jax.nn.silu(dot(x, lp["lin_wg"])).reshape(T, Hv, dv)
    y = dot((o * gate).reshape(T, Hv * dv), lp["lin_wo"])
    h = x + rmsnorm(y, lp["ln1"], eps)
    return ffn(h, lp, cfg, dot)


LAYERS = {FULL: (full_layer, ATTN_KEYS), LINEAR: (linear_layer, LINEAR_KEYS)}


def period_runs(pattern: tuple):
    """(period, [(kind, first, last)]) of a layer pattern: its smallest
    repeating unit, and inside it the runs of one kind as places [first,
    last) within the period."""
    n = len(pattern)
    p = next(p for p in range(1, n + 1)
             if n % p == 0 and pattern == pattern[:p] * (n // p))
    runs, at = [], 0
    while at < p:
        end = at
        while end < p and pattern[end] == pattern[at]:
            end += 1
        runs.append((pattern[at], at, end))
        at = end
    return p, runs


def hidden(params, tokens, cfg, dot):
    """Final hidden states [T, D] (before the last norm) of one sequence:
    a scan over the periods of the pattern, inside it one scan for every
    run of layers of one kind."""
    pattern = cfg["pattern"]
    p, runs = period_runs(pattern)
    n = len(pattern) // p
    per_kind = {kind: pattern[:p].count(kind) for kind in LAYERS}

    def by_period(name, each):
        a = params[name]
        return a.reshape((n, each) + a.shape[1:])

    stacked = {k: by_period(k, p) for k in FFN_KEYS}
    for kind, (_, keys) in LAYERS.items():
        if per_kind[kind]:
            stacked.update({k: by_period(k, per_kind[kind]) for k in keys})

    def period(x, pp):
        for kind, first, last in runs:
            fn, keys = LAYERS[kind]
            k0 = pattern[:first].count(kind)
            lp = {k: pp[k][first:last] for k in FFN_KEYS}
            lp.update({k: pp[k][k0:k0 + last - first] for k in keys})
            x, _ = jax.lax.scan(lambda c, l: (fn(c, l, cfg, dot), None),
                                x, lp)
        return x, None

    x = jnp.take(params["embed_tokens"], tokens, axis=0).astype(F32)
    x, _ = jax.lax.scan(period, x, stacked)
    return x


def head_logits(params, x, cfg, dot):
    x = rmsnorm(x, params["norm"].astype(F32), cfg["eps"])
    return dot(x, params["lm_head"].astype(F32))


def ref_dims(cfg: dict) -> dict:
    """The hashable sizes the reference needs from a configuration file."""
    H = cfg["num_attention_heads"]
    return {"H": H, "KV": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or cfg["hidden_size"] // H,
            "eps": float(cfg["rms_norm_eps"]),
            "Hk": cfg["linear_num_key_heads"],
            "Hv": cfg["linear_num_value_heads"],
            "dk": cfg["linear_key_head_dim"],
            "dv": cfg["linear_value_head_dim"],
            "K": cfg["linear_conv_kernel_dim"],
            "neg": bool(cfg["linear_allow_neg_eigval"]),
            "pattern": tuple(cfg["layer_types"])}


def hashable(cfg: dict) -> tuple:
    return tuple(sorted(ref_dims(cfg).items()))


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
