"""The Olmo-Hybrid family: a layer pattern of linear-attention layers (the
gated delta rule: a state of fixed size a request, whatever its length) with
a full-attention layer after every few (a KV cache that grows by one K row
and one V row a token, in those layers only); QK-norm, no rotation, the
OLMo 2 family's reordered norm; every layer has the SwiGLU FFN.

The program's side is its own JSON-spec builder, the one every fleet replica
starts from (`paddle_tpu.inference.replica.build_batcher`): this file hands
it the model spec and the mix's `engine` settings and names no class of the
program. The reference is perfbench/ref/olmo_hybrid.py. There is no training
cell: `train_step` says so. Every function takes the configuration as the
plain dict of its file under perfbench/configs/.

The work is counted here, independent of what the program does:

  a FULL layer     4 D H hd + 3 D F products a token; causal attention 4 H
                   hd t^2 / 2 a prompt, 4 H hd rows a decoded token
  a LINEAR layer   D (2 Hk dk + Hv dv) + 2 D Hv + 2 D Hv dv + 3 D F products
                   a token, the convolution's K taps, and the rule:
      the chunk-64 scan as published, a chunk of C tokens and a head:
          K K^T and Q K^T            2 * 2 C^2 dk
          (I + A)^-1 by substitution 2 C^3 / 3
          T (beta V), T (beta e^G K) 2 C^2 dv + 2 C^2 dk
          W S^T, Q S^T, U^T K        3 * 2 C dk dv
          M U                        2 C^2 dv
      one decoded token and a head: decay, S k, the rank-one update, S q:
          7 dv dk
"""
from __future__ import annotations

from .. import arith

FULL, LINEAR = "full_attention", "linear_attention"
CHUNK = 64      # the published scan's chunk

# ------------------------------------------------------------- the program

GAINS = ("ln1", "ln2", "norm", "q_norm", "k_norm", "lin_norm")
WHOLE = ("embed_tokens", "lm_head", "norm")     # leaves not stacked by layer


def dims(cfg: dict) -> dict:
    """The sizes the arithmetic needs, by short names."""
    H = cfg["num_attention_heads"]
    kinds = list(cfg["layer_types"])
    d = {"L": cfg["num_hidden_layers"], "D": cfg["hidden_size"],
         "F": cfg["intermediate_size"], "V": cfg["vocab_size"], "H": H,
         "KV": cfg["num_key_value_heads"],
         "hd": cfg.get("head_dim") or cfg["hidden_size"] // H,
         "nF": kinds.count(FULL), "nL": kinds.count(LINEAR),
         "Hk": cfg["linear_num_key_heads"],
         "Hv": cfg["linear_num_value_heads"],
         "dk": cfg["linear_key_head_dim"],
         "dv": cfg["linear_value_head_dim"],
         "K": cfg["linear_conv_kernel_dim"]}
    if len(kinds) != d["L"] or d["nF"] + d["nL"] != d["L"]:
        raise ValueError(f"layer_types names {len(kinds)} layers of kinds "
                         f"{sorted(set(kinds))}, num_hidden_layers {d['L']}")
    d["conv"] = 2 * d["Hk"] * d["dk"] + d["Hv"] * d["dv"]
    return d


def shapes(cfg: dict) -> dict:
    """The program's parameter tree: each kind of layer stacked on a
    leading axis of its own, the FFN and the two norms over all layers."""
    d = dims(cfg)
    L, D, F, V, nF, nL = (d[k] for k in ("L", "D", "F", "V", "nF", "nL"))
    q, kv, val = d["H"] * d["hd"], d["KV"] * d["hd"], d["Hv"] * d["dv"]
    return {"embed_tokens": (V, D),
            "wq": (nF, D, q), "wk": (nF, D, kv), "wv": (nF, D, kv),
            "wo": (nF, q, D), "q_norm": (nF, q), "k_norm": (nF, kv),
            "lin_wqkv": (nL, D, d["conv"]), "lin_wa": (nL, D, d["Hv"]),
            "lin_wb": (nL, D, d["Hv"]), "lin_wg": (nL, D, val),
            "lin_wo": (nL, val, D), "lin_conv": (nL, d["K"], d["conv"]),
            "lin_A_log": (nL, d["Hv"]), "lin_dt_bias": (nL, d["Hv"]),
            "lin_norm": (nL, d["dv"]),
            "w_gate": (L, D, F), "w_up": (L, D, F), "w_down": (L, F, D),
            "lm_head": (D, V), "ln1": (L, D), "ln2": (L, D), "norm": (D,)}


def model_spec(cfg: dict, max_len: int) -> dict:
    """The configuration as the program's JSON model spec states it. The
    published file gives `rope_parameters.rope_theta` null: no rotation."""
    d = dims(cfg)
    return {
        "vocab_size": d["V"], "hidden_size": d["D"],
        "intermediate_size": d["F"], "num_hidden_layers": d["L"],
        "num_attention_heads": d["H"], "num_key_value_heads": d["KV"],
        "head_dim": d["hd"], "max_position_embeddings": max(max_len, 128),
        "rms_norm_eps": cfg["rms_norm_eps"],
        "rope_theta": cfg["rope_parameters"]["rope_theta"],
        "tie_word_embeddings": bool(cfg.get("tie_word_embeddings", False)),
        "dtype": cfg.get("dtype", "bfloat16"),
        "qk_norm": True, "norm_placement": "post",
        "layer_types": list(cfg["layer_types"]),
        "linear_num_key_heads": d["Hk"], "linear_num_value_heads": d["Hv"],
        "linear_key_head_dim": d["dk"], "linear_value_head_dim": d["dv"],
        "linear_conv_kernel_dim": d["K"],
        "linear_allow_neg_eigval": bool(cfg["linear_allow_neg_eigval"]),
        "state_dtype": cfg.get("state_dtype", "float32")}


def engine(cfg: dict, traffic: dict, weights):
    """The program's own builder from a JSON spec: the mix's `engine`
    settings are the engine's arguments as they stand. It installs the
    default admission policy (a queue of at most 4 x max_batch; the mixes'
    backlogs are far below) and serves greedily unless the mix says
    otherwise."""
    from paddle_tpu.inference.replica import build_batcher
    settings = dict(traffic["engine"])
    return build_batcher({"config": model_spec(cfg, settings["max_len"]),
                          "batcher": settings}, params=weights)


def train_step(cfg: dict, job: dict, mesh, make_weights):
    raise SystemExit(f"no training cell for this configuration "
                     f"({cfg.get('name')!r}): the program trains no layer "
                     "pattern yet (ROADMAP Queue 2(a) M2)")


# ----------------------------------------------------------- the reference

def reference():
    from ..ref import olmo_hybrid
    return olmo_hybrid


def layer_axes(name: str, ndim: int):
    """Every leaf but the embedding, the head and the last norm is stacked
    on a leading axis (of its kind's layers): one norm a layer."""
    return None if name in WHOLE else tuple(range(1, ndim))


# ---------------------------------------------------------------- the work

def full_layer_params(cfg: dict) -> int:
    d = dims(cfg)
    q, kv = d["H"] * d["hd"], d["KV"] * d["hd"]
    return 2 * d["D"] * q + 2 * d["D"] * kv + q + kv + ffn_params(cfg)


def linear_layer_params(cfg: dict) -> int:
    d = dims(cfg)
    val = d["Hv"] * d["dv"]
    return (d["D"] * d["conv"] + 2 * d["D"] * d["Hv"] + 2 * d["D"] * val
            + d["K"] * d["conv"] + 2 * d["Hv"] + d["dv"] + ffn_params(cfg))


def ffn_params(cfg: dict) -> int:
    """The SwiGLU FFN and the layer's two norm vectors."""
    d = dims(cfg)
    return 3 * d["D"] * d["F"] + 2 * d["D"]


def embed_params(cfg: dict) -> int:
    d = dims(cfg)
    return d["V"] * d["D"]


def total_params(cfg: dict) -> int:
    d = dims(cfg)
    return (d["nF"] * full_layer_params(cfg)
            + d["nL"] * linear_layer_params(cfg)
            + 2 * embed_params(cfg) + d["D"])      # embedding, head, norm


def matmul_params(cfg: dict) -> int:
    """Every parameter a token is multiplied with: the layers and the head
    (the embedding table is a lookup)."""
    return total_params(cfg) - embed_params(cfg)


def weight_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """What one decode step has to read of the weights."""
    return matmul_params(cfg) * dtype_bytes


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    """One K row and one V row in every FULL layer."""
    d = dims(cfg)
    return d["nF"] * 2 * d["KV"] * d["hd"] * dtype_bytes


def rule_state_bytes(cfg: dict) -> int:
    """The rule's state of ONE linear layer and request: [Hv, dv, dk]."""
    d = dims(cfg)
    width = {"float32": 4, "bfloat16": 2}[cfg.get("state_dtype", "float32")]
    return d["Hv"] * d["dv"] * d["dk"] * width


def state_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """What ONE request holds beside its KV rows, whatever its length: in
    every LINEAR layer the rule's state and the convolution's last K - 1
    inputs."""
    d = dims(cfg)
    return d["nL"] * (rule_state_bytes(cfg)
                      + (d["K"] - 1) * d["conv"] * dtype_bytes)


def scan_flops(cfg: dict, tlen: int) -> float:
    """The chunk scan over tlen tokens, all LINEAR layers and heads (the
    table at the top of this file)."""
    d = dims(cfg)
    C, dk, dv = CHUNK, d["dk"], d["dv"]
    chunk = (2.0 * C * C * (3 * dk + 2 * dv) + 2.0 * C ** 3 / 3
             + 6.0 * C * dk * dv)
    return d["nL"] * d["Hv"] * -(-tlen // C) * chunk


def scan_bytes(cfg: dict, tlen: int, dtype_bytes: int = 2) -> float:
    """q, k, v read and o written in the activations' type, g and beta in
    float32, the state written once."""
    d = dims(cfg)
    row = (d["conv"] + d["Hv"] * d["dv"]) * dtype_bytes + 2 * d["Hv"] * 4
    return float(d["nL"] * (tlen * row + rule_state_bytes(cfg)))


def step_flops(cfg: dict) -> float:
    """The rule for ONE decoded token, all LINEAR layers and heads."""
    d = dims(cfg)
    return 7.0 * d["nL"] * d["Hv"] * d["dv"] * d["dk"]


def prefill_flops(cfg: dict, tlen: int) -> float:
    """A prompt of tlen real tokens: the products of every layer over every
    token (the convolution's taps among them), the head once, causal
    attention in the FULL layers, the scan in the LINEAR ones."""
    d = dims(cfg)
    norms = d["nF"] * (d["H"] + d["KV"]) * d["hd"] \
        + d["nL"] * (2 * d["Hv"] + d["dv"]) + 2 * d["L"] * d["D"]
    per_tok = 2.0 * (matmul_params(cfg) - d["D"] * d["V"] - d["D"] - norms)
    attn = 2.0 * d["nF"] * d["H"] * d["hd"] * tlen * tlen
    return per_tok * tlen + 2.0 * d["D"] * d["V"] + attn \
        + scan_flops(cfg, tlen)


def decode_flops(cfg: dict, context: int) -> float:
    """One output token attending `context` cached rows."""
    d = dims(cfg)
    return 2.0 * matmul_params(cfg) \
        + 4.0 * d["nF"] * d["H"] * d["hd"] * context + step_flops(cfg)


def prefill_work(cfg: dict, tlen: int):
    """(operations, bytes) of one prefill of tlen real tokens: the weights
    read once, the prompt's KV rows and one request's state written."""
    return (prefill_flops(cfg, tlen),
            weight_bytes(cfg) + tlen * kv_bytes_per_token(cfg)
            + state_bytes(cfg))


def burst_work(cfg: dict, decode_steps: int, decodes):
    """(operations, bytes) of one burst: the weights once per executed
    decode step, the LIVE KV rows each emitted token attends and the row it
    writes, and a request's state read and written once a token, whatever
    its context."""
    tokens = sum(n for _, n in decodes)
    read = sum(arith.live_kv_rows(c, n) for c, n in decodes)
    # token by token decode_flops(cfg, rows it attends), in closed form
    flops = tokens * decode_flops(cfg, 0) \
        + read * (decode_flops(cfg, 1) - decode_flops(cfg, 0))
    return flops, (decode_steps * weight_bytes(cfg)
                   + (read + tokens) * kv_bytes_per_token(cfg)
                   + tokens * 2 * state_bytes(cfg))


def scope_work(cfg: dict, scope: str, steps):
    """(operations, bytes) the window's work needs under one device-side
    scope of the program, from the runner's `steps`: `gdn_scan` the chunk
    scans of its prefills, `gdn_step` the rule for its decoded tokens (the
    state read and written once a token). The same work whatever
    implements it; None for a scope this family does not count."""
    if scope == "gdn_scan":
        lens = [t for s in steps for t in s["prefills"]]
        return (sum(scan_flops(cfg, t) for t in lens),
                sum(scan_bytes(cfg, t) for t in lens))
    if scope == "gdn_step":
        tokens = sum(n for s in steps for _, n in s["decodes"])
        d = dims(cfg)
        return (tokens * step_flops(cfg),
                float(tokens * 2 * d["nL"] * rule_state_bytes(cfg)))
    return None


def held_bytes(cfg: dict, live_rows: int, n_live: int) -> int:
    """The cache and state held for n_live requests of live_rows rows
    together."""
    return live_rows * kv_bytes_per_token(cfg) + n_live * state_bytes(cfg)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward 2N and backward 4N over the matmul parameters, causal
    attention in the FULL layers, the scan in the LINEAR ones three times
    (forward, and twice its cost backward). No cell trains: arithmetic."""
    d = dims(cfg)
    return 6.0 * matmul_params(cfg) \
        + 6.0 * d["nF"] * d["H"] * d["hd"] * seq_len \
        + 3.0 * scan_flops(cfg, seq_len) / seq_len


def train_attention_calls(cfg: dict, batch: int, seq_len: int):
    """The attention kernel runs in the FULL layers only."""
    d = dims(cfg)
    return [((batch, d["H"], d["KV"], seq_len, d["hd"]), d["nF"])]
