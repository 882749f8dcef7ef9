"""The K-EXAONE family (model_type exaone_moe): window and full attention mixed
in one layer pattern (a window layer holds the K/V rows of a request's last
`sliding_window` positions in a ring, a full layer one K row and one V row a
token in pages), per-head QK-norm, rotation in the window layers only; a
dense SwiGLU FFN in the leading layer, then sparse layers: a router over all
`num_experts_routed` experts (sigmoid scores, a selection bias), this chip's
share of them (`num_experts` held from `experts_held_first` on) and a shared
expert every token takes.

The program's side is its own JSON-spec builder, the one every fleet replica
starts from (`paddle_tpu.inference.replica.build_batcher`): this file hands
it the model spec and the mix's `engine` settings and names no class of the
program. The reference is perfbench/ref/exaone_moe.py. There is no training
cell: `train_step` says so. Every function takes the configuration as the
plain dict of its file under perfbench/configs/.

The work is counted here, independent of what the program does. A token's
products, by layer: attention 2 D (H + KV) hd + 2 H hd D whatever the kind;
the dense FFN 6 D F; a sparse layer's router 2 D E, its shared expert 6 D Fe
x shared, and 6 D Fe for each of the token's k assignments that lands on a
held expert: held / E of them, the EXPECTATION under even routing (seeded
random weights route evenly; the engine counts what really landed,
`eng.stats["moe_expert_tokens"]`, and the metric `engine.moe_load_max_over_
mean` reads it). Attention: a full layer 4 H hd rows a token over the rows it
sees, a window layer over min(rows, window). Bytes: a decode step reads every
weight outside the experts once and the weights of the held experts its
tokens reach (n tokens reach held (1 - (1 - k / E)^n) of them a layer: all of
them once n >> E / k); a token reads its live rows in the full layers (8 KiB
a row at the published sizes) and min(rows, window) ring rows in the window
layers, and writes one row in each.
"""
from __future__ import annotations

from .. import arith

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"

# ------------------------------------------------------------- the program

GAINS = ("ln1", "ln2", "norm", "q_norm", "k_norm")
WHOLE = ("embed_tokens", "lm_head", "norm")     # leaves not stacked by layer


def dims(cfg: dict) -> dict:
    """The sizes the arithmetic needs, by short names."""
    H = cfg["num_attention_heads"]
    kinds, ffns = list(cfg["layer_types"]), list(cfg["mlp_layer_types"])
    d = {"L": cfg["num_hidden_layers"], "D": cfg["hidden_size"],
         "F": cfg["intermediate_size"], "Fe": cfg["moe_intermediate_size"],
         "V": cfg["vocab_size"], "H": H, "KV": cfg["num_key_value_heads"],
         "hd": cfg.get("head_dim") or cfg["hidden_size"] // H,
         "nG": kinds.count(FULL), "nW": kinds.count(SLIDING),
         "nD": ffns.count(DENSE), "nS": ffns.count(SPARSE),
         "E": cfg["num_experts_routed"], "Eh": cfg["num_experts"],
         "first": cfg["experts_held_first"],
         "k": cfg["num_experts_per_tok"], "W": cfg["sliding_window"],
         "shared": cfg["num_shared_experts"]}
    if len(kinds) != d["L"] or d["nG"] + d["nW"] != d["L"] \
            or len(ffns) != d["L"] or d["nD"] + d["nS"] != d["L"]:
        raise ValueError(
            f"layer_types names {len(kinds)} layers of kinds "
            f"{sorted(set(kinds))}, mlp_layer_types {len(ffns)} of "
            f"{sorted(set(ffns))}, num_hidden_layers {d['L']}")
    if d["first"] + d["Eh"] > d["E"]:
        raise ValueError(f"experts {d['first']}..{d['first'] + d['Eh'] - 1} "
                         f"are no range of the router's {d['E']}")
    return d


def shapes(cfg: dict) -> dict:
    """The program's parameter tree: the attention matrices and the norms
    stacked over all layers, each kind of FFN over its own."""
    d = dims(cfg)
    L, D, F, Fe, V, nD, nS = (d[k] for k in
                              ("L", "D", "F", "Fe", "V", "nD", "nS"))
    q, kv, Fs = d["H"] * d["hd"], d["KV"] * d["hd"], d["shared"] * d["Fe"]
    out = {"embed_tokens": (V, D),
           "wq": (L, D, q), "wk": (L, D, kv), "wv": (L, D, kv),
           "wo": (L, q, D), "q_norm": (L, d["hd"]), "k_norm": (L, d["hd"]),
           "w_gate": (nD, D, F), "w_up": (nD, D, F), "w_down": (nD, F, D),
           "gate_w": (nS, D, d["E"]), "gate_bias": (nS, d["E"]),
           "moe_w_gate": (nS, d["Eh"], D, Fe),
           "moe_w_up": (nS, d["Eh"], D, Fe),
           "moe_w_down": (nS, d["Eh"], Fe, D)}
    if Fs:
        out.update({"shared_w_gate": (nS, D, Fs), "shared_w_up": (nS, D, Fs),
                    "shared_w_down": (nS, Fs, D)})
    out.update({"lm_head": (D, V), "ln1": (L, D), "ln2": (L, D),
                "norm": (D,)})
    return out


def model_spec(cfg: dict, max_len: int) -> dict:
    """The configuration as the program's JSON model spec states it."""
    d = dims(cfg)
    if cfg["qk_norm"] != "per_head" or cfg.get("n_group", 1) != 1 \
            or cfg.get("topk_group", 1) != 1:
        raise ValueError("this family states per-head QK-norm and no group "
                         "limit on the selection (n_group = topk_group = 1)")
    return {
        "vocab_size": d["V"], "hidden_size": d["D"],
        "intermediate_size": d["F"], "num_hidden_layers": d["L"],
        "num_attention_heads": d["H"], "num_key_value_heads": d["KV"],
        "head_dim": d["hd"], "max_position_embeddings": max(max_len, 128),
        "rms_norm_eps": cfg["rms_norm_eps"],
        "rope_theta": cfg["rope_parameters"]["rope_theta"],
        "tie_word_embeddings": bool(cfg.get("tie_word_embeddings", False)),
        "dtype": cfg.get("dtype", "bfloat16"),
        "qk_norm_per_head": True, "norm_placement": cfg["norm_placement"],
        "layer_types": list(cfg["layer_types"]),
        "rope_layer_types": list(cfg["rope_layer_types"]),
        "sliding_window": d["W"],
        "mlp_layer_types": list(cfg["mlp_layer_types"]),
        "num_experts": d["E"], "num_experts_per_tok": d["k"],
        "moe_intermediate_size": d["Fe"],
        "num_shared_experts": d["shared"],
        "scoring_func": cfg["scoring_func"],
        "norm_topk_prob": bool(cfg["norm_topk_prob"]),
        "routed_scaling_factor": cfg["routed_scaling_factor"],
        "experts_held": [d["first"], d["Eh"]]}


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
                     "pattern and not through the dropless expert layer yet "
                     "(ROADMAP Queue 2(a) M1)")


# ----------------------------------------------------------- the reference

def reference():
    from ..ref import exaone_moe
    return exaone_moe


def layer_axes(name: str, ndim: int):
    """Every leaf but the embedding, the head and the last norm is stacked
    on a leading axis (of its kind's layers): one norm a layer."""
    return None if name in WHOLE else tuple(range(1, ndim))


# ---------------------------------------------------------------- the work

def attn_params(cfg: dict) -> int:
    """One layer's attention matrices (the gains are no products)."""
    d = dims(cfg)
    return 2 * d["D"] * d["H"] * d["hd"] + 2 * d["D"] * d["KV"] * d["hd"]


def expert_params(cfg: dict) -> int:
    """One routed expert: three matrices."""
    d = dims(cfg)
    return 3 * d["D"] * d["Fe"]


def outside_params(cfg: dict) -> int:
    """Every parameter a token is multiplied with whatever it is routed to:
    attention, the dense FFN, routers, shared experts, the head."""
    d = dims(cfg)
    return (d["L"] * attn_params(cfg) + d["nD"] * 3 * d["D"] * d["F"]
            + d["nS"] * (d["D"] * d["E"]
                         + d["shared"] * expert_params(cfg))
            + d["D"] * d["V"])


def held_expert_params(cfg: dict) -> int:
    d = dims(cfg)
    return d["nS"] * d["Eh"] * expert_params(cfg)


def total_params(cfg: dict) -> int:
    """What the chip holds: the products' weights, the embedding, the gains
    and the selection bias."""
    d = dims(cfg)
    small = d["L"] * (2 * d["D"] + 2 * d["hd"]) + d["D"] + d["nS"] * d["E"]
    return (outside_params(cfg) + held_expert_params(cfg)
            + d["V"] * d["D"] + small)


def local_share(cfg: dict) -> float:
    """The share of a token's assignments that lands on held experts under
    even routing."""
    d = dims(cfg)
    return d["Eh"] / d["E"]


def experts_reached(cfg: dict, tokens: float) -> float:
    """Held experts of ONE sparse layer that `tokens` tokens reach under
    even routing: a token leaves an expert out with 1 - k / E."""
    d = dims(cfg)
    return d["Eh"] * (1.0 - (1.0 - d["k"] / d["E"]) ** max(tokens, 0.0))


def token_flops(cfg: dict) -> float:
    """One token's products outside attention's scores."""
    d = dims(cfg)
    return 2.0 * outside_params(cfg) \
        + 2.0 * d["nS"] * d["k"] * local_share(cfg) * expert_params(cfg)


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    """One K row and one V row in every FULL layer."""
    d = dims(cfg)
    return d["nG"] * 2 * d["KV"] * d["hd"] * dtype_bytes


def ring_row_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """One K row and one V row of ONE window layer's ring."""
    d = dims(cfg)
    return 2 * d["KV"] * d["hd"] * dtype_bytes


def state_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """What ONE request holds beside its KV rows, whatever its length: a
    ring of `sliding_window` rows in every window layer."""
    d = dims(cfg)
    return d["nW"] * d["W"] * ring_row_bytes(cfg, dtype_bytes)


def window_pairs(cfg: dict, tlen: int) -> float:
    """(query, key) pairs a window layer's causal attention holds over a
    prompt of tlen tokens: query i sees min(i + 1, window) keys."""
    w = min(dims(cfg)["W"], tlen)
    return tlen * w - w * (w - 1) / 2.0


def prefill_flops(cfg: dict, tlen: int) -> float:
    """A prompt of tlen real tokens: the products of every layer over every
    token but the head's (once), causal attention in the full layers, the
    window's in the others."""
    d = dims(cfg)
    head = 2.0 * d["D"] * d["V"]
    attn = 4.0 * d["H"] * d["hd"] * (
        d["nG"] * tlen * (tlen + 1) / 2.0 + d["nW"] * window_pairs(cfg, tlen))
    return (token_flops(cfg) - head) * tlen + head + attn


def step_weight_bytes(cfg: dict, tokens: float, dtype_bytes: int = 2) -> float:
    """The weights a pass over `tokens` tokens has to read: those outside
    the experts once, and the held experts the tokens reach."""
    d = dims(cfg)
    return dtype_bytes * (outside_params(cfg) + d["nS"] * expert_params(cfg)
                          * experts_reached(cfg, tokens))


def prefill_work(cfg: dict, tlen: int):
    """(operations, bytes) of one prefill of tlen real tokens: the weights
    read once, the prompt's KV rows and one request's rings written."""
    return (prefill_flops(cfg, tlen),
            step_weight_bytes(cfg, tlen) + tlen * kv_bytes_per_token(cfg)
            + state_bytes(cfg))


def ring_rows_read(cfg: dict, ctx0: int, n_new: int) -> int:
    """Ring rows n_new consecutive tokens read in ONE window layer when the
    first attends ctx0 + 1 positions: min(positions, window) each."""
    w = dims(cfg)["W"]
    return sum(min(ctx0 + 1 + i, w) for i in range(n_new))


def burst_work(cfg: dict, decode_steps: int, decodes):
    """(operations, bytes) of one burst, step by step: every executed decode
    step reads the weights outside the experts and the held experts its
    tokens in flight reach; every emitted token reads its LIVE rows in the
    full layers and min(rows, window) ring rows in the window layers, and
    writes one row in each."""
    d = dims(cfg)
    tokens = sum(n for _, n in decodes)
    full_rows = sum(arith.live_kv_rows(c, n) for c, n in decodes)
    ring_rows = sum(ring_rows_read(cfg, c, n) for c, n in decodes)
    scores = 4.0 * d["H"] * d["hd"]
    flops = tokens * token_flops(cfg) \
        + scores * (d["nG"] * full_rows + d["nW"] * ring_rows)
    in_flight = tokens / decode_steps if decode_steps else 0.0
    byts = decode_steps * step_weight_bytes(cfg, in_flight) \
        + (full_rows + tokens) * kv_bytes_per_token(cfg) \
        + d["nW"] * (ring_rows + tokens) * ring_row_bytes(cfg)
    return flops, byts


def scope_work(cfg: dict, scope: str, steps):
    """(operations, bytes) the window's work needs under one device-side
    scope of the program, from the runner's `steps`: `moe_experts` the
    grouped products of its prefills and decode steps (the rows that land on
    held experts in and out, the reached experts' weights), `win_read` the
    ring reads of its decoded tokens, `win_attn` the window layers'
    attention of its prefills (q, k, v read and o written once). The same
    work whatever implements it; None for a scope this family does not
    count."""
    d = dims(cfg)
    if scope == "moe_experts":
        flops = byts = 0.0
        row = 2 * d["D"] * 2                    # a row in and out, bf16
        for s in steps:
            passes = [(float(t), 1) for t in s["prefills"]]
            if s["decode_steps"]:
                tokens = sum(n for _, n in s["decodes"])
                passes.append((tokens / s["decode_steps"],
                               s["decode_steps"]))
            for tokens, times in passes:
                landed = tokens * d["k"] * local_share(cfg)     # a layer
                flops += times * d["nS"] * landed * 2.0 * expert_params(cfg)
                byts += times * d["nS"] * (
                    2 * expert_params(cfg) * experts_reached(cfg, tokens)
                    + landed * row)
        return flops, byts
    if scope == "win_read":
        rows = sum(ring_rows_read(cfg, c, n)
                   for s in steps for c, n in s["decodes"])
        return (4.0 * d["H"] * d["hd"] * d["nW"] * rows,
                float(d["nW"] * rows * ring_row_bytes(cfg)))
    if scope == "win_attn":
        lens = [t for s in steps for t in s["prefills"]]
        rows = 2 * d["hd"] * (2 * d["H"] + 2 * d["KV"])     # q, o; k, v
        return (4.0 * d["H"] * d["hd"] * d["nW"]
                * sum(window_pairs(cfg, t) for t in lens),
                float(d["nW"] * sum(lens) * rows))
    return None


def held_bytes(cfg: dict, live_rows: int, n_live: int) -> int:
    """The cache and rings held for n_live requests of live_rows rows
    together."""
    return live_rows * kv_bytes_per_token(cfg) + n_live * state_bytes(cfg)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward 2N and backward 4N over the parameters a token meets (all its
    k experts, as in the uncut model), causal attention in the full layers,
    the window's in the others. No cell trains: arithmetic."""
    d = dims(cfg)
    met = outside_params(cfg) + d["nS"] * d["k"] * expert_params(cfg)
    pairs = d["nG"] * (seq_len + 1) / 2.0 \
        + d["nW"] * window_pairs(cfg, seq_len) / seq_len
    return 6.0 * met + 12.0 * d["H"] * d["hd"] * pairs


def train_attention_calls(cfg: dict, batch: int, seq_len: int):
    """The attention kernel runs in every layer (with a window in nW)."""
    d = dims(cfg)
    return [((batch, d["H"], d["KV"], seq_len, d["hd"]), d["L"])]
