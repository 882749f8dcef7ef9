"""Operations and bytes from shapes: the yardstick's arithmetic.

Every function takes the configuration as the plain dict of its file under
perfbench/configs/ and returns counts; nothing here imports the program.
The training arithmetic is bench.py's (6*(N - N_embed) + 6*L*H*hd*T per
token, recomputation not counted), the live-KV accounting is the exact-live
count of benchmarks/decode_bench.py::ragged_read_bytes; both are copies, so
that a later PR to the program cannot move the yardstick.
"""
from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def dims(cfg: dict) -> dict:
    """The sizes the arithmetic needs, by short names."""
    H = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // H
    return {"L": cfg["num_hidden_layers"], "D": cfg["hidden_size"],
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "H": H, "KV": cfg["num_key_value_heads"], "hd": hd,
            "tied": bool(cfg.get("tie_word_embeddings", False))}


def layer_params(cfg: dict) -> int:
    """Parameters of one decoder layer: q, k, v, o, gate, up, down and the
    two norm vectors."""
    d = dims(cfg)
    attn = d["D"] * d["H"] * d["hd"] * 2 + d["D"] * d["KV"] * d["hd"] * 2
    return attn + 3 * d["D"] * d["F"] + 2 * d["D"]


def embed_params(cfg: dict) -> int:
    d = dims(cfg)
    return d["V"] * d["D"]


def total_params(cfg: dict) -> int:
    d = dims(cfg)
    head = 0 if d["tied"] else d["D"] * d["V"]
    return d["L"] * layer_params(cfg) + embed_params(cfg) + head + d["D"]


def matmul_params(cfg: dict) -> int:
    """N - N_embed: every parameter a token is multiplied with (the layers
    and the output head; the embedding table is a lookup)."""
    return total_params(cfg) - embed_params(cfg)


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    """One K row and one V row in every layer."""
    d = dims(cfg)
    return d["L"] * 2 * d["KV"] * d["hd"] * dtype_bytes


def weight_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """What one decode step has to read of the weights: N - N_embed."""
    return matmul_params(cfg) * dtype_bytes


# ---------------------------------------------------------------- training

def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """bench.py's arithmetic: forward 2N and backward 4N over the matmul
    parameters, plus causal attention 6*L*H*hd*T. Recomputed operations
    (remat) are not counted."""
    d = dims(cfg)
    return 6.0 * matmul_params(cfg) + 6.0 * d["L"] * d["H"] * d["hd"] * seq_len


# ----------------------------------------------------------------- serving

def prefill_flops(cfg: dict, tlen: int) -> float:
    """A prompt of tlen real tokens: the layers over every token, the head
    once (only the last position's logits are needed), causal attention."""
    d = dims(cfg)
    per_tok = 2.0 * d["L"] * (layer_params(cfg) - 2 * d["D"])
    attn = 2.0 * d["L"] * d["H"] * d["hd"] * tlen * tlen  # 4*T^2/2
    return per_tok * tlen + 2.0 * d["D"] * d["V"] + attn


def decode_flops(cfg: dict, context: int) -> float:
    """One output token attending `context` cached rows."""
    d = dims(cfg)
    return 2.0 * matmul_params(cfg) + 4.0 * d["L"] * d["H"] * d["hd"] * context


def live_kv_rows(ctx0: int, n_new: int) -> int:
    """Rows read by n_new consecutive tokens when the first attends ctx0+1
    rows (itself included): token t reads exactly t + 1 rows."""
    return n_new * (ctx0 + 1) + n_new * (n_new - 1) // 2


# ----------------------------------------------------------------- kernels

def flash_fwd_cost(batch: int, heads: int, kv_heads: int, seq: int,
                   head_dim: int, dtype_bytes: int = 2):
    """(flops, bytes) one causal flash forward needs: QK^T and PV over the
    lower triangle; q, k, v read and o, lse written once."""
    flops = 4.0 * batch * heads * seq * seq * head_dim / 2
    byts = batch * seq * head_dim * dtype_bytes * (2 * heads + 2 * kv_heads) \
        + batch * heads * seq * 4
    return flops, float(byts)


def flash_bwd_cost(batch: int, heads: int, kv_heads: int, seq: int,
                   head_dim: int, dtype_bytes: int = 2):
    """(flops, bytes) the causal backward needs from q, k, v, do and lse:
    five products (S, dP, dV, dK, dQ). A kernel pair that forms S and dP
    twice does more than this and is not credited for it."""
    flops = 10.0 * batch * heads * seq * seq * head_dim / 2
    byts = batch * seq * head_dim * dtype_bytes * (3 * heads + 4 * kv_heads) \
        + 2 * batch * heads * seq * 4
    return flops, float(byts)


# ------------------------------------------------------------------- peaks

def load_peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown kind is an error, never a
    default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks known for device_kind {device_kind!r}: "
                       f"add it to perfbench/peaks.json with its source")
    return table[device_kind]


def roofline_seconds(flops: float, byts: float, peaks: dict):
    """(least seconds, which bound applies)."""
    tf = flops / peaks["bf16_flops_per_s"]
    tb = byts / peaks["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")
