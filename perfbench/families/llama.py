"""The Llama family: every decoder layer alike (GQA attention with rope, a
gated FFN, two RMS norms), stacked on the leading axis `L`, and a KV cache
that grows by one K row and one V row a token in every layer.

The program's side is `LlamaConfig`, `LlamaTrainStep` and
`ContinuousBatcher` over `models/llama.py`; the reference is
perfbench/ref/llama.py. The training arithmetic is bench.py's (6*(N -
N_embed) + 6*L*H*hd*T per token, recomputation not counted); both that and
the live-KV accounting (arith.live_kv_rows) are copies, so that a later PR
to the program cannot move the yardstick. Every function takes the
configuration as the plain dict of its file under perfbench/configs/.
"""
from __future__ import annotations

import gc

from .. import arith

# ------------------------------------------------------------- the program

GAINS = ("ln1", "ln2", "norm")


def dims(cfg: dict) -> dict:
    """The sizes the arithmetic needs, by short names."""
    H = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // H
    return {"L": cfg["num_hidden_layers"], "D": cfg["hidden_size"],
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "H": H, "KV": cfg["num_key_value_heads"], "hd": hd,
            "tied": bool(cfg.get("tie_word_embeddings", False))}


def shapes(cfg: dict) -> dict:
    d = dims(cfg)
    L, D, F, V, H, KV, hd = (d[k] for k in ("L", "D", "F", "V", "H", "KV",
                                            "hd"))
    return {"embed_tokens": (V, D), "wq": (L, D, H * hd),
            "wk": (L, D, KV * hd), "wv": (L, D, KV * hd),
            "wo": (L, H * hd, D), "w_gate": (L, D, F), "w_up": (L, D, F),
            "w_down": (L, F, D), "lm_head": (D, V),
            "ln1": (L, D), "ln2": (L, D), "norm": (D,)}


def llama_config(cfg: dict, seq_len: int):
    import jax.numpy as jnp
    from paddle_tpu.models import LlamaConfig
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=max(seq_len, 128),
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        dtype=jnp.dtype(cfg.get("dtype", "bfloat16")))


def engine(cfg: dict, traffic: dict, weights):
    """The mix's `engine` settings are ContinuousBatcher's own arguments,
    passed through as they stand (a list becomes a tuple, a null is left to
    the engine's default), so a mix that sets another option of the engine
    needs no edit here. Greedy unless the mix says otherwise: `correct`
    compares greedy tokens."""
    from paddle_tpu.inference import ContinuousBatcher
    kw = {"temperature": 0.0}
    for k, v in traffic["engine"].items():
        if v is not None:
            kw[k] = tuple(v) if isinstance(v, list) else v
    return ContinuousBatcher(llama_config(cfg, kw["max_len"]), weights, **kw)


def train_step(cfg: dict, job: dict, mesh, make_weights):
    """The program under test with the benchmark's weights in it."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import LlamaTrainStep
    from paddle_tpu.optimizer import AdamW

    opt = job["optimizer"]
    step = LlamaTrainStep(
        llama_config(cfg, job["seq_len"]), mesh=mesh, remat=job["remat"],
        seed=0, optimizer=AdamW(
            learning_rate=opt["lr"], beta1=opt["beta1"], beta2=opt["beta2"],
            epsilon=opt["eps"], weight_decay=opt["weight_decay"],
            moment_dtype=jnp.dtype(opt["moment_dtype"])))
    # the program's own initial state goes before the benchmark's is made,
    # so that the two never stand on the device together
    step.load_resilience_state({"params": None, "opt_state": None, "step": 0})
    gc.collect()
    weights = make_weights()
    if mesh is not None:
        from paddle_tpu.models.llama import shard_llama_params
        weights = shard_llama_params(weights, step.config, mesh)
    step.load_resilience_state({"params": weights,
                                "opt_state": step.optimizer.init_state(weights),
                                "step": 0})
    jax.block_until_ready(step.params)
    return step


# ----------------------------------------------------------- the reference

def reference():
    from ..ref import llama
    return llama


def layer_axes(name: str, ndim: int):
    """A layer-stacked parameter gives one leaf a layer: its norm is taken
    over every axis but the first."""
    stacked = ndim == 3 or (ndim == 2 and name in GAINS)
    return tuple(range(1, ndim)) if stacked else None


# ---------------------------------------------------------------- the work

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


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """bench.py's arithmetic: forward 2N and backward 4N over the matmul
    parameters, plus causal attention 6*L*H*hd*T. Recomputed operations
    (remat) are not counted."""
    d = dims(cfg)
    return 6.0 * matmul_params(cfg) + 6.0 * d["L"] * d["H"] * d["hd"] * seq_len


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


def prefill_work(cfg: dict, tlen: int):
    """(operations, bytes) of one prefill of tlen real tokens: the weights
    read once, the prompt's KV written."""
    return (prefill_flops(cfg, tlen),
            weight_bytes(cfg) + tlen * kv_bytes_per_token(cfg))


def burst_work(cfg: dict, decode_steps: int, decodes):
    """(operations, bytes) of one burst: the weights once per executed
    decode step, the LIVE KV rows each emitted token attends, the rows
    written. It is the same work whether a gather or a kernel does it."""
    flops = sum(decode_flops(cfg, c + 1 + j)
                for c, n in decodes for j in range(n))
    rows = sum(arith.live_kv_rows(c, n) + n for c, n in decodes)
    return flops, (decode_steps * weight_bytes(cfg)
                   + rows * kv_bytes_per_token(cfg))


def held_bytes(cfg: dict, live_rows: int, n_live: int) -> int:
    """The cache held for n_live requests of live_rows rows together: no
    state of fixed size a request."""
    return live_rows * kv_bytes_per_token(cfg)


def train_attention_calls(cfg: dict, batch: int, seq_len: int):
    """Every train step runs the flash forward and backward once in every
    layer, all of one shape."""
    d = dims(cfg)
    return [((batch, d["H"], d["KV"], seq_len, d["hd"]), d["L"])]
