"""Llama — the flagship model family.

Reference capability: the reference trains Llama via its auto-parallel engine
(/root/reference/test/auto_parallel/hybrid_strategy/semi_auto_llama.py, and
PaddleNLP's LlamaForCausalLM on top of paddle.nn); SURVEY.md §6 sets the
north-star benchmark (Llama-2 pretrain ≥45% MFU on v5p).

TPU-native design (MaxText-shaped, not a torch translation):
  * parameters live LAYER-STACKED ([L, ...] leading dim) in a flat dict —
    one `lax.scan` runs the trunk (O(1) compile time in depth), and the same
    tree re-chunks into [S, L/S, ...] for pipeline stages;
  * sharding is declarative: PARAM_RULES maps param name → logical axes, and
    `logical_to_mesh` resolves them onto whatever mesh axes exist
    ('dp'/'fsdp'/'pp'/'tp'/'sp'/'ep') — GSPMD inserts all collectives;
  * attention uses the Pallas flash kernel on TPU (ops/flash_attention),
    bf16 activations with fp32 RMSNorm/softmax/rope;
  * activations carry constraints: batch on dp, sequence on sp/tp (Megatron
    SP), heads on tp.
The eager `LlamaForCausalLM` Layer wraps the same functions for paddle-style
use (loss.backward(), generate()).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import dtypes as _dt
from ..core.engine import apply
from ..core.tensor import Parameter, Tensor
from ..nn.layer.layers import Layer
from ..observability import spans as _spans

__all__ = ["LlamaConfig", "llama_init_params", "llama_forward", "llama_loss",
           "LlamaForCausalLM", "shard_llama_params", "llama_param_specs"]


@dataclasses.dataclass(frozen=True)  # hashable → usable as a static jit arg
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float | None = 10000.0
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    # MoE variant (Mixtral/DeepSeekMoE class)
    num_experts: int = 0
    num_experts_per_tok: int = 2
    moe_intermediate_size: int | None = None
    # --- what a model of another family states (all defaults: a Llama) ---
    # stated head size (None: hidden_size / num_attention_heads)
    head_dim: int | None = None
    # (rope_theta None, above: no rotation is applied)
    # RMSNorm over the whole q and k projections, before the split into heads
    qk_norm: bool = False
    # "pre": x + f(norm(x)) (Llama); "post": x + norm(f(x)) (the OLMo 2
    # family's reordered norm), with the same ln1 / ln2 gains
    norm_placement: str = "pre"
    # ordered layer kinds, FULL, LINEAR or SLIDING (None: every layer FULL).
    # A LINEAR layer's mixer is the gated delta rule (ops/gated_delta.py): it
    # holds no K/V rows, but a state [value heads, value dim, key dim] and
    # the last conv_kernel - 1 inputs of its convolution, for every request.
    # A SLIDING layer is attention over the last `sliding_window` positions:
    # it holds no page, but a ring of that many K/V rows for every request
    layer_types: tuple | None = None
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = False   # beta = 2 sigmoid, not sigmoid
    state_dtype: Any = jnp.float32
    sliding_window: int = 0
    # per-head QK-norm: RMSNorm of every q and k head over its head_dim,
    # one gain vector of head_dim each (qk_norm above: the whole projection)
    qk_norm_per_head: bool = False
    # the layer kinds whose q and k are rotated (None: every attention layer,
    # where rope_theta is set)
    rope_layer_types: tuple | None = None
    # ordered FFN kinds, DENSE or SPARSE (None: every layer alike, the
    # training block `_moe_block` where num_experts > 0). A SPARSE layer is
    # the dropless expert layer of ops/moe_dropless.py: the router scores
    # all `num_experts`, this device computes the experts it holds
    # (`experts_held`: (first, count); None: all) and the shared experts
    mlp_layer_types: tuple | None = None
    num_shared_experts: int = 0
    scoring_func: str = "softmax"           # or "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    experts_held: tuple | None = None

    FULL, LINEAR = "full_attention", "linear_attention"
    SLIDING = "sliding_attention"
    DENSE, SPARSE = "dense", "sparse"

    def __post_init__(self):
        set_ = lambda k, v: object.__setattr__(self, k, v)  # frozen
        if self.head_dim is None:
            set_("head_dim", self.hidden_size // self.num_attention_heads)
        if self.norm_placement not in ("pre", "post"):
            raise ValueError(f"norm_placement {self.norm_placement!r}: "
                             "'pre' or 'post'")
        if self.scoring_func not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring_func {self.scoring_func!r}: "
                             "'softmax' or 'sigmoid'")
        if self.mlp_layer_types is not None:
            set_("mlp_layer_types", tuple(self.mlp_layer_types))
            ffn = set(self.mlp_layer_types)
            if len(self.mlp_layer_types) != self.num_hidden_layers \
                    or not ffn <= {self.DENSE, self.SPARSE}:
                raise ValueError(
                    f"mlp_layer_types names {len(self.mlp_layer_types)} "
                    f"layers of kinds {sorted(ffn)}: it must give "
                    f"num_hidden_layers={self.num_hidden_layers} entries, "
                    f"each {self.DENSE!r} or {self.SPARSE!r}")
            if self.SPARSE in ffn and not (
                    1 <= self.num_experts_per_tok <= self.num_experts):
                raise ValueError(
                    f"a {self.SPARSE!r} layer routes each token to "
                    f"num_experts_per_tok={self.num_experts_per_tok} of "
                    f"num_experts={self.num_experts}")
        if self.experts_held is not None:
            first, count = (int(v) for v in self.experts_held)
            set_("experts_held", (first, count))
            if first < 0 or count < 1 or first + count > self.num_experts:
                raise ValueError(
                    f"experts_held=(first {first}, count {count}) is no "
                    f"range of the {self.num_experts} experts")
        if self.rope_layer_types is not None:
            set_("rope_layer_types", tuple(self.rope_layer_types))
        if self.layer_types is None:
            if self.rope_layer_types is not None or self.sliding_window:
                raise ValueError("rope_layer_types and sliding_window go "
                                 "with a layer pattern (layer_types)")
            return
        set_("layer_types", tuple(self.layer_types))
        kinds = set(self.layer_types)
        known = (self.FULL, self.LINEAR, self.SLIDING)
        if len(self.layer_types) != self.num_hidden_layers \
                or not kinds <= set(known):
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers of kinds "
                f"{sorted(kinds)}: it must give num_hidden_layers="
                f"{self.num_hidden_layers} entries, each {known[0]!r}, "
                f"{known[1]!r} or {known[2]!r}")
        if self.rope_layer_types is not None \
                and not set(self.rope_layer_types) <= {self.FULL,
                                                       self.SLIDING}:
            raise ValueError(
                f"rope_layer_types {self.rope_layer_types}: the kinds that "
                f"rotate q and k are {self.FULL!r} and {self.SLIDING!r} (a "
                f"{self.LINEAR!r} layer has no rotation)")
        if self.SLIDING in kinds and self.sliding_window < 1:
            raise ValueError(f"a {self.SLIDING!r} layer needs "
                             "sliding_window >= 1")
        if self.LINEAR in kinds:
            if self.linear_num_key_heads != self.linear_num_value_heads:
                raise ValueError(
                    "a linear layer with more value heads than key heads "
                    "(keys shared by a group) is not supported: "
                    f"{self.linear_num_value_heads} value heads, "
                    f"{self.linear_num_key_heads} key heads")
            if min(self.linear_num_key_heads, self.linear_key_head_dim,
                   self.linear_value_head_dim) < 1 \
                    or self.linear_conv_kernel_dim < 2:
                raise ValueError("a linear layer needs linear_num_key_heads, "
                                 "linear_key_head_dim, linear_value_head_dim "
                                 ">= 1 and linear_conv_kernel_dim >= 2")
        if self.num_experts > 0 and self.mlp_layer_types is None:
            raise ValueError(
                f"experts under a layer pattern ({known[0]!r} / {known[1]!r} "
                f"/ {known[2]!r}) are served by the dropless layer only: "
                "state mlp_layer_types (the training block `_moe_block` "
                "drops tokens over capacity and knows no pattern)")

    # what the pattern means for whoever holds per-request state
    def kinds(self) -> tuple:
        """The kind of every layer, in order."""
        return self.layer_types or (self.FULL,) * self.num_hidden_layers

    def kind_index(self, layer: int) -> tuple:
        """(kind, the layer's place among the layers of its kind): where
        its per-request state is stacked (FULL: the page pools; LINEAR:
        state and conv; SLIDING: the rings) and, for a LINEAR layer, its
        mixer's parameters (``attn_index`` for the other two)."""
        kinds = self.kinds()
        return kinds[layer], kinds[:layer].count(kinds[layer])

    def attn_index(self, layer: int) -> int:
        """An attention layer's (FULL or SLIDING) place among the attention
        layers: where wq, wk, wv, wo and the QK-norm gains are stacked."""
        return sum(k != self.LINEAR for k in self.kinds()[:layer])

    def ffn_index(self, layer: int) -> tuple:
        """(FFN kind, the layer's place among the layers of that FFN kind):
        where its FFN's parameters are stacked. Without mlp_layer_types
        every layer is alike and stacked over all."""
        if self.mlp_layer_types is None:
            return (self.SPARSE if self.num_experts > 0 else self.DENSE), layer
        kinds = self.mlp_layer_types
        return kinds[layer], kinds[:layer].count(kinds[layer])

    def rotates(self, kind: str) -> bool:
        """Are q and k of a layer of this kind rotated?"""
        return self.rope_theta is not None and (
            self.rope_layer_types is None or kind in self.rope_layer_types)

    @property
    def num_kv_layers(self) -> int:
        """Layers that hold K/V rows for every token: the ones a KV page
        spans (FULL; not SLIDING, whose rows live in a ring)."""
        return self.kinds().count(self.FULL)

    @property
    def num_attn_layers(self) -> int:
        return self.num_hidden_layers - self.num_linear_layers

    @property
    def num_linear_layers(self) -> int:
        return self.kinds().count(self.LINEAR)

    @property
    def num_sliding_layers(self) -> int:
        return self.kinds().count(self.SLIDING)

    @property
    def num_sparse_layers(self) -> int:
        """Layers whose FFN is the dropless expert layer."""
        return (self.mlp_layer_types or ()).count(self.SPARSE)

    @property
    def num_dense_layers(self) -> int:
        if self.mlp_layer_types is None:
            return 0 if self.num_experts > 0 else self.num_hidden_layers
        return self.mlp_layer_types.count(self.DENSE)

    @property
    def held(self) -> tuple:
        """(first, count) of the experts this device holds."""
        return self.experts_held or (0, self.num_experts)

    @property
    def is_recurrent(self) -> bool:
        """Does a request hold a recurrent state (LINEAR layers)?"""
        return self.num_linear_layers > 0

    @property
    def has_ring(self) -> bool:
        """Does a request hold a ring of K/V rows (SLIDING layers)?"""
        return self.num_sliding_layers > 0

    @property
    def slot_state(self) -> str:
        """What a request holds beside its K/V pages, as the engine's
        refusals name it ("" where it holds pages only)."""
        return " and ".join(
            n for n, on in (("a recurrent state (linear-attention layers)",
                             self.is_recurrent),
                            ("a ring of K/V rows (window layers)",
                             self.has_ring)) if on)

    @property
    def linear_conv_dim(self) -> int:
        """Width of the convolution: q~, k~ and v~ side by side."""
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    def state_shapes(self, max_batch: int) -> dict:
        """{leaf: (shape, dtype)} of ONE linear layer's per-request state
        for `max_batch` slots."""
        return {"state": ((max_batch, self.linear_num_value_heads,
                           self.linear_value_head_dim,
                           self.linear_key_head_dim), self.state_dtype),
                "conv": ((max_batch, self.linear_conv_kernel_dim - 1,
                          self.linear_conv_dim), self.dtype)}

    def ring_shapes(self, max_batch: int) -> dict:
        """{leaf: (shape, dtype)} of ONE window layer's ring for `max_batch`
        slots: the K and V rows of a request's last `sliding_window`
        positions, position p in row p % sliding_window. The shape of a
        page pool with one page of `sliding_window` rows a slot, so the
        pools' kernels read and write it."""
        shape = (max_batch, self.sliding_window, self.num_key_value_heads,
                 self.head_dim)
        return {"win_k": (shape, self.dtype), "win_v": (shape, self.dtype)}

    def state_bytes_per_request(self) -> int:
        """Bytes ONE request holds beside its K/V pages, whatever its
        length: the recurrent state of its LINEAR layers and the rings of
        its SLIDING ones (0 for a model of full-attention layers only)."""
        def one(shapes):
            return sum(int(np.prod(shape[1:])) * jnp.dtype(dt).itemsize
                       for shape, dt in shapes.values())
        return (self.num_linear_layers * one(self.state_shapes(1))
                + self.num_sliding_layers * one(self.ring_shapes(1)))

    def require_uniform(self, what: str) -> None:
        """Paths that know one kind of layer say so by name."""
        if self.layer_types is not None or self.mlp_layer_types is not None \
                or self.qk_norm or self.qk_norm_per_head \
                or self.norm_placement != "pre" or self.rope_theta is None:
            raise NotImplementedError(
                f"{what} runs models of one layer kind (pre-norm, rope, "
                f"every layer {self.FULL!r} with one kind of FFN): a layer "
                f"pattern ({self.FULL!r}, {self.LINEAR!r}, {self.SLIDING!r}; "
                f"FFNs {self.DENSE!r} / {self.SPARSE!r}) is served by "
                "ContinuousBatcher(kv_layout='paged') only (ROADMAP Queue "
                "2(a) M1-M3)")

    @classmethod
    def tiny(cls, **kw):
        d = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                 num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
                 max_position_embeddings=128, dtype=jnp.float32)
        d.update(kw)
        return cls(**d)

    @classmethod
    def llama2_7b(cls, **kw):
        return cls(**{**dict(hidden_size=4096, intermediate_size=11008,
                             num_hidden_layers=32, num_attention_heads=32,
                             num_key_value_heads=32), **kw})

    @classmethod
    def llama2_13b(cls, **kw):
        return cls(**{**dict(hidden_size=5120, intermediate_size=13824,
                             num_hidden_layers=40, num_attention_heads=40,
                             num_key_value_heads=40), **kw})


# logical axis name → candidate mesh axes, first present wins
# (MaxText-style logical sharding rules)
LOGICAL_RULES = {
    "vocab": ("tp", "mp"),
    "embed": (),                # hidden dim of embeddings: replicated
    "hidden": (),               # residual stream
    "heads": ("tp", "mp"),      # attention heads / ffn columns
    "kv_heads": ("tp", "mp"),
    "mlp": ("tp", "mp"),
    "layers": ("pp",),          # only used by the pipeline chunking
    "fsdp": ("fsdp", "sharding", "dp"),
    "expert": ("ep", "dp"),
    "batch": ("dp", "fsdp"),
    # sequence: a context-parallel "sep" axis wins (ring attention keeps
    # seq sharded THROUGH attention); else Megatron-SP over tp
    "seq": ("sep", "sp", "tp", "mp"),
}

# param name → logical axes per dim (leading 'stack' dim for layer-stacked
# params is added automatically)
PARAM_RULES = {
    "embed_tokens": ("vocab", "embed"),
    "wq": ("fsdp", "heads"),
    "wk": ("fsdp", "kv_heads"),
    "wv": ("fsdp", "kv_heads"),
    "wo": ("heads", "fsdp"),
    "w_gate": ("fsdp", "mlp"),
    "w_up": ("fsdp", "mlp"),
    "w_down": ("mlp", "fsdp"),
    "ln1": ("embed",),
    "ln2": ("embed",),
    "norm": ("embed",),
    "lm_head": ("embed", "vocab"),
    # MoE
    "gate_w": ("embed", None),
    "moe_w_gate": ("expert", "fsdp", "mlp"),
    "moe_w_up": ("expert", "fsdp", "mlp"),
    "moe_w_down": ("expert", "mlp", "fsdp"),
}


def _resolve_axis(logical, mesh_axes):
    if logical is None:
        return None
    for cand in LOGICAL_RULES.get(logical, ()):
        if cand in mesh_axes:
            return cand
    return None


def llama_param_specs(config: LlamaConfig, mesh_axes, stacked: bool = True):
    """name → PartitionSpec (with the [L] stack dim unsharded, or 'pp' for
    pipeline chunked trees)."""
    specs = {}
    per_layer = {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "ln1", "ln2",
                 "gate_w", "moe_w_gate", "moe_w_up", "moe_w_down"}
    for name, logical in PARAM_RULES.items():
        entries = [_resolve_axis(l, mesh_axes) for l in logical]
        if name in per_layer and stacked:
            entries = [None] + entries
        specs[name] = P(*entries)
    return specs


def _act_spec(mesh_axes, kind):
    """Activation constraint specs: kind ∈ {'btd','bsd_seq','logits'}."""
    b = _resolve_axis("batch", mesh_axes)
    s = _resolve_axis("seq", mesh_axes)
    h = _resolve_axis("heads", mesh_axes)
    if kind == "btd":
        return P(b, None, None)
    if kind == "btd_seq":  # Megatron-SP region
        return P(b, s, None)
    if kind == "bthd":
        return P(b, None, h, None)
    if kind == "logits":
        return P(b, None, _resolve_axis("vocab", mesh_axes))
    return P()


def llama_init_params(config: LlamaConfig, key=None, mesh=None):
    """Initialize the layer-stacked parameter tree (optionally pre-sharded)."""
    if key is None:
        key = jax.random.PRNGKey(0)
    c = config
    L, D, F, V = c.num_hidden_layers, c.hidden_size, c.intermediate_size, c.vocab_size
    H, KV, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    ks = jax.random.split(key, 16)
    std = 0.02

    def init(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(c.dtype)

    # each kind of layer is stacked on a leading axis of its own: the
    # attention matrices over the attention layers (FULL and SLIDING), the
    # linear mixer's (below) over the LINEAR ones, the two norms over all of
    # them, and with mlp_layer_types each kind of FFN over its own layers
    nA = c.num_attn_layers
    params = {
        "embed_tokens": init(ks[0], (V, D)),
        "wq": init(ks[1], (nA, D, H * hd)),
        "wk": init(ks[2], (nA, D, KV * hd)),
        "wv": init(ks[3], (nA, D, KV * hd)),
        "wo": init(ks[4], (nA, H * hd, D)),
        "ln1": jnp.ones((L, D), jnp.float32),
        "ln2": jnp.ones((L, D), jnp.float32),
        "norm": jnp.ones((D,), jnp.float32),
    }
    if c.qk_norm_per_head:
        params["q_norm"] = jnp.ones((nA, hd), jnp.float32)
        params["k_norm"] = jnp.ones((nA, hd), jnp.float32)
    elif c.qk_norm:
        params["q_norm"] = jnp.ones((nA, H * hd), jnp.float32)
        params["k_norm"] = jnp.ones((nA, KV * hd), jnp.float32)
    if c.is_recurrent:
        nL, Hv = c.num_linear_layers, c.linear_num_value_heads
        dv, kk = c.linear_value_head_dim, c.linear_conv_kernel_dim
        params.update({
            "lin_wqkv": init(ks[10], (nL, D, c.linear_conv_dim)),
            "lin_wa": init(ks[11], (nL, D, Hv)),
            "lin_wb": init(ks[12], (nL, D, Hv)),
            "lin_wg": init(ks[13], (nL, D, Hv * dv)),
            "lin_wo": init(ks[14], (nL, Hv * dv, D)),
            "lin_conv": init(ks[15], (nL, kk, c.linear_conv_dim)),
            "lin_A_log": jnp.zeros((nL, Hv), jnp.float32),
            "lin_dt_bias": jnp.zeros((nL, Hv), jnp.float32),
            "lin_norm": jnp.ones((nL, dv), jnp.float32),
        })
    nS, nD = c.num_sparse_layers, c.num_dense_layers
    Fm = c.moe_intermediate_size or F
    if nS:      # the dropless layer: the router over all experts, the held
        E, Eh = c.num_experts, c.held[1]
        kx = jax.random.split(ks[8], 6)
        params["gate_w"] = init(ks[5], (nS, D, E)).astype(jnp.float32)
        params["gate_bias"] = jnp.zeros((nS, E), jnp.float32)
        params["moe_w_gate"] = init(kx[0], (nS, Eh, D, Fm))
        params["moe_w_up"] = init(kx[1], (nS, Eh, D, Fm))
        params["moe_w_down"] = init(kx[2], (nS, Eh, Fm, D))
        if c.num_shared_experts:
            Fs = c.num_shared_experts * Fm
            params["shared_w_gate"] = init(kx[3], (nS, D, Fs))
            params["shared_w_up"] = init(kx[4], (nS, D, Fs))
            params["shared_w_down"] = init(kx[5], (nS, Fs, D))
    elif c.num_experts > 0:
        E = c.num_experts
        params["gate_w"] = init(ks[5], (L, D, E)).astype(jnp.float32)
        params["moe_w_gate"] = init(ks[6], (L, E, D, Fm))
        params["moe_w_up"] = init(ks[7], (L, E, D, Fm))
        params["moe_w_down"] = init(ks[8], (L, E, Fm, D))
    if nD:      # the keys a Llama's FFN always had; others beside experts
        kd = ks[5:8] if not nS else jax.random.split(ks[7], 3)
        params["w_gate"] = init(kd[0], (nD, D, F))
        params["w_up"] = init(kd[1], (nD, D, F))
        params["w_down"] = init(kd[2], (nD, F, D))
    if not c.tie_word_embeddings:
        params["lm_head"] = init(ks[9], (D, V))
    if mesh is not None:
        params = shard_llama_params(params, config, mesh)
    return params


def shard_llama_params(params, config, mesh):
    jm = mesh.jax_mesh if hasattr(mesh, "jax_mesh") else mesh
    axes = set(jm.axis_names)
    specs = llama_param_specs(config, axes)

    def place(name, v):
        spec = specs.get(name)
        if spec is None:
            return v
        # adapt spec for MoE 4-D stacked params ([L, E, ...])
        entries = list(spec)
        if name.startswith("moe_") and len(entries) == v.ndim - 1:
            entries = [None] + entries
        entries = entries[:v.ndim] + [None] * max(0, v.ndim - len(entries))
        # drop shardings that don't divide or reuse an axis already used
        clean, used = [], set()
        for d, e in enumerate(entries):
            if e is not None and (e in used or v.shape[d] % jm.shape[e] != 0):
                e = None
            if e is not None:
                used.add(e)
            clean.append(e)
        return jax.device_put(v, NamedSharding(jm, P(*clean)))

    return {k: place(k, v) for k, v in params.items()}


def _rope(q, k, positions, theta, head_dim):
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B?,T,hd/2]
    sin, cos = jnp.sin(angles), jnp.cos(angles)

    def rot(x):
        # x: [B, T, H, hd]; sin/cos: [B, T, hd/2] -> [B, T, 1, hd/2]
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        si = sin[:, :, None, :]
        co = cos[:, :, None, :]
        return jnp.concatenate([x1 * co - x2 * si, x2 * co + x1 * si], axis=-1)

    return rot(q).astype(q.dtype), rot(k).astype(k.dtype)


def _rmsnorm(x, w, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def _expand_gqa(k, v, config):
    """Repeat kv heads up to the query head count (GQA → MHA layout)."""
    H, KV = config.num_attention_heads, config.num_key_value_heads
    if KV != H:
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


def _attention(q, k, v, config, use_flash=True, mesh=None, window=None):
    """q:[B,T,H,hd] k,v:[B,T,KV,hd] causal. `mesh` (a jax Mesh): the
    program is GSPMD-partitioned over it, so the flash kernel runs per
    shard of (batch, heads) — see flash_attention_raw. `window` (a SLIDING
    layer's): query i sees keys i - window < j <= i; forward only."""
    k, v = _expand_gqa(k, v, config)
    if use_flash:
        # Pallas kernel on TPU, XLA reference otherwise — the predicate
        # lives in flash_attention_raw, not here
        from ..ops.flash_attention import flash_attention_raw
        spec = None
        if mesh is not None:
            # drop an axis the batch or head count does not divide: that
            # dim is then replicated into the kernel instead of failing
            spec = P(*(a if a is None or n % mesh.shape[a] == 0 else None
                       for a, n in zip(_act_spec(set(mesh.axis_names),
                                                 "bthd"), q.shape)))
        return flash_attention_raw(q, k, v, causal=True, mesh=mesh, spec=spec,
                                   window=window)
    scale = 1.0 / math.sqrt(config.head_dim)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    T, S_ = logits.shape[-2], logits.shape[-1]
    mask = jnp.tril(jnp.ones((T, S_), bool), k=S_ - T)
    if window is not None:
        mask &= ~jnp.tril(jnp.ones((T, S_), bool), k=S_ - T - window)
    logits = jnp.where(mask, logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _moe_block(x, gate_w, w_gate, w_up, w_down, config):
    """x:[B,T,D]; expert weights [E,...]. GShard top-k dense dispatch.

    The TRAINING block only (``llama_trunk``): softmax scores, one-hot
    dispatch into ``capacity = 1.25 n k / E`` places an expert, and a token
    past an expert's capacity is DROPPED (it passes through residually), so
    its output differs from the layer's equations wherever routing is
    uneven. Serving runs ``ops/moe_dropless.py`` instead (a SPARSE layer of
    ``LlamaConfig.mlp_layer_types``: no capacity, no dropped token, the
    experts this device holds); training through that layer is ROADMAP
    Queue 2(a) M1."""
    B, T, D = x.shape
    E, k = config.num_experts, config.num_experts_per_tok
    tokens = x.reshape(-1, D)
    n = tokens.shape[0]
    capacity = max(int(1.25 * n * k / E), 4)
    logits = (tokens.astype(jnp.float32) @ gate_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(jnp.sum(gate_vals, -1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)
    flat = onehot.transpose(1, 0, 2).reshape(-1, E)
    pos = (jnp.cumsum(flat, axis=0) - flat)
    pos = jnp.sum(pos * flat, -1).reshape(k, -1).T.astype(jnp.int32)
    keep = pos < capacity
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, capacity), capacity, dtype=jnp.float32)
    disp = jnp.einsum("tke,tkc->tec", onehot * keep[..., None], pos_oh)
    comb = jnp.einsum("tk,tke,tkc->tec", gate_vals * keep, onehot, pos_oh)
    xin = jnp.einsum("tec,td->ecd", disp, tokens.astype(jnp.float32)).astype(x.dtype)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xin, w_gate)) * \
        jnp.einsum("ecd,edf->ecf", xin, w_up)
    out_e = jnp.einsum("ecf,efd->ecd", h, w_down)
    out = jnp.einsum("tec,ecd->td", comb.astype(x.dtype), out_e)
    aux = jnp.sum(jnp.mean(probs, 0) * jnp.mean(onehot[:, 0, :], 0)) * E
    return out.reshape(B, T, D), aux


def _decoder_layer(x, lp, config, mesh, positions):
    """One decoder block; lp: this layer's params (no stack dim).
    `mesh` (a jax Mesh or None) drives activation sharding constraints."""
    c = config
    mesh_axes = set(mesh.axis_names) if mesh is not None else set()

    def cst(v, kind):
        if mesh is not None and isinstance(v, jax.core.Tracer):
            try:
                return jax.lax.with_sharding_constraint(
                    v, NamedSharding(mesh, _act_spec(mesh_axes, kind)))
            except Exception:
                return v
        return v

    x = cst(x, "btd_seq")  # Megatron-SP: residual stream sharded on seq
    with jax.named_scope("attn"):
        x = _attn_block(x, lp, c, mesh, mesh_axes, positions, cst)
    with jax.named_scope("mlp"):
        return _mlp_block(x, lp, c)


def _attn_block(x, lp, c, mesh, mesh_axes, positions, cst):
    h = _rmsnorm(x, lp["ln1"], c.rms_norm_eps)
    B, T, D = h.shape
    q = (h @ lp["wq"]).reshape(B, T, c.num_attention_heads, c.head_dim)
    k = (h @ lp["wk"]).reshape(B, T, c.num_key_value_heads, c.head_dim)
    v = (h @ lp["wv"]).reshape(B, T, c.num_key_value_heads, c.head_dim)
    q, k = _rope(q, k, positions, c.rope_theta, c.head_dim)
    if mesh is not None and "sep" in mesh_axes and mesh.shape["sep"] > 1:
        # context parallelism: seq stays sharded on `sep` straight through
        # attention via the ring kernel (ppermute over the sep axis, online
        # softmax — ops/ring_attention.py). shard_map is manual ONLY over
        # sep; dp/tp remain GSPMD-automatic, so this composes with the
        # batch/heads shardings unchanged.
        from ..ops.ring_attention import ring_attention_sharded
        k, v = _expand_gqa(k, v, c)
        att = ring_attention_sharded(q, k, v, mesh, "sep", causal=True)
    else:
        q = cst(q, "bthd")  # heads on tp (attention region: seq gathered)
        att = _attention(q, k, v, c, mesh=mesh)
    # named residual hook for save_only_these_names remat experiments; the
    # default policy (dots_saveable, see remat_policy) does NOT save it —
    # saving measured slower on v5e than recomputing the flash kernel
    from jax.ad_checkpoint import checkpoint_name
    att = checkpoint_name(att, "flash_out")
    x = x + (att.reshape(B, T, -1) @ lp["wo"])
    return cst(x, "btd_seq")


def _mlp_block(x, lp, c):
    h2 = _rmsnorm(x, lp["ln2"], c.rms_norm_eps)
    if c.num_experts > 0:
        moe_out, aux = _moe_block(h2, lp["gate_w"], lp["moe_w_gate"], lp["moe_w_up"],
                                  lp["moe_w_down"], c)
        x = x + moe_out
        return x, aux

    ff = jax.nn.silu(h2 @ lp["w_gate"]) * (h2 @ lp["w_up"])
    x = x + (ff @ lp["w_down"])
    return x, jnp.zeros((), jnp.float32)


def remat_policy(no_save_rhs_dim: int | None = None):
    """Selective rematerialisation policy for the decoder scan: save matmul
    outputs, recompute the cheap elementwise rest. Measured on v5e (850M,
    seq 2048, bf16): 491ms/step vs 533ms full remat (~8%); also saving the
    named 'flash_out' residual measured *slower* (527ms — the extra VMEM/HBM
    pressure outweighs skipping the flash recompute), so it is not saved.

    no_save_rhs_dim: additionally EXCLUDE dots whose rhs operand's last dim
    equals this value — passing intermediate_size drops the gate/up FFN
    projections (the two largest saved residuals, ~370 MB/layer at B=8
    T=2048) while keeping every other dot. The policy predicate receives
    the eqn's input avals, so the filter is shape-exact."""
    if no_save_rhs_dim is None:
        return jax.checkpoint_policies.dots_saveable

    def policy(prim, *avals, **params):
        if prim.name in ("dot_general", "conv_general_dilated"):
            if (len(avals) >= 2 and getattr(avals[-1], "shape", None)
                    and avals[-1].shape[-1] == no_save_rhs_dim):
                return False
            return True
        return False

    return policy


def llama_trunk(x, stacked_layer_params, config, mesh=None, positions=None,
                remat=True):
    """Scan the decoder stack over layer-stacked params.

    remat: False | True (selective dots policy) | "full" (save nothing —
    the lowest-memory schedule) | "dots_noffn" (dots policy with the MLP
    nested-rematerialised: fits batch 8 on one 16 GB v5e)."""
    config.require_uniform("llama_trunk (training, llama_forward)")
    if positions is None:
        positions = jnp.arange(x.shape[1])[None, :].astype(jnp.int32)
        positions = jnp.broadcast_to(positions, (x.shape[0], x.shape[1]))

    def body(carry, lp):
        y, aux = _decoder_layer(carry, lp, config, mesh, positions)
        return y, aux

    if not remat:
        fn = body
    elif remat == "full":
        fn = jax.checkpoint(body)
    elif remat == "dots_noffn":
        fn = jax.checkpoint(
            body, policy=remat_policy(config.intermediate_size))
    else:
        fn = jax.checkpoint(body, policy=remat_policy())
    x, auxes = jax.lax.scan(fn, x, stacked_layer_params)
    return x, jnp.sum(auxes)


_ATTN_KEYS = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
_LINEAR_KEYS = ("lin_wqkv", "lin_wa", "lin_wb", "lin_wg", "lin_wo", "lin_conv",
                "lin_A_log", "lin_dt_bias", "lin_norm")
_DENSE_KEYS = ("w_gate", "w_up", "w_down")
_SPARSE_KEYS = ("gate_w", "gate_bias", "moe_w_gate", "moe_w_up", "moe_w_down",
                "shared_w_gate", "shared_w_up", "shared_w_down")
_LAYER_KEYS = _ATTN_KEYS + _LINEAR_KEYS + _DENSE_KEYS + _SPARSE_KEYS + (
    "ln1", "ln2")


def split_layer_params(params):
    layer = {k: v for k, v in params.items() if k in _LAYER_KEYS}
    other = {k: v for k, v in params.items() if k not in _LAYER_KEYS}
    return layer, other


def layer_params_at(layer_p, config: LlamaConfig, layer: int) -> dict:
    """One layer's parameters out of the stacked tree. Without a pattern
    every leaf is stacked over all layers; with one, a mixer's leaves are
    stacked over the layers that have such a mixer (``attn_index``: FULL
    and SLIDING together; ``kind_index``: LINEAR), an FFN's over the layers
    of its FFN kind (``ffn_index``), and only the norms over all."""
    c = config
    if c.layer_types is None and c.mlp_layer_types is None:
        return {k: v[layer] for k, v in layer_p.items()}
    kind, at = c.kind_index(layer)
    linear = kind == c.LINEAR
    ffn, fi = c.ffn_index(layer)
    place = {}
    place.update(dict.fromkeys(_ATTN_KEYS,
                               None if linear else c.attn_index(layer)))
    place.update(dict.fromkeys(_LINEAR_KEYS, at if linear else None))
    place.update(dict.fromkeys(_DENSE_KEYS, fi if ffn == c.DENSE else None))
    place.update(dict.fromkeys(_SPARSE_KEYS, fi if ffn == c.SPARSE else None))
    return {k: v[place.get(k, layer)] for k, v in layer_p.items()
            if place.get(k, layer) is not None}


def block_in(x, gain, config: LlamaConfig):
    """What a mixer or an FFN is given of the stream ``x``."""
    return _rmsnorm(x, gain, config.rms_norm_eps) \
        if config.norm_placement == "pre" else x


def block_out(y, gain, config: LlamaConfig):
    """What the stream is given of a mixer's or an FFN's output ``y``."""
    return y if config.norm_placement == "pre" \
        else _rmsnorm(y, gain, config.rms_norm_eps)


def attn_qkv(h, lp, config: LlamaConfig, positions, kind: str | None = None):
    """q [B, T, H, hd], k and v [B, T, KV, hd] of an attention layer of
    ``kind`` (FULL, or SLIDING) from its input h [B, T, D]: the
    projections, the QK-norm where the spec has one (over the whole
    projection before the heads are split, or per head over head_dim), the
    rotation where the spec rotates this kind of layer."""
    c = config
    B, T, _ = h.shape
    q, k, v = h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]
    if c.qk_norm and not c.qk_norm_per_head:
        q = _rmsnorm(q, lp["q_norm"], c.rms_norm_eps)
        k = _rmsnorm(k, lp["k_norm"], c.rms_norm_eps)
    q = q.reshape(B, T, c.num_attention_heads, c.head_dim)
    k = k.reshape(B, T, c.num_key_value_heads, c.head_dim)
    v = v.reshape(B, T, c.num_key_value_heads, c.head_dim)
    if c.qk_norm_per_head:
        q = _rmsnorm(q, lp["q_norm"], c.rms_norm_eps)
        k = _rmsnorm(k, lp["k_norm"], c.rms_norm_eps)
    if c.rotates(kind or c.FULL):
        q, k = _rope(q, k, positions, c.rope_theta, c.head_dim)
    return q, k, v


def heads_at_once_leaves(config: LlamaConfig) -> tuple:
    """The layer leaves whose product's output is split into heads AT ONCE
    (``attn_qkv``: ``(h @ wq).reshape(.., H, hd)``; ``linear_mixer._out``:
    ``(h @ lin_wg).reshape(.., Hv, dv)``): the ones a decode step that is
    handed layer STACKS slices out whole, a matrix a layer, from a
    transposed copy of the stack (ISSUE 35). A QK-norm over the whole
    projection stands between product and split, and such a q / k is read in
    place like every other matrix. ``llama_paged.per_layer_weights`` hands
    the burst these a layer at a time; which LAYOUT each then takes is the
    compiler's say, and ``tests/test_tpu_compile.py`` holds this rule to it."""
    c = config
    whole_norm = c.qk_norm and not c.qk_norm_per_head
    return (("wv",) if whole_norm else ("wq", "wk", "wv")) \
        + (("lin_wg",) if c.is_recurrent else ())


def resolve_head(other):
    """The lm head matrix [D, V] (tied → transposed embedding)."""
    head = other.get("lm_head")
    if head is None:
        head = other["embed_tokens"].T
    return head


def lm_head_logits(x, other, config: LlamaConfig):
    """Final rmsnorm + lm-head projection — THE single epilogue shared by
    training forward, chunked loss, prefill and incremental decode (any
    head-handling change lands in exactly one place).

    bf16 operands + f32 accumulation: runs at bf16 MXU rate (an f32 lm-head
    GEMM is 2-4x slower on TPU) while keeping f32 logits for the softmax."""
    x = _rmsnorm(x, other["norm"], config.rms_norm_eps)
    head = resolve_head(other)
    return jax.lax.dot_general(
        x, head.astype(x.dtype), (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def llama_forward(params, tokens, config: LlamaConfig, mesh=None, remat=True):
    """tokens [B, T] int32 → logits [B, T, V] (compute dtype per config)."""
    layer_p, other = split_layer_params(params)
    with jax.named_scope("embed"):
        x = jnp.take(other["embed_tokens"], tokens,
                     axis=0).astype(config.dtype)
    x, aux = llama_trunk(x, layer_p, config, mesh, remat=remat)
    with jax.named_scope("head_loss"):
        return lm_head_logits(x, other, config), aux


def _chunked_ce(x, head, labels, chunk):
    """Sequence-chunked cross-entropy: materialises logits only one
    [B, chunk, V] block at a time (the block is rematerialised in the
    backward), so the full [B, T, V] f32 logits tensor never hits HBM —
    at B=8 T=2048 V=32000 that tensor alone is 2.1 GB, the difference
    between fitting and OOM on a 16 GB v5e. Returns (sum_nll, n_tokens)."""
    B, T, D = x.shape
    assert T % chunk == 0
    xs = x.reshape(B, T // chunk, chunk, D).swapaxes(0, 1)
    ls = labels.reshape(B, T // chunk, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def one(xc, lc):
        logits = jax.lax.dot_general(
            xc, head.astype(xc.dtype), (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, lc[..., None].astype(jnp.int32),
                                 axis=-1)[..., 0]
        mask = (lc >= 0).astype(jnp.float32)
        return -jnp.sum(ll * mask), jnp.sum(mask)

    def body(carry, xl):
        nll, n = one(*xl)
        return (carry[0] + nll, carry[1] + n), None

    (nll, n), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)), (xs, ls))
    return nll, n


def llama_loss(params, tokens, labels, config: LlamaConfig, mesh=None, remat=True,
               aux_weight=0.01, loss_chunk: int | None = None):
    """loss_chunk: sequence-chunk size for the cross-entropy (None = dense
    [B,T,V] logits). Chunking trades a second lm-head matmul in the backward
    for ~2 GB of logits HBM — measured neutral at B=4 but it is what lets
    B=8 fit under the dots_saveable remat policy (an earlier builder's note,
    not re-measured on the current chip)."""
    if loss_chunk:
        layer_p, other = split_layer_params(params)
        with jax.named_scope("embed"):
            x = jnp.take(other["embed_tokens"], tokens,
                         axis=0).astype(config.dtype)
        jm = mesh.jax_mesh if hasattr(mesh, "jax_mesh") else mesh
        x, aux = llama_trunk(x, layer_p, config, jm, remat=remat)
        with jax.named_scope("head_loss"):
            x = _rmsnorm(x, other["norm"], config.rms_norm_eps)
            nll, n = _chunked_ce(x, resolve_head(other), labels, loss_chunk)
            loss = nll / jnp.maximum(n, 1.0)
    else:
        logits, aux = llama_forward(params, tokens, config, mesh, remat)
        with jax.named_scope("head_loss"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            ll = jnp.take_along_axis(
                logp, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
            mask = (labels >= 0).astype(jnp.float32)
            loss = -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    if config.num_experts > 0:
        loss = loss + aux_weight * aux
    return loss


class LlamaForCausalLM(Layer):
    """Paddle-style eager wrapper over the functional core."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        params = llama_init_params(config)
        for k, v in params.items():
            self.add_parameter(k, Parameter(v, name=k))

    def _param_tree(self):
        return {k: p._value for k, p in self._parameters.items()}

    def forward(self, input_ids, labels=None):
        cfg = self.config

        def f(*vals):
            names = list(self._parameters.keys())
            tree = dict(zip(names, vals[:-1])) if labels is None else \
                dict(zip(names, vals[:-2]))
            if labels is None:
                logits, _ = llama_forward(tree, vals[-1], cfg, remat=False)
                return logits
            return llama_loss(tree, vals[-2], vals[-1], cfg, remat=False)

        plist = list(self._parameters.values())
        if labels is None:
            return apply(f, *plist, input_ids, name="llama")
        return apply(f, *plist, input_ids, labels, name="llama")

    @_spans.traced("llama.generate", cat="serve")
    def generate(self, input_ids, max_new_tokens=32, temperature=0.0, top_k=0):
        """KV-cache incremental decode: one compiled prefill + a scanned
        single-token step (O(T) per token; see models/llama_decode.py).
        Replaces the r2 full-prefix recompute (O(T²))."""
        from ..core import random as _rng
        from .llama_decode import llama_generate
        toks = input_ids._value if isinstance(input_ids, Tensor) else jnp.asarray(input_ids)
        toks = toks.astype(jnp.int32)
        key = _rng.split_key() if temperature > 0 else None
        new = llama_generate(self._param_tree(), toks, self.config,
                             int(max_new_tokens), float(temperature),
                             int(top_k), key=key)
        return Tensor(jnp.concatenate([toks, new.astype(toks.dtype)], axis=1))
