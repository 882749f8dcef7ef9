"""Mixture-of-Experts with expert parallelism.

Reference: /root/reference/python/paddle/incubate/distributed/models/moe/
(MoELayer moe_layer.py:263, gates gate/*.py:31 — GShard/Switch/Naive,
global_scatter/global_gather all-to-all dispatch ops
distributed/utils/moe_utils.py:20,153).

TPU-native (GShard-style): routing is dense one-hot einsum dispatch/combine;
expert FFN weights are stacked [E, ...] and sharded on the 'ep' mesh axis, so
the dispatch einsum contracts a replicated token tensor against an
expert-sharded weight — XLA emits exactly the all-to-all pair the reference's
global_scatter/global_gather kernels implement, scheduled on ICI. Capacity
keeps shapes static (XLA requirement); dropped tokens pass through residually.

This is the TRAINING block only (with ``models/llama.py::_moe_block``): a
token past an expert's capacity (1.25 x the even share) is DROPPED, so the
output differs from the layer's equations wherever routing is uneven, and no
reference can agree with it. Serving runs the dropless layer,
``ops/moe_dropless.py`` (no capacity, sorted rows, grouped products, the
experts one device holds); training through that layer is ROADMAP Queue 2(a)
M1.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import dtypes as _dt
from ..core.engine import apply
from ..core.tensor import Tensor
from ..distributed.placement import Replicate, Shard
from ..distributed.process_mesh import get_mesh
from ..nn.initializer import XavierUniform
from ..nn.layer.layers import Layer

__all__ = ["NaiveGate", "SwitchGate", "GShardGate", "MoELayer"]


class _GateBase(Layer):
    def __init__(self, d_model, num_experts, topk=2):
        super().__init__()
        self.d_model = d_model
        self.num_experts = num_experts
        self.topk = topk
        self.weight = self.create_parameter([d_model, num_experts],
                                            default_initializer=XavierUniform())


class NaiveGate(_GateBase):
    """top-k softmax gate, no aux loss (reference gate/naive_gate.py)."""

    def gate_logits(self, x):
        return x @ self.weight._value


class SwitchGate(_GateBase):
    """top-1 gate with load-balancing loss (reference gate/switch_gate.py)."""

    def __init__(self, d_model, num_experts, topk=1):
        super().__init__(d_model, num_experts, 1)

    def gate_logits(self, x):
        return x @ self.weight._value


class GShardGate(_GateBase):
    """top-2 gate with aux loss (reference gate/gshard_gate.py)."""

    def __init__(self, d_model, num_experts, topk=2):
        super().__init__(d_model, num_experts, 2)

    def gate_logits(self, x):
        return x @ self.weight._value


class MoELayer(Layer):
    """moe(x): route tokens to expert FFNs with capacity.

    experts: list of Layers with identical structure (stacked internally), or
    a dict of stacked weight arrays. The canonical expert is a SwiGLU/ReLU MLP
    created via d_hidden.
    """

    def __init__(self, d_model, d_hidden=None, experts=None, gate=None, num_experts=None,
                 top_k=2, capacity_factor=1.25, ep_axis=None, activation="gelu",
                 recompute_interval=0, mp_group=None, moe_group=None):
        super().__init__()
        self.d_model = d_model
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        mesh = get_mesh()
        self.ep_axis = ep_axis or (moe_group.axis_name if moe_group is not None and
                                   hasattr(moe_group, "axis_name") else None)
        if self.ep_axis is None and mesh is not None:
            for cand in ("ep", "dp"):
                if cand in mesh.dim_names:
                    self.ep_axis = cand
                    break

        if isinstance(gate, Layer):
            self.gate = gate
            num_experts = gate.num_experts
        else:
            gate_cls = {"naive": NaiveGate, "switch": SwitchGate,
                        "gshard": GShardGate, None: GShardGate}.get(gate, GShardGate)
            assert num_experts is not None, "num_experts required"
            self.gate = gate_cls(d_model, num_experts, topk=top_k)
        self.num_experts = num_experts
        self.activation = activation

        d_hidden = d_hidden or 4 * d_model
        self.d_hidden = d_hidden
        init = XavierUniform()
        w1 = jnp.stack([init((d_model, d_hidden), _dt.float32) for _ in range(num_experts)])
        w2 = jnp.stack([init((d_hidden, d_model), _dt.float32) for _ in range(num_experts)])
        if mesh is not None and self.ep_axis:
            spec1 = P(self.ep_axis)
            w1 = jax.device_put(w1, NamedSharding(mesh.jax_mesh, spec1))
            w2 = jax.device_put(w2, NamedSharding(mesh.jax_mesh, spec1))
        from ..core.tensor import Parameter
        self.w1 = Parameter(w1, name="moe_w1")
        self.w2 = Parameter(w2, name="moe_w2")

    def forward(self, x):
        """x: [B, S, d] (or [T, d]). Returns same shape + sets self.aux_loss."""
        squeeze_back = None
        orig_shape = list(x.shape)
        topk = self.top_k
        E = self.num_experts
        cf = self.capacity_factor
        act = {"gelu": jax.nn.gelu, "relu": jax.nn.relu, "silu": jax.nn.silu}[self.activation]
        ep_axis = self.ep_axis
        mesh = get_mesh()

        def f(xv, gate_w, w1, w2):
            shp = xv.shape
            tokens = xv.reshape(-1, shp[-1])  # [T, d]
            T = tokens.shape[0]
            capacity = max(int(cf * T * topk / E), 4)
            logits = (tokens @ gate_w).astype(jnp.float32)  # [T, E]
            probs = jax.nn.softmax(logits, axis=-1)

            # top-k choice per token
            gate_vals, expert_idx = jax.lax.top_k(probs, topk)  # [T, k]
            gate_vals = gate_vals / jnp.maximum(
                jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

            # position of each token within its expert's capacity buffer
            onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # [T, k, E]
            # order: k-th choices after (k-1)-th (GShard's sequential capacity)
            flat = onehot.transpose(1, 0, 2).reshape(-1, E)  # [k*T, E]
            pos_in_expert = jnp.cumsum(flat, axis=0) - flat  # [k*T, E]
            pos = jnp.sum(pos_in_expert * flat, axis=-1).reshape(topk, -1).T
            pos = pos.astype(jnp.int32)  # [T, k]
            keep = pos < capacity
            gate_vals = gate_vals * keep.astype(gate_vals.dtype)

            # dispatch/combine tensors [T, E, C]
            pos_oh = jax.nn.one_hot(jnp.where(keep, pos, capacity), capacity,
                                    dtype=jnp.float32)  # [T, k, C]
            dispatch = jnp.einsum("tke,tkc->tec", onehot * keep[..., None].astype(jnp.float32),
                                  pos_oh)
            combine = jnp.einsum("tk,tke,tkc->tec", gate_vals.astype(jnp.float32),
                                 onehot, pos_oh)

            xin = jnp.einsum("tec,td->ecd", dispatch, tokens.astype(jnp.float32))
            if mesh is not None and ep_axis is not None and isinstance(xin, jax.core.Tracer):
                try:
                    xin = jax.lax.with_sharding_constraint(
                        xin, NamedSharding(mesh.jax_mesh, P(ep_axis)))
                except Exception:
                    pass
            h = act(jnp.einsum("ecd,edh->ech", xin, w1.astype(jnp.float32)))
            out_e = jnp.einsum("ech,ehd->ecd", h, w2.astype(jnp.float32))
            out = jnp.einsum("tec,ecd->td", combine, out_e)

            # aux load-balancing loss (GShard eq.4 / Switch eq.(4))
            me = jnp.mean(probs, axis=0)
            ce = jnp.mean(onehot[:, 0, :], axis=0)
            aux = jnp.sum(me * ce) * E
            return out.reshape(shp).astype(xv.dtype), aux.astype(jnp.float32)

        out, aux = apply(f, x, self.gate.weight, self.w1, self.w2, name="moe")
        self.aux_loss = aux
        return out
