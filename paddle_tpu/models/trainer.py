"""Sharded training step builder for the model zoo.

This is the user-facing analog of the reference's auto-parallel engine
(`Engine._parallel_pir`, SURVEY.md §3.5): given a mesh and a config it emits
ONE jitted SPMD program containing forward, backward, optimizer update —
with parameter/optimizer buffers donated, bf16 compute, remat, and:
  * dp/fsdp: batch sharded, ZeRO via param/opt-state placements
  * tp/sp: Megatron shardings from llama PARAM_RULES + activation constraints
  * pp: the trunk runs through parallel.pipeline_apply (shard_map over 'pp')
  * ep: MoE expert dim sharded (XLA all-to-alls)
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..distributed.process_mesh import ProcessMesh
from ..observability import fleet as _fleet, metrics as _metrics, \
    spans as _spans, xplane as _xplane
from ..optimizer import AdamW, Optimizer
from . import llama as L

__all__ = ["LlamaTrainStep"]


class LlamaTrainStep:
    """step = LlamaTrainStep(config, mesh, optimizer); loss = step(tokens, labels)

    Spans: construction is one ``train.init``; every call is one
    ``train.step`` around the host's work for a step (input transfer and the
    async dispatch; the device step is not waited for). On the device the
    program's operations carry the scopes ``embed``, ``attn``, ``mlp``,
    ``head_loss`` (models/llama.py) and ``optimizer``; the backward's carry
    them under ``transpose(jvp(...))``, remat's recompute under
    ``rematted_computation``."""

    @_spans.traced("train.init", cat="setup")
    def __init__(self, config: L.LlamaConfig, mesh: ProcessMesh | None = None,
                 optimizer: Optimizer | None = None, num_microbatches: int = 1,
                 remat: bool = True, seed: int = 0, pp_schedule: str = "gpipe",
                 loss_chunk: int | None = None):
        self.config = config
        self.mesh = mesh
        self.optimizer = optimizer or AdamW(learning_rate=3e-4, weight_decay=0.1)
        self.num_microbatches = num_microbatches
        self.remat = remat
        sched = pp_schedule.lower()
        if sched not in ("gpipe", "fthenb", "1f1b"):
            raise ValueError(f"unknown pp_schedule {pp_schedule!r}")
        self.pp_schedule = "1f1b" if sched == "1f1b" else "gpipe"
        jm = mesh.jax_mesh if mesh is not None else None
        self._jm = jm

        params = L.llama_init_params(config, jax.random.PRNGKey(seed), mesh=mesh)
        self._params = params
        self._opt_state = self.optimizer.init_state(params)
        self._step_i = 0

        use_pp = jm is not None and "pp" in jm.axis_names and jm.shape["pp"] > 1
        self.use_pp = use_pp

        cfg, opt, mb, do_remat = config, self.optimizer, num_microbatches, remat

        if use_pp:
            S = jm.shape["pp"]
            assert config.num_hidden_layers % S == 0, "layers % pp != 0"
            assert mb >= 1
            Lps = config.num_hidden_layers // S

            def chunk_params(layer_p):
                # [L, ...] -> [S, L/S, ...], stage-major, sharded on pp
                return jax.tree.map(
                    lambda v: jax.lax.with_sharding_constraint(
                        v.reshape((S, Lps) + v.shape[1:]),
                        NamedSharding(jm, P("pp"))),
                    layer_p)

            def make_stage_fn(positions):
                def stage_fn(sp, act):
                    def body(carry, lpar):
                        y, aux = L._decoder_layer(carry, lpar, cfg, None, positions)
                        return y, aux

                    body_fn = jax.checkpoint(body) if do_remat else body
                    out, _ = jax.lax.scan(body_fn, act, sp)
                    return out
                return stage_fn

            def head_loss(norm_w, head, x, labels):
                # rmsnorm -> lm head -> masked-mean token cross-entropy;
                # loss_chunk applies here too (the pp head would otherwise
                # silently materialise the dense [B,T,V] logits)
                x = L._rmsnorm(x, norm_w, cfg.rms_norm_eps)
                if loss_chunk:
                    nll, n = L._chunked_ce(x, head, labels, loss_chunk)
                    return nll / jnp.maximum(n, 1.0)
                logits = x.astype(jnp.float32) @ head.astype(jnp.float32)
                logp = jax.nn.log_softmax(logits, axis=-1)
                ll = jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32),
                                         axis=-1)[..., 0]
                mask = (labels >= 0).astype(jnp.float32)
                return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)

            def positions_for(rows, Tlen):
                pos = jnp.arange(Tlen)[None, :].astype(jnp.int32)
                return jnp.broadcast_to(pos, (rows, Tlen))

        if not use_pp:
            def loss_fn(p, tokens, labels):
                return L.llama_loss(p, tokens, labels, cfg, mesh=jm,
                                    remat=do_remat, loss_chunk=loss_chunk)

            def value_and_grad_fn(p, tokens, labels):
                return jax.value_and_grad(loss_fn)(p, tokens, labels)
        elif self.pp_schedule == "gpipe":
            from ..parallel.pipeline_parallel import pipeline_apply

            def loss_fn(p, tokens, labels):
                layer_p, other = L.split_layer_params(p)
                chunked = chunk_params(layer_p)
                x = jnp.take(other["embed_tokens"], tokens, axis=0).astype(cfg.dtype)
                B = x.shape[0]
                assert B % mb == 0, "batch % microbatches != 0"
                mbs = x.reshape((mb, B // mb) + x.shape[1:])
                outs = pipeline_apply(make_stage_fn(positions_for(B // mb, x.shape[1])),
                                      chunked, mbs, mesh, "pp", remat=False)
                x = outs.reshape((B,) + outs.shape[2:])
                head = other.get("lm_head")
                if head is None:
                    head = other["embed_tokens"].T
                return head_loss(other["norm"], head, x, labels)

            def value_and_grad_fn(p, tokens, labels):
                return jax.value_and_grad(loss_fn)(p, tokens, labels)
        else:  # 1f1b
            # Explicit 1F1B: grads come from the schedule primitive, not
            # jax.grad — activation memory bounded by pipeline depth, not by
            # accumulate_steps. Loss is the mean of per-microbatch means
            # (identical to the global token mean when every microbatch
            # carries the same number of unmasked tokens).
            from ..parallel.pipeline_parallel import pipeline_train_1f1b

            def value_and_grad_fn(p, tokens, labels):
                layer_p, other = L.split_layer_params(p)
                chunked = chunk_params(layer_p)
                B, Tlen = tokens.shape
                assert B % mb == 0, "batch % microbatches != 0"

                tied = other.get("lm_head") is None
                head = other["embed_tokens"].T if tied else other["lm_head"]
                lp = {"norm": other["norm"], "head": head}

                def loss_fn_pp(lp_, y, lbl):
                    return head_loss(lp_["norm"], lp_["head"], y, lbl)

                def embed_fn(emb):
                    x = jnp.take(emb, tokens, axis=0).astype(cfg.dtype)
                    return x.reshape((mb, B // mb) + x.shape[1:])

                mbs, embed_pull = jax.vjp(embed_fn, other["embed_tokens"])
                lbls = labels.reshape((mb, B // mb, Tlen))

                loss, g_stack, g_lp, g_mbs = pipeline_train_1f1b(
                    make_stage_fn(positions_for(B // mb, Tlen)), loss_fn_pp,
                    chunked, lp, mbs, lbls, mesh, "pp")
                (d_emb,) = embed_pull(g_mbs)
                grads = jax.tree.map(
                    lambda v: v.reshape((S * Lps,) + v.shape[2:]), g_stack)
                grads["norm"] = g_lp["norm"]
                if tied:
                    grads["embed_tokens"] = d_emb + g_lp["head"].T
                else:
                    grads["embed_tokens"] = d_emb
                    grads["lm_head"] = g_lp["head"]
                return loss, grads

        def step_fn(p, opt_state, tokens, labels, lr, step_i):
            loss, grads = value_and_grad_fn(p, tokens, labels)
            with jax.named_scope("optimizer"):
                new_p, new_s = opt.apply_gradients(grads, p, opt_state,
                                                   lr=lr, step=step_i)
            return loss, new_p, new_s

        self._jitted = jax.jit(step_fn, donate_argnums=(0, 1))

    def data_sharding(self, ndim=2):
        if self._jm is None:
            return None
        axes = set(self._jm.axis_names)
        b = L._resolve_axis("batch", axes)
        return NamedSharding(self._jm, P(b, *([None] * (ndim - 1))))

    def __call__(self, tokens, labels):
        if hasattr(tokens, "_value"):
            tokens = tokens._value
        if hasattr(labels, "_value"):
            labels = labels._value
        self._step_i += 1
        # the host's work for one step: input transfer and dispatch. The
        # async device step is NOT synced here (bench/tests own their sync
        # points — per-step host syncs would serialize the chip)
        with _spans.span("train.step", cat="step", step=self._step_i):
            tokens = jnp.asarray(tokens, jnp.int32)
            labels = jnp.asarray(labels, jnp.int32)
            if self._jm is not None:
                sh = self.data_sharding(tokens.ndim)
                tokens = jax.device_put(tokens, sh)
                labels = jax.device_put(labels, sh)
            with _metrics.timer("train.step_time_s"):
                loss, self._params, self._opt_state = self._jitted(
                    self._params, self._opt_state, tokens, labels,
                    jnp.float32(self.optimizer.get_lr()),
                    jnp.int32(self._step_i))
        _metrics.counter("train.steps").inc()
        _metrics.counter("train.tokens").inc(int(tokens.size))
        _metrics.maybe_emit_step(self._step_i)
        _fleet.maybe_push(self._step_i)     # fleet heartbeat (env-gated)
        _xplane.maybe_step(self._step_i)    # device-trace window (env-gated)
        return loss

    @property
    def params(self):
        return self._params

    # ---- resilience protocol (distributed.resilience.ResilientLoop) ----
    def resilience_state(self):
        """Everything a bitwise-exact resume needs: params, optimizer
        moments, and the step counter (bias correction depends on it)."""
        return {"params": self._params, "opt_state": self._opt_state,
                "step": np.asarray(self._step_i, np.int64)}

    def load_resilience_state(self, state):
        self._params = state["params"]
        self._opt_state = state["opt_state"]
        self._step_i = int(np.asarray(state["step"]))

    def train_step(self, tokens, labels):
        return self(tokens, labels)
