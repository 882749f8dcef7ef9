"""The plain reference of the stub family (stub_family.py): one recurrent
layer, h_t = sigmoid(mix) * h_{t-1} + embed[token_t], logits_t = (h_t * gain)
@ head, in float32 jax.numpy. It has the five names check.py calls."""
import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def logits(params, tokens):
    """[T] tokens -> [T, V] logits of one sequence."""
    a = jax.nn.sigmoid(params["mix"][0])

    def cell(h, x):
        h = a * h + x
        return h, h
    _, hs = jax.lax.scan(cell, jnp.zeros_like(a), params["embed"][tokens])
    return jnp.dot(hs * params["gain"], params["head"], precision=HIGHEST)


def hashable(cfg: dict) -> tuple:
    return ()


@functools.partial(jax.jit, static_argnames=("cfg", "dot", "n"))
def served_logits(params, tokens, start, picks, *, cfg, dot, n):
    lg = jax.lax.dynamic_slice_in_dim(logits(params, tokens), start, n, 0)
    at = jnp.take_along_axis(lg, picks[:, None], axis=1)[:, 0]
    return lg.max(-1), at, jnp.argmax(lg, -1).astype(jnp.int32)


def batch_loss(params, tokens, labels):
    ll = jax.nn.log_softmax(jax.vmap(lambda t: logits(params, t))(tokens), -1)
    ll = jnp.take_along_axis(ll, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    return -jnp.sum(jnp.where(labels >= 0, ll, 0.0)) \
        / jnp.maximum(jnp.sum(labels >= 0), 1)


@functools.partial(jax.jit, static_argnames=("cfg", "dot"))
def loss_and_grads(params, tokens, labels, *, cfg, dot):
    return jax.value_and_grad(batch_loss)(params, tokens, labels)


@functools.partial(jax.jit, static_argnames=("cfg", "dot"))
def loss_only(params, tokens, labels, *, cfg, dot):
    return batch_loss(params, tokens, labels)


@functools.partial(jax.jit, static_argnames=("hp",))
def adamw_leaf(p, grads, t, *, hp):
    """AdamW at step t from the gradients of steps 1..t, newest last."""
    lr, b1, b2, eps, wd = hp
    m = v = 0.0
    for age, g in enumerate(reversed(grads)):
        m = m + (1 - b1) * b1 ** age * g
        v = v + (1 - b2) * b2 ** age * g * g
    tf = t.astype(jnp.float32)
    return p - lr * (m / (1 - b1 ** tf)) / (
        jnp.sqrt(v / (1 - b2 ** tf)) + eps) - lr * wd * p
