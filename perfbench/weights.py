"""Weights from --seed: made on the device in one jitted call, in the type
they are served or trained in. The benchmark hands the same values to the
program and to the plain reference, which takes nothing that the program
has made."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .arith import dims

STD = 0.02
GAINS = ("ln1", "ln2", "norm")


def seed_key(seed: int):
    """A key for any whole-number seed, also past 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def shapes(cfg: dict) -> dict:
    d = dims(cfg)
    L, D, F, V, H, KV, hd = (d[k] for k in ("L", "D", "F", "V", "H", "KV",
                                            "hd"))
    return {"embed_tokens": (V, D), "wq": (L, D, H * hd),
            "wk": (L, D, KV * hd), "wv": (L, D, KV * hd),
            "wo": (L, H * hd, D), "w_gate": (L, D, F), "w_up": (L, D, F),
            "w_down": (L, F, D), "lm_head": (D, V),
            "ln1": (L, D), "ln2": (L, D), "norm": (D,)}


@functools.partial(jax.jit, static_argnames=("shp", "dtype"))
def _make(key, *, shp, dtype):
    out = {}
    for i, (name, shape) in enumerate(shp):
        k = jax.random.fold_in(key, i)
        if name in GAINS:               # gains near 1, kept in float32
            out[name] = 1.0 + 0.05 * jax.random.normal(k, shape, jnp.float32)
        else:
            out[name] = (jax.random.normal(k, shape, jnp.float32)
                         * STD).astype(dtype)
    return out


def make_weights(cfg: dict, seed: int) -> dict:
    """The parameter tree, under the names the program's tree uses."""
    return _make(seed_key(seed), shp=tuple(shapes(cfg).items()),
                 dtype=jnp.dtype(cfg.get("dtype", "bfloat16")))
