"""Weights from --seed: made on the device in one jitted call, in the type
they are served or trained in. The benchmark hands the same values to the
program and to the plain reference, which takes nothing that the program
has made."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import families

STD = 0.02


def seed_key(seed: int):
    """A key for any whole-number seed, also past 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


@functools.partial(jax.jit, static_argnames=("shp", "gains", "dtype"))
def _make(key, *, shp, gains, dtype):
    out = {}
    for i, (name, shape) in enumerate(shp):
        k = jax.random.fold_in(key, i)
        if name in gains:               # gains near 1, kept in float32
            out[name] = 1.0 + 0.05 * jax.random.normal(k, shape, jnp.float32)
        else:
            out[name] = (jax.random.normal(k, shape, jnp.float32)
                         * STD).astype(dtype)
    return out


def make_weights(cfg: dict, seed: int) -> dict:
    """The parameter tree of the configuration's family, under the names
    the program's tree uses."""
    fam = families.of(cfg)
    return _make(seed_key(seed), shp=tuple(fam.shapes(cfg).items()),
                 gains=tuple(fam.GAINS),
                 dtype=jnp.dtype(cfg.get("dtype", "bfloat16")))
