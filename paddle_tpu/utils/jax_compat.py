"""One spelling of `jax.shard_map` for every call site.

Written for the one installation there is (jax 0.9.0): `jax.shard_map`
with `check_vma`, `jax.lax.axis_size`. Call sites pass `check` and
`axis_names` here and never spell the keywords themselves.
"""
from __future__ import annotations

import jax

__all__ = ["shard_map", "axis_size"]

axis_size = jax.lax.axis_size


def shard_map(fn, mesh, in_specs, out_specs, check=False, axis_names=None):
    """check: `check_vma`. axis_names: the axes `fn` is manual over
    (None = all of them; the rest stay GSPMD-automatic)."""
    kw = {} if axis_names is None else {"axis_names": frozenset(axis_names)}
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check, **kw)
