"""The chained steady-state measurement protocol — single-sourced.

Every TPU bench in this repo times the SAME way: enqueue `chain` dependent
steps, force the whole chain ONCE via `device_get` of the final scalar,
divide by `chain`. A chain removes the per-step host sync a real training
loop never pays. On a local chip `block_until_ready` would do as well; the
benchmark PR (ROADMAP S1) re-checks and simplifies this.
"""
from __future__ import annotations

import time

import jax
import numpy as np

__all__ = ["timed_chain"]


def timed_chain(step_once, chain: int, samples: int):
    """step_once() -> a scalar-bearing output (loss). Returns the list of
    per-step seconds, one entry per chain sample. Callers report the
    MEDIAN as the headline (min/mean alongside)."""
    def sync(out):
        v = out._value if hasattr(out, "_value") else out
        float(jax.device_get(v))

    times = []
    for _ in range(samples):
        t0 = time.perf_counter()
        out = None
        for _k in range(chain):
            out = step_once()
        sync(out)
        times.append((time.perf_counter() - t0) / chain)
    return times


def summarize(times):
    """(median_s, min_s, mean_s) of a timed_chain result."""
    return (float(np.median(times)), float(min(times)),
            float(sum(times) / len(times)))
