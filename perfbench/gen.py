"""Inputs from --seed: the training corpus and the request schedules.

One general generator reads a traffic file's parameters. Lengths: every seed
gets the same set, block by block, in another order; the set of a block is
the distribution's quantile grid and --seed only permutes it, so two seeds
offer the same work in another order. Arrivals (`process` poisson): gaps
drawn independently from the exponential distribution by --seed, so the
count in a window varies and arrivals cluster as a Poisson process does.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

_BLOCK = 64


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """A generator for any whole-number seed, however large."""
    return np.random.default_rng([int(seed), int(stream)])


# ------------------------------------------------------------------ corpus

def synthetic_corpus(n_tokens: int, vocab_size: int = 512, seed: int = 0,
                     branching: int = 8) -> np.ndarray:
    """Copy of paddle_tpu.io.token_loader.synthetic_corpus (a seeded
    Zipf-Markov stream: each token has `branching` likely successors with
    Zipfian weights), kept here so that no later PR changes the traffic.
    The seed is folded into 32 bits, which RandomState needs."""
    rng = np.random.RandomState(int(seed) % (2 ** 32 - 1))
    succ = rng.randint(0, vocab_size, (vocab_size, branching)).astype(np.int32)
    w = 1.0 / np.arange(1, branching + 1)
    cdf = np.cumsum(w / w.sum())
    draws = rng.rand(n_tokens)
    choice = np.searchsorted(cdf, draws).clip(0, branching - 1)
    out = np.empty(n_tokens, np.int32)
    state = 0
    for i in range(n_tokens):
        state = succ[state, choice[i]]
        out[i] = state
    return out


# ------------------------------------------------------------ distributions

def block_values(spec: dict, n: int = _BLOCK) -> np.ndarray:
    """The fixed set of n values of one block of a distribution."""
    dist = spec["dist"]
    u = (np.arange(n) + 0.5) / n
    if dist == "uniform":
        x = spec["lo"] + u * (spec["hi"] - spec["lo"])
    elif dist == "lognormal":
        nd = NormalDist()
        z = np.array([nd.inv_cdf(float(p)) for p in u])
        x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    if "lo" in spec or "hi" in spec:
        x = np.clip(x, spec.get("lo", -np.inf), spec.get("hi", np.inf))
    return x


def draw(spec: dict, n: int, rng: np.random.Generator,
         integer: bool = False) -> np.ndarray:
    """n values: whole blocks of the fixed set, each in an order of its
    own drawn from rng."""
    blocks = -(-n // _BLOCK)
    base = block_values(spec)
    out = np.concatenate([rng.permutation(base) for _ in range(blocks)])[:n]
    return np.rint(out).astype(np.int64) if integer else out


# ---------------------------------------------------------------- requests

def request_schedule(traffic: dict, seed: int, horizon_s: float | None,
                     count: int | None = None) -> list[dict]:
    """Requests [{"due_s", "prompt_len", "output_len"}] from a traffic file.

    Open loop (`arrivals.process` poisson): arrivals up to horizon_s at
    `rate_per_s`, the gaps independent and exponential. Closed loop
    (`closed`): `count` requests, all due at 0.
    """
    rng = rng_for(seed, 1)
    arr = traffic["arrivals"]
    if arr["process"] == "closed":
        n = int(count)
        due = np.zeros(n)
    elif arr["process"] == "poisson":
        mean_gap = 1.0 / float(arr["rate_per_s"])
        gaps = np.zeros(0)      # drawn in chunks until they pass the horizon
        while gaps.sum() < horizon_s:
            n = int(math.ceil(horizon_s / mean_gap)) + _BLOCK
            gaps = np.concatenate([gaps, rng.exponential(mean_gap, n)])
        due = np.cumsum(gaps)
        due = due[due < horizon_s]
        n = len(due)
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    plen = draw(traffic["prompt_len"], n, rng, integer=True)
    olen = draw(traffic["output_len"], n, rng, integer=True)
    return [{"due_s": float(d), "prompt_len": int(p), "output_len": int(o)}
            for d, p, o in zip(due, plen, olen)]


def prompt_ids(seed: int, index: int, length: int, vocab: int) -> list[int]:
    """The ids of request `index`: never 0, which is the pad id."""
    return rng_for(seed, 1000 + index).integers(1, vocab, length).tolist()
