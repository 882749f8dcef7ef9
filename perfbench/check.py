"""The comparison that decides `correct`.

Training: the first step's loss, the first gradient's norm as the optimizer
got it (from its first moment after one step) and the norm of the
parameters' change after the steps the reference follows, both by the worst
leaf, a leaf being one layer's slice of one parameter: the gap between the
program's norm and the reference's, against the reference's norm of that
leaf or of the median leaf, whichever is larger. Leaves whose reference
gradient is under a thousandth of the median leaf's move by round-off alone
and are left out of the change.

Serving: over a sample of the requests the window finished, drawn from the
seed with the longest in it, the widest gap by which a served token's
logit lies below the reference's best at its position.
"""
from __future__ import annotations

import gc
import statistics

import numpy as np

from . import families, gen, harness as hs
from .weights import make_weights

NEGLIGIBLE_GRAD = 1e-3


# ------------------------------------------------------- the program's side

def _norm_dict(tree, fam) -> dict:
    """{leaf: float}; a parameter the family stacks by layer gives one leaf
    a layer."""
    import jax
    import jax.numpy as jnp
    vals = jax.device_get(jax.jit(lambda t: {
        k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)),
                            axis=fam.layer_axes(k, v.ndim)))
        for k, v in t.items()})(tree))
    out = {}
    for k, v in vals.items():
        if np.ndim(v) == 0:
            out[k] = float(v)
        else:
            out.update({f"{k}.{i}": float(x) for i, x in enumerate(v)})
    return out


def first_grad_norms(opt_state: dict, beta1: float, fam) -> dict:
    """After one step Adam's first moment is (1 - beta1) * gradient."""
    m = {k: st["moment1"] for k, st in opt_state.items()}
    return {k: v / (1.0 - beta1) for k, v in _norm_dict(m, fam).items()}


def change_norms(params: dict, initial: dict, fam) -> dict:
    import jax
    import jax.numpy as jnp
    diff = jax.jit(lambda a, b: {
        k: a[k].astype(jnp.float32) - b[k].astype(jnp.float32) for k in a})
    # leaf by leaf, so that no float32 copy of the model stands whole
    out = {}
    for k in params:
        out.update(_norm_dict(diff({k: params[k]}, {k: initial[k]}), fam))
    return out


# ------------------------------------------------------------ the reference

def reference_trajectory(cfg: dict, job: dict, seed: int, batches,
                         dot: str) -> dict:
    """The first steps in float32 (or, for the control, with `dot` in the
    matmuls' place): the loss of each of `batches`, the first gradient's
    norms, and the change after len(batches) - 1 AdamW updates. The
    gradients of earlier steps wait on the host: the moments are their
    decayed sums, and the device holds parameters, one gradient and the
    activations of one row."""
    import jax
    import jax.numpy as jnp
    o = job["optimizer"]
    hp = (o["lr"], o["beta1"], o["beta2"], o["eps"], o["weight_decay"])
    fam = families.of(cfg)
    ref = fam.reference()
    rcfg = ref.hashable(cfg)
    p = {k: v.astype(jnp.float32) for k, v in make_weights(cfg, seed).items()}
    out = {"loss": []}
    past: list[dict] = []                       # on the host
    updates = max(1, len(batches) - 1)
    for t, (tokens, labels) in enumerate(batches, start=1):
        tokens, labels = jnp.asarray(tokens), jnp.asarray(labels)
        if t > updates:
            out["loss"].append(float(ref.loss_only(
                p, tokens, labels, cfg=rcfg, dot=dot)))
            continue
        loss, g = ref.loss_and_grads(p, tokens, labels, cfg=rcfg, dot=dot)
        out["loss"].append(float(loss))
        if t == 1:
            out["grad_norm"] = _norm_dict(g, fam)
        for k in list(p):
            gs = tuple(jnp.asarray(h[k]) for h in past) + (g[k],)
            p[k] = ref.adamw_leaf(p[k], gs, jnp.int32(t), hp=hp)
        if t < updates:
            past.append(jax.device_get(g))
        del g
    out["change_norm"] = change_norms(p, make_weights(cfg, seed), fam)
    del p, past
    gc.collect()
    return out


def _worst_leaf(got: dict, want: dict, keep=None):
    floor = statistics.median(want.values())
    worst, at = 0.0, None
    for k, w in want.items():
        if keep is not None and k not in keep:
            continue
        gap = abs(got[k] - w) / max(w, floor)
        if not gap <= worst:            # a NaN is the worst there is
            worst, at = gap, k
    return worst, at


def row_starts(corpus, tokens) -> list[list[int]]:
    """Where in the corpus each row of a batch stands (every place, should
    the same run of tokens occur twice): the loader draws the starts at
    random and does not say."""
    c = np.asarray(corpus)
    out = []
    for row in np.asarray(tokens):
        row = row.astype(c.dtype)
        at = np.flatnonzero(c[:len(c) - len(row) + 1] == row[0])
        for j in range(1, len(row)):
            if len(at) <= 1:
                break
            at = at[c[at + j] == row[j]]
        out.append([int(s) for s in at
                    if np.array_equal(c[s:s + len(row)], row)])
    return out


def fresh_steps(corpus, batches) -> list[bool]:
    """For each step, whether its batch is fresh: no row of it shares a
    token's place in the corpus with a row of an earlier step. A row that
    does is half memorized after one update at this learning rate, and its
    loss then swings with the rounding of that update: printed beside the
    later steps' losses, which read 30 times higher on such a batch
    (PERF.md, section 2)."""
    seen: list[int] = []
    fresh = []
    for tokens, _ in batches:
        span = np.asarray(tokens).shape[1] + 1      # the labels reach one on
        starts = [s for row in row_starts(corpus, tokens) for s in row]
        fresh.append(all(abs(s - e) >= span for s in starts for e in seen))
        seen.extend(starts)
    return fresh


def compare_training(checks: hs.Checks, got: dict, want: dict,
                     fresh: list[bool]) -> None:
    """The first step's loss, the first gradient's norm and the parameters'
    change, each against the reference. The later steps' losses are printed
    and not compared: no control and no fault reads three times what sound
    runs do there, so a limit could only fail sound runs (PERF.md,
    section 2)."""
    gaps = [abs(a - b) / abs(b) for a, b in zip(got["loss"], want["loss"])]
    checks.add("loss_gap_step1", gaps[0])
    hs.say({"loss_gaps_by_step": gaps, "fresh_batch_by_step": fresh,
            "reference_losses": want["loss"]})
    gap, at = _worst_leaf(got["grad_norm"], want["grad_norm"])
    checks.add("grad_norm_gap", gap)
    med = statistics.median(want["grad_norm"].values())
    moved = {k for k, v in want["grad_norm"].items()
             if v >= NEGLIGIBLE_GRAD * med}
    gap2, at2 = _worst_leaf(got["change_norm"], want["change_norm"], moved)
    checks.add("change_norm_gap", gap2)
    hs.say({"worst_leaf": {"grad_norm_gap": at, "change_norm_gap": at2},
            "leaves_left_out_of_change":
                sorted(set(want["grad_norm"]) - moved)})


# ----------------------------------------------------------------- serving

def _pad_to(n: int, step: int) -> int:
    return -(-n // step) * step


def sample_requests(finished: list[dict], seed: int, k: int) -> list[dict]:
    """k of the finished requests from the seed, the longest among them."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: len(r["prompt"]) + len(r["out"]))
    rest = [r for r in finished if r is not longest]
    idx = gen.rng_for(seed, 2).permutation(len(rest))[:max(0, k - 1)]
    return [longest] + [rest[i] for i in idx]


def served_gaps(weights: dict, cfg: dict, reqs: list[dict],
                control: str | None = None, pad_tokens: int = 256,
                pad_outputs: int = 128) -> dict:
    """The widest gap of the served tokens under the reference; with
    `control`, also that of the tokens the lower precision puts first at
    the same positions of the same prompts and tokens."""
    import jax.numpy as jnp
    ref = families.of(cfg).reference()
    rcfg = ref.hashable(cfg)
    worst = {"served": 0.0, "control": 0.0, "tokens": 0}
    for r in reqs:
        plen, n = len(r["prompt"]), len(r["out"])
        # few padded shapes: each is a program the reference compiles
        n_pad = _pad_to(n, pad_outputs)
        T = _pad_to(max(plen + n, plen - 1 + n_pad), pad_tokens)
        toks = np.zeros(T, np.int32)
        toks[:plen + n] = r["prompt"] + r["out"]
        picks = np.zeros(n_pad, np.int32)
        picks[:n] = r["out"]
        args = (weights, jnp.asarray(toks), jnp.int32(plen - 1))
        best, at, _ = ref.served_logits(*args, jnp.asarray(picks), cfg=rcfg,
                                        dot="f32", n=n_pad)
        worst["served"] = max(worst["served"],
                              float(jnp.max((best - at)[:n])))
        worst["tokens"] += n
        if control:
            _, _, first = ref.served_logits(*args, jnp.asarray(picks),
                                            cfg=rcfg, dot=control, n=n_pad)
            best, at, _ = ref.served_logits(*args, first, cfg=rcfg,
                                            dot="f32", n=n_pad)
            worst["control"] = max(worst["control"],
                                   float(jnp.max((best - at)[:n])))
    return worst
