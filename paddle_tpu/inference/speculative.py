"""Speculative decoding on the paged serving engine (ISSUE 14).

Decode throughput is bounded by one target-model launch per token per
slot. The trade this module makes is: a small DRAFT model proposes up to
``k`` greedy tokens per live slot (k cheap launches of a model a fraction
of the target's size), then the target verifies all of them in ONE launch
(``models.llama_paged.llama_paged_verify``: each slot's row carries
[current_tok, d_1..d_k] as a q_len = k+1 segment at prefill_start = pos
and returns per-position greedy targets). Accept-prefix semantics keep
temperature-0 token identity with plain decode unconditionally:

  * accept the longest prefix where draft and target argmax agree — those
    tokens ARE what plain decode would have emitted (each target argmax
    is conditioned only on already-agreed context);
  * the first disagreement emits the TARGET's token (the correction) and
    discards the rejected tail;
  * a full agreement additionally emits the target's bonus token (the
    verify row's last position is a free plain-decode step).

So the draft's quality moves THROUGHPUT (accepted tokens per launch),
never OUTPUT — a garbage draft degrades to ~1 token per verify launch,
a perfect draft reaches k+1. Rejected tokens cost nothing durable: their
target-pool writes are stale rows behind the validity masks and their
trailing pages are freed (pages a prefix cache shares were copy-on-write
privatized by the growth sweep BEFORE any speculative write — a rewound
shared page is never truncated in place; PR-13 refcount machinery).

The DRAFT here is the target truncated to its leading
``PADDLE_SPEC_DRAFT_LAYERS`` layers (embeddings/norm/head kept) — the
classic cheap draft that needs no second checkpoint — with its own DENSE
slot cache (``llama_decode.init_kv_cache``: one extra row as an overflow
scratch). Dense because rewind must be free: the cache is valid through a
per-slot ``_valid`` watermark and stale rows beyond it are masked, so a
rejected tail costs a host-side integer. The draft re-syncs lazily — a
slot the plain path advanced (spec was skipped for a step, a preemption
re-admitted) catches up by FORCING known sequence tokens through the same
propose launch, proposing fewer tokens that round. ``int8`` weight-only
draft weights (``PADDLE_SPEC_DRAFT_PRECISION``) make the draft nearly
free in HBM.

Gating (``spec_from_env``): ``PADDLE_SPEC_DECODE`` must be on AND the
engine must be paged (dense has no rewindable page unit) AND greedy
(temperature 0 — accept-prefix over argmax is only exact there). Anything
else degrades SILENTLY to plain decode — one flight-recorder note, never
an error: the flag is an optimization, not a mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import metrics, recorder as _recorder, slo as _slo, \
    spans as _spans
from ..utils import env_flags

__all__ = ["SpeculativeDecoder", "accept_prefix", "draft_from_target",
           "draft_spec_burst", "spec_from_env"]

# declared (defaults + docs) in utils/env_flags.py
ENV_SPEC_DECODE = "PADDLE_SPEC_DECODE"
ENV_SPEC_K = "PADDLE_SPEC_K"
ENV_SPEC_DRAFT_LAYERS = "PADDLE_SPEC_DRAFT_LAYERS"
ENV_SPEC_DRAFT_PRECISION = "PADDLE_SPEC_DRAFT_PRECISION"


def _seq_slice(parts, a: int, b: int) -> list:
    """``seq[a:b]`` of a slot's full token sequence, where ``parts`` is
    the (prompt, emitted) PAIR — without materializing their
    concatenation (the spec hot path reads at most k+2 tokens per warm
    slot per launch; building prompt+out each time would be quadratic
    host work over a long generation)."""
    prompt, out = parts
    n = len(prompt)
    if b <= n:
        return prompt[a:b]
    if a >= n:
        return out[a - n:b - n]
    return prompt[a:] + out[:b - n]


def accept_prefix(proposals, targets, *, pos: int, limit: int,
                  eos_id: int):
    """The pure accept-prefix walk → (emitted tokens, accepted count,
    done).

    ``targets`` has ``len(proposals) + 1`` entries: targets[j] is the
    target model's greedy token after consuming [current, d_1..d_j] —
    i.e. the token at absolute position ``pos + j + 1``. The walk emits
    targets[j] as long as the previous positions agreed, stopping at the
    first disagreement (targets[j] IS the correction token), at the
    bonus position (j == len(proposals)), or wherever plain decode would
    freeze (eos, or position reaching ``limit`` — the same
    ``new_pos >= limit`` arithmetic as the decode scan). The emitted
    list is therefore exactly the next tokens a plain greedy serve
    would produce, 1 ≤ len ≤ k+1."""
    emitted: list[int] = []
    accepted = 0
    n_prop = len(proposals)
    for j, t in enumerate(targets):
        t = int(t)
        emitted.append(t)
        new_pos = pos + j + 1
        if t == eos_id or new_pos >= limit:
            return emitted, accepted, True
        if j < n_prop and t == int(proposals[j]):
            accepted += 1
            continue
        break
    return emitted, accepted, False


def draft_from_target(params, config, n_layers: int):
    """(draft_params, draft_config): the target truncated to its first
    ``n_layers`` decoder layers — per-layer stacked leaves sliced
    ``[:n]``, embeddings/final-norm/lm-head kept whole. ``n_layers`` ==
    the target's depth returns the tree UNSLICED (self-draft: proposes
    exactly the target's greedy continuation — the deterministic
    100%-accept fixture tests and benches use)."""
    import dataclasses

    from ..models.llama import split_layer_params

    L = int(config.num_hidden_layers)
    n = max(1, min(int(n_layers), L))
    dcfg = dataclasses.replace(config, num_hidden_layers=n)
    if n == L:
        return params, dcfg
    layer, other = split_layer_params(params)
    draft = dict(other)
    draft.update({name: v[:n] for name, v in layer.items()})
    return draft, dcfg


@functools.partial(jax.jit, static_argnames=("config", "n", "dequant"),
                   donate_argnums=(1,))
def draft_spec_burst(params, cache, pos, inputs, n_forced, config,
                     n: int, dequant=None):
    """n greedy draft steps over all slots — the ONE draft executable.

    pos [B]: the draft-cache position step 0 writes at (the slot's valid
    watermark). inputs [B, n] / n_forced [B]: step j feeds inputs[:, j]
    while j < n_forced (known sequence tokens — catch-up and the current
    token) and its OWN previous sample after (speculation). Each step is
    a plain ``llama_decode_step_slots`` on the dense draft cache; write
    positions clamp to the cache's last row (the overflow scratch row —
    slots at their budget keep proposing junk the host caps away without
    ever clobbering a valid row). Returns (cache, samples [n, B]):
    samples[j] is the greedy token after step j, so a slot with
    n_forced = f proposes samples[f-1 : n-1]."""
    from ..models.llama_decode import llama_decode_step_slots

    S1 = cache["k"][0].shape[1]

    def step(carry, xs):
        cache, cur = carry
        j, forced = xs
        tok = jnp.where(j < n_forced, forced, cur)
        wpos = jnp.minimum(pos.astype(jnp.int32) + j, jnp.int32(S1 - 1))
        p = dequant(params) if dequant is not None else params
        logits, cache = llama_decode_step_slots(p, cache, wpos, tok,
                                                config)
        samp = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (cache, samp), samp

    B = pos.shape[0]
    (cache, _), samples = jax.lax.scan(
        step, (cache, jnp.zeros(B, jnp.int32)),
        (jnp.arange(n, dtype=jnp.int32), inputs.astype(jnp.int32).T))
    return cache, samples


class SpeculativeDecoder:
    """The draft half of speculative serving, owned by ONE batcher (and
    therefore single-threaded like it). ``propose()`` returns up to k
    greedy draft tokens per verifying slot; after the target's verify
    the batcher calls ``commit(slot, accepted)`` (live slot: the valid
    watermark advances over current + accepted tokens) — retiring /
    preempting a slot goes through ``invalidate`` (the batcher's
    ``_retire_slot`` hook), after which the next use re-prefills."""

    def __init__(self, config, params, *, max_batch: int, max_len: int,
                 prompt_buckets, k: int, draft_layers: int | None = None,
                 precision: str | None = None):
        from ..models.llama_decode import init_kv_cache

        self.k = int(k)
        if self.k < 1:
            raise ValueError(f"spec k must be >= 1, got {self.k}")
        L = int(config.num_hidden_layers)
        n = int(draft_layers) if draft_layers else -(-L // 2)
        dparams, self._cfg = draft_from_target(params, config, n)
        self.draft_layers = self._cfg.num_hidden_layers
        self._dequant = None
        if precision in ("int8", "weight_only_int8"):
            from ..quantization import (weight_only_dequantize,
                                        weight_only_quantize)
            dparams = weight_only_quantize(dparams)
            self._dequant = weight_only_dequantize
        elif precision:
            raise ValueError(f"unknown draft precision {precision!r}")
        self._params = dparams
        self.B, self.S = int(max_batch), int(max_len)
        # + 1 row: the overflow scratch row draft_spec_burst clamps
        # over-budget speculative writes into (never a valid row)
        self._S1 = self.S + 1
        self._buckets = tuple(sorted(prompt_buckets))
        self._cache = init_kv_cache(self._cfg, self.B, self._S1)
        # valid[b]: positions [0, valid) of slot b's draft cache hold the
        # K/V of the slot's REAL sequence; pend[b]: where valid lands if
        # the in-flight proposals are accepted (set at propose)
        self._valid = np.zeros(self.B, np.int64)
        self._pend = np.zeros(self.B, np.int64)
        self._key = jax.random.PRNGKey(0)
        self.stats = {"draft_launches": 0, "draft_prefills": 0,
                      "draft_s": 0.0}

    def invalidate(self, slot: int) -> None:
        """Forget a slot's draft state (retire/preempt/re-admit) — the
        next propose re-prefills it from the sequence the host holds."""
        self._valid[slot] = 0
        self._pend[slot] = 0

    def commit(self, slot: int, accepted: int) -> None:
        """The verify accepted ``accepted`` draft tokens for a STILL-LIVE
        slot: its cache is now valid through the current token plus the
        accepted run (the correction/bonus token was never drafted — the
        next propose feeds it as a forced input)."""
        self._valid[slot] = self._pend[slot] + int(accepted)

    def propose(self, jobs) -> dict:
        """jobs: [(slot, pos, limit, (prompt, emitted))] for every
        verifying slot — the two lists ride unconcatenated and
        ``_seq_slice`` reads the few positions each launch needs
        (seq[pos] is the current token). Returns {slot: [proposed
        tokens]} — possibly empty for a slot whose draft is still
        catching up (its verify row degenerates to a plain decode step)
        or whose budget caps speculation."""
        t0 = _slo.now()
        with _spans.span("serve.spec_draft", cat="serve",
                         slots=len(jobs)):
            props = self._propose(jobs)
        dt = _slo.now() - t0
        self.stats["draft_s"] += dt
        metrics.histogram("serve.spec_draft_s").observe(dt)
        return props

    def _propose(self, jobs) -> dict:
        from ..models.llama_decode import llama_prefill_slot

        # 1. cold slots prefill their known prefix (bucketed, ≤ the
        #    widest bucket; any remainder closes via forced catch-up)
        for slot, pos, _limit, parts in jobs:
            if self._valid[slot] == 0 and pos > 0:
                n0 = min(int(pos), self._buckets[-1])
                tb = next(b for b in self._buckets if b >= n0)
                toks = np.zeros(tb, np.int32)
                toks[:n0] = _seq_slice(parts, 0, n0)
                self._key, sub = jax.random.split(self._key)
                _, self._cache = llama_prefill_slot(
                    self._params, self._cache, jnp.asarray(toks),
                    jnp.int32(slot), jnp.int32(n0), sub,
                    config=self._cfg, max_len=self._S1,
                    dequant=self._dequant)
                self._valid[slot] = n0
                self.stats["draft_prefills"] += 1

        # 2. ONE propose launch: k+1 greedy steps; per slot the first
        #    n_forced steps feed known tokens (catch-up gap + the current
        #    token), the rest speculate
        Td = self.k + 1
        base = np.zeros(self.B, np.int32)
        inputs = np.zeros((self.B, Td), np.int32)
        n_forced = np.zeros(self.B, np.int32)
        for slot, pos, _limit, parts in jobs:
            v = int(self._valid[slot])
            nf = min(pos - v + 1, Td)
            inputs[slot, :nf] = _seq_slice(parts, v, v + nf)
            n_forced[slot] = nf
            base[slot] = v
        self._cache, samples_d = draft_spec_burst(
            self._params, self._cache, jnp.asarray(base),
            jnp.asarray(inputs), jnp.asarray(n_forced),
            config=self._cfg, n=Td, dequant=self._dequant)
        samples = np.asarray(jax.device_get(samples_d))    # [Td, B]
        self.stats["draft_launches"] += 1

        props: dict[int, list[int]] = {}
        for slot, pos, limit, _parts in jobs:
            nf = int(n_forced[slot])
            # cap: plain decode from pos can emit at most limit - pos
            # tokens, and m proposals emit at most m + 1 — never draft
            # past what the budget could accept
            cap = max(0, min(self.k, int(limit) - int(pos) - 1, Td - nf))
            props[slot] = [int(samples[nf - 1 + i, slot])
                           for i in range(cap)]
            self._pend[slot] = int(base[slot]) + nf
        return props

    def summary(self) -> dict:
        return {"k": self.k, "draft_layers": self.draft_layers,
                **{n: (round(v, 6) if isinstance(v, float) else v)
                   for n, v in self.stats.items()}}


def spec_from_env(config, params, *, max_batch: int, max_len: int,
                  prompt_buckets, temperature: float, paged: bool,
                  spec_decode: bool | None = None, k: int | None = None,
                  draft_layers: int | None = None,
                  precision: str | None = None):
    """Build the SpeculativeDecoder the env/args describe, or None.

    Every unsupported combination degrades SILENTLY to plain decode with
    one flight-recorder note (never an exception out of engine
    construction): speculative decoding is an optimization — a fleet-wide
    PADDLE_SPEC_DECODE=1 must not break a dense baseline engine or a
    sampling (temperature > 0) deployment, where accept-prefix over
    argmax would not be exact."""
    on = (bool(spec_decode) if spec_decode is not None
          else env_flags.get_bool(ENV_SPEC_DECODE))
    if not on:
        return None

    def off(why: str):
        _recorder.record("serve.spec_disabled", reason=why)
        return None

    if not paged:
        return off("dense kv layout has no rewindable page unit")
    if temperature > 0.0:
        return off("temperature > 0: greedy accept-prefix is only exact "
                   "at temperature 0")
    kk = int(k) if k is not None else env_flags.get_int(ENV_SPEC_K)
    if kk < 1:
        return off(f"PADDLE_SPEC_K={kk} < 1")
    dl = (int(draft_layers) if draft_layers is not None
          else env_flags.get_int(ENV_SPEC_DRAFT_LAYERS))
    prec = (precision if precision is not None
            else (env_flags.get(ENV_SPEC_DRAFT_PRECISION) or None))
    try:
        return SpeculativeDecoder(config, params, max_batch=max_batch,
                                  max_len=max_len,
                                  prompt_buckets=prompt_buckets, k=kk,
                                  draft_layers=dl, precision=prec)
    except Exception as e:   # the draft is optional; serving is not
        return off(f"draft build failed: {type(e).__name__}: {e}")
