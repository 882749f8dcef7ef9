"""Where a routed-expert cell's `served_logit_gap_max` comes from, position by
position: what `decided_selection_margin` and the cell's limit are set from.

    python3 -m perfbench.tools.gap_profile --workload <cell> --seed <n> \
        --out chiprun_out/<name>.npz [--control int8] [--router-bf16]

Runs the cell's set-up and window as `perfbench.run` does, frees the engine,
and walks the reference over the sample `correct` compares, with NO position
left out. For every served position of every sampled request it keeps
(arrays of one length in the .npz, `request` telling the requests apart):

  gap        the float32 reference's best logit less its logit of the
             served token (what `served_logit_gap_max` is the widest of)
  margin     [sparse layers, positions]: the layer's least `edge` over the
             held experts (perfbench/ref/exaone_moe.py::route)
  gap_int8   with `--control int8`: the same gap for the token the int8
             reference puts first there (the control of `correct`)

and prints, for a ladder of margins, the widest gap among the positions whose
every layer is decided by more than the margin, on both sides, beside the
share of positions that leaves. `--router-bf16` serves with the program's
router in bfloat16 (perfbench/tools/router_bf16.py). Needs a family whose
reference's `hidden` gives the margins: the K-EXAONE family today.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json

import numpy as np

from .. import check, families, harness as hs, run as prun
from ..runners import serve

LADDER = (0.0, 2.5e-4, 5e-4, 1e-3, 2e-3, 3e-3, 4e-3, 6e-3, 8e-3, 1.2e-2)


def profile(weights, cfg, reqs, pad_tokens, pad_outputs, control) -> dict:
    import jax.numpy as jnp
    ref = families.of(cfg).reference()
    rcfg = ref.hashable(dict(cfg, decided_selection_margin=0.0))
    eps = dict(rcfg)["eps"]
    out = {k: [] for k in ("gap", "margin", "request")}
    if control:
        out["gap_" + control] = []
    for i, r in enumerate(reqs):
        plen, n = len(r["prompt"]), len(r["out"])
        n_pad = check._pad_to(n, pad_outputs)       # as `served_gaps` pads
        T = check._pad_to(max(plen + n, plen - 1 + n_pad), pad_tokens)
        toks = np.zeros(T, np.int32)
        toks[:plen + n] = r["prompt"] + r["out"]
        picks = np.zeros(n_pad, np.int32)
        picks[:n] = r["out"]
        toks, start = jnp.asarray(toks), jnp.int32(plen - 1)
        x, margins = ref.hidden(weights, toks, rcfg, "f32")
        best, at, _ = ref._head(weights, x, start, jnp.asarray(picks),
                                eps=eps, dot="f32", n=n_pad)
        if control:
            xc, _ = ref.hidden(weights, toks, rcfg, control)
            _, _, first = ref._head(weights, xc, start, jnp.asarray(picks),
                                    eps=eps, dot=control, n=n_pad)
            _, atc, _ = ref._head(weights, x, start, first, eps=eps,
                                  dot="f32", n=n_pad)
            out["gap_" + control].append(np.asarray(best - atc)[:n])
            del xc
        rows = slice(plen - 1, plen - 1 + n)
        out["gap"].append(np.asarray(best - at)[:n])
        out["margin"].append(np.asarray(margins)[:, rows])
        out["request"].append(np.full(n, i, np.int32))
        del x
    return {k: np.concatenate(v, axis=-1) for k, v in out.items()}


def ladder(p: dict) -> list[dict]:
    least = p["margin"].min(axis=0)
    rows = []
    for tau in LADDER:
        keep = least >= tau
        rows.append({"margin": tau, "share_compared": float(keep.mean()),
                     **{k: float(v[keep].max()) if keep.any() else None
                        for k, v in p.items() if k.startswith("gap")}})
    return rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", required=True)
    ap.add_argument("--control", choices=("int8",), default=None)
    ap.add_argument("--router-bf16", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    ctx = prun.context(a.workload, seed=a.seed, seconds=a.seconds,
                       rehearse=a.rehearse)
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    dev = hs.device_info()
    hs.require_chips(dev, ctx["cell"]["chips"], a.rehearse)
    plant = contextlib.nullcontext()
    if a.router_bf16:
        from . import router_bf16
        plant = router_bf16.planted()
    with plant:
        state = serve.prepare(ctx)
        obs = serve.measure(state, ctx)
        weights = state.pop("weights")
        state.clear()
        gc.collect()
    lim = ctx["limits"]
    sample = check.sample_requests(obs.pop("finished"), a.seed,
                                   lim["sample_requests"])
    p = profile(weights, ctx["cfg"], sample, lim.get("pad_tokens", 256),
                lim.get("pad_outputs", 128), a.control)
    np.savez_compressed(a.out, **p)
    print(json.dumps({"seed": a.seed, "router_bf16": a.router_bf16,
                      "e2e": obs["e2e"], "requests": len(sample),
                      "positions": int(p["gap"].size),
                      "ladder": ladder(p)}), flush=True)


if __name__ == "__main__":
    main()
