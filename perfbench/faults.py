"""Faults planted under the timed path, by name.

`--fault <name>` runs a cell with its program broken underneath, to read on
the chip what each number compared makes of the fault; the tests drive the
same names at tiny sizes and see `correct` come out false. The driver never
passes it. A fault wraps the object the window calls and nothing else: the
runner, the window and the comparison are those of a sound run.
"""
from __future__ import annotations


def state_unchanged(step, cfg):
    """A train step that returns its state unchanged: the loss is computed,
    the update is thrown away. The state waits on the host meanwhile, so
    that two copies never stand on the device."""
    import jax

    def call(tokens, labels):
        state = step.resilience_state()
        where = jax.tree.map(lambda x: getattr(x, "sharding", None), state)
        kept = jax.device_get(state)
        loss = jax.block_until_ready(step(tokens, labels))
        step.load_resilience_state(jax.tree.map(
            lambda h, s: h if s is None else jax.device_put(h, s),
            kept, where))
        return loss
    return call


def half_batch(step, cfg):
    """Half of the batch left out, the mean taken over the rest: the second
    half's labels are masked before the step sees them."""
    import numpy as np

    def call(tokens, labels):
        labels = np.array(labels)
        labels[labels.shape[0] // 2:] = -1
        return step(tokens, labels)
    return call


def altered_token(eng, cfg):
    """Every step, the newest token of every request in flight is altered
    after the engine produced it: of every one, because `correct` compares
    a sample of the finished requests, and at a cell's own size (48 slots,
    4 sampled of ~275) one altered request is seldom in it."""
    inner, vocab = eng.step, cfg["vocab_size"]

    def step():
        inner()
        for req in eng._slot_req:
            if req is not None and len(req.out) > 1:
                req.out[-1] = req.out[-1] % (vocab - 1) + 1    # never pad
    eng.step = step
    return eng


def shed_request(eng, cfg):
    """One queued request is shed before it is served."""
    inner, state = eng.step, {"shed": False}

    def step():
        if not state["shed"] and eng._queue:
            eng.shed_newest(1)
            state["shed"] = True
        inner()
    eng.step = step
    return eng


FAULTS = {"train": {"state_unchanged": state_unchanged,
                    "half_batch": half_batch},
          "serve": {"altered_token": altered_token,
                    "shed_request": shed_request}}
NAMES = sorted(n for group in FAULTS.values() for n in group)


def plant(kind: str, name: str | None, target, cfg: dict):
    """`target` (a train step or an engine of configuration `cfg`) with the
    named fault planted; itself when no fault is asked for."""
    if name is None:
        return target
    if name not in FAULTS[kind]:
        raise SystemExit(f"no fault {name!r} for a {kind} cell: "
                         f"{sorted(FAULTS[kind])}")
    return FAULTS[kind][name](target, cfg)
