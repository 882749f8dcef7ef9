"""ops/gated_delta.py against the rule written out in numpy float64: the
chunked scan for lengths that are no multiple of the chunk, from an initial
state, with padding past `length`, with fast gates (no overflow) and slow
ones (a carry 200 tokens back shows), and the one-token step; the step's
Pallas pass (interpreted here) against its jax.numpy body, frozen slots
left bit for bit as they were."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.gated_delta import (CHUNK, _unit_lower_inverse,
                                        gdn_chunk_scan, gdn_step,
                                        gdn_step_body, gdn_step_jnp)

H, DK, DV = 3, 8, 16


def rule(q, k, v, g, beta, S0):
    """S' = e^g S; u = beta (v - S' k); S = S' + u k^T; o = S q."""
    S = S0.astype(np.float64).copy()
    out = np.zeros(v.shape, np.float64)
    for t in range(q.shape[0]):
        for h in range(q.shape[1]):
            Sd = np.exp(g[t, h]) * S[h]
            u = beta[t, h] * (v[t, h] - Sd @ k[t, h])
            S[h] = Sd + np.outer(u, k[t, h])
            out[t, h] = S[h] @ q[t, h]
    return out, S


def inputs(T, seed, g_lo, g_hi, with_state=True):
    r = np.random.default_rng(seed)
    q = r.normal(size=(T, H, DK))
    k = r.normal(size=(T, H, DK))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(DK)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.normal(size=(T, H, DV))
    g = -r.uniform(g_lo, g_hi, size=(T, H))
    beta = r.uniform(0.0, 2.0, size=(T, H))
    S0 = r.normal(size=(H, DV, DK)) if with_state else np.zeros((H, DV, DK))
    return tuple(a.astype(np.float32) for a in (q, k, v, g, beta, S0))


def as_jax(args):
    return tuple(jnp.asarray(a) for a in args)


@pytest.mark.parametrize("T", [1, 63, 64, 150, 257])
def test_chunk_scan_is_the_recurrence_at_any_length(T):
    args = inputs(T, T, 0.05, 1.0)
    want_o, want_S = rule(*args)
    o, S = gdn_chunk_scan(*as_jax(args))
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(S, want_S, atol=2e-5)


def test_no_initial_state_is_a_zero_state():
    args = inputs(100, 1, 0.05, 1.0, with_state=False)
    want_o, want_S = rule(*args)
    o, S = gdn_chunk_scan(*as_jax(args[:5]))
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(S, want_S, atol=2e-5)


@pytest.mark.parametrize("length", [1, 64, 130, 191])
def test_padding_past_length_leaves_the_state_alone(length):
    args = inputs(192, 7, 0.05, 1.0)
    want_o, want_S = rule(*(a[:length] for a in args[:5]), args[5])
    o, S = gdn_chunk_scan(*as_jax(args), length=jnp.int32(length))
    np.testing.assert_allclose(o[:length], want_o, atol=2e-5)
    np.testing.assert_allclose(S, want_S, atol=2e-5)
    assert np.isfinite(np.asarray(o)).all()


def test_fast_gates_do_not_overflow():
    """g down to -3 a token: 64 of them sum to -192, and exp(192) is not a
    float32; only differences G_i - G_j with i >= j may be exponentiated."""
    args = inputs(200, 11, 2.0, 3.0)
    want_o, want_S = rule(*args)
    o, S = gdn_chunk_scan(*as_jax(args))
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(S)).all()
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(S, want_S, atol=2e-5)


def test_slow_gates_carry_the_state_for_hundreds_of_tokens():
    """dt_bias -4 gives g = -softplus(-4) = -0.018 a token: after 200
    tokens 2.7 % of a state is left, so an error in what one chunk hands the
    next must show 200 tokens on. It agrees; and the check can see: the same
    inputs from another initial state differ, 200 tokens later, by far more
    than the tolerance."""
    g0 = float(np.log1p(np.exp(-4.0)))
    args = inputs(300, 13, g0, g0)
    beta_small = (args[4] * 0.05).astype(np.float32)        # little is erased
    args = args[:4] + (beta_small,) + args[5:]
    want_o, want_S = rule(*args)
    o, S = gdn_chunk_scan(*as_jax(args))
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(S, want_S, atol=2e-5)
    other, _ = gdn_chunk_scan(*as_jax(args[:5]), jnp.asarray(args[5] * 2.0))
    assert np.abs(np.asarray(other) - want_o)[200:].max() > 50 * 2e-5


def test_step_is_one_step_of_the_recurrence():
    B = 5
    r = np.random.default_rng(3)
    state = r.normal(size=(B, H, DV, DK)).astype(np.float32)
    q, k, v, g, beta, _ = inputs(B, 5, 0.05, 3.0)
    o, new = gdn_step(*as_jax((q, k, v, g, beta, state)))
    for b in range(B):
        want_o, want_S = rule(q[b:b + 1], k[b:b + 1], v[b:b + 1],
                              g[b:b + 1], beta[b:b + 1], state[b])
        np.testing.assert_allclose(o[b], want_o[0], atol=1e-5)
        np.testing.assert_allclose(new[b], want_S, atol=1e-5)


def test_nilpotent_product_is_the_inverse():
    r = np.random.default_rng(9)
    A = np.tril(r.normal(size=(2, CHUNK, CHUNK)) * 0.3, -1).astype(np.float32)
    want = np.linalg.inv(np.eye(CHUNK) + A.astype(np.float64))
    np.testing.assert_allclose(_unit_lower_inverse(jnp.asarray(A)), want,
                               rtol=1e-4, atol=1e-4)


def test_prompt_then_steps_is_one_sequence():
    """Prefill by the chunk scan, then decode by steps: the state handed
    over is the whole memory of the prompt."""
    args = inputs(90, 17, 0.05, 1.0, with_state=False)
    want_o, _ = rule(*args)
    _, S = gdn_chunk_scan(*as_jax(a[:70] for a in args[:5]))
    S = S[None]
    assert gdn_step_body(S.shape, S.dtype) == "kernel"     # interpreted here
    step = jax.jit(gdn_step)
    for t in range(70, 90):
        o, S = step(*as_jax(a[t:t + 1] for a in args[:5]), S)
        np.testing.assert_allclose(o[0], want_o[t], atol=2e-5)


def step_inputs(B, heads, dk, dv, seed, dtype=np.float32):
    """One token of B slots, q and k as the mixer makes them (unit, q
    scaled), gates over the range the cell's random weights give."""
    r = np.random.default_rng(seed)
    q = r.normal(size=(B, heads, dk))
    k = r.normal(size=(B, heads, dk))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.normal(size=(B, heads, dv))
    g = -r.uniform(0.05, 3.0, size=(B, heads))
    beta = r.uniform(0.0, 2.0, size=(B, heads))
    state = r.normal(size=(B, heads, dv, dk))
    return tuple(jnp.asarray(a, dt) for a, dt in zip(
        (q, k, v, g, beta, state),
        (dtype, dtype, dtype, np.float32, np.float32, np.float32)))


@pytest.mark.parametrize("B,heads,dk,dv,dtype", [
    (5, H, DK, DV, np.float32),
    (3, 30, 96, 192, jnp.bfloat16),        # Olmo-Hybrid's widths, as served
])
def test_step_kernel_is_the_jnp_body(B, heads, dk, dv, dtype):
    """The Pallas pass (interpreted) makes what gdn_step_jnp makes, to
    float32 rounding: the same products, the sums along dk in another
    order; and both are one step of the recurrence."""
    args = step_inputs(B, heads, dk, dv, B + dk, dtype)
    assert gdn_step_body(args[5].shape, args[5].dtype) == "kernel"
    o, new = gdn_step(*args, interpret=True)
    want_o, want_new = gdn_step_jnp(*args)
    np.testing.assert_allclose(o, want_o, rtol=0, atol=2e-6)
    np.testing.assert_allclose(new, want_new, rtol=0, atol=2e-6)
    assert new.dtype == jnp.float32 and o.shape == (B, heads, dv)
    f = [np.asarray(a, np.float64) for a in args]
    for b in range(B):
        ro, rS = rule(*(a[b:b + 1] for a in f[:5]), f[5][b])
        np.testing.assert_allclose(o[b], ro[0], atol=1e-5)
        np.testing.assert_allclose(new[b], rS, atol=1e-5)


@pytest.mark.parametrize("frozen", [
    (False, True, False, True, False),      # between live slots
    (True, True, False, False, True),       # before the first live slot
    (True, True, True, True, True),         # every slot
])
def test_frozen_slot_comes_back_bit_for_bit(frozen):
    """A frozen slot's state is neither read nor written by the kernel (its
    block index repeats another slot's, or with none live every step holds
    slot 0's and writes it back as it came): bitwise what it was, its
    output 0; the live slots as gdn_step_jnp makes them."""
    args = step_inputs(5, H, DK, DV, 21)
    mask = jnp.asarray(frozen)
    o, new = gdn_step(*args, mask, interpret=True)
    want_o, want_new = gdn_step_jnp(*args, mask)
    state = np.asarray(args[5])
    f = np.asarray(frozen)
    assert (np.asarray(new)[f] == state[f]).all()
    assert (np.asarray(o)[f] == 0).all()
    np.testing.assert_allclose(new, want_new, rtol=0, atol=2e-6)
    np.testing.assert_allclose(o, want_o, rtol=0, atol=2e-6)
    if not f.all():
        assert not (np.asarray(new)[~f] == state[~f]).any()


def test_step_body_by_dtype_and_vmem():
    """The kernel takes a float32 state whose slot fits its VMEM; a state
    in another dtype, or a slot too wide, keeps the jnp body (which rounds
    the new state to the state's dtype and holds frozen slots by a
    select)."""
    assert gdn_step_body((24, 30, 192, 96), jnp.float32) == "kernel"
    assert gdn_step_body((24, 30, 192, 96), jnp.bfloat16) == "jnp"
    assert gdn_step_body((24, 64, 256, 128), jnp.float32) == "jnp"
    args = step_inputs(4, H, DK, DV, 5)
    low = args[:5] + (args[5].astype(jnp.bfloat16),)
    frozen = jnp.asarray([False, True, False, False])
    o, new = gdn_step(*low, frozen, interpret=True)
    assert new.dtype == jnp.bfloat16
    assert (np.asarray(new[1]) == np.asarray(low[5][1])).all()
    want_o, _ = gdn_step_jnp(*args[:5], low[5].astype(jnp.float32))
    np.testing.assert_allclose(o[0], want_o[0], atol=1e-6)
