"""Pallas flash-attention kernel correctness via interpret mode (CPU) —
validates the kernel logic without TPU hardware."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.flash_attention import _flash_fwd_impl, _fa_reference


def _qkv(b=1, l=256, h=2, d=128, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, l, h, d).astype(np.float32) * 0.3)
    return mk(), mk(), mk()


class TestFlashKernelInterpret:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v = _qkv()
        out, lse = _flash_fwd_impl(q, k, v, causal, 128, 128, interpret=True)
        ref = _fa_reference(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_rectangular_blocks(self):
        q, k, v = _qkv(l=512)
        out, _ = _flash_fwd_impl(q, k, v, True, 256, 128, interpret=True)
        ref = _fa_reference(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("l,s", [(256, 512), (512, 256)])
    def test_causal_rectangular_lq_ne_lk(self, l, s):
        # bottom-right-aligned causal must agree with the reference (and hence
        # the custom-vjp backward recompute) when query/kv lengths differ;
        # fully-masked rows (L>S head) must be zero with defined gradients
        from paddle_tpu.ops.flash_attention import _flash_fwd_bwd
        rng = np.random.RandomState(3)
        q = jnp.asarray(rng.randn(1, l, 2, 128).astype(np.float32) * 0.3)
        k = jnp.asarray(rng.randn(1, s, 2, 128).astype(np.float32) * 0.3)
        v = jnp.asarray(rng.randn(1, s, 2, 128).astype(np.float32) * 0.3)
        out, _ = _flash_fwd_impl(q, k, v, True, 128, 128, interpret=True)
        ref = _fa_reference(q, k, v, True)
        assert np.isfinite(np.asarray(ref)).all()
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)
        # gradients through the custom-vjp (backward recomputes via reference)
        grads = jax.grad(
            lambda q, k, v: _flash_fwd_bwd(q, k, v, True, 128, 128, True).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for name, g in zip("qkv", grads):
            assert np.isfinite(np.asarray(g)).all(), f"nan in d{name}"

    def test_lse_values(self):
        q, k, v = _qkv(l=128, h=1)
        _, lse = _flash_fwd_impl(q, k, v, False, 128, 128, interpret=True)
        # reference lse
        s = jnp.einsum("blhd,bshd->bhls", q, k) / np.sqrt(q.shape[-1])
        ref_lse = jax.scipy.special.logsumexp(s.astype(jnp.float32), axis=-1)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                                   rtol=1e-4, atol=1e-5)


class TestFlashBackwardInterpret:
    """Pallas backward kernels (dq + dk/dv) vs jax.grad of the reference."""

    def _grads(self, q, k, v, causal, bq=128, bk=128):
        from paddle_tpu.ops.flash_attention import _flash_bwd_impl
        out, lse = _flash_fwd_impl(q, k, v, causal, bq, bk, interpret=True)
        dout = jnp.ones_like(out) * 0.5 + 0.1 * out  # non-trivial cotangent
        dq, dk, dv = _flash_bwd_impl(q, k, v, out, lse, dout, causal, bq, bk,
                                     interpret=True)

        # build the reference cotangent the same way (dout depends on out)
        rout = _fa_reference(q, k, v, causal)
        rdout = jnp.ones_like(rout) * 0.5 + 0.1 * rout
        _, vjp = jax.vjp(lambda a, b, c: _fa_reference(a, b, c, causal), q, k, v)
        rdq, rdk, rdv = vjp(rdout)
        return (dq, dk, dv), (rdq, rdk, rdv)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_reference(self, causal):
        q, k, v = _qkv(l=256)
        (dq, dk, dv), (rdq, rdk, rdv) = self._grads(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(rdq), atol=2e-3, rtol=2e-3)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk), atol=2e-3, rtol=2e-3)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(rdv), atol=2e-3, rtol=2e-3)

    def test_grads_rectangular_blocks(self):
        q, k, v = _qkv(l=512, seed=1)
        (dq, dk, dv), (rdq, rdk, rdv) = self._grads(q, k, v, True, bq=256, bk=128)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(rdq), atol=2e-3, rtol=2e-3)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk), atol=2e-3, rtol=2e-3)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(rdv), atol=2e-3, rtol=2e-3)

    @pytest.mark.parametrize("l,s", [(128, 384), (384, 128)])
    def test_grads_causal_lq_ne_lk(self, l, s):
        rng = np.random.RandomState(2)
        q = jnp.asarray(rng.randn(1, l, 2, 128).astype(np.float32) * 0.3)
        k = jnp.asarray(rng.randn(1, s, 2, 128).astype(np.float32) * 0.3)
        v = jnp.asarray(rng.randn(1, s, 2, 128).astype(np.float32) * 0.3)
        (dq, dk, dv), (rdq, rdk, rdv) = self._grads(q, k, v, True)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(rdq), atol=2e-3, rtol=2e-3)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk), atol=2e-3, rtol=2e-3)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(rdv), atol=2e-3, rtol=2e-3)

    def test_custom_vjp_end_to_end_interpret(self):
        from paddle_tpu.ops.flash_attention import _flash_fwd_bwd
        q, k, v = _qkv(l=256, seed=3)

        def loss(q_, k_, v_):
            return jnp.sum(_flash_fwd_bwd(q_, k_, v_, True, 128, 128, True) ** 2)

        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        def ref_loss(q_, k_, v_):
            return jnp.sum(_fa_reference(q_, k_, v_, True) ** 2)

        rg = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, rg):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3,
                                       rtol=2e-3)


def test_fit_block_always_tiles():
    from paddle_tpu.ops.flash_attention import _fit_block
    # L=640 with requested 512: naive min() would truncate rows 512-639
    assert _fit_block(512, 640) == 128
    assert _fit_block(512, 768) == 384
    assert _fit_block(512, 512) == 512
    assert _fit_block(512, 1024) == 512
    assert _fit_block(128, 896) == 128
    for req in (128, 256, 512):
        for length in range(128, 2049, 128):
            b = _fit_block(req, length)
            assert length % b == 0 and b % 128 == 0 and b <= max(req, 128)


def test_non_dividing_block_covers_tail_interpret():
    # 640-long sequence with requested block 512 -> _fit_block picks 128;
    # the kernel grads must cover the tail rows the old min() would drop
    from paddle_tpu.ops.flash_attention import _fit_block, _flash_bwd_impl
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(1, 640, 1, 128).astype(np.float32) * 0.3)
    k = jnp.asarray(rng.randn(1, 640, 1, 128).astype(np.float32) * 0.3)
    v = jnp.asarray(rng.randn(1, 640, 1, 128).astype(np.float32) * 0.3)
    bq, bk = _fit_block(512, 640), _fit_block(512, 640)
    out, lse = _flash_fwd_impl(q, k, v, True, bq, bk, interpret=True)
    dout = jnp.ones_like(out)
    dq, dk, dv = _flash_bwd_impl(q, k, v, out, lse, dout, True, bq, bk,
                                 interpret=True)
    _, vjp = jax.vjp(lambda a, b, c: _fa_reference(a, b, c, True), q, k, v)
    rdq, rdk, rdv = vjp(dout)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rdq), atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk), atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rdv), atol=2e-3, rtol=2e-3)


def test_head_dim_64_pad_path_interpret():
    # D=64 is padded to the 128-lane tile with sm_scale = 1/sqrt(64);
    # zero columns must be exactly inert in fwd and grads
    import math
    from paddle_tpu.ops.flash_attention import _flash_fwd_bwd
    rng = np.random.RandomState(5)
    mk = lambda: jnp.asarray(rng.randn(1, 256, 2, 64).astype(np.float32) * 0.3)
    q, k, v = mk(), mk(), mk()
    pad = [(0, 0)] * 3 + [(0, 64)]
    scale = 1.0 / math.sqrt(64)

    def f(q_, k_, v_):
        o = _flash_fwd_bwd(jnp.pad(q_, pad), jnp.pad(k_, pad), jnp.pad(v_, pad),
                           True, 128, 128, True, scale)
        return o[..., :64]

    out = f(q, k, v)
    ref = _fa_reference(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-5)
    g = jax.grad(lambda *a: jnp.sum(f(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
    rg = jax.grad(lambda *a: jnp.sum(_fa_reference(*a, True) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, rg):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3,
                                   rtol=2e-3)
