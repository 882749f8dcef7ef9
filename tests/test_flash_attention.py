"""Pallas flash-attention kernel correctness via interpret mode (CPU) —
validates the kernel logic without TPU hardware."""
import functools

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


# ---- PR 31: the tile bodies (mask only on the diagonal, operands as
# stored, K/V sub-tiles walked by an inner loop with a causal trip count).
# Every case at blocks of 128, so that the three kinds of block exist:
# wholly under the diagonal (no mask), on it (masked), above it (skipped).
BODY_CASES = {
    # L = S = 384: q block 2 has kv block 0 and 1 under the diagonal, block
    # 2 on it; q block 0 skips kv blocks 1 and 2
    "under_on_skipped": dict(l=384, s=384, causal=True, d=128),
    # the S - L offset: row r sees cols <= r + 256
    "offset_s_gt_l": dict(l=256, s=512, causal=True, d=128),
    # L > S: the first 256 rows see nothing (zero output, lse = -inf)
    "offset_l_gt_s": dict(l=512, s=256, causal=True, d=128),
    "non_causal": dict(l=256, s=384, causal=False, d=128),
    # head_dim 64: zero-padded to the 128-lane tile, scale of the true dim
    "head_dim_64": dict(l=384, s=384, causal=True, d=64),
}
# bf16 carries 8 significant bits: neighbours lie 2**-8 (relative) apart at
# the top of a binade, 2**-7 at its bottom. Kernel and reference both round
# their probabilities and their results to bf16, at different places, so
# two values may differ by a step of the LARGEST magnitude in the tensor
# (a sum of rounded terms), and a little more where two such roundings
# stack: 3 steps of 2**-7 of the reference's largest magnitude.
# float32: 1e-5, which an operand rounded to bf16 (2**-8) misses by 400x.
_TOL = {"float32": lambda ref: 1e-5 * max(1.0, float(np.abs(ref).max())),
        "bfloat16": lambda ref: 3 * 2.0 ** -7 * float(np.abs(ref).max())}


def _ref_lse(q, k, causal, scale):
    s = jnp.einsum("blhd,bshd->bhls", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        ql, kl = s.shape[-2:]
        s = jnp.where(jnp.tril(jnp.ones((ql, kl), bool), k=kl - ql), s,
                      -jnp.inf)
    return jax.scipy.special.logsumexp(s, axis=-1)


@functools.lru_cache(maxsize=None)
def _body_case(case, dtype):
    """Kernel and reference values of one case, computed once: out, lse and
    the three gradients under a random cotangent."""
    import math
    from paddle_tpu.ops.flash_attention import _flash_bwd_impl
    c = BODY_CASES[case]
    rng = np.random.RandomState(7)
    mk = lambda n: jnp.asarray(
        rng.randn(1, n, 2, c["d"]).astype(np.float32) * 0.3).astype(dtype)
    q, k, v, dout = mk(c["l"]), mk(c["s"]), mk(c["s"]), mk(c["l"])
    scale = 1.0 / math.sqrt(c["d"])
    pad = [(0, 0)] * 3 + [(0, -c["d"] % 128)]
    qp, kp, vp, dp = (jnp.pad(x, pad) for x in (q, k, v, dout))
    out, lse = _flash_fwd_impl(qp, kp, vp, c["causal"], 128, 128,
                               interpret=True, sm_scale=scale)
    grads = _flash_bwd_impl(qp, kp, vp, out, lse, dp, c["causal"], 128, 128,
                            interpret=True, sm_scale=scale)
    assert out.dtype == dtype and all(g.dtype == dtype for g in grads)
    got = {"out": out, "dq": grads[0], "dk": grads[1], "dv": grads[2]}
    got = {n: x[..., :c["d"]] for n, x in got.items()}
    got["lse"] = lse
    ref, vjp = jax.vjp(lambda a, b, c_: _fa_reference(a, b, c_, c["causal"]),
                       q, k, v)
    rdq, rdk, rdv = vjp(dout)
    want = {"out": ref, "dq": rdq, "dk": rdk, "dv": rdv,
            "lse": _ref_lse(q, k, c["causal"], scale)}
    f32 = lambda d: {n: np.asarray(x.astype(jnp.float32))
                     for n, x in d.items()}
    return f32(got), f32(want)


@pytest.mark.parametrize("what", ["out", "lse", "dq", "dk", "dv"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("case", sorted(BODY_CASES))
def test_tile_bodies_match_reference(case, dtype, what):
    got, want = _body_case(case, jnp.dtype(dtype))
    g, w = got[what], want[what]
    if what == "lse":
        # the statistics stay float32 whatever the inputs: held to 1e-5 in
        # bf16 too (the products of bf16 operands are exact in float32);
        # rows with no visible column read -inf on both sides
        assert (np.isneginf(g) == np.isneginf(w)).all()
        fin = np.isfinite(w)
        np.testing.assert_allclose(g[fin], w[fin], atol=1e-5, rtol=1e-5)
        return
    assert np.isfinite(g).all()
    np.testing.assert_allclose(g, w, atol=_TOL[dtype](w), rtol=0)


def test_float32_operands_are_not_cast_down():
    """With float32 inputs the kernel's products take float32 operands: q,
    k, v carry bits below bf16's 8 and the output sees them."""
    rng = np.random.RandomState(11)
    mk = lambda: jnp.asarray(rng.randn(1, 256, 1, 128).astype(np.float32))
    q, k, v = mk(), mk(), mk()
    out, _ = _flash_fwd_impl(q, k, v, True, 128, 128, interpret=True)
    ref = _fa_reference(q, k, v, True)
    rounded = _fa_reference(*(x.astype(jnp.bfloat16).astype(jnp.float32)
                              for x in (q, k, v)), True)
    err = float(jnp.abs(out - ref).max())
    assert err < 1e-5 < float(jnp.abs(rounded - ref).max()) / 10


# the operand a kernel walks comes in major blocks of as many tiles as the
# VMEM budget allows: every test above holds its sequences whole, so the
# budget is cut here until a major block holds 3 tiles, 2, then 1 (768 rows
# of float32 x 128 cost 4 x 512 B a row), which brings in the second grid
# axis, its clamped index maps, and tiles that are skipped inside a block
@pytest.mark.parametrize("l,s", [(768, 768), (384, 768)])
@pytest.mark.parametrize("tiles", [6, 3, 2, 1])
def test_major_blocks_of_fewer_tiles(monkeypatch, tiles, l, s):
    from paddle_tpu.ops import flash_attention as fa
    monkeypatch.setattr(fa, "_MAJOR_VMEM_BYTES", 4 * tiles * 128 * 512)
    assert fa._major_block(128, 768, 512) == tiles * 128
    rng = np.random.RandomState(13)
    mk = lambda n: jnp.asarray(rng.randn(1, n, 1, 128).astype(np.float32) * 0.3)
    q, k, v, dout = mk(l), mk(s), mk(s), mk(l)
    out, lse = fa._flash_fwd_impl(q, k, v, True, 128, 128, interpret=True)
    grads = fa._flash_bwd_impl(q, k, v, out, lse, dout, True, 128, 128,
                               interpret=True)
    ref, vjp = jax.vjp(lambda a, b, c: _fa_reference(a, b, c, True), q, k, v)
    np.testing.assert_allclose(np.asarray(lse),
                               np.asarray(_ref_lse(q, k, True, 128 ** -0.5)),
                               atol=1e-5)
    for g, w in zip((out,) + tuple(grads), (ref,) + tuple(vjp(dout))):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5,
                                   rtol=0)


def test_major_block_rule():
    from paddle_tpu.ops.flash_attention import _major_block
    # every sequence the cells run is held whole: bf16 x 128 is 256 B a row
    for rows in (1024, 1536, 2048, 3072):
        assert _major_block(512, rows, 256) == rows
    # 4 x rows x row bytes within 8 MiB, a divisor of the sequence in tiles
    assert _major_block(512, 8192, 256) == 8192
    assert _major_block(512, 16384, 256) == 8192
    assert _major_block(512, 8192, 512) == 4096          # float32
    assert _major_block(512, 12288, 256) == 6144
    # a tile that alone is over the budget still runs, one a step
    assert _major_block(512, 1024, 1 << 20) == 512
