"""Pallas flash attention for TPU.

Reference capability: phi/kernels/gpu/flash_attn_kernel.cu (vendored
third_party/flashattn). TPU-native design: an online-softmax tiled kernel over
VMEM blocks (q-block × kv-block grid), bf16 in / fp32 accumulate on the MXU,
with a custom_vjp whose backward recomputes attention blockwise
(flash-attention-2 style).

`flash_attention_raw` is the entry for model code and says when the kernel
runs; `flash_attention(q, k, v, causal=...)` is the same on [B, L, H, D]
Tensors.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..core.engine import apply
from ..core.tensor import Tensor

_MIN_BLOCK = 128

# index-map constant: with jax_enable_x64 a literal 0 traces as i64, which
# Mosaic cannot legalize in BlockSpec index maps
import numpy as _np
_i0 = _np.int32(0)


def flash_attention_tpu_available() -> bool:
    try:
        return jax.default_backend() == "tpu"
    except Exception:
        return False


def _block_run(qi, ki, block_q, block_k, L, S, causal):
    """Causal block-skip: does block (qi, ki) contain any visible entry?
    Bottom-right-aligned convention: row r sees cols <= r + S - L. Shared by
    the forward and both backward kernels so the convention cannot diverge."""
    if causal:
        return (ki * block_k) <= (qi * block_q + block_q - 1 + S - L)
    return ki >= 0


def _causal_mask_scores(s, qi, ki, block_q, block_k, L, S):
    """Apply the in-block bottom-right causal mask to a score tile."""
    rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(rows + (S - L) >= cols, s, -jnp.inf)


def masked_softmax(logits, mask):
    """Softmax along the last axis where fully-masked rows (e.g. the L>S head
    of a bottom-right causal mask) get all-zero probs — and defined
    gradients — instead of softmax(-inf row)=nan. Matches the Pallas
    forward's handling of rows with no visible kv."""
    m = jnp.max(jnp.where(mask, logits, -jnp.inf), axis=-1, keepdims=True)
    m = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.where(mask, jnp.exp(logits - m), 0.0)
    return p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)


def _fa_reference(q, k, v, causal):
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("blhd,bshd->bhls", q, k).astype(jnp.float32) * scale
    if causal:
        ql, kl = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((ql, kl), bool), k=kl - ql)
        probs = masked_softmax(logits, mask).astype(q.dtype)
    else:
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhls,bshd->blhd", probs, v)


def flash_attention_raw(q, k, v, causal: bool = False, block_q: int = 512,
                        block_k: int = 512, mesh=None, spec=None):
    """Raw-jnp-array flash attention ([B, L, H, D] in/out) — the shared entry
    for the Tensor API and model code.

    The Pallas kernel runs when the platform is TPU and both sequence
    lengths are multiples of 128 (its minimum tile); any other shape, and
    every off-TPU call, takes the XLA reference `_fa_reference`. The shape
    rule is a property of the kernel, not a safety net: chip_smoke.py and
    tests/test_tpu_compile.py prove the main path's shapes take the kernel.

    mesh/spec: inside a GSPMD-partitioned program (the sharded train step)
    the kernel is wrapped in a `shard_map` over `mesh` with `spec` on q, k,
    v and the output — Mosaic kernels cannot be partitioned automatically,
    and attention is independent per (batch row, head), so each shard runs
    the same kernel on its own rows and heads with no collective.

    FLAGS_flash_block_q / FLAGS_flash_block_k (env or set_flags) override
    the tile sizes globally; 0 keeps the caller's value."""
    from ..utils.flags import flag_value
    block_q = int(flag_value("flash_block_q") or block_q)
    block_k = int(flag_value("flash_block_k") or block_k)
    L, S = q.shape[1], k.shape[1]
    if (L % _MIN_BLOCK) or (S % _MIN_BLOCK) or not flash_attention_tpu_available():
        return _fa_reference(q, k, v, causal)
    kernel = functools.partial(_flash_kernel, causal=causal,
                               bq=_fit_block(block_q, L),
                               bk=_fit_block(block_k, S))
    if mesh is not None:
        from ..utils.jax_compat import shard_map
        kernel = shard_map(kernel, mesh, (spec, spec, spec), spec)
    return kernel(q, k, v)


def _flash_kernel(q, k, v, *, causal, bq, bk):
    D = q.shape[-1]
    if D % 128 == 0:
        return _flash_fwd_bwd(q, k, v, causal, bq, bk)
    # head_dim 64 (GPT-2 / tiny-llama class): zero-pad D to the 128-lane
    # MXU tile — zero columns contribute nothing to q·k and produce zero
    # output/grad columns, so padding + slicing is exact. The softmax
    # scale must use the TRUE head dim, passed via sm_scale.
    D_pad = -(-D // 128) * 128
    pad = [(0, 0)] * 3 + [(0, D_pad - D)]
    out = _flash_fwd_bwd(jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad),
                         causal, bq, bk, False, 1.0 / math.sqrt(D))
    return out[..., :D]


def flash_attention(query, key, value, causal: bool = False, block_q: int = 512,
                    block_k: int = 512):
    """[B, L, H, D] in/out. Falls back to the XLA path for small/ragged shapes."""

    def f(q, k, v):
        return flash_attention_raw(q, k, v, causal, block_q, block_k)

    return apply(f, query, key, value, name="flash_attention")


def _fit_block(requested: int, length: int) -> int:
    """Largest multiple of _MIN_BLOCK that divides `length` and is <= requested
    (the grid fully tiles the sequence — no truncated tail)."""
    b = max(min(requested, length), _MIN_BLOCK)
    b -= b % _MIN_BLOCK
    while length % b:
        b -= _MIN_BLOCK
    return b


# ---------------- pallas kernel ----------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_fwd_bwd(q, k, v, causal, block_q, block_k, interpret=False,
                   sm_scale=None):
    out, _ = _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret,
                             sm_scale)
    return out


def _flash_fwd_rule(q, k, v, causal, block_q, block_k, interpret=False,
                    sm_scale=None):
    out, lse = _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret,
                               sm_scale)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, block_q, block_k, interpret, sm_scale, res, dout):
    q, k, v, out, lse = res
    return _flash_bwd_impl(q, k, v, out, lse, dout, causal, block_q, block_k,
                           interpret, sm_scale)


_flash_fwd_bwd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _flash_bwd_impl(q, k, v, out, lse, dout, causal, block_q, block_k,
                    interpret=False, sm_scale=None):
    """Flash-attention-2 backward as two Pallas kernels.

    Recomputes p = exp(q k^T * scale - lse) blockwise from the saved lse, so
    nothing O(L*S) is ever materialised:
      delta = rowsum(dout * out)                 (precomputed, [B,H,L])
      dp = dout v^T;  ds = p * (dp - delta)
      dq = ds k * scale   (kernel 1: q-block rows, accumulate over kv blocks)
      dk = ds^T q * scale; dv = p^T dout
                          (kernel 2: kv-block rows, accumulate over q blocks)
    The causal block-skip condition matches the forward kernel's.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, L, H, D = q.shape
    S = k.shape[1]
    assert L % block_q == 0 and S % block_k == 0, \
        f"blocks must tile the sequences: {L}%{block_q}, {S}%{block_k}"
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    grid_q = L // block_q
    grid_k = S // block_k

    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    dot = jnp.swapaxes(dout, 1, 2)                  # [B, H, L, D]
    delta = jnp.sum(dot.astype(jnp.float32) * jnp.swapaxes(out, 1, 2).astype(jnp.float32),
                    axis=-1, keepdims=True)          # [B, H, L, 1]
    lse4 = lse[..., None]                            # [B, H, L, 1]

    def block_run(qi, ki):
        return _block_run(qi, ki, block_q, block_k, L, S, causal)

    def p_and_ds(qb, kb, vb, dob, lseb, deltab, qi, ki):
        # qb [bq, D] f32 (pre-scaled), others f32; returns p, ds [bq, bk]
        s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask_scores(s, qi, ki, block_q, block_k, L, S)
        safe_lse = jnp.where(jnp.isneginf(lseb), 0.0, lseb)
        p = jnp.exp(s - safe_lse)                    # masked entries: exp(-inf)=0
        dp = jax.lax.dot_general(dob, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - deltab)
        return p, ds

    # ---- kernel 1: dq (rows = q blocks, reduce over kv blocks) ----
    def dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref, acc):
        qi, ki = pl.program_id(2), pl.program_id(3)

        @pl.when(ki == 0)
        def _init():
            acc[:] = jnp.zeros_like(acc)

        @pl.when(block_run(qi, ki))
        def _body():
            qb = q_ref[0, 0].astype(jnp.float32) * scale
            kb = k_ref[0, 0].astype(jnp.float32)
            vb = v_ref[0, 0].astype(jnp.float32)
            dob = do_ref[0, 0].astype(jnp.float32)
            _, ds = p_and_ds(qb, kb, vb, dob, lse_ref[0, 0], dl_ref[0, 0], qi, ki)
            acc[:] += jax.lax.dot_general(ds, kb, (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32) * scale

        @pl.when(ki == grid_k - 1)
        def _fin():
            dq_ref[0, 0] = acc[:].astype(dq_ref.dtype)

    dqt = pl.pallas_call(
        dq_kernel,
        grid=(B, H, grid_q, grid_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, _i0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h, ki, _i0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h, ki, _i0)),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, _i0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, qi, ki: (b, h, qi, _i0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, qi, ki: (b, h, qi, _i0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, _i0)),
        out_shape=jax.ShapeDtypeStruct((B, H, L, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ) if not interpret else None,
        interpret=interpret,
        name="flash_bwd_dq",
    )(qt, kt, vt, dot, lse4, delta)

    # ---- kernel 2: dk, dv (rows = kv blocks, reduce over q blocks) ----
    def dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dk_ref, dv_ref,
                   acc_dk, acc_dv):
        ki, qi = pl.program_id(2), pl.program_id(3)

        @pl.when(qi == 0)
        def _init():
            acc_dk[:] = jnp.zeros_like(acc_dk)
            acc_dv[:] = jnp.zeros_like(acc_dv)

        @pl.when(block_run(qi, ki))
        def _body():
            qb = q_ref[0, 0].astype(jnp.float32) * scale
            kb = k_ref[0, 0].astype(jnp.float32)
            vb = v_ref[0, 0].astype(jnp.float32)
            dob = do_ref[0, 0].astype(jnp.float32)
            p, ds = p_and_ds(qb, kb, vb, dob, lse_ref[0, 0], dl_ref[0, 0], qi, ki)
            acc_dv[:] += jax.lax.dot_general(p, dob, (((0,), (0,)), ((), ())),
                                             preferred_element_type=jnp.float32)
            # qb is pre-scaled, so ds^T @ qb already carries the 1/sqrt(D)
            acc_dk[:] += jax.lax.dot_general(ds, qb, (((0,), (0,)), ((), ())),
                                             preferred_element_type=jnp.float32)

        @pl.when(qi == grid_q - 1)
        def _fin():
            dk_ref[0, 0] = acc_dk[:].astype(dk_ref.dtype)
            dv_ref[0, 0] = acc_dv[:].astype(dv_ref.dtype)

    dkt, dvt = pl.pallas_call(
        dkv_kernel,
        grid=(B, H, grid_k, grid_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, ki, qi: (b, h, qi, _i0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, qi: (b, h, ki, _i0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, qi: (b, h, ki, _i0)),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, ki, qi: (b, h, qi, _i0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, ki, qi: (b, h, qi, _i0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, ki, qi: (b, h, qi, _i0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, qi: (b, h, ki, _i0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, qi: (b, h, ki, _i0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, S, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ) if not interpret else None,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qt, kt, vt, dot, lse4, delta)

    return (jnp.swapaxes(dqt, 1, 2), jnp.swapaxes(dkt, 1, 2),
            jnp.swapaxes(dvt, 1, 2))


def _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret=False,
                    sm_scale=None):
    """Tiled online-softmax forward in Pallas (interpret=True runs the same
    kernel on CPU for correctness tests without a TPU)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, L, H, D = q.shape
    S = k.shape[1]
    assert L % block_q == 0 and S % block_k == 0, \
        f"blocks must tile the sequences: {L}%{block_q}, {S}%{block_k}"
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    grid_q = L // block_q
    grid_k = S // block_k

    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_i, l_i):
        qi = pl.program_id(2)
        ki = pl.program_id(3)

        @pl.when(ki == 0)
        def _init():
            acc[:] = jnp.zeros_like(acc)
            m_i[:] = jnp.full_like(m_i, -jnp.inf)
            l_i[:] = jnp.zeros_like(l_i)

        @pl.when(_block_run(qi, ki, block_q, block_k, L, S, causal))
        def _body():
            qb = q_ref[0, 0].astype(jnp.float32) * scale  # [block_q, D]
            kb = k_ref[0, 0].astype(jnp.float32)          # [block_k, D]
            vb = v_ref[0, 0].astype(jnp.float32)
            s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if causal:
                s = _causal_mask_scores(s, qi, ki, block_q, block_k, L, S)
            m_prev = m_i[:]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            # rows with no visible kv yet keep m=-inf; exp against 0 avoids
            # the -inf - -inf = nan path while leaving p/alpha exactly 0
            safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
            p = jnp.exp(s - safe_m[:, None])
            alpha = jnp.exp(m_prev - safe_m)
            l_i[:] = l_i[:] * alpha + jnp.sum(p, axis=1)
            acc[:] = acc[:] * alpha[:, None] + jax.lax.dot_general(
                p, vb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            m_i[:] = m_new

        @pl.when(ki == grid_k - 1)
        def _fin():
            denom = jnp.maximum(l_i[:], 1e-30)
            o_ref[0, 0] = (acc[:] / denom[:, None]).astype(o_ref.dtype)
            lse_ref[0, 0] = (m_i[:] + jnp.log(denom))[:, None]

    # layout: [B, H, L, D] for clean blocking
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)

    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H, grid_q, grid_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, _i0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h, ki, _i0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h, ki, _i0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, _i0)),
            # lse carried as [..., 1] — Mosaic requires the last two block dims
            # to be (8k, 128k) or equal to the array dims; (block_q, 1) is legal
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, qi, ki: (b, h, qi, _i0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, L, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, L, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ) if not interpret else None,
        interpret=interpret,
        name="flash_fwd",
    )(qt, kt, vt)
    return jnp.swapaxes(out, 1, 2), lse[..., 0]
