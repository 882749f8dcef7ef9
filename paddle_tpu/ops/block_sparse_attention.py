"""Block-sparse flash attention (reference
phi/kernels/sparse/fused_attention_kernel.h — sparse-masked attention
whose CSR pattern selects the attendable pairs).

TPU-native lowering (VERDICT r3 next #7): instead of materializing the
[T, T] pattern and dense logits (O(T²) memory — the thing sparse masks
exist to avoid), the CSR pattern is compiled ONCE into
  * block_map  [grid_q, grid_k] int32 — 0: block has no attendable pair
    (kernel skips it entirely: no K/V load, no MXU work), >0: 1 + index
    into the partial-mask array;
  * partial_masks [P, block_q, block_k] int8 — dense bits ONLY for blocks
    the pattern partially covers; slot 0 is all-ones and is shared by
    every fully-covered block.
For banded / sliding-window / global-token patterns P is O(T/block), so
memory is O(T·block) instead of O(T²), and compute skips inactive blocks
— the same online-softmax accumulation as ops/flash_attention.py
otherwise. Forward AND backward (dq, dk/dv) kernels honor the map.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["block_sparse_attention", "pattern_to_block_map"]


def pattern_to_block_map(rows, cols, T, block_q, block_k):
    """Compile a COO pattern (host arrays) into (block_map, partial_masks).

    O(nnz) host work, done once per mask — never materializes [T, T].
    """
    rows = np.asarray(rows, np.int64).reshape(-1)
    cols = np.asarray(cols, np.int64).reshape(-1)
    gq, gk = T // block_q, T // block_k
    # per-block nnz (duplicate pattern entries collapse via unique pairs)
    uniq_pair = np.unique(rows * T + cols)
    urows, ucols = uniq_pair // T, uniq_pair % T
    ulin = (urows // block_q) * gk + (ucols // block_k)
    counts = np.bincount(ulin, minlength=gq * gk).reshape(gq, gk)
    full = counts == block_q * block_k
    partial = (counts > 0) & ~full
    pidx = np.flatnonzero(partial.reshape(-1))
    # block_map semantics: 0 = skip; v > 0 = compute with mask slot v-1
    # (slot 0 is the shared all-ones block for fully-covered tiles)
    block_map = np.zeros((gq, gk), np.int32)
    block_map[full] = 1
    block_map.reshape(-1)[pidx] = np.arange(len(pidx), dtype=np.int32) + 2
    masks = np.zeros((len(pidx) + 1, block_q, block_k), np.int8)
    masks[0] = 1
    slot_by_lin = np.zeros(gq * gk, np.int64)
    slot_by_lin[pidx] = np.arange(len(pidx)) + 1
    in_partial = partial.reshape(-1)[ulin]
    pr, pc = urows[in_partial], ucols[in_partial]
    masks[slot_by_lin[ulin[in_partial]], pr % block_q, pc % block_k] = 1
    return block_map, masks


def _bsa_fwd_impl(q, k, v, block_map, masks, block_q, block_k,
                  interpret=False, sm_scale=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, L, H, D = q.shape
    S = k.shape[1]
    grid_q, grid_k = block_map.shape
    assert L == grid_q * block_q and S == grid_k * block_k
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)

    def kernel(bmap_ref, q_ref, k_ref, v_ref, m_ref, o_ref, lse_ref,
               acc, m_i, l_i):
        qi = pl.program_id(2)
        ki = pl.program_id(3)

        @pl.when(ki == 0)
        def _init():
            acc[:] = jnp.zeros_like(acc)
            m_i[:] = jnp.full_like(m_i, -jnp.inf)
            l_i[:] = jnp.zeros_like(l_i)

        @pl.when(bmap_ref[qi, ki] > 0)
        def _body():
            qb = q_ref[0, 0].astype(jnp.float32) * scale
            kb = k_ref[0, 0].astype(jnp.float32)
            vb = v_ref[0, 0].astype(jnp.float32)
            s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            # -inf (not a big-negative) so a row fully masked within this
            # block contributes p = 0 and l stays 0 — the safe_m dance
            # below then keeps fully-empty rows at output 0
            s = jnp.where(m_ref[0] != 0, s, -jnp.inf)
            m_prev = m_i[:]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
            p = jnp.exp(s - safe_m[:, None])
            alpha = jnp.exp(m_prev - safe_m)
            l_i[:] = l_i[:] * alpha + jnp.sum(p, axis=1)
            acc[:] = acc[:] * alpha[:, None] + jax.lax.dot_general(
                p, vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_i[:] = m_new

        @pl.when(ki == grid_k - 1)
        def _fin():
            denom = jnp.maximum(l_i[:], 1e-30)
            o_ref[0, 0] = (acc[:] / denom[:, None]).astype(o_ref.dtype)
            lse_ref[0, 0] = (m_i[:] + jnp.log(denom))[:, None]

    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)

    # the mask BlockSpec routes each (qi, ki) to its slot (0 for full or
    # skipped blocks) via the scalar-prefetched block_map
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, grid_q, grid_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki, bm: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki, bm: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki, bm: (b, h, ki, 0)),
            pl.BlockSpec(
                (1, block_q, block_k),
                lambda b, h, qi, ki, bm: (
                    jnp.maximum(bm[qi, ki] - 1, 0), 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki, bm: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, qi, ki, bm: (b, h, qi, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, L, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, L, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ) if not interpret else None,
        interpret=interpret,
        name="block_sparse_fwd",
    )(block_map, qt, kt, vt, masks)
    return jnp.swapaxes(out, 1, 2), lse[..., 0]


def _bsa_bwd_impl(q, k, v, out, lse, dout, block_map, masks, block_q,
                  block_k, interpret=False, sm_scale=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, L, H, D = q.shape
    S = k.shape[1]
    grid_q, grid_k = block_map.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)

    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    ot = jnp.swapaxes(out, 1, 2)
    dot = jnp.swapaxes(dout, 1, 2).astype(jnp.float32)
    delta = jnp.sum(ot.astype(jnp.float32) * dot, axis=-1, keepdims=True)
    lse4 = lse[..., None]

    def p_and_ds(qb, kb, vb, dob, lseb, deltab, maskb):
        s = jax.lax.dot_general(
            qb * scale, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        # fully-empty rows carry lse = -inf; exp(-inf - -inf) would be
        # nan, so pin their lse to 0 — their p is forced to 0 by the mask
        lse_safe = jnp.where(jnp.isfinite(lseb), lseb, 0.0)
        p = jnp.where(maskb != 0, jnp.exp(s - lse_safe), 0.0)
        dp = jax.lax.dot_general(dob, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - deltab) * scale
        return p, ds

    def dq_kernel(bmap_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                  m_ref, dq_ref, acc):
        qi = pl.program_id(2)
        ki = pl.program_id(3)

        @pl.when(ki == 0)
        def _init():
            acc[:] = jnp.zeros_like(acc)

        @pl.when(bmap_ref[qi, ki] > 0)
        def _body():
            _, ds = p_and_ds(q_ref[0, 0].astype(jnp.float32),
                             k_ref[0, 0].astype(jnp.float32),
                             v_ref[0, 0].astype(jnp.float32),
                             do_ref[0, 0], lse_ref[0, 0], dl_ref[0, 0],
                             m_ref[0])
            acc[:] += jax.lax.dot_general(
                ds, k_ref[0, 0].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(ki == pl.num_programs(3) - 1)
        def _fin():
            dq_ref[0, 0] = acc[:].astype(dq_ref.dtype)

    grid_spec_dq = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, grid_q, grid_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki, bm: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki, bm: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki, bm: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki, bm: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, qi, ki, bm: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, qi, ki, bm: (b, h, qi, 0)),
            pl.BlockSpec(
                (1, block_q, block_k),
                lambda b, h, qi, ki, bm: (
                    jnp.maximum(bm[qi, ki] - 1, 0), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, ki, bm: (b, h, qi, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
    )
    dqt = pl.pallas_call(
        dq_kernel,
        grid_spec=grid_spec_dq,
        out_shape=jax.ShapeDtypeStruct((B, H, L, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ) if not interpret else None,
        interpret=interpret,
        name="block_sparse_bwd_dq",
    )(block_map, qt, kt, vt, dot, lse4, delta, masks)

    # dk/dv iterate (ki, qi) — needs the transposed map semantics
    def dkv_kernel(bmap_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                   m_ref, dk_ref, dv_ref, acc_dk, acc_dv):
        ki = pl.program_id(2)
        qi = pl.program_id(3)

        @pl.when(qi == 0)
        def _init():
            acc_dk[:] = jnp.zeros_like(acc_dk)
            acc_dv[:] = jnp.zeros_like(acc_dv)

        @pl.when(bmap_ref[qi, ki] > 0)
        def _body():
            qb = q_ref[0, 0].astype(jnp.float32)
            p, ds = p_and_ds(qb, k_ref[0, 0].astype(jnp.float32),
                             v_ref[0, 0].astype(jnp.float32),
                             do_ref[0, 0], lse_ref[0, 0], dl_ref[0, 0],
                             m_ref[0])
            acc_dv[:] += jax.lax.dot_general(
                p, do_ref[0, 0], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_dk[:] += jax.lax.dot_general(
                ds, qb, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(qi == pl.num_programs(3) - 1)
        def _fin():
            dk_ref[0, 0] = acc_dk[:].astype(dk_ref.dtype)
            dv_ref[0, 0] = acc_dv[:].astype(dv_ref.dtype)

    grid_spec_dkv = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, grid_k, grid_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, ki, qi, bm: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, qi, bm: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, qi, bm: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, ki, qi, bm: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, ki, qi, bm: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, ki, qi, bm: (b, h, qi, 0)),
            pl.BlockSpec(
                (1, block_q, block_k),
                lambda b, h, ki, qi, bm: (
                    jnp.maximum(bm[qi, ki] - 1, 0), 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, qi, bm: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, qi, bm: (b, h, ki, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
    )
    dkt, dvt = pl.pallas_call(
        dkv_kernel,
        grid_spec=grid_spec_dkv,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, S, D), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ) if not interpret else None,
        interpret=interpret,
        name="block_sparse_bwd_dkv",
    )(block_map, qt, kt, vt, dot, lse4, delta, masks)

    return (jnp.swapaxes(dqt, 1, 2), jnp.swapaxes(dkt, 1, 2),
            jnp.swapaxes(dvt, 1, 2))


@functools.lru_cache(maxsize=8)
def _get_bsa_fn(rows_bytes, cols_bytes, T, block_q, block_k, interpret):
    """custom_vjp-wrapped kernel closure for one compiled pattern. Cached
    on the COO pattern itself (nnz-sized — hashing it per call is cheap;
    the multi-MB mask blocks are built once HERE and live only in the
    closure), so repeated steps with the same mask reuse the jitted
    executable without re-deriving or re-hashing the block map. maxsize
    is small because each entry can pin large mask arrays + a compiled
    kernel."""
    rows = np.frombuffer(rows_bytes, np.int64)
    cols = np.frombuffer(cols_bytes, np.int64)
    block_map, masks = pattern_to_block_map(rows, cols, T, block_q,
                                            block_k)

    @jax.custom_vjp
    def f(q, k, v):
        out, _ = _bsa_fwd_impl(q, k, v, block_map, masks, block_q,
                               block_k, interpret)
        return out

    def fwd(q, k, v):
        out, lse = _bsa_fwd_impl(q, k, v, block_map, masks, block_q,
                                 block_k, interpret)
        return out, (q, k, v, out, lse)

    def bwd(res, dout):
        q, k, v, out, lse = res
        return _bsa_bwd_impl(q, k, v, out, lse, dout, block_map, masks,
                             block_q, block_k, interpret)

    f.defvjp(fwd, bwd)
    return jax.jit(f)


def compile_pattern(rows, cols, T, block_q: int = 512, block_k: int = 512,
                    interpret=None):
    """Resolve (and cache) the compiled kernel closure for one COO pattern.
    This is the ONLY point that reads the pattern to host (np.asarray) and
    hashes its bytes — callers that hold a pattern across steps should call
    this once and reuse the returned fn (csr.fused_attention memoizes it on
    the mask object), so steady-state steps pay no O(nnz) transfer/hash."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _get_bsa_fn(np.asarray(rows, np.int64).tobytes(),
                       np.asarray(cols, np.int64).tobytes(),
                       T, block_q, block_k, bool(interpret))


def block_sparse_attention(q, k, v, rows, cols, block_q: int = 512,
                           block_k: int = 512, interpret=None):
    """Attention over the COO pattern (rows, cols) without any [T, T]
    intermediate. q/k/v: [B, T, H, D] (flash_attention layout). Rows fully
    outside the pattern get output 0 (softmax over an empty set)."""
    B, T, H, D = q.shape
    block_q = min(block_q, T)
    block_k = min(block_k, T)
    assert T % block_q == 0 and T % block_k == 0, \
        f"pattern blocks must tile T: {T} % {block_q}/{block_k}"
    fn = compile_pattern(rows, cols, T, block_q, block_k, interpret)
    return fn(q, k, v)
