"""Pallas ragged paged attention for TPU (ISSUE 8 tentpole).

Reference: "Ragged Paged Attention" (PAPERS.md, arxiv 2604.15464) — the
fused TPU kernel behind vLLM-on-TPU. ``models/llama_paged.py`` expressed
the paged-KV idea at the XLA level: decode gathers K/V rows through the
block table with ``jnp.take`` and attends ``page_bucket × page_size``
rows. That shape is static, so the serving engine compiles one burst
executable per PAGE BUCKET and one prefill executable per PROMPT BUCKET —
an inventory that grows with the bucket grid, and a bytes/token bill that
follows the bucket width, not the live context.

This module is the kernel-level replacement. One Pallas program per
(slot, kv-head) reads the slot's LIVE pages from the HBM pool with
per-page async copies (double-buffered: page j+1 streams in while page j's
logits are on the MXU), driven by scalar-prefetched block tables and
per-slot sequence lengths. Because raggedness lives in SMEM scalars
instead of array shapes, ONE executable covers every context length AND
every prefill length: prefill rows (q_len = prompt length, causal) and
decode rows (q_len = 1) are just different ``q_lens`` values against the
same compiled program — the mixed prefill+decode burst of
``llama_ragged_burst`` launches it with no bucket grid at all.

Semantics match ``llama_decode._cached_attention_slots`` /
``llama._attention`` op-for-op (f32 logits, ``-1e30`` mask, full-width
softmax whose masked lanes underflow to exact zeros), so greedy outputs
are token-identical to the gather and dense paths — pinned by
``tests/test_ragged_attention.py``.

CPU/tier-1: the kernel runs under ``interpret=True`` (same jnp ops, DMAs
emulated). The COMPILED path does not exist yet: Mosaic refuses the kernel
at every serving geometry (the two messages are in ``supported()`` below
and pinned by ``tests/test_tpu_compile.py``), so ``supported(...,
interpret=False)`` is False everywhere and ``ContinuousBatcher(
kv_layout="ragged")`` raises on a TPU instead of serving through another
path unasked. ``PADDLE_RAGGED_ATTN=0`` is the one explicit way to ask a
ragged-mode caller for the XLA block-table gather (``enabled()`` below).

Sharding (GSPMD, arxiv 2105.04663): programs are independent per
(slot, kv-head), so a pool sharded ``P(None, None, "model", None)`` runs
the SAME kernel per shard under ``shard_map`` — each chip DMAs only its
own KV heads' pages. See ``parallel/sharding.py:kv_pool_sharding``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import env_flags

__all__ = ["ragged_paged_attention", "enabled", "supported",
           "ENV_RAGGED_ATTN"]

ENV_RAGGED_ATTN = "PADDLE_RAGGED_ATTN"

# index-map constant: with jax_enable_x64 a literal 0 traces as i64, which
# Mosaic cannot legalize in BlockSpec index maps (see ops/flash_attention)
_i0 = np.int32(0)

def enabled() -> bool:
    """The PADDLE_RAGGED_ATTN fallback switch: '0' sends every ragged-mode
    caller back to the XLA block-table gather (token-identical, just
    bucket-bound again). Anything else leaves the kernel on."""
    return env_flags.get_bool(ENV_RAGGED_ATTN)


def supported(head_dim: int, page_size: int, interpret: bool,
              kv_dtype: str | None = None) -> bool:
    """Can this (pool, config) run the kernel? It says what the compiler
    says. Interpret mode always can. The compiled path is refused by
    Mosaic (jax 0.9.0 / libtpu 0.0.34, described v5e) at every geometry
    tried, for two separate reasons:

      * ``page_size`` < 128 (the batcher's default is 16) — decode
        ``B8 Q1 H32 KV32 hd128``, GQA ``H32 KV8`` and prefill ``Q128``
        alike: "cannot statically prove that index in dimension 1 is a
        multiple of 128" on the ``tpu.vector_store`` of each page's logits
        tile into the ``[span, max_pages*page_size]`` scratch at lane
        offset ``j*page_size`` (``page_step`` in both kernel bodies);
      * ``page_size`` = 128 — that store passes, and the per-(page,
        kv-head) DMA is refused: "Slice shape along dimension 2 must be
        aligned to tiling (8), but is 1" on the ``memref_slice`` of the
        ``[num_pages, page_size, KV, hd]`` pool (``kdma``/``vdma``): one
        KV head cannot be sliced out of the sublane-tiled ``KV`` dim in
        HBM.

    The repair (a later perf_opt issue) is a per-page online softmax
    instead of a full-width logits scratch, and a page DMA that moves
    whole tiles — head-major pages, or all KV heads of a page at once.
    Until a geometry compiles, the compiled path is refused outright;
    ``tests/test_tpu_compile.py`` holds the two refusals as strict xfails
    and must agree with this function in every case it holds."""
    del head_dim, page_size, kv_dtype   # no geometry compiles yet
    return bool(interpret)


def _kernel_body(bt_ref, qlen_ref, kvlen_ref, q_ref, kp_ref, vp_ref, o_ref,
                 kbuf, vbuf, lbuf, ksem, vsem, *, page_size, max_pages,
                 groups, q_max, scale):
    """One (slot b, kv-head k) program.

    Scalar prefetch (SMEM): bt_ref [B, Pmax] block table, qlen_ref /
    kvlen_ref [B]. q_ref block [1, 1, q_max*groups, hd] (row = qpos*g+gi).
    kp/vp_ref: the WHOLE pool in HBM (pl.ANY) — only live pages move.

    Pipeline: page j's K lands in kbuf[j%2] while page j+1's copy is in
    flight (double buffering); its logits tile goes to lbuf as soon as the
    wait clears. V pages stream into the contiguous vbuf because every
    live row is needed AFTER the softmax. Raggedness: n_pages = ceil(
    kv_len/page_size) bounds the fori_loop — bytes moved follow the LIVE
    context, and no shape depends on it.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    k = pl.program_id(1)
    ps = page_size
    span = q_max * groups
    rows_total = max_pages * ps
    q_len = qlen_ref[b]
    kv_len = kvlen_ref[b]
    # every traced scalar is pinned i32: paddle_tpu enables jax_enable_x64,
    # under which a stray Python-int promotion to i64 breaks lowering
    n_pages = (kv_len + jnp.int32(ps - 1)) // jnp.int32(ps)

    @pl.when(q_len == 0)
    def _skip():
        # slot takes no queries this launch (e.g. a decoding slot during
        # the prefill-phase launch): write zeros, never NaN residue
        o_ref[0, 0] = jnp.zeros_like(o_ref[0, 0])

    @pl.when(q_len > 0)
    def _run():
        q = q_ref[0, 0].astype(jnp.float32)          # [span, hd]

        def kdma(j, slot):
            return pltpu.make_async_copy(
                kp_ref.at[bt_ref[b, j], :, k, :], kbuf.at[slot],
                ksem.at[slot])

        def vdma(j, slot):
            return pltpu.make_async_copy(
                vp_ref.at[bt_ref[b, j], :, k, :],
                vbuf.at[pl.ds(j * jnp.int32(ps), ps), :],
                vsem.at[jax.lax.rem(j, jnp.int32(2))])

        kdma(jnp.int32(0), jnp.int32(0)).start()
        vdma(jnp.int32(0), jnp.int32(0)).start()

        def page_step(j, _):
            slot = jax.lax.rem(j, jnp.int32(2))
            nxt = jax.lax.rem(j + jnp.int32(1), jnp.int32(2))

            @pl.when(j + jnp.int32(1) < n_pages)
            def _prefetch():                         # double buffer: j+1
                kdma(j + jnp.int32(1), nxt).start()  # streams while j
                vdma(j + jnp.int32(1), nxt).start()  # computes below

            kdma(j, slot).wait()
            vdma(j, slot).wait()
            kpage = kbuf[slot].astype(jnp.float32)   # [ps, hd]
            lbuf[:, pl.ds(j * jnp.int32(ps), ps)] = jax.lax.dot_general(
                q, kpage, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            return 0

        jax.lax.fori_loop(0, n_pages, page_step, 0)

        def zero_tail(j, _):
            # vbuf rows past the live pages are stale VMEM: the masked
            # softmax zeroes their PROBS exactly, but 0 * NaN is NaN —
            # zero the rows themselves so dead lanes contribute exact 0
            vbuf[pl.ds(j * jnp.int32(ps), ps), :] = jnp.zeros(
                (ps, vbuf.shape[1]), vbuf.dtype)
            return 0

        jax.lax.fori_loop(n_pages, jnp.int32(max_pages), zero_tail, 0)

        # mask + softmax over the FULL static width, exactly like the XLA
        # gather path: invalid lanes pinned at -1e30 underflow to exact
        # zero probability, so stale logits (incl. NaN) never contribute
        cols = jax.lax.broadcasted_iota(jnp.int32, (span, rows_total), 1)
        qpos = jax.lax.broadcasted_iota(jnp.int32, (span, rows_total),
                                        0) // jnp.int32(groups)
        valid = (cols < kv_len) & (cols <= kv_len - q_len + qpos)
        logits = jnp.where(valid, lbuf[:], jnp.float32(-1e30))
        probs = jax.nn.softmax(logits, axis=-1).astype(vbuf.dtype)
        out = jax.lax.dot_general(probs, vbuf[:], (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def _kernel_body_quant(bt_ref, qlen_ref, kvlen_ref, q_ref, kp_ref, vp_ref,
                       ksp_ref, vsp_ref, o_ref, kbuf, ksbuf, vtmp, vsbuf,
                       vbuf, lbuf, ksem, kssem, vsem, vssem, *, page_size,
                       max_pages, groups, q_max, scale):
    """The quantized-pool variant of ``_kernel_body`` (ISSUE 10).

    The payload pools are int8/fp8 and per-(page, row, head) f32 scale
    pools ride alongside (``ksp_ref``/``vsp_ref``, [num_pages, ps, KV]).
    Each streamed page is DEQUANTIZED inside the double-buffered DMA loop:
    page j's payload and its [ps] scale slice land together, and the f32
    ``payload × scale`` product feeds the same logits tile / masked
    softmax as the unquantized kernel. V pages stream through their own
    double buffer (``vtmp``) and land dequantized-f32 in the contiguous
    ``vbuf`` run, so the post-softmax ``probs @ V`` consumes exact f32 —
    the arithmetic the XLA gather path gets from dequantizing right after
    its ``jnp.take`` (token-identical on CPU, pinned by
    tests/test_quant.py)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    k = pl.program_id(1)
    ps = page_size
    span = q_max * groups
    rows_total = max_pages * ps
    q_len = qlen_ref[b]
    kv_len = kvlen_ref[b]
    n_pages = (kv_len + jnp.int32(ps - 1)) // jnp.int32(ps)

    @pl.when(q_len == 0)
    def _skip():
        o_ref[0, 0] = jnp.zeros_like(o_ref[0, 0])

    @pl.when(q_len > 0)
    def _run():
        q = q_ref[0, 0].astype(jnp.float32)          # [span, hd]

        def kdma(j, slot):
            return pltpu.make_async_copy(
                kp_ref.at[bt_ref[b, j], :, k, :], kbuf.at[slot],
                ksem.at[slot])

        def ksdma(j, slot):
            return pltpu.make_async_copy(
                ksp_ref.at[bt_ref[b, j], :, k], ksbuf.at[slot],
                kssem.at[slot])

        def vdma(j, slot):
            return pltpu.make_async_copy(
                vp_ref.at[bt_ref[b, j], :, k, :], vtmp.at[slot],
                vsem.at[slot])

        def vsdma(j, slot):
            return pltpu.make_async_copy(
                vsp_ref.at[bt_ref[b, j], :, k], vsbuf.at[slot],
                vssem.at[slot])

        for dma in (kdma, ksdma, vdma, vsdma):
            dma(jnp.int32(0), jnp.int32(0)).start()

        def page_step(j, _):
            slot = jax.lax.rem(j, jnp.int32(2))
            nxt = jax.lax.rem(j + jnp.int32(1), jnp.int32(2))

            @pl.when(j + jnp.int32(1) < n_pages)
            def _prefetch():                         # double buffer: j+1
                for dma in (kdma, ksdma, vdma, vsdma):
                    dma(j + jnp.int32(1), nxt).start()

            kdma(j, slot).wait()
            ksdma(j, slot).wait()
            # per-page dequantize INSIDE the DMA loop, mirroring the
            # gather path's arithmetic EXACTLY: payload × scale in f32,
            # rounded to the model dtype (the gather's _kv_decode(...,
            # c.dtype) after its jnp.take), then f32 for the logits dot —
            # for a bf16 model both paths round identically, so gather
            # and kernel stay token-identical for ANY model dtype
            kpage = (kbuf[slot].astype(jnp.float32)
                     * ksbuf[slot][:, None]).astype(q_ref.dtype) \
                .astype(jnp.float32)                 # [ps, hd]
            lbuf[:, pl.ds(j * jnp.int32(ps), ps)] = jax.lax.dot_general(
                q, kpage, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            vdma(j, slot).wait()
            vsdma(j, slot).wait()
            vbuf[pl.ds(j * jnp.int32(ps), ps), :] = \
                (vtmp[slot].astype(jnp.float32)
                 * vsbuf[slot][:, None]).astype(vbuf.dtype)
            return 0

        jax.lax.fori_loop(0, n_pages, page_step, 0)

        def zero_tail(j, _):
            vbuf[pl.ds(j * jnp.int32(ps), ps), :] = jnp.zeros(
                (ps, vbuf.shape[1]), vbuf.dtype)
            return 0

        jax.lax.fori_loop(n_pages, jnp.int32(max_pages), zero_tail, 0)

        cols = jax.lax.broadcasted_iota(jnp.int32, (span, rows_total), 1)
        qpos = jax.lax.broadcasted_iota(jnp.int32, (span, rows_total),
                                        0) // jnp.int32(groups)
        valid = (cols < kv_len) & (cols <= kv_len - q_len + qpos)
        logits = jnp.where(valid, lbuf[:], jnp.float32(-1e30))
        # probs round to the model dtype like the unquantized kernel (and
        # the gather path's softmax(...).astype(q.dtype)) — vbuf already
        # holds model-dtype dequantized rows, so the value product is the
        # same arithmetic the gather einsum runs
        probs = jax.nn.softmax(logits, axis=-1).astype(vbuf.dtype)
        out = jax.lax.dot_general(probs, vbuf[:], (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def ragged_paged_attention(q, k_pool, v_pool, block_table, q_lens, kv_lens,
                           *, page_size: int, interpret: bool,
                           k_scale=None, v_scale=None):
    """Ragged paged attention over a shared page pool.

    q           [B, Qmax, H, hd] — per-slot query rows; slot b uses rows
                [0, q_lens[b]) as queries at absolute positions
                kv_lens[b] - q_lens[b] + r (decode: Qmax=1, q_lens=1;
                prefill: ragged prompt lengths, causal).
    k/v_pool    [num_pages, page_size, KV, hd] — the paged KV pool.
    block_table [B, Pmax] int32 — logical→physical page map per slot.
    q_lens      [B] int32 — 0 skips the slot (zeros out).
    kv_lens     [B] int32 — live context rows (attend rows < kv_lens[b]).
    k/v_scale   (ISSUE 10) [num_pages, page_size, KV] f32 — per-block
                scales of an int8/fp8 pool; both given = quantized pools,
                dequantized per streamed page inside the DMA loop.

    Returns [B, Qmax, H, hd] in q.dtype. All raggedness is carried by the
    scalar-prefetched q_lens/kv_lens/block_table — the compiled program
    depends only on (B, Qmax, Pmax, page_size, KV, hd, dtype).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, q_max, H, hd = q.shape
    n_pages_pool, ps, KV, _ = k_pool.shape
    assert ps == page_size, (ps, page_size)
    max_pages = block_table.shape[1]
    groups = H // KV
    span = q_max * groups
    scale = np.float32(1.0) / np.sqrt(np.float32(hd))
    if (k_scale is None) != (v_scale is None):
        # both-or-neither: one missing scale would either consume raw
        # int8 payloads as numbers (garbage, silently) or die opaquely
        # inside the jit — make the contract loud instead
        raise ValueError("quantized pools need BOTH k_scale and v_scale "
                         "(got exactly one)")
    quant = k_scale is not None

    # [B, Qmax, H, hd] -> [B, KV, Qmax*groups, hd]; row = qpos*g + gi
    # keeps the gather path's head mapping h = k*g + gi bit-for-bit
    qh = q.reshape(B, q_max, KV, groups, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, KV, span, hd)

    body = _kernel_body_quant if quant else _kernel_body
    kernel = functools.partial(
        body, page_size=ps, max_pages=max_pages, groups=groups,
        q_max=q_max, scale=scale)
    in_specs = [
        pl.BlockSpec((1, 1, span, hd), lambda b, k, *_: (b, k, _i0, _i0)),
        pl.BlockSpec(memory_space=pl.ANY),   # K pool stays in HBM;
        pl.BlockSpec(memory_space=pl.ANY),   # live pages are DMA'd
    ]
    if quant:
        scratch = [
            pltpu.VMEM((2, ps, hd), k_pool.dtype),           # K payload dbuf
            pltpu.VMEM((2, ps), jnp.float32),                # K scale dbuf
            pltpu.VMEM((2, ps, hd), v_pool.dtype),           # V payload dbuf
            pltpu.VMEM((2, ps), jnp.float32),                # V scale dbuf
            pltpu.VMEM((max_pages * ps, hd), q.dtype),       # V dequant run
            #              (model dtype: rounds like the gather's decode)
            pltpu.VMEM((span, max_pages * ps), jnp.float32),  # logits
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ]
        in_specs += [pl.BlockSpec(memory_space=pl.ANY),   # K scales
                     pl.BlockSpec(memory_space=pl.ANY)]   # V scales
        operands = (qh, k_pool, v_pool, k_scale.astype(jnp.float32),
                    v_scale.astype(jnp.float32))
    else:
        scratch = [
            pltpu.VMEM((2, ps, hd), k_pool.dtype),          # K double buffer
            pltpu.VMEM((max_pages * ps, hd), v_pool.dtype),  # V, contiguous
            pltpu.VMEM((span, max_pages * ps), jnp.float32),  # logits
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ]
        operands = (qh, k_pool, v_pool)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, span, hd),
                               lambda b, k, *_: (b, k, _i0, _i0)),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, span, hd), q.dtype),
        compiler_params=(None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))),
        interpret=interpret,
    )(block_table.astype(jnp.int32), q_lens.astype(jnp.int32),
      kv_lens.astype(jnp.int32), *operands)

    return out.reshape(B, KV, q_max, groups, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, q_max, H, hd)
