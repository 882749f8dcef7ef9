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
(slot, block of kv-heads) copies the slot's LIVE pages from the HBM pool
into contiguous K and V runs in VMEM (one async copy per page, all in
flight at once), driven by scalar-prefetched block tables and per-slot
sequence lengths. Because raggedness lives in SMEM scalars instead of
array shapes, ONE executable covers every context length AND every
prefill length: prefill rows (q_len = prompt length, causal) and decode
rows (q_len = 1) are just different ``q_lens`` values against the same
compiled program — the mixed prefill+decode burst of
``llama_ragged_burst`` launches it with no bucket grid at all.

Semantics match ``llama_decode._cached_attention_slots`` /
``llama._attention`` op-for-op (f32 logits, ``-1e30`` mask, full-width
softmax whose masked lanes underflow to exact zeros), so greedy outputs
are token-identical to the gather and dense paths — pinned by
``tests/test_ragged_attention.py``.

CPU/tier-1: the kernel runs under ``interpret=True`` (same jnp ops, DMAs
emulated). On a TPU it is compiled by Mosaic; ``supported()`` below says
which pools the compiler takes (pinned by ``tests/test_tpu_compile.py``),
and ``ContinuousBatcher(kv_layout="ragged")`` raises for the others
instead of serving through another path unasked. ``PADDLE_RAGGED_ATTN=0``
is the one explicit way to ask a ragged-mode caller for the XLA
block-table gather (``enabled()`` below).

What the compiled kernel is NOT yet (ROADMAP S4, a perf_opt issue): it is
shaped by what Mosaic accepts, not tuned. Bytes moved follow the live
context, but the logits product, the softmax and probs@V run over the
slot's FULL width; each head's K/V rows are read out of the [rows, heads,
hd] runs with sublane-strided loads; and compile time grows steeply with
``max_len`` (``_MAX_COMPILED_ROWS``). No speed has been measured.

Sharding (GSPMD, arxiv 2105.04663): programs are independent per
(slot, kv-head block), so a pool sharded ``P(None, None, "model", None)`` runs
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


# the compiled kernel holds a slot's whole context in VMEM and unrolls over
# it: on a described v5e one kernel compiled in 10 s at 512 rows, 29 s at
# 1024, 79 s at 2048, minutes at 4096, and not within 17 min at 8192
_MAX_COMPILED_ROWS = 4096


def supported(head_dim: int, kv_heads: int, max_len: int, interpret: bool,
              kv_dtype: str | None = None) -> bool:
    """Can this (pool, config) run the kernel? It says what the compiler
    says (jax 0.9.0 / libtpu 0.0.34, compiled for a described v5e;
    ``tests/test_tpu_compile.py`` holds a case on each side of every rule
    and must agree with this function). Interpret mode always can. The
    compiled path needs:

      * ``head_dim % 128 == 0`` — else the page DMA is refused: "Slice
        shape along dimension 3 must be aligned to tiling (128), but is
        64";
      * ``kv_heads % 8 == 0``, or 2 or 4 — the page DMA moves a block of
        KV heads (``_head_block``), which must be whole sublane tiles or
        the whole dim: 12 heads give "Slice shape along dimension 2 must
        be aligned to tiling (8), but is 12";
      * an unquantized pool — the [page_size, heads] slice of the scale
        pools is refused: "Slice shape along dimension 2 must be aligned
        to tiling (128), but is 16";
      * ``max_len <= 4096`` — see ``_MAX_COMPILED_ROWS``.

    Any ``page_size`` compiles (1, 5, 8, 16, 32 and 128 were tried)."""
    if interpret:
        return True
    return (kv_dtype is None and head_dim % 128 == 0
            and (kv_heads % 8 == 0 or kv_heads in (2, 4))
            and max_len <= _MAX_COMPILED_ROWS)


def _head_block(kv_heads: int) -> int:
    """KV heads per kernel program. The page DMA slices the pool's KV dim,
    which HBM tiles in sublanes, and Mosaic takes such a slice only in
    whole tiles or as the whole dim (a single head is refused: "Slice
    shape along dimension 2 must be aligned to tiling (8), but is 1").
    16 fills a bf16 tile, 8 an f32 one."""
    for block in (16, 8):
        if kv_heads % block == 0:
            return block
    return kv_heads


def _kernel_body(bt_ref, qlen_ref, kvlen_ref, q_ref, kp_ref, vp_ref, *rest,
                 page_size, max_pages, groups, q_max, heads, scale, quant):
    """One (slot b, block of `heads` kv-heads) program.

    Scalar prefetch (SMEM): bt_ref [B, Pmax] block table, qlen_ref /
    kvlen_ref [B]. q_ref block [1, heads, q_max*groups, hd] (row =
    qpos*g+gi). kp/vp_ref: the WHOLE pool in HBM (pl.ANY) — only live
    pages move. ``quant``: ksp/vsp_ref, the per-(page, row, head) f32 scale
    pools of an int8/fp8 pool (ISSUE 10), ride alongside.

    Every live page's [page_size, heads, hd] slice is copied into the
    contiguous K and V runs (all copies in flight at once, then awaited):
    n_pages = ceil(kv_len/page_size) bounds both loops, so bytes moved
    follow the LIVE context and no shape depends on it. Each head then
    takes ONE full-width logits product against its K run — no per-page
    store at a lane offset, which Mosaic refuses below 128 lanes ("cannot
    statically prove that index in dimension 1 is a multiple of 128").
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if quant:
        ksp_ref, vsp_ref, o_ref, kbuf, vbuf, ksbuf, vsbuf, sem = rest
    else:
        o_ref, kbuf, vbuf, sem = rest
    b = pl.program_id(0)
    ps = page_size
    span = q_max * groups
    rows_total = max_pages * ps
    q_len = qlen_ref[b]
    kv_len = kvlen_ref[b]
    # every traced scalar is pinned i32: paddle_tpu enables jax_enable_x64,
    # under which a stray Python-int promotion to i64 breaks lowering
    n_pages = (kv_len + jnp.int32(ps - 1)) // jnp.int32(ps)
    head0 = pl.multiple_of(pl.program_id(1) * jnp.int32(heads), heads)

    @pl.when(q_len == 0)
    def _skip():
        # slot takes no queries this launch (e.g. a decoding slot during
        # the prefill-phase launch): write zeros, never NaN residue
        o_ref[0] = jnp.zeros_like(o_ref[0])

    @pl.when(q_len > 0)
    def _run():
        def page_copies(j):
            page = bt_ref[b, j]
            rows = pl.ds(pl.multiple_of(j * jnp.int32(ps), ps), ps)
            block = pl.ds(head0, heads)
            copies = [(kp_ref.at[page, :, block, :], kbuf.at[rows]),
                      (vp_ref.at[page, :, block, :], vbuf.at[rows])]
            if quant:
                copies += [(ksp_ref.at[page, :, block], ksbuf.at[rows]),
                           (vsp_ref.at[page, :, block], vsbuf.at[rows])]
            return [pltpu.make_async_copy(src, dst, sem.at[jnp.int32(i)])
                    for i, (src, dst) in enumerate(copies)]

        def start(j, _):
            for copy in page_copies(j):
                copy.start()
            return 0

        def wait(j, _):
            for copy in page_copies(j):
                copy.wait()
            return 0

        jax.lax.fori_loop(jnp.int32(0), n_pages, start, 0)
        jax.lax.fori_loop(jnp.int32(0), n_pages, wait, 0)

        # mask + softmax over the FULL static width, exactly like the XLA
        # gather path: invalid lanes pinned at -1e30 underflow to exact
        # zero probability, so stale logits (incl. NaN) never contribute
        cols = jax.lax.broadcasted_iota(jnp.int32, (span, rows_total), 1)
        qpos = jax.lax.broadcasted_iota(jnp.int32, (span, rows_total),
                                        0) // jnp.int32(groups)
        valid = (cols < kv_len) & (cols <= kv_len - q_len + qpos)
        # rows past the live context are stale VMEM: their PROBS are exact
        # zeros, but 0 * NaN is NaN — zero the V rows themselves
        live = jax.lax.broadcasted_iota(
            jnp.int32, (rows_total, vbuf.shape[-1]), 0) < kv_len
        for h in range(heads):
            q = q_ref[0, h].astype(jnp.float32)          # [span, hd]
            k, v = kbuf[:, h, :], vbuf[:, h, :]          # [rows_total, hd]
            if quant:
                # dequantize mirroring the gather path's arithmetic
                # EXACTLY: payload × scale in f32, rounded to the model
                # dtype (the gather's _kv_decode(..., c.dtype) after its
                # jnp.take) — for a bf16 model both paths round
                # identically, so gather and kernel stay token-identical
                # for ANY model dtype
                k = (k.astype(jnp.float32)
                     * ksbuf[:, h][:, None]).astype(q_ref.dtype)
                v = (v.astype(jnp.float32)
                     * vsbuf[:, h][:, None]).astype(q_ref.dtype)
            logits = jax.lax.dot_general(
                q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            logits = jnp.where(valid, logits, jnp.float32(-1e30))
            # probs round to the model dtype like the gather path's
            # softmax(...).astype(q.dtype)
            probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
            out = jax.lax.dot_general(
                probs, jnp.where(live, v, jnp.zeros_like(v)),
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            o_ref[0, h] = out.astype(o_ref.dtype)


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

    heads = _head_block(KV)
    rows_total = max_pages * ps
    kernel = functools.partial(
        _kernel_body, page_size=ps, max_pages=max_pages, groups=groups,
        q_max=q_max, heads=heads, scale=scale, quant=quant)
    q_block = pl.BlockSpec((1, heads, span, hd),
                           lambda b, k, *_: (b, k, _i0, _i0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)   # pools stay in HBM; live
    in_specs = [q_block, hbm, hbm]            # pages are DMA'd
    scratch = [pltpu.VMEM((rows_total, heads, hd), k_pool.dtype),   # K run
               pltpu.VMEM((rows_total, heads, hd), v_pool.dtype)]   # V run
    operands = (qh, k_pool, v_pool)
    if quant:
        in_specs += [hbm, hbm]                                  # scales
        scratch += [pltpu.VMEM((rows_total, heads), jnp.float32)] * 2
        operands += (k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32))
    scratch.append(pltpu.SemaphoreType.DMA((len(operands) - 1,)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV // heads),
        in_specs=in_specs,
        out_specs=q_block,
        scratch_shapes=scratch,
    )
    # the K and V runs hold a slot's whole context: sublane-padded to a
    # bf16 tile they outgrow the 16 MiB default scoped limit near 1k rows
    run_bytes = 2 * rows_total * max(heads, 16) * hd * k_pool.dtype.itemsize
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, span, hd), q.dtype),
        compiler_params=(None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=min(max(2 * run_bytes, 32 << 20), 100 << 20))),
        interpret=interpret,
        name="ragged_paged_attention",
    )(block_table.astype(jnp.int32), q_lens.astype(jnp.int32),
      kv_lens.astype(jnp.int32), *operands)

    return out.reshape(B, KV, q_max, groups, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, q_max, H, hd)
