"""Pallas kernels of the paged KV pool: the decode read and the row write.

Reference: "Ragged Paged Attention" (PAPERS.md, arxiv 2604.15464), the
fused TPU kernel behind vLLM-on-TPU. ``models/llama_paged.py`` keeps the
pool (``[num_pages, page_size, KV, hd]`` a layer) and the block tables; its
XLA read gathers ``page_bucket × page_size`` rows through the table with
``jnp.take`` and attends them under a mask, so bytes and compute follow the
bucket's width. The two kernels here are what a decode step of the default
``kv_layout="paged"`` runs instead where the pool's geometry allows
(``llama_paged.paged_kv_read`` decides, from ``decode_supported``).

The read, ``paged_decode_attention`` (ISSUE 28): one query row a slot. One
grid step per slot walks the slot's LIVE pages in chunks, the next chunk's
page copies in flight while the current one is computed (since ISSUE 33
also across slots: under a slot's last chunk the next slot's first one is
in flight, so the copy pipeline drains once a launch, not once a slot),
with a running max / sum / accumulator in f32 (the flash kernels' online
softmax): bytes AND compute follow ``ceil(kv_len / page_size)`` pages, VMEM
holds two chunks whatever the context, and the kernel compiles in seconds
at any ``max_len``. Raggedness rides in scalar-prefetched block tables and
lengths, not in shapes. A page is read as ``[page_size * KV, head_dim]`` (a bitcast
of the pool: the rows of all KV heads interleaved, as they lie in HBM), ALL
query heads take one MXU product against the chunk and each keeps its own
KV head's columns under the mask, so every K and V element passes the MXU
once and nothing is loaded with a sublane stride. K, V and q enter the
products as stored (bf16 x bf16 is exact in the f32 accumulator), softmax
is f32, probabilities round to the model dtype before probs @ V: the
arithmetic the configuration states; only the summation order differs from
the gather's full-width softmax, so the two agree to rounding, not bitwise
(``tests/test_ragged_attention.py`` holds both to an f32 reference).
Measured on a v5e by ``tools/paged_decode_microbench.py`` (PERF.md, PR 33)
at the batch cell's geometry (48 slots, 128-page table, contexts 512-2048,
bf16): 0.354 / 0.365 ms a layer on two draws of contexts, 85 / 86 % of HBM
bandwidth over the live rows (0.393 / 0.407 ms, 77 %, while every slot
started its copies cold; 92 % where every context is whole chunks); at the
hybrid cell's (24 slots, 216-page table, 32 KV heads, contexts 1024-3456)
91 % (90 %). The fill-mode gather + masked attention took 4.30 ms at the
batch cell's geometry (PERF.md, PR 28).

The write beside the read (ISSUE 28): ``paged_kv_scatter`` puts a decode
step's fresh K/V rows (or a prefill's pages) into the pool with ONE launch
of HBM-to-HBM copies a layer, the pools aliased in place, where the
programs otherwise unroll two ``dynamic_update_slice``s a slot or page
(2304 a decode step at 48 slots x 24 layers). The bytes are the same; the
operations are not, and a decode step 5 times shorter made their count the
price of every traced run (PERF.md, PR 28). ``scatter_supported()`` is its
rule.

CPU/tier-1: both kernels run under ``interpret=True`` (same jnp ops, DMAs
emulated). On a TPU they are compiled by Mosaic; ``decode_supported()`` /
``scatter_supported()`` say which pools the compiler takes (pinned by
``tests/test_tpu_compile.py``). A quantized or GSPMD-sharded pool, and every
read of more than one query row a slot (speculative verify, a prefix-shared
suffix prefill), keep the XLA gather.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["paged_decode_attention", "paged_kv_scatter", "decode_supported",
           "scatter_supported"]

# index-map constant: with jax_enable_x64 a literal 0 traces as i64, which
# Mosaic cannot legalize in BlockSpec index maps (see ops/flash_attention)
_i0 = np.int32(0)


# flat rows ([row, kv-head] pairs) of one chunk of the decode body: the
# logits of all query heads against a chunk are [H, _DECODE_CHUNK_ROWS] f32
# (32 vregs at 16 heads). Measured on a v5e with the copies chained across
# slots (tools/paged_decode_microbench.py; PERF.md, PR 33), 512 / 1024 /
# 2048 / 4096 flat rows a chunk: 0.551 / 0.403 / 0.354 / 0.356 ms a layer at
# the batch cell's geometry, 2.06 / 1.48 / 1.29 / 1.29 ms at the hybrid
# cell's. A chunk costs the same scalar work whatever its size (a
# predicated start and wait a page copy), so fewer, larger chunks win until
# the half-empty last one and the logits' registers take it back: one size
# for both pools
_DECODE_CHUNK_ROWS = 2048


def decode_supported(head_dim: int, kv_heads: int, page_size: int,
                     kv_dtype: str | None = None) -> bool:
    """Can a pool be read by ``paged_decode_attention``? The rule is the
    compiler's (compiled for a described v5e; ``tests/
    test_tpu_compile.py`` holds a case on each side) and is the SAME on
    every backend, so that the CPU's tests take the read the chip takes:

      * an unquantized pool (the body carries no scale pools);
      * ``head_dim % 128 == 0``: a page is read as ``[page_size *
        kv_heads, head_dim]``, head_dim on the lanes; 64 is refused:
        "Slice shape along dimension 2 must be aligned to tiling (128),
        but is 64";
      * ``(page_size * kv_heads) % 8 == 0``: a page lands in the chunk
        buffer at a multiple of its own rows, which has to be whole
        sublane tiles; 4 rows of bf16 are refused: "Slice shape along
        dimension 1 must be aligned to tiling (8), but is 4".

    Any ``max_len`` compiles, in seconds (a 2048-page table was tried).
    ``models/llama_paged.paged_kv_read`` selects by this rule; a pool it
    refuses keeps the XLA gather."""
    return (kv_dtype is None and head_dim % 128 == 0
            and (page_size * kv_heads) % 8 == 0)


def _decode_body(bt_ref, qlen_ref, kvlen_ref, q_ref, kp_ref, vp_ref, o_ref,
                 kbuf, vbuf, sem, chain, *, page_size, kv_heads, groups,
                 chunk_pages, table_pages, scale):
    """One slot's decode row against its live pages; the slots in order.

    Scalar prefetch (SMEM): bt_ref [B, P], qlen_ref / kvlen_ref [B]. q_ref
    / o_ref block [1, H, hd] (head h = kv_head * groups + gi, the gather
    path's order). kp/vp_ref: the WHOLE pool in HBM viewed as [num_pages,
    page_size * KV, hd]: flat row f of a page is (row f // KV, kv-head
    f % KV). kbuf/vbuf [2, chunk_pages * page_size * KV, hd]: two chunks.
    chain (SMEM scratch, carried from slot to slot): [the buffer half the
    next chunk lands in, whether the slot before started this slot's
    first chunk].

    Per chunk: only the slot's live pages are copied; logits = q @ chunk^T
    for all H heads at once, [H, flat rows] f32; a head keeps the columns
    of its own KV head at rows < kv_len (``-1e30`` elsewhere, which
    underflows to an exact zero probability); running max / sum /
    accumulator in f32. Rows the copies did not write are stale VMEM:
    their probabilities are exact zeros, but 0 * NaN is NaN, so the V rows
    themselves are zeroed.

    The copies run one chunk ahead of the products ACROSS slots (ISSUE
    33): before a chunk is awaited, the slot's next chunk is started into
    the other half, and under a slot's LAST chunk, chunk 0 of slot b + 1.
    So the half of a chunk is the launch's running chunk count, not the
    slot's own. A slot without context (kv_len 0, or q_len 0: it reads
    nothing) has no chunks: it is handed none and starts none, and the
    slot after it starts cold, as the first slot does. Every started copy
    is awaited by the slot that computes it, and the last slot starts
    nothing it does not compute.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # every traced scalar is pinned i32: paddle_tpu enables jax_enable_x64,
    # under which a stray Python-int promotion to i64 breaks lowering
    i32 = jnp.int32
    ps, KV, CP = page_size, kv_heads, chunk_pages
    H = KV * groups
    page_rows = ps * KV                  # flat rows of one page
    flat = CP * page_rows                # flat rows of one chunk
    chunk_rows = CP * ps                 # context rows of one chunk
    b = pl.program_id(0)

    def context(slot):
        """Rows slot ``slot`` reads in this launch."""
        return jnp.where(qlen_ref[slot] > 0, kvlen_ref[slot], i32(0))

    kv_len = context(b)
    n_chunks = (kv_len + i32(chunk_rows - 1)) // i32(chunk_rows)
    last = pl.num_programs(0) - i32(1)
    nxt = jnp.minimum(b + i32(1), last)
    next_len = jnp.where(b < last, context(nxt), i32(0))

    @pl.when(b == 0)
    def _unchained():
        chain[0] = i32(0)
        chain[1] = i32(0)

    first_half, handed = chain[0], chain[1]

    def page_copies(slot, rows, c, half):
        """(is the page live, its K copy, its V copy) for chunk c of a
        slot that reads ``rows`` rows."""
        n_pages = (rows + i32(ps - 1)) // i32(ps)
        out = []
        for j in range(CP):
            pg = c * i32(CP) + i32(j)
            page = bt_ref[slot, jnp.minimum(pg, i32(table_pages - 1))]
            at = pl.ds(j * page_rows, page_rows)
            out.append((pg < n_pages,
                        pltpu.make_async_copy(kp_ref.at[page],
                                              kbuf.at[half, at],
                                              sem.at[half, i32(0)]),
                        pltpu.make_async_copy(vp_ref.at[page],
                                              vbuf.at[half, at],
                                              sem.at[half, i32(1)])))
        return out

    def start(slot, rows, c, half):
        for live, kc, vc in page_copies(slot, rows, c, half):
            @pl.when(live)
            def _go():
                kc.start()
                vc.start()

    def wait(slot, rows, c, half):
        for live, kc, vc in page_copies(slot, rows, c, half):
            @pl.when(live)
            def _done():
                kc.wait()
                vc.wait()

    @pl.when((n_chunks > 0) & (handed == 0))
    def _cold():
        start(b, kv_len, i32(0), first_half)

    # column f of a chunk's logits is (row f // KV, kv-head f % KV); query
    # head h reads kv-head h // groups. The same for every chunk
    col = jax.lax.broadcasted_iota(i32, (H, flat), 1)
    head = jax.lax.broadcasted_iota(i32, (H, flat), 0)
    if KV & (KV - 1) == 0:
        col_head, col_row = col & i32(KV - 1), col >> i32(KV.bit_length() - 1)
    else:
        col_head = jax.lax.rem(col, i32(KV))
        col_row = jax.lax.div(col, i32(KV))
    own_head = col_head == jax.lax.div(head, i32(groups))
    q = q_ref[0]                                               # [H, hd]

    def chunk(c, carry):
        m, l, acc = carry
        half = (first_half + c) & i32(1)
        # in flight under this chunk's products: the slot's next chunk,
        # or under its last one chunk 0 of the next slot (no page of it
        # is live where that slot has no context or there is none)
        more = c + i32(1) < n_chunks
        start(jnp.where(more, b, nxt), jnp.where(more, kv_len, next_len),
              jnp.where(more, c + i32(1), i32(0)), i32(1) - half)
        wait(b, kv_len, c, half)
        left = kv_len - c * i32(chunk_rows)     # live rows from this chunk on
        k, v = kbuf[half], vbuf[half]                          # [flat, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # [H, flat]
        s = jnp.where(own_head & (col_row < left), s, jnp.float32(-1e30))
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        stale = jax.lax.broadcasted_iota(i32, v.shape, 0) >= left * i32(KV)
        # probs round to the model dtype like the gather path's
        # softmax(...).astype(q.dtype)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), jnp.where(stale, jnp.zeros_like(v), v),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l, acc

    hd = q.shape[-1]
    _, l, acc = jax.lax.fori_loop(
        i32(0), n_chunks, chunk,
        (jnp.full((H, 1), -1e30, jnp.float32), jnp.zeros((H, 1), jnp.float32),
         jnp.zeros((H, hd), jnp.float32)))
    chain[0] = (first_half + n_chunks) & i32(1)
    chain[1] = ((n_chunks > 0) & (next_len > 0)).astype(i32)
    # a slot without context (no query this launch, or no rows) writes
    # zeros, never NaN residue
    out = jnp.where(kv_len > 0, acc / jnp.maximum(l, jnp.float32(1e-30)),
                    jnp.float32(0))
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pool, v_pool, block_table, q_lens, kv_lens,
                           *, interpret: bool):
    """One decode row a slot over a shared page pool: grid over slots,
    ``_decode_body``. The grid is ``"arbitrary"``: the slots run in order
    on one core, each handing the next its first chunk in flight, so the
    order is part of the kernel's contract on any chip (a v5e has one
    TensorCore; a chip with two would not split this grid).

    q           [B, 1, H, hd]: slot b's query at absolute position
                kv_lens[b] - 1.
    k/v_pool    [num_pages, page_size, KV, hd]: the paged KV pool, as
                ``decode_supported`` takes it.
    block_table [B, P] int32: logical -> physical page map per slot.
    q_lens      [B] int32: 0 skips the slot (zeros out), else 1.
    kv_lens     [B] int32: live context rows (attend rows < kv_lens[b]).

    Returns [B, 1, H, hd] in q.dtype. All raggedness is carried by the
    scalar-prefetched q_lens / kv_lens / block_table: the compiled program
    depends only on (B, P, page_size, KV, H, hd, dtype).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, _, H, hd = q.shape
    num_pages, ps, KV, _ = k_pool.shape
    page_rows = ps * KV
    # at most 32 pages a chunk: the body unrolls over them
    chunk_pages = min(max(_DECODE_CHUNK_ROWS // page_rows, 1), 32)
    flat = chunk_pages * page_rows
    kernel = functools.partial(
        _decode_body, page_size=ps, kv_heads=KV, groups=H // KV,
        chunk_pages=chunk_pages, table_pages=block_table.shape[1],
        scale=np.float32(1.0) / np.sqrt(np.float32(hd)))
    q_block = pl.BlockSpec((1, H, hd), lambda b, *_: (b, _i0, _i0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B,),
        in_specs=[q_block, hbm, hbm], out_specs=q_block,
        scratch_shapes=[pltpu.VMEM((2, flat, hd), k_pool.dtype),
                        pltpu.VMEM((2, flat, hd), v_pool.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((2,), jnp.int32)])
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        compiler_params=(None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))),
        interpret=interpret,
        name="paged_decode_attention",
    )(block_table.astype(jnp.int32), q_lens.astype(jnp.int32),
      kv_lens.astype(jnp.int32), q.reshape(B, H, hd),
      # a page is contiguous in HBM: the pool's rows and KV heads merge
      # into one dim for free (a bitcast, also under the TPU's tiling)
      k_pool.reshape(num_pages, page_rows, hd),
      v_pool.reshape(num_pages, page_rows, hd))
    return out.reshape(B, 1, H, hd)


def scatter_supported(head_dim: int, kv_heads: int, page_size: int,
                      kv_dtype: str | None = None) -> bool:
    """Can ``paged_kv_scatter`` write this pool? What ``decode_supported``
    asks, and whole sublane tiles a ROW: ``kv_heads % 8 == 0`` (a decode
    step writes one row of a page, ``kv_heads`` flat rows)."""
    return (decode_supported(head_dim, kv_heads, page_size, kv_dtype)
            and kv_heads % 8 == 0)


def _scatter_body(page_ref, row_ref, ks_ref, vs_ref, kp_in, vp_in, kp_ref,
                  vp_ref, sem, *, n, rows, kv_heads):
    """Copy item i's ``rows`` rows of K and V into page ``page_ref[i]`` from
    row ``row_ref[i]`` on: 2n HBM-to-HBM copies, all in flight at once.
    kp_in / vp_in are the pools' input names; kp_ref / vp_ref alias them."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del kp_in, vp_in
    i32 = jnp.int32
    width = rows * kv_heads

    def copies(i):
        at = pl.ds(pl.multiple_of(row_ref[i] * i32(kv_heads), kv_heads),
                   width)
        return (pltpu.make_async_copy(ks_ref.at[i],
                                      kp_ref.at[page_ref[i], at],
                                      sem.at[i32(0)]),
                pltpu.make_async_copy(vs_ref.at[i],
                                      vp_ref.at[page_ref[i], at],
                                      sem.at[i32(1)]))

    def start(i, _):
        for copy in copies(i):
            copy.start()
        return 0

    def wait(i, _):
        for copy in copies(i):
            copy.wait()
        return 0

    jax.lax.fori_loop(i32(0), i32(n), start, 0)
    jax.lax.fori_loop(i32(0), i32(n), wait, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_kv_scatter(k_pool, v_pool, k_rows, v_rows, pages, rows, *,
                     interpret: bool):
    """Write rows into pages of the pool, in place: ONE launch for K and V.

    k/v_pool [num_pages, page_size, KV, hd]; k/v_rows [n, r, KV, hd]: item
    i's r consecutive rows land in page ``pages[i]`` from row ``rows[i]``
    on (a decode step: n = slots, r = 1; a prefill: n = the prompt's
    pages, r = page_size, rows 0). Returns the two pools, which alias the
    inputs (donate them). It replaces 2n ``dynamic_update_slice``s a layer:
    the same bytes, but one operation in the program, its lowering and a
    device trace instead of 96 (the batch cell: 2304 a decode step)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    num_pages, ps, KV, hd = k_pool.shape
    n, r = k_rows.shape[:2]
    kernel = functools.partial(_scatter_body, n=n, rows=r, kv_heads=KV)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    flat = (num_pages, ps * KV, hd)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(1,),
        in_specs=[hbm, hbm, hbm, hbm], out_specs=[hbm, hbm],
        scratch_shapes=[pltpu.SemaphoreType.DMA((2,))])
    kp, vp = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(flat, k_pool.dtype),
                   jax.ShapeDtypeStruct(flat, v_pool.dtype)],
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
        name="paged_kv_scatter",
    )(pages.astype(jnp.int32), rows.astype(jnp.int32),
      k_rows.reshape(n, r * KV, hd), v_rows.reshape(n, r * KV, hd),
      k_pool.reshape(flat), v_pool.reshape(flat))
    return kp.reshape(k_pool.shape), vp.reshape(v_pool.shape)
