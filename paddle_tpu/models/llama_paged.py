"""Paged KV-cache decode for the llama family (Ragged Paged Attention,
PAPERS.md arxiv 2604.15464, expressed at the XLA level).

The dense slot cache (llama_decode.init_kv_cache) sizes HBM at
``max_batch × max_len`` and every decode step streams ALL ``max_len`` rows
of every slot through the attention einsum under a validity mask — both
footprint and bandwidth are paid at worst case. Here the cache is a shared
POOL of fixed-size pages with per-slot block tables:

  * pool      — per-layer ``[num_pages, page_size, KV, hd]`` buffers (one
    buffer per layer, same in-place-update discipline as the dense cache:
    see init_kv_cache's measured rationale);
  * block table — ``[B, P]`` int32, logical page j of slot b lives in
    physical page ``block_table[b, j]``. The host allocates pages on admit
    and frees them on retire, so HBM scales with LIVE tokens and the pool,
    not ``max_batch``, bounds admission.
  * decode attention gathers K/V through the block table and computes over
    ``P × page_size`` rows, where P is the page-count BUCKET of the longest
    active context — bandwidth scales with actual context length, which is
    the decode budget (the GQA-einsum note in llama_decode applies: at
    decode the cache read IS the bandwidth). P is static per executable;
    bucketing P (same trick as prompt buckets) keeps the inventory at
    O(prompt buckets + page buckets), independent of request mix.

Physical page 0 is a SCRATCH page by convention (the serving allocator
never hands it out): freed/idle slots point every block-table entry at it,
so their frozen in-flight writes land in scratch instead of a page another
request owns. Scratch rows are never read unmasked.

Numerics match the dense path exactly: gathered rows sit at the same
logical positions, the validity mask keeps the same prefix, and masked
lanes underflow to exact zeros — so greedy outputs are token-identical to
the dense slot cache (pinned by tests/test_serving_paged.py).

Sharding note (GSPMD, arxiv 2105.04663): the pool keeps KV-heads as a
leading-free trailing axis exactly like the dense cache, so a
``NamedSharding(mesh, P(None, None, "model", None))`` shards pages across
model-parallel chips unchanged; the block table is replicated host
metadata (``parallel/sharding.py:shard_kv_pool`` applies it; the serving
engine reads ``PADDLE_SERVE_MESH_MODEL``).

Which read a decode step takes (ISSUE 28): the ``kv_read`` scope of
``_paged_decode_step_slots`` is either the kernel
(``ops/ragged_attention.paged_decode_attention``: live pages only) or the
XLA gather + masked attention over the page bucket.
``llama_paged_decode_burst`` takes what ``paged_kv_read`` says for the pool
it is handed — the kernel
for an unquantized pool with ``head_dim % 128 == 0`` on one device, the
gather for everything else (int8/fp8 pages, head_dim 64, the tiny
configurations of tier-1, a GSPMD-sharded pool) — the same choice on every
backend, so the CPU's tests run the read the chip runs. The bucket still
sets the block table's width; raggedness inside it rides in ``pos``. Where
the read is the kernel's and what is written is whole sublane tiles
(``_kernel_write``: a row of KV heads in eights; at 4, a page), the fresh
rows of a decode step and the pages of a bucketed prefill go into the pool
through ``paged_kv_scatter``, one launch a layer, instead of the per-slot /
per-page ``dynamic_update_slice`` loop.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .linear_mixer import mixer_prefill, mixer_step
from .llama import LlamaConfig, _rmsnorm, _rope, attn_qkv, block_in, \
    block_out, heads_at_once_leaves, layer_params_at, lm_head_logits, \
    split_layer_params
from ..ops.moe_dropless import moe_dropless, route
from ..ops.ragged_attention import _DECODE_CHUNK_ROWS, decode_supported, \
    paged_decode_attention, paged_kv_scatter, scatter_supported
from .llama_decode import _cached_attention_slots, _mlp, _qkv, _sample

__all__ = ["init_paged_kv_cache", "paged_kv_read", "pool_kv_heads",
           "llama_paged_prefill_slot",
           "llama_paged_prefill_suffix", "llama_paged_decode_burst",
           "llama_paged_verify", "per_layer_weights", "burst_for_layouts",
           "ring_page_rows",
           "paged_kv_bytes_per_token", "page_bytes",
           "gather_pages", "scatter_pages", "copy_pages"]


# ------------------------------------------------- quantized pages (ISSUE 10)
# kv_dtype = "int8" | "fp8" stores pages through the paddle_tpu.quant block
# codecs: the payload pools keep the [num_pages, page_size, KV, hd] layout
# in the wire dtype and a per-(row, kv-head) float32 scale rides in
# parallel [num_pages, page_size, KV] pools (block = the head_dim vector).
# Writes quantize (prefill rows and per-step decode rows alike); the reads
# dequantize right after their jnp.take (a quantized pool never takes the
# decode kernel: paged_kv_read). kv_dtype=None is byte-for-byte the
# pre-quant code: no scale pools exist and no branch below runs.


def _kv_encode(rows, kv_dtype: str):
    """rows [..., KV, hd] float -> (payload wire dtype, scale [..., KV])."""
    from ..quant.codec import quantize_lastdim
    return quantize_lastdim(rows, kv_dtype)


def _kv_decode(payload, scale, out_dtype):
    from ..quant.codec import dequantize_lastdim
    return dequantize_lastdim(payload, scale, out_dtype)


def pool_kv_heads(config: LlamaConfig, kv_dtype: str | None = None,
                  mesh=None) -> int:
    """KV heads a row of this model's pool holds: the ONE place the pool's
    geometry is decided (``init_paged_kv_cache``, ``page_bytes`` and
    ``paged_kv_read`` ask here; the programs read it off the cache's
    shape). The model's own, or, where only their number keeps the decode
    kernel from reading the pool well, the next multiple of 8 (30 -> 32).
    The kernel reads a page as ``[page_size * KV, head_dim]``; a KV count
    that is no whole sublane tile makes that view of the tiled 4-D pool a
    COPY of the whole pool, twice a layer and call (compiled for a
    described v5e at 30 heads: 8 copies of 0.58 GB in one burst program,
    which then does not fit), and keeps ``paged_kv_scatter`` off. The
    padded heads hold zeros, cost 1/15 of the pool at 30 heads, and are
    never read back: q is padded with zero heads to match and the output
    sliced. Only ``llama_paged_prefill_slot`` and
    ``llama_paged_decode_burst`` know a padded pool; the engine refuses
    its other readers for such a model (``ContinuousBatcher``)."""
    kv = int(config.num_key_value_heads)
    if (kv > 8 and kv % 8 and kv_dtype is None and mesh is None
            and config.head_dim % 128 == 0):
        return -(-kv // 8) * 8
    return kv


def _pad_heads(rows, heads: int):
    """rows [..., KV, hd] -> [..., heads, hd], zeros in the added heads."""
    extra = heads - rows.shape[-2]
    if not extra:
        return rows
    return jnp.pad(rows, ((0, 0),) * (rows.ndim - 2) + ((0, extra), (0, 0)))


def init_paged_kv_cache(config: LlamaConfig, num_pages: int, page_size: int,
                        kv_dtype: str | None = None, max_batch: int = 0,
                        mesh=None):
    """Shared page pool: PER-LAYER tuples of [num_pages, page_size, KV, hd],
    one entry for every layer that holds K/V rows (``num_kv_layers``: all
    of them without a layer pattern). A spec with LINEAR layers adds, for
    each of those, ``state`` [max_batch, Hv, dv, dk] (``state_dtype``) and
    ``conv`` [max_batch, K - 1, conv_dim] under the same names: per SLOT,
    not per page, written whole by a slot's prefill and updated in place by
    every decode step. A pool row holds ``pool_kv_heads`` heads (``mesh``:
    the serving mesh a caller will shard the pool over, if any).

    Per-layer buffers for the same reason as the dense cache
    (llama_decode.init_kv_cache): XLA only updates a carried/donated leaf
    in place when it is a whole buffer. Page 0 is scratch (see module
    docstring) — the usable pool is ``num_pages - 1`` pages.

    ``kv_dtype`` (ISSUE 10): "int8"/"fp8" store the pools in the wire
    dtype and add per-(row, head) f32 scale pools under "k_scale" /
    "v_scale" — the page id indexes payload and scale together, so the
    host allocator/block tables stay layout-agnostic.
    """
    c = config
    shape = (int(num_pages), int(page_size),
             pool_kv_heads(c, kv_dtype, mesh), c.head_dim)
    n_kv = c.num_kv_layers
    if kv_dtype is None:
        cache = {"k": tuple(jnp.zeros(shape, c.dtype) for _ in range(n_kv)),
                 "v": tuple(jnp.zeros(shape, c.dtype) for _ in range(n_kv))}
        if c.is_recurrent:
            for name, (shp, dt) in c.state_shapes(int(max_batch)).items():
                cache[name] = tuple(jnp.zeros(shp, dt)
                                    for _ in range(c.num_linear_layers))
        if c.has_ring:
            for name, (shp, dt) in c.ring_shapes(int(max_batch)).items():
                cache[name] = tuple(jnp.zeros(shp, dt)
                                    for _ in range(c.num_sliding_layers))
        if c.num_sparse_layers:
            cache["moe_counts"] = (jnp.zeros((2, c.held[1] + 1), jnp.int32),)
        return cache
    if c.is_recurrent or c.has_ring:
        raise ValueError(f"quantized KV pages beside {c.slot_state} are "
                         "not supported")
    from ..quant.codec import SCALE_DTYPE, wire_dtype
    wire = wire_dtype(kv_dtype)
    sshape = shape[:-1]
    return {
        "k": tuple(jnp.zeros(shape, wire) for _ in range(n_kv)),
        "v": tuple(jnp.zeros(shape, wire) for _ in range(n_kv)),
        "k_scale": tuple(jnp.zeros(sshape, SCALE_DTYPE)
                         for _ in range(n_kv)),
        "v_scale": tuple(jnp.zeros(sshape, SCALE_DTYPE)
                         for _ in range(n_kv)),
    }


def gather_pages(cache, page_ids) -> dict:
    """Host copies of the pool slices at ``page_ids`` — the EXPORT read of
    the disaggregated page transfer (ISSUE 11). Returns {leaf name: [one
    numpy array of shape [n_pages, ...] per layer]} covering every leaf
    the pool has (payload pools always, scale pools when quantized). The
    slices are taken in logical order, so index j of each array is logical
    page j of the request — physical page ids never leave the process.
    ONE device_get covers the whole structure (the slices dispatch async,
    then a single batched readback) — an export runs on the serve-loop
    thread between bursts, and per-leaf round trips would stretch the
    prefill replica's inter-burst gap by 4·L sync latencies."""
    import numpy as np
    ids = jnp.asarray(np.asarray(page_ids, np.int32))
    return jax.device_get({name: [buf[ids] for buf in bufs]
                           for name, bufs in cache.items()})


def scatter_pages(cache, page_ids, rows: dict) -> dict:
    """Write transferred page rows into the pool at ``page_ids`` — the
    INSTALL write of the disaggregated page transfer (inverse of
    :func:`gather_pages`). ``rows`` maps leaf names to per-layer arrays of
    shape [n_pages, ...]; leaves absent from ``rows`` keep their buffers
    (a full-precision install never touches scale pools). Values are cast
    to each buffer's dtype, so callers hand pool-format arrays (payload in
    the wire dtype, scales f32) or full-precision rows for an unquantized
    pool. Runs OUTSIDE jit (one ``.at[].set`` per layer per leaf) — an
    install is a once-per-request event, not a per-step one."""
    import numpy as np
    ids = jnp.asarray(np.asarray(page_ids, np.int32))
    out = {}
    for name, bufs in cache.items():
        if name not in rows:
            out[name] = bufs
            continue
        if len(rows[name]) != len(bufs):
            raise ValueError(
                f"scatter_pages: {name} carries {len(rows[name])} layers, "
                f"pool has {len(bufs)}")
        out[name] = tuple(
            buf.at[ids].set(jnp.asarray(r).astype(buf.dtype))
            for buf, r in zip(bufs, rows[name]))
    return out


def copy_pages(cache, src_ids, dst_ids):
    """Copy whole pool pages ``src_ids[i] -> dst_ids[i]`` across every
    leaf (payload pools always, scale pools when quantized) — the
    COPY-ON-WRITE primitive of prefix sharing (ISSUE 13): before a burst
    writes into a page other block tables still map, the scheduler copies
    it into a freshly allocated private page and redirects only the
    writer. Runs OUTSIDE jit (one ``.at[].set`` per layer per leaf, like
    :func:`scatter_pages`): a COW is a once-per-shared-tail event, not a
    per-step one."""
    import numpy as np
    s = jnp.asarray(np.asarray(src_ids, np.int32))
    d = jnp.asarray(np.asarray(dst_ids, np.int32))
    return {name: tuple(buf.at[d].set(buf[s]) for buf in bufs)
            for name, bufs in cache.items()}


def _kv_row_head_bytes(config: LlamaConfig, kv_dtype: str | None) -> int:
    """Bytes ONE (row, kv-head) K-or-V block occupies: head_dim payload
    elements plus, quantized, its f32 block scale."""
    if kv_dtype is None:
        return int(config.head_dim) * jnp.dtype(config.dtype).itemsize
    from ..quant.codec import scale_itemsize, wire_itemsize
    return int(config.head_dim) * wire_itemsize(kv_dtype) + scale_itemsize()


def page_bytes(config: LlamaConfig, page_size: int,
               kv_dtype: str | None = None, mesh=None) -> int:
    """HBM bytes one PAGE ID costs (K+V across all layers that hold K/V
    rows, scales included) — the unit the pool budget is spent in. The serving
    engine's ``pool_hbm_bytes=`` sizing divides by this, which is how an
    int8/fp8 pool admits ~2× the live tokens of a bf16 pool at the same
    budget (pinned by tests/test_quant.py)."""
    c = config
    return int(2 * c.num_kv_layers * int(page_size)
               * pool_kv_heads(c, kv_dtype, mesh)
               * _kv_row_head_bytes(c, kv_dtype))


def paged_kv_bytes_per_token(config: LlamaConfig, pages: int,
                             page_size: int,
                             live_tokens: int | None = None,
                             kv_dtype: str | None = None) -> int:
    """Decode-attention K+V bytes read per emitted token per slot.

    Gather path: the read is `pages` (the page-count BUCKET of the widest
    active context) × page_size rows — pass the bucket width (dense reads
    the same expression with pages*page_size == max_len, always).

    Kernel read: the per-page copies stop at the slot's LIVE pages, so
    bytes follow the live context, not the bucket — pass ``live_tokens``
    and `pages` is ignored in favor of ``ceil(live_tokens / page_size)``.

    ``kv_dtype`` (ISSUE 10): quantized pages bill wire-dtype payload plus
    the per-(row, head) scale reads — roughly half the bf16 bill."""
    c = config
    if live_tokens is not None:
        live_tokens = int(live_tokens)
        pages = 0 if live_tokens <= 0 \
            else (live_tokens - 1) // int(page_size) + 1
    return int(2 * c.num_kv_layers * pages * page_size
               * c.num_key_value_heads * _kv_row_head_bytes(c, kv_dtype))


def paged_kv_read(config: LlamaConfig, page_size: int,
                  kv_dtype: str | None = None, mesh=None) -> str:
    """Which read ``llama_paged_decode_burst`` takes for a pool: "kernel"
    (the decode body of ``ops/ragged_attention.py``: live pages only) or
    "gather" (``jnp.take`` over the page bucket + masked attention). Decided
    by the pool's geometry alone (``ragged_attention.decode_supported``),
    the same on every backend; a GSPMD-sharded pool (``mesh``) keeps the
    gather, which XLA partitions by itself."""
    if mesh is None and decode_supported(
            config.head_dim, pool_kv_heads(config, kv_dtype, mesh),
            int(page_size), kv_dtype):
        return "kernel"
    return "gather"


def _kernel_write(config: LlamaConfig, page_size: int, kv_dtype, kv_read,
                  mesh, rows: int = 1) -> bool:
    """Do a program's K/V rows go into the pool through ONE
    ``paged_kv_scatter`` launch a layer instead of two
    ``dynamic_update_slice``s a slot (or page)? Where the read is the
    kernel's (``kv_read`` None = ``paged_kv_read``'s choice) and ``rows``
    rows of the pool (1: a decode step's; ``page_size``: a prefill's pages)
    are whole sublane tiles (``scatter_supported``), on one device. The
    same bytes either way; the loop is what the gather's pools keep."""
    if kv_read is None:
        kv_read = paged_kv_read(config, page_size, kv_dtype, mesh)
    return (kv_read == "kernel" and mesh is None and scatter_supported(
        config.head_dim, pool_kv_heads(config, kv_dtype, mesh),
        int(page_size), kv_dtype, rows))


def _sparse(lp, config: LlamaConfig) -> bool:
    return config.mlp_layer_types is not None and "gate_w" in lp


def _route_early(x, lp, config: LlamaConfig):
    """A SPARSE layer's selection where the spec's router reads the
    attention's input (``router_input`` "attention"): made from the float32
    norm(x; ln1) of the stream x [B, T, D] before attention (scope
    ``moe_router``) and carried across it to ``_ffn``. None otherwise."""
    c = config
    if c.router_input != "attention" or not _sparse(lp, c):
        return None
    u = block_in(x.astype(jnp.float32), lp["ln1"], c)
    with jax.named_scope("moe_router"):
        return route(u.reshape(-1, u.shape[-1]), lp["gate_w"],
                     lp.get("gate_bias"), c)


def _ffn(x, lp, config: LlamaConfig, valid, interpret, stacked, layer: int,
         routed=None):
    """The FFN of layer ``layer`` on the stream x [B, T, D], by its kind:
    the SwiGLU FFN (``_mlp``), or for a SPARSE layer of ``mlp_layer_types``
    the dropless expert layer (``valid`` [B, T] bool: the tokens that are
    real; ``stacked``: the layer-stacked tree ``lp`` was taken from, whose
    expert weights the grouped products read in place; ``routed``: the
    selection ``_route_early`` made before attention, if the spec's router
    reads there). Returns (x, the layer's count of assignments [held + 1];
    0 for a dense FFN)."""
    c = config
    if not _sparse(lp, c):
        return _mlp(x, lp, c), 0
    g = block_in(x.astype(jnp.float32), lp["ln2"], c)
    lp = {**lp, **{k: stacked[k] for k in
                   ("moe_w_gate", "moe_w_up", "moe_w_down")}}
    y, counts = moe_dropless(
        g.reshape(-1, g.shape[-1]), lp, c, c.ffn_index(layer)[1],
        valid.reshape(-1), interpret, routed)
    return x + block_out(y.reshape(x.shape), lp["ln2"], c), counts


def ring_page_rows(window: int, kv_heads: int) -> int:
    """Rows of one page of a ring as the decode read walks it: the ring of
    a slot [window, KV, hd] is read as window / rows pages (a reshape of
    its leading dims: free), the most rows, a multiple of 8 dividing the
    window, whose ``rows * KV`` flat rows fit one chunk of the read
    (``_DECODE_CHUNK_ROWS``): 128 at window 128 and 8 KV heads (the ring
    is one page, as before), 512 at window 4096 and 4. So the read copies
    only a slot's live pages, chunk by chunk, in VMEM of two chunks
    whatever the window; a whole 4096-row ring as one page would be two
    buffers of 4 MiB each for K and V. The window itself where no such
    rows exist."""
    fits = [r for r in range(8, window + 1, 8)
            if window % r == 0 and r * kv_heads <= _DECODE_CHUNK_ROWS]
    return fits[-1] if fits else window


def _ring_step(q, k, v, ring_k, ring_v, pos32, frozen, config: LlamaConfig,
               interpret):
    """One token of every slot in a SLIDING layer: its K/V row goes into row
    ``pos % window`` of the slot's ring (scope ``kv_write``), and q attends
    the ring's live rows (scope ``win_read``): rows <= pos until the ring
    has wrapped, all of them after, which are the positions (pos - window,
    pos] in some order, and softmax knows no order. A ring is a page pool
    of window / ``ring_page_rows`` pages a slot under a fixed table, so
    where the pool kernels take that geometry (``decode_supported`` /
    ``scatter_supported``) they read and write it, the read walking only
    the live pages (``min(pos + 1, window)`` rows) and no page of a frozen
    slot (``frozen`` [B] bool); otherwise XLA does (a row scatter, masked
    attention). q [B, 1, H, hd]; k, v [B, KV, hd]. Returns (att, ring_k,
    ring_v)."""
    c = config
    B, W, KV, hd = ring_k.shape
    row = pos32 % jnp.int32(W)
    slots = jnp.arange(B, dtype=jnp.int32)
    with jax.named_scope("kv_write"):
        if scatter_supported(hd, KV, W):
            ring_k, ring_v = paged_kv_scatter(
                ring_k, ring_v, k[:, None], v[:, None], slots, row,
                interpret=interpret)
        else:
            ring_k = ring_k.at[slots, row].set(k, unique_indices=True)
            ring_v = ring_v.at[slots, row].set(v, unique_indices=True)
    rp = ring_page_rows(W, KV)
    with jax.named_scope("win_read"):
        if decode_supported(hd, KV, rp):
            n = W // rp
            table = slots[:, None] * jnp.int32(n) \
                + jnp.arange(n, dtype=jnp.int32)[None, :]
            att = paged_decode_attention(
                q, ring_k.reshape(B * n, rp, KV, hd),
                ring_v.reshape(B * n, rp, KV, hd), table,
                (~frozen).astype(jnp.int32),
                jnp.minimum(pos32 + 1, jnp.int32(W)), interpret=interpret)
        else:
            att = _cached_attention_slots(q, ring_k, ring_v, pos32, c)
    return att, ring_k, ring_v


def _ring_of_prompt(rows, tlen, window: int):
    """The ring [window, KV, hd] a prompt of ``tlen`` real tokens leaves:
    row r holds the last position p < tlen with p % window == r (rows no
    position has reached yet hold a copy of another row, which no read sees
    before a decode step has written them). rows [T, KV, hd]."""
    r = jnp.arange(window, dtype=jnp.int32)
    last = tlen.astype(jnp.int32) - 1
    p = last - (last - r) % jnp.int32(window)
    return jnp.take(rows, jnp.clip(p, 0, rows.shape[0] - 1), axis=0)


def _paged_decode_step_slots(params, cache, block_table, pos, tok,
                             config: LlamaConfig, kv_dtype: str | None = None,
                             kv_read: str | None = None,
                             interpret: bool | None = None, mesh=None,
                             done=None):
    """One single-token step over all slots, K/V through the block table.

    block_table [B, P] int32; pos/tok [B]. Slot b writes this token's K/V
    into physical page ``block_table[b, pos[b] // page_size]`` at row
    ``pos[b] % page_size`` and attends rows ``<= pos[b]`` of its pages.
    Layers unrolled, per-layer pool buffers, written in place: per-lane
    dynamic_update_slice (the measured discipline of
    llama_decode_step_slots), or, where the read is the kernel's and the
    pool allows (``_kernel_write``), one ``paged_kv_scatter`` launch a
    layer: the same rows, 1 operation for 96 at 48 slots.

    ``kv_read`` (static; None = what ``paged_kv_read`` says for this
    pool): "kernel" reads through ``paged_decode_attention`` — each slot's
    ceil((pos+1)/page_size) live pages, chunk by chunk (``interpret`` None
    = off the TPU; never a quantized or sharded pool: ``paged_kv_read``
    says "gather" for both). "gather"
    gathers the [P*page_size] rows of the whole bucket and attends them
    under the same ``row <= pos`` mask as the dense path, dequantizing
    payload×scale right after the takes. The takes clip: the table is in
    bounds by construction (unused entries hold SCRATCH_PAGE), and
    ``jnp.take``'s default fill mode would write a second copy of the
    gathered rows through a select (53 % of the batch cell's device time
    before ISSUE 28).

    ``kv_dtype``: writes quantize the fresh K/V row (payload + per-head
    scale land together).

    Device-side names (``jax.named_scope``, read by the trace reducers):
    per layer ``kv_write`` (the per-slot row writes), ``kv_read`` (the
    kernel, or the gather with its dequantize, reshape and masked
    attention), ``attn_out``, ``mlp``; ``head_sample`` the logits (and,
    in the bursts, the sampling). One scope a layer and phase, none inside
    the per-slot loop, whose 48 x 24 bodies are traced in every run.
    """
    c = config
    layer_p, other = split_layer_params(params)
    B = tok.shape[0]
    ps = int(cache["k"][0].shape[1])
    pool_heads = int(cache["k"][0].shape[2])    # the model's, or padded
    if kv_read is None:
        kv_read = paged_kv_read(c, ps, kv_dtype, mesh)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    kernel_write = _kernel_write(c, ps, kv_dtype, kv_read, mesh)
    q_heads = pool_heads * (c.num_attention_heads // c.num_key_value_heads)
    x = jnp.take(other["embed_tokens"], tok[:, None], axis=0).astype(c.dtype)
    positions = pos[:, None].astype(jnp.int32)
    pos32 = pos.astype(jnp.int32)
    page_of = pos32 // jnp.int32(ps)     # [B] logical page of the write
    row_of = pos32 % jnp.int32(ps)       # [B] row within that page
    wpage = jnp.take_along_axis(block_table, page_of[:, None], axis=1)[:, 0]
    z = jnp.int32(0)
    one = jnp.ones((B,), jnp.int32)

    quant = kv_dtype is not None
    ks, vs = list(cache["k"]), list(cache["v"])
    kss = list(cache["k_scale"]) if quant else None
    vss = list(cache["v_scale"]) if quant else None
    states, tails = list(cache.get("state", ())), list(cache.get("conv", ()))
    rk, rv = list(cache.get("win_k", ())), list(cache.get("win_v", ()))
    moe = cache.get("moe_counts")       # (burst, prefill) x assignments
    routed = 0
    frozen = jnp.zeros((B,), bool) if done is None else done
    real = ~frozen[:, None]
    for layer in range(c.num_hidden_layers):
        lp = layer_params_at(layer_p, c, layer)
        kind, l = c.kind_index(layer)   # l: its place among its kind
        h = block_in(x, lp["ln1"], c)
        with jax.named_scope("mlp"):
            early = _route_early(x, lp, c)
        if kind == c.LINEAR:
            mix, states[l], tails[l] = mixer_step(
                h[:, 0], lp, c, states[l], tails[l], frozen, interpret)
            y = x + block_out(mix[:, None], lp["ln1"], c)
            with jax.named_scope("mlp"):
                x, n = _ffn(y, lp, c, real, interpret, layer_p, layer, early)
            routed = routed + n
            continue
        q, k, v = attn_qkv(h, lp, c, positions, kind)
        if kind == c.SLIDING:
            att, rk[l], rv[l] = _ring_step(q, k[:, 0], v[:, 0], rk[l], rv[l],
                                           pos32, frozen, c, interpret)
        else:
            kp, vp = ks[l], vs[l]
            ku, vu = _pad_heads(k[:, 0], pool_heads), _pad_heads(v[:, 0],
                                                                 pool_heads)
            ksp = vsp = None
            if quant:
                ku, ksr = _kv_encode(ku, kv_dtype)   # [B, KV, hd] + [B, KV]
                vu, vsr = _kv_encode(vu, kv_dtype)
                ksp, vsp = kss[l], vss[l]
            with jax.named_scope("kv_write"):
                if kernel_write:
                    kp, vp = paged_kv_scatter(kp, vp, ku[:, None], vu[:, None],
                                              wpage, row_of, interpret=interpret)
                else:
                    for b in range(B):
                        at = (wpage[b], row_of[b], z, z)
                        kp = jax.lax.dynamic_update_slice(
                            kp, ku[b][None, None], at)
                        vp = jax.lax.dynamic_update_slice(
                            vp, vu[b][None, None], at)
                        if quant:
                            ats = (wpage[b], row_of[b], z)
                            ksp = jax.lax.dynamic_update_slice(
                                ksp, ksr[b][None, None], ats)
                            vsp = jax.lax.dynamic_update_slice(
                                vsp, vsr[b][None, None], ats)
            ks[l], vs[l] = kp, vp
            if quant:
                kss[l], vss[l] = ksp, vsp
            with jax.named_scope("kv_read"):
                if kv_read == "kernel":
                    att = paged_decode_attention(
                        _pad_heads(q, q_heads), kp, vp, block_table, one,
                        pos32 + 1, interpret=interpret)
                    att = att[:, :, :c.num_attention_heads]
                else:
                    # gather the slot's pages into a [B, P*ps, KV, hd] view:
                    # the read whose bytes scale with the page bucket
                    kc = _take_pages(kp, block_table)
                    vc = _take_pages(vp, block_table)
                    if quant:
                        kc = _kv_decode(kc, _take_pages(ksp, block_table),
                                        c.dtype)
                        vc = _kv_decode(vc, _take_pages(vsp, block_table),
                                        c.dtype)
                    kc = kc.reshape(B, -1, pool_heads, c.head_dim)
                    vc = vc.reshape(B, -1, pool_heads, c.head_dim)
                    att = _cached_attention_slots(
                        q, kc[:, :, :c.num_key_value_heads],
                        vc[:, :, :c.num_key_value_heads], pos, c)
        with jax.named_scope("attn_out"):
            y = x + block_out(att.reshape(B, 1, -1) @ lp["wo"], lp["ln1"], c)
        with jax.named_scope("mlp"):
            x, n = _ffn(y, lp, c, real, interpret, layer_p, layer, early)
        routed = routed + n

    out = {"k": tuple(ks), "v": tuple(vs)}
    if quant:
        out["k_scale"], out["v_scale"] = tuple(kss), tuple(vss)
    if states:
        out["state"], out["conv"] = tuple(states), tuple(tails)
    if rk:
        out["win_k"], out["win_v"] = tuple(rk), tuple(rv)
    if moe is not None:
        out["moe_counts"] = (moe[0].at[0].add(routed),)
    with jax.named_scope("head_sample"):
        logits = lm_head_logits(x[:, 0, :], other, c)
    return logits, out


def _one_kind_pool(cache, config: LlamaConfig, what: str) -> None:
    """``what`` knows one kind of layer and a pool of the model's own
    geometry: no layer pattern, no padded KV heads (``pool_kv_heads``)."""
    config.require_uniform(what)
    if int(cache["k"][0].shape[2]) != config.num_key_value_heads:
        raise NotImplementedError(
            f"{what} does not know a pool with padded KV heads "
            f"({cache['k'][0].shape[2]} for the model's "
            f"{config.num_key_value_heads}: pool_kv_heads)")


def _take_pages(pool, table):
    """``pool[table]`` over the page dim, clipping: no fill select over
    the gathered shape (``jnp.take``'s default ``mode="fill"`` adds one)."""
    return jnp.take(pool, table, axis=0, mode="clip")


@functools.partial(jax.jit, static_argnames=(
    "config", "temperature", "top_k", "dequant", "kv_dtype", "kv_read",
    "interpret", "mesh"), donate_argnums=(1,))
@jax.named_scope("prefill")
def llama_paged_prefill_slot(params, cache, tokens, page_ids, tlen, key,
                             config: LlamaConfig,
                             temperature: float = 0.0, top_k: int = 0,
                             dequant=None, kv_dtype: str | None = None,
                             kv_read: str | None = None,
                             interpret: bool | None = None, mesh=None,
                             slot=None):
    """Prefill ONE request's prompt into its allocated pages.

    tokens [Tb] int32 padded to a bucket length; page_ids [ceil(Tb/ps)]
    int32 physical pages (logical order); tlen = real prompt length
    (traced). Writes all ceil(Tb/ps) pages — rows past tlen hold pad
    garbage that the validity mask hides until decode overwrites them, so
    the host may free pages past ``tlen // ps`` right after dispatch (any
    later owner rewrites before its mask ever exposes them). Samples the
    first generated token at tlen-1 and returns (first_token, cache).
    One executable per prompt bucket, like llama_prefill_slot.

    ``kv_dtype``: the prompt forward runs in full precision (the first
    token is sampled from exact activations — the standard quantized-KV
    deployment shape); only the CACHE WRITES quantize, so quantization
    error enters at the first decode read, never the prefill compute.

    ``kv_read`` / ``interpret`` / ``mesh`` (as the burst takes them): where
    the engine's decode steps read through the kernel, the prompt's pages
    are written by one ``paged_kv_scatter`` launch a layer instead of two
    ``dynamic_update_slice``s a page (``_kernel_write``).

    A layer pattern (``config.layer_types`` / ``mlp_layer_types``): the
    layers are walked one by one (no scan: they are not alike). A LINEAR
    layer runs the chunk scan from a zero state over the ``tlen`` real
    tokens (``linear_mixer.mixer_prefill``) and writes the state and the
    convolution's tail into row ``slot`` (traced; the engine's slot) of its
    ``state`` / ``conv`` buffers, whatever the slot's last request left
    there; a SLIDING layer attends through the flash forward with a window
    (scope ``win_attn``) and writes the prompt's last ``sliding_window``
    rows into row ``slot`` of its ring (``_ring_of_prompt``); pages are
    written for the FULL layers only. A SPARSE FFN is the dropless expert
    layer over the real tokens (``_ffn``); its counts of assignments add to
    ``cache["moe_counts"][0][1]`` (a burst's to row 0).
    """
    c = config
    if dequant is not None:
        params = dequant(params)
    layer_p, other = split_layer_params(params)
    T = tokens.shape[0]
    ps = int(cache["k"][0].shape[1])
    pool_heads = int(cache["k"][0].shape[2])    # the model's, or padded
    kernel_write = _kernel_write(c, ps, kv_dtype, kv_read, mesh, rows=ps)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n_pages = page_ids.shape[0]
    pad = n_pages * ps - T
    x = jnp.take(other["embed_tokens"], tokens[None, :],
                 axis=0).astype(c.dtype)
    positions = jnp.arange(T, dtype=jnp.int32)[None, :]

    from .llama import _attention

    def body(carry, lp):
        h = block_in(carry, lp["ln1"], c)
        q, k, v = attn_qkv(h, lp, c, positions)
        att = _attention(q, k, v, c)
        y = carry + block_out(att.reshape(1, T, -1) @ lp["wo"], lp["ln1"], c)
        y = _mlp(y, lp, c)
        return y, (k, v)

    states, tails = list(cache.get("state", ())), list(cache.get("conv", ()))
    rk, rv = list(cache.get("win_k", ())), list(cache.get("win_v", ()))
    moe, routed = cache.get("moe_counts"), 0
    if c.layer_types is None and c.mlp_layer_types is None \
            and not any(isinstance(v, tuple) for v in layer_p.values()):
        x, (ks, vs) = jax.lax.scan(body, x, layer_p)  # ks [L, 1, T, KV, hd]
    else:
        ks, vs = [], []
        real = positions < tlen                       # [1, T]: not padding
        for layer in range(c.num_hidden_layers):
            lp = layer_params_at(layer_p, c, layer)
            kind, l = c.kind_index(layer)
            h = block_in(x, lp["ln1"], c)
            early = _route_early(x, lp, c)
            if kind == c.LINEAR:
                mix, state, tail = mixer_prefill(h[0], lp, c, tlen)
                y = x + block_out(mix[None], lp["ln1"], c)
                states[l] = jax.lax.dynamic_update_slice(
                    states[l], state[None].astype(states[l].dtype),
                    (slot,) + (jnp.int32(0),) * 3)
                tails[l] = jax.lax.dynamic_update_slice(
                    tails[l], tail[None].astype(tails[l].dtype),
                    (slot,) + (jnp.int32(0),) * 2)
            else:
                q, k, v = attn_qkv(h, lp, c, positions, kind)
                if kind == c.SLIDING:   # the window's rows go to the ring
                    with jax.named_scope("win_attn"):
                        att = _attention(q, k, v, c, window=c.sliding_window)
                    at = (slot,) + (jnp.int32(0),) * 3
                    with jax.named_scope("kv_write"):
                        rk[l] = jax.lax.dynamic_update_slice(
                            rk[l], _ring_of_prompt(
                                k[0], tlen, c.sliding_window)[None], at)
                        rv[l] = jax.lax.dynamic_update_slice(
                            rv[l], _ring_of_prompt(
                                v[0], tlen, c.sliding_window)[None], at)
                else:
                    att = _attention(q, k, v, c)
                    ks.append(k)
                    vs.append(v)
                y = x + block_out(att.reshape(1, T, -1) @ lp["wo"],
                                  lp["ln1"], c)
            x, n = _ffn(y, lp, c, real, interpret, layer_p, layer, early)
            routed = routed + n

    quant = kv_dtype is not None
    z = jnp.int32(0)
    kl, vl = list(cache["k"]), list(cache["v"])
    ksl = list(cache["k_scale"]) if quant else None
    vsl = list(cache["v_scale"]) if quant else None
    heads_pad = pool_heads - c.num_key_value_heads
    for l in range(c.num_kv_layers):
        krows = jnp.pad(ks[l][0], ((0, pad), (0, heads_pad), (0, 0)))
        vrows = jnp.pad(vs[l][0], ((0, pad), (0, heads_pad), (0, 0)))
        if quant:
            krows, ksrows = _kv_encode(krows, kv_dtype)  # + [T+pad, KV]
            vrows, vsrows = _kv_encode(vrows, kv_dtype)
            ksp, vsp = ksl[l], vsl[l]
        kp, vp = kl[l], vl[l]
        if kernel_write:
            paged = (n_pages, ps) + krows.shape[1:]
            kp, vp = paged_kv_scatter(
                kp, vp, krows.reshape(paged), vrows.reshape(paged), page_ids,
                jnp.zeros(n_pages, jnp.int32), interpret=interpret)
        else:
            for j in range(n_pages):
                at = (page_ids[j], z, z, z)
                kp = jax.lax.dynamic_update_slice(
                    kp, krows[j * ps:(j + 1) * ps][None], at)
                vp = jax.lax.dynamic_update_slice(
                    vp, vrows[j * ps:(j + 1) * ps][None], at)
                if quant:
                    ats = (page_ids[j], z, z)
                    ksp = jax.lax.dynamic_update_slice(
                        ksp, ksrows[j * ps:(j + 1) * ps][None], ats)
                    vsp = jax.lax.dynamic_update_slice(
                        vsp, vsrows[j * ps:(j + 1) * ps][None], ats)
        kl[l], vl[l] = kp, vp
        if quant:
            ksl[l], vsl[l] = ksp, vsp
    cache = {"k": tuple(kl), "v": tuple(vl)}
    if quant:
        cache["k_scale"], cache["v_scale"] = tuple(ksl), tuple(vsl)
    if states:
        cache["state"], cache["conv"] = tuple(states), tuple(tails)
    if rk:
        cache["win_k"], cache["win_v"] = tuple(rk), tuple(rv)
    if moe is not None:
        cache["moe_counts"] = (moe[0].at[1].add(routed),)

    last = jax.lax.dynamic_slice_in_dim(x[0], tlen - 1, 1, axis=0)  # [1, D]
    logits = lm_head_logits(last, other, c)
    first = _sample(logits, temperature, top_k, key)
    return first[0], cache


def _suffix_attention(q, k_all, v_all, start, rows_p, config: LlamaConfig):
    """Causal attention of suffix queries over [gathered prefix rows ++
    in-pass suffix rows]. q [1, T, H, hd]; k_all/v_all [1, rows_p + T,
    KV, hd] where the first ``rows_p`` rows are the prefix pages gathered
    from the pool (valid below the traced ``start``, scratch garbage
    beyond) and the last T rows are the suffix computed this pass
    (causal). Same arithmetic as ``llama._attention``'s XLA reference —
    f32 logits, -1e30 mask, softmax rounded to q.dtype — so a
    prefix-shared prefill stays token-identical to the unshared dense
    pass it replaces (pinned by tests/test_prefix_cache.py)."""
    from .llama import _expand_gqa
    c = config
    k_all, v_all = _expand_gqa(k_all, v_all, c)
    scale = 1.0 / math.sqrt(c.head_dim)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q,
                        k_all).astype(jnp.float32) * scale
    T = q.shape[1]
    cols = jnp.arange(rows_p + T, dtype=jnp.int32)[None, :]
    qpos = jnp.arange(T, dtype=jnp.int32)[:, None]
    valid = jnp.where(cols < jnp.int32(rows_p), cols < start,
                      (cols - jnp.int32(rows_p)) <= qpos)
    logits = jnp.where(valid[None, None], logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v_all)


@functools.partial(jax.jit, static_argnames=(
    "config", "temperature", "top_k", "dequant", "kv_dtype"),
    donate_argnums=(1,))
def llama_paged_prefill_suffix(params, cache, tokens, page_ids,
                               prefix_table, start, tlen, key,
                               config: LlamaConfig,
                               temperature: float = 0.0, top_k: int = 0,
                               dequant=None, kv_dtype: str | None = None):
    """Prefill ONLY a prompt's unshared SUFFIX against cached prefix pages
    (ISSUE 13 — the prefill-FLOPs half of prefix sharing).

    tokens [Tb] int32: the suffix (prompt positions [start, start+tlen))
    padded to a bucket length; page_ids [ceil(Tb/ps)] fresh pages the
    suffix rows land in (logical order, page-aligned: ``start`` is a
    multiple of page_size); prefix_table [Pp] the SHARED pages holding
    positions [0, start) (padded with scratch to a page bucket — rows at
    or past ``start`` are masked); tlen = real suffix length (traced).
    Per layer the suffix K/V is written into its fresh pages exactly like
    :func:`llama_paged_prefill_slot`, then attention runs the suffix
    queries over [prefix pages gathered from the pool ++ in-pass suffix]
    — the pool rows are the SAME bits the original request's prefill
    wrote (quantized pools dequantize them, the standard quantized-KV
    read), so greedy outputs match an unshared serve. Samples the first
    generated token at suffix position tlen-1; returns (first, cache).
    One executable per (suffix bucket, prefix page bucket)."""
    c = config
    _one_kind_pool(cache, c, "the prefix-shared suffix prefill")
    if dequant is not None:
        params = dequant(params)
    layer_p, other = split_layer_params(params)
    T = tokens.shape[0]
    ps = cache["k"][0].shape[1]
    n_pages = page_ids.shape[0]
    pad = n_pages * ps - T
    Pp = prefix_table.shape[0]
    rows_p = Pp * ps
    x = jnp.take(other["embed_tokens"], tokens[None, :],
                 axis=0).astype(c.dtype)
    start32 = start.astype(jnp.int32) if hasattr(start, "astype") \
        else jnp.int32(start)
    positions = start32 + jnp.arange(T, dtype=jnp.int32)[None, :]

    quant = kv_dtype is not None
    z = jnp.int32(0)
    kl, vl = list(cache["k"]), list(cache["v"])
    ksl = list(cache["k_scale"]) if quant else None
    vsl = list(cache["v_scale"]) if quant else None
    for l in range(c.num_hidden_layers):
        lp = layer_params_at(layer_p, c, l)
        h = _rmsnorm(x, lp["ln1"], c.rms_norm_eps)
        q, k, v = _qkv(h, lp, c)
        q, k = _rope(q, k, positions, c.rope_theta, c.head_dim)
        kp, vp = kl[l], vl[l]
        krows = jnp.pad(k[0], ((0, pad), (0, 0), (0, 0)))
        vrows = jnp.pad(v[0], ((0, pad), (0, 0), (0, 0)))
        if quant:
            kw, ksrows = _kv_encode(krows, kv_dtype)
            vw, vsrows = _kv_encode(vrows, kv_dtype)
            ksp, vsp = ksl[l], vsl[l]
        else:
            kw, vw = krows, vrows
        for j in range(n_pages):
            at = (page_ids[j], z, z, z)
            kp = jax.lax.dynamic_update_slice(
                kp, kw[j * ps:(j + 1) * ps][None], at)
            vp = jax.lax.dynamic_update_slice(
                vp, vw[j * ps:(j + 1) * ps][None], at)
            if quant:
                ats = (page_ids[j], z, z)
                ksp = jax.lax.dynamic_update_slice(
                    ksp, ksrows[j * ps:(j + 1) * ps][None], ats)
                vsp = jax.lax.dynamic_update_slice(
                    vsp, vsrows[j * ps:(j + 1) * ps][None], ats)
        kl[l], vl[l] = kp, vp
        if quant:
            ksl[l], vsl[l] = ksp, vsp
        # gather the SHARED prefix rows from the pool (pages disjoint from
        # this request's fresh writes) — the read decode already does
        kc = jnp.take(kp, prefix_table, axis=0)
        vc = jnp.take(vp, prefix_table, axis=0)
        if quant:
            kc = _kv_decode(kc, jnp.take(ksp, prefix_table, axis=0),
                            c.dtype)
            vc = _kv_decode(vc, jnp.take(vsp, prefix_table, axis=0),
                            c.dtype)
        kc = kc.reshape(rows_p, c.num_key_value_heads, c.head_dim)
        vc = vc.reshape(rows_p, c.num_key_value_heads, c.head_dim)
        k_all = jnp.concatenate([kc[None], k], axis=1)
        v_all = jnp.concatenate([vc[None], v], axis=1)
        att = _suffix_attention(q, k_all, v_all, start32, rows_p, c)
        y = x + (att.reshape(1, T, -1) @ lp["wo"])
        x = _mlp(y, lp, c)

    cache = {"k": tuple(kl), "v": tuple(vl)}
    if quant:
        cache["k_scale"], cache["v_scale"] = tuple(ksl), tuple(vsl)

    last = jax.lax.dynamic_slice_in_dim(x[0], tlen - 1, 1, axis=0)  # [1, D]
    logits = lm_head_logits(last, other, c)
    first = _sample(logits, temperature, top_k, key)
    return first[0], cache


@functools.partial(jax.jit, static_argnames=(
    "config", "n", "temperature", "top_k", "pad_id", "dequant", "kv_dtype",
    "kv_read", "interpret", "mesh"), donate_argnums=(1,))
@jax.named_scope("burst")
def llama_paged_decode_burst(params, cache, block_table, pos, tok, done,
                             limit, eos_id, key, config: LlamaConfig,
                             n: int, temperature: float = 0.0,
                             top_k: int = 0, pad_id: int = 0, dequant=None,
                             kv_dtype: str | None = None,
                             kv_read: str | None = None,
                             interpret: bool | None = None, mesh=None):
    """n scanned paged-decode steps — the paged serving hot loop.

    Same contract as llama_decode_burst plus block_table [B, P]: a slot
    stops on eos_id or `limit`, finished slots emit pad_id and freeze
    (their frozen write lands in their own page while active, in scratch
    page 0 once the host retires them and zeroes their table row).
    Returns (cache, pos, tok, done, emitted [n, B]). One executable per
    (B, P, n) — P is the page-count bucket, so the inventory is
    O(page buckets), not O(contexts). ``kv_read`` / ``interpret`` /
    ``mesh``: see ``_paged_decode_step_slots`` (None = ``paged_kv_read``'s
    choice for this pool).
    """
    def step(carry, _):
        cache, pos, tok, done, key = carry
        p = dequant(params) if dequant is not None else params
        logits, cache = _paged_decode_step_slots(
            p, cache, block_table, pos, tok, config, kv_dtype=kv_dtype,
            kv_read=kv_read, interpret=interpret, mesh=mesh, done=done)
        key, sub = jax.random.split(key)
        with jax.named_scope("head_sample"):
            nxt = _sample(logits, temperature, top_k, sub)
        emit = jnp.where(done, jnp.int32(pad_id), nxt)
        new_pos = jnp.where(done, pos, pos + 1)
        new_tok = jnp.where(done, tok, nxt)
        new_done = done | (nxt == eos_id) | (new_pos >= limit)
        return (cache, new_pos, new_tok, new_done, key), emit

    (cache, pos, tok, done, _), emitted = jax.lax.scan(
        step, (cache, pos, tok, done, key), None, length=n)
    return cache, pos, tok, done, emitted


# ------------------------------- the burst's weights, a layer at a time (ISSUE 35)
# A decode step walks the layers unrolled with static indices. Handed a layer
# STACK of a projection whose output is split into heads at once, XLA's TPU
# compiler transposes the whole stack at every call and then slices every
# layer's matrix out of that copy into a buffer of its own at every step
# (5-14 % of the serving cells' device time). Handed the same leaf a layer at
# a time, in the layout the compiled burst asks for, each matrix goes from the
# parameter to VMEM and nothing else. The engine makes that form once at load.


def per_layer_weights(params, config: LlamaConfig, formats=None) -> dict:
    """``params`` with the leaves of ``heads_at_once_leaves`` as TUPLES of
    per-layer arrays in place of their layer stacks (``layer_params_at``
    indexes either), every other leaf as it is (the experts' stacks too:
    the grouped products read them in place). ``formats``: {leaf: a
    ``Format`` a layer}, as ``burst_for_layouts(...).input_formats`` names
    them: each slice is placed in its own; None leaves the slices in the
    default layout. Arrays or ``ShapeDtypeStruct``s."""
    out = dict(params)
    for name in heads_at_once_leaves(config):
        stack = params.get(name)
        if stack is None or isinstance(stack, tuple) or not stack.shape[0]:
            continue
        if isinstance(stack, jax.ShapeDtypeStruct):
            out[name] = (jax.ShapeDtypeStruct(stack.shape[1:], stack.dtype),
                         ) * stack.shape[0]
        elif formats is None:
            out[name] = tuple(stack[i] for i in range(stack.shape[0]))
        else:   # one program a stack: every slice written once, where asked
            out[name] = jax.jit(tuple, out_shardings=tuple(formats[name]))(
                stack)
    return out


def burst_for_layouts(params, cache, slots: int, pages: int, sharding,
                      **static):
    """``llama_paged_decode_burst`` for a [slots, pages] block table,
    compiled ahead of time with the LAYOUT of every per-layer leaf of
    ``params`` (a tuple: ``per_layer_weights``) left to the compiler
    (``Layout.AUTO``) and every other argument as stored. ``params`` /
    ``cache``: arrays or ``ShapeDtypeStruct``s (only shapes are read);
    ``sharding``: the one device's; ``static``: the burst's own static
    arguments. Returns the ``jax.stages.Compiled``: it IS the burst program
    for that table (call it with the dynamic arguments), and
    ``.input_formats[0][0]`` says which format it wants each leaf of
    ``params`` in. Asking is compiling, so the engine compiles once."""
    from jax.experimental.layout import Format, Layout
    auto = Format(Layout.AUTO, sharding)
    formats = {k: (auto,) * len(v) if isinstance(v, tuple) else sharding
               for k, v in params.items()}
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), (params, cache))
    i32 = jax.ShapeDtypeStruct((slots,), jnp.int32)
    rest = (jax.ShapeDtypeStruct((slots, pages), jnp.int32), i32, i32,
            jax.ShapeDtypeStruct((slots,), jnp.bool_), i32,
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.eval_shape(lambda: jax.random.PRNGKey(0)))

    burst = functools.partial(llama_paged_decode_burst.__wrapped__, **static)
    # jit names a program by its function's __name__, which a partial lacks
    # (a trace would show "jit__unknown"): the burst keeps its own name
    burst.__name__ = llama_paged_decode_burst.__name__
    return jax.jit(
        burst, in_shardings=(formats,) + (sharding,) * 8,
        donate_argnums=(1,)).lower(*shapes, *rest).compile()


# ------------------------------------------------------- verify (ISSUE 14)
# Speculative decoding's target half: each verifying slot's row carries
# [current_tok, d_1 .. d_np] — its np draft proposals behind the token the
# plain path would feed next — as a short "prefill-carrying" segment at
# prefill_start = pos (q_len = np + 1, TRACED), and the launch returns the
# greedy target token for EVERY row position. Accept-prefix then emits the
# longest prefix where draft and target argmax agree plus the target's
# correction/bonus token, so up to k+1 tokens cost ONE target launch while
# staying token-identical to plain greedy decode (the host walk in
# inference/speculative.py mirrors the scan's eos/limit arithmetic).
# q_len rides in a traced vector, so mixed per-slot proposal counts (slots
# near their budget propose fewer; a draft catching up proposes none and
# the row degenerates to a plain decode step) all share ONE executable —
# no per-k bucket grid (pinned by tests/test_speculative.py).


def _verify_attention(q, kc, vc, start, config: LlamaConfig):
    """Verify-segment attention: q [B, Tv, H, hd] queries at absolute
    positions ``start[b] + j`` over the block-table-gathered rows kc/vc
    [B, R, KV, hd] (R = page_bucket × page_size, row r = logical position
    r). Query j attends rows ≤ start + j. Same arithmetic family as
    ``_cached_attention_slots`` (grouped einsum, f32 logits, -1e30 mask, softmax rounded to q.dtype)
    so greedy targets match the plain decode step's token for token."""
    c = config
    H, KV = c.num_attention_heads, c.num_key_value_heads
    g = H // KV
    B, Tv, _, hd = q.shape
    R = kc.shape[1]
    qg = q.reshape(B, Tv, KV, g, hd)
    scale = 1.0 / jnp.sqrt(jnp.float32(c.head_dim))
    logits = jnp.einsum("btkgd,bskd->bkgts", qg.astype(jnp.float32),
                        kc.astype(jnp.float32)) * scale
    cols = jnp.arange(R, dtype=jnp.int32)[None, None, :]
    qpos = (start.astype(jnp.int32)[:, None, None]
            + jnp.arange(Tv, dtype=jnp.int32)[None, :, None])
    valid = cols <= qpos                          # [B, Tv, R]
    logits = jnp.where(valid[:, None, None], logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, vc)
    return out.reshape(B, Tv, H, hd)


@functools.partial(jax.jit, static_argnames=(
    "config", "dequant", "kv_dtype"), donate_argnums=(1,))
def llama_paged_verify(params, cache, block_table, start, tokens, n_tok,
                       config: LlamaConfig, *, dequant=None,
                       kv_dtype: str | None = None):
    """ONE launch verifying every slot's speculative segment (ISSUE 14).

    tokens [B, Tv] int32 (Tv = k+1, static per engine): slot b's row is
    [current_tok, proposals...] padded; n_tok [B] the live row length
    (0 skips the slot — its writes go to scratch, its outputs are junk
    the host ignores); start [B] = the slot's pos (row j lands at
    absolute position start+j, NOT page-aligned — writes are per-row).
    K/V rows are written through the block table exactly like a decode
    step would write them one launch at a time, then read back through
    the XLA gather + ``_verify_attention`` (whichever read the engine's
    decode steps take: the decode kernel reads one row a slot). Rows past
    the accepted prefix become stale pool garbage the validity masks hide — rewind is free (the host just
    resets pos and frees trailing pages; shared pages were COW'd by the
    growth sweep BEFORE these writes could touch them).

    Returns (targets [B, Tv] int32 — the greedy target token after each
    row position, i.e. targets[b, j] is the token at start+j+1 — and the
    updated cache). Greedy only: speculative serving is gated to
    temperature 0, where accept-prefix is exact."""
    from ..inference.paging import SCRATCH_PAGE

    c = config
    _one_kind_pool(cache, c, "speculative verification")
    p = dequant(params) if dequant is not None else params
    layer_p, other = split_layer_params(p)
    B, Tv = tokens.shape
    ps = int(cache["k"][0].shape[1])
    P = block_table.shape[1]
    start32 = start.astype(jnp.int32)
    lens32 = n_tok.astype(jnp.int32)
    x = jnp.take(other["embed_tokens"], tokens, axis=0).astype(c.dtype)
    positions = start32[:, None] + jnp.arange(Tv, dtype=jnp.int32)[None, :]
    live = jnp.arange(Tv, dtype=jnp.int32)[None, :] < lens32[:, None]
    pg_idx = jnp.minimum(positions // jnp.int32(ps), jnp.int32(P - 1))
    wpage = jnp.where(live,
                      jnp.take_along_axis(block_table, pg_idx, axis=1),
                      jnp.int32(SCRATCH_PAGE))
    wrow = positions % jnp.int32(ps)
    z = jnp.int32(0)

    quant = kv_dtype is not None
    ks, vs = list(cache["k"]), list(cache["v"])
    kss = list(cache["k_scale"]) if quant else None
    vss = list(cache["v_scale"]) if quant else None
    for l in range(c.num_hidden_layers):
        lp = layer_params_at(layer_p, c, l)
        h = _rmsnorm(x, lp["ln1"], c.rms_norm_eps)
        q, k, v = _qkv(h, lp, c)
        q, k = _rope(q, k, positions, c.rope_theta, c.head_dim)
        kp, vp = ks[l], vs[l]
        ku, vu = k, v                              # [B, Tv, KV, hd]
        if quant:
            ku, ksr = _kv_encode(ku, kv_dtype)     # + scales [B, Tv, KV]
            vu, vsr = _kv_encode(vu, kv_dtype)
            ksp, vsp = kss[l], vss[l]
        for b in range(B):
            for j in range(Tv):
                at = (wpage[b, j], wrow[b, j], z, z)
                kp = jax.lax.dynamic_update_slice(
                    kp, ku[b, j][None, None], at)
                vp = jax.lax.dynamic_update_slice(
                    vp, vu[b, j][None, None], at)
                if quant:
                    ats = (wpage[b, j], wrow[b, j], z)
                    ksp = jax.lax.dynamic_update_slice(
                        ksp, ksr[b, j][None, None], ats)
                    vsp = jax.lax.dynamic_update_slice(
                        vsp, vsr[b, j][None, None], ats)
        ks[l], vs[l] = kp, vp
        if quant:
            kss[l], vss[l] = ksp, vsp
        kc = jnp.take(kp, block_table, axis=0)
        vc = jnp.take(vp, block_table, axis=0)
        if quant:
            kc = _kv_decode(kc, jnp.take(ksp, block_table, axis=0), c.dtype)
            vc = _kv_decode(vc, jnp.take(vsp, block_table, axis=0), c.dtype)
        kc = kc.reshape(B, -1, c.num_key_value_heads, c.head_dim)
        vc = vc.reshape(B, -1, c.num_key_value_heads, c.head_dim)
        att = _verify_attention(q, kc, vc, start32, c)
        y = x + (att.reshape(B, Tv, -1) @ lp["wo"])
        x = _mlp(y, lp, c)

    logits = lm_head_logits(x, other, c)           # [B, Tv, V] f32
    targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out = {"k": tuple(ks), "v": tuple(vs)}
    if quant:
        out["k_scale"], out["v_scale"] = tuple(kss), tuple(vss)
    return targets, out
