"""Pallas flash attention for TPU.

Reference capability: phi/kernels/gpu/flash_attn_kernel.cu (vendored
third_party/flashattn). TPU-native design: an online-softmax tiled forward
and a custom_vjp whose backward recomputes attention tile by tile
(flash-attention-2 style), three kernels in all: `flash_fwd`,
`flash_bwd_dq`, `flash_bwd_dkv` (the names a device trace shows).

How a kernel walks the sequence (since PR 31): the grid is (batch, head,
block of rows, major block); a grid step holds its block of rows (q for the
forward and dq, k/v for dk/dv) and a MAJOR block of the operand it reduces
over (`_major_block`: up to 8 MiB of VMEM, the whole sequence at every shape
the benchmark's cells run) and walks that operand's 512-row tiles with an
inner loop whose trip count is the causal set's, so the walked operand is
fetched once a head, no grid step is spent above the diagonal, and every
tile that runs is masked in registers. What is float32 and what rounds is in
`_flash_fwd_impl` (operands as stored, float32 statistics kept over 128
lanes, p rounded to the inputs' dtype) and `_flash_bwd_impl` (float32 tiles;
dk/dv on transposed tiles); a float32 input is never cast down. Measured on
a v5e, us a 512 x 512 block of the causal set at [2, 2048, 32, 128] bf16
(tools/flash_microbench.py; PERF.md section 6, PR 31): forward 3.20 -> 1.03,
dq 1.82 -> 1.3, dk/dv 2.39 -> 1.6, against 0.68 / 1.02 / 1.36 for the
products alone at the MXU's peak.

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
_LANES = 128
# what a kernel may take of the 16 MiB of scoped VMEM the compiler grants by
# default before it asks for more (_compiler_params); the chip has 128 MiB
_VMEM_GRANTED = 12 << 20
# VMEM given to the two operands a kernel walks with its inner loop (K and V;
# q and dout), double-buffered: half the 16 MiB the compiler grants a kernel
_MAJOR_VMEM_BYTES = 8 << 20

# index-map constant: with jax_enable_x64 a literal 0 traces as i64, which
# Mosaic cannot legalize in BlockSpec index maps
import numpy as _np
_i0 = _np.int32(0)


def flash_attention_tpu_available() -> bool:
    try:
        return jax.default_backend() == "tpu"
    except Exception:
        return False


def masked_softmax(logits, mask):
    """Softmax along the last axis where fully-masked rows (e.g. the L>S head
    of a bottom-right causal mask) get all-zero probs — and defined
    gradients — instead of softmax(-inf row)=nan. Matches the Pallas
    forward's handling of rows with no visible kv."""
    m = jnp.max(jnp.where(mask, logits, -jnp.inf), axis=-1, keepdims=True)
    m = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.where(mask, jnp.exp(logits - m), 0.0)
    return p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)


def _fa_reference(q, k, v, causal, window=None):
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("blhd,bshd->bhls", q, k).astype(jnp.float32) * scale
    if causal:
        ql, kl = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((ql, kl), bool), k=kl - ql)
        if window is not None:      # row r sees cols r + kl - ql - window < c
            mask &= ~jnp.tril(jnp.ones((ql, kl), bool), k=kl - ql - window)
        probs = masked_softmax(logits, mask).astype(q.dtype)
    else:
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhls,bshd->blhd", probs, v)


def flash_attention_raw(q, k, v, causal: bool = False, block_q: int = 512,
                        block_k: int = 512, mesh=None, spec=None,
                        window: int | None = None):
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

    Tiles: 512 x 512 (`_fit_block` shrinks one to a divisor of its
    sequence). On the chip no other tile beat it once the kernels walk K/V
    with an inner loop (1024-wide kv tiles waste the diagonal, 256-wide ones
    pay the statistics twice as often: PERF.md section 6, PR 31); what
    adapts to L, S, D and the dtype is the major block a grid step holds
    (`_major_block`) and the scoped VMEM asked for (`_compiler_params`).
    FLAGS_flash_block_q / FLAGS_flash_block_k (env or set_flags) override
    the tile sizes globally, for sweeps; 0 keeps the caller's value.

    window (a sliding-window layer's; needs causal): row r sees only the
    `window` newest of the columns a causal row sees, i - window < j <= i
    for L == S. The forward's inner loop then STARTS at the tile that holds
    the row block's oldest visible column (`_kv_first_tile`), so its trip
    count is the window's and not the prefix's. Forward only: `jax.grad`
    through a windowed call raises (no cell trains a window). None keeps
    the trip counts and the arithmetic of a call without it, bit for bit."""
    from ..utils.flags import flag_value
    block_q = int(flag_value("flash_block_q") or block_q)
    block_k = int(flag_value("flash_block_k") or block_k)
    L, S = q.shape[1], k.shape[1]
    if window is not None and (not causal or window < 1):
        raise ValueError("a window needs causal=True and window >= 1")
    if (L % _MIN_BLOCK) or (S % _MIN_BLOCK) or not flash_attention_tpu_available():
        return _fa_reference(q, k, v, causal, window)
    kernel = functools.partial(_flash_kernel, causal=causal,
                               bq=_fit_block(block_q, L),
                               bk=_fit_block(block_k, S), window=window)
    if mesh is not None:
        from ..utils.jax_compat import shard_map
        kernel = shard_map(kernel, mesh, (spec, spec, spec), spec)
    return kernel(q, k, v)


def _flash_kernel(q, k, v, *, causal, bq, bk, window=None):
    D = q.shape[-1]
    if window is not None:
        if D % 128:
            raise NotImplementedError(
                f"a windowed flash forward needs head_dim % 128 == 0, not {D}")
        return _flash_fwd_window(q, k, v, window, bq, bk)
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


# ---------------- pallas kernels ----------------
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_fwd_window(q, k, v, window, block_q, block_k, interpret=False):
    """The causal forward under a window; no backward exists."""
    return _flash_fwd_impl(q, k, v, True, block_q, block_k, interpret,
                           window=window)[0]


def _no_window_grad(*_):
    raise NotImplementedError(
        "flash attention with a window is forward only: the backward "
        "kernels know no window (ROADMAP Queue 2(a) M3)")


_flash_fwd_window.defvjp(_no_window_grad, _no_window_grad)


def _clip(x, lo, hi):
    return jnp.minimum(jnp.maximum(x, _np.int32(lo)), _np.int32(hi))


def _major_block(block: int, length: int, row_bytes: int) -> int:
    """Rows of the operand a kernel walks with its inner loop that one grid
    step holds in VMEM: the largest multiple of `block` that divides
    `length` and keeps two such operands, double-buffered, inside
    _MAJOR_VMEM_BYTES. Every sequence the cells run is held whole (3072
    rows of bf16 x 128 are 3 MB), so K and V are fetched once a head and
    not once a q block."""
    n = length // block
    subs = max(d for d in range(1, n + 1)
               if n % d == 0 and (d == 1 or 4 * d * block * row_bytes
                                  <= _MAJOR_VMEM_BYTES))
    return subs * block


def _kv_tiles(qi, block_q, block_k, L, S, causal):
    """How many kv tiles of `block_k`, counted from column 0, hold an entry
    that q block `qi` sees; the tiles past them are never run.
    Bottom-right-aligned convention: row r sees cols <= r + S - L. Shared
    by the forward and the dq kernel; `_q_tiles` is the same set seen from
    a kv block, so the convention cannot diverge."""
    i32, n = _np.int32, S // block_k
    if not causal:
        return i32(n)
    last_row = qi * i32(block_q) + i32(block_q - 1 + S - L)
    return _clip(last_row // i32(block_k) + i32(1), 0, n)


def _kv_first_tile(qi, block_q, block_k, L, S, window):
    """The first kv tile of `block_k` that holds a column q block `qi` sees
    under a window: its first row r = qi * block_q sees columns
    > r + S - L - window. 0 without a window."""
    if window is None:
        return _np.int32(0)
    first_col = qi * _np.int32(block_q) + _np.int32(S - L - window + 1)
    return _clip(first_col // _np.int32(block_k), 0, S // block_k)


def _q_tiles(ki, block_q, block_k, L, S, causal):
    """The first q tile of `block_q` rows that sees an entry of kv block
    `ki`; the tiles before it are never run."""
    if not causal:
        return _np.int32(0)
    first_col = ki * _np.int32(block_k) - _np.int32(S - L)
    return _clip(first_col // _np.int32(block_q), 0, L // block_q)


def _causal_mask_scores(s, q_axis, q0, k0, off, window=None):
    """Apply the bottom-right causal mask to a score tile whose q positions
    run along axis `q_axis` from q0 and whose kv positions along the other
    from k0: q position r sees kv positions <= r + off, and under a window
    only those > r + off - window."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    seen = q_pos + _np.int32(off) >= k_pos
    if window is not None:
        seen &= q_pos + _np.int32(off - window) < k_pos
    return jnp.where(seen, s, -jnp.inf)


def _row_block(b, h, i, j):
    """Index map of the block of rows a grid step (b, h, i, j) owns."""
    return (b, h, i, _i0)


def _kv_major_index(geometry, kv_subs, grid_k):
    """Index map of the K/V major block of grid step (b, h, qi, kj): block
    kj, or where q block qi sees nothing of it (a step above the diagonal)
    the last block it does see, which is resident, so no copy is issued."""
    def index(b, h, qi, kj):
        if geometry[-1] and grid_k > 1:
            last = (_kv_tiles(qi, *geometry) - _np.int32(1)) // _np.int32(kv_subs)
            kj = jnp.minimum(kj, _clip(last, 0, grid_k - 1))
        return (b, h, kj, _i0)
    return index


def _for_tiles(pl, subs, lo, hi, step):
    """step(t) for the sub-tiles lo <= t < hi of a major block of `subs`."""
    if subs == 1:
        pl.when(hi > lo)(lambda: step(_np.int32(0)))
    else:
        jax.lax.fori_loop(lo, hi, lambda t, c: (step(t), c)[1], _np.int32(0))


def _tile(pl, ref, t, rows, subs):
    """Sub-tile `t` (of `rows` rows) of the major block in `ref`."""
    if subs == 1:
        return ref[0, 0]
    return ref[0, 0, pl.ds(pl.multiple_of(t * _np.int32(rows), rows), rows), :]


def _lanes(x, n):
    """A statistic kept in every one of 128 lanes, [rows, 128], against a
    tile of n columns."""
    return x if n == _LANES else jnp.tile(x, (1, n // _LANES))


def _compiler_params(pltpu, interpret, block_q, block_k, major_bytes):
    """`major_bytes`: the walked operands' buffers. The compiler's own grant
    of scoped VMEM holds them and a 512 x 512 tile's float32 temporaries
    (s, p, dp, ds and what exp and the mask need: eight tiles) at every
    shape the cells run, and is raised only for a kernel that needs more: a
    long float32 sequence, tiles forced larger by the flags."""
    if interpret:
        return None
    need = major_bytes + 8 * block_q * block_k * 4
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=None if need <= _VMEM_GRANTED else min(2 * need,
                                                                100 << 20))


def _flash_bwd_impl(q, k, v, out, lse, dout, causal, block_q, block_k,
                    interpret=False, sm_scale=None):
    """Flash-attention-2 backward as two Pallas kernels.

    Recomputes p = exp(q k^T * scale - lse) tile by tile from the saved lse,
    so nothing O(L*S) is ever materialised:
      delta = rowsum(dout * out)                 (precomputed, [B,H,L])
      dp = dout v^T;  ds = p * (dp - delta)
      dq = ds k * scale   (kernel 1: q-block rows, reduce over kv tiles)
      dk = ds^T q * scale; dv = p^T dout
                          (kernel 2: kv-block rows, reduce over q tiles)
    Which tiles run is `_kv_tiles` / `_q_tiles`, the forward's set; every
    tile that runs is masked (`_flash_fwd_impl` says why). The operand a
    kernel reduces over comes in major blocks (`_major_block`) walked by an
    inner loop; a grid step whose major block has nothing to run names the
    nearest block that has, so no copy is issued for it.

    The arithmetic: tiles are cast to float32 and q is scaled once a tile of
    q rows, before the products, so p, dp, ds, delta, lse and the
    accumulators are float32 throughout and a float32 input is never cast
    down. Operands as stored with p and ds rounded to the inputs' dtype (the
    forward's way) measured 2-3 % SLOWER here, because the scale then
    multiplies every [block_q, block_k] tile instead of q (PERF.md section
    6, PR 31), so the backward keeps float32 tiles.

    The dk/dv kernel computes its tiles TRANSPOSED, s^T = k q^T of shape
    [block_k, block_q]: lse and delta are then rows ([1, block_q], one row
    a q tile, broadcast along sublanes at no cost) and dv += p^T dout,
    dk += ds^T q are plain products with no transpose of a tile.

    Measured on a v5e at [2, 2048, 32, 128] bf16 causal, tiles 512 x 512, a
    512 x 512 block of the causal set (tools/flash_microbench.py; PERF.md
    section 6, PR 31): dq 1.82 -> ~1.3 us, dk/dv 2.39 -> ~1.6 us; the
    three and four products of a block need 1.02 and 1.36 us at the peak.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i32 = _np.int32
    B, L, H, D = q.shape
    S = k.shape[1]
    assert L % block_q == 0 and S % block_k == 0, \
        f"blocks must tile the sequences: {L}%{block_q}, {S}%{block_k}"
    scale = _np.float32(sm_scale if sm_scale is not None else 1.0 / math.sqrt(D))
    geometry = (block_q, block_k, L, S, causal)
    nt = (((1,), (1,)), ((), ()))                   # a @ b^T
    nn = (((1,), (0,)), ((), ()))                   # a @ b

    def f32(x):
        return x.astype(jnp.float32)

    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    dot = jnp.swapaxes(dout, 1, 2)                  # [B, H, L, D]
    delta = jnp.sum(f32(dot) * f32(jnp.swapaxes(out, 1, 2)), axis=-1)

    def p_and_ds(s, dp, lse_t, delta_t, q_axis, q0, k0):
        """p and ds of one tile from its scaled scores `s` and `dp`; lse_t
        and delta_t broadcast against the tile."""
        if causal:
            s = _causal_mask_scores(s, q_axis, q0, k0, S - L)
            # a row with no visible column has lse = -inf; its s is all
            # -inf too, and exp(-inf - 0) = 0 where -inf - -inf is nan
            lse_t = jnp.where(jnp.isneginf(lse_t), 0.0, lse_t)
        p = jnp.exp(s - lse_t)
        return p, p * (dp - delta_t)

    # ---- kernel 1: dq (rows = q blocks, reduce over kv tiles) ----
    kv_major = _major_block(block_k, S, D * k.dtype.itemsize)
    kv_subs, grid_k = kv_major // block_k, S // kv_major
    kv_index = _kv_major_index(geometry, kv_subs, grid_k)

    def dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref, acc):
        qi, kj = pl.program_id(2), pl.program_id(3)

        @pl.when(kj == 0)
        def _init():
            acc[...] = jnp.zeros_like(acc)

        first = kj * i32(kv_subs)
        run_end = _clip(_kv_tiles(qi, *geometry) - first, 0, kv_subs)
        qb = f32(q_ref[0, 0]) * scale                    # [block_q, D]
        dob = f32(do_ref[0, 0])

        def step(t):
            kb = f32(_tile(pl, k_ref, t, block_k, kv_subs))   # [block_k, D]
            vb = f32(_tile(pl, v_ref, t, block_k, kv_subs))
            s = jax.lax.dot_general(qb, kb, nt,
                                    preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(dob, vb, nt,
                                     preferred_element_type=jnp.float32)
            _, ds = p_and_ds(s, dp, lse_ref[0, 0], dl_ref[0, 0], 0,
                             qi * i32(block_q), (first + t) * i32(block_k))
            acc[...] += jax.lax.dot_general(
                ds, kb, nn, preferred_element_type=jnp.float32)

        _for_tiles(pl, kv_subs, i32(0), run_end, step)

        @pl.when(kj == grid_k - 1)
        def _fin():
            dq_ref[0, 0] = (acc[...] * scale).astype(dq_ref.dtype)

    dqt = pl.pallas_call(
        dq_kernel,
        grid=(B, H, L // block_q, grid_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), _row_block),
            pl.BlockSpec((1, 1, kv_major, D), kv_index),
            pl.BlockSpec((1, 1, kv_major, D), kv_index),
            pl.BlockSpec((1, 1, block_q, D), _row_block),
            pl.BlockSpec((1, 1, block_q, 1), _row_block),
            pl.BlockSpec((1, 1, block_q, 1), _row_block),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), _row_block),
        out_shape=jax.ShapeDtypeStruct((B, H, L, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=_compiler_params(pltpu, interpret, block_q, block_k,
                                         4 * kv_major * D * k.dtype.itemsize),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qt, kt, vt, dot, lse[..., None], delta[..., None])

    # ---- kernel 2: dk, dv (rows = kv blocks, reduce over q tiles) ----
    # q and dout come in major blocks of q rows; lse and delta beside them
    # as one row a q tile, [B, H, tiles, 1, block_q]
    q_major = _major_block(block_q, L, D * q.dtype.itemsize)
    q_subs, grid_q = q_major // block_q, L // q_major
    as_rows = (B, H, L // block_q, 1, block_q)

    def dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dk_ref, dv_ref,
                   acc_dk, acc_dv):
        ki, qj = pl.program_id(2), pl.program_id(3)

        @pl.when(qj == 0)
        def _init():
            acc_dk[...] = jnp.zeros_like(acc_dk)
            acc_dv[...] = jnp.zeros_like(acc_dv)

        first = qj * i32(q_subs)
        run_start = _clip(_q_tiles(ki, *geometry) - first, 0, q_subs)
        kb, vb = f32(k_ref[0, 0]), f32(v_ref[0, 0])      # [block_k, D]

        def step(t):
            # qb is pre-scaled, so ds^T @ qb already carries the 1/sqrt(D)
            qb = f32(_tile(pl, q_ref, t, block_q, q_subs)) * scale
            dob = f32(_tile(pl, do_ref, t, block_q, q_subs))
            s_t = jax.lax.dot_general(kb, qb, nt,        # [block_k, block_q]
                                      preferred_element_type=jnp.float32)
            dp_t = jax.lax.dot_general(vb, dob, nt,
                                       preferred_element_type=jnp.float32)
            p_t, ds_t = p_and_ds(s_t, dp_t, lse_ref[0, 0, t], dl_ref[0, 0, t],
                                 1, (first + t) * i32(block_q),
                                 ki * i32(block_k))
            acc_dv[...] += jax.lax.dot_general(
                p_t, dob, nn, preferred_element_type=jnp.float32)
            acc_dk[...] += jax.lax.dot_general(
                ds_t, qb, nn, preferred_element_type=jnp.float32)

        _for_tiles(pl, q_subs, run_start, i32(q_subs), step)

        @pl.when(qj == grid_q - 1)
        def _fin():
            dk_ref[0, 0] = acc_dk[...].astype(dk_ref.dtype)
            dv_ref[0, 0] = acc_dv[...].astype(dv_ref.dtype)

    def q_index(b, h, ki, qj):
        if causal and grid_q > 1:
            first = _q_tiles(ki, *geometry) // i32(q_subs)
            qj = jnp.maximum(qj, _clip(first, 0, grid_q - 1))
        return (b, h, qj, _i0)

    def q_stat_index(b, h, ki, qj):
        return q_index(b, h, ki, qj) + (_i0,)

    dkt, dvt = pl.pallas_call(
        dkv_kernel,
        grid=(B, H, S // block_k, grid_q),
        in_specs=[
            pl.BlockSpec((1, 1, q_major, D), q_index),
            pl.BlockSpec((1, 1, block_k, D), _row_block),
            pl.BlockSpec((1, 1, block_k, D), _row_block),
            pl.BlockSpec((1, 1, q_major, D), q_index),
            pl.BlockSpec((1, 1, q_subs, 1, block_q), q_stat_index),
            pl.BlockSpec((1, 1, q_subs, 1, block_q), q_stat_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D), _row_block),
            pl.BlockSpec((1, 1, block_k, D), _row_block),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, S, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        compiler_params=_compiler_params(pltpu, interpret, block_q, block_k,
                                         4 * q_major * D * q.dtype.itemsize),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qt, kt, vt, dot, lse.reshape(as_rows), delta.reshape(as_rows))

    return (jnp.swapaxes(dqt, 1, 2), jnp.swapaxes(dkt, 1, 2),
            jnp.swapaxes(dvt, 1, 2))


def _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret=False,
                    sm_scale=None, window=None):
    """Tiled online-softmax forward in Pallas (interpret=True runs the same
    kernel on CPU for correctness tests without a TPU). Returns out
    [B, L, H, D] in q's dtype and lse [B, H, L] float32 (-inf for a row
    that sees no column, whose output is 0).

    A grid step holds one q block and a major block of K and V
    (`_major_block`: the whole sequence at every size the cells run) and
    walks the kv tiles of `block_k` rows that hold an entry the q block sees
    (`_kv_tiles`) with an inner loop; tiles above the diagonal are not run,
    and with more than one major block a step above the diagonal names the
    last block that ran, so nothing is fetched for it. Under `causal` every
    tile that runs is masked (iota / compare / select): a second, maskless
    body for the tiles wholly under the diagonal measured 2-3 % slower, the
    mask's vector work hides under the products. Under a `window` (causal)
    the loop starts at `_kv_first_tile` and the mask has the window's lower
    bound too; None runs the loop from tile 0 as before.

    The arithmetic: q, k, v go to the MXU in the dtype they have, with
    float32 accumulation; the scale multiplies the float32 product; exp and
    the accumulator are float32; p rounds to v's dtype before P @ V, which
    is where `_fa_reference` and `llama._attention` round it. A float32
    input is never cast down. The running max is float32 `[block_q, 128]`,
    a row's value in every lane (the layout of the public Pallas TPU flash
    kernel): the one relayout a tile pays is the lane-broadcast of the row
    maxima of s. The running sum stays SPREAD over the 128 lanes (lane j
    sums the columns j, j + 128, ...: plain vector adds) and is summed
    across lanes once, when the block is written.

    Measured on a v5e, bf16 causal, tiles 512 x 512, a 512 x 512 block of
    the causal set (tools/flash_microbench.py; PERF.md section 6, PR 31):
    3.20 -> ~1.03 us at [2, 2048, 32, 128] (1-D statistics 3.2, `(block_q,
    1)` columns 2.0-2.3, lanes 1.25-1.55, K/V held whole 1.0-1.2); the two
    products of a block need 0.68 us at the peak.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i32 = _np.int32
    B, L, H, D = q.shape
    S = k.shape[1]
    assert L % block_q == 0 and S % block_k == 0, \
        f"blocks must tile the sequences: {L}%{block_q}, {S}%{block_k}"
    scale = _np.float32(sm_scale if sm_scale is not None else 1.0 / math.sqrt(D))
    geometry = (block_q, block_k, L, S, causal)
    kv_major = _major_block(block_k, S, D * k.dtype.itemsize)
    kv_subs, grid_k = kv_major // block_k, S // kv_major
    kv_index = _kv_major_index(geometry, kv_subs, grid_k)

    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_i, l_i):
        qi, kj = pl.program_id(2), pl.program_id(3)

        @pl.when(kj == 0)
        def _init():
            acc[...] = jnp.zeros_like(acc)
            m_i[...] = jnp.full_like(m_i, -jnp.inf)
            l_i[...] = jnp.zeros_like(l_i)

        first = kj * i32(kv_subs)
        run_end = _clip(_kv_tiles(qi, *geometry) - first, 0, kv_subs)
        run_start = i32(0) if window is None else _clip(
            _kv_first_tile(qi, block_q, block_k, L, S, window) - first,
            0, kv_subs)
        qb = q_ref[0, 0]                                  # [block_q, D]

        def step(t):
            kb = _tile(pl, k_ref, t, block_k, kv_subs)    # [block_k, D]
            vb = _tile(pl, v_ref, t, block_k, kv_subs)
            s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            m_prev = m_i[...]                             # [block_q, 128]
            if causal:
                s = _causal_mask_scores(s, 0, qi * i32(block_q),
                                        (first + t) * i32(block_k), S - L,
                                        window)
            m_new = safe_m = jnp.maximum(m_prev,
                                         jnp.max(s, axis=1, keepdims=True))
            if causal:
                # a row may have seen nothing yet and keep m = -inf; exp
                # against 0 avoids the -inf - -inf = nan path while leaving
                # p and alpha exactly 0
                safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
            p = jnp.exp(s - _lanes(safe_m, block_k))
            alpha = jnp.exp(m_prev - safe_m)
            l_i[...] = l_i[...] * alpha + sum(
                p[:, j:j + _LANES] for j in range(0, block_k, _LANES))
            acc[...] = acc[...] * _lanes(alpha, D) + jax.lax.dot_general(
                p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_i[...] = m_new

        _for_tiles(pl, kv_subs, run_start, run_end, step)

        @pl.when(kj == grid_k - 1)
        def _fin():
            denom = jnp.maximum(jnp.sum(l_i[...], axis=1, keepdims=True), 1e-30)
            o_ref[0, 0] = (acc[...] / denom).astype(o_ref.dtype)
            lse_ref[0, 0] = m_i[...][:, :1] + jnp.log(denom)

    # layout: [B, H, L, D] for clean blocking
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)

    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H, L // block_q, grid_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), _row_block),
            pl.BlockSpec((1, 1, kv_major, D), kv_index),
            pl.BlockSpec((1, 1, kv_major, D), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), _row_block),
            # lse carried as [..., 1] — Mosaic requires the last two block dims
            # to be (8k, 128k) or equal to the array dims; (block_q, 1) is legal
            pl.BlockSpec((1, 1, block_q, 1), _row_block),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, L, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, L, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=_compiler_params(pltpu, interpret, block_q, block_k,
                                         4 * kv_major * D * k.dtype.itemsize),
        interpret=interpret,
        name="flash_fwd",
    )(qt, kt, vt)
    return jnp.swapaxes(out, 1, 2), lse[..., 0]
