"""Block-wise quantization codecs — the ONE numerics core both quantized
consumers share (ISSUE 10 tentpole).

Two codecs, both symmetric, both with per-block scales:

  * ``int8`` — round-to-nearest onto the [-127, 127] integer grid;
    ``scale = absmax / 127`` per block, payload ``jnp.int8``.
  * ``fp8``  — saturating cast onto float8 e4m3 (±448 finite range);
    ``scale = absmax / 448`` per block, payload ``jnp.float8_e4m3fn``.
    The cast clips BEFORE converting: a bare ``astype`` maps out-of-range
    values to NaN on this jax, which would poison every consumer sum.

A "block" is the LAST axis of whatever the caller hands in: the allreduce
path reshapes its flat payload to ``[n_blocks, block_size]``
(``PADDLE_QUANT_BLOCK``), the KV-page path quantizes per (row, kv-head)
with the ``head_dim`` vector as the block. Scales are always float32 —
the scale multiply is where accumulated error would compound, and one f32
per block is noise next to the payload bytes it describes.

Contracts (pinned by tests/test_quant.py):

  * **round-trip exactness where representable** — any tensor whose
    block values already sit on ``scale × grid`` (int8: integers in
    [-127, 127] times the block scale; fp8: e4m3-representable values
    times the block scale) round-trips bitwise through
    quantize→dequantize. All-zero blocks round-trip to exact zeros (the
    scale floor below keeps 0/scale finite).
  * **jittable** — pure jnp ops, no host sync, safe under jit/shard_map
    and as a Pallas interpret-mode building block.
  * **monotone** — dequantized values never exceed the block absmax
    (clipping is saturating, never wrapping).
"""
from __future__ import annotations

import jax.numpy as jnp

__all__ = ["MODES", "SCALE_DTYPE", "SCALE_GRANS", "wire_dtype",
           "wire_itemsize", "scale_itemsize", "quantize_lastdim",
           "dequantize_lastdim", "normalize_kv_dtype",
           "normalize_scale_gran"]

# mode -> (payload dtype, qmax = largest representable magnitude on the grid)
MODES = {
    "int8": (jnp.int8, 127.0),
    "fp8": (jnp.float8_e4m3fn, 448.0),
}

# scale floor: an all-zero block must quantize to zeros, not 0/0 = NaN.
# Any positive denormal-safe float works — dequantized zeros are exact
# regardless of the floor's value (0 * scale == 0).
_SCALE_FLOOR = 1e-30

SCALE_DTYPE = jnp.float32


# kv_dtype spellings that mean "pages in the model dtype" (the pre-quant
# layout): the engine and both benches parse the knob through ONE list
_KV_DTYPE_OFF = ("", "0", "off", "bf16", "bfloat16", "native")


def normalize_kv_dtype(raw) -> str | None:
    """The ONE parser for the kv_dtype knob (engine argument and
    PADDLE_SERVE_KV_DTYPE alike): None for every "unquantized" spelling,
    the codec mode for int8/fp8, a loud ValueError for typos — a typo'd
    dtype must not silently serve full precision while the operator
    believes the pool is quantized."""
    v = (raw or "").strip().lower()
    if v in _KV_DTYPE_OFF:
        return None
    if v not in MODES:
        raise ValueError(f"unknown kv_dtype {v!r} "
                         "(int8 | fp8 | bf16/'' for unquantized)")
    return v


# KV scale granularities for the disaggregated page-transfer wire
# (ISSUE 11): "row" ships the pool's native per-(row, head) scales
# verbatim (bit-exact transfer); "page" re-blocks to ONE scale per
# (page, head) — ~page_size× fewer scale bytes on the wire, paid for with
# a requantization pass whose accuracy cost is measured and pinned in
# tests/test_disagg_serving.py. The POOL layout never changes — this is a
# wire format, so the read paths are untouched.
SCALE_GRANS = ("row", "page")


def normalize_scale_gran(raw) -> str:
    """The ONE parser for the PADDLE_SERVE_KV_SCALE_GRAN knob: ''/None
    mean the default "row"; anything else must name a granularity — a
    typo'd knob must not silently ship the fat wire the operator believes
    they shrank."""
    v = (raw or "").strip().lower()
    if not v:
        return "row"
    if v not in SCALE_GRANS:
        raise ValueError(f"unknown KV scale granularity {v!r} "
                         f"(one of {SCALE_GRANS})")
    return v


def wire_dtype(mode: str):
    """The payload dtype that travels (wire or HBM) for `mode`."""
    return MODES[mode][0]


def wire_itemsize(mode: str) -> int:
    return jnp.dtype(MODES[mode][0]).itemsize


def scale_itemsize() -> int:
    return jnp.dtype(SCALE_DTYPE).itemsize


def quantize_lastdim(x, mode: str):
    """Quantize `x` with the LAST axis as the block.

    Returns ``(payload, scale)``: payload has x's shape in the mode's wire
    dtype, scale has shape ``x.shape[:-1]`` in float32 with
    ``scale = max(absmax, floor) / qmax`` so ``payload * scale ≈ x``.
    """
    dt, qmax = MODES[mode]
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(absmax, _SCALE_FLOOR) / jnp.float32(qmax)
    scaled = xf / scale[..., None]
    if mode == "int8":
        q = jnp.clip(jnp.round(scaled), -qmax, qmax).astype(dt)
    else:
        # saturate BEFORE the cast: float8_e4m3fn astype maps overflow to
        # NaN, and one NaN lane would poison a whole reduction block
        q = jnp.clip(scaled, -qmax, qmax).astype(dt)
    return q, scale.astype(SCALE_DTYPE)


def dequantize_lastdim(payload, scale, out_dtype=jnp.float32):
    """Inverse of :func:`quantize_lastdim`: ``payload * scale`` in f32,
    cast to `out_dtype` last (the f32 product is the accumulation-ready
    value the EQuARX reduce consumes directly)."""
    return (payload.astype(jnp.float32)
            * scale.astype(jnp.float32)[..., None]).astype(out_dtype)
