"""KV-page transfer wire format for disaggregated serving (ISSUE 11).

A prefill replica finishes a prompt pass holding the request's live KV
pages in its pool; a decode replica needs those SAME rows in its own pool
before it can stream tokens. This module is the wire in between:

  * **serialize** — the parked pages' payload slices (and, quantized,
    their per-(row, head) scale slices) leave the pool in LOGICAL page
    order and are packed into one RAW byte string inside the blob dict.
    The wire dtype is whatever the pool already stores (``quant/codec``
    int8/fp8 payload + f32 block scales — the ~4× cheaper format the
    ROADMAP names), with a float32 fallback for unquantized pools.
  * **framing** (ISSUE 12 satellite, ROADMAP disagg follow-up 3) — on
    the HTTP wire the blob travels as a LENGTH-PREFIXED BINARY FRAME
    (:func:`pack_frame` / :func:`unpack_frame`: magic + u32 header
    length + JSON header + raw payload), replacing the base64-inside-
    JSON encoding that inflated every transfer by 4/3 (~33% transport
    cut, plus the JSON string-escape walk over megabytes of payload).
    The payload bytes are never re-encoded: frame transport cost is
    ``wire_bytes`` plus a ~hundred-byte header.
  * **install** — the blob lands in the destination pool via
    ``models.llama_paged.scatter_pages`` at freshly allocated page ids.
    When source and destination share a kv_dtype (the fleet builds every
    replica from ONE spec) the quantized payload+scales transfer
    VERBATIM — the destination pool is bit-identical to the source, so
    greedy decode is token-identical to a never-disaggregated serve.
    Mismatched pools (operator misconfiguration, or deliberate
    precision-change handoff) go through dequantize → re-encode.
  * **scale granularity** (ISSUE 11 satellite, the ROADMAP
    per-page-coarser carry-over): ``scale_gran="page"`` re-blocks the
    quantization to ONE scale per (page, head) — ``~page_size×`` fewer
    scale bytes on the wire. The POOL keeps its per-(row, head) layout
    on both sides (the read paths untouched); the
    coarser blocks exist only in flight, at the cost of one
    requantization whose greedy-agreement impact is measured and pinned
    by tests/test_disagg_serving.py. Rows past the live length are
    zeroed before re-blocking so bucket-pad garbage cannot inflate a
    page's absmax.

Accounting (:func:`wire_breakdown` / :func:`wire_ratio_vs_f32`) is the
acceptance-criteria arithmetic: payload itemsize + scale overhead per
(row, head) block, quantized ≤ 0.30× the f32 bytes for the same live
tokens at deployment head dims (pinned at both granularities).
"""
from __future__ import annotations

import json
import struct

import jax.numpy as jnp
import numpy as np

from ...quant.codec import (MODES, dequantize_lastdim, normalize_scale_gran,
                            quantize_lastdim, scale_itemsize, wire_itemsize)

__all__ = ["serialize_pages", "install_pages", "wire_breakdown",
           "wire_ratio_vs_f32", "pages_in_blob", "check_blob_geometry",
           "pack_frame", "unpack_frame", "blob_meta", "slice_blob"]

# wire schema version: an install refuses a blob it cannot parse instead
# of corrupting a pool with misaligned bytes
_WIRE_V = 2

# binary frame magic: "paddle kv" + frame-format version
_FRAME_MAGIC = b"PKV2"

# the f32 fallback wire dtype for unquantized pools: bf16/f32 pool values
# round-trip exactly through float32, so the transfer is value-identical
# whatever the model dtype
_F32 = np.float32


def _np_wire_dtype(mode: str):
    return np.dtype(jnp.dtype(MODES[mode][0]))


def _geometry(config, page_size: int):
    return (int(config.num_hidden_layers), int(page_size),
            int(config.num_key_value_heads), int(config.head_dim))


# ------------------------------------------------------------- accounting

def wire_breakdown(config, n_pages: int, page_size: int,
                   kv_dtype: str | None,
                   scale_gran: str = "row") -> dict:
    """Exact wire byte accounting for ``n_pages`` transferred pages:
    ``{"payload_bytes", "scale_bytes", "wire_bytes"}`` (K+V, all layers).
    This is the number the bench reports and the acceptance criterion
    asserts — raw packed bytes, and since the binary framing (ISSUE 12)
    also the transport cost to within one small frame header (the old
    base64-JSON dressing paid 4/3× on top of it)."""
    L, ps, kv, hd = _geometry(config, page_size)
    rows = 2 * L * int(n_pages) * ps * kv          # (row, head) blocks, K+V
    if kv_dtype is None:
        return {"payload_bytes": rows * hd * 4, "scale_bytes": 0,
                "wire_bytes": rows * hd * 4}
    payload = rows * hd * wire_itemsize(kv_dtype)
    if normalize_scale_gran(scale_gran) == "row":
        scales = rows * scale_itemsize()
    else:  # one scale per (page, head) instead of per (row, head)
        scales = 2 * L * int(n_pages) * kv * scale_itemsize()
    return {"payload_bytes": payload, "scale_bytes": scales,
            "wire_bytes": payload + scales}


def wire_ratio_vs_f32(config, page_size: int, kv_dtype: str | None,
                      scale_gran: str = "row") -> float:
    """Quantized wire bytes over the f32 fallback's, same live tokens —
    the ≤ 0.30× acceptance number (per-page ratio == per-request ratio,
    pages cancel)."""
    q = wire_breakdown(config, 1, page_size, kv_dtype, scale_gran)
    f = wire_breakdown(config, 1, page_size, None)
    return q["wire_bytes"] / f["wire_bytes"]


def pages_in_blob(blob: dict) -> int:
    return int(blob["n_pages"])


# -------------------------------------------------------------- serialize

def _live_row_mask(n_pages: int, page_size: int, tlen: int):
    """[n_pages, page_size] float32 — 1.0 where the global row index is a
    live prompt position, 0.0 for bucket-pad garbage past ``tlen``."""
    rows = (np.arange(n_pages)[:, None] * page_size
            + np.arange(page_size)[None, :])
    return (rows < int(tlen)).astype(np.float32)


def serialize_pages(config, cache, page_ids, tlen: int, first: int,
                    kv_dtype: str | None,
                    scale_gran: str = "row") -> dict:
    """Pack one request's parked pages into the JSON-able wire blob.

    ``page_ids`` are the slot's PHYSICAL pages in logical order (they
    never leave the process — the blob is positional); ``tlen`` is the
    live prompt length, ``first`` the prefill-sampled first token the
    decode side resumes from. Returns the blob dict; the pool is not
    mutated (the caller frees the pages after this returns)."""
    from ...models.llama_paged import gather_pages

    scale_gran = normalize_scale_gran(scale_gran)
    L, _, kv, hd = _geometry(config, cache["k"][0].shape[1])
    ps = int(cache["k"][0].shape[1])
    n_pages = len(page_ids)
    rows = gather_pages(cache, page_ids)
    payload_parts: list[bytes] = []
    scale_parts: list[bytes] = []
    if kv_dtype is None:
        for l in range(L):
            payload_parts.append(np.asarray(rows["k"][l], _F32).tobytes())
            payload_parts.append(np.asarray(rows["v"][l], _F32).tobytes())
    elif scale_gran == "row":
        # pool-native blocks travel verbatim: payload bytes + per-(row,
        # head) f32 scales — the destination pool lands bit-identical
        for l in range(L):
            payload_parts.append(np.asarray(rows["k"][l]).tobytes())
            payload_parts.append(np.asarray(rows["v"][l]).tobytes())
            scale_parts.append(np.asarray(rows["k_scale"][l],
                                          _F32).tobytes())
            scale_parts.append(np.asarray(rows["v_scale"][l],
                                          _F32).tobytes())
    else:
        # page granularity: dequantize to values, zero dead rows (pad
        # garbage must not inflate a page's absmax), re-block per
        # (page, head) over the page's ps×hd values, requantize
        mask = _live_row_mask(n_pages, ps, tlen)[..., None, None]
        for l in range(L):
            for leaf, sleaf in (("k", "k_scale"), ("v", "v_scale")):
                vals = dequantize_lastdim(
                    jnp.asarray(rows[leaf][l]),
                    jnp.asarray(rows[sleaf][l]), jnp.float32)
                vals = vals * jnp.asarray(mask)
                blocks = vals.transpose(0, 2, 1, 3).reshape(
                    n_pages, kv, ps * hd)
                q, s = quantize_lastdim(blocks, kv_dtype)
                payload_parts.append(np.asarray(q).tobytes())
                scale_parts.append(np.asarray(s, _F32).tobytes())
    payload_bytes = sum(len(p) for p in payload_parts)
    scale_bytes = sum(len(p) for p in scale_parts)
    raw = b"".join(payload_parts + scale_parts)
    return {
        "v": _WIRE_V,
        "tlen": int(tlen), "first": int(first),
        "n_pages": n_pages, "page_size": ps,
        "layers": L, "kv_heads": kv, "head_dim": hd,
        "kv_dtype": kv_dtype, "scale_gran": scale_gran,
        "payload_bytes": payload_bytes, "scale_bytes": scale_bytes,
        "wire_bytes": payload_bytes + scale_bytes,
        "data": raw,   # RAW packed bytes; the HTTP hops frame them binary
    }


def _blob_segments(blob: dict):
    """The packed-byte layout of one blob as (dtype, shape) pairs in
    serialization order, each with the page count on axis 0 — the ONE
    authoritative walk :func:`slice_blob`, ``_blob_values`` AND
    ``install_pages``' verbatim fast path all consume. Mirrors
    :func:`serialize_pages` exactly; a wire-format change edits the two
    of them together and nothing else."""
    L, n, ps = int(blob["layers"]), int(blob["n_pages"]), \
        int(blob["page_size"])
    kv, hd = int(blob["kv_heads"]), int(blob["head_dim"])
    mode, gran = blob["kv_dtype"], blob.get("scale_gran", "row")
    if mode is None:
        return [(_F32, (n, ps, kv, hd))] * (2 * L)
    wdt = _np_wire_dtype(mode)
    if gran == "row":
        return [(wdt, (n, ps, kv, hd))] * (2 * L) \
            + [(_F32, (n, ps, kv))] * (2 * L)
    return [(wdt, (n, kv, ps * hd))] * (2 * L) + [(_F32, (n, kv))] * (2 * L)


def slice_blob(blob: dict, from_page: int) -> dict:
    """A blob covering only pages [from_page, n_pages) — the prefix-
    sharing transfer shrink (ISSUE 13): when the DECODE pool's prefix
    cache already holds the request's leading pages (the /kv_transfer
    probe says so), the wire carries only the unshared remainder and the
    install maps the prefix from the cache. ``from_page`` accumulates in
    the blob header (``n_pages`` becomes the remainder) so geometry and
    byte-count checks stay exact; page-granular scale blocks slice the
    already-quantized bytes, so the sliced pages land bit-identical to a
    full transfer's. Callers keep ``from_page < n_pages`` — the tail page
    always travels (it is the one decode writes into)."""
    k = int(from_page)
    n = int(blob["n_pages"])
    if k <= 0:
        return blob
    if k >= n:
        raise ValueError(f"slice_blob: from_page {k} must leave at least "
                         f"the tail page of {n}")
    raw = _Reader(bytes(blob["data"]))
    parts: list[bytes] = []
    payload_bytes = scale_bytes = 0
    segs = _blob_segments(blob)
    for i, (dt, shape) in enumerate(segs):
        arr = raw.take(dt, shape)[k:]
        b = np.ascontiguousarray(arr).tobytes()
        parts.append(b)
        # scale segments are the trailing half only for quantized blobs
        if blob["kv_dtype"] is not None and i >= len(segs) // 2:
            scale_bytes += len(b)
        else:
            payload_bytes += len(b)
    out = dict(blob)
    out["n_pages"] = n - k
    out["from_page"] = int(blob.get("from_page", 0) or 0) + k
    out["payload_bytes"] = payload_bytes
    out["scale_bytes"] = scale_bytes
    out["wire_bytes"] = payload_bytes + scale_bytes
    out["data"] = b"".join(parts)
    return out


# ---------------------------------------------------------------- framing

def blob_meta(blob: dict) -> dict:
    """The blob WITHOUT its payload — the JSON-able half that rides in
    result records and frame headers (geometry, wire accounting, tlen/
    first). Everything :func:`check_blob_geometry` needs except the byte
    count, which the frame carries as raw length."""
    return {k: v for k, v in blob.items() if k != "data"}


def pack_frame(header: dict, payload: bytes) -> bytes:
    """One length-prefixed binary frame: ``PKV2 | u32 header_len |
    header JSON | payload``. The payload is appended VERBATIM — no
    base64, no JSON escaping — so transport cost is ``len(payload)``
    plus a ~hundred-byte header instead of the old 4/3× inflation."""
    hdr = json.dumps(header).encode()
    return b"".join((_FRAME_MAGIC, struct.pack("<I", len(hdr)), hdr,
                     payload))


def unpack_frame(buf) -> tuple[dict, bytes]:
    """``pack_frame``'s inverse → (header, payload). Raises ValueError on
    a foreign or truncated frame — the /kv_transfer boundary answers 400
    with it instead of feeding misaligned bytes to an install."""
    buf = bytes(buf)
    if len(buf) < 8 or buf[:4] != _FRAME_MAGIC:
        raise ValueError("not a kv transfer frame (bad magic)")
    n = struct.unpack("<I", buf[4:8])[0]
    if len(buf) < 8 + n:
        raise ValueError(f"kv transfer frame truncated mid-header "
                         f"(need {8 + n} bytes, have {len(buf)})")
    try:
        header = json.loads(buf[8:8 + n])
    except ValueError as e:
        raise ValueError(f"kv transfer frame header unparsable: {e}")
    if not isinstance(header, dict):
        raise ValueError("kv transfer frame header is not an object")
    return header, buf[8 + n:]


# ---------------------------------------------------------------- install

class _Reader:
    def __init__(self, raw: bytes):
        self.raw, self.off = raw, 0

    def take(self, dtype, shape) -> np.ndarray:
        dt = np.dtype(dtype)
        n = int(np.prod(shape)) * dt.itemsize
        if self.off + n > len(self.raw):
            raise ValueError("kv transfer blob truncated "
                             f"(need {self.off + n}, have {len(self.raw)})")
        out = np.frombuffer(self.raw, dtype=dt, count=int(np.prod(shape)),
                            offset=self.off).reshape(shape)
        self.off += n
        return out


def _check_geometry(blob: dict, config, page_size: int):
    L, ps, kv, hd = _geometry(config, page_size)
    want = {"layers": L, "page_size": ps, "kv_heads": kv, "head_dim": hd}
    for k, v in want.items():
        if int(blob.get(k, -1)) != v:
            raise ValueError(
                f"kv transfer blob does not fit this pool: {k}="
                f"{blob.get(k)!r}, pool has {v} — prefill and decode "
                "replicas must build from one spec")
    if int(blob.get("v", -1)) != _WIRE_V:
        raise ValueError(f"unknown kv transfer wire version {blob.get('v')!r}")


def check_blob_geometry(blob: dict, config, page_size: int) -> int:
    """The admission-time half of install validation: wire version,
    layer/head/page geometry, a known kv_dtype/granularity, and the
    packed byte count all fit this pool. Raises ValueError otherwise;
    returns the blob's page count. This is what a /kv_transfer handler
    answers 400 with — a drifted blob must be refused at the wire, never
    crash a serve loop mid-install."""
    _check_geometry(blob, config, page_size)
    n = int(blob.get("n_pages", -1))
    if n < 1:
        raise ValueError(f"kv transfer blob has n_pages={n}")
    tlen = int(blob.get("tlen", -1))
    k = int(blob.get("from_page", 0) or 0)
    total = 0 if tlen < 1 else (tlen - 1) // int(page_size) + 1
    if k < 0 or k >= max(1, total):
        # a sliced blob (ISSUE 13) must leave at least the tail page —
        # the one decode writes into is never supplied by a prefix cache
        raise ValueError(
            f"kv transfer blob from_page={k} out of range for "
            f"tlen={tlen} at page_size={page_size}")
    if tlen < 1 or n != total - k:
        # the install allocates pages_for(tlen) - from_page pages and
        # scatter refuses a count mismatch — catch the inconsistency at
        # the boundary so it answers 400, not a serve-loop-side terminal
        # error (and so the pool-pressure gate never reserves an
        # inflated page count)
        raise ValueError(
            f"kv transfer blob holds {n} pages for tlen={tlen} "
            f"(from_page={k}) at page_size={page_size} — inconsistent")
    mode, gran = blob.get("kv_dtype"), blob.get("scale_gran", "row")
    if mode is not None and mode not in MODES:
        raise ValueError(f"unknown kv transfer wire dtype {mode!r}")
    acct = wire_breakdown(config, n, page_size, mode,
                          normalize_scale_gran(gran))
    # raw length check — NO decode, no copy: this runs on the HTTP
    # handler thread per transfer; the binary frame already handed us
    # the exact payload bytes. Value-level corruption that preserves the
    # length surfaces at install, where it costs one request (the serve
    # loop's install guard), never the loop.
    data = blob.get("data")
    if not isinstance(data, (bytes, bytearray, memoryview)):
        raise ValueError("kv transfer blob data missing or misframed "
                         "(raw bytes expected)")
    if len(data) != acct["wire_bytes"]:
        raise ValueError(
            f"kv transfer blob carries {len(data)} bytes, geometry says "
            f"{acct['wire_bytes']} — truncated or mispacked")
    return n


def _blob_values(blob: dict, raw: _Reader):
    """Yield per-layer (k_values, v_values) float32 [n_pages, ps, KV, hd]
    reconstructed from the wire — the universal intermediate every
    mismatched-format install goes through. Driven by
    :func:`_blob_segments`, the ONE authoritative layout walk."""
    L, n, ps = int(blob["layers"]), int(blob["n_pages"]), \
        int(blob["page_size"])
    kv, hd = int(blob["kv_heads"]), int(blob["head_dim"])
    mode, gran = blob["kv_dtype"], blob.get("scale_gran", "row")
    arrs = [raw.take(dt, shape) for dt, shape in _blob_segments(blob)]
    if mode is None:
        for l in range(L):
            yield np.asarray(arrs[2 * l]), np.asarray(arrs[2 * l + 1])
        return
    payload, scales = arrs[:2 * L], arrs[2 * L:]
    for l in range(L):
        kvals = dequantize_lastdim(jnp.asarray(payload[2 * l]),
                                   jnp.asarray(scales[2 * l]))
        vvals = dequantize_lastdim(jnp.asarray(payload[2 * l + 1]),
                                   jnp.asarray(scales[2 * l + 1]))
        if gran == "row":
            yield np.asarray(kvals), np.asarray(vvals)
        else:
            yield (np.asarray(kvals.reshape(n, kv, ps, hd)
                              .transpose(0, 2, 1, 3)),
                   np.asarray(vvals.reshape(n, kv, ps, hd)
                              .transpose(0, 2, 1, 3)))


def install_pages(cache, config, page_ids, blob: dict,
                  kv_dtype: str | None):
    """Write a transfer blob into the destination pool at ``page_ids``
    (freshly allocated, logical order). Returns the new cache.

    The bit-exact fast path — source and destination pools share a
    kv_dtype and the wire is row-granular — writes payload + scales
    verbatim. Everything else reconstructs f32 values and re-encodes into
    the destination's format (quantize per-row, or cast for an
    unquantized pool)."""
    from ...models.llama_paged import scatter_pages

    ps = int(cache["k"][0].shape[1])
    _check_geometry(blob, config, ps)
    if int(blob["n_pages"]) != len(page_ids):
        raise ValueError(f"blob holds {blob['n_pages']} pages, "
                         f"{len(page_ids)} allocated")
    L = int(blob["layers"])
    mode, gran = blob["kv_dtype"], blob.get("scale_gran", "row")
    raw = _Reader(bytes(blob["data"]))

    if mode is not None and mode == kv_dtype and gran == "row":
        arrs = [raw.take(dt, shape) for dt, shape in _blob_segments(blob)]
        rows = {"k": arrs[0:2 * L:2], "v": arrs[1:2 * L:2],
                "k_scale": arrs[2 * L::2], "v_scale": arrs[2 * L + 1::2]}
        return scatter_pages(cache, page_ids, rows)

    if kv_dtype is None:
        rows = {"k": [], "v": []}
        for kvals, vvals in _blob_values(blob, raw):
            rows["k"].append(kvals)
            rows["v"].append(vvals)
        return scatter_pages(cache, page_ids, rows)

    # destination pool is quantized: re-encode per (row, head) — the
    # pool's native block — whatever granularity or precision arrived
    rows = {"k": [], "v": [], "k_scale": [], "v_scale": []}
    for kvals, vvals in _blob_values(blob, raw):
        kq, ks = quantize_lastdim(jnp.asarray(kvals), kv_dtype)
        vq, vs = quantize_lastdim(jnp.asarray(vvals), kv_dtype)
        rows["k"].append(np.asarray(kq))
        rows["v"].append(np.asarray(vq))
        rows["k_scale"].append(np.asarray(ks, _F32))
        rows["v_scale"].append(np.asarray(vs, _F32))
    return scatter_pages(cache, page_ids, rows)
