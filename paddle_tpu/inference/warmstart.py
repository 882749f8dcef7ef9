"""Warm start for scale-out replicas (ISSUE 16): transfer, not compile.

A cold serving replica pays two bills before its first token: jit
compilation of the prefill/decode executables and weight
materialization. Both are already paid by every live peer — so a
scale-out replica fetches them instead:

  * **jit executable cache** — every replica runs with jax's persistent
    compilation cache pointed at its own ``--cache-dir``
    (``PADDLE_WARMSTART_CACHE_DIR``). ``WarmStartCache`` exports that
    directory as one tar archive keyed by the fleet's config/spec hash,
    served over the registered GET ``/warm_cache`` route on the
    replica's AdminServer; a new replica unpacks it into its OWN cache
    dir before building the batcher, so jax's first trace hits the
    cache instead of XLA.
  * **weights** — GET ``/weights`` ships the peer's parameter pytree as
    one npz frame (arrays + a JSON skeleton), so the new replica skips
    ``llama_init_params``. Every fleet replica builds from the same
    seeded spec, so peer weights are bit-identical to a local build —
    the fetch changes WHERE the bytes come from, never their values.

Both routes answer 404 when the requested spec hash does not match the
serving replica's (a config-drifted fleet must cold-start rather than
install a foreign executable cache), and 400 on a missing/malformed
``spec`` parameter.

``fetch_warm_cache`` / ``fetch_weights`` are the client side, each
guarded by the ``warmstart.fetch`` chaos site: an injected (or real)
fetch failure degrades to ``None`` + a flight record — the caller falls
back to the cold path, never wedges, and the fleet's tokens never
change (warm start moves compilation time, not numerics).
"""
from __future__ import annotations

import hashlib
import io
import json
import os
import tarfile
import urllib.request

import numpy as np

from ..distributed.resilience import chaos
from ..observability import metrics, recorder as _recorder, slo as _slo
from ..observability.admin import job_token
from ..utils import env_flags

__all__ = ["WarmStartCache", "spec_hash", "enable_jit_cache",
           "pack_cache_dir", "unpack_cache_archive", "pack_params",
           "unpack_params", "fetch_warm_cache", "fetch_weights"]

ENV_TIMEOUT = "PADDLE_WARMSTART_TIMEOUT_S"


def spec_hash(spec: dict) -> str:
    """Canonical hash of a fleet spec: sorted-keys JSON, sha256. Every
    replica of one fleet builds from the SAME spec dict, so this is the
    cache key that makes a peer's executables/weights installable."""
    blob = json.dumps(spec, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


def enable_jit_cache(cache_dir: str):
    """Point jax's persistent compilation cache at ``cache_dir`` with
    thresholds at zero — the serving executables are small on CPU CI,
    and a warm start that silently skipped caching them would measure
    cold. Idempotent; safe before any trace. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set the directory stays the
    variable's (one logged line); only the thresholds are applied."""
    import jax

    from ..utils.compile_cache import ENV_CACHE_DIR
    placed = os.environ.get(ENV_CACHE_DIR)
    if placed:
        # the cache was placed from outside: JAX reads the variable
        # itself, and a second path set here would split the cache
        _recorder.record("warmstart.cache_dir_yielded", echo=True,
                         message=f"[warmstart] {ENV_CACHE_DIR}={placed} is "
                                 f"set — not re-pointing the jit cache at "
                                 f"{cache_dir}",
                         env_dir=placed, cache_dir=cache_dir)
    else:
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # the GPU-only XLA side caches (kernel cache, fusion autotuner) get
    # ABSOLUTE PATHS UNDER cache_dir baked into the hashed compile
    # options — with them on, a peer's entries can never hit from a
    # different directory, which is the entire warm-start transfer. Off:
    # the key depends only on program + toolchain, so a fetched cache
    # serves any replica (they are inert on CPU/TPU anyway).
    jax.config.update("jax_persistent_cache_enable_xla_caches", "none")


# ------------------------------------------------------------- archives

def pack_cache_dir(cache_dir: str) -> bytes:
    """One tar frame of every file under ``cache_dir`` (relative paths,
    deterministic order). Empty dir → empty archive, still valid."""
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w") as tar:
        for root, dirs, files in os.walk(cache_dir):
            dirs.sort()
            for fn in sorted(files):
                full = os.path.join(root, fn)
                rel = os.path.relpath(full, cache_dir)
                tar.add(full, arcname=rel)
    return buf.getvalue()


def unpack_cache_archive(data: bytes, cache_dir: str) -> int:
    """Unpack a /warm_cache tar frame into ``cache_dir``; returns the
    file count. Rejects members that would escape the target dir."""
    os.makedirs(cache_dir, exist_ok=True)
    n = 0
    with tarfile.open(fileobj=io.BytesIO(data), mode="r") as tar:
        for m in tar.getmembers():
            if not m.isfile():
                continue
            name = os.path.normpath(m.name)
            if name.startswith("..") or os.path.isabs(name):
                raise ValueError(f"archive member escapes cache dir: "
                                 f"{m.name!r}")
            src = tar.extractfile(m)
            if src is None:
                continue
            dst = os.path.join(cache_dir, name)
            os.makedirs(os.path.dirname(dst) or cache_dir, exist_ok=True)
            with open(dst, "wb") as f:
                f.write(src.read())
            n += 1
    return n


# -------------------------------------------------------------- weights

def _pack_node(node, arrays: list):
    """JSON-able skeleton of a params pytree; array leaves become
    ``{"~a": i}`` references into the npz payload."""
    if isinstance(node, dict):
        return {"~d": {k: _pack_node(v, arrays) for k, v in node.items()}}
    if isinstance(node, (list, tuple)):
        return {"~l": [_pack_node(v, arrays) for v in node],
                "~t": isinstance(node, tuple)}
    if hasattr(node, "shape") and hasattr(node, "dtype"):
        arrays.append(np.asarray(node))
        return {"~a": len(arrays) - 1}
    return {"~v": node}  # plain scalar/str config leaf


def _unpack_node(skel, arrays):
    if "~d" in skel:
        return {k: _unpack_node(v, arrays) for k, v in skel["~d"].items()}
    if "~l" in skel:
        seq = [_unpack_node(v, arrays) for v in skel["~l"]]
        return tuple(seq) if skel.get("~t") else seq
    if "~a" in skel:
        import jax.numpy as jnp
        return jnp.asarray(arrays[f"a{skel['~a']}"])
    return skel.get("~v")


def pack_params(params) -> bytes:
    """One npz frame of a parameter pytree: arrays ``a0..aN`` plus the
    ``__tree__`` skeleton that reassembles them."""
    arrays: list = []
    skel = _pack_node(params, arrays)
    buf = io.BytesIO()
    np.savez(buf, __tree__=np.frombuffer(
        json.dumps(skel).encode(), dtype=np.uint8),
        **{f"a{i}": a for i, a in enumerate(arrays)})
    return buf.getvalue()


def unpack_params(data: bytes):
    """Reassemble a /weights npz frame into the parameter pytree (jax
    arrays, ready for the batcher)."""
    with np.load(io.BytesIO(data)) as z:
        skel = json.loads(bytes(z["__tree__"].tobytes()).decode())
        return _unpack_node(skel, z)


# ------------------------------------------------------------ the cache

class WarmStartCache:
    """The server side: export this replica's jit cache dir + weights,
    keyed by the fleet spec hash. Wired into ReplicaServer's AdminServer
    as GET /warm_cache and GET /weights (routes.py declares both)."""

    def __init__(self, spec: dict, cache_dir: str | None, params=None):
        self.hash = spec_hash(spec)
        self.cache_dir = cache_dir or None
        self._params = params

    def _check(self, query: dict):
        got = (query.get("spec") or [""])[0]
        if not got:
            return 400, {"ok": False, "reason": "spec=<hash> required"}
        if got != self.hash:
            return 404, {"ok": False,
                         "reason": f"spec hash mismatch (serving "
                                   f"{self.hash[:12]}…) — cold-start "
                                   "instead of installing a foreign "
                                   "cache"}
        return None

    def handle_warm_cache(self, query: dict):
        """GET /warm_cache?spec=<hash> → tar frame of the jit cache."""
        bad = self._check(query)
        if bad is not None:
            return bad
        if not self.cache_dir or not os.path.isdir(self.cache_dir):
            return 404, {"ok": False,
                         "reason": "no persistent jit cache on this "
                                   "replica (PADDLE_WARMSTART_CACHE_DIR "
                                   "unset)"}
        frame = pack_cache_dir(self.cache_dir)
        metrics.counter("warmstart.cache_served").inc()
        return 200, frame

    def handle_weights(self, query: dict):
        """GET /weights?spec=<hash> → npz frame of the params pytree."""
        bad = self._check(query)
        if bad is not None:
            return bad
        if self._params is None:
            return 404, {"ok": False, "reason": "no weights exported"}
        frame = pack_params(self._params)
        metrics.counter("warmstart.weights_served").inc()
        return 200, frame


# ------------------------------------------------------------ the fetch

def _fetch(peer: str, path: str, timeout: float) -> bytes:
    base = peer if peer.startswith("http") else f"http://{peer}"
    req = urllib.request.Request(
        base + path, headers={"X-Paddle-Job-Token": job_token()})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read()


def _timeout() -> float:
    return env_flags.get_float(ENV_TIMEOUT)


def fetch_warm_cache(peer: str, shash: str, cache_dir: str,
                     timeout: float | None = None) -> int | None:
    """Fetch a peer's jit cache archive into ``cache_dir``; returns the
    unpacked file count, or None on ANY failure (chaos-injected or
    real) — the caller compiles cold, flight record explains why."""
    t0 = _slo.now()
    try:
        chaos.hit("warmstart.fetch")
        data = _fetch(peer, f"/warm_cache?spec={shash}",
                      timeout if timeout is not None else _timeout())
        n = unpack_cache_archive(data, cache_dir)
    except Exception as e:
        metrics.counter("warmstart.fetch_failed").inc()
        _recorder.record("warmstart.fetch_failed", echo=True,
                         message=f"[warmstart] cache fetch from {peer} "
                                 f"failed ({type(e).__name__}: {e}) — "
                                 "cold compilation instead",
                         peer=peer, what="cache",
                         error=f"{type(e).__name__}: {e}")
        return None
    metrics.histogram("warmstart.fetch_s").observe(_slo.now() - t0)
    metrics.counter("warmstart.cache_fetched").inc()
    _recorder.record("warmstart.cache_fetched", peer=peer, files=n)
    return n


def fetch_weights(peer: str, shash: str, timeout: float | None = None):
    """Fetch a peer's weights pytree; returns params, or None on ANY
    failure — the caller initializes from the seeded spec instead
    (bit-identical by construction, just slower)."""
    t0 = _slo.now()
    try:
        chaos.hit("warmstart.fetch")
        data = _fetch(peer, f"/weights?spec={shash}",
                      timeout if timeout is not None else _timeout())
        params = unpack_params(data)
    except Exception as e:
        metrics.counter("warmstart.fetch_failed").inc()
        _recorder.record("warmstart.fetch_failed", echo=True,
                         message=f"[warmstart] weight fetch from {peer} "
                                 f"failed ({type(e).__name__}: {e}) — "
                                 "initializing from the seeded spec",
                         peer=peer, what="weights",
                         error=f"{type(e).__name__}: {e}")
        return None
    metrics.histogram("warmstart.fetch_s").observe(_slo.now() - t0)
    metrics.counter("warmstart.weights_fetched").inc()
    _recorder.record("warmstart.weights_fetched", peer=peer,
                     bytes=len(data))
    return params
