"""TokenDataLoader — python binding for the native data-pipeline core.

Reference capability: the C++ data feed stack (fluid/framework/data_feed.cc).
See io/native/datafeed.cpp. Builds the .so on first use (g++, cached);
falls back to a numpy implementation when no compiler is available.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import warnings

import numpy as np

from ..observability import spans as _spans

__all__ = ["TokenDataLoader", "write_token_file"]

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "libptdatafeed.so")
_lib_lock = threading.Lock()
_lib: list = [None]


def _load_lib():
    with _lib_lock:
        if _lib[0] is not None:
            return _lib[0]
        try:
            if not os.path.exists(_SO_PATH):
                subprocess.run(["make", "-C", _NATIVE_DIR], check=True,
                               capture_output=True)
            lib = ctypes.CDLL(_SO_PATH)
        except (OSError, subprocess.CalledProcessError) as e:
            # said once per process: the numpy path is correct but slower,
            # and an input pipeline that changed must not change in silence
            warnings.warn(f"native data feeder unavailable ({e}); "
                          f"TokenDataLoader uses its numpy implementation",
                          RuntimeWarning, stacklevel=3)
            _lib[0] = False
            return False
        lib.ptdf_open.restype = ctypes.c_void_p
        lib.ptdf_open.argtypes = [ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
                                  ctypes.c_uint64, ctypes.c_int, ctypes.c_int,
                                  ctypes.c_int]
        lib.ptdf_next.restype = ctypes.c_int
        lib.ptdf_next.argtypes = [ctypes.c_void_p,
                                  np.ctypeslib.ndpointer(np.int32, flags="C")]
        lib.ptdf_len.restype = ctypes.c_int64
        lib.ptdf_len.argtypes = [ctypes.c_void_p]
        lib.ptdf_close.argtypes = [ctypes.c_void_p]
        _lib[0] = lib
        return lib


def write_token_file(path, tokens, dtype=np.uint16):
    np.asarray(tokens, dtype=dtype).tofile(path)


def synthetic_corpus(n_tokens, vocab_size=512, seed=0, branching=8):
    """Deterministic Zipf-Markov token corpus for zero-egress convergence
    runs: each token has `branching` likely successors with Zipfian weights,
    so the stream has real sequential structure (bigram entropy well below
    log(V)) that a model must LEARN — unlike an i.i.d. or repeated batch, a
    memorized answer does not exist. Returns int32 [n_tokens]."""
    rng = np.random.RandomState(seed)
    succ = rng.randint(0, vocab_size, (vocab_size, branching)).astype(np.int32)
    w = 1.0 / np.arange(1, branching + 1)
    cdf = np.cumsum(w / w.sum())
    draws = rng.rand(n_tokens)
    choice = np.searchsorted(cdf, draws).clip(0, branching - 1)
    out = np.empty(n_tokens, np.int32)
    state = 0
    for i in range(n_tokens):
        state = succ[state, choice[i]]
        out[i] = state
    return out


class TokenDataLoader:
    """Infinite iterator of (inputs [B,T], labels [B,T]) int32 batches cut
    from a memory-mapped token corpus; native threads keep a ring of ready
    batches ahead of the training step."""

    def __init__(self, path, batch_size, seq_len, seed=0, token_bytes=2,
                 num_threads=2, ring=4):
        self.path = str(path)
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.seed = seed
        self.token_bytes = token_bytes
        self._buf = np.empty((batch_size, seq_len + 1), np.int32)
        lib = _load_lib()
        self._native = bool(lib)
        if self._native:
            self._lib = lib
            self._h = lib.ptdf_open(self.path.encode(), batch_size, seq_len,
                                    seed, token_bytes, num_threads, ring)
            if not self._h:
                raise OSError(f"cannot open token file: {path}")
            self._n_tokens = lib.ptdf_len(self._h)
        else:  # numpy fallback
            dt = np.uint16 if token_bytes == 2 else np.int32
            self._mm = np.memmap(self.path, dtype=dt, mode="r")
            self._n_tokens = len(self._mm)
            self._rng_i = 0

    @property
    def num_tokens(self):
        return int(self._n_tokens)

    def __iter__(self):
        return self

    @_spans.traced("loader.next", cat="data")
    def __next__(self):
        if self._native:
            rc = self._lib.ptdf_next(self._h, self._buf)
            if rc != 0:
                raise StopIteration
            arr = self._buf
        else:
            rng = np.random.RandomState((self.seed * 2654435761 + self._rng_i)
                                        % (2 ** 32))
            self._rng_i += 1
            row = self.seq_len + 1
            starts = rng.randint(0, self._n_tokens - row, self.batch_size)
            arr = np.stack([self._mm[s:s + row] for s in starts]).astype(np.int32)
        return arr[:, :-1].copy(), arr[:, 1:].copy()

    def close(self):
        if self._native and getattr(self, "_h", None):
            self._lib.ptdf_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
