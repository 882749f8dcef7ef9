"""The gated delta rule (linear attention with a decayed, corrected state).

Per head, with a state ``S`` in R^{dv x dk}, a log-decay ``g_t <= 0`` and a
write strength ``beta_t``::

    S'  = exp(g_t) S_{t-1}
    u   = beta_t (v_t - S' k_t)
    S_t = S' + u k_t^T          (= exp(g_t) S_{t-1} (I - beta_t k_t k_t^T)
    o_t = S_t q_t                  + beta_t v_t k_t^T)

``gdn_chunk_scan`` is the chunked WY form of the
published algorithm for a whole prompt: inside a chunk of ``C`` tokens only
matrix products, across chunks a ``lax.scan`` that carries ``S`` in
float32. ``gdn_step`` is one token of every slot of a serving batch.

The chunk form. ``G_i`` is the running sum of ``g`` inside the chunk,
``S_0`` the state the chunk starts from::

    A_ij = beta_i exp(G_i - G_j) (k_i . k_j)      j <  i, else 0
    T    = (I + A)^-1
    U    = T (beta V) - T (beta e^G K) S_0^T      the rows u_i
    O    = (e^G Q) S_0^T + M U                    M_ij = exp(G_i - G_j)
    S_C  = e^{G_C} S_0 + U^T (e^{G_C - G} K)             (q_i . k_j), j <= i

Three things it is careful about. Decays only ever appear as differences
``exp(G_i - G_j)`` with ``i >= j`` (all <= 1): ``exp(-G_j)`` alone overflows
float32 when a token decays by e^-2.5 and a chunk holds 64 of them.
Positions at or past ``length`` (bucket padding) get ``g = 0`` and ``beta =
0``: they leave the state as the last real token left it. And ``A`` is
strictly lower triangular, so nilpotent: ``(I + A)^-1 = (I - A)(I + A^2)(I +
A^4)...`` is exact after log2(C) factors, all of them batched products;
a row-by-row substitution is a chain of C dependent steps, which a TPU
runs badly. Those small products are made at the highest matmul precision
(a TPU rounds float32 operands to bfloat16 otherwise, and the inverse
amplifies it); the large ones take their operands as they come, as the
published kernels do.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["gdn_chunk_scan", "gdn_step", "CHUNK"]

CHUNK = 64
_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def gdn_step(q, k, v, g, beta, state):
    """One token of every slot. q, k [B, H, dk]; v [B, H, dv]; g, beta
    [B, H]; state [B, H, dv, dk] float32. Returns (o [B, H, dv] float32,
    the new state). Elementwise products and sums in float32, no matrix
    unit: a step is bound by reading and writing the state. ``o`` is taken
    as ``S' q + u (k . q)``, so that both sums over the state's rows read
    the old state in one pass and the new one is written in a second."""
    q, k, v = q.astype(_F32), k.astype(_F32), v.astype(_F32)
    decay = jnp.exp(g.astype(_F32))[..., None]              # [B, H, 1]
    kb, qb = k[:, :, None, :], q[:, :, None, :]
    Sk = jnp.sum(state * kb, axis=-1) * decay               # S' k  [B, H, dv]
    Sq = jnp.sum(state * qb, axis=-1) * decay               # S' q
    u = beta.astype(_F32)[..., None] * (v - Sk)
    o = Sq + u * jnp.sum(k * q, axis=-1, keepdims=True)
    new = state * decay[..., None] + u[..., None] * kb
    return o, new


def _unit_lower_inverse(A):
    """(I + A)^-1 for strictly lower triangular A [..., C, C]: the product
    (I + N)(I + N^2)(I + N^4)... with N = -A, exact once the power reaches
    C (N is nilpotent)."""
    C = A.shape[-1]
    N = -A
    inv = jnp.eye(C, dtype=A.dtype) + N
    power = 2
    while power < C:
        N = jnp.matmul(N, N, precision=_HI)
        inv = inv + jnp.matmul(inv, N, precision=_HI)
        power *= 2
    return inv


def gdn_chunk_scan(q, k, v, g, beta, initial_state=None, length=None,
                   chunk: int = CHUNK):
    """The rule over one sequence, chunk by chunk. q, k [T, H, dk]; v [T,
    H, dv]; g, beta [T, H]; initial_state [H, dv, dk] (None: zeros);
    ``length`` (traced or None): positions at or past it are padding and
    leave the state alone (their outputs mean nothing). Any T: the last
    chunk is padded the same way. Returns (o [T, H, dv] float32, the state
    after the last real token, float32)."""
    T, H, dk = q.shape
    dv = v.shape[2]
    C = int(chunk)
    N = -(-T // C)
    real = jnp.arange(N * C) < (T if length is None else length)

    def chunks(a, fill=0.0):        # [T, H, ...] -> [N, H, C, ...]
        a = jnp.pad(a, ((0, N * C - T),) + ((0, 0),) * (a.ndim - 1))
        a = jnp.where(real.reshape((-1,) + (1,) * (a.ndim - 1)), a,
                      jnp.asarray(fill, a.dtype))
        return jnp.moveaxis(a.reshape((N, C) + a.shape[1:]), 2, 1)

    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    gc, bc = chunks(g.astype(_F32)), chunks(beta.astype(_F32))  # [N, H, C]
    G = jnp.cumsum(gc, axis=-1)
    diff = G[..., :, None] - G[..., None, :]                # G_i - G_j
    tri = jnp.tril(jnp.ones((C, C), bool))
    D = jnp.exp(jnp.where(tri, diff, -jnp.inf))             # j <= i, else 0
    eG = jnp.exp(G)[..., None]                              # [N, H, C, 1]
    to_end = jnp.exp(G[..., -1:] - G)[..., None]            # e^{G_C - G_i}
    end = jnp.exp(G[..., -1])                               # [N, H]

    def dot(a, b, spec):
        return jnp.einsum(spec, a, b, preferred_element_type=_F32)

    A = bc[..., None] * jnp.tril(D, -1) * dot(kc, kc, "nhcd,nhsd->nhcs")
    Tm = _unit_lower_inverse(A)
    kf, vf = kc.astype(_F32), vc.astype(_F32)
    U0 = dot(Tm, bc[..., None] * vf, "nhcs,nhsv->nhcv")
    W = dot(Tm, bc[..., None] * eG * kf, "nhcs,nhsd->nhcd")
    M = D * dot(qc, kc, "nhcd,nhsd->nhcs")
    qg = eG * qc.astype(_F32)
    kd = to_end * kf

    S0 = jnp.zeros((H, dv, dk), _F32) if initial_state is None \
        else initial_state.astype(_F32)

    def one(S, x):
        U0n, Wn, Mn, qgn, kdn, endn = x
        U = U0n - dot(Wn, S, "hcd,hvd->hcv")
        o = dot(qgn, S, "hcd,hvd->hcv") + dot(Mn, U, "hcs,hsv->hcv")
        S = endn[:, None, None] * S + dot(U, kdn, "hcv,hcd->hvd")
        return S, o

    S, o = jax.lax.scan(one, S0, (U0, W, M, qg, kd, end))
    o = jnp.moveaxis(o, 1, 2).reshape(N * C, H, dv)[:T]
    return o, S
