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

The step. It is bound by the state's bytes: [B, H, dv, dk] float32, read
for two sums over its rows and written anew, a few operations an element.
Written in ``jax.numpy`` (``gdn_step_jnp``) XLA makes three passes of it: the
two sums read the state, and the update is put off past the layer's output
and fused with the frozen slots' select into a pass that reads the state
again and writes it. ``gdn_step`` is ONE Pallas pass (``name="gdn_step"``)
where ``gdn_step_body`` says so: a float32 state whose slot fits VMEM. A
grid step is one slot: its state is copied into VMEM once, S'k, S'q, u, o
and the new state are made there head by head on the VPU (sums along dk,
as ``gdn_step_jnp`` makes them), and the new state goes back to the SAME
HBM buffer (``input_output_aliases``). A frozen slot is not copied at all:
its block index repeats the last live slot's, so the pipeline neither
reads nor writes it, and its state stays what it was, bit for bit. Off the
TPU the kernel runs interpreted.

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

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["gdn_chunk_scan", "gdn_step", "gdn_step_jnp", "gdn_step_body",
           "CHUNK"]

CHUNK = 64
_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
# index-map constant: with jax_enable_x64 a literal 0 traces as i64, which
# Mosaic cannot legalize (ops/ragged_attention.py)
_i0 = np.int32(0)
# the step kernel's scoped VMEM, and what a slot's state blocks may take of
# it: in and out, double-buffered (4 x 2.95 MB at Olmo-Hybrid's 30 heads of
# [192, 96], 96 lanes tiled to 128); the rest is the small blocks and the
# body's own values
_STEP_VMEM = 32 << 20
_STEP_BLOCKS = 24 << 20


def gdn_step_body(state_shape, state_dtype) -> str:
    """Which body ``gdn_step`` runs for a state ``[B, H, dv, dk]`` of
    ``state_dtype``: "kernel" (one Pallas pass, interpreted off the TPU)
    for a float32 state whose slot, as the chip tiles it, fits the kernel's
    VMEM four times over; "jnp" (``gdn_step_jnp``) otherwise. The same
    answer on every backend."""
    _, H, dv, dk = state_shape
    tiled = H * (-(-dv // 8) * 8) * (-(-dk // 128) * 128) * 4
    fits = 4 * tiled <= _STEP_BLOCKS
    return "kernel" if jnp.dtype(state_dtype) == _F32 and fits else "jnp"


def gdn_step(q, k, v, g, beta, state, frozen=None, *, interpret=None):
    """One token of every slot. q, k [B, H, dk]; v [B, H, dv]; g, beta
    [B, H]; state [B, H, dv, dk]; ``frozen`` [B] bool (None: none): slots
    whose state comes back bit for bit as it was and whose output is 0.
    Returns (o [B, H, dv] float32, the new state in the state's dtype).
    The body is what ``gdn_step_body`` says; ``interpret`` (None: off the
    TPU) runs the kernel interpreted."""
    if frozen is None:
        frozen = jnp.zeros(state.shape[:1], bool)
    if gdn_step_body(state.shape, state.dtype) == "jnp":
        return gdn_step_jnp(q, k, v, g, beta, state, frozen)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _gdn_step_kernel(q, k, v, g, beta, state, frozen,
                            interpret=interpret)


def gdn_step_jnp(q, k, v, g, beta, state, frozen=None):
    """``gdn_step`` in ``jax.numpy``: the rule as XLA makes it, and the
    kernel's reference. Elementwise products and sums in float32, no matrix
    unit. ``o`` is taken as ``S' q + u (k . q)``, so that both sums over the
    state's rows read the old state."""
    q, k, v = q.astype(_F32), k.astype(_F32), v.astype(_F32)
    S = state.astype(_F32)
    decay = jnp.exp(g.astype(_F32))[..., None]              # [B, H, 1]
    kb, qb = k[:, :, None, :], q[:, :, None, :]
    Sk = jnp.sum(S * kb, axis=-1) * decay                   # S' k  [B, H, dv]
    Sq = jnp.sum(S * qb, axis=-1) * decay                   # S' q
    u = beta.astype(_F32)[..., None] * (v - Sk)
    o = Sq + u * jnp.sum(k * q, axis=-1, keepdims=True)
    new = (S * decay[..., None] + u[..., None] * kb).astype(state.dtype)
    if frozen is None:
        return o, new
    return (jnp.where(frozen[:, None, None], 0.0, o),
            jnp.where(frozen[:, None, None, None], state, new))


def _step_kernel(src_ref, live_ref, decay_ref, beta_ref, q_ref, k_ref, v_ref,
                 s_ref, o_ref, new_ref, *, heads):
    """One slot b: ``s_ref`` / ``new_ref`` its state block [1, H, dv, dk]
    (one buffer in HBM), q_ref / k_ref [1, H, dk], v_ref / o_ref [1, dv, H]
    (a head's values a column, as the sums along dk leave S'k). Scalar
    prefetch: src_ref [B] the slot whose blocks this step holds (b, or for
    a frozen b the last live slot before it, else the first live one),
    live_ref [B]; decay_ref / beta_ref [B * H] in SMEM."""
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    live = live_ref[b] != 0

    @pl.when(live)
    def _rule():
        for h in range(heads):
            S = s_ref[0, h]                                 # [dv, dk]
            kr = k_ref[0, pl.ds(h, 1), :]                   # [1, dk]
            qr = q_ref[0, pl.ds(h, 1), :]
            d = decay_ref[b * heads + h]
            Sk = jnp.sum(S * kr, axis=1, keepdims=True) * d  # [dv, 1]
            Sq = jnp.sum(S * qr, axis=1, keepdims=True) * d
            u = beta_ref[b * heads + h] * (v_ref[0, :, pl.ds(h, 1)] - Sk)
            kq = jnp.sum(kr * qr, axis=1, keepdims=True)    # [1, 1]
            o_ref[0, :, pl.ds(h, 1)] = Sq + u * kq
            new_ref[0, h] = S * d + u * kr

    @pl.when(jnp.logical_not(live))
    def _frozen():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

        # no slot is live: every step holds slot 0's block, which goes back
        # as it came
        @pl.when(live_ref[src_ref[b]] == 0)
        def _keep():
            new_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gdn_step_kernel(q, k, v, g, beta, state, frozen, interpret: bool):
    """``gdn_step``'s Pallas pass: grid over slots, ``_step_kernel``. The
    grid is "arbitrary": a frozen slot's step revisits the block of the
    step before it, which only holds where the steps run in order. Jitted:
    a burst's LINEAR layers hand it the same shapes, so its body (the heads
    unrolled) is traced and lowered once a program, not once a layer."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, dv, dk = state.shape
    i32 = jnp.int32
    live = jnp.logical_not(frozen)
    slots = jnp.arange(B, dtype=i32)
    before = jax.lax.cummax(jnp.where(live, slots, i32(-1)), axis=0)
    src = jnp.where(before >= 0, before, jnp.argmax(live).astype(i32))

    def held(b, src_ref, live_ref):     # the blocks step b holds: src[b]'s
        return src_ref[b], _i0, _i0

    def held_state(b, src_ref, live_ref):
        return src_ref[b], _i0, _i0, _i0

    row = pl.BlockSpec((1, H, dk), held)
    col = pl.BlockSpec((1, dv, H), held)
    smem = pl.BlockSpec((B * H,), lambda *_: (_i0,),
                        memory_space=pltpu.SMEM)
    block = pl.BlockSpec((1, H, dv, dk), held_state)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B,),
        in_specs=[smem, smem, row, row, col, block],
        out_specs=[pl.BlockSpec((1, dv, H), lambda b, *_: (b, _i0, _i0)),
                   block])
    o, new = pl.pallas_call(
        functools.partial(_step_kernel, heads=H), grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, dv, H), _F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={7: 1},
        compiler_params=(None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_STEP_VMEM)),
        interpret=interpret,
        name="gdn_step",
    )(src, live.astype(i32), jnp.exp(g.astype(_F32)).reshape(B * H),
      beta.astype(_F32).reshape(B * H), q.astype(_F32), k.astype(_F32),
      jnp.swapaxes(v.astype(_F32), 1, 2), state)
    return jnp.swapaxes(o, 1, 2), new


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
