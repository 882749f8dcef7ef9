"""L-BFGS with strong-Wolfe line search.

Reference surface: /root/reference/python/paddle/optimizer/lbfgs.py:342
(class LBFGS with ``step(closure)``, max_iter/max_eval/tolerance_grad/
tolerance_change/history_size/line_search_fn knobs and a state_dict of
the same shape). Two entry points here:

* ``minimize_lbfgs(fun, x0, ...)`` — the TPU-native core: one jittable
  function whose outer iteration and strong-Wolfe line search are both
  ``lax.while_loop``s and whose curvature history lives in fixed-size
  circular buffers, so the whole optimization compiles to a single XLA
  program (no host round-trip per iteration — a host sync per iteration
  would dwarf the linear algebra for every classic L-BFGS problem size).
* ``class LBFGS`` — reference-parity eager API driving arbitrary user
  closures (forward+backward through the tape per evaluation); the line
  search and two-loop recursion share the same math helpers as the
  jittable core.

The strong-Wolfe search follows the classic bracket+zoom scheme with
safeguarded cubic interpolation (Nocedal & Wright §3.5), the same
algorithm the reference implements in python
(/root/reference/python/paddle/optimizer/lbfgs.py:120 _strong_wolfe).
"""
from __future__ import annotations

from collections import deque
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..core.tensor import Tensor
from .optimizer import Optimizer

__all__ = ["LBFGS", "minimize_lbfgs", "minimize_bfgs"]


# --------------------------------------------------------------------------
# shared math
# --------------------------------------------------------------------------

def _cubic_interpolate(x1, f1, g1, x2, f2, g2, lo, hi):
    """Minimizer of the cubic through (x1,f1,g1),(x2,f2,g2), clipped to
    [lo, hi]; falls back to bisection when the cubic is degenerate. Pure
    jnp — used by both the jitted and the eager line search."""
    d1 = g1 + g2 - 3 * (f1 - f2) / (x1 - x2)
    d2_sq = d1 * d1 - g1 * g2
    sqrt_ok = d2_sq >= 0
    d2 = jnp.sqrt(jnp.where(sqrt_ok, d2_sq, 0.0))
    # orientation: formula assumes x1 <= x2 (Nocedal & Wright eq. 3.59)
    d2 = jnp.where(x1 <= x2, d2, -d2)
    denom = g2 - g1 + 2 * d2
    t = x2 - (x2 - x1) * (g2 + d2 - d1) / denom
    usable = sqrt_ok & jnp.isfinite(t) & (denom != 0)
    t = jnp.where(usable, t, (lo + hi) / 2.0)
    return jnp.clip(t, lo, hi)


def _pinned_vg(fun):
    """value_and_grad with outputs pinned to the input dtype: with
    jax_enable_x64 on (package default) a user fun built from float
    literals returns f64, which would flip the while_loop carry dtypes
    mid-trace. Shared by minimize_lbfgs and minimize_bfgs."""
    _vg = jax.value_and_grad(fun)

    def vg(x):
        f, g = _vg(x)
        return f.astype(x.dtype), g.astype(x.dtype)
    return vg


def _phi_factory(vg):
    def phi_at(x, d):
        def phi(t):
            f, g = vg(x + t * d)
            return f, g, jnp.dot(g, d)
        return phi
    return phi_at


def _descent_guard(g, d, gtd):
    """Fall back to steepest descent when the (quasi-)Newton direction is
    not a descent direction (history/estimate gone bad)."""
    bad = gtd > -1e-12 * jnp.maximum(jnp.dot(g, g), 1e-38)
    return (jnp.where(bad, -g, d),
            jnp.where(bad, -jnp.dot(g, g), gtd))


def _initial_step(k, g, dtype, learning_rate):
    """First iteration: scale by 1/|g|_1 (torch's rule); later: lr."""
    return jnp.where(k == 0,
                     jnp.minimum(1.0, 1.0 / jnp.maximum(
                         jnp.sum(jnp.abs(g)), 1e-38)) * learning_rate,
                     jnp.asarray(learning_rate, dtype))


def _stop_pred(g_new, s, f_new, f, tolerance_grad, tolerance_change):
    return (jnp.max(jnp.abs(g_new)) <= tolerance_grad) | \
           (jnp.max(jnp.abs(s)) <= tolerance_change) | \
           (jnp.abs(f_new - f) <= tolerance_change) | \
           ~jnp.isfinite(f_new)


def _direction(g, s_hist, y_hist, rho, k, m, H0=None):
    """Two-loop recursion over a circular history of m slots (slot j%m holds
    iteration j's pair); entries outside [k-m, k) are masked via rho=0.
    Returns the descent direction -H_k @ g. H0: optional initial inverse
    Hessian — applied as the reference does (r = H0 @ q, no gamma); when
    None the standard gamma*I scaling is used."""
    q = g
    alphas = jnp.zeros((m,), dtype=g.dtype)

    def loop1(t, carry):
        q, alphas = carry
        j = k - 1 - t                      # most recent first
        slot = jnp.mod(j, m)
        valid = (j >= 0) & (j >= k - m)
        r = jnp.where(valid, rho[slot], 0.0)
        alpha = r * jnp.dot(s_hist[slot], q)
        q = q - alpha * y_hist[slot]
        return q, alphas.at[slot].set(alpha)

    q, alphas = lax.fori_loop(0, m, loop1, (q, alphas))

    if H0 is not None:
        r_vec = H0 @ q
    else:
        slot_last = jnp.mod(k - 1, m)
        ys = jnp.dot(s_hist[slot_last], y_hist[slot_last])
        yy = jnp.dot(y_hist[slot_last], y_hist[slot_last])
        gamma = jnp.where((k > 0) & (yy > 0), ys / jnp.maximum(yy, 1e-38),
                          1.0)
        r_vec = gamma * q

    def loop2(t, r_vec):
        j = k - m + t                      # oldest first
        slot = jnp.mod(j, m)
        valid = (j >= 0) & (j < k)
        rr = jnp.where(valid, rho[slot], 0.0)
        beta = rr * jnp.dot(y_hist[slot], r_vec)
        return r_vec + jnp.where(valid, alphas[slot] - beta, 0.0) * s_hist[slot]

    r_vec = lax.fori_loop(0, m, loop2, r_vec)
    return -r_vec


# --------------------------------------------------------------------------
# jittable strong-Wolfe line search
# --------------------------------------------------------------------------

class _WolfeResult(NamedTuple):
    t: jnp.ndarray
    f: jnp.ndarray
    g: jnp.ndarray          # gradient vector at x + t*d
    n_evals: jnp.ndarray


def _strong_wolfe_jit(phi, t0, f0, g0_vec, gtd0, c1=1e-4, c2=0.9,
                      max_ls=25, tol_change=1e-9):
    """phi(t) -> (f, g_vec, gtd) along the ray. Bracket then zoom, both as
    while_loops; mirrors the reference's _strong_wolfe control flow."""
    f_new, g_new, gtd_new = phi(t0)

    # ---- phase 1: bracket a point satisfying (or straddling) the Wolfe
    # conditions. Carry both ends' (t, f, gtd) plus both gradient vectors.
    def bracket_cond(st):
        (ls_iter, done, *_rest) = st
        return (~done) & (ls_iter < max_ls)

    def bracket_body(st):
        (ls_iter, done, t_prev, f_prev, g_prev, gtd_prev,
         t, f, g, gtd, have) = st
        # Armijo fails (or not a decrease vs previous): bracket [prev, t]
        armijo_fail = (f > f0 + c1 * t * gtd0) | ((ls_iter > 0) & (f >= f_prev))
        wolfe_ok = jnp.abs(gtd) <= -c2 * gtd0
        pos_deriv = gtd >= 0

        new_done = armijo_fail | wolfe_ok | pos_deriv
        have_b = armijo_fail | (pos_deriv & ~wolfe_ok)

        # otherwise extrapolate (torch's rule): t_next in
        # [t + 0.01*(t - t_prev), 10*t]
        min_step = t + 0.01 * (t - t_prev)
        max_step = t * 10.0
        t_next = _cubic_interpolate(t_prev, f_prev, gtd_prev, t, f, gtd,
                                    min_step, max_step)
        fn, gn, gtdn = phi(t_next)
        # on finish freeze BOTH points — they are the bracket's two ends
        sel = lambda a, b: jnp.where(new_done, a, b)
        return (ls_iter + 1, new_done,
                sel(t_prev, t), sel(f_prev, f),
                jnp.where(new_done, g_prev, g), sel(gtd_prev, gtd),
                sel(t, t_next), sel(f, fn),
                jnp.where(new_done, g, gn), sel(gtd, gtdn),
                have | (new_done & have_b))

    zero = jnp.zeros_like(f0)
    st0 = (jnp.int32(0), jnp.asarray(False),
           zero, f0, g0_vec, gtd0,                     # prev point (t=0)
           t0, f_new, g_new, gtd_new,                  # current point
           jnp.asarray(False))
    st = lax.while_loop(bracket_cond, bracket_body, st0)
    (ls_iter, done, t_prev, f_prev, g_prev, gtd_prev,
     t, f, g, gtd, have_bracket) = st

    wolfe_now = (jnp.abs(gtd) <= -c2 * gtd0) & (f <= f0 + c1 * t * gtd0)
    # if bracket phase exhausted without success, fall back to current t
    need_zoom = have_bracket & ~wolfe_now

    # the bracket's two ends ARE the frozen carry points (t_prev, t) with
    # their f/g/gtd already in hand — no re-evaluation. Order so the lower
    # objective comes first (zoom invariant: f(lo) <= f(hi)).
    swap = f < f_prev
    lo_, hi_ = jnp.where(swap, t, t_prev), jnp.where(swap, t_prev, t)
    f_lo_, f_hi_ = jnp.where(swap, f, f_prev), jnp.where(swap, f_prev, f)
    gtd_lo_ = jnp.where(swap, gtd, gtd_prev)
    gtd_hi_ = jnp.where(swap, gtd_prev, gtd)
    g_lo_ = jnp.where(swap, g, g_prev)

    def zoom_cond(st):
        zi, done, *_ = st
        return (~done) & (zi < max_ls)

    def zoom_body(st):
        (zi, done, lo, f_lo, g_lo, gtd_lo, hi, f_hi, gtd_hi,
         t_best, f_best, g_best) = st
        width = jnp.abs(hi - lo)
        tz = _cubic_interpolate(lo, f_lo, gtd_lo, hi, f_hi, gtd_hi,
                                jnp.minimum(lo, hi) + 0.1 * width,
                                jnp.maximum(lo, hi) - 0.1 * width)
        fz, gz, gtdz = phi(tz)
        armijo_fail = (fz > f0 + c1 * tz * gtd0) | (fz >= f_lo)
        wolfe_ok = (~armijo_fail) & (jnp.abs(gtdz) <= -c2 * gtd0)
        # shrink: on armijo failure tz becomes hi; else tz becomes lo
        # (flipping hi to old lo when derivative sign says so)
        flip = (~armijo_fail) & (gtdz * (hi - lo) >= 0)
        new_hi = jnp.where(armijo_fail, tz, jnp.where(flip, lo, hi))
        new_f_hi = jnp.where(armijo_fail, fz, jnp.where(flip, f_lo, f_hi))
        new_gtd_hi = jnp.where(armijo_fail, gtdz,
                               jnp.where(flip, gtd_lo, gtd_hi))
        new_lo = jnp.where(armijo_fail, lo, tz)
        new_f_lo = jnp.where(armijo_fail, f_lo, fz)
        new_gtd_lo = jnp.where(armijo_fail, gtd_lo, gtdz)
        new_g_lo = jnp.where(armijo_fail, g_lo, gz)
        stall = width * 0.9 <= tol_change
        return (zi + 1, done | wolfe_ok | stall,
                new_lo, new_f_lo, new_g_lo, new_gtd_lo,
                new_hi, new_f_hi, new_gtd_hi,
                jnp.where(wolfe_ok, tz, new_lo),
                jnp.where(wolfe_ok, fz, new_f_lo),
                jnp.where(wolfe_ok, gz, new_g_lo))

    zst0 = (jnp.int32(0), ~need_zoom, lo_, f_lo_, g_lo_, gtd_lo_,
            hi_, f_hi_, gtd_hi_, lo_, f_lo_, g_lo_)
    zst = lax.while_loop(zoom_cond, zoom_body, zst0)
    t_zoom, f_zoom, g_zoom = zst[9], zst[10], zst[11]

    t_out = jnp.where(need_zoom, t_zoom, t)
    f_out = jnp.where(need_zoom, f_zoom, f)
    g_out = jnp.where(need_zoom, g_zoom, g)
    return _WolfeResult(t_out, f_out, g_out, ls_iter + zst[0] + 1)


class LbfgsResult(NamedTuple):
    x: jnp.ndarray
    fun: jnp.ndarray
    grad: jnp.ndarray
    num_iters: jnp.ndarray
    converged: jnp.ndarray
    # real results carry a jnp.int32; the DEFAULT must stay a plain int —
    # a jnp default would create a device array AT IMPORT and initialize
    # the XLA backend before jax.distributed.initialize can run
    num_func_calls: int | jnp.ndarray = 0


def minimize_lbfgs(fun, x0, *, history_size: int = 10, max_iters: int = 50,
                   tolerance_grad: float = 1e-7,
                   tolerance_change: float = 1e-9,
                   line_search_fn: str = "strong_wolfe",
                   initial_inverse_hessian=None,
                   initial_step: float = 1.0, max_ls: int = 25,
                   learning_rate: float = 1.0,
                   dtype="float32") -> LbfgsResult:
    """Jittable L-BFGS: ``fun`` maps a flat vector to a scalar loss.
    The entire optimization — outer iteration, two-loop recursion over
    fixed-size circular history buffers, strong-Wolfe bracketing/zoom —
    is compiler-visible control flow, so under ``jax.jit`` it runs as one
    XLA program with zero host syncs. initial_inverse_hessian: applied as
    ``r = H0 @ q`` in the two-loop recursion (reference semantics); when
    None the standard gamma*I scaling is used. dtype: float32 (default)
    or float64 (x64 is enabled package-wide)."""
    if line_search_fn not in ("strong_wolfe", None):
        raise ValueError(f"unsupported line_search_fn {line_search_fn!r}")

    x0 = jnp.asarray(x0, dtype=jnp.dtype(dtype)).reshape(-1)
    n, m = x0.shape[0], int(history_size)
    H0 = (None if initial_inverse_hessian is None
          else jnp.asarray(initial_inverse_hessian, x0.dtype))
    vg = _pinned_vg(fun)
    phi_at = _phi_factory(vg)
    f0, g0 = vg(x0)

    def cond(st):
        (k, x, f, g, *_h, stop) = st
        return (~stop) & (k < max_iters)

    def body(st):
        (k, x, f, g, s_hist, y_hist, rho, calls, stop) = st
        d = _direction(g, s_hist, y_hist, rho, k, m, H0)
        d, gtd = _descent_guard(g, d, jnp.dot(g, d))

        t0 = _initial_step(k, g, x.dtype, learning_rate)
        if line_search_fn == "strong_wolfe":
            res = _strong_wolfe_jit(phi_at(x, d), t0, f, g, gtd,
                                    max_ls=max_ls,
                                    tol_change=tolerance_change)
            t, f_new, g_new = res.t, res.f, res.g
            calls = calls + res.n_evals
        else:
            t = t0
            f_new, g_new = vg(x + t * d)
            calls = calls + 1

        s = t * d
        x_new = x + s
        y = g_new - g
        ys = jnp.dot(y, s)
        slot = jnp.mod(k, m)
        # curvature guard: only store pairs with y.s > eps (keeps H ≻ 0)
        keep = ys > 1e-10
        upd = lambda H, v: jnp.where(keep, H.at[slot].set(v), H)
        s_hist = upd(s_hist, s)
        y_hist = upd(y_hist, y)
        rho = jnp.where(keep, rho.at[slot].set(1.0 / jnp.maximum(ys, 1e-38)),
                        rho)
        # when the pair is rejected the slot must not advance — but k also
        # counts iterations; mask instead by zeroing rho for that slot
        rho = jnp.where(keep, rho, rho.at[slot].set(0.0))

        stop_new = _stop_pred(g_new, s, f_new, f, tolerance_grad,
                              tolerance_change)
        return (k + 1, x_new, f_new, g_new, s_hist, y_hist, rho, calls,
                stop_new)

    # converged = stopped by a tolerance (grad/step/fchange) with a finite
    # objective — NOT by exhausting max_iters. At f32 the gradient floor of
    # a well-conditioned problem sits near 1e-5·|g0|, so grad-tol alone
    # under-reports convergence the reference's f64 path never hits.

    st0 = (jnp.int32(0), x0, f0, g0,
           jnp.zeros((m, n), x0.dtype), jnp.zeros((m, n), x0.dtype),
           jnp.zeros((m,), x0.dtype), jnp.int32(1),
           jnp.max(jnp.abs(g0)) <= tolerance_grad)
    k, x, f, g, _s, _y, _r, calls, stop = lax.while_loop(cond, body, st0)
    converged = stop & jnp.isfinite(f)
    return LbfgsResult(x, f, g, k, converged, calls)


# --------------------------------------------------------------------------
# eager reference-parity class
# --------------------------------------------------------------------------

def _strong_wolfe_eager(phi, t, f0, g0, gtd0, c1=1e-4, c2=0.9, max_ls=25,
                        tol_change=1e-9):
    """Python-loop strong Wolfe for arbitrary (non-traceable) closures.
    Same bracket/zoom scheme and the same _cubic_interpolate as the jitted
    path; each phi() call runs the user's forward+backward eagerly.
    Gradient vectors are tracked for BOTH bracket ends so the returned
    (t, f, g) always belong to the same point (the reference keeps the
    same bracket_g bookkeeping, lbfgs.py:208)."""
    f, g, gtd = phi(t)
    t_prev, f_prev, g_prev, gtd_prev = 0.0, f0, g0, gtd0
    bracket = None
    for ls_iter in range(max_ls):
        if f > f0 + c1 * t * gtd0 or (ls_iter > 0 and f >= f_prev):
            bracket = (t_prev, f_prev, g_prev, gtd_prev, t, f, g, gtd)
            break
        if abs(gtd) <= -c2 * gtd0:
            return t, f, g
        if gtd >= 0:
            bracket = (t_prev, f_prev, g_prev, gtd_prev, t, f, g, gtd)
            break
        min_step = t + 0.01 * (t - t_prev)
        max_step = t * 10.0
        t_next = float(_cubic_interpolate(t_prev, f_prev, gtd_prev,
                                          t, f, gtd, min_step, max_step))
        t_prev, f_prev, g_prev, gtd_prev = t, f, g, gtd
        t = t_next
        f, g, gtd = phi(t)
    if bracket is None:           # exhausted without bracketing
        return t, f, g
    lo, f_lo, g_lo, gtd_lo, hi, f_hi, g_hi, gtd_hi = bracket
    if f_hi < f_lo:
        (lo, f_lo, g_lo, gtd_lo, hi, f_hi, g_hi, gtd_hi) = \
            (hi, f_hi, g_hi, gtd_hi, lo, f_lo, g_lo, gtd_lo)
    for _ in range(max_ls):
        width = abs(hi - lo)
        if width * 0.9 <= tol_change:
            break
        tz = float(_cubic_interpolate(lo, f_lo, gtd_lo, hi, f_hi, gtd_hi,
                                      min(lo, hi) + 0.1 * width,
                                      max(lo, hi) - 0.1 * width))
        fz, gz, gtdz = phi(tz)
        if fz > f0 + c1 * tz * gtd0 or fz >= f_lo:
            hi, f_hi, g_hi, gtd_hi = tz, fz, gz, gtdz
        else:
            if abs(gtdz) <= -c2 * gtd0:
                return tz, fz, gz
            if gtdz * (hi - lo) >= 0:
                hi, f_hi, g_hi, gtd_hi = lo, f_lo, g_lo, gtd_lo
            lo, f_lo, g_lo, gtd_lo = tz, fz, gz, gtdz
    return lo, f_lo, g_lo


class LBFGS(Optimizer):
    """Reference-parity L-BFGS (lbfgs.py:342): ``step(closure)`` re-evaluates
    the model as many times as the line search needs. History lives in
    deques of flat vectors; the update math is shared with the jittable
    ``minimize_lbfgs`` (use that directly for closed-form objectives —
    it compiles the whole optimization into one XLA program)."""

    def __init__(self, learning_rate=1.0, max_iter=20, max_eval=None,
                 tolerance_grad=1e-7, tolerance_change=1e-9, history_size=100,
                 line_search_fn=None, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         False, name)
        if max_eval is None:
            max_eval = max_iter * 5 // 4
        self.max_iter = int(max_iter)
        self.max_eval = int(max_eval)
        self.tolerance_grad = float(tolerance_grad)
        self.tolerance_change = float(tolerance_change)
        self.history_size = int(history_size)
        if line_search_fn not in (None, "strong_wolfe"):
            raise ValueError(
                f"only 'strong_wolfe' or None is supported, got "
                f"{line_search_fn!r}")
        self.line_search_fn = line_search_fn
        self._state = {"func_evals": 0, "n_iter": 0}

    # -- flat-vector plumbing over the trainable parameter list ----------
    def _trainable(self):
        return [p for p in self._ensure_params() if getattr(p, "trainable", True)]

    def _gather_flat_grad(self, params):
        return jnp.concatenate([
            (jnp.zeros(p._value.size, jnp.float32) if p._grad_value is None
             else jnp.ravel(p._grad_value).astype(jnp.float32))
            for p in params])

    def _gather_flat(self, params):
        return jnp.concatenate([jnp.ravel(p._value).astype(jnp.float32)
                                for p in params])

    def _scatter_flat(self, params, x):
        off = 0
        for p in params:
            n = int(p._value.size)
            p._value = jnp.reshape(x[off:off + n], p._value.shape).astype(
                p._value.dtype)
            off += n

    def step(self, closure):
        """closure: re-evaluates the model and returns the loss (after
        clearing grads and calling backward, exactly like the reference)."""
        params = self._trainable()
        st = self._state
        evals_this_step = [0]      # max_eval bounds evals PER step() call
                                   # (func_evals in state is the lifetime
                                   # total, reference-parity)

        def evaluate(x):
            self._scatter_flat(params, x)
            loss = closure()
            st["func_evals"] += 1
            evals_this_step[0] += 1
            lv = loss._value if isinstance(loss, Tensor) else loss
            return float(jax.device_get(lv)), self._gather_flat_grad(params)

        x = self._gather_flat(params)
        f, g = evaluate(x)
        orig_loss = f
        if float(jnp.max(jnp.abs(g))) <= self.tolerance_grad:
            return Tensor(jnp.asarray(orig_loss))

        s_hist = st.setdefault("old_stps", deque(maxlen=self.history_size))
        y_hist = st.setdefault("old_dirs", deque(maxlen=self.history_size))
        rho = st.setdefault("ro", deque(maxlen=self.history_size))
        lr = self.get_lr()

        for it in range(self.max_iter):
            st["n_iter"] += 1
            # two-loop recursion over the deques (newest at the right)
            q = g
            alphas = []
            for s_i, y_i, r_i in zip(reversed(s_hist), reversed(y_hist),
                                     reversed(rho)):
                a = r_i * float(jnp.dot(s_i, q))
                q = q - a * y_i
                alphas.append(a)
            if y_hist:
                y_last = y_hist[-1]
                gamma = float(jnp.dot(s_hist[-1], y_last) /
                              jnp.maximum(jnp.dot(y_last, y_last), 1e-38))
            else:
                gamma = 1.0
            r_vec = gamma * q
            for (s_i, y_i, r_i), a in zip(zip(s_hist, y_hist, rho),
                                          reversed(alphas)):
                b = r_i * float(jnp.dot(y_i, r_vec))
                r_vec = r_vec + (a - b) * s_i
            d = -r_vec

            gtd = float(jnp.dot(g, d))
            if gtd > -1e-12:
                d, gtd = -g, -float(jnp.dot(g, g))
            t = (min(1.0, 1.0 / max(float(jnp.sum(jnp.abs(g))), 1e-38)) * lr
                 if st["n_iter"] == 1 else lr)

            if self.line_search_fn == "strong_wolfe":
                def phi(tt):
                    ff, gg = evaluate(x + tt * d)
                    return ff, gg, float(jnp.dot(gg, d))
                t, f_new, g_new = _strong_wolfe_eager(
                    phi, t, f, g, gtd, max_ls=min(25, self.max_eval),
                    tol_change=self.tolerance_change)
            else:
                f_new, g_new = evaluate(x + t * d)

            s = t * d
            x_new = x + s
            y = g_new - g
            ys = float(jnp.dot(y, s))
            if ys > 1e-10:
                s_hist.append(s)
                y_hist.append(y)
                rho.append(1.0 / ys)

            x, f, g = x_new, f_new, g_new
            if (float(jnp.max(jnp.abs(g))) <= self.tolerance_grad
                    or float(jnp.max(jnp.abs(s))) <= self.tolerance_change
                    or evals_this_step[0] >= self.max_eval):
                break

        self._scatter_flat(params, x)
        self._step_count += 1
        return Tensor(jnp.asarray(orig_loss))

    # -- reference-shaped state dict -------------------------------------
    def state_dict(self):
        st = self._state
        return {
            "func_evals": st.get("func_evals", 0),
            "n_iter": st.get("n_iter", 0),
            "old_stps": list(st.get("old_stps", [])),
            "old_dirs": list(st.get("old_dirs", [])),
            "ro": list(st.get("ro", [])),
        }

    def set_state_dict(self, sd):
        self._state = {
            "func_evals": int(sd.get("func_evals", 0)),
            "n_iter": int(sd.get("n_iter", 0)),
            "old_stps": deque(sd.get("old_stps", []),
                              maxlen=self.history_size),
            "old_dirs": deque(sd.get("old_dirs", []),
                              maxlen=self.history_size),
            "ro": deque(sd.get("ro", []), maxlen=self.history_size),
        }


class BfgsResult(NamedTuple):
    x: jnp.ndarray
    fun: jnp.ndarray
    grad: jnp.ndarray
    num_iters: jnp.ndarray
    num_func_calls: jnp.ndarray
    converged: jnp.ndarray
    inverse_hessian: jnp.ndarray


def minimize_bfgs(fun, x0, *, max_iters: int = 50,
                  tolerance_grad: float = 1e-7,
                  tolerance_change: float = 1e-9,
                  initial_inverse_hessian=None,
                  line_search_fn: str = "strong_wolfe",
                  max_ls: int = 50, learning_rate: float = 1.0,
                  dtype="float32") -> BfgsResult:
    """Jittable dense BFGS (Nocedal & Wright Alg. 6.1): the full N×N
    inverse-Hessian estimate is carried and updated each step —
    TPU-native answer to the reference's
    incubate/optimizer/functional/bfgs.py:36 (which builds the same loop
    out of static-graph while ops). Shares the strong-Wolfe line search
    with minimize_lbfgs."""
    if line_search_fn not in ("strong_wolfe", None):
        raise ValueError(f"unsupported line_search_fn {line_search_fn!r}")
    x0 = jnp.asarray(x0, dtype=jnp.dtype(dtype)).reshape(-1)
    n = x0.shape[0]
    vg = _pinned_vg(fun)
    phi_at = _phi_factory(vg)

    H0 = (jnp.eye(n, dtype=x0.dtype) if initial_inverse_hessian is None
          else jnp.asarray(initial_inverse_hessian, x0.dtype))
    f0, g0 = vg(x0)

    def cond(st):
        (k, *_rest, stop) = st
        return (~stop) & (k < max_iters)

    def body(st):
        (k, x, f, g, H, calls, stop) = st
        d, gtd = _descent_guard(g, -(H @ g), jnp.dot(g, -(H @ g)))
        t0 = _initial_step(k, g, x.dtype, learning_rate)
        if line_search_fn == "strong_wolfe":
            res = _strong_wolfe_jit(phi_at(x, d), t0, f, g, gtd,
                                    max_ls=max_ls,
                                    tol_change=tolerance_change)
            t, f_new, g_new = res.t, res.f, res.g
            calls = calls + res.n_evals
        else:
            t = t0
            f_new, g_new = vg(x + t * d)
            calls = calls + 1

        s = t * d
        y = g_new - g
        ys = jnp.dot(y, s)
        keep = ys > 1e-10
        rho = 1.0 / jnp.maximum(ys, 1e-38)
        Hy = H @ y
        # H' = H + (s.y + y.Hy) ρ² ssᵀ − ρ (Hy sᵀ + s Hyᵀ)   (N&W 6.17)
        H_new = H \
            + (ys + jnp.dot(y, Hy)) * (rho * rho) * jnp.outer(s, s) \
            - rho * (jnp.outer(Hy, s) + jnp.outer(s, Hy))
        H = jnp.where(keep, H_new, H)

        x_new = x + s
        stop_new = _stop_pred(g_new, s, f_new, f, tolerance_grad,
                              tolerance_change)
        return (k + 1, x_new, f_new, g_new, H, calls, stop_new)

    st0 = (jnp.int32(0), x0, f0, g0, H0, jnp.int32(1),
           jnp.max(jnp.abs(g0)) <= tolerance_grad)
    k, x, f, g, H, calls, stop = lax.while_loop(cond, body, st0)
    return BfgsResult(x, f, g, k, calls, stop & jnp.isfinite(f), H)
