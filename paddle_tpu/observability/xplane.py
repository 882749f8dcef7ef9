"""Optional on-device (XPlane) trace hook around a step window.

The host chrome trace shows WHEN a step was slow; the device trace shows
WHY (which fusion, which DMA). This hook bridges them two ways:

  * ENV window — when ``PADDLE_XPLANE_DIR`` is set, ``maybe_step(step)``
    (called from the Engine / LlamaTrainStep step hooks and the serving
    scheduler) starts ``jax.profiler`` at step ``PADDLE_XPLANE_START``
    (default 2 — past compile), stops it ``PADDLE_XPLANE_STEPS`` steps
    later (default 2). Runs at most once per process.
  * ARMED window — ``arm(steps=N)`` opens a bounded window at the NEXT
    ``maybe_step`` call, regardless of env configuration and re-armable
    after it closes. This is the trigger engine's capture-the-slow-rank-
    WHILE-it-is-slow hook (ROADMAP MPMD follow-up): a ``fleet.straggler``
    or ``slo.breach`` arms the offending rank's window through the
    telemetry command channel, so the device-side story of the slow
    window is on disk before the slowness passes.

Either way the XPlane dump path is recorded into the host trace's metadata
(``otherData.xplane_dir`` via ``spans.set_trace_metadata``) plus a flight
event — the merged fleet trace names where the device-side story lives.

Without the env var and without an arm this is a true no-op (one dict read
per step); jax is imported lazily and every profiler call is guarded — a
broken/absent profiler degrades to a recorded error, never a failed step.
"""
from __future__ import annotations

import atexit
import os
import tempfile

from . import metrics, recorder, spans

__all__ = ["maybe_step", "arm", "active", "stop", "reset"]

ENV_DIR = "PADDLE_XPLANE_DIR"
ENV_START = "PADDLE_XPLANE_START"
ENV_STEPS = "PADDLE_XPLANE_STEPS"

_state = {"active": False, "env_done": False, "broken": False,
          "start_step": None, "win_steps": None, "armed": None}
_PROFILER = None  # test seam: inject a fake; None = resolve jax.profiler


def _profiler():
    if _PROFILER is not None:
        return _PROFILER
    import jax.profiler
    return jax.profiler


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def active() -> bool:
    return _state["active"]


def arm(steps: int | None = None, xdir: str | None = None,
        reason: str | None = None) -> bool:
    """Arm a profiler window covering the next `steps` scheduler steps
    (default PADDLE_XPLANE_STEPS, 2). Returns False (and stays put) while
    a window is already active or armed, or after the profiler proved
    broken — a trigger storm must collapse to one capture, not a pile-up.
    `xdir` defaults to $PADDLE_XPLANE_DIR, else <PADDLE_TRACE_DIR>/xplane,
    else <system temp dir>/paddle_xplane — never the current directory: an
    unasked dump must not land in a checkout."""
    if _state["active"] or _state["armed"] is not None or _state["broken"]:
        return False
    trace_dir = os.environ.get("PADDLE_TRACE_DIR")
    xdir = (xdir or os.environ.get(ENV_DIR)
            or (os.path.join(trace_dir, "xplane") if trace_dir
                else os.path.join(tempfile.gettempdir(), "paddle_xplane")))
    n = max(1, _env_int(ENV_STEPS, 2) if steps is None else int(steps))
    _state["armed"] = {"steps": n, "dir": xdir, "reason": reason}
    metrics.counter("xplane.arms").inc()
    recorder.record("xplane.armed", echo=True,
                    message=f"[xplane] armed a {n}-step device-trace window"
                            f" ({reason or 'manual'}) -> {xdir}",
                    steps=n, dir=xdir, reason=reason)
    return True


def maybe_step(step: int):
    """Drive the window state machine at one step boundary. A no-op unless
    PADDLE_XPLANE_DIR is set or ``arm()`` is pending; the env window runs
    at most once per process, armed windows are re-armable."""
    if _state["active"]:
        if step >= _state["start_step"] + _state["win_steps"]:
            stop()
        return
    armed = _state["armed"]
    if armed is not None:
        _state["armed"] = None
        _start(armed["dir"], step, armed["steps"], reason=armed["reason"])
        return
    xdir = os.environ.get(ENV_DIR)
    if not xdir or _state["env_done"] or _state["broken"]:
        return
    start = _env_int(ENV_START, 2)
    n = max(1, _env_int(ENV_STEPS, 2))
    if start <= step < start + n:
        _state["env_done"] = True  # one window per process, even on error
        _start(xdir, step, n)


def _start(xdir: str, step: int, n_steps: int, reason: str | None = None):
    try:
        _profiler().start_trace(xdir)
    except Exception as e:
        _state["broken"] = True  # don't retry a broken profiler every step
        recorder.record("xplane.error", echo=True,
                        message=f"[xplane] start_trace failed: {e}",
                        error=f"{type(e).__name__}: {e}")
        return
    _state["active"] = True
    _state["start_step"] = step
    _state["win_steps"] = max(1, int(n_steps))
    # a run that ends (or is preempted) mid-window must still close the
    # trace — jax.profiler only writes the XPlane dump on stop_trace
    atexit.register(stop)
    spans.set_trace_metadata("xplane_dir", xdir)
    spans.set_trace_metadata("xplane_start_step", step)
    metrics.counter("xplane.windows").inc()
    recorder.record("xplane.start", step=step, dir=xdir, steps=n_steps,
                    reason=reason)


def stop():
    """Close an open window (also safe to call at shutdown)."""
    if not _state["active"]:
        return
    _state["active"] = False
    try:
        _profiler().stop_trace()
    except Exception as e:
        recorder.record("xplane.error", echo=True,
                        message=f"[xplane] stop_trace failed: {e}",
                        error=f"{type(e).__name__}: {e}")
        return
    recorder.record("xplane.stop", dir=os.environ.get(ENV_DIR))


def reset():
    """Re-arm the window (tests)."""
    _state.update(active=False, env_done=False, broken=False,
                  start_step=None, win_steps=None, armed=None)
