"""ResilientLoop — restartable training with checkpoint-exact recovery.

Wraps any step-able trainable (``models.trainer.LlamaTrainStep``,
``distributed.engine.Engine``, or anything implementing the small protocol
below) with the full robustness contract:

  * periodic + final checkpoints through ``distributed.checkpoint`` (atomic,
    checksummed, keep-last-K);
  * classified-transient failures (chaos faults, wire/IO blips, watchdog
    timeouts) restore the last VALID checkpoint and replay — because the
    step program is deterministic given (state, batch), the recovered
    trajectory is bitwise identical to a fault-free run (the contract
    tests/test_resilience.py pins: resume_max_rel == 0.0);
  * SIGTERM/SIGINT latches an emergency save + ``PREEMPTED.json`` marker at
    the next step boundary, and a relaunch resumes step-exact. The
    emergency save is ASYNC: the marker (naming the last known-good
    generation) lands first, serialization overlaps the telemetry flush on
    the background writer, and the wait is bounded by the remaining
    SIGTERM grace window (``PADDLE_PREEMPT_GRACE_S``) — a slow filesystem
    can cost the freshest step, never the marker;
  * communication loss (``CommLostError`` — the typed deadline raised by
    collective readiness polls and fleet barriers when a peer is gone)
    under elastic supervision becomes
    abort-and-reform instead of death: with an in-process coordinator
    (``elastic=`` an ``ElasticManager``) the loop re-rendezvouses with the
    survivors, restores the checkpoint, and replays under the new world;
    under a launcher-coordinated fleet (``PADDLE_ELASTIC_ACTIVE=1``) it
    checkpoints, writes the marker, and exits with ``REFORM_EXIT`` (75) so
    the launcher re-rendezvouses and relaunches it step-exact.

Trainable protocol (duck-typed; adapters exist on LlamaTrainStep/Engine):
  resilience_state() -> pytree containing a scalar ``step`` leaf
  load_resilience_state(tree) -> None   (restore, same structure)
  train_step(*batch) -> loss            (or __call__ / .step fallback)

Data replay: ``run(batch_fn, num_steps)`` pulls ``batch_fn(step)`` — the
batch for a given global step must be a pure function of the step index so
a restored run replays the identical batches. (This is the same determinism
checkpointed data loaders provide; a stateful iterator cannot resume-exact.)
"""
from __future__ import annotations

import dataclasses
import os
import time

import jax
import numpy as np

from ...core.tensor import Tensor
from ...observability import fleet as _fleet, metrics as _metrics, \
    recorder as _recorder, spans as _spans
from . import chaos, preempt
from .retry import DeadlineExceeded, RetryPolicy, classify

__all__ = ["ResilientLoop", "RunResult", "REFORM_EXIT"]

# exit code a worker uses to hand control back to the launcher after a
# communication loss: "I checkpointed; re-rendezvous the fleet and relaunch
# me" — distinct from failure (any other non-zero) and success (0)
REFORM_EXIT = 75


@dataclasses.dataclass
class RunResult:
    steps: int              # global step reached (== num_steps when done)
    last_loss: float | None
    restores: int           # transient recoveries performed
    preempted: bool         # True: stopped on a preemption signal
    resumed_from: int | None = None  # step a pre-existing checkpoint supplied


def _leaf_key(i: int) -> str:
    return f"leaf{i:05d}"


class ResilientLoop:
    """loop = ResilientLoop(trainable, ckpt_dir); loop.run(batch_fn, steps)"""

    def __init__(self, trainable, ckpt_dir: str, save_every: int = 0,
                 keep_last_k: int = 3, max_restores: int = 8,
                 policy: RetryPolicy | None = None, handle_signals: bool = True,
                 process_group=None, elastic=None, on_world_change=None):
        self.trainable = trainable
        self.ckpt_dir = ckpt_dir
        self.save_every = int(save_every)
        self.keep_last_k = keep_last_k
        self.max_restores = int(max_restores)
        self.policy = policy or RetryPolicy(max_attempts=0, base_delay=0.05,
                                            max_delay=1.0)
        self.process_group = process_group
        self.preemption = preempt.PreemptionHandler()
        self._handle_signals = handle_signals
        # in-process elastic coordinator: anything with re_rendezvous()
        # (fleet.elastic.ElasticManager); on_world_change(result) lets the
        # caller rebuild meshes/groups for the new world before replay
        self.elastic = elastic
        self.on_world_change = on_world_change
        self.restores = 0        # lifetime total (reported in RunResult)
        self.reforms = 0         # lifetime fleet re-formations survived
        self._consec = 0         # consecutive failures; reset on progress
        self._consec_reforms = 0  # consecutive reforms; reset on progress
        self._last_good_uid: int | None = None
        _recorder.install_crash_hook()  # an uncaught death leaves FLIGHT.json

        if not (hasattr(trainable, "resilience_state")
                and hasattr(trainable, "load_resilience_state")):
            raise TypeError(
                f"{type(trainable).__name__} does not implement the "
                "resilience protocol (resilience_state/load_resilience_state)")
        if hasattr(trainable, "train_step"):
            self._step_fn = trainable.train_step
        elif hasattr(trainable, "step") and callable(trainable.step):
            self._step_fn = trainable.step
        elif callable(trainable):
            self._step_fn = trainable
        else:
            raise TypeError(f"{type(trainable).__name__} is not step-able")

    # ---------------- state <-> checkpoint ----------------
    def _get_step(self) -> int:
        tree = self.trainable.resilience_state()
        return int(np.asarray(tree["step"]))

    def save_checkpoint(self, async_save: bool = False) -> int:
        """Write one atomic checkpoint generation; returns its unique_id.
        async_save=True enqueues the write on the background writer (call
        ``checkpoint.wait_async_save`` before trusting the uid) — the
        generation only becomes "last good" once that wait succeeds."""
        from ..checkpoint import save_state_dict
        tree = self.trainable.resilience_state()
        leaves, _ = jax.tree.flatten(tree)
        flat = {_leaf_key(i): v for i, v in enumerate(leaves)}
        uid = save_state_dict(flat, self.ckpt_dir,
                              process_group=self.process_group,
                              keep_last_k=self.keep_last_k,
                              async_save=async_save)
        if not async_save:
            self._last_good_uid = uid
        return uid

    def restore_checkpoint(self, unique_id=None) -> int | None:
        """Restore the newest VALID generation (torn ones are skipped by the
        loader). Returns the restored global step, or None when the
        directory holds no loadable checkpoint."""
        from ..checkpoint import load_state_dict
        tree = self.trainable.resilience_state()
        leaves, treedef = jax.tree.flatten(tree)
        holders = {}
        for i, leaf in enumerate(leaves):
            if isinstance(leaf, jax.Array):
                holders[_leaf_key(i)] = Tensor(leaf)
            else:
                holders[_leaf_key(i)] = np.array(leaf)
        try:
            load_state_dict(holders, self.ckpt_dir, unique_id=unique_id,
                            process_group=self.process_group)
        except FileNotFoundError:
            return None
        new_leaves = [h._value if isinstance(h, Tensor) else h
                      for h in (holders[_leaf_key(i)]
                                for i in range(len(leaves)))]
        self.trainable.load_resilience_state(jax.tree.unflatten(treedef,
                                                                new_leaves))
        return self._get_step()

    # ---------------- recovery ----------------
    def _recover(self, exc: Exception, delays):
        """Transient failure: back off, then restore the last valid
        checkpoint (or continue from current state when none exists yet —
        the failure was in saving, nothing has diverged).

        max_restores bounds CONSECUTIVE failures — a long run that
        recovers, makes progress, and blips again hours later must not
        die on a lifetime quota (the counter resets on every completed
        step)."""
        self.restores += 1
        self._consec += 1
        _metrics.counter("resilience.restores").inc()
        if self._consec > self.max_restores:
            _recorder.record(
                "resilience.give_up", echo=True,
                message=f"[resilience] {self._consec} consecutive failures "
                        f"exceed max_restores={self.max_restores}; dying",
                error=f"{type(exc).__name__}: {exc}")
            _recorder.dump_flight(self.ckpt_dir, reason="recovery exhausted")
            raise DeadlineExceeded("resilient-loop.recover", self._consec,
                                   0.0, last=exc) from exc
        _recorder.record(
            "resilience.recover", echo=True,
            message=f"[resilience] transient failure "
                    f"({type(exc).__name__}: {exc}); recovery "
                    f"{self._consec}/{self.max_restores}",
            error=f"{type(exc).__name__}: {exc}", consec=self._consec)
        time.sleep(next(delays))
        restored = self.restore_checkpoint()
        if restored is not None:
            _recorder.record(
                "resilience.restored", echo=True,
                message=f"[resilience] restored checkpoint at step {restored}",
                step=restored)
        # the run survived a fault — dump the story while it is fresh, so a
        # later hard death (or a postmortem without re-run) still has it
        _recorder.dump_flight(self.ckpt_dir, reason="resilient-loop restore")

    def _emergency_save(self, reason: str = "preemption") -> None:
        """Emergency checkpoint overlapping the kill grace window.

        Ordering is the contract: (1) the marker lands FIRST, naming the
        last known-good generation — if the grace window expires mid-save
        the relaunch still resumes from a valid save; (2) the fresh
        generation serializes on the background writer while this thread
        flushes telemetry; (3) the async wait is bounded by the remaining
        grace (PADDLE_PREEMPT_GRACE_S) and, on success, the marker is
        re-pointed at the fresh generation."""
        from ..checkpoint import wait_async_save
        step = self._get_step()
        signum = self.preemption.signum
        preempt.write_marker(self.ckpt_dir, step, unique_id=self._last_good_uid,
                             signum=signum,
                             extra={"provisional": True, "reason": reason})
        uid = None
        try:
            uid = self.save_checkpoint(async_save=True)
            # overlap: the shard write runs on the background writer while
            # this thread leaves the postmortem behind
            _recorder.dump_flight(self.ckpt_dir,
                                  reason=f"{reason} save (in flight)")
            wait_async_save(timeout=self.preemption.grace_remaining())
            self._last_good_uid = uid
            preempt.write_marker(self.ckpt_dir, step, unique_id=uid,
                                 signum=signum, extra={"reason": reason})
        except Exception as e:  # keep the provisional marker
            _recorder.record(
                "resilience.emergency_save_failed", echo=True,
                message=f"[resilience] emergency save failed ({e}); marker "
                        f"points at the last good generation",
                error=f"{type(e).__name__}: {e}")
            uid = self._last_good_uid
        _recorder.record(
            "resilience.preempted", echo=True,
            message=f"[resilience] {reason}: emergency checkpoint uid={uid} "
                    f"step={step} marker written",
            uid=uid, step=step, signum=signum)
        _recorder.dump_flight(self.ckpt_dir, reason=f"{reason} save")

    # ---------------- elastic: abort-and-reform ----------------
    def _elastic_enabled(self) -> bool:
        if self.elastic is not None:
            return True
        from ..fleet.elastic import elastic_active
        return elastic_active()

    def _comm_loss(self, exc: Exception) -> bool:
        """A failure that means 'a peer is gone', answerable by re-forming
        the fleet. Only CommLostError qualifies — the typed deadline the
        collective/rendezvous waits raise (collective._finish_wait, fleet
        barriers). A transient wire/IO error (ConnectionError, a checkpoint
        deadline) keeps the plain retry/restore discipline: re-forming the
        fleet cannot fix a dead disk, and a save-blip must not cost a
        whole-fleet reform. Only meaningful under elastic supervision."""
        from .retry import CommLostError
        return isinstance(exc, CommLostError) and self._elastic_enabled()

    def _reform(self, exc: Exception) -> None:
        """Answer a communication loss: re-rendezvous in-process when a
        coordinator is attached, else checkpoint + exit REFORM_EXIT for the
        launcher to re-form the fleet and relaunch us."""
        self.reforms += 1
        self._consec_reforms += 1
        _metrics.counter("elastic.comm_loss").inc()
        if self._consec_reforms > self.max_restores:
            _recorder.record(
                "elastic.give_up", echo=True,
                message=f"[resilience] {self._consec_reforms} consecutive "
                        f"fleet re-formations exceed "
                        f"max_restores={self.max_restores}; dying",
                error=f"{type(exc).__name__}: {exc}")
            raise DeadlineExceeded("resilient-loop.reform",
                                   self._consec_reforms, 0.0,
                                   last=exc) from exc
        if self.elastic is not None:
            _recorder.record(
                "elastic.reform", echo=True,
                message=f"[resilience] communication lost "
                        f"({type(exc).__name__}: {exc}); re-rendezvousing "
                        f"with survivors",
                error=f"{type(exc).__name__}: {exc}")
            res = self.elastic.re_rendezvous(
                reason=f"{type(exc).__name__}: {exc}")
            if self.on_world_change is not None:
                self.on_world_change(res)
            restored = self.restore_checkpoint()
            _recorder.record(
                "elastic.resumed", echo=True,
                message=f"[resilience] fleet re-formed: gen={res.generation} "
                        f"world={res.world} rank={res.rank}; resuming from "
                        f"step {restored if restored is not None else self._get_step()}",
                gen=res.generation, world=res.world, rank=res.rank,
                step=restored)
            _recorder.dump_flight(self.ckpt_dir, reason="elastic reform")
            return
        # launcher-coordinated: save + marker now, then hand control back
        # with the reform exit code — the relaunched world resumes step-exact
        self._emergency_save(reason="elastic-reform")
        _recorder.record(
            "elastic.reform_exit", echo=True,
            message=f"[resilience] communication lost ({type(exc).__name__}: "
                    f"{exc}); exiting rc={REFORM_EXIT} for launcher "
                    f"re-rendezvous",
            error=f"{type(exc).__name__}: {exc}")
        _recorder.dump_flight(reason="elastic reform exit")
        raise SystemExit(REFORM_EXIT)

    # ---------------- the loop ----------------
    def run(self, batch_fn, num_steps: int, on_step=None) -> RunResult:
        """Train to ``num_steps`` global steps, recovering along the way.

        batch_fn(step) -> batch (tuple/list of step-fn args, or a single
        array). on_step(step, loss) observes completed steps.
        """
        os.makedirs(self.ckpt_dir, exist_ok=True)
        if self._handle_signals:
            self.preemption.install()
        prev_active = None
        if self.elastic is not None:
            # an attached in-process coordinator IS elastic supervision:
            # flip the switch so collective waits become deadline-bounded
            # (CommLostError) — otherwise a real peer loss would block in C
            # and the watchdog would exit 124, never reaching _reform
            from ..fleet import elastic as _el
            prev_active = _el._active[0]
            _el.set_elastic_active(True)
        try:
            return self._run(batch_fn, num_steps, on_step)
        finally:
            if prev_active is not None:
                _el.set_elastic_active(prev_active)
            if self._handle_signals:
                self.preemption.uninstall()

    def _run(self, batch_fn, num_steps, on_step) -> RunResult:
        delays = self.policy.delays()
        last_loss = None

        # resume: a prior run's checkpoint (possibly with a preemption
        # marker) restores step-exact; otherwise anchor generation 0 so
        # recovery always has a restore target.
        resumed_from = self.restore_checkpoint()
        if resumed_from is not None:
            marker = preempt.read_marker(self.ckpt_dir)
            _recorder.record(
                "resilience.resume", echo=True,
                message=f"[resilience] resuming from step {resumed_from}"
                        f"{' (preemption marker)' if marker else ''}",
                step=resumed_from, preemption_marker=bool(marker))
            preempt.clear_marker(self.ckpt_dir)
        else:
            while True:
                try:
                    self.save_checkpoint()
                    break
                except Exception as e:
                    if not classify(e):
                        raise
                    self._recover(e, delays)

        step = self._get_step()
        while step < num_steps:
            if self.preemption.requested:
                self._emergency_save()
                _fleet.maybe_push(step, force=True)  # last words out the door
                return RunResult(step, _loss_float(last_loss), self.restores,
                                 True, resumed_from)
            try:
                # loop.step_time_s (NOT train.step_time_s: an Engine/
                # LlamaTrainStep trainable already observes that inside
                # _step_fn — two observations of one step would skew the
                # histogram; the fleet straggler detector prefers train.*
                # and falls back to loop.*)
                with _spans.span("loop.step", cat="step", step=step), \
                        _metrics.timer("loop.step_time_s"):
                    batch = batch_fn(step)
                    if not isinstance(batch, (tuple, list)):
                        batch = (batch,)
                    loss = self._step_fn(*batch)
                step = self._get_step()
                last_loss = loss
                if self._consec or self._consec_reforms:
                    # progress: reset failure budgets + backoff
                    self._consec = 0
                    self._consec_reforms = 0
                    delays = self.policy.delays()
                if on_step is not None:
                    on_step(step, loss)
                # fleet telemetry heartbeat: interval-paced, loss-tolerant
                # (a drop is counted, never raises into the step)
                _fleet.maybe_push(step)
                if self.save_every and step < num_steps \
                        and step % self.save_every == 0:
                    self.save_checkpoint()
            except Exception as e:
                if self._comm_loss(e):
                    # a dead peer, not a transient blip: re-form the fleet
                    # (in-process or via the launcher) and replay from the
                    # checkpoint under the new world
                    self._reform(e)
                    step = self._get_step()
                    continue
                if not classify(e):
                    raise
                self._recover(e, delays)
                step = self._get_step()

        # completion checkpoint: a restart after the run re-loads the final
        # state instead of retraining
        while True:
            try:
                self.save_checkpoint()
                break
            except Exception as e:
                if not classify(e):
                    raise
                self._recover(e, delays)
        preempt.clear_marker(self.ckpt_dir)
        # final push so the aggregator's merged trace covers the tail steps
        # between the last interval-paced push and exit
        _fleet.maybe_push(step, force=True)
        if os.environ.get("PADDLE_TRACE_DIR"):
            # traced runs leave their flight behind even on success, so the
            # launcher's FLEET_FLIGHT.json covers every rank's story
            _recorder.dump_flight(reason="run complete")
        return RunResult(step, _loss_float(last_loss), self.restores, False,
                         resumed_from)


def _loss_float(loss):
    if loss is None:
        return None
    return float(jax.device_get(
        loss._value if isinstance(loss, Tensor) else loss))
