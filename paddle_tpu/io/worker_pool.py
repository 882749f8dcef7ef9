"""Multiprocess DataLoader workers.

Reference: python/paddle/io/dataloader/dataloader_iter.py:368
(_DataLoaderIterMultiProcess — worker processes pull index batches from
queues, run Dataset.__getitem__ + collate, push assembled batches back;
:154 single-process variant). TPU-native constraints baked in:

* workers are SPAWNED, not forked: the parent holds a live PJRT/TPU client
  and forked children inheriting it deadlock — spawn gives clean processes.
* workers do NUMPY-ONLY work (transforms, collate); the device transfer
  happens in the parent, after the queue hop — a worker should never touch
  jax (datasets whose transforms build Tensors are still handled, but pay a
  per-worker jax client).
* batches return tagged with their index; the parent re-orders, so results
  are deterministic regardless of worker scheduling.
* outstanding tasks are bounded to prefetch_factor*num_workers and refilled
  as batches are consumed (backpressure — a slow training step cannot cause
  the whole epoch to pile up in the parent's result queue).
* with persistent_workers the pool outlives the epoch: the next __iter__
  reuses the spawned interpreters instead of paying their startup again.
"""
from __future__ import annotations

import multiprocessing as mp
import queue as pyqueue

import numpy as np

from ..observability import metrics as _metrics, recorder as _recorder, \
    spans as _spans


class WorkerInfo:
    def __init__(self, id, num_workers, dataset=None, seed=None):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset
        self.seed = seed


_worker_info: list = [None]


def get_worker_info():
    """Inside a worker: (id, num_workers, dataset); None in the parent
    (reference dataloader/worker.py get_worker_info)."""
    return _worker_info[0]


def numpy_collate(batch):
    """Collate into numpy; Tensor samples (a transform that tensorized early)
    are pulled back to host so the parent does ONE device transfer."""
    sample = batch[0]
    if isinstance(sample, (np.ndarray, np.generic)):
        return np.stack(batch)
    if hasattr(sample, "_value"):  # paddle_tpu Tensor, duck-typed (no import)
        return np.stack([np.asarray(s._value) for s in batch])
    if isinstance(sample, (int, float)):
        return np.asarray(batch)
    if isinstance(sample, (list, tuple)):
        return tuple(numpy_collate([b[i] for b in batch])
                     for i in range(len(sample)))
    if isinstance(sample, dict):
        return {k: numpy_collate([b[k] for b in batch]) for k in sample}
    return batch


def passthrough_collate(samples):
    """Top-level (spawn-picklable) identity collate: workers return raw
    sample lists; the parent runs the user's collate_fn."""
    return samples


def _worker_loop(dataset, task_q, result_q, collate_fn, worker_id,
                 num_workers, worker_init_fn, base_seed):
    try:
        # if ANY user code in this worker touches jax (e.g. a transform that
        # tensorizes early), it must get the CPU backend: a chip belongs to
        # one process at a time, and a child must never take the parent's
        # (it would fail or hang). The config override also wins over an
        # inherited JAX_PLATFORMS.
        try:
            import jax as _jax
            _jax.config.update("jax_platforms", "cpu")
        except Exception:
            pass
        np.random.seed((base_seed + worker_id) % (2 ** 31))
        _worker_info[0] = WorkerInfo(worker_id, num_workers, dataset,
                                     base_seed + worker_id)
        if worker_init_fn is not None:
            worker_init_fn(worker_id)
        while True:
            task = task_q.get()
            if task is None:
                return
            batch_idx, indices = task
            try:
                data = collate_fn([dataset[i] for i in indices])
                result_q.put((batch_idx, data, None))
            except Exception as e:  # propagate per-batch errors
                import traceback
                result_q.put((batch_idx, None,
                              f"{type(e).__name__}: {e}\n"
                              f"{traceback.format_exc()}"))
    except (KeyboardInterrupt, EOFError, BrokenPipeError):
        return


def _chaos_active():
    # mirrors resilience.chaos.active(); checked inline so chaos-free runs
    # never import the distributed package from the data path
    import os
    return bool(os.environ.get("PADDLE_CHAOS"))


class WorkerPool:
    """Spawned worker pool usable across epochs (persistent_workers)."""

    def __init__(self, dataset, num_workers, collate_fn=None,
                 worker_init_fn=None, base_seed=0):
        ctx = mp.get_context("spawn")
        self._task_q = ctx.Queue()
        self._result_q = ctx.Queue()
        self.num_workers = num_workers
        self._workers = []
        self._epoch = 0  # generation token: stale results from an abandoned
        #                  epoch (chaos fault, consumer bailed) are dropped
        collate = collate_fn or numpy_collate
        for w in range(num_workers):
            p = ctx.Process(
                target=_worker_loop,
                args=(dataset, self._task_q, self._result_q, collate, w,
                      num_workers, worker_init_fn, base_seed),
                daemon=True)
            p.start()
            self._workers.append(p)

    def alive(self):
        return bool(self._workers) and all(w.is_alive() for w in self._workers)

    def run_epoch(self, index_batches, prefetch=2, timeout=0):
        """Yield collated batches IN ORDER with bounded in-flight tasks.

        timeout: seconds to wait for one batch; <=0 blocks indefinitely (the
        reference default) with worker-death detection every 60s."""
        batches = list(index_batches)
        n = len(batches)
        window = max(prefetch, 1) * max(self.num_workers, 1)
        self._epoch += 1
        epoch = self._epoch
        _recorder.record("io.epoch", epoch=epoch, batches=n,
                         workers=self.num_workers)
        # ended when the generator is exhausted or collected, not by this
        # frame: never the parent of what the consumer does between yields
        epoch_span = _spans.span("io.epoch", cat="data", epoch=epoch,
                                 batches=n).begin(nest=False)
        submitted = 0
        pending: dict = {}
        nxt = 0
        while submitted < min(window, n):
            self._task_q.put(((epoch, submitted), list(batches[submitted])))
            submitted += 1
        poll = timeout if timeout and timeout > 0 else 60
        hard = timeout if timeout and timeout > 0 else None
        try:
            while nxt < n:
                if nxt in pending:
                    # fault BEFORE consuming: an injected data.next error must
                    # not eat a batch a replayed epoch still needs
                    if _chaos_active():
                        from ..distributed.resilience import chaos
                        chaos.hit("data.next")
                    data = pending.pop(nxt)
                    nxt += 1
                    # consumed one -> admit one (backpressure window slides)
                    if submitted < n:
                        self._task_q.put(((epoch, submitted),
                                          list(batches[submitted])))
                        submitted += 1
                    _metrics.counter("io.batches").inc()
                    yield data
                    continue
                try:
                    key, data, err = self._result_q.get(timeout=poll)
                    ep, bi = key
                    if ep != epoch:
                        continue  # leftover from an abandoned earlier epoch
                except pyqueue.Empty:
                    dead = [w.pid for w in self._workers if not w.is_alive()]
                    if dead:
                        _recorder.record("io.worker_dead", pids=dead,
                                         epoch=epoch)
                        raise RuntimeError(
                            f"DataLoader worker(s) died: pids {dead}")
                    if hard is not None:
                        _recorder.record("io.worker_timeout", timeout_s=hard,
                                         epoch=epoch)
                        raise RuntimeError(
                            f"DataLoader worker timeout after {hard}s")
                    continue  # no timeout requested: keep waiting
                if err is not None:
                    _recorder.record("io.batch_failed", batch=bi, epoch=epoch)
                    raise RuntimeError(f"DataLoader worker failed on batch "
                                       f"{bi}:\n{err}")
                pending[bi] = data
        finally:
            epoch_span.end()

    def shutdown(self):
        for w in self._workers:
            if w.is_alive():
                w.terminate()
        for w in self._workers:
            w.join(timeout=5)
        self._workers = []

    def __del__(self):
        try:
            self.shutdown()
        except Exception:
            pass
