"""Rank script: RPC + parameter-server across real processes.

Rank 0 = 'server0' (hosts sparse tables), others = workers that pull/push
embedding rows through distributed.rpc (reference pattern: rpc + the_one_ps
runtimes). A finish barrier through server0 keeps every rank alive until all
workers are done — otherwise a fast worker can exit (and deregister) before
a slow rank finishes its rendezvous."""
import os
import sys
import time

# CPU only: a chip belongs to one process at a time, so ranks racing for
# it would fail or stall past the rendezvous
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np

import paddle_tpu.distributed.ps as ps
import paddle_tpu.distributed.rpc as rpc

_DONE = set()
_SERVER_READY = []


def double(x):
    return x * 2


def mark_done(worker):
    _DONE.add(worker)
    return len(_DONE)


def done_count():
    return len(_DONE)


def server_ready():
    """True once the server's OWN rendezvous returned. Workers must not
    deregister before then: rpc handler threads serve as soon as the TCP
    server binds, so a 1-worker job can finish and leave() while the
    server is still polling the KV for its membership — after which the
    server can never discover it and hangs to the rendezvous timeout."""
    return bool(_SERVER_READY)


def main():
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    world = int(os.environ["PADDLE_TRAINERS_NUM"])
    n_workers = world - 1
    name = f"server{rank}" if rank == 0 else f"worker{rank}"
    rt = ps.TheOnePSRuntime(name=name, rank=rank, world_size=world)
    if rt.server is not None:
        _SERVER_READY.append(True)

    if rt.worker is not None:
        # plain rpc: call a function on the server
        got = rpc.rpc_sync("server0", double, (21,))
        assert got == 42, got
        fut = rpc.rpc_async("server0", double, (5,))
        assert fut.result() == 10
        # lambdas/closures go by value (pickled), not by name
        k = 7
        assert rpc.rpc_sync("server0", lambda x: x + k, (1,)) == 8
        # remote errors surface as named RuntimeErrors
        try:
            rpc.rpc_sync("server0", "nonexistent.module:fn", ())
            raise AssertionError("expected remote failure")
        except RuntimeError as e:
            assert "server0" in str(e)

        rt.worker.create_table("emb", dim=8, lr=0.5)
        ids = np.array([1, 2, 3, 1 + rank * 10])
        rows = rt.worker.pull("emb", ids)
        assert rows.shape == (4, 8), rows.shape
        # push a known gradient and verify the update landed (rank-unique
        # row id: no cross-worker races on the same row)
        rid = np.array([7 + rank * 1000])
        before = rt.worker.pull("emb", rid)
        rt.worker.push("emb", rid, np.ones((1, 8), np.float32))
        after = rt.worker.pull("emb", rid)
        np.testing.assert_allclose(after, before - 0.5, rtol=1e-5)
        assert rt.worker.table_size("emb") >= 4
        # empty pull keeps the [*, dim] contract
        empty = rt.worker.pull("emb", np.zeros((0,), np.int64))
        assert empty.shape == (0, 8), empty.shape

        # finish barrier: report done, wait until every worker is done AND
        # the server's rendezvous completed (see server_ready) — only then
        # is it safe to deregister
        rpc.rpc_sync("server0", mark_done, (name,))
        deadline = time.time() + 300
        while (rpc.rpc_sync("server0", done_count, ()) < n_workers
               or not rpc.rpc_sync("server0", server_ready, ())):
            if time.time() > deadline:
                raise TimeoutError("finish barrier")
            time.sleep(0.3)
    else:
        # server: hold until every worker reported done
        deadline = time.time() + 330
        while len(_DONE) < n_workers:
            if time.time() > deadline:
                raise TimeoutError(f"server finish barrier: {_DONE}")
            time.sleep(0.3)
        time.sleep(1.0)  # let workers read the final done_count

    print("RPC_PS_OK")
    rt.stop()


if __name__ == "__main__":
    main()
