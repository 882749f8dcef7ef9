"""Group-sharded (ZeRO) facade.

Reference: /root/reference/python/paddle/distributed/sharding/group_sharded.py
(group_sharded_parallel: stage os/os_g/p_g_os → GroupShardedStage2/3 +
GroupShardedOptimizerStage2) and fleet DygraphShardingOptimizer.

TPU-native: ZeRO == placements. Stage 1/2 shard optimizer states (and rely on
GSPMD reduce-scattering grads into the sharded update inside the compiled
step); stage 3 shards the parameters themselves (XLA all-gathers at use,
discards after). See distributed.api.ShardingStage1/2/3 for the placement
policies; this wraps them in the reference's facade signature.
"""
from __future__ import annotations

from ..distributed.api import ShardingStage1, ShardingStage2, ShardingStage3, shard_optimizer
from ..distributed.process_mesh import get_mesh

__all__ = ["group_sharded_parallel", "kv_pool_pspec", "kv_scale_pspec",
           "serving_mesh", "shard_kv_pool", "ENV_SERVE_MESH"]

ENV_SERVE_MESH = "PADDLE_SERVE_MESH_MODEL"

# ------------------------------------------------------- serving KV pool
# GSPMD page-pool sharding (ISSUE 8): the paged KV pool keeps KV heads as
# its third axis ([num_pages, page_size, KV, hd]), so one NamedSharding
# spreads a serving replica's cache across a pod slice with NO layout
# change — each chip holds every page's slice of ITS heads, the block
# table stays replicated host metadata, and the XLA gather (which a
# sharded pool always reads through: llama_paged.paged_kv_read) is
# partitioned by GSPMD automatically, so every chip reads only local bytes.


def kv_pool_pspec(axis: str = "model"):
    """The page-pool partition spec: P(None, None, "model", None) —
    pages and rows replicated in layout, KV heads sharded (GSPMD,
    arxiv 2105.04663)."""
    from jax.sharding import PartitionSpec as P
    return P(None, None, axis, None)


def kv_scale_pspec(axis: str = "model"):
    """Quantized pools' per-(page, row, head) scale spec (ISSUE 10):
    [num_pages, page_size, KV] shards its KV axis with the payload pages
    — a scale lives on the same chip as the page rows it describes, so
    neither read path ever crosses a shard for a dequantize."""
    from jax.sharding import PartitionSpec as P
    return P(None, None, axis)


def serving_mesh(n: int | None = None, axis: str = "model"):
    """A 1-D serving mesh over the first `n` local devices (None: the
    PADDLE_SERVE_MESH_MODEL env knob). Returns None when n <= 1 — the
    single-chip engine takes no sharding code path at all."""
    import jax
    import numpy as np

    from ..utils import env_flags
    if n is None:
        n = env_flags.get_int(ENV_SERVE_MESH)
    n = int(n)
    if n <= 1:
        return None
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(
            f"{ENV_SERVE_MESH}={n} but only {len(devs)} devices visible")
    from jax.sharding import Mesh
    return Mesh(np.asarray(devs[:n]), (axis,))


def shard_kv_pool(cache, mesh, axis: str = "model"):
    """device_put every per-layer pool buffer with the KV-head sharding.
    The buffers are donated through the serving jits, so the placement
    sticks for the engine's lifetime. Quantized pools (ISSUE 10) carry
    "k_scale"/"v_scale" leaves that shard along the same head axis."""
    import jax
    from jax.sharding import NamedSharding
    sh = NamedSharding(mesh, kv_pool_pspec(axis))

    def put(a):
        return jax.device_put(a, sh)

    out = {"k": tuple(put(a) for a in cache["k"]),
           "v": tuple(put(a) for a in cache["v"])}
    if "k_scale" in cache:
        ssh = NamedSharding(mesh, kv_scale_pspec(axis))
        out["k_scale"] = tuple(jax.device_put(a, ssh)
                               for a in cache["k_scale"])
        out["v_scale"] = tuple(jax.device_put(a, ssh)
                               for a in cache["v_scale"])
    return out


def group_sharded_parallel(model, optimizer, level="os_g", scaler=None, group=None,
                           offload=False, sync_buffers=False, buffer_max_size=None,
                           segment_size=None, sync_comm=False, dp_group=None,
                           exclude_layer=None):
    """level: 'os' (stage1) | 'os_g' (stage2) | 'p_g_os' (stage3)."""
    mesh = get_mesh()
    axis = None
    if group is not None and hasattr(group, "axis_name"):
        axis = group.axis_name
    elif mesh is not None:
        for cand in ("sharding", "dp"):
            if cand in mesh.dim_names:
                axis = cand
                break
    stage = {"os": ShardingStage1, "os_g": ShardingStage2, "p_g_os": ShardingStage3}[level]
    optimizer = shard_optimizer(optimizer, stage(mesh, axis))
    if scaler is not None:
        return model, optimizer, scaler
    return model, optimizer
